"""Control flow in the graph: `contrib.foreach` (parity: upstream's
`src/operator/control_flow.cc` `_foreach` and `python/mxnet/{symbol,
ndarray}/contrib.py foreach`).

    outputs, states = F.contrib.foreach(body, data, init_states)

`body(data_slice, states) -> (outputs, new_states)` is applied to every
slice of `data` along axis 0, the states handed from one application to the
next; `outputs` come back stacked along a new axis 0.  `data`, the states
and the outputs are each one array (or Symbol) or a (nested) list of them.

Eagerly (`mx.nd.contrib.foreach`) it is a Python loop over recorded ops.
In a graph (`mx.sym.contrib.foreach`, so under `hybridize()`) the body is
traced ONCE into a sub-graph and the node runs it as `lax.scan`
(symbol/graph.py `LoopBody`): the program text holds the body once however
many steps the loop takes.
"""
from __future__ import annotations

from ..base import Arg, MXNetError
from .registry import register


def flatten(args, leaf, what="input"):
    """(flat list, format) of a leaf or a (nested) list of leaves."""
    if isinstance(args, leaf):
        return [args], 0
    if not isinstance(args, (list, tuple)):
        raise MXNetError(f"contrib.foreach: {what} must be a (nested) list "
                         f"of {leaf.__name__}, got {type(args).__name__}")
    flat, fmts = [], []
    for a in args:
        f, fmt = flatten(a, leaf, what)
        flat.extend(f)
        fmts.append(fmt)
    return flat, fmts


def regroup(flat, fmt):
    """The inverse of `flatten`: (structure, what is left of `flat`)."""
    if fmt == 0:
        return flat[0], flat[1:]
    out = []
    for f in fmt:
        item, flat = regroup(flat, f)
        out.append(item)
    return out, flat


def _as_is(v):
    return v


@register("_foreach", input_names=(), variadic=True, takes_is_train=True,
          args=[Arg("body", _as_is, required=True),
                Arg("num_data", int, required=True),
                Arg("num_states", int, required=True),
                Arg("num_out_data", int, required=True)])
def _foreach(p, *ins):
    """The graph node `sym.contrib.foreach` makes.  Inputs: the scanned
    data, the initial states, then everything the body closes over (the
    blocks' parameters above all), which every step reads whole.  Outputs:
    the body's outputs stacked along axis 0, then the final states."""
    return p["body"].scan(ins, p["num_data"], p["num_states"],
                          p["num_out_data"], p["__is_train__"])
