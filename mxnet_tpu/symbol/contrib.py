"""`mx.sym.contrib`: control flow in the graph (parity:
python/mxnet/symbol/contrib.py).  The contrib operators themselves are in
the flat `mx.sym` namespace and in `mx.contrib.symbol`."""
from __future__ import annotations

from ..attribute import current_attrs
from ..base import MXNetError
from ..name import NameManager
from ..ops import registry as _reg
from ..ops.control_flow import flatten, regroup
from .graph import LoopBody
from .symbol import Group, Symbol, Variable, _Node


def foreach(body, data, init_states, name=None):
    """Run `body` over the slices of `data` along axis 0 as one node of
    the graph.

    body(data_slice, states) -> (outputs, new_states); `data`,
    `init_states` and both results are a Symbol or a (nested) list of
    Symbols, and `new_states` has the structure (shapes and dtypes too)
    of `init_states`.  Returns (outputs stacked along a new axis 0, final
    states).  A body without outputs returns `[]` for them.

    The body is traced once, here, into a sub-graph.  What it closes over
    becomes the node's inputs: free variables (the parameters of the
    blocks it calls, the outer graph's inputs) and any value of the outer
    graph it reads.  Every step reads those whole; a parameter's gradient
    is the sum over the steps.  The node runs as `lax.scan`
    (symbol/graph.py `LoopBody`, which also says what a recorded call
    keeps of a loop).

    Raises MXNetError for a body that holds an auxiliary state or an
    operator that draws random numbers: neither is carried through the
    loop.  A parameter used only inside a body needs its shape known when
    it is made (`in_units=`): shapes are not inferred backwards through
    the loop.
    """
    flat_data, data_fmt = flatten(data, Symbol, "data")
    flat_init, state_fmt = flatten(init_states, Symbol, "init_states")
    if not flat_data:
        raise MXNetError("contrib.foreach: no data to loop over")
    node_name = NameManager.current().get(name, "foreach")
    data_vars = [Variable(f"{node_name}_data{i}")
                 for i in range(len(flat_data))]
    state_vars = [Variable(f"{node_name}_state{i}")
                  for i in range(len(flat_init))]
    made_before = _Node.made
    outs, new_states = body(regroup(data_vars, data_fmt)[0],
                            regroup(state_vars, state_fmt)[0])
    flat_outs, out_fmt = flatten(outs, Symbol, "the body's outputs")
    flat_new, new_fmt = flatten(new_states, Symbol, "the body's states")
    if new_fmt != state_fmt:
        raise MXNetError(
            f"contrib.foreach '{node_name}': the body returns states of "
            f"structure {new_fmt}, init_states has {state_fmt}")
    sub = Group(flat_outs + flat_new)

    # What the body reads of the outer graph: a value computed before the
    # body ran is cut out of the sub-graph and handed in as an input.
    placeholders = {id(v._entries[0][0]) for v in data_vars + state_vars}
    outer, outer_vars, free, seen = {}, [], [], set()

    def visit(node):
        if id(node) in seen:
            return
        seen.add(id(node))
        if node.is_var:
            if id(node) not in placeholders:
                free.append(node)
            return
        op = _reg.get_op(node.op)
        if op.aux_inputs or op.needs_rng:
            what = "an auxiliary state" if op.aux_inputs else \
                "random numbers"
            raise MXNetError(
                f"contrib.foreach '{node_name}': node '{node.name}' "
                f"({node.op}) needs {what}, which a loop body does not "
                "carry from step to step")
        for i, (src, oi) in enumerate(node.inputs):
            if src.is_var or src.serial > made_before:
                visit(src)
                continue
            if (id(src), oi) not in outer:
                var = Variable(f"{node_name}_outer{len(outer)}")
                outer[(id(src), oi)] = (var._entries[0], (src, oi))
                outer_vars.append(var)
            node.inputs[i] = outer[(id(src), oi)][0]

    for k, (node, _oi) in enumerate(sub._entries):
        if not node.is_var and node.serial <= made_before:
            raise MXNetError(
                f"contrib.foreach '{node_name}': result {k} of the body "
                "was computed outside the body")
        visit(node)

    inner = data_vars + state_vars + outer_vars
    inputs = [s._entries[0] for s in flat_data + flat_init] \
        + [entry for _var, entry in outer.values()] \
        + [(v, 0) for v in free]
    loop = LoopBody(sub, [v.name for v in inner] + [v.name for v in free])
    node = _Node("_foreach", node_name,
                 params={"body": loop, "num_data": len(flat_data),
                         "num_states": len(flat_init),
                         "num_out_data": len(flat_outs)},
                 inputs=inputs, attrs=current_attrs(None))
    results = [Symbol([(node, i)])
               for i in range(len(flat_outs) + len(flat_init))]
    n = len(flat_outs)
    return (regroup(results[:n], out_fmt)[0] if n else [],
            regroup(results[n:], state_fmt)[0])
