"""`mx.nd.contrib`: control flow over NDArrays (parity:
python/mxnet/ndarray/contrib.py).  The contrib operators themselves are in
the flat `mx.nd` namespace and in `mx.contrib.ndarray`."""
from __future__ import annotations

from ..base import MXNetError
from ..ops.control_flow import flatten, regroup
from .ndarray import NDArray
from .register import _gen


def foreach(body, data, init_states):
    """Run `body` over the slices of `data` along axis 0, eagerly.

    body(data_slice, states) -> (outputs, new_states); `data`,
    `init_states` and both results are an NDArray or a (nested) list of
    NDArrays.  Returns (outputs stacked along a new axis 0, final states).
    A Python loop over recorded operators: under `autograd.record()` every
    step is on the tape, and a parameter the body reads gets the sum of its
    steps' gradients.  `mx.sym.contrib.foreach` is the same loop as one
    node of a graph.
    """
    flat_data, data_fmt = flatten(data, NDArray, "data")
    if not flat_data:
        raise MXNetError("contrib.foreach: no data to loop over")
    steps = flat_data[0].shape[0]
    if any(d.shape[0] != steps for d in flat_data):
        raise MXNetError("contrib.foreach: data of different lengths "
                         f"{[d.shape[0] for d in flat_data]}")
    states, rows, out_fmt = init_states, [], 0
    for i in range(steps):
        outs, states = body(regroup([d[i] for d in flat_data], data_fmt)[0],
                            states)
        flat_outs, out_fmt = flatten(outs, NDArray, "the body's outputs")
        rows.append(flat_outs)
    stacked = [_gen.stack(*col, axis=0) for col in zip(*rows)]
    return (regroup(stacked, out_fmt)[0] if stacked else []), states
