"""Graph plan + pure-JAX interpreter + shape/type inference.

Reference parity: this module replaces the nnvm pass machinery the
GraphExecutor drove (`src/executor/graph_executor.cc`): InferShape/InferType
(:597) become incremental `jax.eval_shape` over the plan; PlanMemory /
AttachOpExecs / op bulking are all subsumed by tracing `run()` under one
`jax.jit` (XLA plans memory and fuses).  Parameter-shape hooks reproduce the
reference ops' InferShape for auto-created weights (e.g. FC weight from
num_hidden × flattened data — src/operator/nn/fully_connected-inl.h).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as _np

from ..base import MXNetError, np_dtype
from .. import layout as _layout
from ..observability import introspect as _introspect
from ..ops import registry as _reg
from ..ops.elemwise import _BINARY as _EW_BINARY, _SCALAR as _EW_SCALAR, \
    _UNARY as _EW_UNARY
from ..ops.sequence import rnn_param_size, _GATES
from .symbol import Symbol, _Node, _truthy


# -- whole-graph channels-last propagation (VERDICT r4 #1b) -----------------
# Per-op boundary transposes (layout.py to_cl/from_cl inside each spatial
# op) measured SLOWER than NCHW on-chip (58f48c3:LAYOUT_r04: framework NHWC 1540
# vs NCHW 1577) even though raw-JAX NHWC wins (1929 vs 1860): XLA does
# not reliably cancel the transpose pairs across conv→BN→relu→conv
# chains once bf16 converts/broadcasts sit between them.  This pass
# moves the layout decision to the GRAPH level: spatial ops exchange
# channels-last values directly (ops/nn.py `__io_layout__`), elementwise
# ops pass the tag through, and a real transpose is materialized only
# where a layout-sensitive consumer (FC, reshape, softmax, ...) or a
# graph output needs NCHW — i.e. at true graph edges.

# ops that are layout-transparent on their single array input
_CL_EW_ONE = (set(_EW_UNARY) | set(_EW_SCALAR) |
              {"Activation", "Dropout", "_copy", "BlockGrad",
               "make_loss", "clip", "Cast", "smooth_l1"})
# binary elemwise: transparent when both inputs have the same shape
_CL_EW_TWO = {"broadcast_" + k for k in _EW_BINARY}


def _prod(xs):
    out = 1
    for x in xs:
        out *= x
    return out


# op name -> fn(params, in_shapes) -> {input_index: shape} for unknown-var fill
def _fc_hook(p, shp):
    d = shp[0]
    red = _prod(d[1:]) if p.get("flatten", True) else d[-1]
    out = {1: (p["num_hidden"], red)}
    if not p.get("no_bias"):
        out[2] = (p["num_hidden"],)
    return out


def _conv_hook(p, shp):
    d = shp[0]
    out = {1: (p["num_filter"], d[1] // p.get("num_group", 1)) + tuple(p["kernel"])}
    if not p.get("no_bias"):
        out[2] = (p["num_filter"],)
    return out


def _deconv_hook(p, shp):
    d = shp[0]
    out = {1: (d[1], p["num_filter"] // p.get("num_group", 1)) + tuple(p["kernel"])}
    if not p.get("no_bias"):
        out[2] = (p["num_filter"],)
    return out


def _bn_hook(p, shp):
    c = shp[0][p.get("axis", 1) % len(shp[0])]
    return {1: (c,), 2: (c,), 3: (c,), 4: (c,)}


def _in_hook(p, shp):
    c = shp[0][1]
    return {1: (c,), 2: (c,)}


def _ln_hook(p, shp):
    c = shp[0][p.get("axis", -1) % len(shp[0])]
    return {1: (c,), 2: (c,)}


def _emb_hook(p, shp):
    return {1: (p["input_dim"], p["output_dim"])}


def _rnn_hook(p, shp):
    T, B, I = shp[0]
    L, H = p["num_layers"], p["state_size"]
    d = 2 if p.get("bidirectional") else 1
    out = {1: (rnn_param_size(L, I, H, bool(p.get("bidirectional")), p["mode"]),),
           2: (L * d, B, H)}
    if p["mode"] == "lstm":
        out[3] = (L * d, B, H)
    return out


def _prelu_hook(p, shp):
    if p.get("act_type") == "prelu" and len(shp) > 1:
        return {1: (shp[0][1] if len(shp[0]) > 1 else shp[0][0],)}
    return {}


def _softmax_output_hook(p, shp):
    d = shp[0]
    if p.get("multi_output"):
        return {1: (d[0],) + tuple(d[2:])}
    if p.get("preserve_shape"):
        return {1: tuple(d[:-1])}
    return {1: (d[0],)}


def _regression_hook(p, shp):
    return {1: tuple(shp[0])}


def _ce_hook(p, shp):
    return {1: (shp[0][0],)}


def _custom_hook(p, shp):
    from ..ops.custom import _custom_shape_hook
    return _custom_shape_hook(p, shp)


PARAM_SHAPE_HOOKS: Dict[str, Callable] = {
    "Custom": _custom_hook,
    "SoftmaxOutput": _softmax_output_hook,
    "LinearRegressionOutput": _regression_hook,
    "LogisticRegressionOutput": _regression_hook,
    "MAERegressionOutput": _regression_hook,
    "SVMOutput": _ce_hook,
    "softmax_cross_entropy": _ce_hook,
    "FullyConnected": _fc_hook,
    "Convolution": _conv_hook,
    "Deconvolution": _deconv_hook,
    "BatchNorm": _bn_hook,
    "InstanceNorm": _in_hook,
    "LayerNorm": _ln_hook,
    "Embedding": _emb_hook,
    "RNN": _rnn_hook,
    "LeakyReLU": _prelu_hook,
}


class _Step:
    __slots__ = ("node", "op", "params", "in_refs", "out_base", "aux_var_names")

    def __init__(self, node, op, params, in_refs, out_base, aux_var_names):
        self.node = node
        self.op = op
        self.params = params      # normalized dict (without __is_train__)
        self.in_refs = in_refs    # list of ('var', name) | ('val', (step_idx, out_idx))
        self.out_base = out_base  # index into the flat value table
        self.aux_var_names = aux_var_names  # input-aux-slot -> var name (or None)


class GraphPlan:
    """Topologically-ordered executable plan for a Symbol."""

    def __init__(self, symbol: Symbol):
        self.symbol = symbol
        nodes = symbol._topo()
        self.arg_names = symbol.list_arguments()
        self.aux_names = symbol.list_auxiliary_states()
        self.input_names = set(self.arg_names) | set(self.aux_names)
        node_out: Dict[int, Any] = {}
        self.steps: List[_Step] = []
        for n in nodes:
            if n.is_var:
                node_out[id(n)] = ("var", n.name)
                continue
            op = _reg.get_op(n.op)
            params = dict(op.normalize(_canon_params(op, n, len(n.inputs))))
            in_refs = []
            for src, oi in n.inputs:
                ref = node_out[id(src)]
                if ref[0] == "var":
                    in_refs.append(ref)
                else:
                    in_refs.append(("val", (ref[1], oi)))
            aux_map = {}
            for pos, ai in enumerate(op.aux_inputs):
                if ai < len(n.inputs) and n.inputs[ai][0].is_var:
                    aux_map[pos] = n.inputs[ai][0].name
            step_idx = len(self.steps)
            self.steps.append(_Step(n, op, params, in_refs, step_idx, aux_map))
            node_out[id(n)] = ("step", step_idx)
        # map output entries
        self.out_refs = []
        for node, oi in symbol._entries:
            ref = node_out[id(node)]
            if ref[0] == "var":
                self.out_refs.append(("var", node.name))
            else:
                self.out_refs.append(("val", (ref[1], oi)))

    def out_stypes(self) -> list:
        """Storage type of each graph output: 'row_sparse'/'csr' when the
        producing node is cast_storage with a sparse target, else
        'default'.  The executor wraps such outputs in real sparse
        NDArrays at the graph boundary (parity: cast_storage.cc
        CastStorageComputeEx producing an rsp/csr output chunk — inside
        XLA compute stays dense, the storage class materializes where
        the value leaves the compiled program)."""
        out = []
        for ref in self.out_refs:
            st = "default"
            if ref[0] == "val":
                step = self.steps[ref[1][0]]
                if step.op.name == "cast_storage":
                    st = step.params.get("stype", "default")
            out.append(st if st in ("row_sparse", "csr") else "default")
        return out

    def sparse_grad_args(self) -> Dict[str, list]:
        """Arg names whose gradient the executor can produce ROWS-ONLY:
        variables used exclusively as the weight of
        Embedding(sparse_grad=True) steps whose data input is itself a
        graph input (the Module-API sparse-embedding pattern; parity:
        indexing_op.h rsp EmbeddingOpBackward + infer-storage making the
        weight grad row_sparse).  Returns {name: [(step_idx, data_var)]}.
        """
        users: Dict[str, list] = {}
        for si, s in enumerate(self.steps):
            for pos, ref in enumerate(s.in_refs):
                if ref[0] == "var":
                    users.setdefault(ref[1], []).append((si, s, pos))
        direct_outs = {r[1] for r in self.out_refs if r[0] == "var"}
        out = {}
        for name, us in users.items():
            if name in direct_outs:
                continue
            if all(s.op.name == "Embedding" and pos == 1
                   and bool(s.params.get("sparse_grad"))
                   and s.in_refs[0][0] == "var"
                   for _, s, pos in us):
                out[name] = [(si, s.in_refs[0][1]) for si, s, _ in us]
        return out

    def specialize_init_shapes(self, known_shapes: Dict[str, tuple]) -> None:
        """Resolve 0-dims in init-op shape params (rnn begin_state) against
        the bound arg shapes — the bind-time leg of the candidate
        substitution in infer_shapes_types."""
        if not known_shapes or not any(
                s.op.name in ("_zeros", "_ones", "_full")
                and s.params.get("shape") is not None
                and any(int(d) == 0 for d in s.params["shape"])
                for s in self.steps):
            return
        try:
            plan2, _, _ = infer_shapes_types(
                self.symbol, {k: tuple(v) for k, v in known_shapes.items()
                              if v is not None}, {})
        except MXNetError:
            return
        self.init_overrides = getattr(plan2, "init_overrides", {})
        for si, p in self.init_overrides.items():
            self.steps[si].params.update(p)

    # -- whole-graph channels-last pass --------------------------------
    def _apply_cl(self, step, ins, in_cl, overridden):
        """One step of the layout propagation: given resolved inputs and
        their channels-last tags, return (ins', extra_params, out_cl).
        out_cl tags OUTPUT 0 only (spatial ops' secondary outputs — BN
        saved mean/var — are per-channel vectors, never CL)."""
        name = step.op.name
        p = step.params

        def demote():
            return ([_layout.from_cl(v) if f else v
                     for v, f in zip(ins, in_cl)], None, False)

        if overridden:
            return demote()
        x = ins[0] if ins else None
        nd = getattr(x, "ndim", 0)
        if name in ("Convolution", "Deconvolution"):
            if nd == len(p["kernel"]) + 2:
                out = [_layout.from_cl(v) if f and i else v
                       for i, (v, f) in enumerate(zip(ins, in_cl))]
                if not in_cl[0]:
                    out[0] = _layout.to_cl(x)
                return out, {"__io_layout__": "NHWC"}, True
            return demote()
        if name == "Pooling" and nd >= 3:
            return ([x if in_cl[0] else _layout.to_cl(x)],
                    {"__io_layout__": "NHWC"}, True)
        if name == "BatchNorm" and nd >= 3 and p.get("axis", 1) % nd == 1:
            out = list(ins)
            out[0] = x if in_cl[0] else _layout.to_cl(x)
            return out, {"__io_layout__": "NHWC"}, True
        if name in _CL_EW_ONE and len(ins) == 1:
            if name == "LeakyReLU" and p.get("act_type") == "prelu":
                return demote()
            return list(ins), None, bool(in_cl[0])
        if name in _CL_EW_TWO and len(ins) == 2 and any(in_cl):
            s0, s1 = (getattr(v, "shape", None) for v in ins)
            if s0 is not None and s0 == s1:
                return [v if f else _layout.to_cl(v)
                        for v, f in zip(ins, in_cl)], None, True
        if (name == "Concat" and p.get("dim", 1) == 1 and any(in_cl)
                and all(getattr(v, "ndim", 0) >= 3 for v in ins)):
            # channel-axis concat stays channels-last (the axis moves to
            # the minor position — ops/matrix.py honors __io_layout__);
            # densenet/inception concat chains keep the CL region intact
            return ([v if f else _layout.to_cl(v)
                     for v, f in zip(ins, in_cl)],
                    {"__io_layout__": "NHWC"}, True)
        return demote()

    # -- execution (pure; call under jit) -----------------------------------
    def run(self, arg_values: Dict[str, Any], aux_values: Dict[str, Any],
            key, is_train: bool, step_overrides=None, segments: int = 1):
        """Execute the graph. Returns (outputs, new_aux_values).

        `step_overrides` maps step index -> fn(params, inputs) returning
        the step's output tuple (the executor's rows-only embedding-grad
        rewrite rides this hook).

        `segments > 1` runs the step list as that many contiguous
        `jax.checkpoint` segments: a vjp over the call then saves only
        the segment-boundary live values and recomputes within each
        segment during backprop — sqrt(N) activation memory, the TPU
        redesign of the reference's backward-mirror pass
        (MXNET_BACKWARD_DO_MIRROR, src/executor/graph_executor.cc
        mirror-stage selection).  A whole-graph jax.checkpoint gives no
        saving (the recompute re-materializes every activation at
        once); segmentation is what makes remat pay."""
        if segments and segments > 1 and not step_overrides:
            return self._run_segmented(arg_values, aux_values, key,
                                       is_train, int(segments))
        values: List[Tuple] = [None] * len(self.steps)
        new_aux = dict(aux_values)
        use_cl = _layout.channels_last() and _layout.whole_graph()
        cl_flags: Dict[tuple, bool] = {}

        def resolve(ref):
            if ref[0] == "var":
                nm = ref[1]
                if nm in arg_values:
                    return arg_values[nm]
                if nm in new_aux:
                    return new_aux[nm]
                raise MXNetError(f"unbound variable '{nm}'")
            si, oi = ref[1]
            return values[si][oi]

        def cl_of(ref):
            return ref[0] == "val" and cl_flags.get(ref[1], False)

        for si, step in enumerate(self.steps):
            ins = [resolve(r) for r in step.in_refs]
            if use_cl:
                ins, extra, out_cl = self._apply_cl(
                    step, ins, [cl_of(r) for r in step.in_refs],
                    bool(step_overrides and si in step_overrides))
                cl_flags[(si, 0)] = out_cl
            else:
                extra = None
            p = dict(step.params)
            if extra:
                p.update(extra)
            if step.op.takes_is_train:
                p["__is_train__"] = is_train
            if step.op.needs_rng:
                ins.append(jax.random.fold_in(key, si))
            # layer attribution (ISSUE 13): each step traces under a
            # jax.named_scope of its node name, so HLO instruction
            # metadata carries layer names through forward AND the vjp
            # and the node's operator beside its name (introspect.per_layer
            # and op_scopes parse them back out).  Trace-time
            # only — compiled programs pay nothing per execution; one
            # boolean when MXNET_INTROSPECT=0
            with _introspect.layer_scope(step.node.name, step.op.name):
                if step_overrides and si in step_overrides:
                    out = step_overrides[si](p, ins)
                else:
                    out = step.op.fn(p, *ins)
            out = out if isinstance(out, tuple) else (out,)
            n_vis = len(out) - len(step.op.aux_inputs)
            values[si] = out[:n_vis]
            for pos, nm in step.aux_var_names.items():
                new_aux[nm] = out[n_vis + pos]
        outputs = [_layout.from_cl(resolve(r)) if cl_of(r) else resolve(r)
                   for r in self.out_refs]
        return outputs, new_aux

    def _segment_layout(self, k: int):
        """Contiguous segmentation [(b0, b1, live_in_keys), ...] where
        live_in_keys are the (step, out_idx) values produced before b0
        and still consumed at/after b0 (step index len(steps) stands for
        the graph outputs).  Cached per k."""
        cache = self.__dict__.setdefault("_seg_cache", {})
        if k in cache:
            return cache[k]
        n = len(self.steps)
        k = max(1, min(k, n))
        bounds = sorted({int(round(i * n / k)) for i in range(k + 1)})
        consumers: Dict[tuple, list] = {}
        for si, step in enumerate(self.steps):
            for ref in step.in_refs:
                if ref[0] == "val":
                    consumers.setdefault(ref[1], []).append(si)
        for ref in self.out_refs:
            if ref[0] == "val":
                consumers.setdefault(ref[1], []).append(n)
        segs = []
        for b0, b1 in zip(bounds[:-1], bounds[1:]):
            live_in = sorted(key for key, cs in consumers.items()
                             if key[0] < b0 and any(c >= b0 for c in cs))
            segs.append((b0, b1, live_in))
        # live set AFTER the last segment = exactly the output values
        cache[k] = segs
        return segs

    def _run_segmented(self, arg_values, aux_values, key, is_train, k):
        segs = self._segment_layout(k)
        use_cl = _layout.channels_last() and _layout.whole_graph()
        # tags persist across segment traces (values crossing a
        # checkpoint boundary keep their physical layout; the dict is
        # filled in execution order, segment i before i+1)
        cl_flags: Dict[tuple, bool] = {}

        def make_seg(b0, b1, live_out_keys):
            def seg(args, live_in, aux_in, key_):
                local: Dict[tuple, Any] = dict(live_in)
                aux = dict(aux_in)

                def resolve(ref):
                    if ref[0] == "var":
                        nm = ref[1]
                        if nm in args:
                            return args[nm]
                        if nm in aux:
                            return aux[nm]
                        raise MXNetError(f"unbound variable '{nm}'")
                    return local[ref[1]]

                def cl_of(ref):
                    return ref[0] == "val" and cl_flags.get(ref[1], False)

                for si in range(b0, b1):
                    step = self.steps[si]
                    ins = [resolve(r) for r in step.in_refs]
                    if use_cl:
                        ins, extra, out_cl = self._apply_cl(
                            step, ins, [cl_of(r) for r in step.in_refs],
                            False)
                        cl_flags[(si, 0)] = out_cl
                    else:
                        extra = None
                    p = dict(step.params)
                    if extra:
                        p.update(extra)
                    if step.op.takes_is_train:
                        p["__is_train__"] = is_train
                    if step.op.needs_rng:
                        ins.append(jax.random.fold_in(key_, si))
                    with _introspect.layer_scope(step.node.name,
                                                 step.op.name):
                        out = step.op.fn(p, *ins)
                    out = out if isinstance(out, tuple) else (out,)
                    n_vis = len(out) - len(step.op.aux_inputs)
                    for oi in range(n_vis):
                        local[(si, oi)] = out[oi]
                    for pos, nm in step.aux_var_names.items():
                        aux[nm] = out[n_vis + pos]
                return {kk: local[kk] for kk in live_out_keys}, aux
            return jax.checkpoint(seg)

        live: Dict[tuple, Any] = {}
        aux = dict(aux_values)
        out_keys = sorted({ref[1] for ref in self.out_refs
                           if ref[0] == "val"})
        for i, (b0, b1, _) in enumerate(segs):
            nxt = segs[i + 1][2] if i + 1 < len(segs) else out_keys
            live, aux = make_seg(b0, b1, nxt)(arg_values, live, aux, key)
        outputs = [arg_values[r[1]] if r[0] == "var" and r[1] in arg_values
                   else aux[r[1]] if r[0] == "var"
                   else (_layout.from_cl(live[r[1]])
                         if cl_flags.get(r[1], False) else live[r[1]])
                   for r in self.out_refs]
        return outputs, aux


class LoopBody:
    """The body of a `contrib.foreach` loop: a sub-graph traced once, and
    how a `_foreach` node runs it.

    `symbol` groups the body's outputs and then its new states; `in_names`
    are the sub-graph's variables in the order of the node's inputs: the
    data slices, the states, then everything the body closes over (values
    of the outer graph, then free variables: the blocks' parameters).

    `scan` runs the loop as ONE `lax.scan`: the compiled program holds the
    body once.  The closed-over inputs are constants of the scan, not
    scanned and not carried, so the gradient of a parameter is the sum over
    the steps, as jax transposes a constant.  The body runs under its own
    `jax.checkpoint`: under a recorded CachedOp call the caller's policy
    reaches into it (gluon/block.py `_RESIDUAL_POLICY`, as it reaches into
    `ops/decoder.py moe_ffn`), so the forward scan stacks the outputs of
    the body's products and kernels along the loop axis, and the backward
    scan recomputes one step's element-wise work at a time; anywhere else
    (an executor's backward) a step is recomputed whole from its carry.

    A body may hold no auxiliary state (BatchNorm's moving statistics, an
    expert layer's load counter) and no operator that draws random numbers
    (Dropout): neither a state written inside a step nor a key for each
    step is carried through the loop.  `sym.contrib.foreach` raises
    MXNetError for both when it builds the node.
    """

    def __init__(self, symbol: Symbol, in_names):
        self.symbol = symbol
        self.in_names = tuple(in_names)
        self._plan = None

    def __repr__(self):
        return f"LoopBody({len(self.symbol._topo())} nodes)"

    @property
    def plan(self) -> "GraphPlan":
        if self._plan is None:
            self._plan = GraphPlan(self.symbol)
        return self._plan

    def scan(self, ins, n_data, n_states, n_out, is_train):
        from jax import lax
        names = self.in_names
        if len(ins) != len(names):
            raise MXNetError(f"_foreach: {len(ins)} inputs for a body of "
                             f"{len(names)} variables")
        cut = n_data + n_states
        closed = dict(zip(names[cut:], ins[cut:]))

        def step(carry, xs):
            args = dict(closed)
            args.update(zip(names[:n_data], xs))
            args.update(zip(names[n_data:cut], carry))
            outs, _ = self.plan.run(args, {}, None, is_train)
            return tuple(outs[n_out:]), tuple(outs[:n_out])

        # within a scan nothing can be merged with the recomputation
        final, stacked = lax.scan(jax.checkpoint(step, prevent_cse=False),
                                  tuple(ins[n_data:cut]),
                                  tuple(ins[:n_data]))
        return tuple(stacked) + tuple(final)


def _canon_params(op, node, n_inputs):
    p = {}
    for k, v in node.params.items():
        if k in op.schema.args:
            p[k] = v
    if op.variadic and "num_args" in op.schema.args:
        p["num_args"] = n_inputs
    return p


# ---------------------------------------------------------------------------
# shape / type inference
# ---------------------------------------------------------------------------
def _node_eval_shape(op, params, in_structs):
    p = dict(params)
    if op.takes_is_train:
        p["__is_train__"] = False
    args = list(in_structs)
    if op.needs_rng:
        args.append(jax.random.PRNGKey(0))

    def f(*ins):
        out = op.fn(p, *ins)
        return out if isinstance(out, tuple) else (out,)

    return jax.eval_shape(f, *args)


def infer_shapes_types(symbol: Symbol, known_shapes: Dict[str, tuple],
                       known_types: Dict[str, Any], partial: bool = False):
    """Returns ({input_name: (shape, dtype)}, [(shape, dtype) per output]).

    Variables carrying a partial `__shape__` hint with 0-dims (the
    reference's "unknown dim" convention — e.g. RNN begin_state (0, H),
    rnn_cell.py state_info) are resolved by candidate substitution: try
    each dim appearing in the known input shapes for the 0s; a wrong
    candidate fails loudly at the first binary-op shape check, the right
    one completes inference.  This replaces nnvm's bidirectional
    InferShape pass for the begin-state case without a full constraint
    solver.
    """
    plan = GraphPlan(symbol)
    info: Dict[str, Optional[jax.ShapeDtypeStruct]] = {}
    partial_hints: Dict[str, tuple] = {}
    for nm in plan.input_names:
        shp = known_shapes.get(nm)
        node_attr_shape = None
        dt = known_types.get(nm, _np.float32)
        if shp is None:
            # __shape__ attr hint on the variable
            for n in symbol._topo():
                if n.is_var and n.name == nm and "__shape__" in n.attrs:
                    node_attr_shape = eval(n.attrs["__shape__"], {"__builtins__": {}})
            shp = node_attr_shape
        if shp is not None and any(int(d) == 0 for d in shp):
            partial_hints[nm] = tuple(int(d) for d in shp)
            shp = None  # 0-dims mean "unknown" until substitution
        if shp is not None:
            info[nm] = jax.ShapeDtypeStruct(tuple(int(d) for d in shp),
                                            np_dtype(dt))
        else:
            info[nm] = None

    # init ops (_zeros/_ones, e.g. rnn begin_state) with 0-dims in their
    # static shape param are likewise unknown-until-substitution
    partial_steps: Dict[int, tuple] = {}
    for si, step in enumerate(plan.steps):
        shp = step.params.get("shape")
        if step.op.name in ("_zeros", "_ones", "_full") and shp is not None \
                and any(int(d) == 0 for d in shp):
            partial_steps[si] = tuple(int(d) for d in shp)

    if (partial_hints or partial_steps) and known_shapes:
        candidates: List[int] = []
        for s in known_shapes.values():
            for d in s:
                if int(d) > 0 and int(d) not in candidates:
                    candidates.append(int(d))
        # 1 broadcasts against everything, so it can never "fail loudly";
        # try it only after every stricter candidate has been rejected
        if 1 in candidates:
            candidates.remove(1)
            candidates.append(1)
        for c in candidates:
            trial = dict(info)
            for nm, hint in partial_hints.items():
                if trial.get(nm) is None:
                    filled = tuple(c if d == 0 else d for d in hint)
                    trial[nm] = jax.ShapeDtypeStruct(
                        filled, np_dtype(known_types.get(nm, _np.float32)))
            overrides = {si: {"shape": tuple(c if d == 0 else d for d in hint)}
                         for si, hint in partial_steps.items()}
            try:
                res = _infer_forward(plan, symbol, trial, partial=False,
                                     param_overrides=overrides)
            except MXNetError:
                continue
            # record + apply the winning substitution so executors running
            # this plan materialize correctly-sized begin-states
            plan.init_overrides = overrides
            for si, p in overrides.items():
                plan.steps[si].params.update(p)
            return res
    return _infer_forward(plan, symbol, info, partial=partial)


def _infer_forward(plan, symbol, info, partial, param_overrides=None):

    step_out: List[Optional[tuple]] = [None] * len(plan.steps)

    def ref_struct(ref):
        if ref[0] == "var":
            return info.get(ref[1])
        si, oi = ref[1]
        return step_out[si][oi] if step_out[si] is not None else None

    for si, step in enumerate(plan.steps):
        structs = [ref_struct(r) for r in step.in_refs]
        if any(s is None for s in structs):
            hook = PARAM_SHAPE_HOOKS.get(step.op.name)
            if hook is not None and structs[0] is not None:
                fills = hook(step.params, [s.shape if s else None for s in structs])
                for idx, shp in fills.items():
                    if idx < len(structs) and structs[idx] is None:
                        ref = step.in_refs[idx]
                        if ref[0] == "var":
                            st = jax.ShapeDtypeStruct(tuple(int(x) for x in shp),
                                                      structs[0].dtype)
                            info[ref[1]] = st
                            structs[idx] = st
        if any(s is None for s in structs):
            if partial:
                continue
            missing = [step.in_refs[i] for i, s in enumerate(structs) if s is None]
            raise MXNetError(
                f"infer_shape: cannot infer input(s) {missing} of node "
                f"'{step.node.name}' ({step.op.name}); provide their shapes")
        try:
            p = step.params
            if param_overrides and si in param_overrides:
                p = {**p, **param_overrides[si]}
            outs = _node_eval_shape(step.op, p, structs)
        except Exception as e:  # shape error inside op
            raise MXNetError(f"infer_shape failed at node '{step.node.name}' "
                             f"({step.op.name}): {e}") from None
        n_vis = len(outs) - len(step.op.aux_inputs)
        step_out[si] = tuple(outs[:n_vis])

    out_structs = []
    for ref in plan.out_refs:
        out_structs.append(ref_struct(ref))
    return plan, info, out_structs


def infer_shape(symbol: Symbol, partial: bool, *args, **kwargs):
    known = {}
    arg_names = symbol.list_arguments()
    if args:
        for nm, shp in zip(arg_names, args):
            if shp is not None:
                known[nm] = shp
    known.update({k: v for k, v in kwargs.items() if v is not None})
    try:
        plan, info, outs = infer_shapes_types(symbol, known, {}, partial=partial)
    except MXNetError:
        if partial:
            return None, None, None
        raise
    # `is not None`, not truthiness: a scalar output's ShapeDtypeStruct
    # raises on __len__ (loss graphs end in shape-() outputs)
    arg_shapes = [tuple(info[n].shape) if info.get(n) is not None else None
                  for n in arg_names]
    aux_shapes = [tuple(info[n].shape) if info.get(n) is not None else None
                  for n in symbol.list_auxiliary_states()]
    out_shapes = [tuple(o.shape) if o is not None else None for o in outs]
    return arg_shapes, out_shapes, aux_shapes


def _f32_forced_vars(symbol: Symbol):
    """Variables that stay f32 under reduced-precision training — declared
    per-op in the registry (Operator.f32_inputs: BN scale/stats, class-id/
    index inputs)."""
    plan = GraphPlan(symbol)
    forced = set()
    for step in plan.steps:
        for i in step.op.f32_inputs:
            if i < len(step.in_refs) and step.in_refs[i][0] == "var":
                forced.add(step.in_refs[i][1])
    return forced


def infer_type(symbol: Symbol, *args, **kwargs):
    known_t = {}
    arg_names = symbol.list_arguments()
    if args:
        for nm, dt in zip(arg_names, args):
            if dt is not None:
                known_t[nm] = dt
    known_t.update({k: v for k, v in kwargs.items() if v is not None})
    # reference-style propagation: unknown float params take the training
    # dtype — fp16/bf16 data implies fp16/bf16 weights, exactly how
    # reference fp16 training binds — except the registry's f32-forced
    # inputs.  The training dtype = the first known float input in
    # argument (topological) order that is NOT itself f32-forced (so a
    # f32 label never wins the scan over bf16 data, whatever the names).
    forced = _f32_forced_vars(symbol)
    float_default = _np.float32
    for nm in arg_names:
        dt = known_t.get(nm)
        if dt is None or nm in forced:
            continue
        # jnp.issubdtype: bf16/f16 are ml_dtypes, invisible to numpy's
        # floating hierarchy
        if jax.numpy.issubdtype(np_dtype(dt), jax.numpy.floating):
            float_default = np_dtype(dt)
            break
    def var_t(n):
        if n in known_t:
            return np_dtype(known_t[n])
        return _np.dtype(_np.float32) if n in forced else float_default

    arg_types = [var_t(n) for n in arg_names]
    aux_types = [var_t(n) for n in symbol.list_auxiliary_states()]
    out_types = [np_dtype(float_default)] * len(symbol._entries)
    return arg_types, out_types, aux_types
