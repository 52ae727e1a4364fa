"""A recorded CachedOp call runs its forward once (gluon/block.py).

Under `autograd.record()` the forward program returns the graph's pullback
with the residuals its policy keeps; the backward program applies it and
runs none of the forward's products or kernels again.  Checked here on the
CPU, over a small convolutional net (Dense, Conv, BatchNorm, Dropout) and
the two language models of the benchmark's cells at toy sizes: the same
mathematics as `jax.vjp` over `plan.run` at the same key, what the lowered
and the compiled programs hold, what survives `backward`, and what the
counters read.
"""
import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd
from mxnet_tpu import random as mx_random
from mxnet_tpu.gluon import block as blk
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.model_zoo.decoder import DecoderLM
from mxnet_tpu.gluon.model_zoo.transformer import TransformerLM
from mxnet_tpu.observability import metrics

from test_flash_attention import _kernel_eqns

NETS = ("convnet", "transformer", "decoder")
PRODUCTS = ("dot_general", "conv_general_dilated", "ragged_dot_general")
# a kernel's own products sit in its body (the forward's and the attention
# backward's alike): none is a forward product of the graph
OPAQUE = ("scan", "while", "pallas_call")


def _build(which):
    mx.random.seed(11)
    rs = np.random.RandomState(5)
    if which == "convnet":
        net = nn.HybridSequential()
        with net.name_scope():
            net.add(nn.Conv2D(4, 3, padding=1), nn.BatchNorm(),
                    nn.Activation("relu"), nn.Dense(8, activation="relu"),
                    nn.Dropout(0.5), nn.Dense(3))
        x = mx.nd.array(rs.randn(6, 2, 5, 5).astype("f"))
    elif which == "transformer":
        net = TransformerLM(vocab=32, dim=16, num_layers=2, num_heads=2,
                            ffn_dim=32, max_len=32, attn_type="flash")
        x = mx.nd.array(rs.randint(0, 32, (2, 32)).astype("f"))
    else:
        net = DecoderLM(32, 16, 3, 2, 8, 8, 4, 4, 8, 24, 12, 4, 2,
                        held_experts=2, attn_type="flash")
        x = mx.nd.array(rs.randint(0, 32, (2, 32)).astype("f"))
    net.initialize(mx.init.Normal(0.2), ctx=mx.cpu())
    net.hybridize()
    with autograd.pause():
        net(x)  # deferred shapes; builds the CachedOp
    return net, x


@pytest.fixture(scope="module", params=NETS)
def case(request):
    net, x = _build(request.param)
    return request.param, net, x


@pytest.fixture
def fixed_key(monkeypatch):
    key = jax.random.PRNGKey(3)
    monkeypatch.setattr(mx_random, "next_key", lambda: key)
    return key


def _program_inputs(net, x):
    """(args, aux) as CachedOp hands them to its programs."""
    args = {net._cached_input_names[0]: x._data}
    aux = {}
    for name, p in net._cached_params.items():
        (aux if name in net._cached_aux else args)[name] = p.data()._data
    return args, aux


def _count(jaxpr, into=None):
    """Primitive name -> equations, through every sub-jaxpr but OPAQUE's."""
    into = collections.Counter() if into is None else into
    for eqn in jaxpr.eqns:
        into[eqn.primitive.name] += 1
        if eqn.primitive.name in OPAQUE:
            continue
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _count(sub, into)
    return into


def _close(a, b):
    np.testing.assert_allclose(np.asarray(a, "f"), np.asarray(b, "f"),
                               rtol=2e-4, atol=2e-5)


def _record_backward(net, x):
    params = [p for p in net.collect_params().values()
              if p.grad_req != "null"]
    with autograd.record():
        out = net(x)
    out.backward()
    return out, {p.name: p.grad().asnumpy() for p in params}


def test_gradients_and_auxiliary_states_equal_vjp_over_the_plan(
        case, fixed_key):
    _which, net, x = case
    args, aux = _program_inputs(net, x)
    plan = net._cached_op.plan
    (outs, new_aux), pull = jax.vjp(
        lambda a: plan.run(a, aux, fixed_key, True), args)
    (want,) = pull(([jnp.ones_like(o) for o in outs],
                    {k: jnp.zeros_like(v) for k, v in new_aux.items()}))
    out, got = _record_backward(net, x)
    _close(out.asnumpy(), outs[0])
    assert got
    for name, g in got.items():
        _close(g, want[name])
    for name, v in new_aux.items():  # updated once, by the forward program
        _close(net._cached_params[name].data().asnumpy(), v)


def test_backward_program_runs_no_forward_product(case, fixed_key):
    which, net, x = case
    op = net._cached_op
    args, aux = _program_inputs(net, x)
    fwd = _count(jax.make_jaxpr(
        lambda a, s, k: op._fwd.__wrapped__(a, s, k, True, False))(
            args, aux, fixed_key).jaxpr)
    outs, new_aux, pull = op._fwd(args, aux, fixed_key, True, True)
    cots = (tuple(jnp.ones_like(o) for o in outs),
            {k: jnp.zeros_like(v) for k, v in new_aux.items()})
    bwd = _count(jax.make_jaxpr(op._bwd.__wrapped__)(
        pull, (args, aux, fixed_key), cots).jaxpr)
    n_fwd = sum(fwd[p] for p in PRODUCTS)
    assert n_fwd > 0
    # dx and dW of every product, and no third: the forward is not there
    assert sum(bwd[p] for p in PRODUCTS) == 2 * n_fwd, (fwd, bwd)
    assert bwd["ragged_dot_general"] == 2 * fwd["ragged_dot_general"]
    # the attention backward's two kernels a layer, and no forward kernel:
    # every `pallas_call` of the backward program sits under `flash_bwd_*`
    assert bwd["pallas_call"] == 2 * fwd["pallas_call"]
    if which != "convnet":
        assert fwd["pallas_call"] == {"transformer": 2, "decoder": 3}[which]


@pytest.mark.parametrize("which", ["transformer", "decoder"])
def test_attention_backward_is_kernels_fed_by_kept_statistics(
        which, fixed_key):
    """The recorded forward program hands the rows' log-sum-exp of every
    attention layer to the backward program (B * H, 1, T floats a layer,
    compact), and the backward program's attention is `pallas_call`s under
    the `flash_bwd_*` scopes: no `scan` or `while` of plain XLA is left."""
    net, x = _build(which)
    op = net._cached_op
    args, aux = _program_inputs(net, x)
    layers = {"transformer": 2, "decoder": 3}[which]
    heads, T = 2, x.shape[1]
    outs, new_aux, kept, _refs = _pullback_nodes(op, args, aux, fixed_key)
    stats = [n for n in kept if n.shape == (x.shape[0] * heads, 1, T)]
    assert len(stats) == layers and all(n.dtype == jnp.float32 for n in stats)
    _o, _a, pull = op._fwd(args, aux, fixed_key, True, True)
    cots = (tuple(jnp.ones_like(o) for o in outs),
            {k: jnp.zeros_like(v) for k, v in new_aux.items()})
    jaxpr = jax.make_jaxpr(op._bwd.__wrapped__)(
        pull, (args, aux, fixed_key), cots).jaxpr
    bwd = _count(jaxpr)
    assert bwd["scan"] == bwd["while"] == 0, bwd
    scopes = collections.Counter(
        str(e.source_info.name_stack).rsplit("/", 1)[-1]
        for e in _kernel_eqns(jaxpr) if e.primitive.name == "pallas_call")
    assert scopes == {"flash_bwd_dkv": layers, "flash_bwd_dq": layers}
    assert metrics.FLASH_BWD.get(path="kernel") >= layers


def test_retained_graph_gives_the_same_gradients_again(case, fixed_key):
    _which, net, x = case
    with autograd.record():
        out = net(x)
    out.backward(retain_graph=True)
    params = [p for p in net.collect_params().values()
              if p.grad_req != "null"]
    first = [p.grad().asnumpy() for p in params]
    out.backward()
    for a, p in zip(first, params):
        np.testing.assert_array_equal(a, p.grad().asnumpy())
    assert autograd._state.tape == []


def test_parameters_are_alive_and_unchanged_after_backward(case, fixed_key):
    _which, net, x = case
    params = list(net.collect_params().values())
    arrays = [p.data()._data for p in params]
    before = [np.array(a) for a in arrays]
    _record_backward(net, x)
    for p, a, b in zip(params, arrays, before):
        if p.name in net._cached_aux:
            continue  # a new array, by design
        assert p.data()._data is a and not a.is_deleted()
        np.testing.assert_array_equal(np.asarray(a), b)


def test_call_outside_record_keeps_the_old_outputs_only(case, fixed_key):
    _which, net, x = case
    op = net._cached_op
    args, aux = _program_inputs(net, x)
    shapes = op._fwd.eval_shape(args, aux, fixed_key, False, False)
    assert len(shapes) == 2
    outs, new_aux = shapes
    assert len(outs) == 1 and set(new_aux) == set(aux)
    before = len(autograd._state.tape)
    y = net(x)
    assert len(autograd._state.tape) == before
    _close(y.asnumpy(),
           op.plan.run(args, aux, fixed_key, False)[0][0])


def _pullback_nodes(op, args, aux, key):
    outs, new_aux, pull = op._fwd(args, aux, key, True, True)
    nodes = jax.tree_util.tree_leaves(pull, is_leaf=blk._is_input_ref)
    kept = [n for n in nodes if not blk._is_input_ref(n)]
    refs = [n.index for n in nodes if blk._is_input_ref(n)]
    return outs, new_aux, kept, refs


def test_counters_read_what_the_shapes_say(case, fixed_key):
    _which, net, x = case
    op = net._cached_op
    args, aux = _program_inputs(net, x)
    _outs, _aux, kept, refs = _pullback_nodes(op, args, aux, fixed_key)
    inputs = jax.tree_util.tree_leaves((args, aux, fixed_key))
    kept = sum(n.nbytes for n in kept)
    primal = sum(inputs[i].nbytes for i in refs)
    assert kept > 0 and primal > 0
    launches = metrics.CACHEDOP_BACKWARDS.value
    _record_backward(net, x)
    assert metrics.CACHEDOP_RESIDUAL_BYTES.get(kind="kept") == kept
    assert metrics.CACHEDOP_RESIDUAL_BYTES.get(kind="primal") == primal
    assert metrics.CACHEDOP_BACKWARDS.value == launches + 1


def test_forward_program_writes_no_copy_of_an_input(case, fixed_key):
    """Every weight a product reads is a residual of the graph (dx = g W^T),
    and each leaves the pullback as a reference, once (the forwarding hangs
    on a tracer's identity surviving `jax.vjp` under `jit`: a jax that
    stops that fails here).  The compiled forward program writes the kept
    residuals, the outputs and the new auxiliary states, and beyond them
    nothing but the CPU's table of one pointer an output: no weight."""
    _which, net, x = case
    op = net._cached_op
    args, aux = _program_inputs(net, x)
    outs, new_aux, kept, refs = _pullback_nodes(op, args, aux, fixed_key)
    assert len(set(refs)) == len(refs)
    matrices = {i for i, name in enumerate(sorted(args))  # the leaf order
                if args[name].ndim > 1}
    assert len(matrices) > 1 and set(refs) >= matrices, (refs, sorted(args))
    written = [*kept, *outs, *new_aux.values()]
    compiled = op._fwd.lower(args, aux, fixed_key, True, True).compile()
    size = compiled.memory_analysis().output_size_in_bytes
    payload = sum(a.nbytes for a in written)
    assert payload <= size <= payload + 8 * len(written), (size, payload)


@pytest.mark.parametrize("which", NETS)
def test_a_call_places_its_arrays_once_and_compiles_once(monkeypatch, which):
    """Arrays made on the host (`jnp.asarray`: an initializer's, `cast`'s,
    `set_data`'s) are not committed to a device, the update's outputs that
    replace the parameters are, and `jit` keys a call on it.  A CachedOp
    call commits what it reads where it lies (the same buffer) and keeps
    what it committed, so the next call places nothing and each recorded
    program is compiled once over the steps (PERF.md Open questions 13)."""
    net, x = _build(which)
    op = net._cached_op
    params = list(net.collect_params().values())
    for p in params:  # as they come from an initializer
        p.data()._data = jnp.asarray(np.asarray(p.data()._data))
    x2 = mx.nd.NDArray(jnp.asarray(x.asnumpy()), x.context)
    arrays = [x2] + [p.data() for p in params]
    assert not any(a._data._committed for a in arrays)
    where = [a._data.unsafe_buffer_pointer() for a in arrays]
    trainer = mx.gluon.Trainer(net.collect_params(), "sgd",
                               {"learning_rate": 0.01})
    sizes, placed = [], []
    put = jax.device_put

    def counted(*a, **k):
        placed[-1] += 1
        return put(*a, **k)

    monkeypatch.setattr(jax, "device_put", counted)
    for step in range(3):
        placed.append(0)
        with autograd.record():
            out = net(x2)
        if step == 0:
            assert all(a._data._committed for a in arrays)
            # the auxiliary states are the call's new ones
            assert [a._data.unsafe_buffer_pointer() for a, p in
                    zip(arrays, [None] + params)
                    if p is None or p.grad_req != "null"] == \
                [w for w, p in zip(where, [None] + params)
                 if p is None or p.grad_req != "null"]
        out.backward()
        trainer.step(1)
        sizes.append((op._fwd._cache_size(), op._bwd._cache_size()))
    assert placed[0] == len(arrays) and placed[1:] == [0, 0], placed
    assert sizes[0] == sizes[1] == sizes[2], sizes
