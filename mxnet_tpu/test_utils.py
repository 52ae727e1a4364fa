"""Test utilities (parity: python/mxnet/test_utils.py, 1,571 LoC).

The reference's op-test machinery: assert_almost_equal, finite-difference
check_numeric_gradient (:789), check_symbolic_forward/backward (:921,995),
rand_ndarray, default_context, and check_consistency (:1203) — re-targeted
as CPU-vs-TPU (instead of CPU-vs-GPU) cross-backend equivalence.
"""
from __future__ import annotations

import numpy as _np

from .base import MXNetError
from .context import Context, cpu, current_context
from . import io
from . import ndarray as nd
from .ndarray import NDArray
from . import symbol as sym
from . import random as _random

_rng = _np.random.RandomState(1234)


def default_context() -> Context:
    return current_context()


def set_default_context(ctx: Context) -> None:
    Context.default_ctx = ctx


def default_dtype():
    return _np.float32


def get_atol(atol=None):
    return 1e-20 if atol is None else atol


def get_rtol(rtol=None):
    return 1e-5 if rtol is None else rtol


def random_arrays(*shapes):
    arrays = [_np.array(_np.random.randn(), dtype=default_dtype())
              if len(s) == 0 else
              _np.random.randn(*s).astype(default_dtype()) for s in shapes]
    if len(arrays) == 1:
        return arrays[0]
    return arrays


def random_sample(population, k):
    population_copy = population[:]
    _np.random.shuffle(population_copy)
    return population_copy[0:k]


def rand_shape_2d(dim0=10, dim1=10):
    return _rng.randint(1, dim0 + 1), _rng.randint(1, dim1 + 1)


def rand_shape_3d(dim0=10, dim1=10, dim2=10):
    return (_rng.randint(1, dim0 + 1), _rng.randint(1, dim1 + 1),
            _rng.randint(1, dim2 + 1))


def rand_shape_nd(num_dim, dim=10):
    return tuple(_rng.randint(1, dim + 1, size=num_dim))


def rand_ndarray(shape, stype="default", density=None, dtype=None,
                 distribution=None):
    """Parity: test_utils.rand_ndarray incl. sparse storage types."""
    if stype == "default":
        return nd.array(random_arrays(shape), dtype=dtype)
    density = 0.1 if density is None else density
    dense = _np.random.randn(*shape).astype(dtype or "float32")
    mask = _np.random.rand(*shape) < density
    dense = dense * mask
    from .ndarray import sparse
    if stype == "row_sparse":
        return sparse.row_sparse_array(dense)
    if stype == "csr":
        return sparse.csr_matrix(dense)
    raise MXNetError(f"unknown storage type {stype}")


def np_reduce(dat, axis, keepdims, numpy_reduce_func):
    if isinstance(axis, int):
        axis = [axis]
    else:
        axis = list(axis) if axis is not None else range(len(dat.shape))
    ret = dat
    for i in reversed(sorted(axis)):
        ret = numpy_reduce_func(ret, axis=i)
    if keepdims:
        keepdims_shape = list(dat.shape)
        for i in axis:
            keepdims_shape[i] = 1
        ret = ret.reshape(tuple(keepdims_shape))
    return ret


def find_max_violation(a, b, rtol=None, atol=None):
    rtol, atol = get_rtol(rtol), get_atol(atol)
    diff = _np.abs(a - b)
    tol = atol + rtol * _np.abs(b)
    violation = diff / (tol + 1e-20)
    loc = _np.argmax(violation)
    idx = _np.unravel_index(loc, violation.shape)
    return idx, _np.max(violation)


def same(a, b):
    return _np.array_equal(a, b)


def assert_almost_equal(a, b, rtol=None, atol=None, names=("a", "b"),
                        equal_nan=False):
    """Parity: test_utils.assert_almost_equal (:467)."""
    a = a.asnumpy() if isinstance(a, NDArray) else _np.asarray(a)
    b = b.asnumpy() if isinstance(b, NDArray) else _np.asarray(b)
    rtol, atol = get_rtol(rtol), get_atol(atol)
    if _np.allclose(a, b, rtol=rtol, atol=atol, equal_nan=equal_nan):
        return
    index, rel = find_max_violation(a, b, rtol, atol)
    raise AssertionError(
        f"Error {rel} exceeds tolerance rtol={rtol}, atol={atol}. "
        f"Location of maximum error: {index}, "
        f"{names[0]}={a[index]:.8f}, {names[1]}={b[index]:.8f}")


def almost_equal(a, b, rtol=None, atol=None, equal_nan=False):
    return _np.allclose(a, b, rtol=get_rtol(rtol), atol=get_atol(atol),
                        equal_nan=equal_nan)


def assert_exception(f, exception_type, *args, **kwargs):
    try:
        f(*args, **kwargs)
        assert False
    except exception_type:
        return


def simple_forward(sym_, ctx=None, is_train=False, **inputs):
    ctx = ctx or default_context()
    inputs = {k: nd.array(v) for k, v in inputs.items()}
    exe = sym_.bind(ctx, args=inputs)
    exe.forward(is_train=is_train)
    outputs = [o.asnumpy() for o in exe.outputs]
    if len(outputs) == 1:
        outputs = outputs[0]
    return outputs


def _parse_location(sym_, location, ctx, dtype=None):
    assert isinstance(location, (dict, list, tuple))
    if isinstance(location, dict):
        if set(location.keys()) != set(sym_.list_arguments()):
            raise ValueError(
                f"Symbol arguments and keys of the given location do not "
                f"match. symbol args: {sym_.list_arguments()}, location.keys():"
                f" {list(location.keys())}")
    else:
        location = {k: v for k, v in zip(sym_.list_arguments(), location)}
    location = {k: nd.array(v, ctx=ctx, dtype=v.dtype if dtype is None
                            else dtype)
                if isinstance(v, _np.ndarray) else
                (v if isinstance(v, NDArray) else nd.array(v, ctx=ctx))
                for k, v in location.items()}
    return location


def _parse_aux_states(sym_, aux_states, ctx, dtype=None):
    if aux_states is None:
        return {}
    if isinstance(aux_states, dict):
        if set(aux_states.keys()) != set(sym_.list_auxiliary_states()):
            raise ValueError("Symbol aux_states names and given aux_states "
                             "do not match.")
    elif isinstance(aux_states, (list, tuple)):
        aux_names = sym_.list_auxiliary_states()
        aux_states = {k: v for k, v in zip(aux_names, aux_states)}
    return {k: nd.array(v, ctx=ctx) if not isinstance(v, NDArray) else v
            for k, v in aux_states.items()}


def numeric_grad(executor, location, aux_states=None, eps=1e-4,
                 use_forward_train=True):
    """Finite-difference gradients via central differences."""
    approx_grads = {k: _np.zeros(v.shape, dtype=_np.float32)
                    for k, v in location.items()}
    for k, v in location.items():
        executor.arg_dict[k][:] = v
    for k in location:
        old_value = location[k].copy()
        for i in range(int(_np.prod(old_value.shape))):
            idx = _np.unravel_index(i, old_value.shape)
            # forward perturbed +eps
            loc_p = old_value.copy()
            loc_p[idx] += eps
            executor.arg_dict[k][:] = loc_p
            f_peps = executor.forward(is_train=use_forward_train)[0].asnumpy().sum()
            loc_m = old_value.copy()
            loc_m[idx] -= eps
            executor.arg_dict[k][:] = loc_m
            f_meps = executor.forward(is_train=use_forward_train)[0].asnumpy().sum()
            approx_grads[k][idx] = (f_peps - f_meps) / (2 * eps)
        executor.arg_dict[k][:] = old_value
    return approx_grads


def check_numeric_gradient(sym_, location, aux_states=None,
                           numeric_eps=1e-3, rtol=1e-2, atol=None,
                           grad_nodes=None, use_forward_train=True, ctx=None,
                           grad_stype_dict=None, dtype=_np.float64):
    """Finite-difference gradient checking (parity: test_utils.py:789).

    Note: runs in float32 (TPU-native default); tolerances follow the
    reference's float32-path defaults.
    """
    ctx = ctx or default_context()
    location = _parse_location(sym_, location, ctx=ctx)
    location_np = {k: v.asnumpy() for k, v in location.items()}
    aux = _parse_aux_states(sym_, aux_states, ctx)

    if grad_nodes is None:
        grad_nodes = [k for k in sym_.list_arguments()]
    elif isinstance(grad_nodes, dict):
        grad_nodes = list(grad_nodes.keys())

    # random projection to scalar so we check d(proj.out)/d(arg)
    out = sym_
    proj_shape = sym_.infer_shape(
        **{k: v.shape for k, v in location_np.items()})[1][0]
    proj = _np.random.uniform(-1, 1, size=proj_shape).astype(_np.float32)

    grad_req = {k: ("write" if k in grad_nodes else "null")
                for k in sym_.list_arguments()}
    exe = sym_.bind(ctx, args=location,
                    args_grad={k: nd.zeros(location[k].shape, ctx=ctx)
                               for k in grad_nodes},
                    grad_req=grad_req, aux_states=aux)
    exe.forward(is_train=True)
    exe.backward(out_grads=[nd.array(proj, ctx=ctx)])
    symbolic_grads = {k: exe.grad_dict[k].asnumpy() for k in grad_nodes}

    # numeric: perturb each entry, objective = sum(out * proj)
    fwd_exe = sym_.bind(ctx, args={k: v.copy() for k, v in location.items()},
                        aux_states={k: v.copy() for k, v in aux.items()})

    def objective():
        return float((fwd_exe.forward(
            is_train=use_forward_train)[0].asnumpy() * proj).sum())

    for name in grad_nodes:
        base = location_np[name].astype(_np.float64)
        approx = _np.zeros_like(base)
        it = _np.nditer(base, flags=["multi_index"])
        while not it.finished:
            idx = it.multi_index
            pert = base.copy()
            pert[idx] += numeric_eps
            fwd_exe.arg_dict[name][:] = pert.astype(_np.float32)
            fp = objective()
            pert[idx] -= 2 * numeric_eps
            fwd_exe.arg_dict[name][:] = pert.astype(_np.float32)
            fm = objective()
            approx[idx] = (fp - fm) / (2 * numeric_eps)
            it.iternext()
        fwd_exe.arg_dict[name][:] = base.astype(_np.float32)
        assert_almost_equal(approx, symbolic_grads[name], rtol,
                            atol if atol is not None else 1e-4,
                            (f"NUMERICAL_{name}", f"BACKWARD_{name}"))


def check_symbolic_forward(sym_, location, expected, rtol=1e-4, atol=None,
                           aux_states=None, ctx=None, equal_nan=False,
                           dtype=None):
    """Parity: test_utils.py:921."""
    ctx = ctx or default_context()
    location = _parse_location(sym_, location, ctx=ctx, dtype=dtype)
    aux = _parse_aux_states(sym_, aux_states, ctx)
    if isinstance(expected, dict):
        expected = [expected[k] for k in sym_.list_outputs()]
    exe = sym_.bind(ctx, args=location, aux_states=aux)
    outputs = exe.forward(is_train=False)
    for output_name, expect, output in zip(sym_.list_outputs(), expected,
                                           outputs):
        assert_almost_equal(expect, output.asnumpy(), rtol, atol or 1e-5,
                            ("EXPECTED_%s" % output_name,
                             "FORWARD_%s" % output_name),
                            equal_nan=equal_nan)
    return [o.asnumpy() for o in outputs]


def check_symbolic_backward(sym_, location, out_grads, expected, rtol=1e-5,
                            atol=None, aux_states=None, grad_req="write",
                            ctx=None, grad_stypes=None, equal_nan=False,
                            dtype=None):
    """Parity: test_utils.py:995."""
    ctx = ctx or default_context()
    location = _parse_location(sym_, location, ctx=ctx, dtype=dtype)
    aux = _parse_aux_states(sym_, aux_states, ctx)
    if isinstance(expected, (list, tuple)):
        expected = {k: v for k, v in zip(sym_.list_arguments(), expected)}
    if isinstance(grad_req, str):
        grad_req = {k: grad_req for k in sym_.list_arguments()}
    elif isinstance(grad_req, (list, tuple)):
        grad_req = {k: v for k, v in zip(sym_.list_arguments(), grad_req)}
    args_grad = {k: nd.zeros(location[k].shape, ctx=ctx)
                 for k in expected if grad_req.get(k, "null") != "null"}
    # 'add' semantics: preload random values
    adds = {}
    for k, req in grad_req.items():
        if req == "add" and k in args_grad:
            adds[k] = _np.random.normal(
                size=location[k].shape).astype(_np.float32)
            args_grad[k][:] = adds[k]
    exe = sym_.bind(ctx, args=location, args_grad=args_grad,
                    grad_req=grad_req, aux_states=aux)
    exe.forward(is_train=True)
    if isinstance(out_grads, (tuple, list)):
        out_grads = [nd.array(v, ctx=ctx) if not isinstance(v, NDArray) else v
                     for v in out_grads]
    elif isinstance(out_grads, dict):
        out_grads = [nd.array(out_grads[k], ctx=ctx)
                     for k in sym_.list_outputs()]
    exe.backward(out_grads)
    grads = {k: v.asnumpy() for k, v in exe.grad_dict.items()}
    for name in expected:
        if grad_req.get(name, "null") == "write":
            assert_almost_equal(expected[name], grads[name], rtol,
                                atol or 1e-6,
                                (f"EXPECTED_{name}", f"BACKWARD_{name}"),
                                equal_nan=equal_nan)
        elif grad_req.get(name) == "add":
            assert_almost_equal(expected[name] + adds[name],
                                grads[name], rtol, atol or 1e-6,
                                (f"EXPECTED_{name}", f"BACKWARD_{name}"),
                                equal_nan=equal_nan)
    return grads


def check_consistency(sym_, ctx_list, scale=1.0, grad_req="write",
                      arg_params=None, aux_params=None, tol=None,
                      raise_on_err=True, ground_truth=None, equal_nan=False,
                      report=None):
    """Cross-backend equivalence (parity: test_utils.py:1203 — the reference
    compared cpu vs gpu; here cpu vs tpu/accelerator ctx lists)."""
    tol = tol or {_np.dtype(_np.float16): 1e-1, _np.dtype(_np.float32): 1e-3,
                  _np.dtype(_np.float64): 1e-5, _np.dtype(_np.uint8): 0,
                  _np.dtype(_np.int32): 0}
    if isinstance(tol, float):
        tol = {_np.dtype(d): tol for d in
               (_np.float16, _np.float32, _np.float64, _np.uint8, _np.int32)}
    assert len(ctx_list) > 1
    if isinstance(sym_, sym.Symbol):
        sym_ = [sym_] * len(ctx_list)

    output_points = []
    for s, ctx in zip(sym_, ctx_list):
        ctx_spec = dict(ctx)
        context = ctx_spec.pop("ctx")
        type_dict = ctx_spec.pop("type_dict", {})
        exe = s.simple_bind(context, grad_req=grad_req, type_dict=type_dict,
                            **ctx_spec)
        if arg_params:
            for k, v in arg_params.items():
                exe.arg_dict[k][:] = v
        else:
            if not output_points:
                for name, arr in exe.arg_dict.items():
                    arr[:] = _np.random.normal(
                        size=arr.shape, scale=scale).astype(_np.float32)
                arg_params = {k: v.asnumpy() for k, v in exe.arg_dict.items()}
            else:
                for k, v in arg_params.items():
                    exe.arg_dict[k][:] = v
        if aux_params:
            for k, v in aux_params.items():
                exe.aux_dict[k][:] = v
        exe.forward(is_train=grad_req != "null")
        output_points.append([o.asnumpy() for o in exe.outputs])

    dtypes = [o.dtype for o in output_points[0]]
    gt = ground_truth or output_points[0]
    for i, outs in enumerate(output_points[1:], 1):
        for j, (g, o) in enumerate(zip(gt, outs)):
            # kind 'f' misses ml_dtypes floats (bfloat16 is kind 'V') —
            # exactly the dtypes the TPU consistency tier audits
            if report is not None and (g.dtype.kind == "f"
                                       or "float" in g.dtype.name):
                report["max_err"] = max(
                    report.get("max_err", 0.0),
                    float(_np.max(_np.abs(_np.asarray(g, _np.float64) -
                                          _np.asarray(o, _np.float64)))))
            try:
                assert_almost_equal(g, o, rtol=tol[_np.dtype(dtypes[j])],
                                    atol=tol[_np.dtype(dtypes[j])],
                                    equal_nan=equal_nan)
            except AssertionError:
                if raise_on_err:
                    raise
    return gt


def discard_stderr(*args, **kwargs):
    import contextlib
    import io
    return contextlib.redirect_stderr(io.StringIO())


def list_gpus():
    from .context import num_gpus
    return list(range(num_gpus()))


def download(url, fname=None, dirname=None, overwrite=False):
    from .gluon.utils import download as _dl
    return _dl(url, fname or dirname, overwrite)


def get_mnist(num_train=600, num_test=100):
    """Synthetic MNIST-shaped dataset when real files are unavailable
    (zero-egress environments).  LEARNABLE: each class is a fixed smooth
    prototype image plus noise, so classifiers trained on it reach high
    accuracy and demos (adversarial examples, multi-task, fine-tuning)
    behave like they do on the real data."""
    rs = _np.random.RandomState(42)
    # smooth per-class prototypes (low-freq random fields, blurred)
    protos = rs.rand(10, 1, 32, 32).astype(_np.float32)
    k = _np.ones(5, _np.float32) / 5.0  # separable box blur
    blurred = []
    for p in protos:
        img = p[0]
        for _ in range(2):
            img = _np.stack([
                _np.convolve(row, k, mode="same") for row in img])
            img = _np.stack([
                _np.convolve(col, k, mode="same") for col in img.T]).T
        blurred.append(img[2:30, 2:30])
    protos = _np.stack(blurred)[:, None]          # (10,1,28,28)
    protos = (protos - protos.min()) / (_np.ptp(protos) + 1e-9)

    def make(n):
        y = rs.randint(0, 10, n)
        x = protos[y] + rs.normal(0, 0.25, (n, 1, 28, 28))
        return x.clip(0, 1).astype(_np.float32), y.astype(_np.float32)

    train_x, train_y = make(num_train)
    test_x, test_y = make(num_test)
    return {"train_data": train_x, "train_label": train_y,
            "test_data": test_x, "test_label": test_y}


# ---------------------------------------------------------------------------
# Golden-logit zoo fixtures (VERDICT r3 #2; parity:
# tests/python/gpu/test_forward.py — committed expected logits pin the
# model zoo against silent numeric drift).  Params and inputs are
# regenerated deterministically from fixed seeds (jax PRNG + numpy
# RandomState), so the committed .npz holds only the tiny logits block.
# ---------------------------------------------------------------------------
def golden_model_cases():
    """name -> zero-arg builder returning (net, input NDArray).  Shared by
    tools/make_golden.py (writer), tests/test_golden_forward.py (CPU
    gate) and tests_tpu/test_consistency.py (on-chip check)."""
    from . import nd as _nd
    from . import random as _random
    from . import initializer as _init
    from .gluon.model_zoo import vision as _vision
    from .gluon.model_zoo.transformer import TransformerLM as _TLM

    def _vision_case(factory, shape=(2, 3, 64, 64)):
        def build():
            _random.seed(0)
            net = factory()
            net.initialize(_init.Xavier(rnd_type="gaussian",
                                        factor_type="in", magnitude=2))
            rs = _np.random.RandomState(42)
            x = _nd.array(rs.normal(0, 1, shape).astype(_np.float32))
            return net, x
        return build

    def _lm_case():
        def build():
            _random.seed(0)
            net = _TLM(vocab=32, dim=32, num_layers=2, num_heads=4,
                       max_len=16)
            net.initialize(_init.Xavier(rnd_type="gaussian",
                                        factor_type="in", magnitude=2))
            rs = _np.random.RandomState(42)
            x = _nd.array(rs.randint(0, 32, (2, 16)).astype(_np.float32))
            return net, x
        return build

    return {
        "resnet18_v1": _vision_case(_vision.resnet18_v1),
        "resnet18_v2": _vision_case(_vision.resnet18_v2),
        "mobilenet0_25": _vision_case(_vision.mobilenet0_25),
        "squeezenet1_0": _vision_case(_vision.squeezenet1_0),
        # densenet's final AvgPool2D(7) assumes the 224 input contract
        "densenet121": _vision_case(_vision.densenet121,
                                    shape=(1, 3, 224, 224)),
        # inception's branchy concat tree is the whole-graph NHWC
        # pass's hardest shape (channel-axis Concat stays CL); 299 is
        # its input contract
        "inception_v3": _vision_case(_vision.inception_v3,
                                     shape=(1, 3, 299, 299)),
        "alexnet": _vision_case(_vision.alexnet,
                                shape=(2, 3, 224, 224)),
        "transformer_lm": _lm_case(),
    }


def golden_forward(name):
    """Deterministic logits for one golden case (inference mode)."""
    net, x = golden_model_cases()[name]()
    out = net(x)
    return _np.asarray(out.asnumpy(), _np.float32)


def golden_fixture_path(name):
    import os as _os
    return _os.path.join(_os.path.dirname(_os.path.dirname(
        _os.path.abspath(__file__))), "tests", "golden",
        f"{name}.npz")


# -- reference test_utils closure (round-4 API audit) -----------------------

def rand_sparse_ndarray(shape, stype, density=None, dtype=None,
                        distribution=None, data_init=None,
                        rsp_indices=None, modifier_func=None,
                        shuffle_csr_indices=False):
    """Random sparse NDArray (parity: test_utils.rand_sparse_ndarray —
    returns (arr, aux) with aux = (vals, idx) for rsp, (data, indices,
    indptr) for csr).  distribution: 'uniform' (default) or 'powerlaw'
    (csr only — geometrically decaying per-row nnz, the reference's
    skewed-structure generator)."""
    density = 0.1 if density is None else density
    dtype = dtype or "float32"
    if distribution not in (None, "uniform", "powerlaw"):
        raise MXNetError(f"unsupported distribution {distribution!r}")
    from .ndarray import sparse
    if stype == "row_sparse":
        if distribution == "powerlaw":
            raise MXNetError("powerlaw distribution is csr-only")
        if rsp_indices is not None:
            idx = _np.asarray(rsp_indices, _np.int64)
        else:
            n = max(1, int(round(shape[0] * density)))
            idx = _np.sort(_np.random.choice(shape[0], n, replace=False))
        vals = _np.random.randn(len(idx), *shape[1:]).astype(dtype)
        if data_init is not None:
            vals[:] = data_init
        if modifier_func is not None and vals.size:
            vals = _np.vectorize(modifier_func)(vals).astype(dtype)
        arr = sparse.row_sparse_array((vals, idx), shape=shape, dtype=dtype)
        return arr, (vals, idx)
    if stype == "csr":
        if distribution == "powerlaw":
            # Reference semantics (test_utils.py:164-210): exponentially
            # INCREASING per-row occupancy — every row is first seeded at
            # column 0 (so no row is empty), then row i fills columns
            # 1..min(2^(i+1), ncols) until the nnz budget is spent;
            # values are 1 + U(0.001, 2).  Requires nnz >= 2*nrows.
            total = int(shape[0] * shape[1] * density)
            if total < 2 * shape[0]:
                raise MXNetError(
                    "powerlaw not supported for density %s at shape %s: "
                    "needs nrows*ncols*density >= 2*nrows"
                    % (density, (shape[0], shape[1])))
            dense = _np.zeros(shape, dtype)
            unused = total

            def _vals(n):
                return (1 + _np.random.uniform(0.001, 2, n)).astype(dtype)

            for i in range(shape[0]):
                if unused <= 0:
                    break
                dense[i, 0] = _vals(1)[0]
                unused -= 1
            col_max = 2
            for i in range(shape[0]):
                if unused <= 0:
                    break
                col_limit = min(shape[1], col_max)
                if col_limit == shape[1] and unused > col_limit:
                    dense[i, 1:] = _vals(shape[1] - 1)
                    unused -= col_limit - 1
                    continue
                n = min(col_limit - 1, unused)
                dense[i, 1:1 + n] = _vals(n)
                unused -= n
                col_max *= 2
            if unused > 0:
                raise MXNetError(
                    "powerlaw not supported for density %s at shape %s"
                    % (density, (shape[0], shape[1])))
        else:
            dense = _np.random.randn(*shape).astype(dtype)
            dense *= _np.random.rand(*shape) < density
        if data_init is not None:
            dense[dense != 0] = data_init
        if modifier_func is not None:
            nz = dense != 0
            if nz.any():
                dense[nz] = _np.vectorize(modifier_func)(dense[nz])
        arr = sparse.csr_matrix(nd.array(dense.astype(dtype)))
        if shuffle_csr_indices:
            arr = shuffle_csr_column_indices(arr)
        return arr, (arr.data.asnumpy(), arr.indices.asnumpy(),
                     arr.indptr.asnumpy())
    raise MXNetError(f"unknown storage type {stype}")


def create_sparse_array(shape, stype, data_init=None, rsp_indices=None,
                        dtype=None, modifier_func=None, density=0.5,
                        shuffle_csr_indices=False):
    """Parity: test_utils.create_sparse_array."""
    arr, _ = rand_sparse_ndarray(shape, stype, density=density, dtype=dtype,
                                 data_init=data_init,
                                 rsp_indices=rsp_indices,
                                 modifier_func=modifier_func,
                                 shuffle_csr_indices=shuffle_csr_indices)
    return arr


def create_sparse_array_zd(shape, stype, density, data_init=None,
                           rsp_indices=None, dtype=None,
                           modifier_func=None, shuffle_csr_indices=False):
    """Sparse array generator admitting zero-density (parity:
    test_utils.create_sparse_array_zd)."""
    if stype == "row_sparse" and density == 0:
        rsp_indices = _np.array([], _np.int64)
    return create_sparse_array(shape, stype, data_init=data_init,
                               rsp_indices=rsp_indices, dtype=dtype,
                               modifier_func=modifier_func,
                               density=density,
                               shuffle_csr_indices=shuffle_csr_indices)


def shuffle_csr_column_indices(csr):
    """Permute column order within each CSR row (parity: tests feed
    unsorted-column CSRs to check kernels don't assume sorted cols)."""
    from .ndarray.sparse import CSRNDArray
    indptr = _np.asarray(csr.indptr.asnumpy())
    cols = _np.array(csr.indices.asnumpy())
    vals = _np.array(csr.data.asnumpy())
    for i in range(len(indptr) - 1):
        s, e = indptr[i], indptr[i + 1]
        p = _np.random.permutation(e - s)
        cols[s:e] = cols[s:e][p]
        vals[s:e] = vals[s:e][p]
    return CSRNDArray(vals, indptr, cols, csr.shape)


def almost_equal_ignore_nan(a, b, rtol=None, atol=None):
    """Parity: test_utils.almost_equal_ignore_nan — drop positions where
    EITHER side is NaN, compare the rest."""
    a = _np.copy(a)
    b = _np.copy(b)
    nan_mask = _np.logical_or(_np.isnan(a), _np.isnan(b))
    a[nan_mask] = 0
    b[nan_mask] = 0
    return almost_equal(a, b, rtol, atol)


def assert_almost_equal_ignore_nan(a, b, rtol=None, atol=None,
                                   names=("a", "b")):
    a = _np.copy(a)
    b = _np.copy(b)
    nan_mask = _np.logical_or(_np.isnan(a), _np.isnan(b))
    a[nan_mask] = 0
    b[nan_mask] = 0
    assert_almost_equal(a, b, rtol, atol, names)


def same_array(array1, array2):
    """Whether two NDArrays share the same backing buffer (parity:
    test_utils.same_array's aliasing probe — functional buffers make
    identity the sharing criterion).  Sparse arrays rebuild their dense
    view per access, so only object identity can witness sharing."""
    if array1 is array2:
        return True
    if array1.shape != array2.shape:
        return False
    if array1.stype != "default" or array2.stype != "default":
        return False
    return array1._data is array2._data


def assign_each(the_input, function):
    """Elementwise python function application (parity: assign_each)."""
    arr = _np.array(the_input.asnumpy() if hasattr(the_input, "asnumpy")
                    else the_input)
    out = _np.vectorize(function)(arr) if function is not None else arr
    return nd.array(out.astype(arr.dtype))


def assign_each2(input1, input2, function):
    a = _np.array(input1.asnumpy() if hasattr(input1, "asnumpy")
                  else input1)
    b = _np.array(input2.asnumpy() if hasattr(input2, "asnumpy")
                  else input2)
    out = _np.vectorize(function)(a, b) if function is not None else a
    return nd.array(out.astype(a.dtype))


class DummyIter(io.DataIter):
    """Infinite repetition of the first batch of a real iterator —
    removes IO cost from op benchmarks (parity: test_utils.DummyIter,
    a DataIter so reset()-calling training loops work)."""

    def __init__(self, real_iter):
        super().__init__(real_iter.batch_size)
        self.real_iter = real_iter
        self._provide_data = real_iter.provide_data
        self._provide_label = real_iter.provide_label
        self.the_batch = next(iter(real_iter))

    @property
    def provide_data(self):
        return self._provide_data

    @property
    def provide_label(self):
        return self._provide_label

    def next(self):
        return self.the_batch


def check_speed(sym_, location=None, ctx=None, N=20, grad_req=None,
                typ="whole", **kwargs):
    """Mean seconds/iteration of forward(+backward) on a bound executor
    (parity: test_utils.check_speed)."""
    import time
    ctx = ctx or default_context()
    if typ not in ("whole", "forward"):
        raise MXNetError(f"typ must be 'whole' or 'forward', got {typ!r}")
    if grad_req is None:
        grad_req = "write" if typ == "whole" else "null"
    if location is None:
        shapes, _, _ = sym_.infer_shape(**kwargs)
        location = {k: _np.random.normal(0, 1, s).astype("float32")
                    for k, s in zip(sym_.list_arguments(), shapes)}
    exe = sym_.simple_bind(ctx=ctx, grad_req=grad_req,
                           **{k: v.shape for k, v in location.items()})
    for k, v in location.items():
        exe.arg_dict[k][:] = v
    # warmup (compile) then timed loop with one end sync
    if typ == "whole":
        exe.forward(is_train=True)
        exe.backward(out_grads=exe.outputs)
        exe.outputs[0].wait_to_read()
        tic = time.time()
        for _ in range(N):
            exe.forward(is_train=True)
            exe.backward(out_grads=exe.outputs)
        _np.asarray(exe.outputs[0].asnumpy())
        return (time.time() - tic) / N
    exe.forward(is_train=False)
    exe.outputs[0].wait_to_read()
    tic = time.time()
    for _ in range(N):
        exe.forward(is_train=False)
    _np.asarray(exe.outputs[0].asnumpy())
    return (time.time() - tic) / N


def get_bz2_data(data_dir, data_name, url, data_origin_name):
    """Fetch+decompress a .bz2 dataset (parity: test_utils.get_bz2_data;
    on an egress-less pod an already-present archive is decompressed
    without network)."""
    import bz2
    import os
    path = os.path.join(data_dir, data_name)
    origin = os.path.join(data_dir, data_origin_name)
    if os.path.exists(path):
        return path
    if not os.path.exists(origin):
        download(url, fname=origin)
    # decompress to a same-dir tmp, then one os.replace: a crash
    # mid-decompress must not leave a torn file that the
    # os.path.exists fast path above would trust forever after
    tmp = f"{path}.tmp-{os.getpid()}"
    with bz2.BZ2File(origin, "rb") as src, open(tmp, "wb") as dst:
        dst.write(src.read())
    os.replace(tmp, path)
    return path


def set_env_var(key, val, default_val=""):
    """Set an env var, returning the previous value (parity:
    test_utils.set_env_var)."""
    import os
    prev = os.environ.get(key, default_val)
    if val is None:
        os.environ.pop(key, None)
    else:
        os.environ[key] = str(val)
    return prev


def retry(n):
    """Decorator: re-run a flaky test up to n times on assertion failure
    (parity: test_utils.retry)."""
    assert n > 0

    def decorate(f):
        import functools

        @functools.wraps(f)
        def wrapper(*args, **kwargs):
            for i in range(n):
                try:
                    return f(*args, **kwargs)
                except AssertionError:
                    if i == n - 1:
                        raise
            return None
        return wrapper
    return decorate


def check_resnet_dp_equivalence(ctxs, rs=None, batch=None):
    """BN-under-SPMD equivalence harness (VERDICT r4 #4), shared by
    tests/test_parallel.py and __graft_entry__._dryrun_resnet_dp so the
    driver dryrun and the CI test cannot drift.

    Builds a tiny-image ResNet-18 (real BatchNorm in every block) +
    SoftmaxOutput Module with KVStore('tpu_sync') and the fused
    multi-precision momentum optimizer, runs ONE forward_backward on the
    `ctxs` mesh and on a single device from identical init, and asserts
    grads and BN running stats agree tightly: under the SPMD executor
    the batch mean/var are computed over the GLOBAL batch, so a
    per-shard-statistics bug shows up as O(0.1) error while legitimate
    all-reduce summation-order noise is ~1e-4.
    (Reference harness: tests/nightly/dist_device_sync_kvstore.py:33-60.)

    Returns (build, X, Y): the module factory + dataset, so callers can
    run their own training-level checks on top (e.g. a multi-epoch fit).
    """
    from . import context as _ctx_mod  # noqa: F401  (mx.* below)
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision

    rs = rs or _np.random.RandomState(3)
    n = len(ctxs) if isinstance(ctxs, (list, tuple)) else 1
    B = batch or 2 * n
    X = rs.normal(0, 1, (2 * B, 3, 8, 8)).astype(_np.float32)
    Y = rs.randint(0, 4, 2 * B).astype(_np.float32)
    X[:, :, :4, :4] += (Y - 1.5)[:, None, None, None]  # learnable signal

    def build(cs):
        net = vision.resnet18_v1(classes=4, thumbnail=True,
                                 prefix="rn_")  # stable names across builds
        out = mx.sym.SoftmaxOutput(net(mx.sym.Variable("data")),
                                   name="softmax")
        it = mx.io.NDArrayIter(X, Y, batch_size=B)
        mod = mx.mod.Module(out, context=cs)
        mod.bind(data_shapes=it.provide_data,
                 label_shapes=it.provide_label)
        mx.random.seed(11)  # identical init across builds
        mod.init_params(mx.init.Xavier(rnd_type="gaussian", magnitude=2))
        mod.init_optimizer(kvstore="tpu_sync", optimizer="sgd",
                           optimizer_params={"learning_rate": 0.05,
                                             "momentum": 0.9, "wd": 1e-4,
                                             "multi_precision": True})
        return mod, it

    def one_step(cs):
        mod, it = build(cs)
        it.reset()
        mod.forward_backward(next(iter(it)))
        grads = {k: v.asnumpy() for k, v in mod._exec.grad_dict.items()}
        _, aux = mod.get_params()
        return grads, {k: v.asnumpy() for k, v in aux.items()}

    g_mesh, x_mesh = one_step(ctxs)
    g_one, x_one = one_step(ctxs[0] if isinstance(ctxs, (list, tuple))
                            else ctxs)
    assert set(g_mesh) == set(g_one) and set(x_mesh) == set(x_one)
    for k in g_mesh:
        _np.testing.assert_allclose(g_mesh[k], g_one[k],
                                    rtol=1e-2, atol=2e-3, err_msg=k)
    for k in x_mesh:  # global-batch BN stats, not shard stats
        _np.testing.assert_allclose(x_mesh[k], x_one[k],
                                    rtol=1e-3, atol=1e-4, err_msg=k)
    return build, X, Y
