"""Host-side framework overhead per fit() step, isolated from compute.

The r04 window attributed ~4-5 ms of the ~30 ms/step framework-vs-raw
gap to the 3-programs/step structure (chained dispatches pipelined at
~1.8 ms/call through the round-4 setup).  The rest is either device time
or HOST time between dispatches — this harness measures the host part
with a model so tiny that compute is negligible:

  raw:  the same 3-program chain (fwd+bwd, update, metric) issued as
        bare jax calls in a python loop — the dispatch floor
  fit:  Module.fit with on-device metric — the product path

ms/step(fit) - ms/step(raw) = framework tax per step (NDArray wrapping,
arg gathering, kvstore bookkeeping, callback/metric plumbing).  The tax
adds directly to step time whenever it exceeds the device step's slack.

    python experiments/step_overhead.py [N=300] [B=8]
"""
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import mxnet_tpu as mx

N = int(os.environ.get("N", 300))
B = int(os.environ.get("B", 8))
H = 32


def build_module():
    net = mx.sym.Variable("data")
    net = mx.sym.Activation(mx.sym.FullyConnected(net, num_hidden=H,
                                                  name="fc1"),
                            act_type="relu")
    net = mx.sym.SoftmaxOutput(mx.sym.FullyConnected(net, num_hidden=4,
                                                     name="fc2"),
                               name="softmax")
    mod = mx.mod.Module(net)
    mod.bind(data_shapes=[("data", (B, 8))],
             label_shapes=[("softmax_label", (B,))])
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(kvstore="tpu_sync", optimizer="sgd",
                       optimizer_params={"learning_rate": 0.01,
                                         "momentum": 0.9})
    return mod


def sync(x):
    float(np.asarray(x if not hasattr(x, "asnumpy") else x.asnumpy()
                     ).ravel()[0])


def measure_fit(mod):
    rs = np.random.RandomState(0)
    x = mx.nd.array(rs.normal(0, 1, (B, 8)).astype("f"))
    y = mx.nd.array(rs.randint(0, 4, B).astype("f"))
    batch = mx.io.DataBatch([x], [y], pad=0, index=None)

    import jax
    import jax.numpy as jnp
    nll = jax.jit(lambda p, l: -jnp.log(
        jnp.take_along_axis(p, l.astype(jnp.int32)[:, None],
                            axis=1) + 1e-8).mean())

    vals = []
    for _ in range(20):  # warm: compile all three programs
        mod.forward_backward(batch)
        mod.update()
        vals.append(nll(mod.get_outputs()[0]._data, y._data))
    sync(vals[-1])

    t0 = time.perf_counter()
    for _ in range(N):
        mod.forward_backward(batch)
        mod.update()
        vals.append(nll(mod.get_outputs()[0]._data, y._data))
    sync(vals[-1])
    sync(next(iter(mod._exec.arg_dict.values())))
    return (time.perf_counter() - t0) / N * 1e3


def measure_raw(mod):
    """The identical program sequence as bare jax calls."""
    import jax
    import jax.numpy as jnp
    ex = mod._exec
    fb = ex._fwd_bwd
    arg_vals = {k: v._data for k, v in ex.arg_dict.items()}
    aux_vals = {k: v._data for k, v in ex.aux_dict.items()}
    key = jax.random.PRNGKey(0)
    ograds = [None]
    upd = jax.jit(lambda params, grads, lr: jax.tree_util.tree_map(
        lambda p, g: p - lr * g, params, grads))
    nll = jax.jit(lambda p, l: -jnp.log(
        jnp.take_along_axis(p, l.astype(jnp.int32)[:, None],
                            axis=1) + 1e-8).mean())
    y = arg_vals["softmax_label"]

    grad_names = [n for n in ex._grad_names]
    for _ in range(20):
        outs, new_aux, grads, _ = fb(arg_vals, aux_vals, key, ograds)
        new_p = upd({k: arg_vals[k] for k in grad_names}, grads, 0.01)
        arg_vals.update(new_p)
        v = nll(outs[0], y)
    sync(v)

    t0 = time.perf_counter()
    for _ in range(N):
        outs, new_aux, grads, _ = fb(arg_vals, aux_vals, key, ograds)
        new_p = upd({k: arg_vals[k] for k in grad_names}, grads, 0.01)
        arg_vals.update(new_p)
        v = nll(outs[0], y)
    sync(v)
    return (time.perf_counter() - t0) / N * 1e3


def main():
    mod = build_module()
    raw = measure_raw(mod)
    fit = measure_fit(mod)
    print("raw 3-program chain: %.3f ms/step" % raw)
    print("framework step:      %.3f ms/step" % fit)
    print("framework tax:       %.3f ms/step" % (fit - raw))


if __name__ == "__main__":
    main()
