"""Plain reference for `resnet50_v1`: ResNet v1 with bottleneck blocks
(He et al., arXiv:1512.03385, Table 1) as the gluon model zoo builds it,
in straightforward `jax.numpy`, float32, matmul precision "highest", train
mode (batch statistics).  Imports nothing of `mxnet_tpu`.

Departures from the paper, shared with the program (see config.json):
each stage's stride sits in the bottleneck's first 1x1 convolution, and
the bottleneck's 1x1 convolutions carry a bias.

The interface every configuration's reference gives the benchmark:

  leaves(cfg)                 [(name, shape, kind)] of the trainable leaves,
                              in the order the program's own parameters have
  init_weights(seed, cfg, dtype)
                              {name: array} from the seed, one jitted call (the
                              program is loaded from the same call, in the
                              dtype it serves)
  make_batches(seed, n, batch, cfg, traffic)
                              (x, y): n distinct batches from the seed
  loss(params, x, y, cfg, q)  mean loss of one batch; `q`, when given, is the
                              lower-precision control's rounding: of both
                              operands of every convolution and matrix
                              product, and (here) of every activation a layer
                              hands on, as the program holds them in bfloat16
"""
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST


def _blocks(cfg):
    """(stage, block, in_channels, channels, stride, downsample)."""
    out = []
    chans = cfg["channels"]
    for s, n in enumerate(cfg["layers"]):
        cin, c = chans[s], chans[s + 1]
        for b in range(n):
            first = b == 0
            out.append((s + 1, b, cin if first else c, c,
                        (1 if s == 0 else 2) if first else 1,
                        first and c != cin))
    return out


def leaves(cfg):
    out = []

    def bn(prefix, c, gamma=None):
        kind = "gamma" if gamma is None else f"gamma:{gamma}"
        out.append((prefix + ".gamma", (c,), kind))
        out.append((prefix + ".beta", (c,), "beta"))

    c0 = cfg["channels"][0]
    out.append(("stem.conv.weight", (c0, 3, 7, 7), "conv"))
    bn("stem.bn", c0)
    for s, b, cin, c, _stride, down in _blocks(cfg):
        p = f"s{s}.b{b}"
        m = c // 4
        out.append((p + ".conv1.weight", (m, cin, 1, 1), "conv"))
        out.append((p + ".conv1.bias", (m,), "bias"))
        bn(p + ".bn1", m)
        out.append((p + ".conv2.weight", (m, m, 3, 3), "conv"))
        bn(p + ".bn2", m)
        out.append((p + ".conv3.weight", (c, m, 1, 1), "conv"))
        out.append((p + ".conv3.bias", (c,), "bias"))
        # the last scale of each residual branch starts at `last_gamma`
        bn(p + ".bn3", c, cfg.get("last_gamma"))
        if down:
            out.append((p + ".down.weight", (c, cin, 1, 1), "conv"))
            bn(p + ".downbn", c)
    out.append(("fc.weight", (cfg["classes"], cfg["channels"][-1]), "dense"))
    out.append(("fc.bias", (cfg["classes"],), "bias"))
    return out


def init_leaf(key, shape, kind):
    """One leaf from its key: He-normal weights, gamma 1 (or the value
    after the colon of its kind), the rest 0."""
    if kind in ("conv", "dense"):
        fan_in = math.prod(shape[1:])
        return jax.random.normal(key, shape, jnp.float32) * \
            math.sqrt(2.0 / fan_in)
    if kind.startswith("gamma"):
        value = float(kind.split(":")[1]) if ":" in kind else 1.0
        return jnp.full(shape, value, jnp.float32)
    return jnp.zeros(shape, jnp.float32)


def leaf_key(seed, i):
    return jax.random.fold_in(jax.random.key(seed), i)


def init_weights(seed, cfg, dtype=jnp.float32):
    """Every leaf from the seed, in one jitted call, in `dtype`."""
    spec = leaves(cfg)

    @jax.jit
    def make(seed_):
        return {name: init_leaf(leaf_key(seed_, i), shape, kind).astype(dtype)
                for i, (name, shape, kind) in enumerate(spec)}

    return make(jnp.uint32(seed % (2 ** 31)))


def make_batches(seed, n, batch, cfg, traffic):
    """n batches of images (n, B, 3, S, S) float32 and labels (n, B).
    Each image is a smooth pattern of its own (four low spatial
    frequencies with random per-channel weights) plus noise: images that
    are iid noise all look alike to a deep network, and batch
    normalisation then amplifies rounding instead of signal."""
    size, classes = cfg["image_size"], cfg["classes"]

    @jax.jit
    def make(seed_):
        key = jax.random.fold_in(jax.random.key(seed_), 2 ** 20)
        kl, kc, kf, kp, kn = jax.random.split(key, 5)
        nb, k = n * batch, 4
        labels = jax.random.randint(kl, (nb,), 0, classes)
        coef = jax.random.normal(kc, (nb, 3, k), jnp.float32)
        freq = jax.random.uniform(kf, (nb, k, 2), jnp.float32, 0.0, 4.0)
        phase = jax.random.uniform(kp, (nb, k), jnp.float32, 0.0, 2 * jnp.pi)
        u = jnp.arange(size, dtype=jnp.float32) / size
        arg = 2 * jnp.pi * (freq[:, :, 0, None, None] * u[None, None, :, None]
                            + freq[:, :, 1, None, None] * u[None, None, None, :]) \
            + phase[:, :, None, None]
        pattern = jnp.einsum("bck,bkhw->bchw", coef, jnp.cos(arg))
        noise = jax.random.normal(kn, (nb, 3, size, size), jnp.float32)
        x = pattern + 0.5 * noise
        return (x.reshape(n, batch, 3, size, size),
                labels.reshape(n, batch))

    return make(jnp.uint32(seed % (2 ** 31)))


def _conv(x, w, stride, pad, q):
    if q is not None:
        x, w = q(x), q(w)
    return lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"), precision=HI)


def _bn(x, gamma, beta, eps):
    mean = jnp.mean(x, axis=(0, 2, 3), keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=(0, 2, 3), keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * gamma[None, :, None, None] \
        + beta[None, :, None, None]


def _bias(x, b):
    return x + b[None, :, None, None]


def _held(x, q):
    """An activation as a layer hands it on: in the control, rounded."""
    return x if q is None else q(x)


def _bottleneck(p, x, stride, down, eps, q):
    h = _held(_bias(_conv(x, p["conv1.weight"], stride, 0, q),
                    p["conv1.bias"]), q)
    h = _held(jax.nn.relu(_bn(h, p["bn1.gamma"], p["bn1.beta"], eps)), q)
    h = _held(_conv(h, p["conv2.weight"], 1, 1, q), q)
    h = _held(jax.nn.relu(_bn(h, p["bn2.gamma"], p["bn2.beta"], eps)), q)
    h = _held(_bias(_conv(h, p["conv3.weight"], 1, 0, q), p["conv3.bias"]), q)
    h = _held(_bn(h, p["bn3.gamma"], p["bn3.beta"], eps), q)
    if down:
        x = _held(_conv(x, p["down.weight"], stride, 0, q), q)
        x = _held(_bn(x, p["downbn.gamma"], p["downbn.beta"], eps), q)
    return _held(jax.nn.relu(h + x), q)


def logits(params, x, cfg, q=None):
    eps = cfg["bn_eps"]
    x = _held(_conv(x.astype(jnp.float32), params["stem.conv.weight"], 2, 3,
                    q), q)
    x = _held(jax.nn.relu(_bn(x, params["stem.bn.gamma"],
                              params["stem.bn.beta"], eps)), q)
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
                          [(0, 0), (0, 0), (1, 1), (1, 1)])
    for s, b, _cin, _c, stride, down in _blocks(cfg):
        pre = f"s{s}.b{b}."
        sub = {k[len(pre):]: v for k, v in params.items()
               if k.startswith(pre)}
        # one block's activations live at a time in the backward pass
        block = jax.checkpoint(functools.partial(
            _bottleneck, stride=stride, down=down, eps=eps, q=q))
        x = block(sub, x)
    x = jnp.mean(x, axis=(2, 3))
    w, bias = params["fc.weight"], params["fc.bias"]
    if q is not None:
        x, w = q(x), q(w)
    return jnp.dot(x, w.T, precision=HI) + bias


def loss(params, x, y, cfg, q=None):
    lg = logits(params, x, cfg, q)
    logp = jax.nn.log_softmax(lg, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None].astype(jnp.int32),
                                         axis=1))
