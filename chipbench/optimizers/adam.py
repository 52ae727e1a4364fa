"""Adam over float32 master weights, as the program's `adam_update` with
the bias correction folded into the rate: the plain rule for the
reference, and how the first gradient is read back from the program's
state after one step.

  g    <- g + wd * w
  m    <- b1 m + (1 - b1) g ;  v <- b2 v + (1 - b2) g^2
  w    <- w - lr sqrt(1 - b2^t) / (1 - b1^t) * m / (sqrt(v) + eps)
"""
import jax.numpy as jnp


def init(params):
    return {k: (jnp.zeros_like(v), jnp.zeros_like(v))
            for k, v in params.items()}


def update(params, grads, state, hp, t, wd_mask):
    b1, b2, eps = hp["beta1"], hp["beta2"], hp["epsilon"]
    lr_t = hp["lr"] * jnp.sqrt(1.0 - b2 ** t) / (1.0 - b1 ** t)
    new_p, new_s = {}, {}
    for k, w in params.items():
        g = grads[k] + (hp["wd"] if wd_mask[k] else 0.0) * w
        m, v = state[k]
        m = b1 * m + (1.0 - b1) * g
        v = b2 * v + (1.0 - b2) * jnp.square(g)
        new_s[k] = (m, v)
        new_p[k] = w - lr_t * m / (jnp.sqrt(v) + eps)
    return new_p, new_s


def split_state(state, weight):
    """The program's state of one leaf -> ((mean, var), float32 master):
    under `multi_precision` a low-precision weight's state is ((mean, var),
    master); a float32 weight is its own master."""
    if isinstance(state[0], (tuple, list)):
        return state[0], state[1]
    return state, weight


def first_grad(state, weight, hp, wd_on):
    """mean1 = (1 - b1) g1 (the traffic sets wd 0 for Adam, so g1 is the
    gradient as the optimizer got it)."""
    if wd_on and hp["wd"]:
        raise ValueError("adam: reading the first gradient back needs wd 0")
    (mean, _var), _w = split_state(state, weight)
    g = mean.astype(jnp.float32) / (1.0 - hp["beta1"])
    return g


def master(state, weight):
    return split_state(state, weight)[1]
