"""SGD with momentum over float32 master weights, as the configuration
states it (`mp_sgd_mom_update`): the plain rule for the reference, and how
the first gradient is read back from the program's state after one step.

  mom <- momentum * mom - lr * (g + wd * w)
  w   <- w + mom
"""
import jax.numpy as jnp


def init(params):
    return {k: jnp.zeros_like(v) for k, v in params.items()}


def update(params, grads, state, hp, t, wd_mask):
    new_p, new_s = {}, {}
    for k, w in params.items():
        wd = hp["wd"] if wd_mask[k] else 0.0
        m = hp["momentum"] * state[k] - hp["lr"] * (grads[k] + wd * w)
        new_s[k] = m
        new_p[k] = w + m
    return new_p, new_s


def split_state(state, weight):
    """The program's state of one leaf -> (momentum, float32 master): under
    `multi_precision` a low-precision weight's state is (momentum, master);
    a float32 weight is its own master."""
    if isinstance(state, (tuple, list)):
        return state[0], state[1]
    return state, weight


def first_grad(state, weight, hp, wd_on):
    """The gradient the optimizer was given in step 1 (float32), from the
    state after that step: mom1 = -lr (g + wd w0), w1 = w0 + mom1."""
    mom, w1 = split_state(state, weight)
    mom = mom.astype(jnp.float32)
    w0 = w1.astype(jnp.float32) - mom
    g = -mom / hp["lr"] - (hp["wd"] if wd_on else 0.0) * w0
    return g


def master(state, weight):
    return split_state(state, weight)[1]
