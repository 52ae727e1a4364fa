"""Check `train_steps`: what decides `correct` for a training cell.  A
traffic file names its check (`"check": "train_steps"`); run.py finds this
file by that name and calls `before(cell, entry)` in set-up and
`after(cell, taken)` once the window has closed and the program is freed.

The program's first three steps (driven in set-up through the window's own
call and feed) are followed by the configuration's plain reference from the
same seed, and seven numbers are compared, each against a limit of its own
(`chipbench/limits/<cell>.json`):

  loss1, loss2, loss3   |program - reference| / |reference| of each step's loss
  grad_norm_gap         the first gradient as the optimizer got it, read back
                        from its state after step 1: the gap between the
                        program's norm and the reference's, by the worst leaf,
                        against the reference's norm of that leaf or of the
                        median leaf, whichever is larger
  dparam_norm_gap       the same measure for the norm of the float32 master
                        weights' change after the three steps; leaves whose
                        reference gradient is under a thousandth of the median
                        leaf's are left out (they move by round-off alone)
  grad_norm_gap_median, dparam_norm_gap_median
                        the same per-leaf gaps, the median over the leaves in
                        place of the worst: steady from seed to seed where the
                        worst leaf (a BatchNorm scale of 64 numbers) is noise,
                        so it tells float8 from bfloat16 where the worst
                        leaf cannot (PERF.md, section 2)

The reference imports nothing of the program and takes nothing it made: it
makes the weights and batches again from the seed.  `precision="fp8"` is
the control (both operands of every convolution and matrix product rounded
to float8_e4m3, and their gradients to float8_e5m2, each with a per-tensor
scale), `half_batch=True` the planted fault
"half of the batch left out, the mean taken over the rest".
"""
import json
import statistics

import jax
import jax.numpy as jnp
import numpy as np

NUMBERS = ("loss1", "loss2", "loss3", "grad_norm_gap", "dparam_norm_gap",
           "grad_norm_gap_median", "dparam_norm_gap_median")
STEPS = 3
# (exponent bits, mantissa bits) for lax.reduce_precision.  A convert to a
# narrower type and back is NOT used anywhere here: on the TPU XLA removes
# such a pair (it allows excess precision), and the rounding silently does
# not happen (chip runs, PR 24: the reference then started from weights
# that were never rounded, and every large leaf's change read double).
FORMATS = {"bfloat16": (8, 7), "float8_e4m3": (4, 3), "float8_e5m2": (5, 2)}
# largest finite values of lax.reduce_precision's (IEEE-like) formats
E4M3_MAX = 240.0
E5M2_MAX = 57344.0
_STEPS = {}


def _round(x, fmt):
    e, m = FORMATS[fmt]
    return jax.lax.reduce_precision(x.astype(jnp.float32), e, m)


def _scaled(x, fmt, top):
    """Round to `fmt` under a per-tensor scale that puts the largest
    magnitude at `top`, the format's largest finite value."""
    x = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return _round(x / scale, fmt) * scale


@jax.custom_vjp
def q8(x):
    """The control's rounding, as float8 training has it (Micikevicius et
    al., arXiv:2209.05433): the value to e4m3 on the way forward, its
    gradient to e5m2 on the way back, each under a per-tensor scale."""
    return _scaled(x, "float8_e4m3", E4M3_MAX)


q8.defvjp(lambda x: (q8(x), None),
          lambda _res, g: (_scaled(g, "float8_e5m2", E5M2_MAX),))


def served(x, dtype):
    """A float32 value as the program holds it: rounded to `dtype`."""
    name = jnp.dtype(dtype).name
    if name == "float32":
        return x.astype(jnp.float32)
    return _round(x, name)


def leaf_parts(ref, cfg):
    """{leaf: [(name, start, stop)]}: the rows of a leaf whose norms are
    taken apart (a fused q, k, v projection's bias is three leaves to the
    measure, since the key's third has no gradient under softmax).  A
    reference without `parts` has every leaf whole."""
    return ref.parts(cfg) if hasattr(ref, "parts") else {}


def part_sq(name, value, parts):
    """{part name: sum of squares} of one leaf's array."""
    if name not in parts:
        return {name: jnp.sum(jnp.square(value))}
    return {pname: jnp.sum(jnp.square(value[a:b]))
            for pname, a, b in parts[name]}


def _sq_norms(tree, parts):
    out = {}
    for k, v in tree.items():
        out.update(part_sq(k, v, parts))
    return out


def wd_mask(spec, wd_leaves):
    if wd_leaves == "all":
        return {name: True for name, _s, _k in spec}
    if wd_leaves == "weight_gamma":
        return {name: kind.split(":")[0] in ("conv", "dense", "embed", "gamma")
                for name, _s, kind in spec}
    raise ValueError(f"unknown wd_leaves {wd_leaves!r}")


def hyper(traffic):
    hp = dict(traffic["optimizer_params"])
    hp["lr"] = hp.pop("learning_rate")
    hp.setdefault("wd", 0.0)
    return hp


def run_reference(ref, opt, cfg, traffic, seed, precision="f32",
                  half_batch=False):
    """Three steps of the plain reference.  Returns {"losses": [3],
    "grad_norm": {leaf: norm}, "dparam_norm": {leaf: norm}}."""
    spec = ref.leaves(cfg)
    hp = hyper(traffic)
    mask = wd_mask(spec, traffic["wd_leaves"])
    q = {"f32": None, "fp8": q8}[precision]
    parts = leaf_parts(ref, cfg)
    dt = traffic["dtype"]
    batch = traffic["batch"]

    def start():
        return {k: served(v, dt)
                for k, v in ref.init_weights(seed, cfg).items()}

    # one traced program a (reference, sizes, precision): control.py follows
    # many seeds in one process
    key = (ref.__name__, opt.__name__, precision, half_batch,
           json.dumps([cfg, traffic], sort_keys=True))
    if key not in _STEPS:
        def step(params, state, x, y, t):
            with jax.default_matmul_precision("highest"):
                loss, grads = jax.value_and_grad(ref.loss)(params, x, y, cfg,
                                                           q)
            gsq = _sq_norms(grads, parts)
            params, state = opt.update(params, grads, state, hp, t, mask)
            return loss, gsq, params, state

        _STEPS[key] = jax.jit(step, donate_argnums=(0, 1))
    step = _STEPS[key]
    params = start()
    state = opt.init(params)
    xs, ys = ref.make_batches(seed, STEPS, batch, cfg, traffic)
    losses, grad_norm = [], None
    for i in range(STEPS):
        x, y = xs[i], ys[i]
        if jnp.issubdtype(x.dtype, jnp.floating):
            x = served(x, dt)
        if half_batch:
            x, y = x[:batch // 2], y[:batch // 2]
        loss, gsq, params, state = step(params, state, x, y,
                                        jnp.float32(i + 1))
        losses.append(float(loss))
        if i == 0:
            grad_norm = {k: float(np.sqrt(v)) for k, v in gsq.items()}
    del state, xs, ys
    p0 = start()
    dsq = jax.jit(lambda a, b: _sq_norms({k: a[k] - b[k] for k in a},
                                         parts))(params, p0)
    dparam = {k: float(np.sqrt(v)) for k, v in dsq.items()}
    del params, p0
    return {"losses": losses, "grad_norm": grad_norm, "dparam_norm": dparam}


def program_first_grad(opt, spec, states, traffic, parts):
    """{leaf: norm} of the first gradient, from the program's optimizer
    state after step 1 (`states`: (state, weight) of every leaf, in the
    order of `spec`)."""
    hp = hyper(traffic)
    mask = wd_mask(spec, traffic["wd_leaves"])
    names = [name for name, _s, _k in spec]

    @jax.jit
    def read(states_):
        out = {}
        for n, (s, w) in zip(names, states_):
            out.update(part_sq(n, opt.first_grad(s, w, hp, mask[n]), parts))
        return out

    return {n: float(np.sqrt(v)) for n, v in read(states).items()}


def program_dparam(ref, opt, cfg, traffic, seed, spec, states, parts):
    """{leaf: norm} of master - initial weight, the initial weight made
    again from the seed one leaf at a time (so that no second copy of the
    model is ever held beside the program's state)."""
    dt = traffic["dtype"]
    cache = {}
    out = {}
    seed32 = jnp.uint32(seed % (2 ** 31))
    for i, ((name, shape, kind), (st, w)) in enumerate(zip(spec, states)):
        # one program a shape; a leaf with parts needs its own (their names)
        key = (shape, kind, name if name in parts else None)
        if key not in cache:
            def change_sq(master, rng, name=name, shape=shape, kind=kind):
                w0 = served(ref.init_leaf(rng, shape, kind), dt)
                return part_sq(name, master.astype(jnp.float32) - w0, parts)
            cache[key] = jax.jit(change_sq)
        got = cache[key](opt.master(st, w), ref.leaf_key(seed32, i))
        if name in parts:
            out.update({k: float(np.sqrt(v)) for k, v in got.items()})
        else:  # the cached program carries the name of the first such leaf
            (v,) = got.values()
            out[name] = float(np.sqrt(v))
    return out


def leaf_gaps(prog, ref_, keep=None):
    """{leaf: gap} over the leaves that `keep` keeps: the gap between the
    program's norm and the reference's against the reference's norm of that
    leaf or of the median leaf, whichever is larger."""
    med = statistics.median(ref_.values())
    gaps = {}
    for k, r in ref_.items():
        if keep is not None and not keep[k]:
            continue
        gap = abs(prog[k] - r) / max(r, med, 1e-30)
        gaps[k] = gap if np.isfinite(gap) else float("inf")
    return gaps


def moved(ref_):
    """{leaf: whether its reference gradient is at least a thousandth of
    the median leaf's}: the others move by round-off alone."""
    med = statistics.median(ref_["grad_norm"].values())
    return {k: g >= 1e-3 * med for k, g in ref_["grad_norm"].items()}


def _summed(prog, ref_, keep=None):
    """(worst gap, median gap, the worst leaf with both norms and the
    median leaf's norm)."""
    gaps = leaf_gaps(prog, ref_, keep)
    leaf = max(gaps, key=gaps.get)
    detail = {"leaf": leaf, "program": prog.get(leaf),
              "reference": ref_.get(leaf),
              "median_reference": statistics.median(ref_.values())}
    return gaps[leaf], statistics.median(gaps.values()), detail


def compare(prog, ref_):
    """The seven numbers, and which leaf read worst."""
    out, leaf = {}, {}
    for i in range(STEPS):
        lp, lr = prog["losses"][i], ref_["losses"][i]
        gap = abs(lp - lr) / max(abs(lr), 1e-30)
        out[f"loss{i + 1}"] = gap if np.isfinite(gap) else float("inf")
    out["grad_norm_gap"], out["grad_norm_gap_median"], \
        leaf["grad_norm_gap"] = _summed(prog["grad_norm"], ref_["grad_norm"])
    out["dparam_norm_gap"], out["dparam_norm_gap_median"], \
        leaf["dparam_norm_gap"] = _summed(prog["dparam_norm"],
                                          ref_["dparam_norm"], moved(ref_))
    return out, leaf


def judge(numbers, limits):
    """(correct, {name: {"value", "limit"}}): every number that has a
    limit has to lie at or under it; a number whose limit is null is not
    compared (PERF.md names it)."""
    report, ok = {}, True
    for name in NUMBERS:
        lim = limits.get(name)
        val = numbers[name]
        report[name] = {"value": val, "limit": lim}
        if lim is not None and not val <= lim:
            ok = False
    return ok, report


def before(cell, entry):
    """The program's side of the comparison, taken while set-up drives the
    first three steps through the window's own call and feed."""
    parts = leaf_parts(cell.ref, cell.cfg)
    steps = entry.drive(steps=1)
    prog = {"losses": [steps[0][1]]}
    prog["grad_norm"] = program_first_grad(
        cell.opt, cell.spec, entry.leaf_states(), cell.traffic, parts)
    steps = entry.drive(steps=STEPS - 1)
    prog["losses"] += [l for _t, l in steps]
    prog["dparam_norm"] = program_dparam(
        cell.ref, cell.opt, cell.cfg, cell.traffic, cell.seed, cell.spec,
        entry.leaf_states(), parts)
    for l in prog["losses"]:
        if isinstance(l, Exception):
            raise l
    return prog


def after(cell, prog):
    """(correct, {name: {"value", "limit"}}, detail for the run's record):
    the reference follows the three steps now that the program is freed."""
    ref_ = run_reference(cell.ref, cell.opt, cell.cfg, cell.traffic,
                         cell.seed)
    numbers, worst = compare(prog, ref_)
    ok, compared = judge(numbers, cell.limits)
    return ok, compared, {"losses_program": prog["losses"],
                          "losses_reference": ref_["losses"],
                          "worst_leaf": worst}
