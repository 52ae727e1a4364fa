"""Check `train_steps_mesh`: `train_steps` for a cell whose batch one chip
cannot follow in float32.  The seven numbers, their limits, the control and
the planted fault are `train_steps`' own (this file imports them); the one
difference is where the plain reference runs: its batches are laid over all
the chips of the host, split along the batch axis, and `jax.jit` makes one
program over them, the weights on every chip.  The reference's code does
not change and knows nothing of it: a mean over the batch (batch
normalisation's statistics, the loss) is then a mean over all the chips'
rows, as it is on one chip, summed in another order.

Why: the ResNet-50 reference at batch 1,024 in float32 needs 26.0 GB on one
chip of 15.75 (v5e compile, PR 31); over a host's four chips it is the
one-chip cell's 256 rows a chip.
"""
import os

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from chipbench import cell as cellmod

_base = cellmod.load_module(
    os.path.join(cellmod.HERE, "checks", "train_steps.py"),
    "chipbench_check_train_steps")
before, compare, judge = _base.before, _base.compare, _base.judge


class _OverTheChips:
    """A configuration's reference whose batches come back split over the
    host's chips along the batch axis; everything else is the reference."""

    def __init__(self, ref):
        self._ref = ref
        self.__name__ = ref.__name__ + "_over_the_chips"

    def __getattr__(self, name):
        return getattr(self._ref, name)

    def make_batches(self, seed, n, batch, cfg, traffic):
        mesh = Mesh(np.array(jax.devices()), ("batch",))
        xs, ys = self._ref.make_batches(seed, n, batch, cfg, traffic)
        rows = lambda a: NamedSharding(mesh, PartitionSpec(
            None, "batch", *(None,) * (a.ndim - 2)))
        return (jax.device_put(xs, rows(xs)), jax.device_put(ys, rows(ys)))


def run_reference(ref, opt, cfg, traffic, seed, **kw):
    return _base.run_reference(_OverTheChips(ref), opt, cfg, traffic, seed,
                               **kw)


def after(cell, prog):
    """As `train_steps.after`, the reference over the chips."""
    ref_ = run_reference(cell.ref, cell.opt, cell.cfg, cell.traffic,
                         cell.seed)
    numbers, worst = compare(prog, ref_)
    ok, compared = judge(numbers, cell.limits)
    return ok, compared, {"losses_program": prog["losses"],
                          "losses_reference": ref_["losses"],
                          "worst_leaf": worst}
