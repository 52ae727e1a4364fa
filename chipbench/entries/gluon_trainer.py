"""Entry `gluon_trainer`: the loop a Gluon user writes.

    with autograd.record():
        loss = loss_block(x, y)
    loss.backward()
    trainer.step(batch)
    float(loss.mean())          # the loop's own read: the step's clock

The net is built by the configuration's model.py, cast to the traffic's
dtype (the optimizer then keeps float32 masters: `multi_precision`),
hybridized, and loaded with the benchmark's weights through
`Parameter.set_data`.  `gluon.Trainer(kvstore=..., update_on_kvstore=False)`
is the fused path (`FusedUpdater`), as chip_smoke.py's `_gluon_setup` has it.
"""
import time

import jax
import numpy as np

from chipbench.cell import raw_state


class Entry:
    def __init__(self, cell):
        self.cell = cell
        self.steps_done = 0

    def build(self):
        import mxnet_tpu as mx
        from mxnet_tpu import gluon
        cell = self.cell
        tr = cell.traffic
        self.mx = mx
        if cell.chips != 1:
            raise NotImplementedError(
                "entry gluon_trainer drives one context; a cell over "
                f"{cell.chips} chips needs an entry that uses them all")
        ctx = cell.contexts(mx)[0]
        self.ctx = ctx
        net = cell.model.build(cell.cfg)
        net.cast(tr["dtype"])
        # cheap on the device; the benchmark's weights replace them below
        net.initialize(mx.init.Zero(), ctx=ctx)
        params = cell.model.trainable(net)
        for p, (name, shape, _kind), w in zip(
                params, cell.spec, cell.weights()):
            if p.shape is not None and all(p.shape) and \
                    tuple(p.shape) != tuple(shape):
                raise ValueError(f"{p.name}: program {p.shape} vs "
                                 f"reference {name} {shape}")
            p.set_data(mx.nd.NDArray(w))
        if len(params) != len(cell.spec):
            raise ValueError(f"{len(params)} trainable parameters, the "
                             f"reference lists {len(cell.spec)}")
        net.hybridize()
        self.net = net
        self.params = params
        self.loss_block = cell.model.gluon_loss(net, cell.cfg)
        opt_params = dict(tr["optimizer_params"])
        self.trainer = gluon.Trainer(
            net.collect_params(), tr["optimizer"], opt_params,
            kvstore=tr["kvstore"], update_on_kvstore=False)
        self.batches = [
            tuple(mx.nd.NDArray(a) for a in b) for b in cell.batches()]

    def leaf_states(self):
        """(optimizer state, weight) of every trainable leaf, as jax arrays,
        in the reference's leaf order."""
        upd = self.trainer._updaters[0]
        index = {id(p): i for i, p in enumerate(self.trainer._params)}
        return [(raw_state(upd.states[index[id(p)]]), p.data()._data)
                for p in self.params]

    def _step(self, i):
        from mxnet_tpu import autograd
        with jax.profiler.TraceAnnotation("make_batch"):
            x, y = self.batches[i % len(self.batches)]
        with jax.profiler.TraceAnnotation("step_call"):
            with autograd.record():
                loss = self.loss_block(x, y)
            loss.backward()
            self.trainer.step(self.cell.traffic["batch"])
        with jax.profiler.TraceAnnotation("loss_read"):
            return float(np.mean(loss.asnumpy().astype(np.float32)))

    def drive(self, steps=None, seconds=None, clock=None):
        """Run steps (a count, or until `seconds` have passed) through the
        loop above; returns [(completion time, loss or the exception)]."""
        out = []
        t_end = None if seconds is None else time.perf_counter() + seconds
        while True:
            if steps is not None and len(out) >= steps:
                break
            if t_end is not None and time.perf_counter() >= t_end:
                break
            try:
                loss = self._step(self.steps_done)
            except Exception as exc:  # noqa: BLE001 - counted as failed
                loss = exc
            self.steps_done += 1
            out.append((time.perf_counter(), loss))
            if clock is not None:
                clock(out[-1])
            if sum(isinstance(l, Exception) for _t, l in out[-3:]) == 3:
                break
        return out

    def wait(self):
        """Until the device has run every step that was queued."""
        jax.block_until_ready([p.data()._data for p in self.params])

    def free(self):
        for name in ("net", "params", "loss_block", "trainer", "batches"):
            self.__dict__.pop(name, None)
