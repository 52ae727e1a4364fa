"""Entry `module_fit`: `mx.mod.Module.fit`, the path the project has
always led with.

The configuration's symbol (under `SoftmaxOutput`) is bound at the
traffic's batch and dtype, loaded with the benchmark's weights through
`init_params(arg_params=...)`, given its optimizer through
`init_optimizer(kvstore=...)`, and every drive is one `fit` call over one
epoch of the benchmark's own `DataIter`, which serves the seeded
device-resident batches and ends the epoch after a count of steps or when
the window closes.  The step's clock is `batch_end_callback`: fit calls it
after `update_metric` has read the step's outputs back (cross-entropy).
"""
import time

import jax
import numpy as np

from chipbench.cell import raw_state


class _Feed:
    """The benchmark's DataIter: cycles the device-resident batches."""

    def __init__(self, mx, batches, descs, first):
        self.mx = mx
        self.batches = batches
        self.provide_data, self.provide_label = descs
        self.batch_size = self.provide_data[0].shape[0]
        self.i = first
        self.steps = None
        self.t_end = None
        self.served = 0

    def arm(self, steps, seconds):
        self.steps, self.served = steps, 0
        self.t_end = None if seconds is None \
            else time.perf_counter() + seconds

    def __iter__(self):
        return self

    def reset(self):
        pass

    def __next__(self):
        with jax.profiler.TraceAnnotation("make_batch"):
            if self.steps is not None and self.served >= self.steps:
                raise StopIteration
            if self.t_end is not None and self.served > 0 and \
                    time.perf_counter() >= self.t_end:
                raise StopIteration
            x, y = self.batches[self.i % len(self.batches)]
            self.i += 1
            self.served += 1
            return self.mx.io.DataBatch(data=[x], label=[y], pad=0)

    next = __next__


class Entry:
    def __init__(self, cell):
        self.cell = cell
        self.steps_done = 0
        self.epoch = 0

    def build(self):
        import mxnet_tpu as mx
        from mxnet_tpu.io import DataDesc
        cell = self.cell
        tr = cell.traffic
        self.mx = mx
        ctxs = cell.contexts(mx)
        net = cell.model.build(cell.cfg)
        sym = cell.model.symbol(net, cell.cfg)
        shape = cell.model.input_shape(cell.cfg, tr)
        descs = ([DataDesc("data", shape, np.dtype(tr["dtype"]))],
                 [DataDesc("softmax_label", (shape[0],), np.float32)])
        mod = mx.mod.Module(sym, context=ctxs if len(ctxs) > 1 else ctxs[0])
        mod.bind(data_shapes=descs[0], label_shapes=descs[1])
        names = [p.name for p in cell.model.trainable(net)]
        if len(names) != len(cell.spec):
            raise ValueError(f"{len(names)} trainable parameters, the "
                             f"reference lists {len(cell.spec)}")
        args = {}
        for pname, (name, shape_, _k), w in zip(names, cell.spec,
                                                cell.weights()):
            have = tuple(mod._exec.arg_dict[pname].shape)
            if have != tuple(shape_):
                raise ValueError(f"{pname}: program {have} vs reference "
                                 f"{name} {shape_}")
            args[pname] = mx.nd.NDArray(w)
        # arguments come from the benchmark; the initializer fills only the
        # auxiliary states (moving mean 0, moving variance 1)
        mod.init_params(mx.init.Xavier(), arg_params=args,
                        allow_missing=False)
        mod.init_optimizer(kvstore=tr["kvstore"], optimizer=tr["optimizer"],
                           optimizer_params=dict(tr["optimizer_params"]))
        self.mod = mod
        self.names = names
        self.metric = mx.metric.CrossEntropy()
        self.feed = _Feed(mx, [tuple(mx.nd.NDArray(a) for a in b)
                               for b in cell.batches()], descs, 0)

    def leaf_states(self):
        """(optimizer state, weight) of every trainable leaf, as jax arrays,
        in the reference's leaf order."""
        mod = self.mod
        upd = mod._kvstore._updater if mod._update_on_kvstore \
            else mod._updater
        states = upd.states
        out = []
        for i, n in enumerate(self.names):
            key = n if n in states else mod._param_names.index(n)
            out.append((raw_state(states[key]), mod._exec.arg_dict[n]._data))
        return out

    def drive(self, steps=None, seconds=None, clock=None):
        out = []
        seen = [0.0, 0]

        def batch_end(param):
            # fit has read the outputs back (update_metric): the step is done
            with jax.profiler.TraceAnnotation("step_clock"):
                m = param.eval_metric
                loss = (m.sum_metric - seen[0]) / max(m.num_inst - seen[1], 1)
                seen[0], seen[1] = m.sum_metric, m.num_inst
                out.append((time.perf_counter(), float(loss)))
                if clock is not None:
                    clock(out[-1])

        self.feed.arm(steps, seconds)
        try:
            self.mod.fit(self.feed, eval_metric=self.metric,
                         batch_end_callback=batch_end,
                         begin_epoch=self.epoch, num_epoch=self.epoch + 1)
        except Exception as exc:  # noqa: BLE001 - counted as failed
            out.append((time.perf_counter(), exc))
        self.epoch += 1
        self.steps_done += len(out)
        return out

    def wait(self):
        """Until the device has run every step that was queued."""
        jax.block_until_ready([self.mod._exec.arg_dict[n]._data
                               for n in self.names])

    def free(self):
        for name in ("mod", "metric", "feed"):
            self.__dict__.pop(name, None)
