"""A cell, built from its data: `BENCHMARK.json` names the cell's
configuration, traffic mix and chips; everything else is found by name.

  configs/<config>/config.json   the sizes as they are run (+ rehearsal.json)
  configs/<config>/model.py      the net through the program's normal API
  configs/<config>/reference.py  the plain reference, weights and batches
  configs/<config>/flops.py      required operations per unit of work
  traffic/<traffic>.json         batch, lengths, dtype, optimizer, entry
  entries/<entry>.py             the training loop a user of that API writes
  optimizers/<optimizer>.py      the plain update rule and its state's layout
  checks/<check>.py              what decides `correct`: `before` (in set-up)
                                 and `after` (once the window has closed)
  limits/<cell>.json             the limits `correct` holds the cell to
  metrics/<reader>.py            per-layer metric `<reader>[.<suffix>]`
"""
import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_module(path, name):
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path):
    with open(path) as f:
        return json.load(f)


def raw_state(state):
    """An optimizer state of the program (NDArrays, nested tuples, None) as
    the jax arrays it wraps."""
    if isinstance(state, (tuple, list)):
        return tuple(raw_state(v) for v in state)
    return None if state is None else state._data


def benchmark():
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def peaks(kind):
    table = load_json(os.path.join(HERE, "peaks.json"))
    if kind not in table:
        raise KeyError(f"no published peaks for device_kind {kind!r} in "
                       f"chipbench/peaks.json (known: {sorted(table)})")
    return table[kind]


class Cell:
    def __init__(self, name, seed, rehearsal=False):
        bench = benchmark()
        entry = next((w for w in bench["workloads"] if w["name"] == name),
                     None)
        if entry is None:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json (have "
                           f"{[w['name'] for w in bench['workloads']]})")
        conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
        self.name = name
        self.seed = int(seed)
        self.rehearsal = rehearsal
        self.chips = int(entry["chips"])
        self.config_name = entry["config"]
        self.traffic_name = entry["traffic"]
        cdir = os.path.dirname(os.path.join(ROOT, conf["file"]))
        self.cfg = load_json(os.path.join(ROOT, conf["file"]))
        self.traffic = load_json(
            os.path.join(HERE, "traffic", entry["traffic"] + ".json"))
        if rehearsal:  # tiny sizes, for the CPU: never a device number
            self.cfg.update(load_json(os.path.join(cdir, "rehearsal.json")))
            self.traffic.update(self.traffic.get("rehearsal", {}))
        tag = self.config_name.replace("-", "_").replace(".", "_")
        self.model = load_module(os.path.join(cdir, "model.py"),
                                 f"chipbench_model_{tag}")
        self.ref = load_module(os.path.join(cdir, "reference.py"),
                               f"chipbench_reference_{tag}")
        self.flops = load_module(os.path.join(cdir, "flops.py"),
                                 f"chipbench_flops_{tag}")
        self.opt = load_module(
            os.path.join(HERE, "optimizers", self.traffic["optimizer"] + ".py"),
            "chipbench_opt_" + self.traffic["optimizer"])
        self.entry_mod = load_module(
            os.path.join(HERE, "entries", self.traffic["entry"] + ".py"),
            "chipbench_entry_" + self.traffic["entry"])
        self.check = load_module(
            os.path.join(HERE, "checks", self.traffic["check"] + ".py"),
            "chipbench_check_" + self.traffic["check"])
        lim = os.path.join(HERE, "limits", name + ".json")
        self.limits = load_json(lim)["limits"] if os.path.isfile(lim) else {}
        self.spec = self.ref.leaves(self.cfg)
        self.metrics = {k: [m for m in bench[k]
                            if name in m.get("workloads", [name])]
                        for k in ("end_to_end", "per_layer")}

    def contexts(self, mx):
        make = mx.cpu if self.rehearsal else mx.tpu
        return [make(i) for i in range(self.chips)]

    def weights(self):
        """The seed's weights in the served dtype, in leaf order."""
        import jax.numpy as jnp
        w = self.ref.init_weights(self.seed, self.cfg,
                                  jnp.dtype(self.traffic["dtype"]))
        return [w[name] for name, _s, _k in self.spec]

    def batches(self):
        """The seed's distinct batches, as the program takes them."""
        n = self.traffic["distinct_batches"]
        xs, ys = self.ref.make_batches(self.seed, n, self.traffic["batch"],
                                       self.cfg, self.traffic)
        return [self.model.program_batch(xs[i], ys[i], self.traffic["dtype"])
                for i in range(n)]

    def units_per_step(self):
        return self.flops.units_per_step(self.cfg, self.traffic)
