"""XLA flag sweep over the raw-JAX ResNet-50 step (VERDICT r4 #1b).

Each configuration runs experiments/layout_probe.py in a SUBPROCESS
(XLA_FLAGS must be set before backend init) with a timeout, in the
winning layout (NHWC bf16 by default).  The list is deliberately short
— chip minutes are the scarce resource — and centers on the two
public knobs that move single-chip conv throughput:

  - latency-hiding scheduler (overlaps DMA with compute)
  - scoped VMEM limit (bigger fusion working sets)

Prints one line per config + a winner line.  The parent never imports
JAX: each child in turn is the one process that holds the chip.
"""
import os
import re
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CONFIGS = [
    ("baseline", ""),
    ("latency-hiding", "--xla_tpu_enable_latency_hiding_scheduler=true"),
    ("vmem-64M", "--xla_tpu_scoped_vmem_limit_kib=65536"),
    ("both", "--xla_tpu_enable_latency_hiding_scheduler=true "
             "--xla_tpu_scoped_vmem_limit_kib=65536"),
]

TIMEOUT = float(os.environ.get("MXT_FLAG_SWEEP_TIMEOUT", 420))
LAYOUT = os.environ.get("MXT_FLAG_SWEEP_LAYOUT", "NHWC")
BATCH = os.environ.get("B", "256")
# comma-separated subset for smoke runs (e.g. "baseline")
ONLY = {s for s in os.environ.get("MXT_FLAG_SWEEP_ONLY", "").split(",")
        if s.strip()}


def main():
    results = []
    for name, flags in CONFIGS:
        if ONLY and name not in ONLY:
            continue
        env = dict(os.environ)
        base = env.get("XLA_FLAGS", "")
        env["XLA_FLAGS"] = (base + " " + flags).strip()
        t0 = time.perf_counter()
        try:
            r = subprocess.run(
                [sys.executable, "experiments/layout_probe.py",
                 "--layout", LAYOUT, "--bn", "f32", "--resident", "bf16",
                 "--batch", BATCH,
                 "--img", os.environ.get("IMG", "224")],
                cwd=REPO, env=env, capture_output=True, text=True,
                timeout=TIMEOUT)
            m = re.search(r"([\d.]+) img/s", r.stdout)
            rate = float(m.group(1)) if (r.returncode == 0 and m) else 0.0
            tail = "" if rate else (r.stdout + r.stderr)[-300:]
        except subprocess.TimeoutExpired:
            rate, tail = 0.0, "TIMEOUT %.0fs" % TIMEOUT
        results.append((name, rate))
        print("%-16s %8.1f img/s  (%.0fs)%s"
              % (name, rate, time.perf_counter() - t0,
                 ("  [" + tail + "]") if tail else ""), flush=True)
    if not results:
        # a typo'd MXT_FLAG_SWEEP_ONLY must fail loudly, not traceback
        known = ", ".join(n for n, _ in CONFIGS)
        print("no configs matched MXT_FLAG_SWEEP_ONLY=%r (known: %s)"
              % (",".join(sorted(ONLY)), known), flush=True)
        return 1
    best = max(results, key=lambda x: x[1])
    base_rate = dict(results).get("baseline", 0.0)
    if best[1] > 0:
        gain = (best[1] / base_rate - 1) * 100 if base_rate else 0.0
        print("WINNER: %s (%.1f img/s, %+.1f%% vs baseline)"
              % (best[0], best[1], gain), flush=True)
    return 0 if any(r for _, r in results) else 1


if __name__ == "__main__":
    sys.exit(main())
