"""Record the small device trace that test_trace_reduce.py checks the
reduction against.  Run on the chip (`chiprun -- python3
chipbench/tests/record_trace.py`); it writes
chiprun_out/small_trace/small.xplane.pb and prints what the trace holds:
planes, lines, and the commonest event names of each line.

The program traced: two jitted programs a step (`small_step`, a matmul
chain; `small_update`, an elementwise pass), five steps, with a 20 ms host
sleep after step 2 under a `host_pause` TraceAnnotation, so the trace has
a gap with a known label.  The Python tracer is off, as in the benchmark's
own traced runs.
"""
import glob
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp


def main():
    if jax.devices()[0].platform != "tpu":
        sys.stderr.write("record_trace: needs a TPU\n")
        return 2
    out = os.path.join("chiprun_out", "small_trace")
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)

    @jax.jit
    def small_step(w, x):
        for _ in range(4):
            x = jnp.tanh(x @ w)
        return x, jnp.mean(x.astype(jnp.float32))

    @jax.jit
    def small_update(w, x):
        return w * 0.999 + 0.001 * jnp.mean(x)

    w = jnp.ones((2048, 2048), jnp.bfloat16)
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    for _ in range(2):  # everything the traced loop runs is compiled here
        x, loss = small_step(w, x)
        w = small_update(w, x)
        float(loss)
    jax.block_until_ready((w, x))
    tmp = os.path.join(out, "raw")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("chipbench_window"):
        for i in range(5):
            with jax.profiler.TraceAnnotation("step_call"):
                x, loss = small_step(w, x)
                w = small_update(w, x)
            with jax.profiler.TraceAnnotation("loss_read"):
                float(loss)
            if i == 2:
                with jax.profiler.TraceAnnotation("host_pause"):
                    time.sleep(0.02)
        jax.block_until_ready((w, x))
    jax.profiler.stop_trace()
    pb = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    dst = os.path.join(out, "small.xplane.pb")
    shutil.copy(pb, dst)
    shutil.rmtree(tmp)
    print("bytes", os.path.getsize(dst))
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(dst)
    for plane in pd.planes:
        print("PLANE", repr(plane.name))
        for line in plane.lines:
            evs = list(line.events)
            names = {}
            for e in evs:
                names[e.name] = names.get(e.name, 0) + 1
            top = sorted(names.items(), key=lambda kv: -kv[1])[:12]
            print("  LINE", repr(line.name), len(evs), top)
    return 0


if __name__ == "__main__":
    sys.exit(main())
