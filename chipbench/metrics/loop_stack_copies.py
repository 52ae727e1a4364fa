"""How many copies of a looped model's stack of layers the step's program
text holds: the program's `mxnet_loop_stack_copies`, counted while the
model's graph is traced (the times the stack was traced into it): 1 where
the loop is a node of the graph (`contrib.foreach`, run as `lax.scan`), the
number of loop steps where the loop is unrolled.  The text is what a cold
start compiles and a warm one loads, and it grows with the copies.  Source:
program_counter.  Layer: step path (gluon/model_zoo/decoder.py `LoopedLM`).

A program without the gauge, or one that traced no looped model: None,
never 0."""


def read(ctx):
    from mxnet_tpu.observability import metrics
    gauge = getattr(metrics, "LOOP_STACK_COPIES", None)
    if gauge is None:
        return None
    return gauge.get() or None
