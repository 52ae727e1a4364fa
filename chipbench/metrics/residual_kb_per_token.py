"""What a recorded step keeps on the device between its forward and its
backward program, for each token of the step: the program's
`mxnet_cachedop_residual_bytes{kind="kept"}` (set from shapes when the
recording forward program is traced: the outputs of matrix products and
kernel calls that the backward program is handed, stacked along the loop
axis where the model loops) over the tokens of a step, in KB (1,000
bytes).  A looped model keeps as many times a layer's products as it
applies the layer, so this is what stands at the chip's memory and what a
backward launch waits for (PERF.md, Open questions 15).  Source:
program_counter.  Layer: step path (gluon/block.py `CachedOp`).

A program without the gauge, or one that recorded no such call: None,
never 0."""


def read(ctx):
    from mxnet_tpu.observability import metrics
    gauge = getattr(metrics, "CACHEDOP_RESIDUAL_BYTES", None)
    if gauge is None:
        return None
    kept = gauge.get(kind="kept")
    tokens = ctx["cell"].units_per_step()
    if not kept or not tokens:
        return None
    return kept / tokens / 1e3
