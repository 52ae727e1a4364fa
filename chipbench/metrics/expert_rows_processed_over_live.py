"""What the expert op's row plumbing does for each row that counts: the
program's `mxnet_moe_buffer_rows_total{kind="processed"}` over
`{kind="live"}` at the traced window's end, summed over the expert layers
since the net was built.  Processed: the rows of the buffer the gathers,
masks and casts around the grouped products ran over (the whole `T x
top_k` where a layer keeps its products, else the rung of
`ops/decoder.py buffer_rungs` the device chose); live: the (token,
choice) pairs routed to a held expert.  1.0 is a buffer exactly as long as
its live rows.  The program fills it from its expert layers' device-side
load counters when asked (`observability.metrics.refresh_moe`: one device
read here, none in the step).  Source: program_counter.  Layer: experts.

A program without the counter, or with no expert layer alive: None."""


def read(ctx):
    from mxnet_tpu.observability import metrics
    refresh = getattr(metrics, "refresh_moe", None)
    rows = getattr(metrics, "MOE_BUFFER_ROWS", None)
    if refresh is None or rows is None:
        return None
    refresh()
    live = rows.get(kind="live")
    return rows.get(kind="processed") / live if live else None
