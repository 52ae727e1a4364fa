"""How unevenly the router loads the experts this chip holds: the
program's `mxnet_moe_expert_load_max_over_mean` at the traced window's end,
the largest assignment count of any held expert of any layer since the net
was built over the mean of all of them (1.0 is even).  The program fills it
from its expert layers' device-side load counters when asked
(`observability.metrics.refresh_moe`: one device read here, none in the
step).  Source: program_counter.  Layer: experts
(gluon/model_zoo/decoder.py `MoEFeedForward`).

A program without the gauge, or with no expert layer alive: None, never 0."""


def read(ctx):
    from mxnet_tpu.observability import metrics
    refresh = getattr(metrics, "refresh_moe", None)
    if refresh is None:
        return None
    refresh()
    return metrics.MOE_LOAD_MAX_OVER_MEAN.get() or None
