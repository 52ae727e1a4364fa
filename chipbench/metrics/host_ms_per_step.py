"""The step path's busy time on the host, visible also where the device
hides it: the sum of the self times of the program's `mx.` spans in the
traced window (a span's self time: the instants at which it is the
innermost open span), `mx.sync.read` left out (the host blocked on the
device is not work), over the steps the loop completed in the window.
Source: device_trace (the profiler's file, host plane).  Layer: the step
path.

No `mx.` span in the window: None, never 0."""
from chipbench import span_reduce


def read(ctx):
    red = ctx.get("reduced")
    steps = ctx["window"]["attempted"]
    if not red or not steps:
        return None
    _lo, _hi, spans = span_reduce.window_spans(red["events"])
    if not spans:
        return None
    own = span_reduce.self_seconds(span_reduce.innermost_segments(spans))
    busy = sum(v for k, v in own.items() if k != span_reduce.SYNC_SPAN)
    return 1e3 * busy / steps
