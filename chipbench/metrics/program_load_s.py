"""What of `setup_s` is compiling and cache loads: the program's own
`mxnet_program_load_seconds_total` when the reader runs, that is from
process start to the traced window's end (the reference has not run yet).
The program feeds the counter from JAX's monitoring events: the seconds JAX
reports for every backend compile or read from the persistent compilation
cache.  Source: host_clock.  Layer: start-up.

A program without the counter: None, never 0."""


def read(ctx):
    from mxnet_tpu.observability import metrics
    counter = getattr(metrics, "PROGRAM_LOAD_SECONDS", None)
    if counter is None or not counter.value:
        return None
    return counter.value
