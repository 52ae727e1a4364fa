"""Program launches on the device per training step: the events of the
device plane's `XLA Modules` line inside the traced window (averaged over
the chips used) over the steps the loop completed in it.  Source:
device_trace.  Layer: the step path (Module or Gluon training)."""


def read(ctx):
    steps = ctx["window"]["attempted"]
    if "reduced" not in ctx or not steps:
        return None
    return ctx["reduced"]["launches"] / steps
