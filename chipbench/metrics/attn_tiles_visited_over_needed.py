"""How many tiles the forward attention kernel visits for each tile its
masks need: the program's `mxnet_flash_fwd_tiles_total{kind="visited"}`
over `{kind="needed"}`, both added up when the program's attention calls
are traced (one a layer; nothing runs in the step): the (query tile, key
tile) pairs the kernel's loops run against the pairs in which the layer's
mask, causal or windowed, leaves a query a key.  1.0 is a kernel that
visits no tile its mask empties; a kernel that ran a windowed layer under
the causal mask alone would read above it.  Source: program_counter.
Layer: kernels (ops/flash_attention.py).

A program without the counter, or one that traced no such call: None,
never 0."""


def read(ctx):
    from mxnet_tpu.observability import metrics
    tiles = getattr(metrics, "FLASH_FWD_TILES", None)
    if tiles is None:
        return None
    visited, needed = tiles.get(kind="visited"), tiles.get(kind="needed")
    if not visited or not needed:
        return None
    return visited / needed
