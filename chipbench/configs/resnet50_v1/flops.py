"""Required operations of one training step of `resnet50_v1`, from its
shapes: two per multiply-add of every convolution and of the classifier,
forward, and twice that again for the backward pass (gradients of the
inputs and of the weights).  Batch normalisation, ReLU, pooling, biases
and the loss are left out, as is usual for a model's FLOP count: they
are under half a percent of the sum.
"""


def forward_macs_per_image(cfg):
    size = cfg["image_size"]
    chans = cfg["channels"]
    macs = 0
    hw = (size + 2 * 3 - 7) // 2 + 1            # stem 7x7, stride 2, pad 3
    macs += chans[0] * 3 * 49 * hw * hw
    hw = (hw + 2 * 1 - 3) // 2 + 1              # max pool 3x3, stride 2, pad 1
    for s, n in enumerate(cfg["layers"]):
        cin, c = chans[s], chans[s + 1]
        m = c // 4
        for b in range(n):
            first = b == 0
            stride = (1 if s == 0 else 2) if first else 1
            c_in = cin if first else c
            out = (hw - 1) // stride + 1        # the stride sits in conv1
            macs += m * c_in * out * out         # conv1 1x1
            macs += m * m * 9 * out * out        # conv2 3x3
            macs += c * m * out * out            # conv3 1x1
            if first and c != cin:
                macs += c * c_in * out * out     # downsample 1x1
            hw = out
    macs += cfg["classes"] * chans[-1]
    return macs


def train_flops_per_unit(cfg, traffic):
    """FLOPs of forward and backward per image."""
    return 6 * forward_macs_per_image(cfg)


def unit(cfg, traffic):
    return "images"


def units_per_step(cfg, traffic):
    return traffic["batch"]
