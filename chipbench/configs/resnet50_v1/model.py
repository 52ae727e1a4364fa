"""`resnet50_v1` through the program's normal API: the gluon model zoo's
ResNet v1 (bottleneck), as a hybridized block for the Gluon entry and as a
symbol under `SoftmaxOutput` for the Module entry.  The benchmark's weights
(reference.init_weights) go in by position: the reference lists its leaves
in the order the program's own parameters have, and the shapes are checked.
"""


def build(cfg):
    """The uninitialised gluon net."""
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.gluon.model_zoo.vision import resnet
    kind, layers, channels = resnet.resnet_spec[50]
    if list(cfg["layers"]) == layers and list(cfg["channels"]) == channels:
        return vision.resnet50_v1(classes=cfg["classes"])
    # a rehearsal's tiny sizes: the same class and block, other numbers
    return resnet.ResNetV1(resnet.BottleneckV1, list(cfg["layers"]),
                           list(cfg["channels"]), classes=cfg["classes"])


def input_shape(cfg, traffic):
    return (traffic["batch"], 3, cfg["image_size"], cfg["image_size"])


def trainable(net):
    """The program's trainable parameters, in its own order."""
    return [p for p in net.collect_params().values() if p.grad_req != "null"]


def symbol(net, cfg):
    import mxnet_tpu as mx
    return mx.sym.SoftmaxOutput(net(mx.sym.Variable("data")), name="softmax")


def gluon_loss(net, cfg):
    """(x, y) -> per-image loss, as a user writes it: the hybridized net,
    then gluon's loss block."""
    from mxnet_tpu import gluon
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    return lambda x, y: loss_fn(net(x), y)


def program_batch(x, y, dtype):
    """The benchmark's batch (jax arrays) as the program takes it: images
    in the traffic's dtype, labels as float32."""
    import jax.numpy as jnp
    return x.astype(jnp.dtype(dtype)), y.astype(jnp.float32)
