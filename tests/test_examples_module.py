"""Module-API trainings on the classic tasks, one child process each (parity
model: the reference CI runs example trainings; tests/python/train tier)."""
import os
import sys

import pytest

import mxnet_tpu as mx

from example_runner import REPO, run_example


def test_train_mnist_mlp():
    out = run_example("example/image-classification/train_mnist.py",
                      "--num-epochs", "2", "--num-examples", "2000")
    assert "Validation-accuracy" in out


def test_custom_softmax_numpy_op_example():
    out = run_example("example/numpy-ops/custom_softmax.py",
                      "--num-epochs", "2")
    assert "validation accuracy" in out


def test_sparse_linear_classification_example():
    out = run_example("example/sparse/linear_classification.py",
                      "--num-epochs", "3")
    line = [l for l in out.splitlines() if "final train accuracy" in l][0]
    acc = float(line.rsplit(" ", 1)[-1])
    assert acc > 0.7, out


def test_train_cifar10_synthetic_resnet():
    out = run_example("example/image-classification/train_cifar10.py",
                      "--num-epochs", "1", "--num-examples", "256",
                      "--batch-size", "64", "--num-layers", "8",
                      "--benchmark", "1")
    assert "Epoch[0]" in out


def test_model_parallel_example():
    out = run_example("example/model-parallel/model_parallel_mlp.py")
    assert "accuracy" in out


def test_matrix_factorization_example():
    out = run_example("example/recommenders/matrix_factorization.py",
                      "--epochs", "2", "--num-samples", "4000")
    assert "final RMSE" in out


def test_profiler_example(tmp_path):
    out = run_example("example/profiler/profiler_executor.py",
                      "--iters", "5", "--file",
                      str(tmp_path / "trace.json"))
    assert "events" in out


SYMBOL_NETS = [("alexnet", {}), ("vgg", {"num_layers": 11}),
               ("googlenet", {}), ("inception-bn", {}),
               ("inception-v3", {}), ("inception-v4", {}),
               ("inception-resnet-v2", {}),
               ("resnext", {"num_layers": 50}),
               ("mobilenet", {}), ("resnet", {"num_layers": 18}),
               ("lenet", {}), ("mlp", {})]


@pytest.mark.parametrize("net,kw", SYMBOL_NETS,
                         ids=[n for n, _ in SYMBOL_NETS])
def test_image_classification_symbols_build(net, kw):
    """Every symbols/<net>.py builds and shape-infers end to end (parity:
    the reference's --network flag surface, symbols/*.py)."""
    import importlib
    ic_path = os.path.join(REPO, "example", "image-classification")
    if ic_path not in sys.path:
        sys.path.insert(0, ic_path)
    mod = importlib.import_module(f"symbols.{net}")
    size = 299 if net == "inception-v3" else 224
    if net in ("lenet", "mlp"):
        size = 28
    sym = mod.get_symbol(num_classes=17, image_shape=f"3,{size},{size}", **kw)
    shape = (2, 1, size, size) if net in ("lenet", "mlp") else \
        (2, 3, size, size)
    arg_shapes, out_shapes, _ = sym.infer_shape(data=shape)
    assert out_shapes[0] == (2, 17), (net, out_shapes)


def test_autoencoder_example():
    out = run_example("example/autoencoder/autoencoder.py",
                      "--num-epochs", "4", "--num-examples", "500")
    line = [l for l in out.splitlines() if "final recon mse" in l][0]
    assert float(line.rsplit(" ", 1)[-1]) < 0.05, out


def test_multi_task_example():
    out = run_example("example/multi-task/multi_task.py",
                      "--num-epochs", "8")
    line = [l for l in out.splitlines() if "final digit-acc" in l][0]
    digit = float(line.split()[2])
    parity = float(line.split()[4])
    assert digit > 0.6 and parity > 0.6, out


def test_module_api_gallery():
    out = run_example("example/module/demo_modules.py",
                      "--num-epochs", "8")
    line = [l for l in out.splitlines() if "val accuracies" in l][0]
    vals = [float(v) for v in line.split()[3::2]]
    assert all(v > 0.8 for v in vals), out


def test_svm_mnist_example():
    out = run_example("example/svm_mnist/svm_mnist.py",
                      "--num-epochs", "6")
    acc = float([l for l in out.splitlines()
                 if "validation accuracy" in l][0].rsplit(" ", 1)[-1])
    assert acc > 0.85, out


def test_python_howto_examples():
    assert "multiple outputs OK" in \
        run_example("example/python-howto/multiple_outputs.py")
    assert "monitor captured" in \
        run_example("example/python-howto/monitor_weights.py")


def test_torch_bridge_example():
    out = run_example("example/torch/torch_bridge.py")
    acc = float([l for l in out.splitlines()
                 if "accuracy" in l][0].rsplit(" ", 1)[-1])
    assert acc > 0.8, out


@mx.test_utils.retry(3)
def test_caffe_prototxt_example():
    # retry: unseeded init makes the 3-epoch accuracy occasionally dip
    # under CI CPU contention
    out = run_example("example/caffe/train_caffe_prototxt.py",
                      "--num-epochs", "3")
    acc = float([l for l in out.splitlines()
                 if "validation accuracy" in l][0].rsplit(" ", 1)[-1])
    assert acc > 0.7, out


def test_memcost_example():
    out = run_example("example/memcost/inception_memcost.py",
                      "--batch-size", "4", "--image-size", "64")
    import json as _json
    line = [l for l in out.splitlines() if l.startswith("{")][-1]
    d = _json.loads(line)
    # training needs more transient memory than inference
    assert d["train_mb"] > d["forward_only_mb"], d
