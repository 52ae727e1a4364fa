"""Smoke tests for the example scripts and deployment surfaces (parity
model: the reference CI runs example trainings; tests/python/train tier)."""
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.test_utils import assert_almost_equal

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = {**os.environ,
       "JAX_PLATFORMS": "cpu",
       "XLA_FLAGS": os.environ.get("XLA_FLAGS", "") +
       " --xla_force_host_platform_device_count=8",
       "PYTHONPATH": REPO}


def run_example(rel, *args, timeout=420):
    path = os.path.join(REPO, rel)
    proc = subprocess.run([sys.executable, path, *args], env=ENV,
                          cwd=os.path.dirname(path), capture_output=True,
                          text=True, timeout=timeout)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    return proc.stdout + proc.stderr


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


def test_lstm_bucketing_example():
    out = run_example("example/rnn/lstm_bucketing.py",
                      "--num-epochs", "1", "--num-hidden", "32",
                      "--num-embed", "32", "--num-layers", "1")
    assert "perplexity" in out.lower() or "Epoch[0]" in out


def test_gluon_image_classification_example():
    out = run_example("example/gluon/image_classification.py",
                      "--epochs", "1", "--num-examples", "128",
                      "--model", "squeezenet1_0", "--image-size", "64")
    assert "val-acc" in out


def test_model_parallel_example():
    out = run_example("example/model-parallel/model_parallel_mlp.py")
    assert "accuracy" in out


def test_im2rec_raw_roundtrip(tmp_path):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import importlib
    im2rec = importlib.import_module("im2rec")
    # build a tiny image tree
    rs = np.random.RandomState(0)
    for cls in ("cat", "dog"):
        d = tmp_path / "imgs" / cls
        d.mkdir(parents=True)
        for i in range(3):
            arr = rs.randint(0, 255, (8, 8, 3)).astype("u1")
            from mxnet_tpu.recordio import _imencode
            (d / f"{i}.png").write_bytes(_imencode(arr, img_fmt=".png"))
    items = im2rec.list_images(str(tmp_path / "imgs"))
    assert len(items) == 6
    labels = {lbl for _, lbl, _ in items}
    assert labels == {0, 1}
    prefix = str(tmp_path / "pack")
    im2rec.write_list(prefix, items)
    im2rec.pack(prefix, str(tmp_path / "imgs"), raw=True)
    # raw records load through TensorRecordIter
    it = mx.io.TensorRecordIter(prefix + ".rec", data_shape=(8, 8, 3),
                                batch_size=2, dtype="uint8")
    batch = next(iter(it))
    assert batch.data[0].shape == (2, 8, 8, 3)


def test_parse_log(tmp_path):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import importlib
    parse_log = importlib.import_module("parse_log")
    log = tmp_path / "t.log"
    log.write_text(
        "INFO Epoch[0] Train-accuracy=0.5\n"
        "INFO Epoch[0] Time cost=1.5\n"
        "INFO Epoch[0] Validation-accuracy=0.4\n"
        "INFO Epoch[1] Train-accuracy=0.8\n")
    rows = parse_log.parse(str(log))
    assert rows[0]["train_acc"] == 0.5
    assert rows[0]["val_acc"] == 0.4
    assert rows[1]["train_acc"] == 0.8


@pytest.mark.parametrize("layout", ["NCHW", "NHWC"])
def test_bench_product_path_smoke(layout):
    """bench.py drives Module.fit + tpu_sync kvstore + fused updates; the
    explicit CPU rehearsal checks the whole path wires up (both internal
    layouts) and the loss-sanity assert passes.  Every record of a
    rehearsal says so and names the device it ran on."""
    import json
    env = {**ENV, "MXT_BENCH_BATCH": "8", "MXT_BENCH_IMG": "64",
           "MXT_BENCH_BATCHES": "2", "MXT_BENCH_LR": "0.01",
           "MXNET_TPU_CONV_LAYOUT": layout}
    proc = subprocess.run([sys.executable, os.path.join(REPO, "bench.py"),
                           "--rehearsal"],
                          env=env, capture_output=True, text=True,
                          timeout=560)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    rec = json.loads(proc.stdout.splitlines()[-1])
    assert rec["metric"] == "resnet50_train_throughput"
    assert rec["value"] > 0
    assert rec["rehearsal"] is True and rec["platform"] == "cpu"
    assert rec["device_kind"] and rec["device_count"] >= 1
    assert "chip_mfu" not in rec  # a CPU has no peak to divide by
    assert "failed" not in rec and "error" not in rec, rec


def test_bench_refuses_cpu_without_rehearsal():
    """bench.py cannot run on the CPU by accident: with no TPU it prints
    its JSON line (device stamped, error named) and exits non-zero."""
    import json
    proc = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                          env=ENV, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    rec = json.loads(proc.stdout.splitlines()[-1])
    assert rec["platform"] == "cpu" and rec["value"] == 0.0
    assert "--rehearsal" in rec["error"] and rec["phase"] == "device"


def test_bench_io_harness():
    """Standalone input-pipeline benchmark (parallel decode pool)."""
    out = run_example("tools/bench_io.py", "--num-images", "64",
                      "--batch-size", "16", "--image-size", "64",
                      "--threads", "4", "--epochs", "1")
    assert "decode+augment throughput" in out


def test_bandwidth_harness():
    sys.path.insert(0, os.path.join(REPO, "tools", "bandwidth"))
    import importlib
    measure = importlib.import_module("measure")
    gbps = measure.run("local", size_mb=1, num_keys=2, repeats=2)
    assert gbps > 0


def test_predictor_roundtrip(tmp_path):
    """c_predict_api parity: save a trained module, reload through the
    Predictor, logits must match."""
    from mxnet_tpu import predictor
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=8, name="fc1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.FullyConnected(net, num_hidden=3, name="fc2")
    net = mx.sym.SoftmaxOutput(net, name="softmax")

    x = np.random.RandomState(0).randn(20, 6).astype("f")
    y = np.zeros(20, "f")
    mod = mx.mod.Module(net, context=mx.cpu())
    it = mx.io.NDArrayIter(x, y, batch_size=10)
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params()
    ref = mod.predict(it).asnumpy()

    prefix = str(tmp_path / "model")
    arg_params, aux_params = mod.get_params()
    mx.model.save_checkpoint(prefix, 0, net, arg_params, aux_params)

    pred = predictor.create(prefix + "-symbol.json",
                            prefix + "-0000.params",
                            {"data": (10, 6)})
    pred.set_input("data", x[:10])
    pred.forward()
    out = pred.get_output(0)
    assert_almost_equal(out, ref[:10], rtol=1e-4, atol=1e-5)

    # cross-device deployment (on-chip finding, CONSISTENCY_r04): params
    # load on the default CPU context but the predictor targets another
    # device — MXPredCreate copies the blob to the requested device, and
    # set_input copies host inputs likewise
    pred2 = predictor.create(prefix + "-symbol.json",
                             prefix + "-0000.params",
                             {"data": (10, 6)}, dev=mx.cpu(2))
    pred2.set_input("data", mx.nd.array(x[:10], ctx=mx.cpu(0)))
    pred2.forward()
    assert_almost_equal(pred2.get_output(0), ref[:10], rtol=1e-4,
                        atol=1e-5)


def test_launch_local(tmp_path):
    """tools/launch.py forks N workers with the rank env contract."""
    script = tmp_path / "worker.py"
    # write per-rank files to avoid interleaved-stdout flakiness
    script.write_text(
        "import os, pathlib\n"
        "rank = os.environ['MXT_PROC_ID']\n"
        "pathlib.Path(f'rank{rank}.txt').write_text(\n"
        "    f\"{rank} of {os.environ['MXT_NUM_PROC']}\")\n")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "launch.py"),
         "-n", "2", sys.executable, str(script)],
        env=ENV, cwd=str(tmp_path), capture_output=True, text=True,
        timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert (tmp_path / "rank0.txt").read_text() == "0 of 2"
    assert (tmp_path / "rank1.txt").read_text() == "1 of 2"


def test_dcgan_example():
    out = run_example("example/gluon/dcgan.py", "--epochs", "1",
                      "--num-examples", "32", "--batch-size", "16",
                      "--ngf", "8", "--ndf", "8")
    assert "lossD" in out


def test_word_lm_example():
    out = run_example("example/gluon/word_language_model.py", "--epochs", "1",
                      "--num-hidden", "16", "--num-embed", "16",
                      "--num-layers", "1", "--bptt", "10", timeout=420)
    assert "perplexity" in out
    # and the stateful (hidden-carrying) greedy decode demo emitted
    gen = [l for l in out.splitlines() if l.startswith("generated:")][0]
    assert len(gen.split()) == 21, gen  # 'generated:' + 20 tokens


def test_long_context_ring_lm_example():
    """example/long-context: ring-attention training over a 4-device sp
    mesh (eager autograd through the sharded kernels) + the
    sequence-sharded KV decode demo."""
    out = run_example("example/long-context/train_ring_lm.py",
                      "--devices", "4", "--seq-len", "32", "--epochs", "1",
                      "--max-batches", "12", "--corpus-len", "3000",
                      timeout=520)
    line = [l for l in out.splitlines() if "final ppl" in l][0]
    # "final ppl X last-batch ppl Y (uniform 32.0)" — the mean includes
    # the untrained first batches; the LAST batch must beat uniform
    # (the learning signal: sharded-attention grads actually train)
    last_ppl = float(line.split()[5])
    assert np.isfinite(last_ppl) and last_ppl < 32.0, out
    gen = [l for l in out.splitlines() if l.startswith("generated:")][0]
    assert len(gen.split()) == 13, gen  # 'generated:' + 12 tokens


def test_ssd_example():
    # rec path: packs a det .rec, trains via ImageDetRecordIter, VOC mAP
    out = run_example("example/ssd/train_ssd.py", "--epochs", "1",
                      "--num-examples", "64", "--batch-size", "8")
    assert "detections kept" in out
    assert "VOC07 mAP" in out


def test_ssd_example_synthetic():
    out = run_example("example/ssd/train_ssd.py", "--epochs", "1",
                      "--data-source", "synthetic",
                      "--batches-per-epoch", "4", "--batch-size", "8")
    assert "detections kept" in out


def test_torch_bridge():
    pytest.importorskip("torch")
    from mxnet_tpu import torch as mxt
    x = nd.array(np.array([-1.0, 0.5, 2.0], "f"))
    y = mxt.relu(x)
    assert isinstance(y, nd.NDArray)
    assert_almost_equal(y.asnumpy(), np.array([0.0, 0.5, 2.0], "f"))
    import torch as t
    mm = mxt.wrap(t.mm)
    a = nd.array(np.eye(3, dtype="f") * 2)
    out = mm(a, a)
    assert_almost_equal(out.asnumpy(), np.eye(3, dtype="f") * 4)


def test_aot_export_roundtrip(tmp_path):
    """amalgamation-analog deployment: serialize StableHLO, reload, logits
    match the live module."""
    from mxnet_tpu import export as mexport
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=4, name="fc")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    x = np.random.RandomState(0).randn(5, 3).astype("f")
    mod = mx.mod.Module(net, context=mx.cpu())
    it = mx.io.NDArrayIter(x, np.zeros(5, "f"), batch_size=5)
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params()
    ref = mod.predict(it).asnumpy()
    arg_params, aux_params = mod.get_params()
    prefix = str(tmp_path / "m")
    mx.model.save_checkpoint(prefix, 0, net, arg_params, aux_params)
    mexport.export_checkpoint(prefix, 0, {"data": (5, 3)},
                              str(tmp_path / "aot"))
    m = mexport.load_model(str(tmp_path / "aot"))
    out = m(x)[0].asnumpy()
    assert_almost_equal(out, ref, rtol=1e-4, atol=1e-5)


def test_rtc_pallas_module():
    """RTC analog: runtime-compile a user kernel from source."""
    mod = mx.rtc.PallasModule("""
import jax.numpy as jnp

def axpy(a, x, y):
    return a * x + y
""")
    k = mod.get_kernel("axpy")
    out = k.launch([nd.array([2.0]), nd.array([3.0]), nd.array([1.0])])
    assert_almost_equal(out.asnumpy(), np.array([7.0], "f"))
    with pytest.raises(mx.base.MXNetError):
        mx.rtc.PallasModule("__global__ void k() {}")


def test_matrix_factorization_example():
    out = run_example("example/recommenders/matrix_factorization.py",
                      "--epochs", "2", "--num-samples", "4000")
    assert "final RMSE" in out


def test_neural_style_example(tmp_path):
    out = run_example("example/neural-style/nstyle.py",
                      "--size", "64", "--max-num-epochs", "4",
                      "--log-every", "2",
                      "--output", str(tmp_path / "out.png"))
    line = [l for l in out.splitlines() if "final loss" in l][0]
    assert np.isfinite(float(line.rsplit(" ", 1)[-1]))


def test_rcnn_end2end_example():
    out = run_example("example/rcnn/train_end2end.py",
                      "--num-epochs", "1", "--batches-per-epoch", "2")
    line = [l for l in out.splitlines() if "final rpn_cls" in l][0]
    vals = [float(v) for v in line.split()[2::2]]
    assert all(np.isfinite(v) for v in vals), out


def test_speech_ctc_example():
    out = run_example("example/speech_recognition/train_speech.py",
                      "--num-epochs", "10", "--num-utts", "48",
                      "--lr", "5e-3")
    line = [l for l in out.splitlines() if "final ctc-loss" in l][0]
    cer = float(line.rsplit(" ", 1)[-1])
    assert cer < 0.9, out  # decodes are emerging (CER 0 by epoch ~20)


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


def test_actor_critic_example():
    out = run_example("example/gluon/actor_critic.py",
                      "--episodes", "10", "--log-every", "5")
    line = [l for l in out.splitlines() if "final running length" in l][0]
    # episodes must actually roll out (a policy collapse or a rollout
    # crash drags the EMA toward 1-2 steps); learning itself is asserted
    # by the longer seeded run in the example docstring, not a CI smoke
    assert float(line.rsplit(" ", 1)[-1]) > 8.0, out


def test_tree_lstm_example():
    out = run_example("example/gluon/tree_lstm.py",
                      "--num-trees", "40", "--epochs", "2")
    line = [l for l in out.splitlines() if "final acc" in l][0]
    # seeded run reaches 0.60 by epoch 2; above-chance composition
    assert float(line.rsplit(" ", 1)[-1]) > 0.52, out


def test_autoencoder_example():
    out = run_example("example/autoencoder/autoencoder.py",
                      "--num-epochs", "4", "--num-examples", "500")
    line = [l for l in out.splitlines() if "final recon mse" in l][0]
    assert float(line.rsplit(" ", 1)[-1]) < 0.05, out


def test_fgsm_adversary_example():
    out = run_example("example/adversary/fgsm.py",
                      "--epochs", "8", "--num-test", "100")
    line = [l for l in out.splitlines() if "clean accuracy" in l][0]
    clean = float(line.split()[2])
    adv = float(line.split()[5])
    # trained net learns the synthetic digits; FGSM must hurt it
    assert clean > 0.8, out
    assert adv < clean - 0.3, out


def test_multi_task_example():
    out = run_example("example/multi-task/multi_task.py",
                      "--num-epochs", "8")
    line = [l for l in out.splitlines() if "final digit-acc" in l][0]
    digit = float(line.split()[2])
    parity = float(line.split()[4])
    assert digit > 0.6 and parity > 0.6, out


def test_transformer_lm_example():
    out = run_example("example/gluon/transformer_lm.py",
                      "--epochs", "2", "--corpus-len", "4000",
                      "--max-batches", "25")
    line = [l for l in out.splitlines() if "final ppl" in l][0]
    ppl = float(line.split()[2])
    # must beat the uniform baseline (vocab=32) after 2 epochs
    assert ppl < 30.0, out
    # and the KV-cache decode demo emitted tokens
    gen = [l for l in out.splitlines() if l.startswith("generated:")][0]
    assert len(gen.split()) == 17, gen  # 'generated:' + 16 tokens


def test_bi_lstm_sort_example():
    # hybridized fused-RNN path: 12 epochs run in ~15s on CPU
    out = run_example("example/bi-lstm-sort/sort_io.py",
                      "--num-epochs", "12", "--num-examples", "2000",
                      "--vocab", "30")
    line = [l for l in out.splitlines() if "final sort accuracy" in l][0]
    assert float(line.rsplit(" ", 1)[-1]) > 0.5, out


def test_cnn_text_classification_example():
    out = run_example("example/cnn_text_classification/text_cnn.py",
                      "--num-epochs", "3", "--num-examples", "1000")
    line = [l for l in out.splitlines() if "dev accuracy" in l][0]
    assert float(line.rsplit(" ", 1)[-1]) > 0.7, out


def test_nce_loss_example():
    out = run_example("example/nce-loss/nce_lm.py",
                      "--num-epochs", "3", "--num-tokens", "8000")
    line = [l for l in out.splitlines() if "true-word top-1" in l][0]
    assert float(line.rsplit(" ", 1)[-1]) > 0.8, out


def test_fcn_xs_example():
    out = run_example("example/fcn-xs/fcn_xs.py",
                      "--num-epochs", "10", "--num-examples", "96")
    line = [l for l in out.splitlines() if "final pixel accuracy" in l][0]
    acc = float(line.split()[3])
    fg = float(line.split()[-1])
    assert acc > 0.85 and fg > 0.15, out


def test_stochastic_depth_example():
    out = run_example("example/stochastic-depth/sd_cifar10.py",
                      "--num-epochs", "4", "--num-examples", "800")
    lines = [l for l in out.splitlines() if "loss=" in l]
    first = float(lines[0].split("loss=")[1].split()[0])
    last = float(lines[-1].split("loss=")[1].split()[0])
    assert last < first * 0.8, out  # training signal through random depth


def test_dec_example():
    out = run_example("example/deep-embedded-clustering/dec.py",
                      "--num-examples", "800", "--pretrain-epochs", "12",
                      "--dec-epochs", "4")
    km = [l for l in out.splitlines() if "k-means init" in l][0]
    fin = [l for l in out.splitlines() if "final cluster" in l][0]
    km_acc = float(km.rsplit(" ", 1)[-1])
    fin_acc = float(fin.rsplit(" ", 1)[-1])
    # refinement must not collapse the k-means solution
    assert fin_acc > max(0.3, km_acc - 0.1), out


def test_captcha_ocr_example():
    out = run_example("example/captcha/captcha_ocr.py",
                      "--num-epochs", "3", "--num-examples", "600",
                      "--lr", "3e-3")
    lines = [l for l in out.splitlines() if "ctc-loss=" in l]
    first = float(lines[0].split("ctc-loss=")[1].split()[0])
    last = float(lines[-1].split("ctc-loss=")[1].split()[0])
    assert last < first, out  # CTC is slow to exit the blank phase; the
    # 30-epoch default reaches real decodes (see example docstring)


def test_dsd_example():
    out = run_example("example/dsd/dsd_mlp.py",
                      "--epochs", "3", "--num-examples", "1000")
    line = [l for l in out.splitlines() if "accuracy dense" in l][0]
    accs = [float(v) for v in line.split()[2:7:2]]
    assert all(a > 0.8 for a in accs), out  # all three phases stay strong
    density = float(line.split()[-1].rstrip(")"))
    assert density < 0.5, out  # pruning really happened


def test_module_api_gallery():
    out = run_example("example/module/demo_modules.py",
                      "--num-epochs", "8")
    line = [l for l in out.splitlines() if "val accuracies" in l][0]
    vals = [float(v) for v in line.split()[3::2]]
    assert all(v > 0.8 for v in vals), out


def test_bayesian_sgld_example():
    out = run_example("example/bayesian-methods/bdk_demo.py",
                      "--burn-in", "300", "--num-samples", "30")
    rmse_line = [l for l in out.splitlines() if "posterior-mean RMSE" in l][0]
    std_line = [l for l in out.splitlines() if "predictive std" in l][0]
    rmse = float(rmse_line.rsplit(" ", 1)[-1])
    vals = std_line.split()
    data_std, extrap_std = float(vals[3]), float(vals[7])
    assert rmse < 0.3, out                      # fits the observed region
    assert extrap_std > data_std, out           # uncertainty grows off-data


def test_vae_example():
    out = run_example("example/vae/vae.py",
                      "--num-epochs", "8", "--num-examples", "800")
    lines = [l for l in out.splitlines() if "recon=" in l]
    first = float(lines[0].split("recon=")[1].split()[0])
    line = [l for l in out.splitlines() if l.startswith("final recon")][0]
    final = float(line.split()[2])
    assert final < first * 0.9, out  # ELBO reconstruction term improves
    assert np.isfinite(float(line.split()[6])), out  # gen-mean


def test_kill_mxnet_tool(tmp_path):
    """kill_mxnet finds and terminates MXT_PROC_ID-tagged workers."""
    import signal
    import time
    worker = tmp_path / "w.py"
    worker.write_text("import time\ntime.sleep(60)\n")
    proc = subprocess.Popen([sys.executable, str(worker)],
                            env={**ENV, "MXT_PROC_ID": "0",
                                 "MXT_NUM_PROC": "1"})
    try:
        time.sleep(1.0)
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "kill_mxnet.py"),
             "--pattern", "w.py"],
            env=ENV, capture_output=True, text=True, timeout=60)
        assert "killing" in out.stdout, out.stdout + out.stderr
        proc.wait(timeout=10)
        assert proc.returncode == -signal.SIGTERM
    finally:
        if proc.poll() is None:
            proc.kill()


def test_rnn_time_major_example():
    out = run_example("example/rnn-time-major/readme_demo.py",
                      "--num-epochs", "3", "--corpus", "8000")
    line = [l for l in out.splitlines() if "final TNC perplexity" in l][0]
    ppl = float(line.rsplit(" ", 1)[-1])
    assert ppl < 48.0, out  # well under the vocab-50 uniform baseline


# ------------------------------------------------- round-4 example families

def test_dcgan_example():
    out = run_example("example/gan/dcgan.py", "--num-epochs", "2",
                      "--batches-per-epoch", "4")
    assert "dcgan done" in out


def test_dqn_example():
    out = run_example("example/reinforcement-learning/dqn.py",
                      "--episodes", "100", timeout=560)
    line = [l for l in out.splitlines() if "dqn done" in l][0]
    early, late = (float(t.split("=")[1]) for t in line.split()[2:4])
    assert late > early, out


def test_svm_mnist_example():
    out = run_example("example/svm_mnist/svm_mnist.py",
                      "--num-epochs", "6", timeout=560)
    acc = float([l for l in out.splitlines()
                 if "validation accuracy" in l][0].rsplit(" ", 1)[-1])
    assert acc > 0.85, out


def test_python_howto_examples():
    assert "multiple outputs OK" in \
        run_example("example/python-howto/multiple_outputs.py")
    assert "monitor captured" in \
        run_example("example/python-howto/monitor_weights.py")


def test_torch_bridge_example():
    out = run_example("example/torch/torch_bridge.py", timeout=560)
    acc = float([l for l in out.splitlines()
                 if "accuracy" in l][0].rsplit(" ", 1)[-1])
    assert acc > 0.8, out


def test_lstm_ocr_ctc_example():
    out = run_example("example/ctc/lstm_ocr.py", "--num-epochs", "12",
                      "--batches-per-epoch", "12", "--lr", "0.02",
                      timeout=560)
    acc = float([l for l in out.splitlines()
                 if "exact-sequence accuracy" in l][0].rsplit(" ", 1)[-1])
    assert acc > 0.8, out


def test_chinese_text_cnn_example():
    out = run_example(
        "example/cnn_chinese_text_classification/text_cnn.py",
        "--num-epochs", "6", "--num-examples", "1024", timeout=560)
    acc = float([l for l in out.splitlines()
                 if "final validation accuracy" in l][0].rsplit(" ", 1)[-1])
    assert acc > 0.75, out


def test_toy_ctc_warpctc_example():
    out = run_example("example/warpctc/toy_ctc.py", "--num-epochs", "14",
                      "--batches", "12", "--frames", "4", timeout=560)
    acc = float([l for l in out.splitlines()
                 if "sequence accuracy" in l][0].rsplit(" ", 1)[-1])
    assert acc > 0.6, out


def test_utils_get_data_cache(tmp_path):
    # second call must hit the on-disk cache and return identical arrays
    import example.utils.get_data as gd
    old = gd._CACHE
    gd._CACHE = str(tmp_path)
    try:
        a = gd.get_mnist(num_examples=64)
        b = gd.get_mnist(num_examples=64)
        assert np.array_equal(a["train_data"], b["train_data"])
        tr, va = gd.mnist_iterator(batch_size=8, num_examples=64)
        batch = next(iter(tr))
        assert batch.data[0].shape == (8, 1, 28, 28)
    finally:
        gd._CACHE = old


def test_getting_started_notebook(tmp_path):
    """Execute every code cell of the tutorial notebook in order (the
    reference's notebooks live in an external repo; ours is CI-run)."""
    import json
    nb_path = os.path.join(REPO, "example/notebooks/getting_started.ipynb")
    with open(nb_path) as f:
        nb = json.load(f)
    script = "\n\n".join("".join(c["source"]) for c in nb["cells"]
                         if c["cell_type"] == "code")
    p = tmp_path / "nb_script.py"
    p.write_text(script)
    proc = subprocess.run([sys.executable, str(p)], env=ENV,
                          cwd=os.path.join(REPO, "example/notebooks"),
                          capture_output=True, text=True, timeout=560)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "module val acc" in proc.stdout


def test_memcost_example():
    out = run_example("example/memcost/inception_memcost.py",
                      "--batch-size", "4", "--image-size", "64",
                      timeout=560)
    import json as _json
    line = [l for l in out.splitlines() if l.startswith("{")][-1]
    d = _json.loads(line)
    # training needs more transient memory than inference
    assert d["train_mb"] > d["forward_only_mb"], d


def test_kaggle_ndsb1_pipeline(tmp_path):
    out = run_example("example/kaggle-ndsb1/train_dsb.py",
                      "--num-epochs", "8", "--num-examples", "1536",
                      "--classes", "8", "--submission",
                      str(tmp_path / "sub.csv"), timeout=560)
    acc = float([l for l in out.splitlines()
                 if "validation accuracy" in l][0].rsplit(" ", 1)[-1])
    assert acc > 0.5, out
    header = (tmp_path / "sub.csv").read_text().splitlines()[0]
    assert header.startswith("image,class_0")


def test_kaggle_ndsb2_crps():
    out = run_example("example/kaggle-ndsb2/Train.py",
                      "--num-epochs", "6", "--num-examples", "768",
                      timeout=560)
    line = [l for l in out.splitlines() if "ndsb2 CRPS" in l][0]
    crps_v = float(line.split()[2])
    mae = float(line.split()[5])
    assert crps_v < 0.05, out
    assert mae < 40, out


def test_adversarial_vae_example():
    out = run_example("example/mxnet_adversarial_vae/vaegan.py",
                      "--num-epochs", "3", "--num-examples", "256",
                      timeout=560)
    lines = [l for l in out.splitlines() if l.startswith("epoch ")]
    assert len(lines) == 3, out
    d0 = float(lines[0].split()[3])
    d2 = float(lines[2].split()[3])
    assert d2 < d0, out  # discriminator is learning
    assert "feat-recon first->last" in out


def test_speech_demo_example(tmp_path):
    post = tmp_path / "post.npz"
    out = run_example("example/speech-demo/train_lstm.py",
                      "--num-epochs", "4", "--posteriors", str(post),
                      timeout=560)
    acc = float([l for l in out.splitlines()
                 if "framewise accuracy" in l][0].rsplit(" ", 1)[-1])
    assert acc > 0.6, out
    z = np.load(post)
    assert any(k.startswith("bucket_") for k in z.files)


@mx.test_utils.retry(3)
def test_caffe_prototxt_example():
    # retry: unseeded init makes the 3-epoch accuracy occasionally dip
    # under CI CPU contention
    out = run_example("example/caffe/train_caffe_prototxt.py",
                      "--num-epochs", "3", timeout=560)
    acc = float([l for l in out.splitlines()
                 if "validation accuracy" in l][0].rsplit(" ", 1)[-1])
    assert acc > 0.7, out


def test_train_imagenet_rec_device_augment(tmp_path):
    """The north-star rec-file path end to end: pack a tiny JPEG .rec,
    train resnet-8 on it with the device-augment input split (the
    default), bf16 data dtype."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import importlib
    bench_io = importlib.import_module("bench_io")
    rec = str(tmp_path / "tiny.rec")
    bench_io.pack(rec, 96, 40)
    out = run_example("example/image-classification/train_imagenet.py",
                      "--data-train", rec, "--network", "resnet",
                      "--num-layers", "8", "--num-classes", "10",
                      "--num-examples", "96", "--image-shape", "3,32,32",
                      "--batch-size", "32", "--num-epochs", "1",
                      "--lr", "0.05", "--device-augment", "1",
                      timeout=560)
    assert "Epoch[0]" in out, out


def test_sparse_benchmark_harness():
    out = run_example("benchmark/python/sparse/sparse_bench.py",
                      "--quick", timeout=560)
    assert "sparse bench done" in out
    assert "grad stype=row_sparse" in out  # rows-only path exercised


def test_setup_py_metadata():
    proc = subprocess.run([sys.executable, os.path.join(REPO, "setup.py"),
                           "--version"], env=ENV, cwd=REPO,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert proc.stdout.strip().startswith("1."), proc.stdout


def test_tutorial_template_notebook(tmp_path):
    import json
    nb = json.load(open(os.path.join(REPO,
                                     "example/MXNetTutorialTemplate.ipynb")))
    script = "\n\n".join("".join(c["source"]) for c in nb["cells"]
                         if c["cell_type"] == "code")
    p = tmp_path / "tpl.py"
    p.write_text(script)
    proc = subprocess.run([sys.executable, str(p)], env=ENV,
                          cwd=str(tmp_path), capture_output=True,
                          text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "accuracy" in proc.stdout


def test_gen_op_docs_tool(tmp_path):
    target = str(tmp_path / "api_ops.md")
    out = run_example("tools/gen_op_docs.py", target, timeout=300)
    assert "wrote" in out
    doc = open(target).read()
    assert "## `Convolution`" in doc and "num_filter" in doc


def test_ssd_deploy_predictor(tmp_path):
    """Train tiny SSD -> save -> deploy.py strips the training head ->
    the deploy checkpoint serves through the Predictor (c_predict_api
    role) and yields (N, anchors, 6) decoded detections."""
    prefix = str(tmp_path / "ssd")
    run_example("example/ssd/train_ssd.py", "--epochs", "1",
                "--batches-per-epoch", "6", "--data-source", "synthetic",
                "--save-prefix", prefix, timeout=560)
    out = run_example("example/ssd/deploy.py", "--prefix", prefix,
                      timeout=560)  # epoch auto-detected (newest)
    assert "deployed" in out, out

    from mxnet_tpu import predictor
    sym_json = open(prefix + "-deploy-symbol.json").read()
    params = open(prefix + "-deploy-0001.params", "rb").read()
    pred = predictor.Predictor(sym_json, params,
                               {"data": (2, 3, 32, 32)})
    x = np.random.RandomState(0).normal(0, 1, (2, 3, 32, 32)).astype("f")
    pred.set_input("data", x)
    pred.forward()
    det = pred.get_output(0)
    assert det.ndim == 3 and det.shape[0] == 2 and det.shape[2] == 6, \
        det.shape


def test_rec2idx_tool(tmp_path):
    """rec2idx builds an index a MXIndexedRecordIO can random-access
    (parity: tools/rec2idx.py IndexCreator)."""
    from mxnet_tpu.recordio import MXRecordIO, MXIndexedRecordIO
    rec = str(tmp_path / "t.rec")
    w = MXRecordIO(rec, "w")
    payloads = [b"rec%d" % i * (i + 1) for i in range(7)]
    for p in payloads:
        w.write(p)
    w.close()
    out = run_example("tools/rec2idx.py", rec, str(tmp_path / "t.idx"))
    assert "7 records indexed" in out
    r = MXIndexedRecordIO(str(tmp_path / "t.idx"), rec, "r")
    for i in (6, 0, 3):
        assert r.read_idx(i) == payloads[i]
    r.close()


def test_diagnose_tool():
    out = run_example("tools/diagnose.py", timeout=180)
    for section in ("Platform Info", "Dependency Versions",
                    "MXNet-TPU Info", "Device Info"):
        assert section in out, out
    assert "jax" in out
    assert "IMPORT FAILED" not in out

    # a user runs it from anywhere with NO PYTHONPATH (the tool must
    # find the package relative to itself, like the reference's)
    env = {k: v for k, v in ENV.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "diagnose.py")],
        env=env, cwd="/tmp", capture_output=True, text=True, timeout=180)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "IMPORT FAILED" not in proc.stdout, proc.stdout
    assert "Version" in proc.stdout


def test_ipynb2md_tool(tmp_path):
    src = os.path.join(REPO, "example/notebooks/getting_started.ipynb")
    dst = str(tmp_path / "g.md")
    out = run_example("tools/ipynb2md.py", src, "-o", dst)
    assert "wrote" in out
    md = open(dst).read()
    assert "```python" in md and "mxnet_tpu" in md


def test_every_example_dir_is_ci_covered():
    """Breadth guard: every example/ directory must be exercised by at
    least one test in this file (or hold only docs) — a new example dir
    without a smoke test fails here, and so does deleting a test while
    keeping the dir."""
    import inspect
    this = open(os.path.abspath(__file__)).read()
    # needles must match a test OTHER than this one — otherwise the
    # needle literals below make every lookup vacuously true
    this = this.replace(
        inspect.getsource(test_every_example_dir_is_ci_covered), "")
    # dirs exercised through an import rather than a script path
    covered_elsewhere = {"utils": "example.utils.get_data"}
    missing = []
    for d in sorted(os.listdir(os.path.join(REPO, "example"))):
        path = os.path.join(REPO, "example", d)
        if not os.path.isdir(path):
            continue
        has_py = any(f.endswith(".py") for _, _, fs in os.walk(path)
                     for f in fs)
        if not has_py:
            continue  # docs-only dir
        needles = [f"example/{d}/"]
        if d in covered_elsewhere:
            needles.append(covered_elsewhere[d])
        if not any(n in this for n in needles):
            missing.append(d)
    assert not missing, f"example dirs without CI coverage: {missing}"


def test_accnn_fc_and_conv_factorization(tmp_path):
    """tools/accnn low-rank acceleration: full-rank factorization is
    numerically exact; reduced rank shrinks weights (parity:
    tools/accnn acc_fc/acc_conv Jaderberg scheme)."""
    import sys as _sys
    accnn = os.path.join(REPO, "tools", "accnn")
    _sys.path.insert(0, accnn)
    try:
        from acc_fc import factorize_fc
        from acc_conv import factorize_conv
        import mxnet_tpu as mx
        from mxnet_tpu.io import DataDesc
        rs = np.random.RandomState(0)
        net = mx.sym.Convolution(mx.sym.Variable("data"), num_filter=8,
                                 kernel=(3, 3), pad=(1, 1), name="c1")
        net = mx.sym.Activation(net, act_type="relu")
        net = mx.sym.SoftmaxOutput(
            mx.sym.FullyConnected(mx.sym.Flatten(net), num_hidden=4,
                                  name="f1"), name="softmax")
        mod = mx.mod.Module(net)
        mod.bind(data_shapes=[DataDesc("data", (2, 3, 12, 12),
                                       np.float32)],
                 label_shapes=[DataDesc("softmax_label", (2,),
                                        np.float32)])
        mod.init_params(mx.init.Xavier())
        arg, aux = mod.get_params()
        X = rs.normal(0, 1, (2, 3, 12, 12)).astype("f")

        def fwd(sym_, args_):
            ex = sym_.simple_bind(ctx=mx.cpu(), grad_req="null",
                                  data=(2, 3, 12, 12))
            for k, v in args_.items():
                if k in ex.arg_dict:
                    ex.arg_dict[k][:] = v.asnumpy()
            ex.arg_dict["data"][:] = X
            return ex.forward(is_train=False)[0].asnumpy()

        base = fwd(net, arg)
        s1, a1, _ = factorize_conv(net, arg, ranks={"c1": 9})  # full
        s2, a2, _ = factorize_fc(s1, a1, ranks={"f1": 4})      # full
        np.testing.assert_allclose(fwd(s2, a2), base, atol=1e-4)
        s3, a3, r3 = factorize_conv(net, arg, energy=0.8)
        assert r3["c1"] < 9  # genuinely reduced
        out = fwd(s3, a3)
        assert np.isfinite(out).all()
    finally:
        _sys.path.remove(accnn)


def test_accnn_dilated_and_explicit_ranks(tmp_path):
    """Dilation rides the factor pair it belongs to, and explicit
    --ranks touches ONLY the named layers."""
    import sys as _sys
    accnn = os.path.join(REPO, "tools", "accnn")
    _sys.path.insert(0, accnn)
    try:
        from acc_conv import factorize_conv
        import json as _json
        import mxnet_tpu as mx
        from mxnet_tpu.io import DataDesc
        rs = np.random.RandomState(1)
        net = mx.sym.Convolution(mx.sym.Variable("data"), num_filter=6,
                                 kernel=(3, 3), pad=(2, 2),
                                 dilate=(2, 2), name="cd")
        net = mx.sym.Convolution(net, num_filter=4, kernel=(3, 3),
                                 pad=(1, 1), name="ck")
        net = mx.sym.SoftmaxOutput(
            mx.sym.FullyConnected(mx.sym.Flatten(net), num_hidden=3,
                                  name="fx"), name="softmax")
        mod = mx.mod.Module(net)
        mod.bind(data_shapes=[DataDesc("data", (2, 3, 12, 12),
                                       np.float32)],
                 label_shapes=[DataDesc("softmax_label", (2,),
                                        np.float32)])
        mod.init_params(mx.init.Xavier())
        arg, aux = mod.get_params()
        X = rs.normal(0, 1, (2, 3, 12, 12)).astype("f")

        def fwd(sym_, args_):
            ex = sym_.simple_bind(ctx=mx.cpu(), grad_req="null",
                                  data=(2, 3, 12, 12))
            for k, v in args_.items():
                if k in ex.arg_dict:
                    ex.arg_dict[k][:] = v.asnumpy()
            ex.arg_dict["data"][:] = X
            return ex.forward(is_train=False)[0].asnumpy()

        base = fwd(net, arg)
        # full-rank factorization of ONLY the dilated conv stays exact
        s1, a1, _ = factorize_conv(net, arg, ranks={"cd": 9})
        np.testing.assert_allclose(fwd(s1, a1), base, atol=1e-4)
        nodes = _json.loads(s1.tojson())["nodes"]
        by_name = {n["name"]: n for n in nodes}
        assert by_name["cd_v"]["attrs"]["dilate"] == "(2, 1)"
        assert by_name["cd"]["attrs"]["dilate"] == "(1, 2)"
        # the unnamed conv is untouched
        assert "ck_v" not in by_name and "ck_weight" in a1
    finally:
        _sys.path.remove(accnn)


def test_benchmark_sweep_driver(tmp_path):
    """The training-throughput sweep driver (reference benchmark.py):
    dry-run lists the planned cells; one tiny real cell produces a
    parsed img/s row and a JSONL report."""
    out = run_example("example/image-classification/benchmark.py",
                      "--dry-run", "--networks", "resnet-18,mobilenet",
                      "--batch-sizes", "8,16")
    assert out.count("train_imagenet.py") == 4
    report = str(tmp_path / "report.jsonl")
    out = run_example("example/image-classification/benchmark.py",
                      "--networks", "mlp", "--batch-sizes", "8",
                      "--image-size", "28", "--batches", "3",
                      "--timeout", "360", "--output", report,
                      timeout=400)
    assert "| mlp | 8 |" in out
    import json as _json
    rec = _json.loads(open(report).read().splitlines()[0])
    assert rec["rc"] == 0 and rec["img_s"] > 0, rec


def test_lm_mfu_probe_smoke():
    """experiments/lm_mfu_probe.py (transformer-LM MFU window leg):
    smoke config must train (finite decreasing-ish loss) and emit one
    JSON line with the tok/s + FLOPs accounting fields."""
    import json
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "experiments/lm_mfu_probe.py")],
        env={**ENV, "MXT_LM_PROBE_SMOKE": "1"}, cwd=REPO,
        capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rec["metric"] == "transformer_lm_train_throughput"
    assert rec["value"] > 0 and rec["train_tflops_per_step"] >= 0
    assert np.isfinite(rec["loss_first"]) and np.isfinite(rec["loss_final"])
    # 2 smoke steps on random tokens: loss must move and not blow up
    assert rec["loss_final"] < rec["loss_first"] + 1.0


def test_decode_probe_smoke():
    """experiments/decode_probe.py (decode window leg): both decode
    strategies must run, agree token-for-token, and emit JSON rows."""
    import json
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "experiments/decode_probe.py")],
        env={**ENV, "MXT_DECODE_PROBE_SMOKE": "1"}, cwd=REPO,
        capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    rows = [json.loads(ln) for ln in proc.stdout.strip().splitlines()
            if ln.startswith("{")]
    metrics = {r["metric"]: r for r in rows}
    assert metrics["decode_static_throughput"]["value"] > 0
    assert metrics["decode_kv_cache_throughput"]["value"] > 0
    assert metrics["decode_paths_agree"]["value"] is True


def test_benchmark_score_watchdogged(tmp_path):
    """benchmark_score.py (VERDICT r4 #6): per-cell subprocess watchdogs
    + --out durable partials — a per-cell timeout records an error row
    instead of killing the run, and good cells still land."""
    import json
    out = tmp_path / "score.jsonl"
    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "example/image-classification",
                      "benchmark_score.py"),
         "--networks", "squeezenet", "--batch-sizes", "1",
         "--repeats", "2", "--cell-timeout", "240",
         "--out", str(out)],
        env=ENV, capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    rows = [json.loads(l) for l in out.read_text().splitlines()]
    assert rows and rows[0]["network"] == "squeezenet"
    assert rows[0]["img_s"] > 0

    # a hopeless per-cell budget must yield an error row, rc 0
    out2 = tmp_path / "score2.jsonl"
    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "example/image-classification",
                      "benchmark_score.py"),
         "--networks", "squeezenet", "--batch-sizes", "1",
         "--repeats", "2", "--cell-timeout", "3",
         "--out", str(out2)],
        env=ENV, capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    rows = [json.loads(l) for l in out2.read_text().splitlines()]
    assert rows and "error" in rows[0], rows
