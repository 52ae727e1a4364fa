"""The surfaces around training: tools/, notebooks, packaging, deployment
(predictor, AOT export, RTC), the torch bridge, and the guard that every
example directory has a smoke test in one of the test_examples_*.py files."""
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.test_utils import assert_almost_equal

from example_runner import (ENV, REPO, notebook_script, run_example,
                            run_python)


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
    run_example("tools/launch.py", "-n", "2", sys.executable, str(script),
                cwd=str(tmp_path), timeout=120)
    assert (tmp_path / "rank0.txt").read_text() == "0 of 2"
    assert (tmp_path / "rank1.txt").read_text() == "1 of 2"


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
        out = run_example("tools/kill_mxnet.py", "--pattern", "w.py",
                          cwd=REPO, timeout=60)
        assert "killing" in out, out
        proc.wait(timeout=10)
        assert proc.returncode == -signal.SIGTERM
    finally:
        if proc.poll() is None:
            proc.kill()


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
    script = notebook_script("example/notebooks/getting_started.ipynb",
                             tmp_path / "nb_script.py")
    proc = run_python([script],
                      cwd=os.path.join(REPO, "example/notebooks"))
    assert "module val acc" in proc.stdout


def test_setup_py_metadata():
    proc = run_python(["setup.py", "--version"], cwd=REPO, timeout=120)
    assert proc.stdout.strip().startswith("1."), proc.stdout


def test_tutorial_template_notebook(tmp_path):
    script = notebook_script("example/MXNetTutorialTemplate.ipynb",
                             tmp_path / "tpl.py")
    proc = run_python([script], cwd=str(tmp_path))
    assert "accuracy" in proc.stdout


def test_gen_op_docs_tool(tmp_path):
    target = str(tmp_path / "api_ops.md")
    out = run_example("tools/gen_op_docs.py", target)
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
                "--save-prefix", prefix)
    out = run_example("example/ssd/deploy.py", "--prefix", prefix)  # epoch auto-detected (newest)
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
    proc = run_python([os.path.join(REPO, "tools", "diagnose.py")],
                      env={"PYTHONPATH": ""}, cwd="/tmp", timeout=180)
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
    least one test of the test_examples_*.py files (or hold only docs) —
    a new example dir without a smoke test fails here, and so does
    deleting a test while keeping the dir."""
    import glob
    import inspect
    files = sorted(glob.glob(os.path.join(REPO, "tests",
                                          "test_examples_*.py")))
    assert os.path.abspath(__file__) in files and len(files) > 1, files
    this = "".join(open(f).read() for f in files)
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
