"""CTC and speech: the OCR and toy CTC trainings, the captcha reader, the
acoustic models."""
import numpy as np

from example_runner import run_example


def test_speech_ctc_example():
    out = run_example("example/speech_recognition/train_speech.py",
                      "--num-epochs", "10", "--num-utts", "48",
                      "--lr", "5e-3")
    line = [l for l in out.splitlines() if "final ctc-loss" in l][0]
    cer = float(line.rsplit(" ", 1)[-1])
    assert cer < 0.9, out  # decodes are emerging (CER 0 by epoch ~20)


def test_captcha_ocr_example():
    out = run_example("example/captcha/captcha_ocr.py",
                      "--num-epochs", "3", "--num-examples", "600",
                      "--lr", "3e-3")
    lines = [l for l in out.splitlines() if "ctc-loss=" in l]
    first = float(lines[0].split("ctc-loss=")[1].split()[0])
    last = float(lines[-1].split("ctc-loss=")[1].split()[0])
    assert last < first, out  # CTC is slow to exit the blank phase; the


def test_lstm_ocr_ctc_example():
    out = run_example("example/ctc/lstm_ocr.py", "--num-epochs", "12",
                      "--batches-per-epoch", "12", "--lr", "0.02")
    acc = float([l for l in out.splitlines()
                 if "exact-sequence accuracy" in l][0].rsplit(" ", 1)[-1])
    assert acc > 0.8, out


def test_toy_ctc_warpctc_example():
    out = run_example("example/warpctc/toy_ctc.py", "--num-epochs", "14",
                      "--batches", "12", "--frames", "4")
    acc = float([l for l in out.splitlines()
                 if "sequence accuracy" in l][0].rsplit(" ", 1)[-1])
    assert acc > 0.6, out


def test_speech_demo_example(tmp_path):
    post = tmp_path / "post.npz"
    out = run_example("example/speech-demo/train_lstm.py",
                      "--num-epochs", "4", "--posteriors", str(post))
    acc = float([l for l in out.splitlines()
                 if "framewise accuracy" in l][0].rsplit(" ", 1)[-1])
    assert acc > 0.6, out
    z = np.load(post)
    assert any(k.startswith("bucket_") for k in z.files)
