"""Image pipelines end to end: detection, segmentation, style transfer, the
Kaggle entries and the .rec ImageNet path, one child process each."""
import numpy as np

from example_runner import pack_rec, run_example


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


def test_fcn_xs_example():
    out = run_example("example/fcn-xs/fcn_xs.py",
                      "--num-epochs", "10", "--num-examples", "96")
    line = [l for l in out.splitlines() if "final pixel accuracy" in l][0]
    acc = float(line.split()[3])
    fg = float(line.split()[-1])
    assert acc > 0.85 and fg > 0.15, out


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


def test_kaggle_ndsb1_pipeline(tmp_path):
    out = run_example("example/kaggle-ndsb1/train_dsb.py",
                      "--num-epochs", "8", "--num-examples", "1536",
                      "--classes", "8", "--submission",
                      str(tmp_path / "sub.csv"))
    acc = float([l for l in out.splitlines()
                 if "validation accuracy" in l][0].rsplit(" ", 1)[-1])
    assert acc > 0.5, out
    header = (tmp_path / "sub.csv").read_text().splitlines()[0]
    assert header.startswith("image,class_0")


def test_kaggle_ndsb2_crps():
    out = run_example("example/kaggle-ndsb2/Train.py",
                      "--num-epochs", "6", "--num-examples", "768")
    line = [l for l in out.splitlines() if "ndsb2 CRPS" in l][0]
    crps_v = float(line.split()[2])
    mae = float(line.split()[5])
    assert crps_v < 0.05, out
    assert mae < 40, out


def test_train_imagenet_rec_device_augment(tmp_path):
    """The north-star rec-file path end to end: pack a tiny JPEG .rec,
    train resnet-8 on it with the device-augment input split (the
    default), bf16 data dtype."""
    rec = str(tmp_path / "tiny.rec")
    pack_rec(rec, 96, 40)
    out = run_example("example/image-classification/train_imagenet.py",
                      "--data-train", rec, "--network", "resnet",
                      "--num-layers", "8", "--num-classes", "10",
                      "--num-examples", "96", "--image-shape", "3,32,32",
                      "--batch-size", "32", "--num-epochs", "1",
                      "--lr", "0.05", "--device-augment", "1")
    assert "Epoch[0]" in out, out
