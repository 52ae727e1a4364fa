"""Gluon trainings (autograd.record + Trainer.step on eager and hybridized
nets), one child process each."""
from example_runner import run_example


def test_gluon_image_classification_example():
    out = run_example("example/gluon/image_classification.py",
                      "--epochs", "1", "--num-examples", "128",
                      "--model", "squeezenet1_0", "--image-size", "64")
    assert "val-acc" in out


def test_tree_lstm_example():
    out = run_example("example/gluon/tree_lstm.py",
                      "--num-trees", "40", "--epochs", "2")
    line = [l for l in out.splitlines() if "final acc" in l][0]
    # seeded run reaches 0.60 by epoch 2; above-chance composition
    assert float(line.rsplit(" ", 1)[-1]) > 0.52, out


def test_fgsm_adversary_example():
    out = run_example("example/adversary/fgsm.py",
                      "--epochs", "8", "--num-test", "100")
    line = [l for l in out.splitlines() if "clean accuracy" in l][0]
    clean = float(line.split()[2])
    adv = float(line.split()[5])
    # trained net learns the synthetic digits; FGSM must hurt it
    assert clean > 0.8, out
    assert adv < clean - 0.3, out


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


def test_dsd_example():
    out = run_example("example/dsd/dsd_mlp.py",
                      "--epochs", "3", "--num-examples", "1000")
    line = [l for l in out.splitlines() if "accuracy dense" in l][0]
    accs = [float(v) for v in line.split()[2:7:2]]
    assert all(a > 0.8 for a in accs), out  # all three phases stay strong
    density = float(line.split()[-1].rstrip(")"))
    assert density < 0.5, out  # pruning really happened
