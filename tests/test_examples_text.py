"""Language models and text classifiers: bucketing LSTM, word LM, the
transformer LMs (dense and ring attention), sorting, NCE, text CNNs."""
import numpy as np

from example_runner import run_example


def test_lstm_bucketing_example():
    out = run_example("example/rnn/lstm_bucketing.py",
                      "--num-epochs", "1", "--num-hidden", "32",
                      "--num-embed", "32", "--num-layers", "1")
    assert "perplexity" in out.lower() or "Epoch[0]" in out


def test_word_lm_example():
    out = run_example("example/gluon/word_language_model.py", "--epochs", "1",
                      "--num-hidden", "16", "--num-embed", "16",
                      "--num-layers", "1", "--bptt", "10")
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
                      "--max-batches", "12", "--corpus-len", "3000")
    line = [l for l in out.splitlines() if "final ppl" in l][0]
    # "final ppl X last-batch ppl Y (uniform 32.0)" — the mean includes
    # the untrained first batches; the LAST batch must beat uniform
    # (the learning signal: sharded-attention grads actually train)
    last_ppl = float(line.split()[5])
    assert np.isfinite(last_ppl) and last_ppl < 32.0, out
    gen = [l for l in out.splitlines() if l.startswith("generated:")][0]
    assert len(gen.split()) == 13, gen  # 'generated:' + 12 tokens


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


def test_rnn_time_major_example():
    out = run_example("example/rnn-time-major/readme_demo.py",
                      "--num-epochs", "3", "--corpus", "8000")
    line = [l for l in out.splitlines() if "final TNC perplexity" in l][0]
    ppl = float(line.rsplit(" ", 1)[-1])
    assert ppl < 48.0, out  # well under the vocab-50 uniform baseline


def test_cnn_text_classification_example():
    out = run_example("example/cnn_text_classification/text_cnn.py",
                      "--num-epochs", "3", "--num-examples", "1000")
    line = [l for l in out.splitlines() if "dev accuracy" in l][0]
    assert float(line.rsplit(" ", 1)[-1]) > 0.7, out


def test_chinese_text_cnn_example():
    out = run_example(
        "example/cnn_chinese_text_classification/text_cnn.py",
        "--num-epochs", "6", "--num-examples", "1024")
    acc = float([l for l in out.splitlines()
                 if "final validation accuracy" in l][0].rsplit(" ", 1)[-1])
    assert acc > 0.75, out


def test_nce_loss_example():
    out = run_example("example/nce-loss/nce_lm.py",
                      "--num-epochs", "3", "--num-tokens", "8000")
    line = [l for l in out.splitlines() if "true-word top-1" in l][0]
    assert float(line.rsplit(" ", 1)[-1]) > 0.8, out
