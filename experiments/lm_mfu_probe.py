"""Transformer-LM training-throughput / MFU probe (round 5).

The flagship ResNet-50 bench tops out ~23% MFU even for raw JAX
(git show 58f48c3:LAYOUT_r04.json): early conv layers are bandwidth-bound and the
spatial dims tile the MXU poorly — that ceiling is the MODEL's, not
the framework's.  This probe tells the other half of the story on a
matmul-dominated workload: a GPT-style TransformerLM (the repo's
long-context flagship, gluon model_zoo) trained through the PRODUCT
path — hybridized CachedOp forward (one program), tape vjp (one
program), fused-optimizer step (one program) — reporting tokens/s and
MFU from an exact matmul-FLOPs count.

Model FLOPs accounting (dense attention, causal ~halves the attention
term but we count the full square like the flash kernel executes it in
dense mode; bwd = 2x fwd):

  P_matmul = L*(4*D^2 + 2*D*FFN) + D*V          (qkv+proj, ffn, head)
  fwd/step = 2*P_matmul*B*T + L*4*B*T^2*D        (matmuls + qk/av)
  train/step = 3 * fwd

Run:  python experiments/lm_mfu_probe.py [--dim 1024 --layers 12 ...]
CPU smoke:  MXT_LM_PROBE_SMOKE=1 (tiny config, 2 steps)
"""
import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser(description="transformer-LM MFU probe")
    ap.add_argument("--dim", type=int, default=1024)
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument("--ffn", type=int, default=4096)
    ap.add_argument("--vocab", type=int, default=32768)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--attn", default="dense", choices=("dense", "flash"))
    ap.add_argument("--dtype", default="bfloat16",
                    choices=("bfloat16", "float32"))
    args = ap.parse_args()
    if os.environ.get("MXT_LM_PROBE_SMOKE"):
        args.dim, args.layers, args.heads, args.ffn = 64, 2, 4, 128
        args.vocab, args.seq, args.batch = 256, 32, 4
        args.steps, args.warmup = 2, 1

    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu.gluon.block import HybridBlock
    from mxnet_tpu.gluon.model_zoo.transformer import TransformerLM

    ctx = mx.tpu() if mx.context.num_tpus() else mx.cpu()

    class TrainStep(HybridBlock):
        """net + next-token CE as ONE hybridized graph (one CachedOp
        forward, one vjp program — each eager op is a dispatch, so the
        loop must stay O(1) dispatches)."""

        def __init__(self, net, vocab, **kw):
            super().__init__(**kw)
            self._v = vocab
            with self.name_scope():
                self.net = net

        def hybrid_forward(self, F, tokens, labels):
            logits = self.net(tokens)                       # (B,T,V)
            # CE in f32: bf16 logits over a 32k vocab lose the softmax
            logits = F.cast(F.reshape(logits, (-1, self._v)), "float32")
            lp = F.log_softmax(logits, axis=-1)
            nll = -F.pick(lp, F.reshape(labels, (-1,)), axis=-1)
            return F.mean(nll)

    net = TransformerLM(args.vocab, dim=args.dim, num_layers=args.layers,
                        num_heads=args.heads, ffn_dim=args.ffn,
                        max_len=args.seq, attn_type=args.attn)
    step_block = TrainStep(net, args.vocab)
    step_block.initialize(mx.init.Xavier(), ctx=ctx)
    if args.dtype != "float32":
        step_block.cast(args.dtype)
    step_block.hybridize()
    trainer = gluon.Trainer(
        step_block.collect_params(), "sgd",
        {"learning_rate": 0.01, "momentum": 0.9,
         "multi_precision": args.dtype != "float32"})

    rs = np.random.RandomState(0)
    toks = rs.randint(0, args.vocab,
                      (args.batch, args.seq + 1)).astype("float32")
    x = mx.nd.array(toks[:, :-1], ctx=ctx)
    y = mx.nd.array(toks[:, 1:], ctx=ctx)

    def one_step():
        with autograd.record():
            loss = step_block(x, y)
        loss.backward()
        trainer.step(args.batch)
        return loss

    t0 = time.time()
    last = one_step()                    # always ≥1 warmup: compile step
    for _ in range(max(0, args.warmup - 1)):
        last = one_step()
    first_loss = float(last.asnumpy())          # force-drain warmup
    compile_s = time.time() - t0

    t0 = time.time()
    for _ in range(args.steps):
        last = one_step()
    final_loss = float(last.asnumpy())          # force-drain timed block
    dt = time.time() - t0

    tokens_per_step = args.batch * args.seq
    tok_s = tokens_per_step * args.steps / dt
    d, f, v, l = args.dim, args.ffn, args.vocab, args.layers
    p_matmul = l * (4 * d * d + 2 * d * f) + d * v
    fwd = 2 * p_matmul * tokens_per_step + l * 4 * args.batch * \
        args.seq ** 2 * d
    train_flops_per_tok = 3 * fwd / tokens_per_step

    out = {"metric": "transformer_lm_train_throughput",
           "value": round(tok_s, 1), "unit": "tok/s",
           "config": {"dim": d, "layers": l, "heads": args.heads,
                      "ffn": f, "vocab": v, "seq": args.seq,
                      "batch": args.batch, "attn": args.attn,
                      "dtype": args.dtype},
           "params_matmul": p_matmul,
           "train_tflops_per_step": round(3 * fwd / 1e12, 3),
           "ms_per_step": round(1e3 * dt / args.steps, 1),
           "compile_s": round(compile_s, 1),
           "loss_first": round(first_loss, 3),
           "loss_final": round(final_loss, 3)}
    if mx.context.num_tpus():  # a CPU run has no MFU
        from mxnet_tpu.chip import mfu
        out.update(mfu(tok_s, flops_per_img=train_flops_per_tok))
    print(json.dumps(out))


if __name__ == "__main__":
    main()
