"""A current decoder language model (gluon): RMS norm, gated feed-forwards,
sparse experts of which this device holds a share, and an attention block
chosen layer by layer.

    x + attn(n1(x));  x + ffn(n2(x))

Attention blocks: `LatentAttention` (queries through a low-rank latent,
keys and values from one latent a token, one shared rotary key) and
`GroupedQueryAttention` (fewer key/value heads than query heads, rotary
positions or none, a sliding window or none).  `DecoderLM(attention=[...])`
takes a pattern of block factories and repeats it over the layers, so a
global position-free layer can stand beside windowed rotary ones; without
it every layer is a `LatentAttention` built from the rank and head
arguments.

The first `first_k_dense` layers have a dense gated feed-forward, the rest
an expert layer: a router over ALL `num_experts` (`router="sigmoid"`:
sigmoid scores plus a selection bias; `"softmax_topk"`: a softmax over the
chosen logits; `top_k` a token either way), the stacked matrices of the
`held_experts` experts from `first_expert` on under a SiLU or a ReLU gate,
and shared experts that every token passes, or none.  With
`router_reads="attention_input"` the router scores the layer's normalised
input, the tensor its attention reads, and not the feed-forward's.  With
`held_experts < num_experts` the block computes this device's part of an
expert-parallel layer without its exchange: a chosen expert that is absent
adds nothing (ops/decoder.py).

`DecoderBlock(sandwich=True)` normalises each sub-layer's output as well
as its input:

    x + n1post(attn(n1(x)));  x + n2post(ffn(n2(x)))

`LoopedLM` is a looped (weight-tied) decoder: ONE stack of such blocks
applied `loop_steps` times, `norm_f` closing every step, the head and an
exit gate (`ExitGate`) reading every step's output.  The loop is a node of
the graph (`F.contrib.foreach`, run as `lax.scan`), so the hybridized
program holds the stack once and a tied parameter's gradient is the sum
over its applications.  `LoopedLMLoss` is its training objective: the
cross-entropy of every exit weighted by the gates' exit distribution
(`ExitDistribution`), less `beta` times that distribution's entropy, one
exit's float32 logits alive at a time.

The whole stack is one HybridBlock, so a step is one CachedOp forward and
one backward, as `TransformerLM`'s is.  There is no decode path yet
(ROADMAP R1 / R6: a latent leaf, a window's ring and a global layer's
table in the decode cache; for a looped model a cache leaf a loop step and
layer, and exits that leave a batch ragged in depth).
"""
from __future__ import annotations

from ...observability import metrics as _metrics
from .. import nn
from ..block import HybridBlock


def _dense(units, in_units, prefix):
    return nn.Dense(units, use_bias=False, flatten=False, in_units=in_units,
                    prefix=prefix)


class RMSNorm(HybridBlock):
    """x / sqrt(mean(x^2) + eps) * gamma over the last axis."""

    def __init__(self, in_channels, epsilon=1e-5, **kw):
        super().__init__(**kw)
        self._eps = epsilon
        self.gamma = self.params.get("gamma", shape=(in_channels,),
                                     init="ones")

    def hybrid_forward(self, F, x, gamma):
        return F.rms_norm(x, gamma, eps=self._eps)


class LatentAttention(HybridBlock):
    """Causal multi-head latent attention over (B, T, D): queries through
    a normalised low-rank latent; keys and values up-projected from ONE
    normalised latent a token, plus one rotary key shared by all heads.
    attn_type: 'dense' | 'flash' (the Pallas kernel; nope + rope == v)."""

    def __init__(self, dim, num_heads, q_rank, kv_rank, nope_dim, rope_dim,
                 v_dim, attn_type="dense", epsilon=1e-5, rope_base=10000.0,
                 **kw):
        super().__init__(**kw)
        self._kv_rank, self._rope_dim = kv_rank, rope_dim
        self._attn = dict(num_heads=num_heads, nope_dim=nope_dim,
                          rope_dim=rope_dim, v_dim=v_dim,
                          rope_base=rope_base, impl=attn_type)
        with self.name_scope():
            self.q_a = _dense(q_rank, dim, "qa_")
            self.q_norm = RMSNorm(q_rank, epsilon, prefix="qnorm_")
            self.q_b = _dense(num_heads * (nope_dim + rope_dim), q_rank,
                              "qb_")
            self.kv_a = _dense(kv_rank + rope_dim, dim, "kva_")
            self.kv_norm = RMSNorm(kv_rank, epsilon, prefix="kvnorm_")
            self.kv_b = _dense(num_heads * (nope_dim + v_dim), kv_rank,
                               "kvb_")
            self.proj = _dense(dim, num_heads * v_dim, "proj_")

    def hybrid_forward(self, F, x):
        q = self.q_b(self.q_norm(self.q_a(x)))
        kva = self.kv_a(x)
        latent = F.slice_axis(kva, axis=2, begin=0, end=self._kv_rank)
        k_rope = F.slice_axis(kva, axis=2, begin=self._kv_rank,
                              end=self._kv_rank + self._rope_dim)
        kv = self.kv_b(self.kv_norm(latent))
        return self.proj(F.latent_attention(q, kv, k_rope, **self._attn))


class GroupedQueryAttention(HybridBlock):
    """Causal attention over (B, T, D) with `num_kv_heads` key/value heads
    for `num_heads` query heads of `head_dim` (query head j reads key/value
    head j // (num_heads / num_kv_heads)), no biases.  rope: rotary
    positions over the whole head, or no positions at all.  window: a
    query sees its own key and the `window - 1` before it; None, every key
    up to its own.  attn_type: 'dense' | 'flash' (the Pallas kernel)."""

    def __init__(self, dim, num_heads, num_kv_heads, head_dim, rope=True,
                 window=None, attn_type="dense", rope_base=10000.0, **kw):
        super().__init__(**kw)
        self._attn = dict(num_heads=num_heads, num_kv_heads=num_kv_heads,
                          rope=rope, rope_base=rope_base,
                          window=window or -1, impl=attn_type)
        with self.name_scope():
            self.q = _dense(num_heads * head_dim, dim, "q_")
            self.k = _dense(num_kv_heads * head_dim, dim, "k_")
            self.v = _dense(num_kv_heads * head_dim, dim, "v_")
            self.proj = _dense(dim, num_heads * head_dim, "proj_")

    def hybrid_forward(self, F, x):
        return self.proj(F.grouped_query_attention(
            self.q(x), self.k(x), self.v(x), **self._attn))


class GatedFeedForward(HybridBlock):
    """down(silu(gate(x)) * up(x)), no biases."""

    def __init__(self, dim, ffn_dim, **kw):
        super().__init__(**kw)
        with self.name_scope():
            self.gate = _dense(ffn_dim, dim, "gate_")
            self.up = _dense(ffn_dim, dim, "up_")
            self.down = _dense(dim, ffn_dim, "down_")

    def hybrid_forward(self, F, x):
        return self.down(F.Activation(self.gate(x), act_type="silu")
                         * self.up(x))


class MoEFeedForward(HybridBlock):
    """Sparse experts, this device's share, plus the shared experts.

    Trainable: the router's weight (num_experts, D) and the stacked gate,
    up (held, D, F) and down (held, F, D) matrices of the held experts.
    Auxiliary (`grad_req="null"`, float32 whatever the net is cast to, as a
    bfloat16 counter stops counting at 256): `select_bias` (num_experts,),
    added to the scores for the selection only, and `load` (held + 2,), to
    which the forward pass adds the assignments of each held expert, then of
    absent ones, and last the rows of the buffer the grouped products ran
    over (read by `observability.metrics.refresh_moe`).
    router, activation: as `moe_ffn` has them.  Called with a second
    input, the router scores that and the experts read the first."""

    def __init__(self, dim, ffn_dim, num_experts, top_k, held_experts=None,
                 first_expert=0, shared_experts=1, routed_scale=1.0,
                 norm_topk=True, router="sigmoid", activation="silu", **kw):
        super().__init__(**kw)
        held = num_experts if held_experts is None else held_experts
        self._moe = dict(num_experts=num_experts, top_k=top_k,
                         first=first_expert, held=held, scale=routed_scale,
                         norm_topk=norm_topk, router=router,
                         activation=activation)
        with self.name_scope():
            self.router_weight = self.params.get(
                "router_weight", shape=(num_experts, dim))
            self.select_bias = self.params.get(
                "select_bias", shape=(num_experts,), init="zeros",
                grad_req="null", differentiable=False)
            self.gate_weight = self.params.get(
                "gate_weight", shape=(held, dim, ffn_dim))
            self.up_weight = self.params.get(
                "up_weight", shape=(held, dim, ffn_dim))
            self.down_weight = self.params.get(
                "down_weight", shape=(held, ffn_dim, dim))
            self.load = self.params.get(
                "load", shape=(held + 2,), init="zeros", grad_req="null",
                differentiable=False)
            self.shared = GatedFeedForward(
                dim, ffn_dim * shared_experts, prefix="shared_") \
                if shared_experts else None
        _metrics.watch_moe_layer(self)

    def cast(self, dtype):
        super().cast(dtype)
        self.select_bias.cast("float32")
        self.load.cast("float32")

    def hybrid_forward(self, F, x, routed_by=None, router_weight=None,
                       select_bias=None, gate_weight=None, up_weight=None,
                       down_weight=None, load=None):
        rest = (router_weight, select_bias, gate_weight, up_weight,
                down_weight, load)
        y = F.moe_ffn(x, *rest, **self._moe) if routed_by is None else \
            F.moe_ffn_routed_by(x, routed_by, *rest, **self._moe)
        return y if self.shared is None else y + self.shared(x)


class DecoderBlock(HybridBlock):
    """x + attn(n1(x)), then x + ffn(n2(x)).  router_reads: 'ffn_input', or
    'attention_input' for an expert layer whose router scores n1(x).
    sandwich: each sub-layer's output is normalised too before it joins
    the residual stream, x + n1post(attn(n1(x))), then x +
    n2post(ffn(n2(x))): two more `RMSNorm`s a layer."""

    def __init__(self, attn, ffn, dim, epsilon=1e-5,
                 router_reads="ffn_input", sandwich=False, **kw):
        super().__init__(**kw)
        if router_reads not in ("ffn_input", "attention_input"):
            raise ValueError(f"DecoderBlock router_reads={router_reads!r}: "
                             "choose 'ffn_input' or 'attention_input'")
        self._early_router = router_reads == "attention_input"
        self.n1_post = self.n2_post = None
        with self.name_scope():
            self.n1 = RMSNorm(dim, epsilon, prefix="n1_")
            self.attn = attn(prefix="attn_")
            if sandwich:
                self.n1_post = RMSNorm(dim, epsilon, prefix="n1post_")
            self.n2 = RMSNorm(dim, epsilon, prefix="n2_")
            self.ffn = ffn(prefix="ffn_")
            if sandwich:
                self.n2_post = RMSNorm(dim, epsilon, prefix="n2post_")

    def hybrid_forward(self, F, x):
        h = self.n1(x)
        a = self.attn(h)
        x = x + (a if self.n1_post is None else self.n1_post(a))
        f = self.ffn(self.n2(x), h) if self._early_router else \
            self.ffn(self.n2(x))
        return x + (f if self.n2_post is None else self.n2_post(f))


class DecoderLM(HybridBlock):
    """Token ids (B, T) -> logits (B, T, vocab); untied head, no biases.

    attention: a pattern of attention-block factories (`prefix=` is the
    one argument each is called with), repeated over the layers: layer i
    gets `attention[i % len(attention)]`.  None: every layer a
    `LatentAttention` from `num_heads`, the ranks and the head sizes."""

    def __init__(self, vocab, dim, num_layers, num_heads=None, q_rank=None,
                 kv_rank=None, nope_dim=None, rope_dim=None, v_dim=None,
                 dense_ffn_dim=None, expert_ffn_dim=None, num_experts=None,
                 top_k=None, held_experts=None, first_expert=0,
                 shared_experts=1, first_k_dense=1, routed_scale=1.0,
                 norm_topk=True, epsilon=1e-5, rope_base=10000.0,
                 attn_type="dense", attention=None, router="sigmoid",
                 activation="silu", router_reads="ffn_input", **kw):
        super().__init__(**kw)

        def latent(prefix):
            return LatentAttention(dim, num_heads, q_rank, kv_rank, nope_dim,
                                   rope_dim, v_dim, attn_type, epsilon,
                                   rope_base, prefix=prefix)

        pattern = list(attention) if attention else [latent]

        def dense_ffn(prefix):
            return GatedFeedForward(dim, dense_ffn_dim, prefix=prefix)

        def expert_ffn(prefix):
            return MoEFeedForward(dim, expert_ffn_dim, num_experts, top_k,
                                  held_experts, first_expert, shared_experts,
                                  routed_scale, norm_topk, router, activation,
                                  prefix=prefix)

        with self.name_scope():
            self.tok = nn.Embedding(vocab, dim, prefix="tok_")
            self.blocks = nn.HybridSequential(prefix="blocks_")
            for i in range(num_layers):
                dense = i < first_k_dense
                self.blocks.add(DecoderBlock(
                    pattern[i % len(pattern)],
                    dense_ffn if dense else expert_ffn, dim, epsilon,
                    "ffn_input" if dense else router_reads,
                    prefix=f"l{i}_"))
            self.norm_f = RMSNorm(dim, epsilon, prefix="normf_")
            self.head = _dense(vocab, dim, "head_")

    def hybrid_forward(self, F, tokens):
        return self.head(self.norm_f(self.blocks(self.tok(tokens))))

    def generate(self, *args, **kwargs):
        raise NotImplementedError(
            "DecoderLM has no decode path yet: a latent leaf, a window's "
            "ring and a global layer's table in the decode cache are "
            "ROADMAP R1 / R6")

    _kv_forward = generate


class ExitGate(HybridBlock):
    """A looped model's exit gate: (B, T, D) -> the logit (B, T) of
    leaving after this loop step, w . h + b in float32 whatever the net is
    cast to (an element-wise product and a sum: nothing of it is rounded
    to the activations' dtype)."""

    def __init__(self, dim, **kw):
        super().__init__(**kw)
        self.weight = self.params.get("weight", shape=(1, dim))
        self.bias = self.params.get("bias", shape=(1,), init="zeros")

    def hybrid_forward(self, F, h, weight, bias):
        w = F.reshape(F.cast(weight, "float32"), (1, 1, -1))
        return F.broadcast_add(
            F.sum(F.broadcast_mul(F.cast(h, "float32"), w), axis=-1),
            F.reshape(F.cast(bias, "float32"), (1, 1)))


class ExitDistribution(HybridBlock):
    """Gate logits (R, B, T) -> (p, log p), each (R, B, T) float32: where
    the gates let a token leave (op `exit_distribution`).  Auxiliary
    (`grad_req="null"`, float32): `mass` (R + 1,), to which a forward pass
    adds p summed over its tokens and, last, their number (read by
    `observability.metrics.refresh_loop`)."""

    def __init__(self, loop_steps, **kw):
        super().__init__(**kw)
        self.mass = self.params.get(
            "mass", shape=(loop_steps + 1,), init="zeros", grad_req="null",
            differentiable=False)
        _metrics.watch_loop_exits(self)

    def cast(self, dtype):
        super().cast(dtype)
        self.mass.cast("float32")

    def hybrid_forward(self, F, gate, mass):
        return F.exit_distribution(gate, mass)


class LoopedLM(HybridBlock):
    """A looped (weight-tied) decoder LM: token ids (B, T) -> the LAST
    exit's logits (B, T, vocab).

        h_0 = tok(x);  h_t = norm_f(blocks(h_{t-1})),  t = 1..loop_steps
        exit t: logits head(h_t), gate logit gate(h_t)

    ONE stack of `num_layers` sandwich-norm `DecoderBlock`s (`attention`:
    a factory called with `prefix=`, a dense gated feed-forward of
    `ffn_dim`) is applied `loop_steps` times, `norm_f` closing every step
    (its output feeds the exit and the next step), so a parameter of the
    stack is read `loop_steps` times a pass and its gradient is the sum
    over them.  The loop is a node of the graph (`F.contrib.foreach`): the
    hybridized program holds the stack once.  Untied head, no biases but
    the gate's.  `LoopedLMLoss` trains all exits; inference with an exit
    threshold of 1 runs every step and answers from the last, as this
    block's plain call does."""

    def __init__(self, vocab, dim, num_layers, loop_steps, attention,
                 ffn_dim, epsilon=1e-6, **kw):
        super().__init__(**kw)
        self._loop_steps, self._num_layers = loop_steps, num_layers
        self._stacks_traced = 0

        def ffn(prefix):
            return GatedFeedForward(dim, ffn_dim, prefix=prefix)

        with self.name_scope():
            self.tok = nn.Embedding(vocab, dim, prefix="tok_")
            self.blocks = nn.HybridSequential(prefix="blocks_")
            for i in range(num_layers):
                self.blocks.add(DecoderBlock(attention, ffn, dim, epsilon,
                                             sandwich=True, prefix=f"l{i}_"))
            self.norm_f = RMSNorm(dim, epsilon, prefix="normf_")
            self.head = _dense(vocab, dim, "head_")
            self.gate = ExitGate(dim, prefix="gate_")
            self.exits = ExitDistribution(loop_steps, prefix="exits_")

    def step(self, h):
        """One pass through the stack: h_{t-1} -> h_t."""
        self._stacks_traced += 1
        return self.norm_f(self.blocks(h))

    def loop(self, F, tokens, exit_fn=None):
        """Embed `tokens` and run the loop.  `exit_fn(h_t)` -> a list of
        per-exit values; returns (those stacked over the steps, h_R)."""
        self._stacks_traced = 0

        def body(_step, states):
            h = self.step(states[0])
            return (exit_fn(h) if exit_fn else []), [h]

        outs, (h,) = F.contrib.foreach(
            body, F.arange(0, self._loop_steps), [self.tok(tokens)])
        _metrics.LOOP_APPLICATIONS.set(self._num_layers * self._loop_steps)
        _metrics.LOOP_STACK_COPIES.set(self._stacks_traced)
        return outs, h

    def hybrid_forward(self, F, tokens):
        return self.head(self.loop(F, tokens)[1])

    def generate(self, *args, **kwargs):
        raise NotImplementedError(
            "LoopedLM has no decode path yet: a cache leaf a loop step and "
            "layer, and exits that leave a batch ragged in depth, are "
            "ROADMAP R1 / R6")

    _kv_forward = generate


class LoopedLMLoss(HybridBlock):
    """(tokens, next tokens), both (B, T) -> a looped LM's training
    objective, per sequence (B,):

        mean over tokens of  sum_t p_t CE(z_t, y)  -  beta H(p)

    z_t the logits of exit t, p the exit distribution of the gates
    (`ExitDistribution`), H its entropy: every exit is trained, weighted by
    the chance of leaving there, and `beta` keeps the gates from
    collapsing onto one exit.  Net and loss are one graph; an exit's
    float32 logits and log-softmax live INSIDE the loop body, so one
    exit's are alive at a time and only the cross-entropies and gate
    logits (R, B, T) leave the loop.  Everything from the logits' cast on
    is float32."""

    def __init__(self, net, beta=0.1, **kw):
        super().__init__(**kw)
        self._beta = beta
        with self.name_scope():
            self.net = net

    def hybrid_forward(self, F, tokens, labels):
        net = self.net

        def exit_fn(h):
            logp = F.log_softmax(F.cast(net.head(h), "float32"), axis=-1)
            return [-F.pick(logp, labels, axis=-1), net.gate(h)]

        (ce, gate), _ = net.loop(F, tokens, exit_fn)
        p, logp = net.exits(gate)
        per_token = F.sum(p * ce, axis=0) + self._beta * F.sum(p * logp,
                                                               axis=0)
        return F.mean(per_token, axis=1)
