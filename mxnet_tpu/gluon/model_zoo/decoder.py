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

`ZayaLM` stacks `ZayaBlock`s: compressed convolutional attention
(`CompressedConvAttention`), an MLP router (`MLPRouter`) whose state each
layer hands to the next through the graph, top-k experts beside a skip
choice that no device holds, learned scales on the residual and on each
sub-layer's output, and a head tied to the embedding (`TiedHead`) whose
loss walks the vocabulary in blocks.

The whole stack is one HybridBlock, so a step is one CachedOp forward and
one backward, as `TransformerLM`'s is.  There is no decode path yet
(ROADMAP R1 / R6: a latent leaf, a window's ring and a global layer's
table in the decode cache; for a looped model a cache leaf a loop step and
layer, and exits that leave a batch ragged in depth).
"""
from __future__ import annotations

import functools

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
    input, the router scores that and the experts read the first.

    scored: the block has no router weight; its second input is the
    router's logits (..., num_experts), computed by the caller (an MLP
    router), and `moe_ffn_scored` routes by them; the last of the
    `num_experts` choices is then a skip that no device holds, and `load`
    is (held + 3,), a slot for the skipped tokens before the buffer's."""

    def __init__(self, dim, ffn_dim, num_experts, top_k, held_experts=None,
                 first_expert=0, shared_experts=1, routed_scale=1.0,
                 norm_topk=True, router="sigmoid", activation="silu",
                 scored=False, **kw):
        super().__init__(**kw)
        held = num_experts if held_experts is None else held_experts
        self.scored = scored
        self._moe = dict(num_experts=num_experts, top_k=top_k,
                         first=first_expert, held=held, scale=routed_scale,
                         norm_topk=norm_topk, router=router,
                         activation=activation)
        with self.name_scope():
            if not scored:
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
                "load", shape=(held + 2 + scored,), init="zeros",
                grad_req="null", differentiable=False)
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
        experts = (select_bias, gate_weight, up_weight, down_weight, load)
        if self.scored:
            y = F.moe_ffn_scored(x, routed_by, *experts, **self._moe)
        elif routed_by is None:
            y = F.moe_ffn(x, router_weight, *experts, **self._moe)
        else:
            y = F.moe_ffn_routed_by(x, routed_by, router_weight, *experts,
                                    **self._moe)
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


class CompressedConvAttention(HybridBlock):
    """Compressed convolutional attention (arXiv:2510.04476, section 3)
    over (B, T, D): `num_heads` query and `num_kv_heads` key/value heads of
    `head_dim`, no biases on the projections.

    Queries and keys are projected, then mixed (op `cca_mix`): a causal
    convolution over time, one filter a channel, of `conv_taps[0]` taps; a
    causal convolution over time and the channels of each head, of
    `conv_taps[1]` taps; the pre-mix mean of a group's query heads and its
    key head added back; L2 norms scaled to sqrt(head_dim), the keys' by a
    learned `temperature` a key/value head; rotary positions over the first
    `rope_dims` of every head.  The values are two projections of half the
    key/value width, the second read one token later.  Attention is
    `grouped_query_attention` without positions of its own (attn_type
    'dense' | 'flash': the Pallas kernels), then one projection back up."""

    def __init__(self, dim, num_heads, num_kv_heads, head_dim, rope_dims,
                 rope_base=10000.0, conv_taps=(2, 2), attn_type="dense",
                 **kw):
        super().__init__(**kw)
        if num_kv_heads % 2:
            raise ValueError("CompressedConvAttention: half the value heads "
                             "read the token before, so the key/value heads "
                             f"must be even, got {num_kv_heads}")
        width = (num_heads + num_kv_heads) * head_dim
        self._mix = dict(num_heads=num_heads, num_kv_heads=num_kv_heads,
                         rope_dims=rope_dims, rope_base=rope_base)
        self._attn = dict(num_heads=num_heads, num_kv_heads=num_kv_heads,
                          rope=False, impl=attn_type)
        half = num_kv_heads * head_dim // 2
        with self.name_scope():
            self.conv0_weight = self.params.get(
                "conv0_weight", shape=(conv_taps[0], width))
            self.conv0_bias = self.params.get("conv0_bias", shape=(width,),
                                              init="zeros")
            self.conv1_weight = self.params.get(
                "conv1_weight", shape=(num_heads + num_kv_heads,
                                       conv_taps[1], head_dim, head_dim))
            self.conv1_bias = self.params.get("conv1_bias", shape=(width,),
                                              init="zeros")
            self.temperature = self.params.get(
                "temperature", shape=(num_kv_heads,), init="ones")
            self.q = _dense(num_heads * head_dim, dim, "q_")
            self.k = _dense(num_kv_heads * head_dim, dim, "k_")
            self.va = _dense(half, dim, "va_")
            self.vb = _dense(half, dim, "vb_")
            self.proj = _dense(dim, num_heads * head_dim, "proj_")

    def hybrid_forward(self, F, x, conv0_weight, conv0_bias, conv1_weight,
                       conv1_bias, temperature):
        q, k, v = F.cca_mix(self.q(x), self.k(x), self.va(x), self.vb(x),
                            conv0_weight, conv0_bias, conv1_weight,
                            conv1_bias, temperature, **self._mix)
        return self.proj(F.grouped_query_attention(q, k, v, **self._attn))


class MLPRouter(HybridBlock):
    """A router that is an MLP (ops `mlp_router`, `mlp_router_carried`, in
    float32 whatever the net is cast to): x (..., D) -> (logits (..., E),
    state (..., R)).  `carried`: the block takes the previous layer's state
    as a second input and adds it, times a learned `state_scale` (R,), to
    its own (exponential depth averaging); the first layer's router has
    neither."""

    def __init__(self, dim, router_dim, num_outputs, carried, epsilon=1e-5,
                 **kw):
        super().__init__(**kw)
        self._carried, self._eps = carried, epsilon
        with self.name_scope():
            self.down_weight = self.params.get("down_weight",
                                               shape=(router_dim, dim))
            self.down_bias = self.params.get("down_bias", shape=(router_dim,),
                                             init="zeros")
            if carried:
                self.state_scale = self.params.get(
                    "state_scale", shape=(router_dim,), init="ones")
            self.norm_gamma = self.params.get(
                "norm_gamma", shape=(router_dim,), init="ones")
            self.w1 = self.params.get("w1", shape=(router_dim, router_dim))
            self.w2 = self.params.get("w2", shape=(router_dim, router_dim))
            self.w3 = self.params.get("w3", shape=(num_outputs, router_dim))

    def hybrid_forward(self, F, x, state=None, down_weight=None,
                       down_bias=None, state_scale=None, norm_gamma=None,
                       w1=None, w2=None, w3=None):
        if self._carried:
            return F.mlp_router_carried(x, state, down_weight, down_bias,
                                        state_scale, norm_gamma, w1, w2, w3,
                                        eps=self._eps)
        return F.mlp_router(x, down_weight, down_bias, norm_gamma, w1, w2,
                            w3, eps=self._eps)


class ZayaBlock(HybridBlock):
    """A layer of attention and experts whose router carries a state from
    layer to layer, with learned scales on the residual and on each
    sub-layer's output:

        x' = a1 * x + b1 * attn(n1(x))
        w = n2(x');  (logits, state) = router(w, previous state)
        out = a2 * x' + b2 * ffn(w, logits)

    a1, b1, a2, b2 (D,), ones at first.  `ffn` is a scored
    `MoEFeedForward`.  (x, state) -> (out, state); the first layer is
    called with x alone."""

    def __init__(self, attn, router, ffn, dim, epsilon=1e-5, **kw):
        super().__init__(**kw)
        with self.name_scope():
            for name in ("attn_in_scale", "attn_out_scale", "ffn_in_scale",
                         "ffn_out_scale"):
                setattr(self, name, self.params.get(name, shape=(dim,),
                                                    init="ones"))
            self.n1 = RMSNorm(dim, epsilon, prefix="n1_")
            self.attn = attn(prefix="attn_")
            self.n2 = RMSNorm(dim, epsilon, prefix="n2_")
            self.router = router(prefix="router_")
            self.ffn = ffn(prefix="ffn_")

    def hybrid_forward(self, F, x, state=None, attn_in_scale=None,
                       attn_out_scale=None, ffn_in_scale=None,
                       ffn_out_scale=None):
        x = F.broadcast_mul(x, attn_in_scale) + F.broadcast_mul(
            self.attn(self.n1(x)), attn_out_scale)
        w = self.n2(x)
        logits, state = self.router(w) if state is None else \
            self.router(w, state)
        return F.broadcast_mul(x, ffn_in_scale) + F.broadcast_mul(
            self.ffn(w, logits), ffn_out_scale), state


class TiedHead(HybridBlock):
    """An output head whose matrix is an embedding's (build it with
    `params=embedding.params`): h (..., D) -> logits (..., V); called with
    labels too, each token's cross-entropy (op `blocked_cross_entropy`,
    float32, no logits held)."""

    def __init__(self, vocab, dim, **kw):
        super().__init__(**kw)
        self._vocab = vocab
        self.weight = self.params.get("weight", shape=(vocab, dim))

    def hybrid_forward(self, F, h, labels=None, weight=None):
        if labels is None:
            return F.FullyConnected(h, weight, no_bias=True,
                                    num_hidden=self._vocab, flatten=False)
        return F.blocked_cross_entropy(h, weight, labels)


class ZayaLM(HybridBlock):
    """Token ids (B, T) -> logits (B, T, vocab): `ZayaBlock` layers of
    compressed convolutional attention (`attention`: a factory called with
    `prefix=`) and top-`top_k` experts with a skip choice, routed by MLP
    routers (width `router_dim`) that hand their state from layer to layer
    through the graph; the head is the embedding (`TiedHead`), no
    embedding scale, no biases.  Of the `num_experts` routed experts and
    the skip, this device holds `held_experts` from `first_expert` on
    (ops/decoder.py `moe_ffn_scored`).  `hidden(F, tokens)` is the final
    norm's output; `head(h, labels)` the per-token loss."""

    def __init__(self, vocab, dim, num_layers, attention, expert_ffn_dim,
                 num_experts, held_experts=None, first_expert=0, top_k=1,
                 router_dim=256, epsilon=1e-5, **kw):
        super().__init__(**kw)

        def router(carried):
            return functools.partial(MLPRouter, dim, router_dim,
                                     num_experts + 1, carried, epsilon)

        def ffn(prefix):
            return MoEFeedForward(
                dim, expert_ffn_dim, num_experts + 1, top_k, held_experts,
                first_expert, shared_experts=0, norm_topk=False,
                router="softmax_topk", scored=True, prefix=prefix)

        with self.name_scope():
            self.tok = nn.Embedding(vocab, dim, prefix="tok_")
            self.blocks = nn.HybridSequential(prefix="blocks_")
            for i in range(num_layers):
                self.blocks.add(ZayaBlock(attention, router(i > 0), ffn, dim,
                                          epsilon, prefix=f"l{i}_"))
            self.norm_f = RMSNorm(dim, epsilon, prefix="normf_")
            self.head = TiedHead(vocab, dim, params=self.tok.params)

    def hidden(self, F, tokens):
        x, state = self.tok(tokens), None
        for blk in self.blocks:
            x, state = blk(x) if state is None else blk(x, state)
        return self.norm_f(x)

    def hybrid_forward(self, F, tokens):
        return self.head(self.hidden(F, tokens))

    def generate(self, *args, **kwargs):
        raise NotImplementedError(
            "ZayaLM has no decode path yet: its cache would hold, beside "
            "K and V, the convolutions' last tokens and the shifted value "
            "heads' previous token (ROADMAP R1 / R6)")

    _kv_forward = generate
