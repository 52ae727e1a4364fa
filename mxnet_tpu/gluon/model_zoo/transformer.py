"""Transformer language-model family (gluon).

Beyond-reference capability (SURVEY.md §2.3 long-context rows): the
reference (2017-era MXNet) predates transformers; this family is the
TPU-native flagship for the long-context story.  Design:

  - the whole decoder stack is one HybridBlock → a single jitted
    CachedOp forward + fused vjp (no per-layer dispatch),
  - attention can run as `dense` (materialized scores — XLA fuses the
    softmax chain) or `flash` (the Pallas `_contrib_flash_attention`
    kernel: O(T) memory online-softmax tiling on the MXU),
  - for sequence lengths beyond one chip, `mxnet_tpu.parallel`'s
    ring_attention / ulysses_attention shard the same math over the
    'sp' mesh axis (see parallel/sequence_parallel.py).

Pre-LN GPT-style decoder: x + MHSA(LN(x)); x + FFN(LN(x)).
"""
from __future__ import annotations



from .. import nn
from ..block import HybridBlock


def _write_frontier(F, tokens, pos, nxt, depth):
    """Scatter nxt (N, 1) into tokens (N, Tmax) at column pos+1 — the
    ONE frontier-write implementation (static greedy/sampled decode and
    the beam step all share it)."""
    oh = F.one_hot(pos + 1.0, depth=depth)
    return tokens * (1.0 - oh) + nxt * oh


def _kv_forward(F, net, tok, pos, caches):
    """The one-token decode stack walk shared by the KV and beam cells:
    (tok (N,1) ids, pos (1,), 2L caches (N,H,Tmax,dh)) -> (logits
    (N, V), updated caches).  Re-composes the SAME sub-blocks and
    parameters as the training forward."""
    x = net.tok(tok) + F.expand_dims(net.pos(pos), axis=0)
    new_caches = []
    for i, blk in enumerate(net.blocks._children):
        h = blk.ln1(x)
        qkv = blk.attn.qkv(h)                       # (N, 1, 3D)
        att, kc, vc = F.mha_decode_step(
            qkv, caches[2 * i], caches[2 * i + 1], pos,
            num_heads=blk.attn._h,
            impl=(blk.attn._type
                  if blk.attn._type in ("ring", "ulysses") else "dense"))
        new_caches += [kc, vc]
        x = x + blk.attn.proj(att)
        x = x + blk.ffn2(blk.ffn1(blk.ln2(x)))
    logits = net.head(net.ln_f(x))                  # (N, 1, V)
    return F.reshape(logits, (0, -1)), new_caches


class MultiHeadSelfAttention(HybridBlock):
    """Causal multi-head self-attention over (B, T, D) activations.

    attn_type: 'dense' | 'flash' (Pallas kernel, TPU hot path) |
    'ring' / 'ulysses' (sequence parallelism over the ambient
    `parallel.sp_scope(mesh)` — trace/call the model inside the scope).
    The sp types compose with eager blocks out of the box (the op
    reshards to the mesh and back); under a jitted executor the whole
    step must run over the same mesh (sharded inputs/params), which is
    how a real sp training step executes anyway.
    """

    def __init__(self, dim, num_heads, attn_type="dense", dropout=0.0,
                 **kw):
        super().__init__(**kw)
        assert dim % num_heads == 0
        if attn_type not in ("dense", "flash", "ring", "ulysses"):
            raise ValueError(f"unknown attn_type {attn_type!r}")
        self._h = num_heads
        self._dh = dim // num_heads
        self._type = attn_type
        with self.name_scope():
            self.qkv = nn.Dense(3 * dim, use_bias=True, flatten=False,
                                prefix="qkv_")
            self.proj = nn.Dense(dim, use_bias=True, flatten=False,
                                 prefix="proj_")
            self.drop = nn.Dropout(dropout) if dropout else None

    def hybrid_forward(self, F, x):
        # the shape-dependent head split / mask / merge lives inside the
        # fused `_contrib_multihead_attention` op (ops always see
        # concrete shapes) — so this block hybridizes to a symbol graph
        qkv = self.qkv(x)                                   # (B,T,3D)
        # 'ring'/'ulysses' shard the sequence over the ambient
        # parallel.sp_scope mesh — trace the model inside the scope
        out = F.multihead_attention(qkv, num_heads=self._h, causal=True,
                                    impl=self._type)
        out = self.proj(out)
        return self.drop(out) if self.drop is not None else out


class TransformerBlock(HybridBlock):
    def __init__(self, dim, num_heads, ffn_dim, attn_type="dense",
                 dropout=0.0, **kw):
        super().__init__(**kw)
        with self.name_scope():
            self.ln1 = nn.LayerNorm(prefix="ln1_")
            self.attn = MultiHeadSelfAttention(dim, num_heads, attn_type,
                                               dropout, prefix="attn_")
            self.ln2 = nn.LayerNorm(prefix="ln2_")
            self.ffn1 = nn.Dense(ffn_dim, activation="relu", flatten=False,
                                 prefix="ffn1_")
            self.ffn2 = nn.Dense(dim, flatten=False, prefix="ffn2_")
            self.drop = nn.Dropout(dropout) if dropout else None

    def hybrid_forward(self, F, x):
        x = x + self.attn(self.ln1(x))
        h = self.ffn2(self.ffn1(self.ln2(x)))
        if self.drop is not None:
            h = self.drop(h)
        return x + h


class TransformerLM(HybridBlock):
    """GPT-style causal LM: token ids (B, T) → logits (B, T, vocab)."""

    def __init__(self, vocab, dim=128, num_layers=2, num_heads=4,
                 ffn_dim=None, max_len=512, attn_type="dense",
                 dropout=0.0, **kw):
        super().__init__(**kw)
        self._max_len = max_len
        with self.name_scope():
            self.tok = nn.Embedding(vocab, dim, prefix="tok_")
            self.pos = nn.Embedding(max_len, dim, prefix="pos_")
            self.blocks = nn.HybridSequential(prefix="blocks_")
            for i in range(num_layers):
                self.blocks.add(TransformerBlock(
                    dim, num_heads, ffn_dim or 4 * dim, attn_type,
                    dropout, prefix=f"l{i}_"))
            self.ln_f = nn.LayerNorm(prefix="lnf_")
            self.head = nn.Dense(vocab, flatten=False, prefix="head_")

    def hybrid_forward(self, F, tokens):
        if hasattr(tokens, "shape") and tokens.shape[1] > self._max_len:
            raise ValueError(
                f"sequence length {tokens.shape[1]} exceeds max_len "
                f"{self._max_len} — positions would silently clamp")
        pos_ids = F.broadcast_like(
            F.expand_dims(F.arange_like(tokens, axis=1), 0), tokens)
        x = self.tok(tokens) + self.pos(pos_ids)
        x = self.blocks(x)
        return self.head(self.ln_f(x))


    def generate(self, prompt, max_new, temperature=0.0, rng=None,
                 static_shapes=None, kv_cache=False, top_k=0,
                 top_p=0.0):
        """Autoregressive decoding from `prompt` (B, T0) token ids.

        Greedy when temperature==0, else softmax sampling.

        static_shapes=True (default — the TPU path): tokens live in a
        fixed (B, max_len) buffer and every decode step is ONE cached
        hybridized program whose shapes never change, so XLA compiles
        once for the whole generation (greedy stays entirely on
        device).  Causality makes this exact: positions beyond the
        frontier hold zeros and cannot influence earlier logits
        (pinned by tests/test_transformer.py::test_causal_masking).

        static_shapes=False re-runs the forward on the growing prefix
        — one fresh XLA program PER LENGTH (a compile per token;
        kept as the debugging/parity reference).

        kv_cache=True decodes through per-layer K/V caches
        (`mha_decode_step`): O(Tmax*D) work per token instead of the
        full re-forward's O(Tmax^2*D) — the long-context decode path.
        One cached program per step; position and caches ride as data.
        """
        import numpy as np
        from ... import ndarray as F
        B, t0 = prompt.shape
        if t0 + max_new > self._max_len:
            raise ValueError(
                f"prompt length {t0} + max_new {max_new} "
                f"exceeds max_len {self._max_len}")
        if kv_cache:
            if static_shapes is not None:
                raise ValueError(
                    "kv_cache=True selects its own decode strategy; "
                    "combining it with an explicit static_shapes "
                    "would be silently ignored — pass one or the other")
            self._check_kv_supported()
            return self._generate_kv(prompt, max_new, temperature, rng,
                                     top_k, top_p)
        static_shapes = True if static_shapes is None else static_shapes
        if not static_shapes:
            toks = prompt
            for _ in range(max_new):
                logits = self(toks)                  # (B, T, V)
                last = logits[:, -1, :]
                nxt = self._sample(last, temperature, rng, top_k, top_p)
                toks = F.concat(toks, F.array(nxt, ctx=toks.context),
                                dim=1)
            return toks

        steps = self._decode_steps()
        pad = self._max_len - t0
        buf = prompt if pad == 0 else F.concat(
            prompt, F.zeros((B, pad), ctx=prompt.context), dim=1)
        for t in range(t0, t0 + max_new):
            pos = F.array([t - 1.0], ctx=prompt.context)
            if temperature == 0:
                buf = steps["greedy"](buf, pos)      # fully on device
            else:
                last = steps["logits"](buf, pos)     # (B, V)
                nxt = self._sample(last, temperature, rng, top_k, top_p)
                buf = steps["write"](buf, pos,
                                     F.array(nxt, ctx=prompt.context))
        return F.slice_axis(buf, axis=1, begin=0, end=t0 + max_new)

    def _init_caches(self, batch, ctx=None, dtype=None, sharded=None):
        """Zero per-layer K/V caches, (batch, H, max_len, dh) x 2L —
        the ONE cache-construction site (KV decode, beam search, and
        the decode-step export all share it).  sharded=(mesh, axis,
        kind) allocates each cache host->shards directly — 'ring'
        splits the sequence axis, 'ulysses' the head axis — so a
        cache larger than one device's memory is never materialized
        on one device."""
        from ... import ndarray as F
        blocks = self.blocks._children
        h, dh = blocks[0].attn._h, blocks[0].attn._dh
        shape = (batch, h, self._max_len, dh)
        if sharded is not None:
            import jax
            import numpy as np
            from jax.sharding import NamedSharding, PartitionSpec as P
            from ...ndarray import NDArray
            mesh, axis, kind = sharded
            sh = NamedSharding(mesh, P(None, None, axis, None)
                               if kind == "ring"
                               else P(None, axis, None, None))
            host = np.zeros(shape, np.dtype(dtype or "float32"))
            return [NDArray(jax.device_put(host, sh))
                    for _ in range(2 * len(blocks))]
        kw = {}
        if ctx is not None:
            kw["ctx"] = ctx
        if dtype is not None:
            kw["dtype"] = dtype
        return [F.zeros(shape, **kw) for _ in range(2 * len(blocks))]

    def _check_kv_supported(self, allow_sp=True):
        """kv_cache decode support by attention type.  'ring' decodes
        over SEQUENCE-SHARDED caches (ring_decode_step; max_len must
        divide by the axis size) and 'ulysses' over HEAD-SHARDED
        caches (ulysses_decode_step; num_heads must divide) — both
        require an active parallel.sp_scope.  Beam search and the
        decode-step export are dense-cache paths (allow_sp=False)."""
        from ...parallel.sequence_parallel import current_sp_scope
        for blk in self.blocks._children:
            t = blk.attn._type
            if t not in ("ring", "ulysses"):
                continue
            if not allow_sp:
                raise NotImplementedError(
                    f"attn_type {t!r} is not supported on this decode "
                    "path — decode with static_shapes instead")
            mesh, axis = current_sp_scope()       # loud error if absent
            n = mesh.shape[axis]
            if t == "ring" and self._max_len % n:
                raise ValueError(
                    f"ring kv decode shards the cache over '{axis}' "
                    f"(size {n}); max_len {self._max_len} must be "
                    "divisible by it")
            if t == "ulysses" and blk.attn._h % n:
                raise ValueError(
                    f"ulysses kv decode shards heads over '{axis}' "
                    f"(size {n}); num_heads {blk.attn._h} must be "
                    "divisible by it")

    @staticmethod
    def _sample(last, temperature, rng, top_k=0, top_p=0.0):
        """Host-side next-token choice from (B, V) logits -> (B, 1).

        top_k > 0 keeps only the k most likely tokens; 0 < top_p <= 1
        keeps the smallest set whose cumulative probability reaches
        top_p (nucleus sampling, always at least the argmax); both
        filters compose (top-k first, then top-p)."""
        import numpy as np
        if top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {top_k}")
        if not 0.0 <= top_p <= 1.0:
            raise ValueError(f"top_p must be in [0, 1], got {top_p}")
        if temperature <= 0:
            return last.asnumpy().argmax(-1).astype(np.float32)[:, None]
        logits = last.asnumpy().astype(np.float64) / temperature
        out = np.empty((logits.shape[0], 1), np.float32)
        r = rng or np.random
        for b, row in enumerate(logits):
            if top_k and top_k < row.size:
                # exactly k survivors even under ties, chosen in
                # stable (first-occurrence) order so top_k=1 keeps
                # precisely the greedy argmax token
                keep = np.argsort(-row, kind="stable")[:top_k]
                masked = np.full_like(row, -np.inf)
                masked[keep] = row[keep]
                row = masked
            p = np.exp(row - row.max())
            p /= p.sum()
            if 0.0 < top_p < 1.0:
                order = np.argsort(-p)
                cum = np.cumsum(p[order])
                # keep the minimal prefix reaching top_p (>= 1 token)
                cut = int(np.searchsorted(cum, top_p)) + 1
                mask = np.zeros_like(p, bool)
                mask[order[:cut]] = True
                p = np.where(mask, p, 0.0)
                p /= p.sum()
            out[b, 0] = r.choice(p.size, p=p)
        return out

    def _decode_steps(self):
        """Build (once) the three hybridized decode-step blocks.

        Stored in __dict__ via a plain dict so Block.__setattr__ does
        not register them as children (the wrapper holds `self` as its
        sub-block — registration would create a parent<->child cycle).
        Only each wrapper's OWN hybrid flag is set: Block.hybridize()
        would recurse into the wrapped model and silently flip a
        deliberately-eager net into hybrid mode (symbol tracing routes
        through hybrid_forward regardless of the net's flag, so the
        wrapper's CachedOp doesn't need it).
        """
        cached = self.__dict__.get("_decode_step_cache")
        if cached is not None:
            return cached
        from ..block import HybridBlock

        outer = self

        def _write_at(F, tokens, pos, nxt):
            return _write_frontier(F, tokens, pos, nxt, outer._max_len)

        class _LogitsStep(HybridBlock):
            """(tokens (B,Tmax), pos (1,)) -> logits at pos, (B, V)."""

            def __init__(self, **kw):
                super().__init__(**kw)
                with self.name_scope():
                    self.net = outer

            def hybrid_forward(self, F, tokens, pos):
                logits = self.net(tokens)            # (B, Tmax, V)
                last = F.take(logits, pos, axis=1)   # (B, 1, V)
                return F.reshape(last, (0, -1))

        class _GreedyStep(_LogitsStep):
            """One whole greedy step on device: read logits at pos,
            argmax, write the winner at pos+1; returns the updated
            (B, Tmax) buffer."""

            def hybrid_forward(self, F, tokens, pos):
                last = super().hybrid_forward(F, tokens, pos)
                nxt = F.argmax(last, axis=-1, keepdims=True)  # (B, 1)
                return _write_at(F, tokens, pos, nxt)

        class _WriteStep(HybridBlock):
            """(tokens, pos, nxt (B,1)) -> tokens with nxt at pos+1."""

            def hybrid_forward(self, F, tokens, pos, nxt):
                return _write_at(F, tokens, pos, nxt)

        steps = {"logits": _LogitsStep(), "greedy": _GreedyStep(),
                 "write": _WriteStep()}
        for blk in steps.values():
            blk._active = True                 # this wrapper only
        self.__dict__["_decode_step_cache"] = steps
        return steps

    def _kv_step(self):
        """Build (once) the KV-cache decode cell: ONE hybridized
        program computing (token_t, pos, *caches) -> (logits_t,
        *updated caches).  Re-composes the stack from the SAME
        sub-blocks/parameters as the training forward — LN, fused QKV,
        `mha_decode_step` (cache write + masked attention over the
        cache), projection, FFN, head — so decode weights can never
        drift from training weights.  Same child-registration and
        hybrid-flag rules as _decode_steps."""
        cached = self.__dict__.get("_kv_step_cache")
        if cached is not None:
            return cached
        from ..block import HybridBlock

        outer = self

        class _KVStep(HybridBlock):
            """(token_t (B,1), pos (1,), *caches) -> [head, *caches].
            greedy=True emits the argmax NEXT TOKEN as the head output
            (the whole step stays on device and its output feeds the
            next step without a host sync); greedy=False emits the
            (B, V) logits for host-side sampling."""

            def __init__(self, greedy, **kw):
                super().__init__(**kw)
                self._greedy = greedy
                with self.name_scope():
                    self.net = outer

            def hybrid_forward(self, F, tok, pos, *caches):
                logits, new_caches = _kv_forward(F, self.net, tok, pos,
                                                 caches)
                head = (F.argmax(logits, axis=-1, keepdims=True)
                        if self._greedy else logits)
                return [head] + new_caches

        steps = {"sample": _KVStep(False), "greedy": _KVStep(True)}
        for blk in steps.values():
            blk._active = True                  # this wrapper only
        self.__dict__["_kv_step_cache"] = steps
        return steps

    def _generate_kv(self, prompt, max_new, temperature, rng,
                     top_k=0, top_p=0.0):
        """KV-cache decode loop: prefill feeds prompt tokens through
        the same one-token cell that generates (cache fills as a side
        effect); every step reuses one compiled program.  Greedy keeps
        the whole loop on device — generated tokens come back as
        (B, 1) handles chained step-to-step and are fetched ONCE at
        the end (async dispatch: no per-token sync)."""
        import numpy as np
        from ... import ndarray as F
        B, t0 = prompt.shape
        ctx = prompt.context
        greedy = temperature == 0
        sp_type = next((blk.attn._type for blk in self.blocks._children
                        if blk.attn._type in ("ring", "ulysses")), None)
        if sp_type:
            # sequence-sharded caches: run the stack walk eagerly so
            # the ring decode op shards over the ambient sp mesh per
            # call (a jitted cell would need the whole step — params
            # included — placed on the mesh, the same rule as the sp
            # training forward)
            def run_step(cur, pos, caches):
                logits, nc = _kv_forward(F, self, cur, pos, caches)
                head = (F.argmax(logits, axis=-1, keepdims=True)
                        if greedy else logits)
                return head, nc
        else:
            cell = self._kv_step()["greedy" if greedy else "sample"]

            def run_step(cur, pos, caches):
                outs = cell(cur, pos, *caches)
                return outs[0], outs[1:]
        if sp_type:
            from ...parallel.sequence_parallel import current_sp_scope
            caches = self._init_caches(
                B, dtype=self.head.weight.dtype,
                sharded=current_sp_scope() + (sp_type,))
        else:
            caches = self._init_caches(B, ctx=ctx,
                                       dtype=self.head.weight.dtype)
        toks_np = prompt.asnumpy()
        pieces = [prompt]                  # (B, k) device-side chunks
        cur = F.array(toks_np[:, 0:1], ctx=ctx)
        for t in range(t0 + max_new - 1):
            pos = F.array([float(t)], ctx=ctx)
            head, caches = run_step(cur, pos, caches)
            if t + 1 < t0:                 # prefill: next prompt column
                cur = F.array(toks_np[:, t + 1:t + 2], ctx=ctx)
            elif greedy:
                cur = head                 # stays on device
                pieces.append(cur)
            else:
                nxt = self._sample(head, temperature, rng, top_k, top_p)
                cur = F.array(nxt, ctx=ctx)
                pieces.append(cur)
        return F.concat(*pieces, dim=1)

    def _beam_step(self, width):
        """Build (once per width) the beam-search step cell: ONE
        hybridized program that advances every beam one token —
        decode-stack logits, log-softmax, combined scores, top-k over
        (width*vocab), beam/cache reindex via gather, frontier write.
        Inputs: (cur (B*W,1), pos (1,), cum (B,W), buf (B*W,Tmax),
        offsets (B,W) = arange(B)*W, *caches); outputs: [cur', cum',
        buf', *caches'].  Same child-registration/hybrid-flag rules as
        the other decode wrappers."""
        cache = self.__dict__.setdefault("_beam_step_cache", {})
        if width in cache:
            return cache[width]
        from ..block import HybridBlock

        outer = self
        vocab = self.head._units

        class _BeamStep(HybridBlock):
            def __init__(self, **kw):
                super().__init__(**kw)
                with self.name_scope():
                    self.net = outer

            def hybrid_forward(self, F, cur, pos, cum, buf, offsets,
                               *caches):
                W = width
                logits, new_caches = _kv_forward(F, self.net, cur, pos,
                                                 caches)        # (BW, V)
                V = vocab
                logp = F.log_softmax(logits, axis=-1)
                scores = F.reshape(cum, (-1, 1)) + logp         # (BW, V)
                scores = F.reshape(scores, (-1, W * V))         # (B, W*V)
                idx = F.topk(scores, k=W, ret_typ="indices", axis=-1,
                             is_ascend=False)                   # (B, W)
                # the value call re-sorts the same tensor inside the
                # same traced program — XLA CSE merges the two argsorts
                # into one, so this costs nothing at runtime
                new_cum = F.topk(scores, k=W, ret_typ="value", axis=-1,
                                 is_ascend=False)               # (B, W)
                beam_src = F.floor(idx / V)                     # (B, W)
                tok = idx - beam_src * V                        # (B, W)
                flat_src = F.reshape(beam_src + offsets, (-1,))  # (BW,)
                buf = F.take(buf, flat_src, axis=0)
                new_caches = [F.take(c, flat_src, axis=0)
                              for c in new_caches]
                tokcol = F.reshape(tok, (-1, 1))                # (BW, 1)
                buf = _write_frontier(F, buf, pos, tokcol,
                                      outer._max_len)
                return [tokcol, new_cum, buf] + new_caches

        step = _BeamStep()
        step._active = True                     # this wrapper only
        cache[width] = step
        return step

    def export_decode_step(self, prefix, batch_size=1):
        """Write the KV decode cell as a standalone predict artifact —
        `{prefix}-symbol.json` + `{prefix}-0000.params` — loadable by
        `mxnet_tpu.predictor.Predictor` AND the flat-C inference ABI
        (`libmxt_predict.so`, parity c_predict_api.h): a plain-C
        program can run LM decoding by looping SetInput(token, pos,
        caches) / Forward / GetOutput(logits, caches), feeding the
        cache outputs back in.

        Inputs (in order): data0 token (B, 1), data1 pos (1,),
        data2..data{2L+1} per-layer K/V caches (B, H, max_len, dh).
        Outputs: [logits (B, vocab), *updated caches].  Returns the
        input-name list.
        """
        from ... import ndarray as F
        from ...model import save_checkpoint
        self._check_kv_supported(allow_sp=False)
        step = self._kv_step()["sample"]
        tok = F.zeros((batch_size, 1))
        pos = F.array([0.0])
        caches = self._init_caches(batch_size)
        inputs, out = step._get_graph(tok, pos, *caches)
        aux_names = set(out.list_auxiliary_states())
        params = {name: p.data()
                  for name, p in step.collect_params().items()}
        save_checkpoint(
            prefix, 0, out,
            {k: v for k, v in params.items() if k not in aux_names},
            {k: v for k, v in params.items() if k in aux_names})
        return [i.name for i in inputs]

    def beam_search(self, prompt, max_new, beam=4):
        """Beam-search decoding over the KV-cache cell.

        Returns (sequences (B, T0+max_new), log_probs (B,)): the
        highest-scoring beam per example and its total log-probability
        over the generated positions.  Every step is one cached
        program: beams ride as batch rows (B*beam), the top-k over
        combined scores, the beam/cache reindex (gather) and the
        frontier write all stay on device; the host fetches once at
        the end.  No EOS handling — the toy LM family has no reserved
        ids; all beams run the full max_new (document-level parity:
        the 2017 reference has no decoder at all).
        """
        import numpy as np
        from ... import ndarray as F
        if beam < 1:
            raise ValueError("beam must be >= 1")
        B, t0 = prompt.shape
        if t0 + max_new > self._max_len:
            raise ValueError(
                f"prompt length {t0} + max_new {max_new} "
                f"exceeds max_len {self._max_len}")
        self._check_kv_supported(allow_sp=False)
        W = beam
        ctx = prompt.context
        prefill = self._kv_step()["sample"]
        step = self._beam_step(W)
        # prefill at B rows (beams are identical over the prompt), then
        # tile the caches to B*W — prompt-dominated decodes must not pay
        # the beam width during prefill
        caches = self._init_caches(B, ctx=ctx,
                                   dtype=self.head.weight.dtype)
        prompt_np = prompt.asnumpy()             # (B, t0)
        cur = F.array(prompt_np[:, 0:1], ctx=ctx)
        for t in range(t0 - 1):                  # prefill prompt tokens
            outs = prefill(cur, F.array([float(t)], ctx=ctx), *caches)
            caches = outs[1:]
            cur = F.array(prompt_np[:, t + 1:t + 2], ctx=ctx)
        caches = [F.repeat(c, repeats=W, axis=0) for c in caches]
        toks_np = np.repeat(prompt_np, W, axis=0)          # (BW, t0)
        pad = self._max_len - t0
        buf = F.array(np.concatenate(
            [toks_np, np.zeros((B * W, pad), "f")], axis=1)
            if pad else toks_np, ctx=ctx)
        # only beam 0 contributes until beams diverge
        cum = F.array(np.tile([0.0] + [-1e30] * (W - 1), (B, 1)), ctx=ctx)
        offsets = F.array(np.arange(B)[:, None] * W *
                          np.ones((1, W), "f"), ctx=ctx)
        cur = F.array(toks_np[:, t0 - 1:t0], ctx=ctx)
        for t in range(t0 - 1, t0 + max_new - 1):
            outs = step(cur, F.array([float(t)], ctx=ctx), cum, buf,
                        offsets, *caches)
            cur, cum, buf, caches = outs[0], outs[1], outs[2], outs[3:]
        buf_np = buf.asnumpy()[:, :t0 + max_new].reshape(B, W, -1)
        cum_np = cum.asnumpy()                   # (B, W), sorted desc
        best = buf_np[:, 0, :]                   # topk is descending
        return (F.array(best, ctx=ctx),
                F.array(cum_np[:, 0], ctx=ctx))


def transformer_lm(vocab, **kwargs):
    return TransformerLM(vocab, **kwargs)
