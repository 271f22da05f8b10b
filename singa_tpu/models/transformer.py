"""GPT-style decoder-only LM — the long-context flagship.

Beyond reference scope (SINGA has no transformer; SURVEY.md §2.3/§5): this
model family exists because long-context + sequence parallelism are
first-class here. `seq_axis` turns every block's attention into ring
attention over that mesh axis (K/V shards rotate over ICI), so context
length scales with the number of chips.
"""

from __future__ import annotations

import jax

from .. import autograd, layer, model
from ..tensor import Tensor, float32
# serving engine lives in singa_tpu/serving.py; re-exports kept so
# existing imports (tests, examples) stay valid
from ..serving import (_DecodeCore, _cast_params, _decode_core, _mm,  # noqa: F401
                       _pool_merge, _quant8, _set_col, build_beam_decode,
                       build_decode, decode_params, decode_raw,
                       decode_state)


class _PosSlice(autograd.Operator):
    """Slice `length` rows of the position table starting at this device's
    global sequence offset (axis_index * length when sequence-sharded)."""

    def __init__(self, length, seq_axis=None):
        super().__init__("PosSlice")
        self.length = length
        self.seq_axis = seq_axis

    def forward(self, table):
        from jax import lax
        off = 0
        if self.seq_axis is not None:
            try:
                off = lax.axis_index(self.seq_axis) * self.length
            except NameError:
                off = 0
        return lax.dynamic_slice_in_dim(table, off, self.length, axis=0)


class _VocabTPMixin:
    """Shared Megatron vocab-parallel head logic for GPT and PipelinedGPT:
    one (V_pad, E) table row-sharded over tp_axis serves as embedding AND
    (transposed) tied head; the loss consumes sharded logits."""

    def _vp_active(self):
        return self.vocab_tp and autograd.axis_bound(self.tp_axis)

    def _tied_logits(self, h):
        """Logits through the embedding-tied head: h @ W_emb^T. Under an
        active tp mesh the table is vocab-sharded, so each device emits
        its (B, S, V/tp) slice (Megatron f on the input: psum of dL/dh)."""
        if self._vp_active():
            h = autograd.tp_copy(h, self.tp_axis)
        hc, Wc = autograd.compute_cast(h, self.tok_embed.W)
        return autograd.matmul(hc, autograd.transpose(Wc),
                               out_dtype="float32")

    def _slice_valid(self, logits):
        if self.padded_vocab == self.vocab_size:
            return logits
        return autograd.slice(logits, [0], [self.vocab_size],
                              [len(logits.shape) - 1])

    def _vp_loss_and_logits(self, local, targets):
        """(loss, caller-facing logits) from SHARDED tied-head logits."""
        tflat = autograd.reshape(targets, (-1,))
        if self._vp_active():
            flat = autograd.reshape(local, (-1, local.shape[-1]))
            loss = autograd.vocab_parallel_sce(
                flat, tflat, self.tp_axis, valid_vocab=self.vocab_size)
            if getattr(self, "vocab_tp_return_logits", True):
                logits = self._slice_valid(
                    autograd.gather_last(local, self.tp_axis))
            else:
                logits = autograd.vocab_parallel_argmax(
                    local, self.tp_axis, valid_vocab=self.vocab_size)
        else:
            logits = self._slice_valid(local)
            flat = autograd.reshape(logits, (-1, self.vocab_size))
            loss = self.sce(flat, tflat)
        return loss, logits


class GPT(_VocabTPMixin, model.Model):

    def __init__(self, vocab_size, max_seq=1024, dim=256, num_heads=8,
                 num_layers=4, mlp_ratio=4, seq_axis=None, tp_axis=None,
                 attn_bias=False, vocab_tp=False, vocab_pad_multiple=128,
                 vocab_tp_return_logits=True,
                 moe_experts=0, moe_k=2, ep_axis=None,
                 moe_capacity_factor=1.25, moe_aux_weight=0.01,
                 moe_z_weight=1e-3, num_kv_heads=None,
                 pos_encoding="learned", rope_theta=10000.0, name=None):
        super().__init__(name)
        assert pos_encoding in ("learned", "rope"), pos_encoding
        # "rope": rotary q/k per block (no learned position table; the
        # model length-generalizes and the decode rotates at the cache
        # position); "learned": the GPT-2-style trained table.
        self.pos_encoding = pos_encoding
        self.rope_theta = float(rope_theta)
        self.vocab_size = vocab_size
        self.max_seq = max_seq
        self.dim = dim
        # Megatron vocab parallelism: at GPT-2 scale the
        # (V, E) embedding and head are the model's largest tensors;
        # `vocab_tp=True` row-shards ONE table over tp_axis and ties the
        # head to it (logits = h @ W_emb^T), instead of replicating both.
        # The vocab is padded to a multiple of `vocab_pad_multiple` so any
        # tp degree dividing it works (50257 -> 50304, Megatron's scheme);
        # padded columns are masked out of the loss and sliced off the
        # returned logits.
        # vocab_tp_return_logits=False keeps the full (B,S,V) logits out of
        # the hot train step entirely: train_one_batch then returns the
        # per-token argmax predictions (B,S) int32 instead of logits — at
        # GPT-2 vocab the all_gather of (B,S,50304) fp32 every step exists
        # only to be returned, so serious training should turn it off.
        self.vocab_tp_return_logits = vocab_tp_return_logits
        if vocab_tp and tp_axis is None:
            raise ValueError(
                "vocab_tp=True needs tp_axis: vocab parallelism shards the "
                "embedding/head over a tensor-parallel mesh axis. Without "
                "one the model would silently build a different parameter "
                "set (untied head, unpadded vocab)")
        self.vocab_tp = bool(vocab_tp)
        if self.vocab_tp:
            m = vocab_pad_multiple
            self.padded_vocab = ((vocab_size + m - 1) // m) * m
            self.tok_embed = layer.Embedding(self.padded_vocab, dim,
                                             tp_axis=tp_axis)
            self.head = None        # tied to tok_embed.W
        else:
            self.padded_vocab = vocab_size
            self.tok_embed = layer.Embedding(vocab_size, dim)
            # fp32-accumulated logits: under amp the CE loss would
            # otherwise upcast the full (B,S,V) tensor
            self.head = layer.Linear(vocab_size, bias=False,
                                     out_dtype="float32")
        # MoE-GPT: moe_experts>0 swaps every block's dense
        # MLP for a top-moe_k expert-parallel MoE FFN; the router's
        # load-balance and z losses are folded into the training loss with
        # the ST-MoE default weights.
        self.moe_experts = moe_experts
        self.moe_aux_weight = moe_aux_weight
        self.moe_z_weight = moe_z_weight
        blocks = [layer.TransformerBlock(
            num_heads, mlp_ratio, causal=True, seq_axis=seq_axis,
            tp_axis=tp_axis, attn_bias=attn_bias, moe_experts=moe_experts,
            moe_k=moe_k, ep_axis=ep_axis,
            moe_capacity_factor=moe_capacity_factor,
            num_kv_heads=num_kv_heads,
            rope=(pos_encoding == "rope"), rope_theta=rope_theta)
                  for _ in range(num_layers)]
        self.blocks = blocks
        self.register_layers(*blocks)
        self.ln_f = layer.LayerNorm()
        self.sce = layer.SoftMaxCrossEntropy()
        self.seq_axis = seq_axis
        self.tp_axis = tp_axis
        self._pos_init = False

    def _pos_embedding(self, x):
        if not self._pos_init:
            p = Tensor((self.max_seq, self.dim), device=x.device,
                       dtype=float32)
            p.gaussian(0.0, 0.02)
            self._register_param("pos_embed", p)
            self._pos_init = True
        S = x.shape[1]  # local shard length under sequence parallelism
        return _PosSlice(S, self.seq_axis)(self.pos_embed)

    def _backbone(self, ids):
        # ids: (B, S) int32 -> (B, S, E) post-final-LN hidden states
        h = self.tok_embed(ids)
        if self.pos_encoding == "rope":
            # positions live in the per-block q/k rotation; no table.
            # (_pos_init still gates the decode-params contract)
            self._pos_init = True
        else:
            # the table's key in get_params(): the scope of what reads it
            with jax.named_scope("pos_embed"):
                pos = self._pos_embedding(h)
                h = autograd.add(h, autograd.expand(pos, h.shape))
        for b in self.blocks:
            h = b(h)
        return self.ln_f(h)

    def forward(self, ids):
        h = self._backbone(ids)
        if not self.vocab_tp:
            return self.head(h)                       # (B, S, V)
        local = self._tied_logits(h)
        if self._vp_active():
            local = autograd.gather_last(local, self.tp_axis)
        return self._slice_valid(local)

    def _moe_losses(self, loss, device):
        """Fold every block's router losses into the training loss."""
        if not self.moe_experts:
            return loss
        import numpy as np
        if not hasattr(self, "_moe_w"):
            from ..tensor import from_numpy
            self._moe_w = (
                from_numpy(np.float32(self.moe_aux_weight), device=device),
                from_numpy(np.float32(self.moe_z_weight), device=device))
        aw, zw = self._moe_w
        for b in self.blocks:
            loss = autograd.add(loss, autograd.mul(b.moe.aux_loss, aw))
            loss = autograd.add(loss, autograd.mul(b.moe.z_loss, zw))
        return loss

    def train_one_batch(self, ids, targets):
        if not self.vocab_tp:
            logits = self.forward(ids)
            # (B, S, V) as the head wrote them: a 2-D view would be a
            # relayout where the compiler laid the vocabulary out second
            loss = self._moe_losses(self.sce(logits, targets), ids.device)
            self.optimizer(loss)
            return logits, loss
        # vocab-parallel path: the loss consumes the SHARDED logits (full
        # (B,S,V) never materialized in the loss graph); the gathered
        # logits exist only on the caller-facing output edge.
        h = self._backbone(ids)
        local = self._tied_logits(h)
        loss, logits = self._vp_loss_and_logits(local, targets)
        loss = self._moe_losses(loss, ids.device)
        self.optimizer(loss)
        return logits, loss

    # ---- serving: KV-cached autoregressive decoding ---------------------
    # The reference's LLM-serving story is ONNX-imported GPT-2 replaying
    # the full graph per token (examples/onnx/gpt2/gpt2.py re-runs the
    # whole prefix each step). TPU-native redesign: one jitted function =
    # prefill + lax.scan over decode steps with a preallocated (T-length)
    # KV cache updated via dynamic_update_slice — O(T) per token instead
    # of O(T^2), no retrace per step, static shapes throughout.

    def _decode_raw(self):
        return decode_raw(self)

    def _decode_state(self, dtype):
        """Memoized decode-param tree (serving.decode_state): QKV fusion
        + cast/quantize run once per weight set; deterministic
        invalidation on any param-buffer replacement."""
        return decode_state(self, dtype)

    def _decode_params(self):
        return decode_params(self)

    def _build_decode(self, *args, **kwargs):
        return build_decode(self, *args, **kwargs)

    def _build_beam_decode(self, *args, **kwargs):
        return build_beam_decode(self, *args, **kwargs)

    def generate_beam(self, prompt, max_new_tokens, num_beams=4,
                      length_penalty=1.0, eos_id=None, pad_id=None,
                      dtype=None, return_scores=False,
                      moe_capacity_factor=None, kv_dtype=None):
        """Beam-search decoding (no reference equivalent; its GPT-2
        example is greedy). One jitted function: prefill once, tile the
        KV cache across beams, and a `lax.scan` whose carry reorders
        cache rows by winning parent beam each step. With `eos_id`,
        finished hypotheses move to a length-normalized pool (HF
        semantics) and the tail after eos is filled with `pad_id`
        (default: eos_id). Returns (B, S0+max_new_tokens) token ids
        (+ the chosen hypothesis' joint log-prob when
        `return_scores`)."""
        import jax
        import numpy as np
        ids = prompt.numpy() if isinstance(prompt, Tensor) \
            else np.asarray(prompt)
        assert ids.ndim == 2 and ids.shape[1] >= 1, \
            "prompt must be (batch, length>=1)"
        assert max_new_tokens >= 1 and num_beams >= 1
        assert num_beams <= self.vocab_size, \
            f"num_beams {num_beams} exceeds vocab_size {self.vocab_size}"
        B, S0 = ids.shape
        assert kv_dtype in (None, "int8", "int4"), kv_dtype
        sig = ("beam", B, S0, max_new_tokens, num_beams,
               float(length_penalty), eos_id, pad_id, dtype,
               moe_capacity_factor, kv_dtype)
        cache = getattr(self, "_decode_cache", None)
        if cache is None:
            cache = self._decode_cache = {}
        fn = cache.get(sig)
        if fn is None:
            fn = cache[sig] = self._build_beam_decode(
                B, S0, max_new_tokens, num_beams, float(length_penalty),
                eos_id, dtype, pad_id, moe_capacity_factor, kv_dtype)
        out, scores = fn(self._decode_state(dtype), ids.astype(np.int32))
        out = np.asarray(jax.device_get(out))
        if return_scores:
            return out, np.asarray(jax.device_get(scores))
        return out

    def generate(self, prompt, max_new_tokens, temperature=0.0, top_k=None,
                 seed=0, dtype=None, moe_capacity_factor=None,
                 kv_dtype=None, draft_model=None, spec_k=0):
        """Autoregressive sampling: greedy (temperature=0) or
        temperature/top-k. `prompt` is (B, S0) int32 (numpy or Tensor);
        returns (B, S0+max_new_tokens) numpy. The decode function is
        compiled once per (B, S0, max_new_tokens, sampler, dtype)
        signature. `dtype="bfloat16"` casts weights/activations for the
        decode (≈2x faster on TPU: each step is weight-bandwidth-bound).
        `kv_dtype` quantizes the KV cache ("int8", or packed-nibble
        "int4"). `draft_model`/`spec_k` switch GREEDY decode to
        draft-model speculative decoding (serving.build_spec_decode):
        the draft proposes spec_k tokens per round, the target verifies
        them in one batched forward — output tokens are identical to
        plain greedy by construction, only the wall time changes."""
        import jax
        import numpy as np
        ids = prompt.numpy() if isinstance(prompt, Tensor) \
            else np.asarray(prompt)
        assert ids.ndim == 2, "prompt must be (batch, length)"
        assert max_new_tokens >= 0, "max_new_tokens must be >= 0"
        if max_new_tokens == 0:
            return ids.astype(np.int32).copy()
        assert ids.shape[1] >= 1, "prompt must contain at least one token"
        if temperature == 0.0:
            top_k = None  # greedy ignores top_k; don't fragment the cache
        elif top_k is not None:
            top_k = max(1, min(int(top_k), self.vocab_size))
        B, S0 = ids.shape
        assert kv_dtype in (None, "int8", "int4"), kv_dtype
        cache = getattr(self, "_decode_cache", None)
        if cache is None:
            cache = self._decode_cache = {}
        if draft_model is not None and spec_k:
            assert temperature == 0.0, \
                "speculative decoding is greedy-only (temperature=0)"
            assert draft_model.vocab_size >= self.vocab_size, \
                "draft vocab must cover the target's"
            from ..serving import build_spec_decode, decode_state
            sig = ("spec", B, S0, max_new_tokens, int(spec_k), dtype,
                   moe_capacity_factor, kv_dtype, id(draft_model))
            fn = cache.get(sig)
            if fn is None:
                fn = cache[sig] = build_spec_decode(
                    self, draft_model, B, S0, max_new_tokens,
                    int(spec_k), dtype, moe_capacity_factor, kv_dtype)
            out = fn(self._decode_state(dtype),
                     decode_state(draft_model, dtype),
                     ids.astype(np.int32))
            return np.asarray(jax.device_get(out))
        sig = (B, S0, max_new_tokens, float(temperature), top_k, dtype,
               moe_capacity_factor, kv_dtype)
        fn = cache.get(sig)
        if fn is None:
            fn = cache[sig] = self._build_decode(
                B, S0, max_new_tokens, float(temperature), top_k, dtype,
                moe_capacity_factor, kv_dtype)
        out = fn(self._decode_state(dtype), ids.astype(np.int32),
                 jax.random.PRNGKey(seed))
        return np.asarray(jax.device_get(out))


# ---------------- pipeline-parallel GPT ----------------------------------
# Block params are STACKED (num_layers, ...) tensors with spec P(pp_axis):
# Model's spec-aware shard_map gives each device its contiguous slice of
# layers, and the whole GPipe schedule runs as ONE tape op whose vjp is the
# reverse pipeline (backward ppermutes transposed) with microbatch gradient
# accumulation via the scan cotangent.

def _fn_layernorm(x, g, b, eps=1e-5):
    import jax.numpy as jnp
    from jax import lax
    m = jnp.mean(x, axis=-1, keepdims=True)
    v = jnp.var(x, axis=-1, keepdims=True)
    return (x - m) * lax.rsqrt(v + eps) * g + b


def _fn_block(params, h, num_heads, tp_axis=None, num_kv_heads=None,
              rope=None):
    """Functional pre-LN transformer block; h (B, S, E) replicated over
    `tp_axis`. With tp: Wq/Wk/Wv/W1 arrive column-sharded (local heads =
    num_heads/tp), Wo/W2 row-sharded — the Megatron layout, two psums per
    block, expressed with custom_vjp f/g so the block stays correct under
    both autodiff-through-scan (GPipe) and explicit vjp (1F1B engine).
    `num_kv_heads` < num_heads is GQA: Wk/Wv are (E, Hkv*D) and each kv
    head serves num_heads/Hkv query heads (repeat before flash).
    `rope`: (cos, sin) (S, D) tables — rotate q/k per position (matches
    the GPT layer path, so rope PipelinedGPT weights transfer to a rope
    GPT for serving)."""
    import jax
    import jax.numpy as jnp
    from ..ops.attention import flash_attention
    from ..parallel.tp import megatron_f, megatron_g
    (g1, b1, Wq, Wk, Wv, Wo, g2, b2, W1, bb1, W2, bb2) = params
    B, S, E = h.shape
    heads = num_heads
    kv_heads = num_kv_heads or num_heads
    grp = heads // kv_heads
    if tp_axis is not None:
        tp_n = jax.lax.axis_size(tp_axis)
        heads = num_heads // tp_n
        kv_heads = kv_heads // tp_n
    x = _fn_layernorm(h, g1, b1)
    if tp_axis is not None:
        x = megatron_f(x, tp_axis)
    q = (x @ Wq).reshape(B, S, heads, -1).transpose(0, 2, 1, 3)
    k = (x @ Wk).reshape(B, S, kv_heads, -1).transpose(0, 2, 1, 3)
    v = (x @ Wv).reshape(B, S, kv_heads, -1).transpose(0, 2, 1, 3)
    if rope is not None:
        from ..autograd import apply_rope
        rcos, rsin = rope
        q = apply_rope(q, rcos, rsin)
        k = apply_rope(k, rcos, rsin)
    if grp > 1:
        k = jnp.repeat(k, grp, axis=1)
        v = jnp.repeat(v, grp, axis=1)
    o = flash_attention(q, k, v, True)
    o = o.transpose(0, 2, 1, 3).reshape(B, S, -1)
    o = o @ Wo
    if tp_axis is not None:
        o = megatron_g(o, tp_axis)
    h = h + o
    x = _fn_layernorm(h, g2, b2)
    if tp_axis is not None:
        x = megatron_f(x, tp_axis)
    y = jax.nn.gelu(x @ W1 + bb1) @ W2
    if tp_axis is not None:
        y = megatron_g(y, tp_axis)
    return h + y + bb2


def _fn_block_moe(params, h, num_heads, k, capacity_factor, ep_axis=None,
                  rope=None):
    """Pre-LN transformer block whose MLP is a top-k MoE FFN (PP x EP
    composition). Expert weights arrive REPLICATED over
    the ep axis (the layer-MoE convention, layer.py _MoEOp): when
    `ep_axis` is bound each device slices its expert group and dispatch
    rides two lax.all_to_all hops (parallel/moe.py moe_ffn_ep); gradient
    reduction must therefore cover (data, ep) — DistOpt(axis=(...)).
    Returns (h, aux, z_loss); capacity is computed from the MICROBATCH
    dispatch group (mb*S tokens), the per-microbatch semantics Megatron
    uses (documented: batch-global routing differs from the
    non-pipelined model outside the no-drop regime)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from ..ops.attention import flash_attention
    from ..parallel.moe import moe_ffn, moe_ffn_ep
    (g1, b1, Wq, Wk, Wv, Wo, g2, b2, Wg, W1e, b1e, W2e, b2e) = params
    B, S, E = h.shape
    x = _fn_layernorm(h, g1, b1)
    q = (x @ Wq).reshape(B, S, num_heads, -1).transpose(0, 2, 1, 3)
    kk = (x @ Wk).reshape(B, S, num_heads, -1).transpose(0, 2, 1, 3)
    v = (x @ Wv).reshape(B, S, num_heads, -1).transpose(0, 2, 1, 3)
    if rope is not None:
        from ..autograd import apply_rope
        rcos, rsin = rope
        q = apply_rope(q, rcos, rsin)
        kk = apply_rope(kk, rcos, rsin)
    o = flash_attention(q, kk, v, True)
    h = h + o.transpose(0, 2, 1, 3).reshape(B, S, -1) @ Wo
    x = _fn_layernorm(h, g2, b2)
    flat = x.reshape(-1, E)
    bound = False
    if ep_axis is not None:
        try:
            n_ep = lax.axis_size(ep_axis)
            bound = True
        except NameError:
            bound = False
    if bound:
        my = lax.axis_index(ep_axis)
        el = W1e.shape[0] // n_ep
        sl = lambda a: lax.dynamic_slice_in_dim(a, my * el, el, 0)
        y, aux, (z, _ovf) = moe_ffn_ep(
            flat, Wg, sl(W1e), sl(b1e), sl(W2e), sl(b2e), ep_axis,
            capacity_factor, k=k)
    else:
        y, aux, (z, _ovf) = moe_ffn(flat, Wg, W1e, b1e, W2e, b2e,
                                    capacity_factor, k=k)
    return h + y.reshape(B, S, E), aux, z


def _make_stage_fn_moe(num_heads, axis, total_layers, k, capacity_factor,
                       ep_axis=None, rope_cfg=None):
    """MoE variant of _make_stage_fn: stage_fn returns (x, aux) with
    aux = [load-balance, z-loss] summed over this stage's REAL layers
    (padding layers contribute zero)."""
    from jax import lax
    import jax.numpy as jnp

    def stage_fn(local_stacks, x):
        per = local_stacks[0].shape[0]
        s = lax.axis_index(axis)
        aux_acc = jnp.zeros((2,), jnp.float32)
        rope = _rope_tables_for(rope_cfg, x.shape[1])
        for li in range(per):
            on = (s * per + li) < total_layers
            y, aux, z = _fn_block_moe([st[li] for st in local_stacks], x,
                                      num_heads, k, capacity_factor,
                                      ep_axis, rope)
            x = jnp.where(on, y, x)
            gate = on.astype(jnp.float32)
            aux_acc = aux_acc + gate * jnp.stack(
                [aux.astype(jnp.float32), z.astype(jnp.float32)])
        return x, aux_acc

    return stage_fn


def _rope_tables_for(rope_cfg, S):
    """(cos, sin) (S, D) tables for positions [0, S) when rope_cfg =
    (theta, head_dim) is set (pipeline microbatches always carry the full
    sequence, so positions are simply arange(S)); None passthrough."""
    if rope_cfg is None:
        return None
    import jax.numpy as jnp
    from ..autograd import rope_tables
    theta, hd = rope_cfg
    return rope_tables(jnp.arange(S), hd, theta)


def _make_chunk_fn(num_heads, axis, total_layers, pc, tp_axis=None,
                   num_kv_heads=None, rope_cfg=None):
    """Chunk-aware stage application for the interleaved schedule: this
    device's local stack rows [c*pc, (c+1)*pc) are virtual chunk `c`
    (global pipeline stage c*n + d), so global layer (c*n+d)*pc + j
    decides the non-uniform padding mask (rows past total_layers are
    identity)."""
    from jax import lax
    import jax.numpy as jnp

    def chunk_fn(local_stacks, x, c):
        # local stacks are (V, pc, ...): chunk-major leading dim (the
        # full tensor is (V, n*pc, ...) with spec P(None, pp) — its
        # row-major order IS the canonical stage-major layer order,
        # since flat index c*(n*pc) + d*pc + j = ((c*n+d)*pc + j))
        n = lax.axis_size(axis)
        d = lax.axis_index(axis)
        rope = _rope_tables_for(rope_cfg, x.shape[1])
        for j in range(pc):
            params = [lax.dynamic_index_in_dim(st, c, 0,
                                               keepdims=False)[j]
                      for st in local_stacks]
            on = ((c * n + d) * pc + j) < total_layers
            y = _fn_block(params, x, num_heads, tp_axis, num_kv_heads,
                          rope)
            x = jnp.where(on, y, x)
        return x

    return chunk_fn


def _make_stage_fn(num_heads, axis, total_layers, tp_axis=None,
                   num_kv_heads=None, rope_cfg=None):
    """Per-stage block application with non-uniform stage support: local
    stacks carry padded_layers/n rows; rows whose GLOBAL index (stage*per +
    li) >= total_layers are padding (zero-init, never trained) and are
    where()-masked to the identity, so `num_layers % stages != 0` works —
    pad rows simply make late stages shorter. `tp_axis` additionally
    tensor-shards every block (PP x TP)."""
    from jax import lax
    import jax.numpy as jnp

    def stage_fn(local_stacks, x):
        per = local_stacks[0].shape[0]
        s = lax.axis_index(axis)
        rope = _rope_tables_for(rope_cfg, x.shape[1])
        for li in range(per):
            on = (s * per + li) < total_layers
            y = _fn_block([st[li] for st in local_stacks], x, num_heads,
                          tp_axis, num_kv_heads, rope)
            x = jnp.where(on, y, x)
        return x

    return stage_fn


class _PipelineBlocks(autograd.Operator):
    """All transformer blocks as one tape op: GPipe (or interleaved
    virtual-chunk GPipe) scan inside shard_map (parallel/pipeline.py),
    serial layer loop outside a mesh."""

    def __init__(self, num_heads, axis=None, n_micro=1, total_layers=None,
                 tp_axis=None, interleave=1, pc=None, moe=None,
                 num_kv_heads=None, rope_cfg=None):
        super().__init__("PipelineBlocks")
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.axis = axis
        self.n_micro = n_micro
        self.total_layers = total_layers
        self.tp_axis = tp_axis
        self.interleave = interleave
        self.pc = pc          # layers per virtual chunk (interleave > 1)
        self.moe = moe        # (k, capacity_factor, ep_axis) or None
        self.rope_cfg = rope_cfg  # (theta, head_dim) or None

    def forward(self, h, *stacks):
        import jax.numpy as jnp
        from ..parallel.pipeline import (gpipe, gpipe_interleaved,
                                         bcast_from_last)
        nh = self.num_heads
        L = self.total_layers or stacks[0].shape[0]
        if self.axis is not None and autograd.axis_bound(self.axis):
            B = h.shape[0]
            nm = self.n_micro
            assert B % nm == 0, f"batch {B} not divisible by n_micro {nm}"
            tp = self.tp_axis if (self.tp_axis is not None
                                  and autograd.axis_bound(self.tp_axis)) \
                else None
            x_micro = h.reshape(nm, B // nm, *h.shape[1:])
            if self.moe is not None:
                from ..parallel.tp import megatron_g
                k, cf, ep = self.moe
                ep = ep if (ep is not None and autograd.axis_bound(ep)) \
                    else None
                stage_fn = _make_stage_fn_moe(nh, self.axis, L, k, cf, ep,
                                              self.rope_cfg)
                outs, auxv = gpipe(stage_fn, list(stacks), x_micro,
                                   self.axis, with_aux=True)
                outs = bcast_from_last(self.axis, outs)
                # sum over stages (psum with identity backward: each
                # device's aux contribution is its own layers', counted
                # once), mean over microbatches
                auxv = megatron_g(auxv, self.axis) / nm
                return (outs.reshape(B, *h.shape[1:]),
                        auxv[0], auxv[1])
            if self.interleave > 1:
                chunk_fn = _make_chunk_fn(nh, self.axis, L, self.pc, tp,
                                          self.num_kv_heads, self.rope_cfg)
                outs = gpipe_interleaved(chunk_fn, list(stacks), x_micro,
                                         self.axis, self.interleave)
            else:
                stage_fn = _make_stage_fn(nh, self.axis, L, tp,
                                          self.num_kv_heads, self.rope_cfg)
                outs = gpipe(stage_fn, list(stacks), x_micro, self.axis)
            outs = bcast_from_last(self.axis, outs)
            return outs.reshape(B, *h.shape[1:])
        # serial fallback (eval / single device): the (V, n*pc, ...)
        # interleaved stacks share the flat canonical memory order, so a
        # reshape recovers layer-major rows; padding rows past L are
        # skipped entirely
        if self.interleave > 1:
            stacks = [s.reshape((-1,) + s.shape[2:]) for s in stacks]
        rope = _rope_tables_for(self.rope_cfg, h.shape[1])
        if self.moe is not None:
            k, cf, _ = self.moe
            aux_t = jnp.zeros((), jnp.float32)
            z_t = jnp.zeros((), jnp.float32)
            for g in range(L):
                h, aux, z = _fn_block_moe([s[g] for s in stacks], h, nh,
                                          k, cf, None, rope)
                aux_t = aux_t + aux.astype(jnp.float32)
                z_t = z_t + z.astype(jnp.float32)
            return h, aux_t, z_t
        for g in range(L):
            h = _fn_block([s[g] for s in stacks], h, nh,
                          num_kv_heads=self.num_kv_heads, rope=rope)
        return h


class _Pipeline1F1B(autograd.Operator):
    """Pipeline training step under the 1F1B schedule as ONE tape op with
    a HAND backward. 1F1B interleaves each microbatch's backward between
    later microbatches' forwards, which is only possible when the loss is
    computed inside the schedule (a tape op that returns activations and
    waits for its cotangent cannot start any backward early) — so this op
    consumes (h, targets, ln_f/head params, block stacks) and produces the
    loss directly; parallel/pipeline.one_f_one_b runs the fused scan and
    hands back every cotangent, which backward() replays to the tape.

    CONTRACT (backward): the second output (activations for the
    caller-facing logits) is an OBSERVATION edge only — backward()
    discards its cotangent `douts`. Any future change that puts a
    differentiable term on the returned logits (e.g. an auxiliary loss
    in train_one_batch) would silently train with ZERO gradient through
    the pipeline blocks. Keep every loss term inside last_fn."""

    def __init__(self, num_heads, axis, n_micro, total_layers,
                 tp_axis=None, tied_vocab=None, num_kv_heads=None,
                 rope_cfg=None):
        super().__init__("Pipeline1F1B")
        self.rope_cfg = rope_cfg
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.axis = axis
        self.n_micro = n_micro
        self.total_layers = total_layers
        self.tp_axis = tp_axis
        self.tied_vocab = tied_vocab  # true vocab size when headW is the
        #                               vocab-sharded embedding table
        self._cache = None

    def forward(self, h, tgt, gf, bf, headW, *stacks):
        import jax
        import jax.numpy as jnp
        from ..parallel.pipeline import one_f_one_b, last_stage_value
        from ..parallel.tp import megatron_f, vocab_parallel_ce
        assert autograd.axis_bound(self.axis), \
            "1f1b schedule needs an active pipeline mesh axis"
        B, S, E = h.shape
        nm = self.n_micro
        assert B % nm == 0, f"batch {B} not divisible by n_micro {nm}"
        tp = self.tp_axis if (self.tp_axis is not None
                              and autograd.axis_bound(self.tp_axis)) \
            else None
        x_micro = h.reshape(nm, B // nm, S, E)
        tgt_micro = tgt.reshape(nm, B // nm, S)
        stage_fn = _make_stage_fn(self.num_heads, self.axis,
                                  self.total_layers, tp,
                                  self.num_kv_heads, self.rope_cfg)
        tied = self.tied_vocab is not None

        def last_fn(lp, y, t):
            # fp32 loss island: final LN + tied/untied head + token-mean CE
            # (matches ln_f -> head(out_dtype=fp32) -> SoftMaxCrossEntropy)
            g, b, W = lp
            z = _fn_layernorm(y.astype(jnp.float32), g.astype(jnp.float32),
                              b.astype(jnp.float32))
            if tied and tp is not None:
                # W is this device's (V_pad/tp, E) table slice: sharded
                # logits + Megatron vocab-parallel CE (custom-vjp
                # collectives — this fn is differentiated by the engine)
                z = megatron_f(z, tp)
                logits = z @ W.astype(jnp.float32).T
                return vocab_parallel_ce(logits, t, tp,
                                         valid_vocab=self.tied_vocab)
            if tied:
                # tp axis not bound (e.g. a {data, pp} mesh): tied head
                # against the FULL table, padded columns masked out
                logits = z @ W.astype(jnp.float32).T
                V_pad = logits.shape[-1]
                if V_pad != self.tied_vocab:
                    logits = jnp.where(
                        jnp.arange(V_pad) < self.tied_vocab,
                        logits, -jnp.inf)
            else:
                logits = z @ W.astype(jnp.float32)
            lse = jax.nn.logsumexp(logits, axis=-1)
            tl = jnp.take_along_axis(logits, t[..., None], axis=-1)[..., 0]
            return jnp.mean(lse - tl)

        loss, outs, d_stage, d_last, dx = one_f_one_b(
            stage_fn, last_fn, list(stacks), (gf, bf, headW),
            x_micro, tgt_micro, self.axis)
        outs = last_stage_value(outs, self.axis)
        self._cache = (dx.reshape(B, S, E), d_last, d_stage)
        return loss, outs.reshape(B, S, E)

    def backward(self, dloss, douts):
        # douts is the cotangent of the caller-facing activations edge;
        # the loss path never flows through it (train_one_batch derives
        # the returned logits from outs OUTSIDE the loss graph), so only
        # dloss scales the cached schedule cotangents.
        dh, (dgf, dbf, dW), d_stage = self._cache
        s = dloss
        return (dh * s, None, dgf * s, dbf * s, dW * s,
                *[g * s for g in d_stage])


class PipelinedGPT(_VocabTPMixin, model.Model):
    """GPT with pipeline parallelism through the Model API: compile with
    `pipeline_axis="pp", n_micro=M` on a mesh carrying a 'pp' axis (plus a
    'data' axis, possibly size 1) and train normally. The block stack —
    where the FLOPs are — is sharded layer-wise over the pipeline.

    `tp_axis` composes PP x TP (the Megatron 3D layout minus sequence
    dims): every block's QKV/MLP weights additionally shard over the tp
    axis (two psums per block via custom-vjp f/g, correct under both
    schedules), and `vocab_tp=True` row-shards ONE padded (V_pad, E)
    table over tp serving as embedding and tied head, with the loss on
    sharded logits — without it the embedding/head replicate per device."""

    _STACK_ATTRS = ("g1", "b1", "Wq", "Wk", "Wv", "Wo",
                    "g2", "b2", "W1", "bb1", "W2", "bb2")
    _MOE_STACK_ATTRS = ("g1", "b1", "Wq", "Wk", "Wv", "Wo", "g2", "b2",
                        "moeWg", "moeW1", "moeb1", "moeW2", "moeb2")

    @property
    def _stack_attrs(self):
        return self._MOE_STACK_ATTRS if self.moe_experts \
            else self._STACK_ATTRS

    def __init__(self, vocab_size, max_seq=1024, dim=256, num_heads=8,
                 num_layers=4, mlp_ratio=4, tp_axis=None, vocab_tp=False,
                 vocab_pad_multiple=128, vocab_tp_return_logits=True,
                 interleave=1, moe_experts=0, moe_k=2, ep_axis=None,
                 moe_capacity_factor=1.25, moe_aux_weight=0.01,
                 moe_z_weight=1e-3, num_kv_heads=None,
                 pos_encoding="learned", rope_theta=10000.0, name=None):
        super().__init__(name)
        assert pos_encoding in ("learned", "rope"), pos_encoding
        # "rope": rotary q/k per block (no learned position table; the
        # model length-generalizes and the decode rotates at the cache
        # position); "learned": the GPT-2-style trained table.
        self.pos_encoding = pos_encoding
        self.rope_theta = float(rope_theta)
        self.vocab_size = vocab_size
        self.max_seq = max_seq
        self.dim = dim
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads or num_heads
        assert num_heads % self.num_kv_heads == 0, \
            f"num_heads {num_heads} not divisible by " \
            f"num_kv_heads {self.num_kv_heads}"
        self.num_layers = num_layers
        self.mlp_ratio = mlp_ratio
        self.tp_axis = tp_axis
        # interleave=V > 1: each device holds V virtual chunks assigned
        # round-robin over the pipeline (Megatron interleaved virtual
        # stages) — cuts the bubble below GPipe's at the same memory
        # profile (parallel/pipeline.py gpipe_interleaved /
        # schedule_table). gpipe schedule only.
        assert interleave >= 1
        self.interleave = int(interleave)
        # moe_experts>0: every block's MLP becomes a top-moe_k MoE FFN
        # inside the pipeline stages (PP x EP: expert dispatch via
        # all_to_all over ep_axis WITHIN the stage scan; DistOpt must
        # reduce over (data, ep)). gpipe schedule, no tp/interleave.
        self.moe_experts = int(moe_experts)
        self.moe_k = moe_k
        self.ep_axis = ep_axis
        self.moe_capacity_factor = moe_capacity_factor
        self.moe_aux_weight = moe_aux_weight
        self.moe_z_weight = moe_z_weight
        if self.moe_experts:
            if tp_axis is not None:
                raise ValueError(
                    "PipelinedGPT moe_experts does not compose with "
                    "tp_axis yet (expert dispatch and Megatron f/g would "
                    "need a fused layout); use pp x dp x ep")
            if self.interleave > 1:
                raise ValueError(
                    "PipelinedGPT moe_experts composes with the plain "
                    "gpipe schedule only (no interleave)")
            if num_kv_heads is not None and num_kv_heads != num_heads:
                raise ValueError(
                    "PipelinedGPT moe_experts does not compose with "
                    "num_kv_heads yet (the MoE stage fn's attention is "
                    "MHA); use GQA with the dense-MLP pipelined model")
        if vocab_tp and tp_axis is None:
            raise ValueError(
                "vocab_tp=True needs tp_axis (see GPT.__init__)")
        self.vocab_tp = bool(vocab_tp)
        self.vocab_tp_return_logits = vocab_tp_return_logits
        if self.vocab_tp:
            m = vocab_pad_multiple
            self.padded_vocab = ((vocab_size + m - 1) // m) * m
            self.tok_embed = layer.Embedding(self.padded_vocab, dim,
                                             tp_axis=tp_axis)
            self.head = None        # tied to tok_embed.W
        else:
            self.padded_vocab = vocab_size
            self.tok_embed = layer.Embedding(vocab_size, dim)
            # fp32-accumulated logits: under amp the CE loss would
            # otherwise upcast the full (B,S,V) tensor
            self.head = layer.Linear(vocab_size, bias=False,
                                     out_dtype="float32")
        self.ln_f = layer.LayerNorm()
        self.sce = layer.SoftMaxCrossEntropy()
        self._stacks_init = False

    def compile(self, inputs, **kwargs):
        # validate BEFORE tracing: raising inside the traced step would
        # leak tracers into the device RNG state
        if kwargs.get("pipeline_schedule") == "1f1b" and \
                self.interleave > 1:
            raise ValueError(
                "interleave>1 composes with the gpipe schedule only: "
                "1f1b's fused scan assumes one contiguous stage per "
                "device (see parallel/pipeline.py schedule_table for "
                "the bubble/memory/compute trade-offs)")
        if kwargs.get("pipeline_schedule") == "1f1b" and self.moe_experts:
            raise ValueError(
                "PipelinedGPT moe_experts composes with the gpipe "
                "schedule only (1f1b's in-schedule loss does not carry "
                "the router aux-loss channel yet)")
        return super().compile(inputs, **kwargs)

    def _mesh_axis_size(self, axis):
        """Mesh degree of `axis`, readable at param-init time (compile
        runs after set_optimizer, so the mesh is already attached)."""
        if axis is None:
            return 1
        try:
            mesh = self._optimizer.communicator.mesh
            return int(mesh.shape[axis])
        except Exception:
            return 1

    def _n_stages(self):
        return self._mesh_axis_size(self.pipeline_axis)

    def _rope_cfg(self):
        return (self.rope_theta, self.dim // self.num_heads) \
            if self.pos_encoding == "rope" else None

    def _blocks_op(self):
        moe = (self.moe_k, float(self.moe_capacity_factor), self.ep_axis) \
            if self.moe_experts else None
        return _PipelineBlocks(
            self.num_heads, self.pipeline_axis, self.n_micro,
            self.num_layers, self.tp_axis, interleave=self.interleave,
            pc=getattr(self, "_chunk_layers", None), moe=moe,
            num_kv_heads=self.num_kv_heads, rope_cfg=self._rope_cfg())

    def _init_stacks(self, dev):
        import numpy as np
        L, E, H = self.num_layers, self.dim, self.dim * self.mlp_ratio
        # non-uniform stages: pad the stack to stages*ceil(L/stages) rows
        # so shard_map can slice it evenly; rows [L, padded) are zero-init
        # padding that _make_stage_fn masks to the identity (late stages
        # simply run fewer real layers). With interleave=V>1 the unit is
        # the virtual chunk: stacks are shaped (V, n*pc, ...) with spec
        # P(None, pp), so device d's local (V, pc, ...) slice holds its V
        # round-robin chunks — and because global stage = c*n + d, the
        # tensor's row-major order IS the canonical layer order (the
        # (V, n*pc) layout is a pure reshape of the flat (Lp,) stack; no
        # permutation, and shapes disambiguate canonical (L,...) inputs
        # from same-config round-trips in set_params).
        n_pp = self._n_stages()
        V = self.interleave
        pc = -(-L // (n_pp * V))
        Lp = n_pp * V * pc
        self.padded_layers = Lp
        self._chunk_layers = pc
        self._stack_lead = (V, n_pp * pc) if V > 1 else (Lp,)
        tp_n = self._mesh_axis_size(self.tp_axis)
        if tp_n > 1:
            assert self.pipeline_axis is not None, (
                "PipelinedGPT tp_axis requires pipeline_axis (the stacked "
                "blocks only run tensor-parallel inside the pipeline mesh)")
            assert E % tp_n == 0 and H % tp_n == 0 \
                and self.num_heads % tp_n == 0, \
                f"dim {E}/hidden {H}/heads {self.num_heads} must divide " \
                f"tp={tp_n}"
        rng = np.random.RandomState(0)
        from jax.sharding import PartitionSpec as P
        pp, tp = self.pipeline_axis, self.tp_axis
        # Megatron layout over the stacked (Lp, ...) params: QKV/W1
        # column-shard their OUTPUT dim over tp, Wo/W2 row-shard their
        # INPUT dim; everything else replicates across tp
        tp_specs = {"Wq": P(pp, None, tp), "Wk": P(pp, None, tp),
                    "Wv": P(pp, None, tp), "W1": P(pp, None, tp),
                    "Wo": P(pp, tp, None), "W2": P(pp, tp, None),
                    "bb1": P(pp, tp)}

        def mk(attr, shape, scale=None):
            lead = self._stack_lead
            t = Tensor(lead + shape, device=dev, dtype=float32)
            vals = np.zeros((Lp,) + shape, np.float32)
            if scale is None:   # layernorm gain/bias
                vals[:L] = 1.0 if attr.startswith("g") else 0.0
            else:
                vals[:L] = (rng.standard_normal((L,) + shape)
                            * scale).astype(np.float32)
            t.copy_from_numpy(vals.reshape(lead + shape))
            if pp is not None:
                spec = tp_specs.get(attr, P(pp)) if tp_n > 1 else P(pp)
                if len(lead) == 2:   # (V, n*pc, ...): pp shards dim 1
                    spec = P(None, *spec)
                t.spec = spec
            self._register_param(attr, t)

        kv_e = E // self.num_heads * self.num_kv_heads
        if tp_n > 1:
            assert self.num_kv_heads % tp_n == 0, \
                f"kv heads {self.num_kv_heads} must divide tp={tp_n}"
        mk("g1", (E,)), mk("b1", (E,))
        for a in ("Wq", "Wk", "Wv", "Wo"):
            mk(a, (E, kv_e if a in ("Wk", "Wv") else E), scale=E ** -0.5)
        mk("g2", (E,)), mk("b2", (E,))
        if self.moe_experts:
            # expert stacks stay REPLICATED over ep (layer._MoEOp
            # convention: each device slices its expert group in-step);
            # only the pp dim shards. Grad reduction must span (data, ep).
            X = self.moe_experts
            mk("moeWg", (E, X), scale=E ** -0.5)
            mk("moeW1", (X, E, H), scale=E ** -0.5)
            mk("moeb1", (X, H), scale=0.0)
            mk("moeW2", (X, H, E), scale=H ** -0.5)
            mk("moeb2", (X, E), scale=0.0)
        else:
            mk("W1", (E, H), scale=E ** -0.5)
            mk("bb1", (H,), scale=0.0)
            mk("W2", (H, E), scale=H ** -0.5)
            mk("bb2", (E,), scale=0.0)
        self._stacks_init = True

    def _embed(self, ids):
        h = self.tok_embed(ids)
        if not self._stacks_init:
            if not hasattr(self, "pipeline_axis"):
                self.pipeline_axis, self.n_micro = None, 1
            self._init_stacks(h.device)
            if self.pos_encoding != "rope":
                p = Tensor((self.max_seq, self.dim), device=h.device,
                           dtype=float32)
                p.gaussian(0.0, 0.02)
                self._register_param("pos_embed", p)
        if self.pos_encoding != "rope":
            # rope: positions live in the per-block q/k rotation (stage
            # fns apply _rope_tables_for); no learned table exists, so
            # rope-trained stacks transfer to a rope GPT for serving
            S = ids.shape[1]
            pos = _PosSlice(S)(self.pos_embed)
            h = autograd.add(h, autograd.expand(pos, h.shape))
        if self.pipeline_axis is not None and \
                autograd.axis_bound(self.pipeline_axis):
            # Megatron-f on the pipeline input: dL/dh is nonzero only on
            # stage 0 (the only stage that consumes h); the psum backward
            # gives every device the full embedding gradient so replicated
            # embed/pos params stay in sync
            h = autograd.tp_copy(h, self.pipeline_axis)
        return h

    def forward(self, ids):
        h = self._embed(ids)
        op = self._blocks_op()
        out = op(h, *[getattr(self, a) for a in self._stack_attrs])
        h = out[0] if self.moe_experts else out
        return self._caller_logits(h)

    def set_params(self, params: dict):
        """Accepts stacks from a model built with a different pipeline
        degree: a CANONICAL-layer-order (num_layers, ...) stack loads
        into this model's stack by zero-padding to padded_layers and
        reshaping to the stack's lead shape ((Lp, ...) normally,
        (V, n*pc, ...) under interleave>1 — same memory order, so this
        is a pure reshape). Same-shape stacks pass through unchanged
        (the shapes disambiguate, so get_params -> set_params round
        trips between identical configs are exact)."""
        import numpy as np
        own = self.get_params()
        fixed = {}
        for n, v in params.items():
            arr = v.numpy() if isinstance(v, Tensor) else np.asarray(v)
            own_shape = tuple(own[n].shape) if n in own else None
            if (own_shape and arr.shape != own_shape
                    and n.split(".")[-1] in self._stack_attrs):
                lead = self._stack_lead
                body = own_shape[len(lead):]
                if arr.shape[1:] == body:       # canonical (L_in, ...)
                    Lp = self.padded_layers
                    glob = np.zeros((Lp,) + body, arr.dtype)
                    m = min(Lp, arr.shape[0])
                    glob[:m] = arr[:m]
                    arr = glob.reshape(lead + body)
            fixed[n] = arr
        super().set_params(fixed)

    def canonical_stacks(self) -> dict:
        """The block stacks as numpy arrays in CANONICAL layer order
        (row 0 = layer 0, padded to padded_layers) regardless of
        interleave — the (V, n*pc, ...) interleaved layout shares the
        flat memory order, so this is a reshape, not a gather."""
        return {a: getattr(self, a).numpy()
                .reshape((self.padded_layers,)
                         + tuple(getattr(self, a).shape)[
                             len(self._stack_lead):])
                for a in self._stack_attrs}

    def _caller_logits(self, h_out):
        """Caller-facing logits from post-block activations, OUTSIDE the
        loss graph."""
        h_out = self.ln_f(h_out)
        if not self.vocab_tp:
            return self.head(h_out)
        local = self._tied_logits(h_out)
        if self._vp_active():
            local = autograd.gather_last(local, self.tp_axis)
        return self._slice_valid(local)

    def train_one_batch(self, ids, targets):
        sched = getattr(self, "pipeline_schedule", "gpipe")
        # (interleave>1 + 1f1b is rejected at compile() time, before any
        # tracing could leak)
        if sched == "1f1b" and self.pipeline_axis is not None and \
                autograd.axis_bound(self.pipeline_axis):
            h = self._embed(ids)
            headW = self.tok_embed.W if self.vocab_tp else self.head.W
            op = _Pipeline1F1B(
                self.num_heads, self.pipeline_axis, self.n_micro,
                self.num_layers, self.tp_axis,
                tied_vocab=self.vocab_size if self.vocab_tp else None,
                num_kv_heads=self.num_kv_heads, rope_cfg=self._rope_cfg())
            loss, outs = op(h, targets, self.ln_f.gamma, self.ln_f.beta,
                            headW,
                            *[getattr(self, a) for a in self._stack_attrs])
            # the 1F1B backward already produced every gradient
            # in-schedule; the logits edge carries no cotangent
            logits = self._caller_logits(outs)
            self.optimizer(loss)
            return logits, loss
        h = self._embed(ids)
        op = self._blocks_op()
        out = op(h, *[getattr(self, a) for a in self._stack_attrs])
        if self.moe_experts:
            h, aux, z = out
        else:
            h = out
        if self.vocab_tp:
            local = self._tied_logits(self.ln_f(h))
            loss, logits = self._vp_loss_and_logits(local, targets)
        else:
            logits = self._caller_logits(h)
            flat = autograd.reshape(logits, (-1, self.vocab_size))
            tflat = autograd.reshape(targets, (-1,))
            loss = self.sce(flat, tflat)
        if self.moe_experts:
            loss = self._fold_moe_losses(loss, aux, z, ids.device)
        self.optimizer(loss)
        return logits, loss

    def _fold_moe_losses(self, loss, aux, z, device):
        import numpy as np
        if not hasattr(self, "_moe_w"):
            from ..tensor import from_numpy
            self._moe_w = (
                from_numpy(np.float32(self.moe_aux_weight), device=device),
                from_numpy(np.float32(self.moe_z_weight), device=device))
        aw, zw = self._moe_w
        loss = autograd.add(loss, autograd.mul(aux, aw))
        return autograd.add(loss, autograd.mul(z, zw))


def load_gpt2_weights(m: "GPT", state: dict):
    """Load GPT-2-convention weights into a native GPT for fast serving.

    `state` maps torch-style GPT-2 names to numpy arrays (e.g.
    `{k: v.numpy() for k, v in torch_model.state_dict().items()}`, or
    initializers pulled from an ONNX file): `wte.weight`, `wpe.weight`,
    `blocks.{i}.{ln1,ln2}.{weight,bias}`, `blocks.{i}.attn.{weight,bias}`
    (fused qkv, (3E,E)/(3E,)), `blocks.{i}.proj.{weight,bias}`,
    `blocks.{i}.{ff1,ff2}.{weight,bias}`, `ln_f.{weight,bias}`; the LM
    head is tied to wte. Torch Linear stores (out,in) so weights are
    transposed into this framework's (in,out) layout. The model must be
    built with `attn_bias=True` and compiled (weights initialized) first.

    This is the migration path from the reference's ONNX-imported GPT-2
    (examples/onnx/gpt2) onto the KV-cached `generate()` serving stack.
    """
    import numpy as np

    if not m._pos_init:
        raise RuntimeError("compile() the model before loading weights")
    E = m.dim

    def put(t, arr):
        arr = np.asarray(arr, np.float32)
        assert tuple(t.shape) == arr.shape, \
            f"shape mismatch: param {tuple(t.shape)} vs weight {arr.shape}"
        t.copy_from_numpy(arr)

    wte = np.asarray(state["wte.weight"], np.float32)
    if m.padded_vocab != m.vocab_size:
        # vocab_tp pads the table (Megatron scheme); checkpoint rows fill
        # the valid prefix, padding rows zero (masked out of loss/decode)
        pad = np.zeros((m.padded_vocab - wte.shape[0], wte.shape[1]),
                       np.float32)
        wte_full = np.concatenate([wte, pad], axis=0)
        put(m.tok_embed.W, wte_full)
    else:
        put(m.tok_embed.W, wte)
    n_wpe = state["wpe.weight"].shape[0]
    if m.max_seq > n_wpe:
        raise ValueError(
            f"model max_seq={m.max_seq} exceeds the checkpoint's "
            f"{n_wpe} position embeddings; positions past {n_wpe} would "
            f"stay randomly initialized — build the GPT with "
            f"max_seq<={n_wpe}")
    pos = m.pos_embed.numpy().copy()
    pos[:] = np.asarray(state["wpe.weight"], np.float32)[:m.max_seq]
    m.pos_embed.copy_from_numpy(pos)
    if m.head is not None:   # vocab_tp ties the head to wte structurally
        put(m.head.W, np.asarray(state["wte.weight"]).T)
    put(m.ln_f.gamma, state["ln_f.weight"])
    put(m.ln_f.beta, state["ln_f.bias"])
    for i, blk in enumerate(m.blocks):
        assert blk.attn.use_bias, \
            "build the GPT with attn_bias=True for GPT-2 weights"
        pre = f"blocks.{i}."
        put(blk.ln1.gamma, state[pre + "ln1.weight"])
        put(blk.ln1.beta, state[pre + "ln1.bias"])
        put(blk.ln2.gamma, state[pre + "ln2.weight"])
        put(blk.ln2.beta, state[pre + "ln2.bias"])
        qkv_w = np.asarray(state[pre + "attn.weight"], np.float32)
        qkv_b = np.asarray(state[pre + "attn.bias"], np.float32)
        assert qkv_w.shape == (3 * E, E), qkv_w.shape
        for j, (W, b) in enumerate(((blk.attn.Wq, blk.attn.bq),
                                    (blk.attn.Wk, blk.attn.bk),
                                    (blk.attn.Wv, blk.attn.bv))):
            put(W, qkv_w[j * E:(j + 1) * E].T)
            put(b, qkv_b[j * E:(j + 1) * E])
        put(blk.attn.Wo, np.asarray(state[pre + "proj.weight"]).T)
        put(blk.attn.bo, state[pre + "proj.bias"])
        put(blk.fc1.W, np.asarray(state[pre + "ff1.weight"]).T)
        put(blk.fc1.b, state[pre + "ff1.bias"])
        put(blk.fc2.W, np.asarray(state[pre + "ff2.weight"]).T)
        put(blk.fc2.b, state[pre + "ff2.bias"])
    return m


def create_model(vocab_size=256, **kwargs):
    return GPT(vocab_size, **kwargs)


def create_pipelined(vocab_size=256, **kwargs):
    return PipelinedGPT(vocab_size, **kwargs)


__all__ = ["GPT", "PipelinedGPT", "create_model", "create_pipelined",
           "load_gpt2_weights"]
