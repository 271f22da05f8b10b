"""Serving engine: KV-cached autoregressive decoding for GPT-family models.

Framework infrastructure, not a "model": the decode core (prefill +
single-token cached step), weight-only int8 quantization, int8 KV caches,
greedy/sampled and beam decoding loops, and the decode-param memo all live
here; `models/transformer.py` keeps only the model definitions and thin
`generate()`/`generate_beam()` wrappers.

The reference's LLM-serving story is ONNX-imported GPT-2 replaying the
full graph per token (/root/reference/examples/onnx/gpt2/gpt2.py re-runs
the whole prefix each step). TPU-native redesign: one jitted function =
prefill + lax.scan over decode steps with a preallocated (T-length) KV
cache updated via dynamic_update_slice — O(T) per token instead of
O(T^2), no retrace per step, static shapes throughout.

Serving-roofline design notes:
- HEAD-PACKED KV caches, (B, H/P, T, P*D) with P = 128//D: TPU bf16
  tiles are (16 sublanes, 128 lanes), so a (B,H,T,D) cache with D=64
  pads every row to 128 lanes — the cache physically occupies and
  STREAMS 2x its logical bytes. Packing P heads into the minor dim
  fills the lanes while keeping the per-token cache update a contiguous
  row write; scores stay exactly per-head via BLOCK-DIAGONAL queries.
- Wq/Wk/Wv fuse into one (E, 3E) matmul at decode-param prep.
- `dtype="int8"` weight-only quantization (per-output-channel symmetric)
  halves the dominant weight traffic; `kv_dtype="int8"` additionally
  quantizes the KV cache with per-(head, position) scales.
"""

from __future__ import annotations

import weakref

import jax

#: every KV-cache storage mode the serving stack supports (the
#: `kv_dtype=` label on singa_serve_* metrics is proven against this
#: tuple by tools/check_metrics_names.py rule 5). "fp" is the
#: activation-dtype cache (the kv_dtype=None API spelling), int8 the
#: per-(head, position)-scaled byte cache, int4 the packed-nibble cache
#: (two values per byte, same scale layout, bytes halved again).
KV_DTYPES = ("fp", "int8", "int4")

#: speculative-decoding per-token verdicts (the `verdict=` label on
#: singa_spec_tokens_total is proven against this tuple by rule 5):
#: "drafted" counts every draft proposal, "accepted" the proposals the
#: target verified, "bonus" the target's own token each verify round
#: emits for free, "wasted" = drafted - accepted (rejected proposals —
#: the compute spent buying nothing).
SPEC_VERDICTS = ("drafted", "accepted", "bonus", "wasted")

#: quantized-KV modes (subset of KV_DTYPES the quantizer handles)
_KVQ = ("int8", "int4")


def kv_label(kv_dtype) -> str:
    """Map the API spelling (None/'int8'/'int4') onto KV_DTYPES."""
    label = kv_dtype or "fp"
    assert label in KV_DTYPES, kv_dtype
    return label


def _quant8(W):
    """Per-output-channel symmetric int8 quantization of a (in, out)
    weight: q8 int8 + fp32 scale row. The scale commutes with the
    contraction (y_j = (sum_i x_i q_ij) * s_j), so the matmul runs on the
    int8 bytes and only the tiny (out,) output is rescaled — halving
    weight HBM traffic vs bf16 on the bandwidth-bound decode path."""
    import jax.numpy as jnp
    s = jnp.max(jnp.abs(W), axis=0, keepdims=True) / 127.0
    s = jnp.maximum(s, 1e-8)
    q = jnp.clip(jnp.round(W / s), -127, 127).astype(jnp.int8)
    return {"q8": q, "sc": s.astype(jnp.float32)}


def _mm(x, W):
    """x @ W where W is a plain array or a _quant8 dict."""
    if isinstance(W, dict):
        y = x @ W["q8"].astype(x.dtype)
        return y * W["sc"].astype(x.dtype)
    return x @ W


_Q8_KEYS = ("Wqkv", "Wo", "W1", "W2", "head")


def _blk(li, name):
    """Device scope of sublayer `name` of block `li`, as the training model
    names it: the decode programs read by the same names as the step."""
    return f"TransformerBlock_{li}/{name}"


def _cast_params(p, dtype):
    """Decode-param tree in the serving dtype: None = as-stored (fp32),
    "bfloat16" = bf16 weights/activations, "int8" = weight-only int8
    (the big streamed matrices quantize; biases, LN params, embedding —
    its gather reads only B rows — and MoE weights stay bf16; W8A16)."""
    import jax
    import jax.numpy as jnp
    if dtype is None:
        return p
    if dtype != "int8":
        cd = jnp.dtype(dtype)
        return jax.tree.map(
            lambda a: a.astype(cd)
            if jnp.issubdtype(a.dtype, jnp.floating) else a, p)
    bf = jnp.bfloat16

    def cast_leaf(a):
        return a.astype(bf) \
            if jnp.issubdtype(a.dtype, jnp.floating) else a

    out = {k: cast_leaf(v) for k, v in p.items() if k != "blocks"}
    out["head"] = _quant8(p["head"])
    blocks = []
    for bp in p["blocks"]:
        nb = {k: cast_leaf(v) for k, v in bp.items()}
        for k in _Q8_KEYS:
            if k in bp:
                nb[k] = _quant8(bp[k])
        blocks.append(nb)
    out["blocks"] = blocks
    return out


class _DecodeCore:
    """Shared functional decode math for greedy/sampled and beam decoding.

    One implementation of the fp32-island LayerNorm, the causal prefill
    (which also fills the KV caches), and the single-token cached block
    step — so every decode flavor shares numerics by construction (the
    beam-1 == greedy test leans on this). See the module docstring for
    the roofline design notes.
    """

    def __init__(self, H, E, S0, T, scale, moe_ks=None, kv_heads=None,
                 rope=False, rope_theta=10000.0, kv_dtype=None):
        self.H, self.E, self.S0, self.T, self.scale = H, E, S0, T, scale
        self.rope = bool(rope)
        self.rope_theta = float(rope_theta)
        # quantized KV (kv_dtype "int8" or "int4"): per-(head, position)
        # symmetric scales. The algebra stays exact-in-structure:
        # K-scales multiply scores per source position after the packed
        # matmul, and V-scales fold into the attention weights for the
        # DIAGONAL (own-head) block — the only block the packed
        # extraction keeps, so the off-block garbage scaling is
        # discarded with the cross-terms. int4 packs two nibbles per
        # byte along the lane dim (ops.attention.nibble_pack's
        # split-half layout) with the same scale shapes; only the
        # quantization basis (max|kv|/7) and the byte stream change.
        assert kv_dtype in (None,) + _KVQ, kv_dtype
        self.kv_dtype = kv_dtype
        self.kv8 = kv_dtype == "int8"
        self.kv4 = kv_dtype == "int4"
        self.kvq = kv_dtype in _KVQ
        # static per-layer MoE routing degree (None = dense MLP); must be
        # static (int() under jit) so it lives here, not in the param tree
        self.moe_ks = moe_ks or []
        # GQA: Hkv kv heads each serve G = H/Hkv query heads; the caches
        # hold Hkv heads (the serving win — KV traffic shrinks G x) and
        # the packed block-diagonal contraction places G query rows per
        # kv-head block instead of 1
        self.Hkv = kv_heads or H
        self.G = H // self.Hkv
        D = E // H
        P = max(1, 128 // D)
        self.P = P if (P > 1 and self.Hkv % P == 0) else 1

    def cast(self, p, dtype):
        return _cast_params(p, dtype)

    def ln(self, x, g, b, scope, eps=1e-5):
        # fp32 island like autograd.LayerNorm: variance in bf16 is
        # catastrophically lossy. `scope`: the device scope, the layer's
        # name in the training model (_blk(li, "ln1"), "ln_f")
        import jax.numpy as jnp
        from jax import lax
        with jax.named_scope(scope):
            x32 = x.astype(jnp.float32)
            m = jnp.mean(x32, axis=-1, keepdims=True)
            v = jnp.var(x32, axis=-1, keepdims=True)
            y = (x32 - m) * lax.rsqrt(v + eps) * g.astype(jnp.float32) \
                + b.astype(jnp.float32)
            return y.astype(x.dtype)

    def embed(self, p, tok, pos):
        """Token rows plus, unless rotary, the rows of positions `pos`."""
        with jax.named_scope("tok_embed"):
            return p["emb"][tok] + (0 if self.rope else p["pos"][pos])

    def head(self, p, h):
        """Final norm and output head: (..., E) -> logits (..., V)."""
        x = self.ln(h, p["gf"], p["bf"], "ln_f")
        with jax.named_scope("head"):
            return _mm(x, p["head"])

    def mlp(self, bp, x, li):
        """Block MLP on (..., E): dense two-layer, or the MoE FFN when
        layer `li` routes to experts (decode uses the single-device
        dense-dispatch path; generous capacity so no token drops)."""
        import jax
        import jax.numpy as jnp
        kcf = self.moe_ks[li] if li < len(self.moe_ks) else None
        if kcf is not None:
            # NOTE: capacity-limited routing is a BATCH-GLOBAL effect (a
            # token's drop depends on the other tokens in the dispatch),
            # so cached decode == full forward only in the no-drop regime
            # (generous capacity_factor); the layer's own factor is used
            # here for honest replication.
            k, cf = kcf
            from .parallel.moe import moe_ffn
            lead = x.shape[:-1]
            flat = x.reshape(-1, x.shape[-1])
            with jax.named_scope(_blk(li, "moe")):
                y, _, _ = moe_ffn(flat, bp["moeWg"], bp["moeW1"],
                                  bp["moeb1"], bp["moeW2"], bp["moeb2"],
                                  capacity_factor=cf, k=k)
            return y.reshape(*lead, x.shape[-1]).astype(x.dtype)
        with jax.named_scope(_blk(li, "fc1")):
            h = jax.nn.gelu(_mm(x, bp["W1"]) + bp["bb1"])
        with jax.named_scope(_blk(li, "fc2")):
            return _mm(h, bp["W2"]) + bp["bb2"]

    def qkv(self, bp, x, n, S=None):
        """Fused QKV projection: one (E, E + 2*Hkv*D) matmul, split into
        q (n,[S,]H,D) and k/v (n,[S,]Hkv,D)."""
        import jax.numpy as jnp
        H, D, E, Hkv = self.H, self.E // self.H, self.E, self.Hkv
        KE = Hkv * D
        fused = _mm(x, bp["Wqkv"]) + bp["bqkv"]
        bounds = ((0, E, H), (E, E + KE, Hkv), (E + KE, E + 2 * KE, Hkv))
        if S is None:
            q, k, v = (fused[..., a:b].reshape(n, h, D)
                       for a, b, h in bounds)
        else:
            q, k, v = (fused[..., a:b].reshape(n, S, h, D).swapaxes(1, 2)
                       for a, b, h in bounds)
        return q, k, v

    def _pack(self, kv, n, S):
        """(n,Hkv,S,D) per-kv-head K/V -> head-packed
        (n, Hkv/P, S, P*D)."""
        D, P, Hkv = self.E // self.H, self.P, self.Hkv
        return kv.reshape(n, Hkv // P, P, S, D).swapaxes(2, 3) \
            .reshape(n, Hkv // P, S, P * D)

    def _quant_kv(self, kv, n, S):
        """(n,Hkv,S,D) -> (packed quantized cache rows, scales
        (n,Hp,S,P) fp32): per-(head, position) symmetric. int8 mode
        yields int8 (n,Hp,S,P*D); int4 yields packed-nibble uint8
        (n,Hp,S,P*D/2) (two values per byte, split-half lane layout —
        see ops.attention.nibble_pack) on a max|kv|/7 basis."""
        import jax.numpy as jnp
        P, Hkv = self.P, self.Hkv
        qmax = 7.0 if self.kv4 else 127.0
        s = jnp.maximum(jnp.max(jnp.abs(kv.astype(jnp.float32)), axis=-1),
                        1e-8) / qmax                        # (n,Hkv,S)
        q = jnp.clip(jnp.round(kv.astype(jnp.float32) / s[..., None]),
                     -qmax, qmax).astype(jnp.int8)
        sp = s.reshape(n, Hkv // P, P, S).swapaxes(2, 3)    # (n,Hp,S,P)
        packed = self._pack(q, n, S)
        if self.kv4:
            from .ops.attention import nibble_pack
            packed = nibble_pack(packed)
        return packed, sp

    def _dequant_cache(self, packed, dtype):
        """Quantized cache rows -> matmul operand in `dtype` (int8 cast,
        int4 nibble unpack) for the XLA einsum paths; the Pallas
        kernels do the same transform in-kernel instead."""
        if self.kv4:
            from .ops.attention import nibble_unpack
            return nibble_unpack(packed, dtype)
        return packed.astype(dtype)

    def _scale_rows(self, sp, G):
        """(n,Hp,T,P) per-position scales -> (n,Hp,P*G,T) row factors
        (packed query row q = c*G + g reads lane block c)."""
        import jax.numpy as jnp
        return jnp.repeat(sp.swapaxes(2, 3), G, axis=2)

    def prefill_parts(self, p, prompt, n):
        """Causal pass over the (n, S) prompt (S from the prompt shape,
        so the serving engine's padded-bucket prompts reuse it): returns
        the final hidden states h (n, S, E) and the per-block RAW
        (rotated, unpacked) k/v (n, Hkv, S, D) — the shared front half
        of both the dense `prefill` (which pads them into T-length
        caches) and the engine's paged prefill (which scatters them into
        pool pages).

        Attention runs through the Pallas flash kernel (O(S) score
        memory — the same kernel the training path uses, GQA via repeat),
        so a 16k+-token prompt prefills on one chip instead of
        materializing an (S, S) score matrix per head; short prompts
        that don't tile the kernel fall back to the O(S^2) reference
        path inside flash_attention itself."""
        import jax.numpy as jnp
        from .ops.attention import flash_attention
        D = self.E // self.H
        S = prompt.shape[1]
        ln = self.ln
        h = self.embed(p, prompt, slice(S))

        kvs = []
        G = self.G
        if self.rope:
            from .autograd import rope_tables, apply_rope
            rcos, rsin = rope_tables(jnp.arange(S), D, self.rope_theta)
        for li, bp in enumerate(p["blocks"]):
            x = ln(h, bp["g1"], bp["b1"], _blk(li, "ln1"))
            with jax.named_scope(_blk(li, "attn")):
                q, k, v = self.qkv(bp, x, n, S)     # q (n,H,·); kv (n,Hkv,·)
                if self.rope:
                    # rotate q/k; the cache stores ROTATED keys (standard),
                    # so decode steps only rotate their own position
                    q = apply_rope(q, rcos, rsin)
                    k = apply_rope(k, rcos, rsin)
                kr = jnp.repeat(k, G, axis=1) if G > 1 else k
                vr = jnp.repeat(v, G, axis=1) if G > 1 else v
                o = flash_attention(q, kr, vr, True, self.scale)
                h = h + _mm(o.swapaxes(1, 2).reshape(n, S, self.E),
                            bp["Wo"]) + bp["bo"]
            x = ln(h, bp["g2"], bp["b2"], _blk(li, "ln2"))
            h = h + self.mlp(bp, x, li)
            kvs.append((k, v))
        return h, kvs

    def prefill(self, p, prompt, n):
        """Causal pass over the (n, S0) prompt; returns the last-position
        logits (n, V) and per-block head-packed KV caches of time-length
        T, shape (n, H/P, T, P*D) (see class docstring)."""
        import jax.numpy as jnp
        D, S0, T, P = self.E // self.H, self.S0, self.T, self.P
        Hkv = self.Hkv
        h, kvs = self.prefill_parts(p, prompt, n)
        caches = []
        qw = (P * D) // 2 if self.kv4 else P * D
        qd = jnp.uint8 if self.kv4 else jnp.int8
        for k, v in kvs:
            if self.kvq:
                k8, ks = self._quant_kv(k, n, S0)
                v8, vs = self._quant_kv(v, n, S0)
                Kc = (jnp.zeros((n, Hkv // P, T, qw), qd)
                      .at[:, :, :S0].set(k8),
                      jnp.zeros((n, Hkv // P, T, P), jnp.float32)
                      .at[:, :, :S0].set(ks))
                Vc = (jnp.zeros((n, Hkv // P, T, qw), qd)
                      .at[:, :, :S0].set(v8),
                      jnp.zeros((n, Hkv // P, T, P), jnp.float32)
                      .at[:, :, :S0].set(vs))
            else:
                Kc = jnp.zeros((n, Hkv // P, T, P * D), k.dtype) \
                    .at[:, :, :S0].set(self._pack(k, n, S0))
                Vc = jnp.zeros((n, Hkv // P, T, P * D), v.dtype) \
                    .at[:, :, :S0].set(self._pack(v, n, S0))
            caches.append((Kc, Vc))
        logits0 = self.head(p, h[:, -1])
        return logits0, caches

    def _pack_q(self, q, n):
        """(n, H, D) per-head queries -> packed BLOCK-DIAGONAL
        (n, Hp, P*G, P*D): packed slot c holds kv head (hp*P + c)'s G
        query rows in block c, zeros elsewhere — the full-width
        contraction with the packed K then yields exactly the per-head
        scores (GQA: G rows per block; MHA is the G=1 case)."""
        import jax.numpy as jnp
        D, P, G = self.E // self.H, self.P, self.G
        Hp = self.Hkv // P
        ar = jnp.arange(P)
        q6 = jnp.moveaxis(q.reshape(n, Hp, P, G, D), 2, 0)
        return jnp.zeros((n, Hp, P, G, P, D), q.dtype) \
            .at[:, :, ar, :, ar, :].set(q6) \
            .reshape(n, Hp, P * G, P * D)

    def _unpack_o(self, O2, n):
        """(n, Hp, P*G, P*D) packed attention output -> (n, E): extract
        the DIAGONAL (own-head) blocks the packed contraction kept."""
        import jax.numpy as jnp
        D, P, G = self.E // self.H, self.P, self.G
        Hp = self.Hkv // P
        ar = jnp.arange(P)
        return jnp.moveaxis(
            O2.reshape(n, Hp, P, G, P, D)[:, :, ar, :, ar, :],
            0, 2).reshape(n, self.E)

    def paged_token_step(self, p, tok, pools, page_table, lens, active,
                         n, page_size, n_pages, use_kernel=None):
        """One ragged decode step against the PAGED KV cache (the
        serving engine's hot path): feed token `tok` (n,) for each slot
        at its own position `lens[i]`, write the new K/V row into the
        slot's current page (inactive slots scatter out-of-bounds and
        are DROPPED), and attend over each slot's pages via
        ops.attention.paged_attention with per-slot lengths. Returns
        (logits (n, V), new pools).

        `pools` is a list per block: (K, V) of (n_pages, Hp, page_size,
        P*D), or with kv8 ((K8, Ks), (V8, Vs)) carrying the fp32
        per-(head, position) scale pools. Numerics match `token_step`
        by construction: same qkv/rope/pack/extract helpers, same scale
        folding — the paged==dense greedy agreement test leans on
        this."""
        import jax.numpy as jnp
        from .ops.attention import paged_attention
        D, E, P = self.E // self.H, self.E, self.P
        G = self.G
        ln = self.ln
        ps = page_size
        # clamp positions so an inactive slot's stale length can never
        # index outside the table/pos-embedding (its output is masked)
        pos = jnp.minimum(lens, self.T - 1)
        h = self.embed(p, tok, pos)
        if self.rope:
            from .autograd import rope_tables, apply_rope
            rcos, rsin = rope_tables(pos, D, self.rope_theta)  # (n, D)
            rcos, rsin = rcos[:, None, :], rsin[:, None, :]
        nidx = jnp.arange(n)
        # inactive slots write to page id n_pages: out of bounds, and
        # the scatter uses mode="drop" — no trash page needed
        pvec = jnp.where(active, page_table[nidx, pos // ps], n_pages)
        off = pos % ps
        ln_att = jnp.where(active, pos + 1, 1)
        new_pools = []
        for li, (bp, pool) in enumerate(zip(p["blocks"], pools)):
            x = ln(h, bp["g1"], bp["b1"], _blk(li, "ln1"))
            with jax.named_scope(_blk(li, "attn")):
                q, kn, vn = self.qkv(bp, x, n)   # q (n,H,D); kv (n,Hkv,D)
                if self.rope:
                    q = apply_rope(q, rcos, rsin)
                    kn = apply_rope(kn, rcos, rsin)
                if self.kvq:
                    (K8, Ks), (V8, Vs) = pool
                    k8, ks = self._quant_kv(kn[:, :, None], n, 1)
                    v8, vs = self._quant_kv(vn[:, :, None], n, 1)
                    K8 = K8.at[pvec, :, off, :].set(k8[:, :, 0], mode="drop")
                    Ks = Ks.at[pvec, :, off, :].set(ks[:, :, 0], mode="drop")
                    V8 = V8.at[pvec, :, off, :].set(v8[:, :, 0], mode="drop")
                    Vs = Vs.at[pvec, :, off, :].set(vs[:, :, 0], mode="drop")
                    pool = ((K8, Ks), (V8, Vs))
                    Kmat, Vmat, Ksc, Vsc = K8, V8, Ks, Vs
                else:
                    K, V = pool
                    K = K.at[pvec, :, off, :].set(
                        self._pack(kn[:, :, None], n, 1)[:, :, 0],
                        mode="drop")
                    V = V.at[pvec, :, off, :].set(
                        self._pack(vn[:, :, None], n, 1)[:, :, 0],
                        mode="drop")
                    pool = (K, V)
                    Kmat, Vmat, Ksc, Vsc = K, V, None, None
                Q2 = self._pack_q(q, n)
                O2 = paged_attention(
                    Q2, Kmat, Vmat, page_table, ln_att, ps,
                    scale=self.scale, k_scales=Ksc, v_scales=Vsc,
                    groups=G, use_kernel=use_kernel)
                o = self._unpack_o(O2.astype(x.dtype), n)
                h = h + _mm(o, bp["Wo"]) + bp["bo"]
            x = ln(h, bp["g2"], bp["b2"], _blk(li, "ln2"))
            h = h + self.mlp(bp, x, li)
            new_pools.append(pool)
        logits = self.head(p, h)
        return logits, new_pools

    def paged_verify_step(self, p, toks, pools, page_table, lens,
                          active, n, page_size, n_pages, k,
                          use_kernel=None, write_limits=None):
        """The speculative VERIFY step against the PAGED pool: feed
        `toks` (n, k) at per-slot positions lens[i]..lens[i]+k-1 in ONE
        batched forward — write all k K/V rows into each slot's pages
        (inactive slots, and positions at or past `write_limits`
        (exclusive bound, default the page-table horizon), scatter
        out-of-bounds and DROP), then attend via paged_attention's
        q_tokens causal ladder. Returns (logits (n, k, V), new pools):
        logits[:, j] equals the j-th sequential paged_token_step's
        logits for every committed position — the engine's spec==greedy
        anchor. Dropped-write positions only ever feed DISCARDED ladder
        outputs (take is capped at the slot's remaining budget)."""
        import jax.numpy as jnp
        from .ops.attention import paged_attention
        D, E, P = self.E // self.H, self.E, self.P
        G = self.G
        ln = self.ln
        ps = page_size
        nidx = jnp.arange(n)
        posk = lens[:, None] + jnp.arange(k)[None, :]      # (n, k)
        pos_emb = jnp.minimum(posk, self.T - 1)
        h = self.embed(p, toks, pos_emb)
        if self.rope:
            from .autograd import rope_tables, apply_rope
            rcos, rsin = rope_tables(pos_emb.reshape(-1), D,
                                     self.rope_theta)
            rcos = rcos.reshape(n, k, D)[:, None]          # (n,1,k,D)
            rsin = rsin.reshape(n, k, D)[:, None]
        wl = write_limits if write_limits is not None \
            else jnp.full((n,), self.T, jnp.int32)
        ok_w = active[:, None] & (posk < wl[:, None])
        pvec = jnp.where(ok_w, page_table[nidx[:, None],
                                          posk // ps], n_pages)
        off = posk % ps
        ln_att = jnp.where(active, lens + k, 1)
        new_pools = []
        for li, (bp, pool) in enumerate(zip(p["blocks"], pools)):
            x = ln(h, bp["g1"], bp["b1"], _blk(li, "ln1"))
            with jax.named_scope(_blk(li, "attn")):
                q, kn, vn = self.qkv(bp, x, n, S=k)  # q (n,H,k,D)
                if self.rope:
                    q = apply_rope(q, rcos, rsin)
                    kn = apply_rope(kn, rcos, rsin)
                if self.kvq:
                    (K8, Ks), (V8, Vs) = pool
                    k8, ks = self._quant_kv(kn, n, k)
                    v8, vs = self._quant_kv(vn, n, k)
                    K8 = K8.at[pvec, :, off, :].set(
                        k8.swapaxes(1, 2), mode="drop")
                    Ks = Ks.at[pvec, :, off, :].set(
                        ks.swapaxes(1, 2), mode="drop")
                    V8 = V8.at[pvec, :, off, :].set(
                        v8.swapaxes(1, 2), mode="drop")
                    Vs = Vs.at[pvec, :, off, :].set(
                        vs.swapaxes(1, 2), mode="drop")
                    pool = ((K8, Ks), (V8, Vs))
                    Kmat, Vmat, Ksc, Vsc = K8, V8, Ks, Vs
                else:
                    K, V = pool
                    kp = self._pack(kn, n, k)
                    vp = self._pack(vn, n, k)
                    K = K.at[pvec, :, off, :].set(
                        kp.swapaxes(1, 2), mode="drop")
                    V = V.at[pvec, :, off, :].set(
                        vp.swapaxes(1, 2), mode="drop")
                    pool = (K, V)
                    Kmat, Vmat, Ksc, Vsc = K, V, None, None
                Q2 = self._pack_q_multi(q, n, k)
                O2 = paged_attention(
                    Q2, Kmat, Vmat, page_table, ln_att, ps,
                    scale=self.scale, k_scales=Ksc, v_scales=Vsc,
                    groups=G, use_kernel=use_kernel, q_tokens=k)
                o = self._unpack_o_multi(O2.astype(x.dtype), n, k)
                h = h + _mm(o, bp["Wo"]) + bp["bo"]
            x = ln(h, bp["g2"], bp["b2"], _blk(li, "ln2"))
            h = h + self.mlp(bp, x, li)
            new_pools.append(pool)
        logits = self.head(p, h)
        return logits, new_pools

    def token_step(self, p, tok, caches, i, n, use_kernel=None):
        """Feed token `tok` (n,) at generated-index `i` (position S0+i)
        through all blocks against the caches; returns (logits (n, V),
        new caches). `use_kernel=None` routes attention through the
        Pallas flash-decode kernel on TPU (in-kernel dequant for
        quantized caches) and the inline einsum math elsewhere."""
        import jax
        import jax.numpy as jnp
        from jax import lax
        H, D, E, P = self.H, self.E // self.H, self.E, self.P
        Hkv, G = self.Hkv, self.G
        Hp = Hkv // P
        ln = self.ln
        pos_idx = self.S0 + i
        h = self.embed(p, tok, pos_idx)
        kmask = (jnp.arange(self.T) <= pos_idx)
        if self.rope:
            from .autograd import rope_tables, apply_rope
            rcos, rsin = rope_tables(pos_idx[None], D, self.rope_theta)
            rcos, rsin = rcos[0], rsin[0]          # (D,) broadcast
        new_caches = []
        for li, ((Kc, Vc), bp) in enumerate(zip(caches, p["blocks"])):
            x = ln(h, bp["g1"], bp["b1"], _blk(li, "ln1"))
            with jax.named_scope(_blk(li, "attn")):
                q, kn, vn = self.qkv(bp, x, n)   # q (n,H,D); kv (n,Hkv,D)
                if self.rope:
                    q = apply_rope(q, rcos, rsin)
                    kn = apply_rope(kn, rcos, rsin)
                # packed caches: one contiguous (P*D)-lane row per token
                if self.kvq:
                    (K8, Ks), (V8, Vs) = Kc, Vc
                    k8, ks = self._quant_kv(kn[:, :, None], n, 1)
                    v8, vs = self._quant_kv(vn[:, :, None], n, 1)
                    K8 = lax.dynamic_update_slice(K8, k8, (0, 0, pos_idx, 0))
                    Ks = lax.dynamic_update_slice(Ks, ks, (0, 0, pos_idx, 0))
                    V8 = lax.dynamic_update_slice(V8, v8, (0, 0, pos_idx, 0))
                    Vs = lax.dynamic_update_slice(Vs, vs, (0, 0, pos_idx, 0))
                    Kc, Vc = (K8, Ks), (V8, Vs)
                    Kmat = self._dequant_cache(K8, x.dtype)
                    Vmat = self._dequant_cache(V8, x.dtype)
                else:
                    Kc = lax.dynamic_update_slice(
                        Kc, kn.reshape(n, Hp, 1, P * D), (0, 0, pos_idx, 0))
                    Vc = lax.dynamic_update_slice(
                        Vc, vn.reshape(n, Hp, 1, P * D), (0, 0, pos_idx, 0))
                    Kmat, Vmat = Kc, Vc
                # block-diagonal queries (see _pack_q): the full-width
                # contraction with the packed K yields exactly the per-head
                # scores (GQA: G rows per block; MHA is the G=1 case)
                Q2 = self._pack_q(q, n)
                use_k = use_kernel if use_kernel is not None \
                    else jax.default_backend() == "tpu"
                if use_k:
                    # TPU: the Pallas flash-decode kernel streams the cache
                    # blockwise — an int8 cache streams its BYTES and
                    # dequantizes in-kernel; the XLA einsum below would
                    # materialize the dequant. (int4 at PD=128 packs to 64
                    # lanes, fails flash_decode's alignment gate and takes
                    # its reference path on TPU — counted, not hidden.)
                    from .ops.attention import flash_decode
                    lens_att = jnp.broadcast_to(pos_idx + 1, (n,)) \
                        .astype(jnp.int32)
                    if self.kvq:
                        O2 = flash_decode(
                            Q2, K8, V8, lens_att, scale=self.scale,
                            k_scales=Ks, v_scales=Vs, groups=G,
                            use_kernel=use_k).astype(x.dtype)
                    else:
                        O2 = flash_decode(
                            Q2, Kc, Vc, lens_att, scale=self.scale,
                            groups=G, use_kernel=use_k).astype(x.dtype)
                else:
                    from .observe import record_attention_dispatch
                    record_attention_dispatch("flash_decode", "reference")
                    s = jnp.einsum("nhqj,nhtj->nhqt", Q2, Kmat) * self.scale
                    if self.kvq:
                        # K-scales: one factor per (source position, own
                        # block)
                        s = s * self._scale_rows(Ks, G)
                    a = jax.nn.softmax(jnp.where(kmask, s, -jnp.inf),
                                       axis=-1)
                    if self.kvq:
                        # V-scales fold into the weights for the own-head
                        # block (the only one extracted below)
                        a = (a * self._scale_rows(Vs, G)).astype(x.dtype)
                    O2 = jnp.einsum("nhqt,nhtj->nhqj", a,
                                    Vmat)           # (n,Hp,P*G,P*D)
                o = self._unpack_o(O2, n)
                h = h + _mm(o, bp["Wo"]) + bp["bo"]
            x = ln(h, bp["g2"], bp["b2"], _blk(li, "ln2"))
            h = h + self.mlp(bp, x, li)
            new_caches.append((Kc, Vc))
        logits = self.head(p, h)
        return logits, new_caches

    def _pack_q_multi(self, q, n, k):
        """(n, H, k, D) per-head queries for k tokens -> packed
        block-diagonal (n, Hp, k*P*G, P*D), token-major rows (the
        (q_tokens, P, G) layout ops.attention's q_tokens ladder
        expects)."""
        import jax.numpy as jnp
        Hp = self.Hkv // self.P
        PG = self.P * self.G
        PD = self.P * (self.E // self.H)
        qf = q.swapaxes(1, 2).reshape(n * k, self.H,
                                      self.E // self.H)
        Q2 = self._pack_q(qf, n * k)            # (n*k, Hp, PG, PD)
        return jnp.moveaxis(Q2.reshape(n, k, Hp, PG, PD), 1, 2) \
            .reshape(n, Hp, k * PG, PD)

    def _unpack_o_multi(self, O2, n, k):
        """(n, Hp, k*P*G, P*D) packed attention output -> (n, k, E)."""
        import jax.numpy as jnp
        Hp = self.Hkv // self.P
        PG = self.P * self.G
        PD = self.P * (self.E // self.H)
        O5 = jnp.moveaxis(O2.reshape(n, Hp, k, PG, PD), 2, 1) \
            .reshape(n * k, Hp, PG, PD)
        return self._unpack_o(O5, n * k).reshape(n, k, self.E)

    def verify_step(self, p, toks, caches, pos, active, n, k,
                    use_kernel=None):
        """The speculative VERIFY step: feed `toks` (n, k) at per-row
        positions pos[i]..pos[i]+k-1 through all blocks in ONE batched
        forward — writes all k KV rows (per-row scatter; inactive rows
        and positions past the cache drop), then attends with the
        causal ladder (token j sees cache positions <= pos+j) via
        ops.attention.flash_decode's q_tokens mode. Returns (logits
        (n, k, V), new caches): logits[:, j] is the target's own next
        token after consuming toks[:, :j+1] — exactly the j-th
        sequential token_step's logits, which is what makes
        longest-accepted-prefix speculative decoding greedy-exact.
        k == 1 with a scalar-broadcast `pos` is token_step's math at
        per-row positions (the draft loop uses it that way)."""
        import jax
        import jax.numpy as jnp
        from .ops.attention import flash_decode
        D, E, P = self.E // self.H, self.E, self.P
        G, Hkv = self.G, self.Hkv
        Hp = Hkv // P
        ln = self.ln
        nidx = jnp.arange(n)
        posk = pos[:, None] + jnp.arange(k)[None, :]       # (n, k)
        pos_emb = jnp.minimum(posk, self.T - 1)
        h = self.embed(p, toks, pos_emb)                   # (n, k, E)
        if self.rope:
            from .autograd import rope_tables, apply_rope
            rcos, rsin = rope_tables(pos_emb.reshape(-1), D,
                                     self.rope_theta)
            rcos = rcos.reshape(n, k, D)[:, None]          # (n,1,k,D)
            rsin = rsin.reshape(n, k, D)[:, None]
        # inactive rows and positions past the cache scatter to row T
        # and are DROPPED (the cache time dim is T)
        posw = jnp.where(active[:, None] & (posk < self.T), posk,
                         self.T)                           # (n, k)
        # NOT clamped to T: the ladder limit for token ti is
        # lens_att - (k-1-ti); clamping would truncate the LAST
        # tokens' masks in the final rounds near the cache end
        # (token ti must always see its own position pos+ti — the
        # positions past T it can also "see" were drop-written and
        # only ever feed discarded outputs)
        lens_att = pos + k                                 # (n,)
        new_caches = []
        for li, ((Kc, Vc), bp) in enumerate(zip(caches, p["blocks"])):
            x = ln(h, bp["g1"], bp["b1"], _blk(li, "ln1"))
            with jax.named_scope(_blk(li, "attn")):
                q, kn, vn = self.qkv(bp, x, n, S=k)  # q (n,H,k,D)
                if self.rope:
                    q = apply_rope(q, rcos, rsin)
                    kn = apply_rope(kn, rcos, rsin)
                if self.kvq:
                    (K8, Ks), (V8, Vs) = Kc, Vc
                    k8, ks = self._quant_kv(kn, n, k)   # (n,Hp,k,·)
                    v8, vs = self._quant_kv(vn, n, k)
                    K8 = K8.at[nidx[:, None], :, posw, :].set(
                        k8.swapaxes(1, 2), mode="drop")
                    Ks = Ks.at[nidx[:, None], :, posw, :].set(
                        ks.swapaxes(1, 2), mode="drop")
                    V8 = V8.at[nidx[:, None], :, posw, :].set(
                        v8.swapaxes(1, 2), mode="drop")
                    Vs = Vs.at[nidx[:, None], :, posw, :].set(
                        vs.swapaxes(1, 2), mode="drop")
                    Kc, Vc = (K8, Ks), (V8, Vs)
                    Kq, Vq, Ksc, Vsc = K8, V8, Ks, Vs
                else:
                    kp = self._pack(kn, n, k)           # (n,Hp,k,P*D)
                    vp = self._pack(vn, n, k)
                    Kc = Kc.at[nidx[:, None], :, posw, :].set(
                        kp.swapaxes(1, 2), mode="drop")
                    Vc = Vc.at[nidx[:, None], :, posw, :].set(
                        vp.swapaxes(1, 2), mode="drop")
                    Kq, Vq, Ksc, Vsc = Kc, Vc, None, None
                Q2 = self._pack_q_multi(q, n, k)
                O2 = flash_decode(Q2, Kq, Vq, lens_att, scale=self.scale,
                                  k_scales=Ksc, v_scales=Vsc, groups=G,
                                  q_tokens=k, use_kernel=use_kernel)
                o = self._unpack_o_multi(O2.astype(x.dtype), n, k)
                h = h + _mm(o, bp["Wo"]) + bp["bo"]
            x = ln(h, bp["g2"], bp["b2"], _blk(li, "ln2"))
            h = h + self.mlp(bp, x, li)
            new_caches.append((Kc, Vc))
        logits = self.head(p, h)
        return logits, new_caches


def _spec_metrics():
    """Speculative-decoding metrics, spelled out for the static lint
    (verdict= values are members of SPEC_VERDICTS; kv_dtype= values of
    KV_DTYPES via kv_label)."""
    from . import observe
    return {
        "tokens": observe.counter(
            "singa_spec_tokens_total",
            "speculative-decoding tokens by verdict (drafted / "
            "accepted / bonus / wasted)"),
        "rounds": observe.counter(
            "singa_spec_rounds_total",
            "speculative verify rounds (one draft+verify cycle)"),
        "acceptance": observe.gauge(
            "singa_spec_acceptance_rate",
            "last call/sync's accepted-over-drafted fraction"),
    }


def record_spec(drafted: int, accepted: int, bonus: int, rounds: int):
    """Book one spec-decoding call/sync's draft economics into the
    singa_spec_* metrics. Returns the acceptance fraction (None when
    nothing was drafted)."""
    from . import observe
    rate = accepted / drafted if drafted > 0 else None
    if not observe.is_enabled():
        return rate
    m = _spec_metrics()
    if drafted:
        m["tokens"].inc(float(drafted), verdict="drafted")
        m["tokens"].inc(float(accepted), verdict="accepted")
        m["tokens"].inc(float(drafted - accepted), verdict="wasted")
    if bonus:
        m["tokens"].inc(float(bonus), verdict="bonus")
    if rounds:
        m["rounds"].inc(float(rounds))
    if rate is not None:
        m["acceptance"].set(rate)
    return rate


def _set_col(buf, i, vals):
    """buf (B,K,L) with column `i` (traced index) set to vals (B,K)."""
    from jax import lax
    return lax.dynamic_update_slice_in_dim(
        buf, vals[..., None], i, axis=2)


def _pool_merge(pool_tok, pool_norm, pool_raw, cand_tok, cand_norm,
                cand_raw, K):
    """Merge candidate finished hypotheses into the K-slot pool, keeping
    the K best by normalized score. Shapes: pool (B,K,L)/(B,K); cand
    (B,kk,L)/(B,kk). Candidates not actually finished carry NEG norm."""
    import jax.numpy as jnp
    all_norm = jnp.concatenate([pool_norm, cand_norm], axis=1)
    all_raw = jnp.concatenate([pool_raw, cand_raw], axis=1)
    all_tok = jnp.concatenate([pool_tok, cand_tok], axis=1)
    from jax import lax
    top_norm, pick = lax.top_k(all_norm, K)
    new_raw = jnp.take_along_axis(all_raw, pick, axis=1)
    new_tok = jnp.take_along_axis(all_tok, pick[..., None], axis=1)
    return new_tok, top_norm, new_raw


def _decode_core(m, S0, max_new, moe_capacity_factor=None, kv_dtype=None):
    """Build the _DecodeCore matching model `m`'s static config."""
    H = m.blocks[0].attn.num_heads
    kv = m.blocks[0].attn.num_kv_heads
    T = S0 + max_new
    assert T <= m.max_seq, \
        f"prompt {S0} + new {max_new} exceeds max_seq {m.max_seq}"
    # decode-time capacity override: capacity-limited routing is a
    # batch-global effect, so cached decode == full forward only in the
    # no-drop regime; a tight TRAINING capacity_factor shouldn't silently
    # drop tokens at serving time — pass moe_capacity_factor (e.g.
    # float(num_experts) for guaranteed no drops) to generate()/
    # generate_beam() to decouple the two.
    moe_ks = [(b.moe.k, float(moe_capacity_factor
                              if moe_capacity_factor is not None
                              else b.moe.capacity_factor))
              if b.moe_experts else None for b in m.blocks]
    return _DecodeCore(H, m.dim, S0, T, (m.dim // H) ** -0.5, moe_ks,
                       kv_heads=kv,
                       rope=(getattr(m, "pos_encoding", "learned")
                             == "rope"),
                       rope_theta=getattr(m, "rope_theta", 10000.0),
                       kv_dtype=kv_dtype)


# ---- decode-param preparation + memo ------------------------------------

def decode_raw(m):
    """Every parameter array the decode consumes — the identity basis for
    the fused/cast decode tree's memo."""
    if not m._pos_init:
        raise RuntimeError(
            "generate() needs initialized weights - call "
            "Model.compile([ids], ...) (or run a forward) first")
    arrs = [m.tok_embed.W.data, m.ln_f.gamma.data, m.ln_f.beta.data]
    if m.pos_encoding != "rope":
        arrs.append(m.pos_embed.data)
    if m.head is not None:
        arrs.append(m.head.W.data)
    for b in m.blocks:
        arrs += [b.ln1.gamma.data, b.ln1.beta.data,
                 b.ln2.gamma.data, b.ln2.beta.data,
                 b.attn.Wq.data, b.attn.Wk.data, b.attn.Wv.data,
                 b.attn.Wo.data]
        if b.attn.use_bias:
            arrs += [b.attn.bq.data, b.attn.bk.data, b.attn.bv.data,
                     b.attn.bo.data]
        if b.moe_experts:
            arrs += [b.moe.Wg.data, b.moe.W1.data, b.moe.b1.data,
                     b.moe.W2.data, b.moe.b2.data]
        else:
            arrs += [b.fc1.W.data, b.fc1.b.data,
                     b.fc2.W.data, b.fc2.b.data]
    return arrs


def _live_refs(arrs):
    """Weakrefs to the param buffers when supported (a freed buffer then
    invalidates the memo deterministically — id() reuse after GC cannot
    produce a false hit); falls back to strong refs, which pin the old
    buffers alive so their ids stay unique until the next decode_state
    call rebuilds the cache."""
    try:
        return tuple(weakref.ref(a) for a in arrs), True
    except TypeError:
        return tuple(arrs), False


def decode_state(m, dtype):
    """Memoized decode-param tree per serving dtype: the QKV fusion, bf16
    cast, and int8 quantization run once per weight set instead of on
    every generate() call. The memo key holds (weak) references to the
    live param buffers and hits only while every buffer is IDENTICAL
    (`is`) to the referenced one — replacing any param (set_params /
    load_checkpoint / load_gpt2_weights) misses deterministically, with
    no reliance on id() non-reuse."""
    arrs = decode_raw(m)
    cached = getattr(m, "_param_cache", None)
    if cached is not None:
        refs, weak, _ = cached
        live = (a() if weak else a for a in refs)
        if len(refs) != len(arrs) or \
                any(r is not a for r, a in zip(live, arrs)):
            cached = None
    if cached is None:
        refs, weak = _live_refs(arrs)
        cached = m._param_cache = (refs, weak, {})
    trees = cached[2]
    if dtype not in trees:
        trees[dtype] = _cast_params(decode_params(m), dtype)
    return trees[dtype]


def decode_params(m):
    """The functional decode-param tree for model `m` (fp32, unfused
    biases zero-filled, QKV fused, head tied/truncated under vocab_tp)."""
    if not m._pos_init:
        raise RuntimeError(
            "generate() needs initialized weights - call "
            "Model.compile([ids], ...) (or run a forward) first")
    import jax.numpy as jnp
    blocks = []
    zeros = jnp.zeros((m.dim,), m.blocks[0].attn.Wq.data.dtype)
    for b in m.blocks:
        ab = b.attn.use_bias
        bp = {
            "g1": b.ln1.gamma.data, "b1": b.ln1.beta.data,
            # fused QKV: one (E,3E) weight stream per block instead of
            # three — fewer ops on the bandwidth-bound decode path
            "Wqkv": jnp.concatenate(
                [b.attn.Wq.data, b.attn.Wk.data, b.attn.Wv.data],
                axis=1),
            "bqkv": jnp.concatenate(
                [b.attn.bq.data, b.attn.bk.data, b.attn.bv.data])
            if ab else jnp.zeros(
                (b.attn.Wq.shape[1] + b.attn.Wk.shape[1]
                 + b.attn.Wv.shape[1],), zeros.dtype),
            "Wo": b.attn.Wo.data,
            "bo": b.attn.bo.data if ab else zeros,
            "g2": b.ln2.gamma.data, "b2": b.ln2.beta.data,
        }
        if b.moe_experts:
            # routing degree/capacity stay STATIC on _DecodeCore
            # (moe_ks), not in the traced param tree
            bp.update({
                "moeWg": b.moe.Wg.data,
                "moeW1": b.moe.W1.data, "moeb1": b.moe.b1.data,
                "moeW2": b.moe.W2.data, "moeb2": b.moe.b2.data,
            })
        else:
            bp.update({
                "W1": b.fc1.W.data, "bb1": b.fc1.b.data,
                "W2": b.fc2.W.data, "bb2": b.fc2.b.data,
            })
        blocks.append(bp)
    emb = m.tok_embed.W.data
    if m.vocab_tp:
        # tied head, truncated to the true vocab so padded rows (never
        # trained toward anything) cannot win an argmax during decode
        head = emb[:m.vocab_size].T
    else:
        head = m.head.W.data
    return {
        "emb": emb,
        "pos": (jnp.zeros((m.max_seq, 0), emb.dtype)
                if m.pos_encoding == "rope"
                else m.pos_embed.data),
        "gf": m.ln_f.gamma.data, "bf": m.ln_f.beta.data,
        "head": head, "blocks": blocks,
    }


# ---- decode-loop builders -----------------------------------------------

def build_decode(m, B, S0, max_new, temperature, top_k,
                 dtype=None, moe_capacity_factor=None, kv_dtype=None):
    """Greedy/sampled decode fn: (params, prompt, key) -> ids.

    Two jitted stages instead of one fused program: `prefill` (causal
    pass + first sampled token) and the `lax.scan` decode loop. The seam
    is where serving telemetry lives — time-to-first-token is the fenced
    prefill stage, tokens/sec the whole call (observe.record_decode) —
    and it is also where a real server would emit the first token. The
    KV caches stay on device between the stages (no host copy), at the
    cost of one cache-sized device copy per call: the scan carry must
    init from immutable input buffers (donation cannot remove it — XLA
    donation is input->output aliasing and the stage outputs only the
    tiny token array). Amortized over max_new tokens; the math is
    op-for-op identical to the previously fused program.
    """
    import time as _time

    import jax
    import jax.numpy as jnp
    from jax import lax

    from . import observe

    core = _decode_core(m, S0, max_new, moe_capacity_factor,
                        kv_dtype=kv_dtype)

    def sample(logits, key):
        logits = logits.astype(jnp.float32)
        if temperature == 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        logits = logits / temperature
        if top_k is not None:
            kth = jnp.sort(logits, axis=-1)[..., -top_k][..., None]
            logits = jnp.where(logits < kth, -jnp.inf, logits)
        return jax.random.categorical(key, logits).astype(jnp.int32)

    def prefill_stage(p, prompt, key):
        # p arrives pre-cast/quantized (decode_state memo)
        logits0, caches = core.prefill(p, prompt, B)
        key, sub = jax.random.split(key)
        tok0 = sample(logits0, sub)                   # (B,)
        # NaN-logit watch (singa_tpu.health): a poisoned checkpoint or a
        # numerics bug shows up here first — count in-graph, one scalar
        nf0 = jnp.sum((~jnp.isfinite(logits0)).astype(jnp.int32))
        return tok0, caches, key, nf0

    def scan_stage(p, tok0, caches, key, nf0):
        # ---- decode: one token per scan step, O(T) attention ----
        def step(carry, i):
            tok, caches, key, nf = carry
            logits, caches = core.token_step(p, tok, caches, i, B)
            nf = nf + jnp.sum((~jnp.isfinite(logits)).astype(jnp.int32))
            key, sub = jax.random.split(key)
            nxt = sample(logits, sub)
            return (nxt, caches, key, nf), nxt

        (_, _, _, nf), toks = lax.scan(
            step, (tok0, caches, key, nf0), jnp.arange(max_new - 1))
        return jnp.concatenate([tok0[:, None], toks.T], axis=1), nf

    # AOT-staged dispatch (singa_tpu.introspect): each distinct abstract
    # signature is built through explicit trace/lower/compile stages, so
    # serving compiles land in singa_compile_phase_seconds and a rebuilt
    # decode fn (new batch/prompt/max_new) produces a recompile-blame
    # record instead of a silent jit retrace. With the warm store
    # enabled (singa_tpu.warmstart), each build also persists its
    # serialized executable keyed by this name + abstract-signature
    # fingerprint — a restarted process (replica respawn, resilience
    # resume) re-stages these same serving executables from disk and
    # its compile phase collapses to near zero
    from . import introspect
    prefill_jit = introspect.AotExecutor(
        jax.jit(prefill_stage), "serving.prefill",
        names=("params", "prompt", "key"))
    scan_jit = introspect.AotExecutor(
        jax.jit(scan_stage), "serving.decode_scan",
        names=("params", "tok0", "caches", "key", "nf"))

    def decode(p, prompt, key):
        # the sync fences exist only to take honest TTFT/latency samples;
        # with observability disabled the stages dispatch fully async
        # (observe.py's "record_* are no-ops when disabled" contract).
        # The outer serving.decode span covers the WHOLE call — including
        # the host-side seams between stages — so the goodput tracker
        # books full serving wall time as productive; the nested stage
        # spans net out of it.
        obs = observe.is_enabled()
        from . import resilience, slo, watchdog
        # an installed SLO tracker needs honest fenced samples even
        # with the metric hooks disabled — the tracker was installed
        # on purpose, and silently starving it of records would make
        # /slo read "no data" for exactly one of the two serving modes
        sample = obs or slo.get_tracker() is not None
        # the watchdog's `decode` deadline arms over the whole call
        # (prefill + scan + the host seams); `serving.decode` is its
        # deterministic FaultPlan hook
        with watchdog.guard("decode", batch=B), \
                observe.span("serving.decode", batch=B,
                             new_tokens=max_new):
            resilience.fault_point("serving.decode", batch=B)
            t0 = _time.perf_counter()
            ttft = None
            with observe.span("serving.prefill", batch=B,
                              prompt_tokens=S0):
                tok0, caches, key, nf = prefill_jit(p, prompt, key)
                if sample:
                    jax.block_until_ready(tok0)
                    ttft = _time.perf_counter() - t0
            # memory-ledger birth-site hook: the per-block KV caches
            # are live host-visible buffers only at this seam (the
            # fused beam program never surfaces its caches) — the
            # ledger's serving.decode snapshot attributes them here.
            # Gated on an installed ledger: without a consumer, the
            # per-array weakref churn would tax every decode call.
            # When a serving engine's page pool owns the kv_cache
            # region (a persistent provider), the transient note is
            # superseded — the pool provider is authoritative and the
            # per-call weakref churn buys nothing
            from . import memory
            if memory.get_ledger() is not None and \
                    not memory.region_has_provider(
                        memory.REGION_KV_CACHE):
                memory.note_arrays(memory.REGION_KV_CACHE, caches)
            if max_new > 1:
                with observe.span("serving.decode_scan", batch=B,
                                  new_tokens=max_new):
                    toks, nf = scan_jit(p, tok0, caches, key, nf)
            else:
                toks = tok0[:, None]
            ids = jnp.concatenate([prompt if isinstance(prompt, jax.Array)
                                   else jnp.asarray(prompt), toks], axis=1)
            if sample:
                jax.block_until_ready(ids)
                kind = "greedy" if temperature == 0.0 else "sampled"
                total = _time.perf_counter() - t0
                if obs:
                    observe.record_decode(
                        kind, total, new_tokens=B * max_new,
                        batch=B, ttft=ttft, prompt_tokens=B * S0)
                    from . import health
                    health.record_nan_logits(int(jax.device_get(nf)),
                                             kind)
                # SLO wiring: the dense path's calls count toward the
                # declared serving objectives too (latency/rate/TTFT),
                # so /slo answers for static-batch deployments —
                # note_decode is a no-op without a tracker
                slo.note_decode(kind, total, B * max_new, ttft=ttft,
                                batch=B)
        return ids

    return decode


def build_spec_decode(m, draft, B, S0, max_new, spec_k, dtype=None,
                      moe_capacity_factor=None, kv_dtype=None,
                      use_kernel=None):
    """Draft-model speculative GREEDY decode fn:
    (target_params, draft_params, prompt) -> (ids, stats).

    Each round: the small draft model proposes `spec_k` tokens
    sequentially against its own KV cache, the target verifies ALL of
    them in ONE batched forward (verify_step: spec_k+1 tokens through
    the cache, the causal ladder), and the longest accepted prefix plus
    the target's own next token commit — 1..spec_k+1 tokens per round
    at ~one decode step's weight traffic. Greedy-equivalence is exact
    by construction: every committed token IS the target's argmax given
    the committed prefix (the spec==greedy test enforces token-for-token
    identity with build_decode's output). Per-row variable acceptance
    rides an active mask + per-row positions, so the verify executable
    compiles ONCE (a single lax.while_loop program).

    The draft runs an fp KV cache regardless of the target's
    `kv_dtype` — draft proposals only gate ACCEPTANCE, never
    correctness, and the draft cache is small."""
    import time as _time

    import jax
    import jax.numpy as jnp
    from jax import lax

    from . import observe

    assert spec_k >= 1, spec_k
    K = int(spec_k)
    core = _decode_core(m, S0, max_new, moe_capacity_factor,
                        kv_dtype=kv_dtype)
    core_d = _decode_core(draft, S0, max_new, moe_capacity_factor,
                          kv_dtype=None)

    def prefill_stage(pt, pd, prompt):
        logits0, caches = core.prefill(pt, prompt, B)
        _dl, dcaches = core_d.prefill(pd, prompt, B)   # logits unused:
        # the first token is the TARGET's — the draft only fills its
        # own KV cache over the prompt here
        tok0 = jnp.argmax(logits0.astype(jnp.float32),
                          axis=-1).astype(jnp.int32)
        nf0 = jnp.sum((~jnp.isfinite(logits0)).astype(jnp.int32))
        return tok0, caches, dcaches, nf0

    def spec_stage(pt, pd, tok0, caches, dcaches, nf0):
        nidx = jnp.arange(B)
        buf = jnp.zeros((B, max_new), jnp.int32).at[:, 0].set(tok0)
        zero = jnp.int32(0)

        def cond(c):
            return jnp.any(c[1] < max_new)

        def body(c):
            buf, cnt, tok, caches, dcaches, nf, drafted, accepted, \
                bonus, rounds = c
            active = cnt < max_new
            pos = S0 + cnt - 1          # the pending token's position

            def dstep(carry, j):
                dt, dc = carry
                lg, dc = core_d.verify_step(
                    pd, dt[:, None], dc, pos + j, active, B, 1,
                    use_kernel=use_kernel)
                nxt = jnp.argmax(lg[:, 0].astype(jnp.float32),
                                 axis=-1).astype(jnp.int32)
                return (nxt, dc), nxt

            # K+1 draft steps for K proposals: the extra step feeds
            # d_K so the draft cache writes row pos+K too — when all
            # K drafts accept (take = K+1, the bonus token commits at
            # pos+K+1), that row would otherwise stay a ZERO hole the
            # draft attends over forever after, silently degrading
            # every later proposal's acceptance
            (_, dcaches), drafts = lax.scan(
                dstep, (tok, dcaches), jnp.arange(K + 1))
            drafts = drafts[:K].T                   # (B, K)
            feed = jnp.concatenate([tok[:, None], drafts], axis=1)
            logits, caches = core.verify_step(
                pt, feed, caches, pos, active, B, K + 1,
                use_kernel=use_kernel)
            g = jnp.argmax(logits.astype(jnp.float32),
                           axis=-1).astype(jnp.int32)  # (B, K+1)
            match = (g[:, :K] == drafts).astype(jnp.int32)
            a = jnp.sum(jnp.cumprod(match, axis=1), axis=1)  # (B,)
            take = jnp.where(active,
                             jnp.minimum(a + 1, max_new - cnt), 0)
            j = jnp.arange(K + 1)[None, :]
            idx = jnp.where(j < take[:, None], cnt[:, None] + j,
                            max_new)
            buf = buf.at[nidx[:, None], idx].set(g, mode="drop")
            tok = jnp.where(active,
                            g[nidx, jnp.clip(take - 1, 0, K)], tok)
            cnt = cnt + take
            # nf: only logits whose tokens commit (the rest are
            # ladder positions past this row's budget — garbage by
            # construction, not a health signal)
            nf = nf + jnp.sum(((~jnp.isfinite(logits))
                               & (j < take[:, None])[..., None])
                              .astype(jnp.int32))
            n_act = jnp.sum(active.astype(jnp.int32))
            drafted = drafted + K * n_act
            # a budget-truncated round (take <= a) commits ONLY
            # accepted draft tokens — the bonus token exists only
            # when the full a+1 window committed
            bo_i = ((take > 0) & (take > a)).astype(jnp.int32)
            accepted = accepted + jnp.sum(take - bo_i)
            bonus = bonus + jnp.sum(bo_i)
            return (buf, cnt, tok, caches, dcaches, nf, drafted,
                    accepted, bonus, rounds + 1)

        init = (buf, jnp.full((B,), 1, jnp.int32), tok0, caches,
                dcaches, nf0, zero, zero, zero, zero)
        buf, _, _, _, _, nf, drafted, accepted, bonus, rounds = \
            lax.while_loop(cond, body, init) if max_new > 1 else init
        return buf, nf, drafted, accepted, bonus, rounds

    from . import introspect
    prefill_jit = introspect.AotExecutor(
        jax.jit(prefill_stage), "serving.spec_prefill",
        names=("params", "draft_params", "prompt"))
    spec_jit = introspect.AotExecutor(
        jax.jit(spec_stage), "serving.spec_verify",
        names=("params", "draft_params", "tok0", "caches",
               "draft_caches", "nf"))

    def decode(pt, pd, prompt):
        from . import resilience, slo, watchdog
        obs = observe.is_enabled()
        sample = obs or slo.get_tracker() is not None
        with watchdog.guard("decode", batch=B), \
                observe.span("serving.decode", batch=B,
                             new_tokens=max_new, spec_k=K):
            resilience.fault_point("serving.decode", batch=B)
            t0 = _time.perf_counter()
            ttft = None
            with observe.span("serving.prefill", batch=B,
                              prompt_tokens=S0):
                tok0, caches, dcaches, nf = prefill_jit(pt, pd, prompt)
                if sample:
                    jax.block_until_ready(tok0)
                    ttft = _time.perf_counter() - t0
            from . import memory
            if memory.get_ledger() is not None and \
                    not memory.region_has_provider(
                        memory.REGION_KV_CACHE):
                memory.note_arrays(memory.REGION_KV_CACHE,
                                   (caches, dcaches))
            with observe.span("serving.spec_verify", batch=B,
                              new_tokens=max_new):
                toks, nf, drafted, accepted, bonus, rounds = spec_jit(
                    pt, pd, tok0, caches, dcaches, nf)
            ids = jnp.concatenate(
                [prompt if isinstance(prompt, jax.Array)
                 else jnp.asarray(prompt), toks], axis=1)
            if sample:
                jax.block_until_ready(ids)
                total = _time.perf_counter() - t0
                drafted, accepted, bonus, rounds = (
                    int(v) for v in jax.device_get(
                        (drafted, accepted, bonus, rounds)))
                record_spec(drafted, accepted, bonus, rounds)
                if obs:
                    observe.record_decode(
                        "spec", total, new_tokens=B * max_new,
                        batch=B, ttft=ttft, prompt_tokens=B * S0)
                    from . import health
                    health.record_nan_logits(int(jax.device_get(nf)),
                                             "spec")
                slo.note_decode("spec", total, B * max_new, ttft=ttft,
                                batch=B)
        return ids

    return decode


def build_beam_decode(m, B, S0, max_new, num_beams, length_penalty,
                      eos_id, dtype, pad_id=None, moe_capacity_factor=None,
                      kv_dtype=None):
    """Jitted beam-search decode fn: (params, prompt) -> (ids, score)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    V = m.vocab_size
    K = num_beams
    core = _decode_core(m, S0, max_new, moe_capacity_factor,
                        kv_dtype=kv_dtype)
    NEG = jnp.float32(-1e9)
    pad = 0 if eos_id is None else (pad_id if pad_id is not None
                                    else eos_id)

    def norm_len(score, length):
        return score / (length.astype(jnp.float32) ** length_penalty)

    def decode(p, prompt):
        # p arrives pre-cast/quantized (decode_state memo)
        # ---- prefill on the B prompts, then tile caches to B*K ----
        logits0, caches = core.prefill(p, prompt, B)
        # beam b*K+k from prompt b (tree-map: kv8 caches are
        # (int8, scales) tuples)
        caches = jax.tree.map(lambda a: jnp.repeat(a, K, axis=0),
                              caches)
        logp0 = jax.nn.log_softmax(
            logits0.astype(jnp.float32), axis=-1)     # (B,V)
        nf = jnp.sum((~jnp.isfinite(logits0)).astype(jnp.int32))
        tokens = jnp.full((B, K, max_new), pad, jnp.int32)
        # finished-hypothesis pool (HF-style): finished beams move
        # here with a length-normalized score and stop competing by
        # raw score against still-growing beams
        pool_tok = jnp.full((B, K, max_new), pad, jnp.int32)
        pool_norm = jnp.full((B, K), NEG)
        pool_raw = jnp.full((B, K), NEG)

        if eos_id is None:
            s0, t0 = lax.top_k(logp0, K)              # (B,K)
            alive_scores = s0
            tokens = tokens.at[:, :, 0].set(t0)
        else:
            # consider 2K candidates so K alive beams survive even if
            # eos ranks high
            kk = min(2 * K, V)
            cs, ct = lax.top_k(logp0, kk)             # (B,kk)
            is_eos = ct == eos_id
            # finished at length 1 -> pool
            cand_pool_tok = jnp.broadcast_to(
                jnp.full((max_new,), pad, jnp.int32)
                .at[0].set(eos_id)[None, None],
                (B, kk, max_new))
            pool_tok, pool_norm, pool_raw = _pool_merge(
                pool_tok, pool_norm, pool_raw,
                cand_pool_tok,
                jnp.where(is_eos, norm_len(cs, jnp.asarray(1)), NEG),
                cs, K)
            # alive beams: best K non-eos
            alive_cs = jnp.where(is_eos, NEG, cs)
            s0, pick = lax.top_k(alive_cs, K)         # (B,K) of [0,kk)
            t0 = jnp.take_along_axis(ct, pick, axis=1)
            alive_scores = s0
            tokens = tokens.at[:, :, 0].set(t0)

        def step(carry, i):
            tokens, scores, caches, pool_tok, pool_norm, pool_raw, nf = \
                carry
            tok = lax.dynamic_index_in_dim(
                tokens, i, axis=2, keepdims=False)    # (B,K)
            logits, caches = core.token_step(
                p, tok.reshape(B * K), caches, i, B * K)
            nf = nf + jnp.sum((~jnp.isfinite(logits)).astype(jnp.int32))
            logp = jax.nn.log_softmax(
                logits.astype(jnp.float32), axis=-1).reshape(B, K, V)
            total = scores[..., None] + logp          # (B,K,V)
            flat = total.reshape(B, K * V)
            kk = min(2 * K, K * V)
            cs, idx = lax.top_k(flat, kk)             # (B,kk)
            beam_idx = idx // V
            cand_tok = (idx % V).astype(jnp.int32)
            gather = jnp.take_along_axis
            cand_hist = gather(tokens, beam_idx[..., None], axis=1)
            cand_hist = _set_col(cand_hist, i + 1, cand_tok)

            if eos_id is not None:
                is_eos = cand_tok == eos_id
                pool_tok, pool_norm, pool_raw = _pool_merge(
                    pool_tok, pool_norm, pool_raw, cand_hist,
                    jnp.where(is_eos,
                              norm_len(cs, jnp.asarray(i + 2)), NEG),
                    cs, K)
                cs = jnp.where(is_eos, NEG, cs)
            new_scores, pick = lax.top_k(cs, K)       # (B,K)
            keep_beam = gather(beam_idx, pick, axis=1)
            tokens = gather(cand_hist, pick[..., None], axis=1)
            src = (jnp.arange(B)[:, None] * K
                   + keep_beam).reshape(B * K)        # flat rows
            caches = jax.tree.map(lambda a: a[src], caches)
            return (tokens, new_scores, caches,
                    pool_tok, pool_norm, pool_raw, nf), None

        carry = (tokens, alive_scores, caches,
                 pool_tok, pool_norm, pool_raw, nf)
        if max_new > 1:
            carry, _ = lax.scan(step, carry, jnp.arange(max_new - 1))
        tokens, scores, _, pool_tok, pool_norm, pool_raw, nf = carry

        # final selection: best of {pool, alive} by normalized score
        alive_norm = norm_len(scores, jnp.asarray(max_new))
        all_norm = jnp.concatenate([pool_norm, alive_norm], axis=1)
        all_raw = jnp.concatenate([pool_raw, scores], axis=1)
        all_tok = jnp.concatenate([pool_tok, tokens], axis=1)
        best = jnp.argmax(all_norm, axis=1)           # (B,)
        out = jnp.take_along_axis(
            all_tok, best[:, None, None], axis=1)[:, 0]
        best_score = jnp.take_along_axis(
            all_raw, best[:, None], axis=1)[:, 0]
        return jnp.concatenate([prompt, out], axis=1), best_score, nf

    from . import introspect
    jitted = introspect.AotExecutor(
        jax.jit(decode), "serving.beam", names=("params", "prompt"))

    def run(p, prompt):
        import time as _time

        from . import observe, slo
        obs = observe.is_enabled()
        if not obs and slo.get_tracker() is None:
            # no fence, no record: pure dispatch
            ids, score, _nf = jitted(p, prompt)
            return ids, score
        t0 = _time.perf_counter()
        from . import watchdog
        with watchdog.guard("decode", batch=B), \
                observe.span("serving.beam_decode", batch=B, beams=K):
            ids, score, nf = jitted(p, prompt)
            jax.block_until_ready(ids)
        # one fused program: no prefill seam, so no TTFT sample here
        total = _time.perf_counter() - t0
        if obs:
            observe.record_decode("beam", total, new_tokens=B * max_new,
                                  batch=B, prompt_tokens=B * S0)
            from . import health
            health.record_nan_logits(int(jax.device_get(nf)), "beam")
        slo.note_decode("beam", total, B * max_new, batch=B)
        return ids, score

    return run


def poisson_workload(seed, n_req, rps, vocab, prompt_lens, new_lens,
                     new_dist="bimodal"):
    """The seeded Poisson serving workload shared by `slo --ab`,
    `capacity --ab`, `audit --ab` and the router's kill-and-replace harness
    (all three of its arms — clean, kill, and the FaultPlan-delayed
    tail-attribution arm replay the same schedule, which is what makes
    the /tailz and cold-vs-warm comparisons apples-to-apples):
    exponential inter-arrival times at `rps`, uniform prompt lengths in
    `prompt_lens = (lo, hi)`, and output lengths in `new_lens = (lo,
    hi)` — bimodal by default (75% short / 25% long, the mix that keeps
    a continuous-batching engine's slots ragged). Fully determined by
    `seed`: two arms replaying the same workload submit byte-identical
    prompts at identical offsets, which is what makes A/B comparisons
    (and the router's token-identity failover assert) meaningful.

    Returns {"arrivals": float array of cumulative offsets (s),
    "prompts": list of int32 prompt arrays, "new_lens": int array}.
    """
    import numpy as np
    p_lo, p_hi = (int(x) for x in prompt_lens)
    n_lo, n_hi = (int(x) for x in new_lens)
    n_req = int(n_req)
    rng = np.random.RandomState(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / float(rps), n_req))
    prompts = [rng.randint(0, int(vocab),
                           (rng.randint(p_lo, p_hi + 1),)).astype(np.int32)
               for _ in range(n_req)]
    if new_dist == "bimodal":
        short_hi = max(n_lo + 1, n_lo + (n_hi - n_lo) // 4)
        long_lo = max(short_hi, n_hi - (n_hi - n_lo) // 8)
        is_long = rng.rand(n_req) < 0.25
        lens = np.where(is_long,
                        rng.randint(long_lo, n_hi + 1, n_req),
                        rng.randint(n_lo, short_hi + 1, n_req))
    else:
        lens = rng.randint(n_lo, n_hi + 1, n_req)
    return {"arrivals": arrivals, "prompts": prompts, "new_lens": lens}


__all__ = ["build_decode", "build_beam_decode", "build_spec_decode",
           "decode_state", "decode_params", "decode_raw",
           "KV_DTYPES", "SPEC_VERDICTS", "kv_label", "record_spec",
           "poisson_workload"]
