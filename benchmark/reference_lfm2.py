"""A sparse language model of gated short convolutions and attention layers
(LFM2, `model_type: "lfm2_moe"`) in plain jax.numpy, float32: forward, the
loss, the rows routed to each held expert, the load of ALL experts, the
selection bias after the step and `jax.grad` of the loss. Written from the
equations of ISSUE 38, not by calling the program's models/. No kernel, no
sort, no recomputation, no tape. It takes the program's own parameter dict
(name -> array) and the bias (L, E) so both sides hold the same numbers.

    layer l:  h = x + Op_l(RMS(x; g1));  y = h + FFN_l(RMS(h; g2))
    RMS(x; g) = x / sqrt(mean(x^2) + eps) * g
    Op = conv (`layer_types[l] == "conv"`):
      (B, C, u) = split3(n W_in), W_in (d, 3d), no bias, in that order
      z = B * u
      c_t = sum_{j=0..L-1} w[:, j] * z_{t-(L-1)+j}, z = 0 before the sequence
      Op = (C * c) W_out
    Op = full_attention: q = n Wq (S x Hq x D), k = n Wk, v = n Wv (S x Hkv
      x D), no bias; RMS(.; gq) on each head of q and RMS(.; gk) of k (same
      eps); rotary (rotate half over the whole head, f_i = theta^(-2i/D));
      query head h reads KV head floor(h / (Hq / Hkv)); scores q_i . k_j /
      sqrt(D) kept where j <= i; softmax; out = concat_h(P v) Wo
    FFN, l < num_dense_layers:  (silu(n W1) * (n W3)) W2
    FFN, the others: s = sigmoid(n Wr) over ALL experts, fp32
      T = top-k of (s + b_l)          b_l (E,): a buffer, no gradient
      w_e = s_e / (sum_{e' in T} s_e' + 1e-6) * routed_scaling_factor
      FFN = sum_{e in T and e held} w_e (silu(n Wg_e) * (n Wu_e)) Wd_e
    after the last layer: RMS(.; g_f), head W_h, loss = mean cross-entropy
    after the step: b_l,e += rate * sign(mean_e(load_l) - load_l,e),
      load_l,e = the (token, choice) pairs layer l sent to expert e, ALL e

What the published `config.json` does not say, and this file assumes (the
configuration file's `assumed`): the QK norms and the 1e-6 (the family's
modelling code), the bias's rate and rule (arXiv:2408.15664), an untied
head, no shared expert, no auxiliary loss.

With `held` of the experts from `offset` on, FFN is that device's part of
the layer's sum (all held: the published layer). Deliberately wrong models,
which the cell's limits have to tell from the right one, by `wrong=`
(`WRONG`): "taps_anticausal" (c_t reads z_{t+(L-1)-j}), "c_gate_off" (Op =
c W_out), "split_order" (the three streams read as (B, u, C): z = B * C,
gated by u), "bias_not_in_selection", "bias_in_gates" (the gates are the
chosen s + b), "softmax_scores" (softmax in the sigmoid's place),
"gates_not_renormalised", "expert_left_out" (the held expert `expert` of
every sparse layer), "layers_swapped" (the attention layer and the conv
layer after it, applied in the other order).

On a TPU an fp32 matmul runs in lower precision unless the precision is
raised, so every function runs under default_matmul_precision("highest").
The score matrix is built for `q_block` query rows at a time, behind
`jax.checkpoint`, so S = 16,384 fits beside the program; experts are a dense
loop: every expert sees every token and the gate is zero where it was not
chosen. Both loops are `lax.map` / `lax.scan`: one body for the compiler.
Nothing here names a dtype: every function computes in the dtype of the
parameters it is given (float32 from the program; bfloat16 for the control
that shows what a limit is worth).
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np

CONV, FULL = "conv", "full_attention"
WRONG = ("taps_anticausal", "c_gate_off", "split_order",
         "bias_not_in_selection", "bias_in_gates", "softmax_scores",
         "gates_not_renormalised", "expert_left_out", "layers_swapped")
GATE_EPS = 1e-6
# what tells a wrong model from the right one inside a layer, as numbers:
# one compiled layer serves them all
_FLAGS = ("taps_anticausal", "c_gate_off", "split_order",
          "bias_not_in_selection", "bias_in_gates", "softmax_scores",
          "gates_not_renormalised")


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rope_tables(cfg, S, dtype=jnp.float32):
    """(cos, sin), each (S, D/2)."""
    D, theta = cfg["head_dim"], float(cfg["rope_theta"])
    f = theta ** (-2.0 * np.arange(D // 2, dtype=np.float64) / D)
    ang = np.arange(S, dtype=np.float64)[:, None] * f[None, :]
    return jnp.asarray(np.cos(ang), dtype), jnp.asarray(np.sin(ang), dtype)


def _rotary(x, cos, sin):
    """x (S, H, D): pair (i, i + D/2) turned by the tables."""
    h = x.shape[-1] // 2
    x1, x2 = x[..., :h], x[..., h:]
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@jax.checkpoint
def _attend(q, k, v, q0):
    """q (rows, Hkv, G, D) at positions q0.., k and v (S, Hkv, D): causal
    softmax(q k^T / sqrt(D)) v for every KV head's group of query heads,
    (rows, Hkv, G, D)."""
    rows, S = q.shape[0], k.shape[0]
    s = jnp.einsum("rhgd,shd->rhgs", q, k) * q.shape[-1] ** -0.5
    keep = jnp.arange(S)[None, :] <= q0 + jnp.arange(rows)[:, None]
    s = jnp.where(keep[:, None, None, :], s, -jnp.inf)
    return jnp.einsum("rhgs,shd->rhgd", jax.nn.softmax(s, axis=-1), v)


def _attention(n, p, cfg, tables, q_block):
    """The query rows `q_block` at a time, as a `lax.map` over the blocks:
    one body to compile however long the sequence (unrolled, the 16,384
    positions' 128 calls took the chip's compiler 270 s a run)."""
    S = n.shape[0]
    Hq, Hkv, D = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    G, eps = Hq // Hkv, float(cfg["norm_eps"])
    assert S % q_block == 0, (S, q_block)
    q = _rms((n @ p["attn.Wq"]).reshape(S, Hq, D), p["attn.q_norm.gamma"],
             eps)
    k = _rms((n @ p["attn.Wk"]).reshape(S, Hkv, D), p["attn.k_norm.gamma"],
             eps)
    q, k = _rotary(q, *tables), _rotary(k, *tables)
    v = (n @ p["attn.Wv"]).reshape(S, Hkv, D)
    # query head h reads KV head h // G
    blocks = q.reshape(S // q_block, q_block, Hkv, G, D)
    out = jax.lax.map(lambda a: _attend(a[0], k, v, a[1]),
                      (blocks, jnp.arange(0, S, q_block)))
    return out.reshape(S, Hq * D) @ p["attn.Wo"]


def taps(z, w, anticausal=False):
    """z (S, d), w (d, L) -> c (S, d): c_t = sum_j w[:, j] z_{t-(L-1)+j}
    with zeros before the sequence (`anticausal`: z_{t+(L-1)-j}, zeros
    after it: the wrong model)."""
    L, S = w.shape[1], z.shape[0]
    zero = jnp.zeros((L - 1, z.shape[1]), z.dtype)
    if anticausal:
        zp = jnp.concatenate([z, zero])
        return sum(w[:, j] * zp[L - 1 - j:L - 1 - j + S] for j in range(L))
    zp = jnp.concatenate([zero, z])
    return sum(w[:, j] * zp[j:j + S] for j in range(L))


def _conv(n, p, flags):
    B, C, u = jnp.split(n @ p["conv.W_in"], 3, axis=-1)
    C, u = (jnp.where(flags["split_order"], u, C),
            jnp.where(flags["split_order"], C, u))
    z, w = B * u, p["conv.w"]
    c = jnp.where(flags["taps_anticausal"], taps(z, w, True), taps(z, w))
    return jnp.where(flags["c_gate_off"], c, C * c) @ p["conv.W_out"]


def route(n, Wr, b, k, scale, flags):
    """(gates (T, k), experts (T, k)) in fp32 whatever n's dtype."""
    logits = (n @ Wr).astype(jnp.float32)
    s = jnp.where(flags["softmax_scores"], jax.nn.softmax(logits, axis=-1),
                  jax.nn.sigmoid(logits))
    biased = s + b.astype(jnp.float32)
    experts = jax.lax.top_k(
        jnp.where(flags["bias_not_in_selection"], s, biased), k)[1]
    top = jnp.take_along_axis(
        jnp.where(flags["bias_in_gates"], biased, s), experts, axis=-1)
    norm = top / (jnp.sum(top, axis=-1, keepdims=True) + GATE_EPS)
    return jnp.where(flags["gates_not_renormalised"], top, norm) * scale, \
        experts


@jax.checkpoint
def _expert(n, w, wg, wu, wd):
    """One expert on every token, weighted by its gate w (T,) (zero where
    it was not chosen). Behind `jax.checkpoint`: differentiated, a layer
    keeps its input and not every expert's activations."""
    return w[:, None] * ((jax.nn.silu(n @ wg) * (n @ wu)) @ wd)


def _moe(n, p, b, cfg, flags, left_out):
    """(this device's part of the layer's sum (T, d), rows routed to each
    held expert (held,), pairs sent to each of ALL experts (E,)).
    `left_out`: the held expert a wrong model leaves out, -1 for none."""
    held, E = p["moe.Wg"].shape[0], p["moe.Wr"].shape[1]
    off = cfg.get("expert_offset", 0)
    gates, experts = route(n, p["moe.Wr"], b, cfg["experts_per_token"],
                           float(cfg.get("routed_scaling_factor", 1.0)),
                           flags)
    load = jnp.sum(experts[..., None] == jnp.arange(E), axis=(0, 1))
    # (held, T): each held expert's gate a token, zero where not chosen
    e = jnp.arange(held)[:, None, None]
    w = jnp.sum(jnp.where((experts[None] == e + off) & (left_out != e),
                          gates[None], 0.0), axis=-1).astype(n.dtype)

    def add(y, xs):     # one body for all the experts, as above
        return y + _expert(n, *xs), None

    y, _ = jax.lax.scan(add, jnp.zeros_like(n), (
        w, p["moe.Wg"], p["moe.Wu"], p["moe.Wd"]))
    return y, load[off:off + held], load


@functools.partial(jax.jit, static_argnames=("cfg", "q_block"))
def _layer(x, p, b, tables, flags, left_out, cfg, q_block):
    """One sequence x (S, d) through one layer -> (y, rows (held,), load
    (E,)); a dense layer's counts are zeros. Which operator and which
    feed-forward the layer has is read off its parameters' names."""
    cfg = dict(cfg)
    eps = float(cfg["norm_eps"])
    n = _rms(x, p["ln1.gamma"], eps)
    h = x + (_conv(n, p, flags) if "conv.W_in" in p
             else _attention(n, p, cfg, tables, q_block))
    n = _rms(h, p["ln2.gamma"], eps)
    if "moe.Wr" in p:
        m, rows, load = _moe(n, p, b, cfg, flags, left_out)
    else:
        m = (jax.nn.silu(n @ p["fc_gate.W"]) * (n @ p["fc1.W"])) @ p["fc2.W"]
        rows = jnp.zeros((cfg["experts_held"],), jnp.int32)
        load = jnp.zeros((cfg["num_experts"],), jnp.int32)
    return h + m, rows, load


def layer_params(params):
    """[{short name: array}] a layer, in depth order, from the program's
    flat names (`TransformerBlock_<i>.<short name>`)."""
    layers = {}
    for name, a in params.items():
        m = re.match(r"TransformerBlock_(\d+)\.(.+)$", name)
        if m:
            layers.setdefault(int(m.group(1)), {})[m.group(2)] = a
    return [layers[i] for i in sorted(layers)]


class _Plan:
    """What a forward needs besides the weights: the order the layers are
    applied in and the flags, with a wrong model's departure applied."""

    def __init__(self, cfg, S, dtype, wrong=None, expert=0, q_block=256):
        assert wrong is None or wrong in WRONG, wrong
        kinds = list(cfg["layer_types"])
        self.order = list(range(len(kinds)))
        if wrong == "layers_swapped":
            i = kinds.index(FULL)
            assert kinds[i + 1] == CONV, kinds
            self.order[i], self.order[i + 1] = i + 1, i
        self.tables = rope_tables(cfg, S, dtype)
        self.flags = {f: jnp.bool_(wrong == f) for f in _FLAGS}
        self.left_out = jnp.int32(
            expert if wrong == "expert_left_out" else -1)
        # what is left of cfg is widths: jit's static key
        held = cfg.get("experts_held") or cfg["num_experts"]
        self.cfg = tuple(sorted(
            {**{k: cfg[k] for k in (
                "num_heads", "num_kv_heads", "head_dim", "num_experts",
                "experts_per_token", "norm_eps")},
             "experts_held": held,
             "expert_offset": cfg.get("expert_offset", 0),
             "routed_scaling_factor":
                 cfg.get("routed_scaling_factor", 1.0)}.items()))
        self.q_block = min(q_block, S)

    def layer(self, x, p, b):
        return _layer(x, p, b, self.tables, self.flags, self.left_out,
                      self.cfg, self.q_block)


def hidden(params, bias, ids, cfg, wrong=None, expert=0):
    """(the final norm's output (B, S, d), rows routed (L, held), load
    (L, E)), the counts by the layer's own index whatever the order."""
    ids = jnp.asarray(ids, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = params["tok_embed.W"][ids]
        plan = _Plan(cfg, ids.shape[1], x.dtype, wrong, expert)
        layers = layer_params(params)
        rows, load = [None] * len(layers), [None] * len(layers)
        for l in plan.order:
            ys, rs, ls = zip(*(plan.layer(seq, layers[l], bias[l])
                               for seq in x))
            x = jnp.stack(ys)
            rows[l], load[l] = sum(rs), sum(ls)
        return _rms(x, params["ln_f.gamma"], float(cfg["norm_eps"])), \
            jnp.stack(rows), jnp.stack(load)


def logits(params, h):
    with jax.default_matmul_precision("highest"):
        return h @ params["head.W"]


def _ce(z, targets):
    lse = jax.nn.logsumexp(z.astype(jnp.float32), axis=-1)
    return lse - jnp.take_along_axis(
        z, targets[..., None], axis=-1)[..., 0].astype(jnp.float32)


def bias_after(bias, load, cfg):
    """The selection bias (L, E) after the step that counted `load`
    (L, E): each sparse layer's entries moved by the rate towards the
    idle experts; a dense layer's row stays."""
    bias, load = np.asarray(bias, np.float32), np.asarray(load, np.float64)
    out = bias.copy()
    if cfg.get("use_expert_bias", True):
        d = cfg["num_dense_layers"]
        out[d:] += np.float32(cfg["bias_update_rate"]) * np.sign(
            load[d:].mean(-1, keepdims=True) - load[d:]).astype(np.float32)
    return out


def loss_parts(params, bias, ids, targets, cfg, rows=None, wrong=None,
               expert=0, token_block=1024):
    """{"loss", "rows" (L, held): the rows routed to each held expert,
    "load" (L, E): the pairs sent to each of all experts, "bias": the bias
    after the step, "sample": the logits at the flat positions `rows`},
    the head taken `token_block` positions at a time."""
    h, routed, load = hidden(params, bias, ids, cfg, wrong, expert)
    tgt = jnp.asarray(targets, jnp.int32).reshape(-1)
    n = tgt.shape[0]
    flat = h.reshape(n, -1)
    ce = jnp.concatenate([
        _ce(logits(params, flat[i:i + token_block]), tgt[i:i + token_block])
        for i in range(0, n, token_block)])
    out = {"loss": float(jnp.mean(ce)), "rows": np.asarray(routed),
           "load": np.asarray(load),
           "bias": bias_after(bias, load, cfg)}
    if rows is not None:
        out["sample"] = logits(params, flat[jnp.asarray(rows)])
    return out


def loss(params, bias, ids, targets, cfg, wrong=None, expert=0):
    """The loss as one differentiable function of `params`, the logits
    whole: small sizes."""
    with jax.default_matmul_precision("highest"):
        h = hidden(params, bias, ids, cfg, wrong, expert)[0]
        return jnp.mean(_ce(logits(params, h),
                            jnp.asarray(targets, jnp.int32)))


grad = jax.grad(loss)


@jax.jit
def _head_vjp(h, w, targets, dce):
    """(dh, dW) of the head and cross-entropy of one token block."""
    return jax.vjp(lambda h, w: _ce(h @ w, targets), h, w)[1](dce)


def grads(params, bias, ids, targets, cfg, token_block=1024):
    """{name: d loss / d parameter}. `grad` for the sizes at which the
    whole graph does not fit: the forward keeps every layer's input, the
    way back takes one layer of one sequence and one token block of the
    head at a time, and a layer's gradients go to the HOST as they are
    made (numpy arrays: beside the program's parameters and Adam's state
    the device has no room for a second copy of them)."""
    ids = jnp.asarray(ids, jnp.int32)
    tgt = jnp.asarray(targets, jnp.int32).reshape(-1)
    n, eps = tgt.shape[0], float(cfg["norm_eps"])
    layers = layer_params(params)
    names = [f"TransformerBlock_{i}." for i in sorted(
        {int(m.group(1)) for m in (re.match(r"TransformerBlock_(\d+)\.", k)
                                   for k in params) if m})]
    with jax.default_matmul_precision("highest"):
        x = params["tok_embed.W"][ids]
        plan = _Plan(cfg, ids.shape[1], x.dtype)
        xs = []
        for l, p in enumerate(layers):
            xs.append(x)
            x = jnp.stack([plan.layer(seq, p, bias[l])[0] for seq in x])
        flat = _rms(x, params["ln_f.gamma"], eps).reshape(n, -1)
        g = {"head.W": jnp.zeros_like(params["head.W"])}
        dce = jnp.full((n,), 1.0 / n, jnp.float32)
        parts = []
        for i in range(0, n, token_block):
            d, dw = _head_vjp(flat[i:i + token_block], params["head.W"],
                              tgt[i:i + token_block], dce[i:i + token_block])
            parts.append(d)
            g["head.W"] = g["head.W"] + dw
            # one call in flight: the device's memory is the program's
            g["head.W"].block_until_ready()
        g["head.W"] = np.asarray(g["head.W"])
        dh, g["ln_f.gamma"] = jax.vjp(
            lambda x, gamma: _rms(x, gamma, eps), x,
            params["ln_f.gamma"])[1](jnp.concatenate(parts).reshape(x.shape))
        g["ln_f.gamma"] = np.asarray(g["ln_f.gamma"])
        del flat, parts, x
        for l in reversed(range(len(layers))):
            back = []
            for seq, dseq in zip(xs.pop(), dh):
                dx, dp = jax.vjp(
                    lambda a, b: plan.layer(a, b, bias[l])[0], seq,
                    layers[l])[1](dseq)
                back.append(dx)
                for k, v in dp.items():
                    g[names[l] + k] = g.get(names[l] + k, 0) + np.asarray(v)
                del dp
            dh = jnp.stack(back)
        g["tok_embed.W"] = np.asarray(
            jnp.zeros_like(params["tok_embed.W"]).at[ids].add(dh))
    return g
