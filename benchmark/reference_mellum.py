"""A sparse language model with sliding-window and full attention layers
(Mellum2) in plain jax.numpy, float32: forward, the loss, the rows routed to
each held expert and `jax.grad` of the loss. Written from the equations of
ISSUE 32, not by calling the program's models/. No kernel, no sort, no
recomputation, no tape. It takes the program's own parameter dict (name ->
array) so both sides hold the same weights.

    layer l of kind k_l:  h = x + Attn_k(RMS(x; g1));  y = h + MoE(RMS(h; g2))
    RMS(x; g) = x / sqrt(mean(x^2) + eps) * g
    Attn_k(n): q = n Wq (S x Hq x D), k = n Wk, v = n Wv (S x Hkv x D), no
      bias; rotary (rotate half over the whole head) with
      cos, sin = c_k cos(pos f_k), c_k sin(pos f_k):
        sliding  f_i = theta^(-2i/D), c = 1
        full     f_i = (1 - g_i) theta^(-2i/D) / factor + g_i theta^(-2i/D),
                 g_i = 1 - clip((i - lo) / (hi - lo), 0, 1), i = 0..D/2-1,
                 lo = floor(D ln(L0 / (beta_fast 2 pi)) / (2 ln theta)),
                 hi = ceil(D ln(L0 / (beta_slow 2 pi)) / (2 ln theta)),
                 both clipped to [0, D - 1]; c = attention_factor
      query head h reads KV head floor(h / (Hq / Hkv)); scores q_i . k_j /
      sqrt(D) kept where j <= i, on sliding layers also i - j < window;
      softmax; out = concat_h(P v) Wo
    MoE(n): p = softmax(n Wr) over ALL experts; T = top-k(p);
      w_e = p_e / sum_{e' in T} p_e';
      MoE(n) = sum_{e in T and e held} w_e (silu(n Wg_e) * (n Wu_e)) Wd_e
    after the last layer: RMS(.; g_f), head W_h, loss = mean cross-entropy

What the published `config.json` does not say, and this file assumes (the
configuration file's `assumed`): softmax before the top-k; no router bias,
shared expert, QK-norm or auxiliary loss; no MTP head.

With `held` of the experts from `offset` on, MoE is that device's part of
the layer's sum (all held: the published layer). Deliberately wrong models,
which the cell's limits have to tell from the right one, by `wrong=`:
"window_off", "yarn_off", "gates_not_renormalised", "expert_left_out" (the
held expert `cfg_wrong["expert"]` of every layer), "layers_swapped" (the
last sliding layer and the full one after it).

On a TPU an fp32 matmul runs in lower precision unless the precision is
raised, so every function runs under default_matmul_precision("highest").
The score matrix is built for `q_block` query rows and one KV head's group
of query heads at a time, behind `jax.checkpoint`, so S = 8192 fits beside
the program (whole it is 8.6 GB a layer); experts are a dense loop: every
expert sees every token and the gate is zero where it was not chosen.
Nothing here names a dtype: every function computes in the dtype of the
parameters it is given (float32 from the program; bfloat16 for the control
that shows what a limit is worth).
"""

import functools
import math
import re

import jax
import jax.numpy as jnp
import numpy as np

SLIDING, FULL = "sliding_attention", "full_attention"
WRONG = ("window_off", "yarn_off", "gates_not_renormalised",
         "expert_left_out", "layers_swapped")


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def frequencies(cfg, kind):
    """((D/2,) rotary frequencies, the factor on cos and sin) of a layer
    kind, as the docstring's equations give them."""
    D, theta = cfg["head_dim"], float(cfg["rope_theta"])
    i = np.arange(D // 2, dtype=np.float64)
    plain = theta ** (-2.0 * i / D)
    sc = cfg.get("rope_scaling")
    if kind != FULL or not sc:
        return plain, 1.0
    L0 = sc["original_max_position_embeddings"]
    at = lambda beta: D * math.log(L0 / (beta * 2 * math.pi)) \
        / (2 * math.log(theta))
    lo = min(max(math.floor(at(sc["beta_fast"])), 0), D - 1)
    hi = min(max(math.ceil(at(sc["beta_slow"])), 0), D - 1)
    g = 1.0 - np.clip((i - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return (1.0 - g) * plain / sc["factor"] + g * plain, \
        float(sc["attention_factor"])


def rope_tables(cfg, kind, S, dtype=jnp.float32):
    """(cos, sin), each (S, D/2)."""
    f, c = frequencies(cfg, kind)
    ang = np.arange(S, dtype=np.float64)[:, None] * f[None, :]
    return (jnp.asarray(c * np.cos(ang), dtype),
            jnp.asarray(c * np.sin(ang), dtype))


def _rotary(x, cos, sin):
    """x (S, H, D): pair (i, i + D/2) turned by the tables."""
    h = x.shape[-1] // 2
    x1, x2 = x[..., :h], x[..., h:]
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.checkpoint, static_argnums=(3,))
def _attend(q, k, v, q0, window):
    """q (rows, G, D) at positions q0.., k and v (S, D) of one KV head:
    softmax(q k^T / sqrt(D)) v under the mask, (rows, G, D). `window`: a
    number (a layer with none is given one that reaches every key)."""
    rows, S = q.shape[0], k.shape[0]
    s = jnp.einsum("rgd,sd->rgs", q, k) * q.shape[-1] ** -0.5
    i = q0 + jnp.arange(rows)[:, None]
    j = jnp.arange(S)[None, :]
    keep = (j <= i) & (i - j < window)
    s = jnp.where(keep[:, None, :], s, -jnp.inf)
    return jnp.einsum("rgs,sd->rgd", jax.nn.softmax(s, axis=-1), v)


def _attention(n, p, cfg, window, tables, q_block):
    S = n.shape[0]
    Hq, Hkv, D = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    G = Hq // Hkv
    q = _rotary((n @ p["attn.Wq"]).reshape(S, Hq, D), *tables)
    k = _rotary((n @ p["attn.Wk"]).reshape(S, Hkv, D), *tables)
    v = (n @ p["attn.Wv"]).reshape(S, Hkv, D)
    out = []
    for r in range(0, S, q_block):
        out.append(jnp.concatenate([
            _attend(q[r:r + q_block, c * G:(c + 1) * G], k[:, c], v[:, c],
                    r, window) for c in range(Hkv)], axis=1))
    return jnp.concatenate(out).reshape(S, Hq * D) @ p["attn.Wo"]


def route(n, Wr, k, renorm=True):
    """(gates (T, k), experts (T, k)) in fp32 whatever n's dtype."""
    prob = jax.nn.softmax((n @ Wr).astype(jnp.float32), axis=-1)
    top, experts = jax.lax.top_k(prob, k)
    return jnp.where(renorm, top / jnp.sum(top, axis=-1, keepdims=True),
                     top), experts


@jax.checkpoint
def _expert(n, w, wg, wu, wd):
    """One expert on every token, weighted by its gate w (T,) (zero where
    it was not chosen). Behind `jax.checkpoint`: differentiated, a layer
    keeps its input and not sixteen experts' activations."""
    return w[:, None] * ((jax.nn.silu(n @ wg) * (n @ wu)) @ wd)


def _moe(n, p, cfg, renorm, left_out):
    """(this device's part of the layer's sum (T, d), rows routed to each
    held expert (held,)). `left_out`: the held expert a wrong model leaves
    out, -1 for none."""
    held = p["moe.Wg"].shape[0]
    off = cfg.get("expert_offset", 0)
    gates, experts = route(n, p["moe.Wr"], cfg["experts_per_token"], renorm)
    y, rows = jnp.zeros_like(n), []
    for e in range(held):
        chosen = experts == e + off
        rows.append(jnp.sum(chosen))
        w = jnp.sum(jnp.where(chosen & (left_out != e), gates, 0.0),
                    axis=-1).astype(n.dtype)
        y = y + _expert(n, w, p["moe.Wg"][e], p["moe.Wu"][e],
                        p["moe.Wd"][e])
    return y, jnp.stack(rows)


@functools.partial(jax.jit, static_argnames=("cfg", "q_block"))
def _layer(x, p, tables, window, renorm, left_out, cfg, q_block):
    """One sequence x (S, d) through one layer -> (y, rows). What tells
    the layers' kinds and the wrong models apart is data (the tables, the
    window as a number, whether the gates are renormalised, the expert
    left out or -1): one compiled function serves them all."""
    cfg = dict(cfg)
    eps = float(cfg["norm_eps"])
    h = x + _attention(_rms(x, p["ln1.gamma"], eps), p, cfg, window, tables,
                       q_block)
    m, rows = _moe(_rms(h, p["ln2.gamma"], eps), p, cfg, renorm, left_out)
    return h + m, rows


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
    """What a forward needs besides the weights: each layer's kind and
    tables, with a wrong model's departure applied."""

    def __init__(self, cfg, S, dtype, wrong=None, expert=0, q_block=1024):
        assert wrong is None or wrong in WRONG, wrong
        cfg = dict(cfg)
        kinds = list(cfg["layer_types"])
        if wrong == "layers_swapped":
            i = kinds.index(FULL)
            kinds[i - 1], kinds[i] = kinds[i], kinds[i - 1]
        table_cfg = dict(cfg, rope_scaling=None) if wrong == "yarn_off" \
            else cfg
        self.kinds = kinds
        self.tables = [rope_tables(table_cfg, k, S, dtype) for k in kinds]
        # a window that reaches every key is none
        self.windows = [jnp.int32(
            cfg["window"] if k == SLIDING and wrong != "window_off" else S)
            for k in kinds]
        self.renorm = jnp.bool_(wrong != "gates_not_renormalised")
        self.left_out = jnp.int32(
            expert if wrong == "expert_left_out" else -1)
        # what is left of cfg is widths: jit's static key
        self.cfg = tuple(sorted((k, v) for k, v in cfg.items() if k in (
            "num_heads", "num_kv_heads", "head_dim", "experts_per_token",
            "expert_offset", "norm_eps")))
        self.q_block = min(q_block, S)

    def layer(self, l, x, p):
        return _layer(x, p, self.tables[l], self.windows[l], self.renorm,
                      self.left_out, self.cfg, self.q_block)


def hidden(params, ids, cfg, wrong=None, expert=0):
    """(the final norm's output (B, S, d), rows routed (L, held))."""
    ids = jnp.asarray(ids, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = params["tok_embed.W"][ids]
        plan = _Plan(cfg, ids.shape[1], x.dtype, wrong, expert)
        rows = []
        for l, p in enumerate(layer_params(params)):
            ys, rs = zip(*(plan.layer(l, seq, p) for seq in x))
            x = jnp.stack(ys)
            rows.append(sum(rs))
        return _rms(x, params["ln_f.gamma"], float(cfg["norm_eps"])), \
            jnp.stack(rows)


def logits(params, h):
    with jax.default_matmul_precision("highest"):
        return h @ params["head.W"]


def _ce(z, targets):
    lse = jax.nn.logsumexp(z.astype(jnp.float32), axis=-1)
    return lse - jnp.take_along_axis(
        z, targets[..., None], axis=-1)[..., 0].astype(jnp.float32)


def loss_parts(params, ids, targets, cfg, rows=None, wrong=None, expert=0,
               token_block=1024):
    """{"loss", "rows" (L, held): the rows routed to each held expert,
    "sample": the logits at the flat positions `rows`}, the head taken
    `token_block` positions at a time."""
    h, routed = hidden(params, ids, cfg, wrong, expert)
    tgt = jnp.asarray(targets, jnp.int32).reshape(-1)
    n = tgt.shape[0]
    flat = h.reshape(n, -1)
    ce = jnp.concatenate([
        _ce(logits(params, flat[i:i + token_block]), tgt[i:i + token_block])
        for i in range(0, n, token_block)])
    out = {"loss": float(jnp.mean(ce)), "rows": np.asarray(routed)}
    if rows is not None:
        out["sample"] = logits(params, flat[jnp.asarray(rows)])
    return out


def loss(params, ids, targets, cfg, wrong=None, expert=0):
    """The loss as one differentiable function of `params`, the logits
    whole: small sizes."""
    with jax.default_matmul_precision("highest"):
        h, _ = hidden(params, ids, cfg, wrong, expert)
        return jnp.mean(_ce(logits(params, h),
                            jnp.asarray(targets, jnp.int32)))


grad = jax.grad(loss)


@jax.jit
def _head_vjp(h, w, targets, dce):
    """(dh, dW) of the head and cross-entropy of one token block."""
    return jax.vjp(lambda h, w: _ce(h @ w, targets), h, w)[1](dce)


def grads(params, ids, targets, cfg, token_block=1024):
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
            x = jnp.stack([plan.layer(l, seq, p)[0] for seq in x])
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
                dx, dp = jax.vjp(lambda a, b: plan.layer(l, a, b)[0], seq,
                                 layers[l])[1](dseq)
                back.append(dx)
                for k, v in dp.items():
                    g[names[l] + k] = g.get(names[l] + k, 0) + np.asarray(v)
                del dp
            dh = jnp.stack(back)
        g["tok_embed.W"] = np.asarray(
            jnp.zeros_like(params["tok_embed.W"]).at[ids].add(dh))
    return g
