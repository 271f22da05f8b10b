"""A sparse language model trained by diffusion over blocks (SDAR) in plain
jax.numpy, float32: the forward over the doubled sequence, the weighted loss
on the noised half, the rows routed to each held expert and `jax.grad` of
the loss. Written from the equations of ISSUE 34, not by calling the
program's models/. No kernel, no sort, no recomputation, no tape. It takes
the program's own parameter dict (name -> array) so both sides hold the same
weights, and the same ids, mask and weights a position.

    layer:  h = x + Attn(RMS(x; g1));  y = h + MoE(RMS(h; g2))
    RMS(x; g) = x / sqrt(mean(x^2) + eps) * g
    Attn(n): q = n Wq (2S x Hq x D), k = n Wk, v = n Wv (2S x Hkv x D), no
      bias; q = RMS(q; gq), k = RMS(k; gk) over each head's D; rotary
      (rotate half over the whole head, f_i = theta^(-2i/D)) at position
      p(i) = i mod S; query head h reads KV head floor(h / (Hq / Hkv));
      scores q_i . k_j / sqrt(D) kept where M(i, j); softmax; out =
      concat_h(P v) Wo
    M over [noised (0..S-1) ; clean (S..2S-1)], blk(i) = (i mod S) // b:
      noised i sees noised j iff blk(j) == blk(i), clean j iff blk(j) < blk(i)
      clean  i sees clean  j iff blk(j) <= blk(i), and no noised j
    MoE(n): p = softmax(n Wr) over ALL experts; T = top-k(p);
      w_e = p_e / sum_{e' in T} p_e';
      MoE(n) = sum_{e in T and e held} w_e (silu(n Wg_e) * (n Wu_e)) Wd_e
    input = [where(masked, MASK, x0) ; x0], MASK = vocabulary held - 1;
    after the last layer: RMS(.; g_f) of the noised half, head W_h,
    loss = sum_i weight_i CE(logits_i, x0_i) / (B S), weight = masked / t

What the published `config.json` does not say, and this file assumes (the
configuration file's `assumed`): the block length, the schedule and its
weight (given to this file as `masked` and `weight`), no shift, the QK
norms, softmax before the top-k, the `[MASK]` row.

With `held` of the experts from `offset` on, MoE is that device's part of
the layer's sum (all held: the published layer). Deliberately wrong models,
which the cell's limits have to tell from the right one, by `wrong=`:
"block_leak" (a noised query also sees the clean keys of its OWN block: the
answer leaks), "causal_mask" (the plain causal mask over the 2S positions),
"positions_run_on" (p(i) = i over 0..2S-1), "qk_norm_off",
"weight_off" (the loss weighs a masked position 1, not 1/t),
"expert_left_out" (the held expert `expert` of every layer).

On a TPU an fp32 matmul runs in lower precision unless the precision is
raised, so every function runs under default_matmul_precision("highest").
The score matrix is built for `q_block` query rows and one KV head's group
of query heads at a time, behind `jax.checkpoint`, so 2S = 8192 fits beside
the program; experts are a dense loop: every held expert sees every row and
the gate is zero where it was not chosen. Nothing here names a dtype: every
function computes in the dtype of the parameters it is given (float32 from
the program; bfloat16 for the control that shows what a limit is worth).
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np

WRONG = ("block_leak", "causal_mask", "positions_run_on", "qk_norm_off",
         "weight_off", "expert_left_out")
# the mask as a number, so that one compiled layer serves every model
_MASKS = {None: 0, "block_leak": 1, "causal_mask": 2}


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def visible(i, j, half, block, mask=0):
    """Whether query i sees key j (arrays that broadcast) in the doubled
    sequence of 2 x `half` positions in blocks of `block`; `mask` 0: the
    block-diffusion mask, 1: with the leak, 2: plain causal."""
    qn, kn = i < half, j < half
    qb, kb = (i % half) // block, (j % half) // block
    from_noised = jnp.where(kn, kb == qb,
                            jnp.where(mask == 1, kb <= qb, kb < qb))
    inside = jnp.where(qn, from_noised, ~kn & (kb <= qb))
    return jnp.where(mask == 2, j <= i, inside)


def rope_tables(cfg, positions, dtype=jnp.float32):
    """(cos, sin), each (len(positions), D/2)."""
    D, theta = cfg["head_dim"], float(cfg["rope_theta"])
    f = theta ** (-2.0 * np.arange(D // 2, dtype=np.float64) / D)
    ang = np.asarray(positions, np.float64)[:, None] * f[None, :]
    return jnp.asarray(np.cos(ang), dtype), jnp.asarray(np.sin(ang), dtype)


def _rotary(x, cos, sin):
    """x (S, H, D): pair (i, i + D/2) turned by the tables."""
    h = x.shape[-1] // 2
    x1, x2 = x[..., :h], x[..., h:]
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.checkpoint, static_argnums=(5,))
def _attend(q, k, v, q0, mask, block):
    """q (rows, G, D) at positions q0.., k and v (2S, D) of one KV head:
    softmax(q k^T / sqrt(D)) v under the mask, (rows, G, D)."""
    rows, S2 = q.shape[0], k.shape[0]
    s = jnp.einsum("rgd,sd->rgs", q, k) * q.shape[-1] ** -0.5
    keep = visible(q0 + jnp.arange(rows)[:, None], jnp.arange(S2)[None, :],
                   S2 // 2, block, mask)
    s = jnp.where(keep[:, None, :], s, -jnp.inf)
    return jnp.einsum("rgs,sd->rgd", jax.nn.softmax(s, axis=-1), v)


def _attention(n, p, cfg, tables, mask, qk_norm, q_block):
    S2 = n.shape[0]
    Hq, Hkv, D = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    G, eps = Hq // Hkv, float(cfg["norm_eps"])
    q = (n @ p["attn.Wq"]).reshape(S2, Hq, D)
    k = (n @ p["attn.Wk"]).reshape(S2, Hkv, D)
    q = jnp.where(qk_norm, _rms(q, p["attn.q_norm.gamma"], eps), q)
    k = jnp.where(qk_norm, _rms(k, p["attn.k_norm.gamma"], eps), k)
    q, k = _rotary(q, *tables), _rotary(k, *tables)
    v = (n @ p["attn.Wv"]).reshape(S2, Hkv, D)
    out = []
    for r in range(0, S2, q_block):
        out.append(jnp.concatenate([
            _attend(q[r:r + q_block, c * G:(c + 1) * G], k[:, c], v[:, c],
                    r, mask, cfg["block_length"]) for c in range(Hkv)],
            axis=1))
    return jnp.concatenate(out).reshape(S2, Hq * D) @ p["attn.Wo"]


def route(n, Wr, k):
    """(gates (T, k) renormalised, experts (T, k)) in fp32 whatever n's
    dtype."""
    prob = jax.nn.softmax((n @ Wr).astype(jnp.float32), axis=-1)
    top, experts = jax.lax.top_k(prob, k)
    return top / jnp.sum(top, axis=-1, keepdims=True), experts


@jax.checkpoint
def _expert(n, w, wg, wu, wd):
    """One expert on every row, weighted by its gate w (T,) (zero where it
    was not chosen). Behind `jax.checkpoint`: differentiated, a layer keeps
    its input and not sixteen experts' activations."""
    return w[:, None] * ((jax.nn.silu(n @ wg) * (n @ wu)) @ wd)


def _moe(n, p, cfg, left_out):
    """(this device's part of the layer's sum (T, d), rows routed to each
    held expert (held,)). `left_out`: the held expert a wrong model leaves
    out, -1 for none."""
    held = p["moe.Wg"].shape[0]
    off = cfg.get("expert_offset", 0)
    gates, experts = route(n, p["moe.Wr"], cfg["experts_per_token"])
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
def _layer(x, p, tables, mask, qk_norm, left_out, cfg, q_block):
    """One doubled sequence x (2S, d) through one layer -> (y, rows). What
    tells the wrong models apart is data (the tables, the mask as a number,
    whether q and k are normed, the expert left out or -1): one compiled
    function serves them all."""
    cfg = dict(cfg)
    eps = float(cfg["norm_eps"])
    h = x + _attention(_rms(x, p["ln1.gamma"], eps), p, cfg, tables, mask,
                       qk_norm, q_block)
    m, rows = _moe(_rms(h, p["ln2.gamma"], eps), p, cfg, left_out)
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
    """What a forward needs besides the weights, with a wrong model's
    departure applied."""

    def __init__(self, cfg, S, dtype, wrong=None, expert=0, q_block=1024):
        assert wrong is None or wrong in WRONG, wrong
        cfg = dict(cfg)
        pos = np.arange(2 * S)
        self.tables = rope_tables(
            cfg, pos if wrong == "positions_run_on" else pos % S, dtype)
        self.mask = jnp.int32(_MASKS.get(wrong, 0))
        self.qk_norm = jnp.bool_(wrong != "qk_norm_off")
        self.left_out = jnp.int32(
            expert if wrong == "expert_left_out" else -1)
        # what is left of cfg is widths: jit's static key
        self.cfg = tuple(sorted((k, v) for k, v in cfg.items() if k in (
            "num_heads", "num_kv_heads", "head_dim", "experts_per_token",
            "expert_offset", "norm_eps", "block_length")))
        self.q_block = min(q_block, 2 * S)

    def layer(self, x, p):
        return _layer(x, p, self.tables, self.mask, self.qk_norm,
                      self.left_out, self.cfg, self.q_block)


def doubled(ids, masked, cfg):
    """[where(masked, MASK, ids) ; ids] (B, 2S)."""
    ids = jnp.asarray(ids, jnp.int32)
    xt = jnp.where(jnp.asarray(masked) != 0, cfg["vocab_size"] - 1, ids)
    return jnp.concatenate([xt, ids], axis=1)


def hidden(params, ids, masked, cfg, wrong=None, expert=0):
    """(the final norm's output on the noised half (B, S, d), rows routed
    (L, held))."""
    S = np.shape(ids)[1]
    with jax.default_matmul_precision("highest"):
        x = params["tok_embed.W"][doubled(ids, masked, cfg)]
        plan = _Plan(cfg, S, x.dtype, wrong, expert)
        rows = []
        for p in layer_params(params):
            ys, rs = zip(*(plan.layer(seq, p) for seq in x))
            x = jnp.stack(ys)
            rows.append(sum(rs))
        return _rms(x[:, :S], params["ln_f.gamma"],
                    float(cfg["norm_eps"])), jnp.stack(rows)


def logits(params, h):
    with jax.default_matmul_precision("highest"):
        return h @ params["head.W"]


def _ce(z, targets):
    lse = jax.nn.logsumexp(z.astype(jnp.float32), axis=-1)
    return lse - jnp.take_along_axis(
        z, targets[..., None], axis=-1)[..., 0].astype(jnp.float32)


def _weights(masked, weight, wrong):
    """The loss's weight a position, flat, fp32."""
    w = masked if wrong == "weight_off" else weight
    return jnp.asarray(w, jnp.float32).reshape(-1)


def loss_parts(params, ids, masked, weight, cfg, rows=None, wrong=None,
               expert=0, token_block=1024):
    """{"loss", "rows" (L, held): the rows routed to each held expert,
    "sample": the logits at the flat positions `rows` of the noised half},
    the head taken `token_block` positions at a time."""
    h, routed = hidden(params, ids, masked, cfg, wrong, expert)
    tgt = jnp.asarray(ids, jnp.int32).reshape(-1)
    w = _weights(masked, weight, wrong)
    n = tgt.shape[0]
    flat = h.reshape(n, -1)
    ce = jnp.concatenate([
        _ce(logits(params, flat[i:i + token_block]), tgt[i:i + token_block])
        for i in range(0, n, token_block)])
    out = {"loss": float(jnp.sum(ce * w) / n), "rows": np.asarray(routed)}
    if rows is not None:
        out["sample"] = logits(params, flat[jnp.asarray(rows)])
    return out


def loss(params, ids, masked, weight, cfg, wrong=None, expert=0):
    """The loss as one differentiable function of `params`, the logits
    whole: small sizes."""
    with jax.default_matmul_precision("highest"):
        h, _ = hidden(params, ids, masked, cfg, wrong, expert)
        ce = _ce(logits(params, h), jnp.asarray(ids, jnp.int32))
        return jnp.sum(ce.reshape(-1) * _weights(masked, weight, wrong)) \
            / ce.size


grad = jax.grad(loss)


@jax.jit
def _head_vjp(h, w, targets, dce):
    """(dh, dW) of the head and cross-entropy of one token block."""
    return jax.vjp(lambda h, w: _ce(h @ w, targets), h, w)[1](dce)


def grads(params, ids, masked, weight, cfg, token_block=1024):
    """{name: d loss / d parameter}. `grad` for the sizes at which the
    whole graph does not fit: the forward keeps every layer's input, the
    way back takes one layer of one sequence and one token block of the
    head at a time, and a layer's gradients go to the HOST as they are
    made (numpy arrays: beside the program's parameters and Adam's state
    the device has no room for a second copy of them)."""
    ids2 = doubled(ids, masked, cfg)
    tgt = jnp.asarray(ids, jnp.int32).reshape(-1)
    S = np.shape(ids)[1]
    n, eps = tgt.shape[0], float(cfg["norm_eps"])
    layers = layer_params(params)
    names = [f"TransformerBlock_{i}." for i in sorted(
        {int(m.group(1)) for m in (re.match(r"TransformerBlock_(\d+)\.", k)
                                   for k in params) if m})]
    with jax.default_matmul_precision("highest"):
        x = params["tok_embed.W"][ids2]
        plan = _Plan(cfg, S, x.dtype)
        xs = []
        for p in layers:
            xs.append(x)
            x = jnp.stack([plan.layer(seq, p)[0] for seq in x])
        flat = _rms(x[:, :S], params["ln_f.gamma"], eps).reshape(n, -1)
        g = {"head.W": jnp.zeros_like(params["head.W"])}
        dce = _weights(masked, weight, None) / n
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
            lambda x, gamma: _rms(x[:, :S], gamma, eps), x,
            params["ln_f.gamma"])[1](
                jnp.concatenate(parts).reshape(x.shape[0], S, -1))
        g["ln_f.gamma"] = np.asarray(g["ln_f.gamma"])
        del flat, parts, x
        for l in reversed(range(len(layers))):
            back = []
            for seq, dseq in zip(xs.pop(), dh):
                dx, dp = jax.vjp(lambda a, b: plan.layer(a, b)[0], seq,
                                 layers[l])[1](dseq)
                back.append(dx)
                for k, v in dp.items():
                    g[names[l] + k] = g.get(names[l] + k, 0) + np.asarray(v)
                del dp
            dh = jnp.stack(back)
        g["tok_embed.W"] = np.asarray(
            jnp.zeros_like(params["tok_embed.W"]).at[ids2].add(dh))
    return g
