"""Operations, bytes and parameters of the sparse model with sliding and full
attention layers (models/mellum.py), from shapes, by the rules of flops.py:
what the forward and backward passes require, recomputation not counted, a
score matrix counted by the pairs inside its mask, the experts by the rows
really routed to them. `cfg` is the configuration's `create_model` group.
"""

import flops

SLIDING = "sliding_attention"


def attention_params(cfg):
    """One layer's four projections: q and o are heads x head_dim wide,
    k and v kv_heads x head_dim."""
    d, D = cfg["dim"], cfg["head_dim"]
    return d * D * (2 * cfg["num_heads"] + 2 * cfg["num_kv_heads"])


def expert_params(cfg):
    """One expert: gate, up and down."""
    return 3 * cfg["dim"] * cfg["ffn_dim"]


def params_held(cfg):
    """Parameters the program holds: the layers (attention, router, the
    held experts, two gains), embedding and untied head, the final gain."""
    d = cfg["dim"]
    layer = attention_params(cfg) + d * cfg["num_experts"] \
        + cfg["experts_held"] * expert_params(cfg) + 2 * d
    return len(cfg["layer_types"]) * layer + 2 * cfg["vocab_size"] * d + d


def pairs(seq, window=None):
    """(query, key) pairs inside the mask of one head: key at or before the
    query, and under a window within window - 1 of it."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def layer_pairs(cfg, seq):
    """[pairs a head] a layer."""
    return [pairs(seq, cfg["window"] if kind == SLIDING else None)
            for kind in cfg["layer_types"]]


def parts_per_step(cfg, batch, seq, rows):
    """{"projections", "attention", "experts", "head"}: the training step's
    FLOPs by part. 6 a matmul entry a token (2 forward, 4 backward); QK^T
    and PV are 4 x head_dim a pair a head forward, three times that with
    the backward (flops.gpt2_train_flops_per_token's rule); the experts by
    `rows` (layers, held): the rows the step reported for each expert."""
    tokens, d = batch * seq, cfg["dim"]
    L = len(cfg["layer_types"])
    return {
        "projections": 6 * tokens * L * (attention_params(cfg)
                                         + d * cfg["num_experts"]),
        "attention": 3 * 4 * cfg["head_dim"] * cfg["num_heads"] * batch
        * sum(layer_pairs(cfg, seq)),
        "experts": 6 * float(sum(map(sum, rows))) * expert_params(cfg),
        "head": 6 * tokens * cfg["vocab_size"] * d}


def train_flops_per_step(cfg, batch, seq, rows):
    return sum(parts_per_step(cfg, batch, seq, rows).values())


def flash_cost(cfg, batch, seq, window, backward, bytes_per=2):
    """(flops, bytes) of one attention pass over the layer's query heads
    (K and V arrive repeated for them): 4 x head_dim a pair forward, 10 the
    backward (five score-sized products against two); it reads q k v (and
    o, do) and writes o (dq dk dv)."""
    n = batch * cfg["num_heads"]
    ops = (10 if backward else 4) * n * pairs(seq, window) * cfg["head_dim"]
    return ops, (8 if backward else 4) * n * seq * cfg["head_dim"] * bytes_per


def grouped_product_cost(cfg, rows, bytes_per=2):
    """(flops, bytes) of ONE grouped product over a layer's held experts,
    whichever of the twelve a step runs (gate, up, down; forward, the
    recomputed forward, the input's gradient, the weight's): 2 x rows x
    dim x width; it reads or writes the rows on both sides and the held
    experts' matrices once."""
    d, f = cfg["dim"], cfg["ffn_dim"]
    return 2 * rows * d * f, \
        (rows * (d + f) + cfg["experts_held"] * d * f) * bytes_per


def least_seconds(cost, device_kind):
    return flops.roofline_seconds(*cost, device_kind)[0]
