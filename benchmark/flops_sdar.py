"""Operations, bytes and parameters of the block-diffusion model
(models/sdar.py), from shapes, by the rules of flops.py: what the forward
and backward passes require, recomputation not counted, a score matrix
counted by the pairs inside its mask, the experts by the rows really routed
to them. `cfg` is the configuration's `create_model` group; `seq` is S, the
data tokens of a sequence: the step feeds 2 S rows through every block and
S through the head.
"""

from flops_mellum import (attention_params, expert_params,  # noqa: F401
                          least_seconds)
# (one layer's four projections; one expert's gate, up and down; the least
# time for a (flops, bytes) cost: the same keys of `cfg`, the same rules)


def params_held(cfg):
    """Parameters the program holds: the layers (attention with the two
    gains on q and k, router, the held experts, two gains), embedding and
    untied head, the final gain."""
    d = cfg["dim"]
    layer = attention_params(cfg) + 2 * cfg["head_dim"] \
        + d * cfg["num_experts"] \
        + cfg["experts_held"] * expert_params(cfg) + 2 * d
    return cfg["num_layers"] * layer + 2 * cfg["vocab_size"] * d + d


def pairs_inside(seq, block):
    """(query, key) pairs of one head inside the block-diffusion mask over
    [noised ; clean] of 2 x seq positions in blocks of `block`: a noised
    query sees its block (seq x block in all) and the clean keys of the
    blocks before it (seq (seq - block) / 2), a clean query the clean keys
    of its block and before (seq (seq + block) / 2): seq^2 + seq x block of
    the square's 4 seq^2."""
    assert seq % block == 0, (seq, block)
    return seq * seq + seq * block


def parts_per_step(cfg, batch, seq, rows):
    """{"projections", "attention", "experts", "head"}: the training step's
    FLOPs by part. 6 a matmul entry a row (2 forward, 4 backward):
    projections and router on the 2 x seq rows of the doubled input, the
    head on the seq rows of the noised half; QK^T and PV are 4 x head_dim a
    pair a head forward, three times that with the backward; the experts by
    `rows` (layers, held): the rows the step reported for each expert."""
    tokens, d = batch * seq, cfg["dim"]
    L = cfg["num_layers"]
    return {
        "projections": 6 * 2 * tokens * L * (attention_params(cfg)
                                             + d * cfg["num_experts"]),
        "attention": 3 * 4 * cfg["head_dim"] * cfg["num_heads"] * batch * L
        * pairs_inside(seq, cfg["block_length"]),
        "experts": 6 * float(sum(map(sum, rows))) * expert_params(cfg),
        "head": 6 * tokens * cfg["vocab_size"] * d}


def train_flops_per_step(cfg, batch, seq, rows):
    return sum(parts_per_step(cfg, batch, seq, rows).values())


def flash_cost(cfg, batch, seq, backward, bytes_per=2):
    """(flops, bytes) of one `_bd` attention pass over the layer's query
    heads (K and V arrive repeated for them) on the doubled sequence: 4 x
    head_dim a pair inside the mask forward, 10 the backward (five
    score-sized products against two); it reads q k v (and o, do) and
    writes o (dq dk dv), each 2 x seq rows."""
    n = batch * cfg["num_heads"]
    ops = (10 if backward else 4) * n * cfg["head_dim"] \
        * pairs_inside(seq, cfg["block_length"])
    return ops, (8 if backward else 4) * n * 2 * seq * cfg["head_dim"] \
        * bytes_per
