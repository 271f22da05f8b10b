"""Operations, bytes and parameters of the sparse model of gated short
convolutions and attention layers (models/lfm2.py), from shapes, by the
rules of flops.py: what the forward and backward passes require,
recomputation not counted, the causal score matrix by the pairs inside its
mask, the experts by the rows really routed to them, the elementwise chain
of a convolution by its multiplies and adds. `cfg` is the configuration's
`create_model` group.
"""

from flops_mellum import attention_params, expert_params, pairs
# (one layer's four attention projections; one expert's gate, up and down;
# the causal pairs of a head: the same keys of `cfg`, the same rules)

CONV = "conv"


def conv_params(cfg):
    """One convolution operator: W_in (d, 3d), W_out (d, d) and a filter
    of `conv_taps` taps a channel."""
    d = cfg["dim"]
    return 4 * d * d + cfg["conv_taps"] * d


def dense_ffn_params(cfg):
    """A leading layer's gated feed-forward: gate, up and down."""
    return 3 * cfg["dim"] * cfg["dense_ffn_dim"]


def layers(cfg):
    """(conv layers, attention layers, dense layers, sparse layers)."""
    L = len(cfg["layer_types"])
    n_conv = list(cfg["layer_types"]).count(CONV)
    return n_conv, L - n_conv, cfg["num_dense_layers"], \
        L - cfg["num_dense_layers"]


def params_held(cfg):
    """Parameters the program holds: each layer's operator (an attention
    layer's with the two gains on q and k) and two gains, a dense layer's
    feed-forward or a sparse layer's router and held experts, embedding
    and untied head, the final gain. The selection bias is a state, not a
    parameter: 32 numbers a sparse layer, not counted."""
    d = cfg["dim"]
    n_conv, n_attn, n_dense, n_sparse = layers(cfg)
    return n_conv * conv_params(cfg) \
        + n_attn * (attention_params(cfg) + 2 * cfg["head_dim"]) \
        + n_dense * dense_ffn_params(cfg) \
        + n_sparse * (d * cfg["num_experts"]
                      + cfg["experts_held"] * expert_params(cfg)) \
        + (n_conv + n_attn) * 2 * d + 2 * cfg["vocab_size"] * d + d


def parts_per_step(cfg, batch, seq, rows):
    """{"conv_projections", "conv_mix", "attention_projections",
    "attention", "dense_ffn", "router", "experts", "head"}: the training
    step's FLOPs by part. 6 a matmul entry a token (2 forward, 4
    backward); QK^T and PV are 4 x head_dim a pair a head forward, three
    times that with the backward; the chain between a convolution's
    products is B * u, `taps` multiply-adds and C *: 2 + 2 x taps a
    channel a token forward, three times that with the backward; the
    experts by `rows` (layers, held): the rows the step reported for each
    expert (a dense layer's row is zeros)."""
    tokens, d = batch * seq, cfg["dim"]
    n_conv, n_attn, n_dense, n_sparse = layers(cfg)
    return {
        "conv_projections": 6 * tokens * n_conv * 4 * d * d,
        "conv_mix": 3 * tokens * n_conv * d * (2 + 2 * cfg["conv_taps"]),
        "attention_projections": 6 * tokens * n_attn * attention_params(cfg),
        "attention": 3 * 4 * cfg["head_dim"] * cfg["num_heads"] * batch
        * n_attn * pairs(seq),
        "dense_ffn": 6 * tokens * n_dense * dense_ffn_params(cfg),
        "router": 6 * tokens * n_sparse * d * cfg["num_experts"],
        "experts": 6 * float(sum(map(sum, rows))) * expert_params(cfg),
        "head": 6 * tokens * cfg["vocab_size"] * d}


def train_flops_per_step(cfg, batch, seq, rows):
    return sum(parts_per_step(cfg, batch, seq, rows).values())


def mix_cost(cfg, batch, seq, backward, bytes_per=2):
    """(flops, bytes) of one pass of the chain between a convolution's
    products over (batch, seq, dim): forward it reads B, C and u and
    writes the result once; backward it reads the cotangent and the three
    and writes their three gradients. No kernel is held to it yet (XLA's
    fusions implement the chain, and a fusion is counted at its root): the
    floor a kernel for the chain would be measured against."""
    n = batch * seq * cfg["dim"]
    ops = (2 if backward else 1) * n * (2 + 2 * cfg["conv_taps"])
    return ops, (7 if backward else 4) * n * bytes_per
