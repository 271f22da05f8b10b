"""Operations and parameters of the looped language model, from shapes, by
the rules of flops.py: what the forward and backward passes require,
recomputation not counted, a causal score matrix counted by its lower half.
`cfg` is the configuration's `create_model` group."""


def block_matmul_params(cfg):
    """One block: four d x d projections and the gated feed-forward's
    three d x f matrices."""
    d, f = cfg["dim"], cfg["ffn_dim"]
    return 4 * d * d + 3 * d * f


def matmul_params_a_token(cfg):
    """Matrix entries that multiply every token in one forward pass of the
    loop: each block once a pass, the head once a pass. The gate's d
    entries a pass and the norms do no matmul."""
    return cfg["ut_steps"] * (cfg["num_layers"] * block_matmul_params(cfg)
                              + cfg["vocab_size"] * cfg["dim"])


def params_held(cfg):
    """Parameters the program holds: the blocks (their matrices and four
    gains each), embedding and untied head, the final gain, the gate."""
    d, V = cfg["dim"], cfg["vocab_size"]
    return cfg["num_layers"] * (block_matmul_params(cfg) + 4 * d) \
        + 2 * V * d + d + d + 1


def train_flops_per_token(cfg, seq):
    """6 FLOPs a matmul entry a token (2 forward, 4 backward) plus causal
    attention in each of the T x L block applications: QK^T and AV are
    4 S d forward for a full score matrix, half of it under the mask, three
    times that with the backward (flops.gpt2_train_flops_per_token's rule)."""
    return 6 * matmul_params_a_token(cfg) \
        + cfg["ut_steps"] * cfg["num_layers"] * 3 * 2 * seq * cfg["dim"]


def parts_per_token(cfg, seq):
    """{"trunk", "attention", "heads"}: train_flops_per_token by part."""
    apps = cfg["ut_steps"] * cfg["num_layers"]
    return {"trunk": 6 * apps * block_matmul_params(cfg),
            "attention": apps * 3 * 2 * seq * cfg["dim"],
            "heads": 6 * cfg["ut_steps"] * cfg["vocab_size"] * cfg["dim"]}
