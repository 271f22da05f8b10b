"""Peaks, and the operations and bytes the algorithm needs, from shapes.

Nothing here asks the compiler what it executed: a model's FLOPs are what
the forward and backward passes require (recomputation does not count, a
causal score matrix counts its lower half), and a kernel's bytes are the
tensors it must read and write once.
"""

# Google Cloud documentation, "TPU v5e" system architecture page: one chip,
# 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s. Keyed by jax's device_kind.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9},
}


def peak(device_kind, what):
    """A device kind that is not in the table is an error, not a default."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add a row "
                       "to benchmark/flops.py PEAKS with its source")
    return PEAKS[device_kind][what]


def gpt2_matmul_params(cfg):
    """Parameters that multiply every token: the blocks' projections and
    MLPs (12 d^2 a layer) and the output head (V d). Embedding lookups,
    biases and norms do no matmul."""
    d, L, V = cfg["dim"], cfg["num_layers"], cfg["vocab_size"]
    r = cfg.get("mlp_ratio", 4)
    return L * (4 + 2 * r) * d * d + V * d


def gpt2_params_held(cfg):
    """Parameters the program holds for this configuration on one chip
    (untied head, attention biases)."""
    d, L, V, S = cfg["dim"], cfg["num_layers"], cfg["vocab_size"], cfg["max_seq"]
    r = cfg.get("mlp_ratio", 4)
    per_layer = (4 + 2 * r) * d * d + (4 + r + 1) * d + 4 * d
    return 2 * V * d + S * d + L * per_layer + 2 * d


def gpt2_train_flops_per_token(cfg, seq):
    """6 FLOPs a matmul parameter (2 forward, 4 backward) plus causal
    attention: QK^T and AV are 4 S d forward for a full score matrix, half
    of it under the causal mask, three times that with the backward."""
    return 6 * gpt2_matmul_params(cfg) \
        + cfg["num_layers"] * 3 * 2 * seq * cfg["dim"]


def flash_fwd_cost(b, h, s, d, causal=True, bytes_per=2):
    """(flops, bytes) of one attention forward over (b, h, s, d)."""
    flops = 4 * b * h * s * s * d * (0.5 if causal else 1.0)
    return flops, 4 * b * h * s * d * bytes_per


def flash_bwd_cost(b, h, s, d, causal=True, bytes_per=2):
    """(flops, bytes) of the backward: five score-sized matmuls against the
    forward's two; reads q k v o do, writes dq dk dv."""
    flops = 10 * b * h * s * s * d * (0.5 if causal else 1.0)
    return flops, 8 * b * h * s * d * bytes_per


def roofline_seconds(flops, nbytes, device_kind):
    """The least time the chip could take, and which peak bounds it."""
    tf = flops / peak(device_kind, "bf16_flops")
    tb = nbytes / peak(device_kind, "hbm_bytes_s")
    return (tf, "compute") if tf >= tb else (tb, "memory")
