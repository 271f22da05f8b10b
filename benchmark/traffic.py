"""The one traffic generator: a traffic file's `traffic` group in, inputs out.

Everything is a function of (parameters, seed) alone. Every seed gives the
same multiset of sizes in another order, so that no seed changes the amount
of work: lengths are fixed quantiles of their distributions, paired by the
file's `pair_seed`, and `--seed` only shuffles each block of pairs and
draws the token ids.
"""

from statistics import NormalDist

import numpy as np


def token_batches(p, vocab, seed):
    """`pool` batches of (ids, next-token targets), each (batch, seq) int32,
    ids Zipf-distributed over the vocabulary (p(rank) ~ rank^-exponent), so
    the loss has a unigram distribution to learn."""
    rng = np.random.default_rng(seed)
    w = np.arange(1, vocab + 1, dtype=np.float64) ** -p["zipf_exponent"]
    cdf = np.cumsum(w / w.sum())
    n = p["pool"] * p["batch"] * (p["seq"] + 1)
    ids = np.minimum(np.searchsorted(cdf, rng.random(n)), vocab - 1)
    ids = ids.astype(np.int32).reshape(p["pool"], p["batch"], p["seq"] + 1)
    return [(b[:, :-1].copy(), b[:, 1:].copy()) for b in ids]


def lognormal_quantiles(d, n):
    """The n mid-quantiles of a lognormal (median, sigma), rounded and
    clipped to [min, max]."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    return np.clip(np.rint(d["median"] * np.exp(d["sigma"] * z)),
                   d["min"], d["max"]).astype(int)


def request_sizes(p, max_ctx):
    """One block of (prompt length, output length) pairs: the same for
    every seed. Outputs are cut so that prompt + output fits the context."""
    n = p["block"]
    pair = np.random.default_rng(p["pair_seed"]).permutation(n)
    prompts = lognormal_quantiles(p["prompt"], n)
    outputs = lognormal_quantiles(p["output"], n)[pair]
    return [(int(a), int(min(b, max_ctx - a))) for a, b in zip(prompts, outputs)]


def requests(p, vocab, max_ctx, seed):
    """`blocks` blocks of requests, [(prompt ids int32, max_new)], each
    block the same pairs in an order of the seed's, token ids uniform."""
    rng = np.random.default_rng(seed)
    sizes = request_sizes(p, max_ctx)
    out = []
    for _ in range(p["blocks"]):
        for i in rng.permutation(len(sizes)):
            s0, n = sizes[i]
            out.append((rng.integers(0, vocab, s0, dtype=np.int32), n))
    return out


def generate(p, vocab, max_ctx, seed):
    return {"token_batches": lambda: token_batches(p, vocab, seed),
            "requests": lambda: requests(p, vocab, max_ctx, seed)}[p["kind"]]()
