"""The chunked gated delta rule as Pallas kernels (ops/gated_delta.py,
`singa_delta_fwd` / `singa_delta_bwd`, in interpret mode here) against the
XLA form they replace (`_rule_xla`) and the token recurrence
(benchmark/reference_olmo_hybrid.delta_rule): o and the gradient of every
input, where the sequence is and is not a multiple of the chunk, beta in
(0, 2) and in (1, 2) (negative eigenvalues), one chunk and several; the
state left as it is where alpha = 1 and beta = 0, across a chunk's edge;
keys that all point one way (the solve's hardest case that a layer can
make); the plan and the dispatch counter. tests/test_olmo_hybrid.py runs
the kernels inside the model (a rebuilt block, the scopes);
tests/test_tpu_compile.py compiles them for a described v5e at the cell's
shape.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from singa_tpu import observe
from singa_tpu.ops import gated_delta as G

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "benchmark_reference_olmo_hybrid",
    os.path.join(ROOT, "benchmark", "reference_olmo_hybrid.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)


def _args(S, H=2, dk=16, dv=32, beta=(0.0, 2.0), seed=0, B=2):
    rng = np.random.default_rng(seed)
    mk = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    q = G.l2_normalize(mk(B, S, H, dk)) * dk ** -0.5
    k = G.l2_normalize(mk(B, S, H, dk))
    g = -jnp.asarray(rng.uniform(0, 1.5, (B, S, H)), jnp.float32)
    b = jnp.asarray(rng.uniform(*beta, (B, S, H)), jnp.float32)
    return q, k, mk(B, S, H, dv), g, b


def _rel(a, b):
    return float(np.max(np.abs(np.asarray(a) - np.asarray(b)))
                 / (np.max(np.abs(np.asarray(b))) + 1e-30))


def _xla(monkeypatch, chunk):
    """The XLA form at `chunk`, whatever the plan says."""
    def rule(*a):
        with monkeypatch.context() as m:
            m.setattr(G, "delta_rule_plan", lambda *_: None)
            return G.chunked_delta_rule(*a, chunk=chunk)
    return rule


def _token_loop(q, k, v, g, beta):
    return jnp.stack([ref.delta_rule(*(a[i] for a in (q, k, v, g, beta)))
                      for i in range(q.shape[0])])


def _value_and_grads(rule, args, w):
    o, vjp = jax.vjp(rule, *args)
    return (o, *vjp(w))


def _dispatch():
    c = observe.get_registry().get("singa_delta_rule_dispatch_total")
    return {p: int(c.value(path=p)) if c is not None else 0
            for p in observe.DELTA_RULE_PATHS}


@pytest.mark.parametrize("S,chunk", [(40, 8), (48, 16), (64, 64)],
                         ids=["tail", "multiple", "one_chunk"])
@pytest.mark.parametrize("beta", [(0.0, 2.0), (1.0, 2.0)],
                         ids=["beta0to2", "beta1to2"])
def test_kernels_match_the_xla_form_and_the_token_recurrence(
        S, chunk, beta, monkeypatch):
    """o, dq, dk, dv, dg and dbeta: against the XLA form (the same sums in
    another order, the solve by doubling for substitution) and against the
    token recurrence, all at fp32's full precision."""
    args = _args(S, beta=beta)
    w = jnp.asarray(np.random.default_rng(1).standard_normal(
        (2, S, 2, 32)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = _value_and_grads(
            lambda *a: G.chunked_delta_rule(*a, chunk=chunk), args, w)
        xla = _value_and_grads(_xla(monkeypatch, chunk), args, w)
        loop = _value_and_grads(_token_loop, args, w)
    for name, a, b, c in zip(("o", "dq", "dk", "dv", "dg", "dbeta"),
                             got, xla, loop):
        assert a.shape == b.shape and a.dtype == jnp.float32, name
        assert _rel(a, b) < (2e-5 if name == "o" else 5e-5), name
        assert _rel(a, c) < (2e-5 if name == "o" else 5e-5), name
        assert bool(jnp.any(a != 0)), name


def test_keys_that_point_one_way_solve_as_the_xla_form_does(monkeypatch):
    """Every key of a head the same and beta near 2 with no decay: A has
    every entry below the diagonal near 2, whose powers grow as 2^m
    binomials; the inverse by doubling multiplies inverses, never powers,
    and reads as the substitution does."""
    q, k, v, g, b = _args(32)
    k = jnp.broadcast_to(k[:, :1], k.shape)
    g, b = jnp.zeros_like(g), jnp.full_like(b, 1.99)
    w = jnp.ones((2, 32, 2, 32), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = _value_and_grads(
            lambda *a: G.chunked_delta_rule(*a, chunk=16), (q, k, v, g, b), w)
        want = _value_and_grads(_xla(monkeypatch, 16), (q, k, v, g, b), w)
    for a, c in zip(got, want):
        assert bool(jnp.all(jnp.isfinite(a)))
        assert _rel(a, c) < 1e-4


def test_alpha_one_and_beta_zero_leave_the_state_across_a_chunk_edge():
    """Tokens 5-12 write nothing and decay nothing (g = 0, beta = 0): the
    state entering the second chunk of 8 (token 8) is the state token 4
    left, and each output there reads it, q_t S_4."""
    q, k, v, g, b = _args(24, H=3, dk=8, dv=16)
    g, b = g.at[:, 5:13].set(0.0), b.at[:, 5:13].set(0.0)
    o = G.chunked_delta_rule(q, k, v, g, b, chunk=8)
    state = np.zeros((2, 3, 16, 8))
    qn, kn, vn, gn, bn = (np.asarray(a, np.float64) for a in (q, k, v, g, b))
    for t in range(5):
        Sk = np.einsum("bhvk,bhk->bhv", state, kn[:, t])
        state = np.exp(gn[:, t])[..., None, None] * (
            state - bn[:, t, :, None, None] * Sk[..., None]
            * kn[:, t, :, None, :]) \
            + bn[:, t, :, None, None] * vn[:, t, ..., None] \
            * kn[:, t, :, None, :]
    for t in range(5, 13):
        np.testing.assert_allclose(
            np.asarray(o[:, t]), np.einsum("bhvk,bhk->bhv", state, qn[:, t]),
            rtol=2e-5, atol=2e-6)
    # the kernel's own record of the state entering each chunk (dk x dv)
    chunked = lambda a: jnp.moveaxis(a.reshape(2, 3, 8, 3), 3, 1).reshape(
        6, 3, 8)
    gb = jnp.stack([jnp.cumsum(chunked(g), -1), chunked(b)], -2)
    _, hs = G._fwd_pallas(q, k, v, gb, G.DeltaTiles(heads=3, tile=1), True)
    np.testing.assert_allclose(np.asarray(hs[:, 1]),
                               np.swapaxes(state, -1, -2).reshape(6, 8, 16),
                               rtol=2e-5, atol=2e-6)
    assert not np.asarray(hs[:, 0]).any()


def test_plan_takes_the_shapes_that_tile_and_only_those():
    """The cell's 30 heads (96 / 192, chunks of 64) go two a tile (128
    rows), six an iteration; heads that a tile does not divide go one a
    tile; a chunk not a power of two of 8 rows or more, a width not of 8
    rows, or a chunk's blocks past the budget take the XLA form."""
    T = G.DeltaTiles
    assert G.delta_rule_plan(30, 64, 96, 192) == T(heads=6, tile=2)
    assert G.delta_rule_plan(7, 64, 96, 192) == T(heads=1, tile=1)
    assert G.delta_rule_plan(8, 128, 96, 192) == T(heads=4, tile=1)
    assert G.delta_rule_plan(32, 8, 16, 32) == T(heads=16, tile=16)
    for heads, chunk, dk, dv in ((4, 4, 16, 32), (4, 24, 16, 32),
                                 (4, 8, 12, 32), (4, 8, 16, 20),
                                 (30, 64, 1024, 4096), (256, 64, 96, 192),
                                 (30, 128, 96, 192)):
        assert G.delta_rule_plan(heads, chunk, dk, dv) is None, (
            heads, chunk, dk, dv)


@pytest.mark.parametrize("tile", [1, 2, 4])
def test_heads_stacked_in_a_tile_solve_apart(tile, monkeypatch):
    """Chunks of 8 with 8, 16 or 32 rows a tile: 1, 2 or 4 of the 4 heads'
    chunks stacked as the diagonal blocks of one problem, two tiles an
    iteration where they fit, give each head's own o and gradients, as the
    XLA form does."""
    monkeypatch.setattr(G, "_TILE", 8 * tile)
    monkeypatch.setattr(G, "_HEADS", 2 * tile)
    plan = G.delta_rule_plan(4, 8, 16, 32)
    assert plan.tile == tile and plan.heads == min(4, 2 * tile)
    args = _args(20, H=4)
    w = jnp.asarray(np.random.default_rng(2).standard_normal(
        (2, 20, 4, 32)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = _value_and_grads(lambda *a: G.chunked_delta_rule(*a, chunk=8),
                               args, w)
        want = _value_and_grads(_xla(monkeypatch, 8), args, w)
    for a, b in zip(got, want):
        assert _rel(a, b) < 5e-5


@pytest.mark.parametrize("chunk,dk,path", [(8, 16, "interpret"),
                                           (4, 16, "xla"), (8, 12, "xla")])
def test_dispatch_counts_the_path_taken(chunk, dk, path, monkeypatch):
    """Where the chunk or a width does not tile (the model tests' chunks of
    4) the XLA form runs, unchanged; where they do, the kernels, in
    interpret mode off a TPU; the counter says which, once a traced call."""
    args = _args(20, dk=dk)
    before = _dispatch()
    o = jax.jit(lambda *a: G.chunked_delta_rule(*a, chunk=chunk))(*args)
    after = _dispatch()
    assert {p: after[p] - before[p] for p in after} == {
        p: int(p == path) for p in observe.DELTA_RULE_PATHS}
    with jax.default_matmul_precision("highest"):
        assert _rel(o, _xla(monkeypatch, chunk)(*args)) < 2e-5
