"""KV-cached autoregressive decoding vs the full-forward reference path."""

import numpy as np
import pytest

from singa_tpu import device, models, tensor


@pytest.fixture(scope="module")
def gpt():
    dev = device.best_device()
    m = models.create_model("gpt", vocab_size=97, max_seq=64, dim=64,
                            num_heads=4, num_layers=2)
    ids = tensor.from_numpy(
        np.random.RandomState(0).randint(0, 97, (2, 8)).astype(np.int32),
        device=dev)
    m.compile([ids], is_train=False, use_graph=False)
    m.eval()
    return m, dev


def _naive_greedy(m, dev, prompt, n_new):
    """No cache: rerun the full forward on the growing sequence."""
    ids = prompt.copy()
    for _ in range(n_new):
        t = tensor.from_numpy(ids.astype(np.int32), device=dev)
        logits = tensor.to_numpy(m(t))          # (B, S, V)
        nxt = np.argmax(logits[:, -1], axis=-1).astype(np.int32)
        ids = np.concatenate([ids, nxt[:, None]], axis=1)
    return ids


def test_greedy_matches_full_forward(gpt):
    m, dev = gpt
    prompt = np.random.RandomState(1).randint(0, 97, (2, 8))
    want = _naive_greedy(m, dev, prompt, 6)
    got = m.generate(prompt, 6, temperature=0.0)
    np.testing.assert_array_equal(got, want)


def test_generate_zero_tokens(gpt):
    m, _ = gpt
    prompt = np.random.RandomState(5).randint(0, 97, (2, 4))
    out = m.generate(prompt, 0)
    np.testing.assert_array_equal(out, prompt)


def test_generate_single_token(gpt):
    m, dev = gpt
    prompt = np.random.RandomState(2).randint(0, 97, (1, 5))
    got = m.generate(prompt, 1)
    assert got.shape == (1, 6)
    np.testing.assert_array_equal(got, _naive_greedy(m, dev, prompt, 1))


def test_sampling_modes(gpt):
    m, _ = gpt
    prompt = np.random.RandomState(3).randint(0, 97, (2, 4))
    a = m.generate(prompt, 5, temperature=0.8, top_k=10, seed=0)
    b = m.generate(prompt, 5, temperature=0.8, top_k=10, seed=0)
    c = m.generate(prompt, 5, temperature=0.8, top_k=10, seed=1)
    assert a.shape == (2, 9)
    np.testing.assert_array_equal(a, b)     # same seed -> same draw
    assert (a[:, 4:] >= 0).all() and (a[:, 4:] < 97).all()
    assert c.shape == a.shape               # different seed: valid draw too


def test_bf16_decode(gpt):
    m, _ = gpt
    prompt = np.random.RandomState(4).randint(0, 97, (2, 6))
    a = m.generate(prompt, 4, dtype="bfloat16")
    b = m.generate(prompt, 4, dtype="bfloat16")
    assert a.shape == (2, 10)
    np.testing.assert_array_equal(a, b)  # deterministic greedy
    assert (a[:, 6:] >= 0).all() and (a[:, 6:] < 97).all()


def _seeded_gpt(dim=128, num_heads=4, vocab=97, max_seq=64, layers=2,
                seed=7):
    """GPT with EXPLICITLY seeded weights (independent of the suite-wide
    device RNG stream position, so tests using it are order-stable)."""
    dev = device.best_device()
    m = models.create_model("gpt", vocab_size=vocab, max_seq=max_seq,
                            dim=dim, num_heads=num_heads,
                            num_layers=layers)
    ids = tensor.from_numpy(
        np.random.RandomState(0).randint(0, vocab, (2, 8))
        .astype(np.int32), device=dev)
    m.compile([ids], is_train=False, use_graph=False)
    m.eval()
    rng = np.random.RandomState(seed)
    m.set_params({n: (rng.standard_normal(tuple(t.shape)) * 0.05)
                  .astype(np.float32) for n, t in m.get_params().items()})
    return m, dev


def test_packed_heads_greedy_matches_full_forward():
    """dim=128/H=4 -> D=32, P=4: the head-PACKED KV-cache path (the
    production decode layout — every fixture above has H % P != 0 and
    falls back to P=1). Block-diagonal packed attention must match the
    naive full-forward loop exactly."""
    m, dev = _seeded_gpt(dim=128, num_heads=4)
    from singa_tpu.models.transformer import _decode_core
    assert _decode_core(m, 8, 4).P == 4  # really exercising the packing
    prompt = np.random.RandomState(2).randint(0, 97, (2, 8))
    want = _naive_greedy(m, dev, prompt, 6)
    got = m.generate(prompt, 6, temperature=0.0)
    np.testing.assert_array_equal(got, want)
    # beam reorders packed caches by parent beam; beam-1 == greedy
    np.testing.assert_array_equal(
        m.generate_beam(prompt, 4, num_beams=1),
        m.generate(prompt, 4, temperature=0.0))


def test_int8_decode():
    """Weight-only int8 decode: deterministic, in-vocab, and close to the
    bf16 greedy path (per-output-channel symmetric quantization keeps the
    argmax stable for most steps; agreement is measured on explicitly
    seeded weights so the threshold is order-stable)."""
    m, _ = _seeded_gpt(dim=128, num_heads=4)
    prompt = np.random.RandomState(5).randint(0, 97, (2, 6))
    a = m.generate(prompt, 8, dtype="int8")
    assert a.shape == (2, 14)
    np.testing.assert_array_equal(a, m.generate(prompt, 8, dtype="int8"))
    assert (a[:, 6:] >= 0).all() and (a[:, 6:] < 97).all()
    b = m.generate(prompt, 8, dtype="bfloat16")
    agree = float(np.mean(a[:, 6:] == b[:, 6:]))
    assert agree >= 0.5, \
        f"int8 greedy diverged from bf16 on {1-agree:.0%} of tokens"
    # beam decoding shares the quantized core
    assert m.generate_beam(prompt, 4, num_beams=2,
                           dtype="int8").shape == (2, 10)


def _seeded_gqa(dim, num_heads, num_kv_heads, vocab=97, max_seq=64,
                layers=2, seed=11):
    dev = device.best_device()
    m = models.create_model("gpt", vocab_size=vocab, max_seq=max_seq,
                            dim=dim, num_heads=num_heads,
                            num_layers=layers,
                            num_kv_heads=num_kv_heads)
    ids = tensor.from_numpy(
        np.random.RandomState(0).randint(0, vocab, (2, 8))
        .astype(np.int32), device=dev)
    m.compile([ids], is_train=False, use_graph=False)
    m.eval()
    rng = np.random.RandomState(seed)
    m.set_params({n: (rng.standard_normal(tuple(t.shape)) * 0.05)
                  .astype(np.float32) for n, t in m.get_params().items()})
    return m, dev


def test_gqa_greedy_matches_full_forward():
    """GQA (num_kv_heads < num_heads): the decode core's grouped packed
    attention (G query rows per kv-head block) must match the layer-path
    full forward (which repeats kv heads before flash) exactly — two
    independent implementations of the same math. dim=256/H=8/kv=4 ->
    D=32, P=4, G=2: the packed GQA path, really."""
    m, dev = _seeded_gqa(dim=256, num_heads=8, num_kv_heads=4)
    from singa_tpu.models.transformer import _decode_core
    core = _decode_core(m, 8, 4)
    assert (core.P, core.G, core.Hkv) == (4, 2, 4)
    # kv projections really are half-width (the param saving)
    assert tuple(m.blocks[0].attn.Wk.shape) == (256, 128)
    prompt = np.random.RandomState(6).randint(0, 97, (2, 8))
    want = _naive_greedy(m, dev, prompt, 6)
    got = m.generate(prompt, 6, temperature=0.0)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(
        m.generate_beam(prompt, 4, num_beams=1),
        m.generate(prompt, 4, temperature=0.0))
    # int8/bf16 serving paths run on the GQA cache layout too
    assert m.generate(prompt, 4, dtype="int8").shape == (2, 12)
    assert m.generate(prompt, 4, dtype="bfloat16").shape == (2, 12)


def test_gqa_unpacked_fallback_matches():
    """Hkv=2 with P=4 -> packing falls back to P=1 (kv heads not
    divisible); numerics must still match the full forward."""
    m, dev = _seeded_gqa(dim=256, num_heads=8, num_kv_heads=2, seed=12)
    from singa_tpu.models.transformer import _decode_core
    core = _decode_core(m, 8, 4)
    assert (core.P, core.G) == (1, 4)
    prompt = np.random.RandomState(7).randint(0, 97, (2, 8))
    np.testing.assert_array_equal(
        m.generate(prompt, 6, temperature=0.0),
        _naive_greedy(m, dev, prompt, 6))


def test_decode_param_memo_invalidates_on_weight_load():
    """_decode_state memoizes the fused/quantized decode tree; loading
    new weights must invalidate it (the memo keys on buffer identity)."""
    m, dev = _seeded_gpt(dim=64, num_heads=2)
    prompt = np.random.RandomState(3).randint(0, 97, (1, 4))
    before = m.generate(prompt, 4, temperature=0.0)
    rng = np.random.RandomState(99)
    m.set_params({n: (rng.standard_normal(tuple(t.shape)) * 0.05)
                  .astype(np.float32) for n, t in m.get_params().items()})
    after = m.generate(prompt, 4, temperature=0.0)
    assert not np.array_equal(before, after), \
        "stale decode params served after set_params"
    want = _naive_greedy(m, dev, prompt, 4)
    np.testing.assert_array_equal(after, want)


def test_attn_bias_greedy_matches_full_forward():
    dev = device.best_device()
    m = models.create_model("gpt", vocab_size=53, max_seq=32, dim=32,
                            num_heads=2, num_layers=2, attn_bias=True)
    ids = tensor.from_numpy(np.zeros((1, 6), np.int32), device=dev)
    m.compile([ids], is_train=False, use_graph=False)
    m.eval()
    # non-zero biases so the bias path actually matters
    rng = np.random.RandomState(7)
    for blk in m.blocks:
        for b in (blk.attn.bq, blk.attn.bk, blk.attn.bv, blk.attn.bo):
            b.copy_from_numpy(rng.standard_normal(b.shape[0])
                              .astype(np.float32) * 0.3)
    prompt = rng.randint(0, 53, (1, 6))
    want = _naive_greedy(m, dev, prompt, 5)
    np.testing.assert_array_equal(m.generate(prompt, 5), want)


def test_gpt2_weight_migration():
    """torch GPT-2 state_dict -> native GPT: logits match, serving runs."""
    torch = pytest.importorskip("torch")
    from singa_tpu.models.transformer import load_gpt2_weights
    import importlib.util
    import jax
    import os
    import sys
    # gpt2.py imports examples/onnx/utils.py, which mutates sys.path and
    # jax_default_matmul_precision at import — snapshot and restore so the
    # rest of the suite is unaffected by test ordering
    path_before = list(sys.path)
    prec_before = jax.config.jax_default_matmul_precision
    try:
        spec = importlib.util.spec_from_file_location(
            "gpt2_example",
            os.path.join(os.path.dirname(__file__), "..",
                         "examples", "onnx", "gpt2", "gpt2.py"))
        ex = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(ex)
    finally:
        sys.path[:] = path_before
        sys.modules.pop("utils", None)
        jax.config.update("jax_default_matmul_precision", prec_before)

    tm = ex.build_torch().eval()
    state = {k: v.numpy() for k, v in tm.state_dict().items()}
    dev = device.best_device()
    m = models.create_model("gpt", vocab_size=ex.VOCAB, max_seq=ex.N_CTX,
                            dim=ex.D, num_heads=ex.H, num_layers=ex.L,
                            attn_bias=True)
    ids = tensor.from_numpy(np.zeros((1, 8), np.int32), device=dev)
    m.compile([ids], is_train=False, use_graph=False)
    m.eval()
    load_gpt2_weights(m, state)

    probe = np.random.RandomState(0).randint(0, ex.VOCAB, (1, 12))
    with torch.no_grad():
        want = tm(torch.from_numpy(probe)).numpy()
    got = tensor.to_numpy(m(tensor.from_numpy(probe.astype(np.int32),
                                              device=dev)))
    err = np.abs(got - want).max() / np.abs(want).std()
    assert err < 0.05, f"normalized max err {err}"
    out = m.generate(probe, 4)
    assert out.shape == (1, 16)


def test_generate_before_compile_raises():
    m = models.create_model("gpt", vocab_size=17, max_seq=16, dim=32,
                            num_heads=2, num_layers=1)
    with pytest.raises(RuntimeError, match="compile"):
        m.generate(np.zeros((1, 3), np.int32), 2)


def test_overlong_generation_raises(gpt):
    m, _ = gpt
    with pytest.raises(AssertionError, match="max_seq"):
        m.generate(np.zeros((1, 60), np.int32), 10)


def test_moe_gpt_greedy_matches_full_forward():
    """MoE blocks in the KV-cached decode (previously NotImplementedError):
    the single-token step routes through the dense-dispatch MoE FFN and
    greedy output matches the naive full-forward loop exactly. Generous
    capacity: with drops, routing is batch-global (a token's fate depends
    on the other tokens in the dispatch group), so the cached decode —
    whose groups are single positions — can only equal the full forward
    in the no-drop regime."""
    dev = device.best_device()
    m = models.create_model("gpt", vocab_size=61, max_seq=32, dim=32,
                            num_heads=4, num_layers=2, moe_experts=4,
                            moe_k=2, moe_capacity_factor=4.0)
    ids = tensor.from_numpy(
        np.random.RandomState(3).randint(0, 61, (2, 6)).astype(np.int32),
        device=dev)
    m.compile([ids], is_train=False, use_graph=False)
    m.eval()
    prompt = np.random.RandomState(4).randint(0, 61, (2, 6))
    want = _naive_greedy(m, dev, prompt, 5)
    got = m.generate(prompt, 5, temperature=0.0)
    np.testing.assert_array_equal(got, want)


def test_rope_greedy_matches_full_forward():
    """RoPE (pos_encoding="rope"): decode rotates q/k at the cache
    position while the layer path rotates whole sequences — two
    independent implementations that must agree exactly. Combined with
    GQA to cover the grouped packed layout."""
    dev = device.best_device()
    m = models.create_model("gpt", vocab_size=97, max_seq=64, dim=128,
                            num_heads=4, num_kv_heads=2, num_layers=2,
                            pos_encoding="rope")
    ids = tensor.from_numpy(
        np.random.RandomState(0).randint(0, 97, (2, 8)).astype(np.int32),
        device=dev)
    m.compile([ids], is_train=False, use_graph=False)
    m.eval()
    rng = np.random.RandomState(13)
    m.set_params({n: (rng.standard_normal(tuple(t.shape)) * 0.05)
                  .astype(np.float32) for n, t in m.get_params().items()})
    assert "pos_embed" not in m.get_params()  # no learned table
    prompt = np.random.RandomState(8).randint(0, 97, (2, 8))
    want = _naive_greedy(m, dev, prompt, 6)
    got = m.generate(prompt, 6, temperature=0.0)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(
        m.generate_beam(prompt, 4, num_beams=1),
        m.generate(prompt, 4, temperature=0.0))
    assert m.generate(prompt, 4, dtype="int8").shape == (2, 12)


def test_kv8_decode_tracks_bf16():
    """int8 KV cache (kv_dtype="int8"): per-(head, position) scales keep
    greedy decode close to the bf16-cache path; deterministic; beam
    shares the quantized cache (tree-mapped tiling/reordering)."""
    m, _ = _seeded_gqa(dim=256, num_heads=8, num_kv_heads=4, seed=21)
    prompt = np.random.RandomState(9).randint(0, 97, (2, 6))
    a = m.generate(prompt, 8, dtype="bfloat16", kv_dtype="int8")
    assert a.shape == (2, 14)
    np.testing.assert_array_equal(
        a, m.generate(prompt, 8, dtype="bfloat16", kv_dtype="int8"))
    b = m.generate(prompt, 8, dtype="bfloat16")
    agree = float(np.mean(a[:, 6:] == b[:, 6:]))
    assert agree >= 0.5, \
        f"kv8 greedy diverged from bf16 cache on {1-agree:.0%} of tokens"
    # full quantized serving: int8 weights + int8 KV, plus beam
    c = m.generate(prompt, 6, dtype="int8", kv_dtype="int8")
    assert c.shape == (2, 12)
    assert m.generate_beam(prompt, 4, num_beams=2, dtype="int8",
                           kv_dtype="int8").shape == (2, 10)
    # MHA (P>1, G=1) layout too
    m2, _ = _seeded_gpt(dim=128, num_heads=4, seed=22)
    d = m2.generate(prompt, 8, dtype="bfloat16", kv_dtype="int8")
    e = m2.generate(prompt, 8, dtype="bfloat16")
    assert float(np.mean(d[:, 6:] == e[:, 6:])) >= 0.5


def test_kv8_decode_agrees_on_trained_model():
    """On a TRAINED model the int8-KV greedy decode must
    near-completely agree with the bf16 cache: training gives the logits
    real margins, so per-(head,position) int8 quantization noise (~0.4%
    relative) should almost never flip an argmax. (The untrained-model
    bound above stays loose because near-uniform logits are maximally
    quantization-sensitive.)"""
    from singa_tpu import models, opt, tensor
    from singa_tpu.device import get_default_device

    dev = get_default_device()
    # pin the device RNG: weight init draws from the process-global
    # stream, so without this the trained model's quality (and the
    # agreement below) depends on which tests ran before this one
    dev.SetRandSeed(7)
    # deterministic corpus: next char is a function of the current one
    text = ("the quick brown fox jumps over the lazy dog. " * 40)
    vocab = sorted(set(text))
    stoi = {c: i for i, c in enumerate(vocab)}
    ids = np.array([stoi[c] for c in text], np.int32)
    B, S = 8, 32
    m = models.create_model("gpt", vocab_size=len(vocab), max_seq=64,
                            dim=128, num_heads=4, num_kv_heads=2,
                            num_layers=2)
    m.set_optimizer(opt.Adam(lr=3e-3))
    tx = tensor.Tensor((B, S), device=dev, dtype=tensor.int32)
    ty = tensor.Tensor((B, S), device=dev, dtype=tensor.int32)
    m.compile([tx], is_train=True, use_graph=True)
    rng = np.random.RandomState(0)
    loss0 = loss = None
    for step in range(80):
        starts = rng.randint(0, len(ids) - S - 1, B)
        xb = np.stack([ids[s:s + S] for s in starts])
        yb = np.stack([ids[s + 1:s + S + 1] for s in starts])
        tx.copy_from_numpy(xb)
        ty.copy_from_numpy(yb)
        _, lt = m(tx, ty)
        loss = float(tensor.to_numpy(lt))
        if loss0 is None:
            loss0 = loss
    assert loss < loss0 * 0.5, (loss0, loss)  # it actually trained
    m.eval()
    prompt = np.stack([ids[s:s + 8] for s in (0, 11, 23, 37)])
    a = m.generate(prompt, 24, dtype="bfloat16", kv_dtype="int8")
    b = m.generate(prompt, 24, dtype="bfloat16")
    agree = float(np.mean(a[:, 8:] == b[:, 8:]))
    assert agree >= 0.9, \
        f"trained kv8 decode diverged on {1-agree:.0%} of tokens"
