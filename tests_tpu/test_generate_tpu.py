"""TPU-gated: KV-cache decode compiles and runs on the real chip."""

import numpy as np


def test_generate_on_chip(dev):
    from singa_tpu import models, tensor
    m = models.create_model("gpt", vocab_size=512, max_seq=128, dim=128,
                            num_heads=4, num_layers=2)
    ids = tensor.from_numpy(
        np.random.RandomState(0).randint(0, 512, (2, 16)).astype(np.int32),
        device=dev)
    m.compile([ids], is_train=False, use_graph=False)
    m.eval()
    prompt = np.random.RandomState(1).randint(0, 512, (2, 16))
    for dtype in (None, "bfloat16"):
        out = m.generate(prompt, 24, temperature=0.0, dtype=dtype)
        assert out.shape == (2, 40)
        np.testing.assert_array_equal(out[:, :16], prompt)
        # deterministic greedy: repeat run matches
        np.testing.assert_array_equal(
            out, m.generate(prompt, 24, temperature=0.0, dtype=dtype))
    # beam search compiles and runs on the chip; beam-1 == greedy
    np.testing.assert_array_equal(
        m.generate_beam(prompt, 12, num_beams=1),
        m.generate(prompt, 12, temperature=0.0))
    assert m.generate_beam(prompt, 12, num_beams=4).shape == (2, 28)


def test_gqa_generate_on_chip(dev):
    """GQA decode (grouped packed caches, int8 and bf16) on the real
    chip: deterministic greedy, beam-1 == greedy."""
    from singa_tpu import models, tensor
    m = models.create_model("gpt", vocab_size=512, max_seq=128, dim=256,
                            num_heads=8, num_kv_heads=4, num_layers=2)
    ids = tensor.from_numpy(
        np.random.RandomState(0).randint(0, 512, (2, 16)).astype(np.int32),
        device=dev)
    m.compile([ids], is_train=False, use_graph=False)
    m.eval()
    prompt = np.random.RandomState(1).randint(0, 512, (2, 16))
    for dtype in ("bfloat16", "int8"):
        out = m.generate(prompt, 24, temperature=0.0, dtype=dtype)
        assert out.shape == (2, 40)
        np.testing.assert_array_equal(
            out, m.generate(prompt, 24, temperature=0.0, dtype=dtype))
    np.testing.assert_array_equal(
        m.generate_beam(prompt, 12, num_beams=1),
        m.generate(prompt, 12, temperature=0.0))


def test_long_prompt_prefill_on_chip(dev):
    """A 16k-token prompt prefills and decodes on ONE chip:
    prefill runs the Pallas flash kernel (O(S0) score memory — the
    naive path's per-head (16k,16k) fp32 score matrices would be ~1 GB
    per layer per head-batch and quadratic in time), and the first
    generated token agrees with the model's own full-forward argmax at
    the last prompt position."""
    from singa_tpu import models, tensor
    S0 = 16384
    m = models.create_model("gpt", vocab_size=512, max_seq=S0 + 8,
                            dim=256, num_heads=4, num_layers=2)
    rng = np.random.RandomState(0)
    prompt = rng.randint(0, 512, (1, S0)).astype(np.int32)
    ids = tensor.from_numpy(prompt, device=dev)
    m.compile([ids], is_train=False, use_graph=False)
    m.eval()
    # fp32 decode for exact parity with the fp32 forward path
    out = m.generate(prompt, 8, temperature=0.0)
    assert out.shape == (1, S0 + 8)
    np.testing.assert_array_equal(out[:, :S0], prompt)
    # first decoded token == argmax of the training-path forward's
    # last-position logits
    logits = tensor.to_numpy(m(tensor.from_numpy(prompt, device=dev)))
    assert int(out[0, S0]) == int(np.argmax(logits[0, -1]))
    # bf16 serving dtype also prefills/decodes the 16k prompt
    out_bf = m.generate(prompt, 8, temperature=0.0, dtype="bfloat16")
    assert out_bf.shape == (1, S0 + 8)
