"""The training steps of the six language models, pinned at a tiny size on
the CPU under bf16 `amp` and Adam: the lowered text of the step, the scope
paths its instructions carry (what benchmark/scopes.py and its siblings
read from a trace), and the parameters and states a seed makes (names,
shapes and initial values). A change to the models' code that means to
leave their programs as they are leaves these digests as they are; a change
that alters a program on purpose writes its new digest here and says so.
"""

import functools
import hashlib
import re

import numpy as np
import pytest

from singa_tpu import data, device, models, opt, tensor

LIN, FULL = "linear_attention", "full_attention"

# (sha256 of `lower_step().as_text()`, the model's arguments)
LOWERED = {
    "gpt": (
        "9aefea607561e1c33c23f57ed036ff1527b0d11a30d8b37bf5739202990f1a5a",
        dict(vocab_size=96, max_seq=64, dim=64, num_heads=4, num_layers=2)),
    "lfm2": (
        "1fa0ff567f4647b97cb4c8c0d5cb323dee9fbf74ec46af6409cd065dfa02d96a",
        dict(vocab_size=96, dim=64, num_heads=4, num_kv_heads=2, head_dim=16,
             layer_types=["conv", "full_attention", "conv"], conv_taps=3,
             num_dense_layers=1, dense_ffn_dim=160, ffn_dim=48,
             num_experts=8, experts_per_token=4, experts_held=4,
             expert_offset=2, sample=16, recompute=2)),
    "looplm": (
        "6150168a8aa10b3c2b19ff520f4693760ca291ab53279742a7a452fcaffc89f6",
        dict(vocab_size=96, dim=64, num_heads=4, num_layers=2, ffn_dim=96,
             ut_steps=2, sample=16, recompute=True)),
    "mellum": (
        "8b9b9a3fa202bcf2688d4421f2fb70ef3c838d7c9f51717f62950072c64b454f",
        dict(vocab_size=96, dim=64, num_heads=4, num_kv_heads=2, head_dim=32,
             layer_types=["sliding_attention", "full_attention"],
             window=32, ffn_dim=48, num_experts=8, experts_per_token=4,
             experts_held=4, expert_offset=2, sample=16, recompute=1)),
    "olmo_hybrid": (
        "f3d59e383091ed06b42e9d65b0731dfd501487a41b4af223f3f0bff0c3d7d8a9",
        dict(vocab_size=96, dim=64, num_heads=4, head_dim=16,
             layer_types=[LIN, LIN, FULL], ffn_dim=96, linear_heads=2,
             linear_key_dim=8, linear_value_dim=16, chunk=8, sample=16,
             recompute=2)),
    "sdar": (
        "b99fb5fd7223c7bdf0bcc5d4dc40ef7ca901c05cff1d0a15fe194cc671495c6c",
        dict(vocab_size=96, dim=64, num_heads=4, num_kv_heads=2, head_dim=16,
             num_layers=2, ffn_dim=32, num_experts=8, experts_per_token=2,
             experts_held=4, expert_offset=2, block_length=4, sample=16,
             recompute=1)),
}

# sha256 of the sorted set of `jit(step)/...` name stacks in the lowered
# text's debug locations: the scope paths, free of file and line
SCOPES = {
    "gpt":
        "c9566f4d96e56f993d44ceb4c19d1aae27455463fad17ba406a3e166c383608c",
    "lfm2":
        "dfd566d1657559ab737a67bea9436bbc8df3ec93c2ed4b253f65089bd830de01",
    "looplm":
        "dfcf706fd447069d46a5be9e58a2c42bc71dcd02cf5b346d311dbda31a05f847",
    "mellum":
        "b98ad113c8400edf9fde91fc19cfe685573c5086c2425ec0276efa7a0b14a79d",
    "olmo_hybrid":
        "723683e7cf3f222eccdadeaed7cfc6fb2d8e6a7197d0ecb811e8f713059af91c",
    "sdar":
        "045fc3abd720fdb5c59e52f2b653cd1eb5b29b2db5d2ce581c04b9a7dd2ace90",
}

# sha256 of the `get_params()` and `get_states()` names and shapes and of
# the states' initial values at seed 3
STATE = {
    "gpt":
        "d115991d1ced5f6fbb29afc4d04c800824eb8da06baa71d63d1d5cea916fd1d7",
    "lfm2":
        "29f5344a7cde13fd8fe991087720b83cef099b01982ebf5c8292d6b2e724c05e",
    "looplm":
        "23f64883d63fdbdf7fdfa7d0d16fb9075030dc41e8df3540caba9fd09a52f34d",
    "mellum":
        "c80f28160b306f683ec07ce7ba145bd862a044d75e2b2ba7f0451b6f99d9d171",
    "olmo_hybrid":
        "e845e7e14378a5b440e5c3fb1e550a4596b3488e99ded666b16299b5790df962",
    "sdar":
        "469533bec145638e482e5b66dcc6d70a5a87a66105ec036d84ae15c472aecd45",
}


def _sha(s):
    return hashlib.sha256(s.encode()).hexdigest()


def _state_digest(m):
    h = hashlib.sha256()
    for k, v in m.get_params().items():
        h.update(f"param {k} {tuple(v.shape)}\n".encode())
    for k, v in m.get_states().items():
        a = np.ascontiguousarray(tensor.to_numpy(v))
        h.update(f"state {k} {a.shape} {a.dtype}\n".encode())
        h.update(a.tobytes())
    return h.hexdigest()


@functools.lru_cache(maxsize=None)
def _fingerprints(name):
    """(text digest, scopes digest, state digest) of one model's step."""
    kw = LOWERED[name][1]
    dev = device.get_default_device()
    dev.SetRandSeed(3)
    m = models.create_model(name, **kw)
    m.set_optimizer(opt.Adam(lr=1e-3))
    rng = np.random.default_rng(0)
    if name == "sdar":
        ids = rng.integers(0, 95, (2, 64)).astype(np.int32)
        masked, weight, _ = data.block_diffusion_noise(
            rng, ids.shape, kw["block_length"])
        args = [tensor.from_numpy(a, device=dev)
                for a in (ids, masked, weight)]
        init = tensor.from_numpy(np.zeros((1, 128), np.int32), device=dev)
    else:
        ids = rng.integers(0, 96, (2, 65)).astype(np.int32)
        args = [tensor.from_numpy(a, device=dev)
                for a in (ids[:, :-1].copy(), ids[:, 1:].copy())]
        init = args[0]
    m.compile([init], is_train=True, use_graph=True, amp="bfloat16")
    state = _state_digest(m)
    m(*args)
    low = m.lower_step()
    scopes = sorted(set(re.findall(r'loc\("(jit\(step\)/[^"]*)"',
                                   low.as_text(debug_info=True))))
    assert scopes, name
    return _sha(low.as_text()), _sha("\n".join(scopes)), state


@pytest.mark.parametrize("name", sorted(LOWERED))
def test_other_models_lower_to_the_text_they_lowered_to(name):
    assert _fingerprints(name)[0] == LOWERED[name][0]


@pytest.mark.parametrize("name", sorted(SCOPES))
def test_step_keeps_its_scope_paths(name):
    assert _fingerprints(name)[1] == SCOPES[name]


@pytest.mark.parametrize("name", sorted(STATE))
def test_seed_makes_the_same_parameters_and_states(name):
    assert _fingerprints(name)[2] == STATE[name]
