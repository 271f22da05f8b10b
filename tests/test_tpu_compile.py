"""The main path's Pallas kernels, and the head and loss of the training
cells, compiled for a described TPU v5e.

Interpret mode (how every other CPU test runs these kernels) cannot see a
tile that is not aligned, a kernel that wants more fast memory than it may
use, or a Mosaic lowering that jax dropped. The chip's compiler is
installed here and compiles for a chip that is described, not attached, so
these cases hand it the kernels at the shapes `chip_smoke.py` runs —
GPT-2-small training (flash forward/backward) and serving (paged and
flash-decode, head-packed: 12 heads x 64 -> 6 x 128 lanes) — and look for
the Mosaic custom call in what comes back. Nothing runs: a compile that
passes is not a chip run.

All in this one file, topology described inside a fixture (never at import
or collection time) and compiled in the test's own process: only one
process at a time may load the TPU's library.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from singa_tpu.ops import attention as A


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip would be written to a persistent
    # cache but can never be read back without the chip: keep it off
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _mosaic_calls(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text().count(
        'custom_call_target="tpu_custom_call"')


def _has_mosaic_call(fn, *args):
    return _mosaic_calls(fn, *args) > 0


def _flash_fwd_and_grad(causal=True):
    def fwd(q, k, v):  # default scale and blocks, compiled
        return A.flash_attention(q, k, v, causal, None, None, None, False)

    def grad(q, k, v):  # holds the forward's call beside the backward's
        return jax.grad(lambda *a: fwd(*a).astype(jnp.float32).sum(),
                        argnums=(0, 1, 2))(q, k, v)

    return fwd, grad


# GPT-2-small (H12, D64) and a D128 head at the smoke's batch and sequence,
# and the benchmark's training cell (GPT-2-medium: 4 x 16 heads of 64).
# EXACTLY one Mosaic call a forward and one a backward: the benchmark's
# flash_roofline.train multiplies the calls it counts by a whole pass.
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize("shape", [(8, 12, 1024, 64), (8, 16, 1024, 128),
                                   (4, 16, 1024, 64)],
                         ids=["h12d64", "h16d128", "train_gpt2m"])
def test_flash_attention_compiles_for_v5e(one_chip, shape, direction):
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    fwd, grad = _flash_fwd_and_grad()
    if direction == "fwd":
        assert _mosaic_calls(fwd, q, q, q) == 1
    else:
        assert _mosaic_calls(grad, q, q, q) == 2


# the other plans flash_plan hands out: the engine's prefill buckets (one
# block, 896 = 7 bands of 128), an encoder (no mask), K streamed over the
# grid with dq for a whole row in VMEM (fused, at its 6 MB budget), and
# long rows, whose backward's sweep holds the last rows' dq beside a `_dq`
# call for the rest (the LFM2 cell's layer: 32 heads, 12,288 of 16,384
# rows held at D = 64). A row of D = 64 takes 128 lanes in VMEM, twice what
# the held rows' budget counts: the fused call at 32 heads x 12,288 needs
# 16.8 MB. VMEM overflow shows here, not on the chip.
@pytest.mark.parametrize("shape,causal,calls", [
    ((1, 20, 128, 64), True, (1, 2)),
    ((1, 20, 384, 64), True, (1, 2)),
    ((1, 20, 896, 64), True, (1, 2)),
    ((4, 16, 1024, 64), False, (1, 2)),
    ((1, 2, 12288, 64), True, (1, 2)),
    ((1, 2, 16384, 128), True, (1, 3)),
    ((1, 32, 12288, 64), True, (1, 2)),
    ((1, 32, 16384, 64), True, (1, 3)),
], ids=["b128", "b384", "b896", "encoder", "s12k_fused", "s16k_split",
        "s12k_fused_h32", "lfm2_s16k"])
def test_flash_plans_compile_for_v5e(one_chip, shape, causal, calls):
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    fwd, grad = _flash_fwd_and_grad(causal)
    assert (_mosaic_calls(fwd, q, q, q), _mosaic_calls(grad, q, q, q)) \
        == calls


# a sliding window: the 8k cell's sliding layers (two tiles a q block, the
# diagonal's and the window's edge, both in bands; the backward's sweep
# holding the last 6,144 rows' dq), the same at the cell's 32 heads, a
# short row whose backward is fused, a window that is no multiple of the
# block (tiles under one mask built from the step's offsets), and one
# narrower than the smallest block
@pytest.mark.parametrize("shape,window,calls", [
    ((1, 4, 8192, 128), 1024, (1, 3)),
    ((1, 32, 8192, 128), 1024, (1, 3)),
    ((1, 4, 2048, 64), 512, (1, 2)),
    ((1, 4, 2048, 128), 640, (1, 2)),
    ((1, 4, 1024, 64), 96, (1, 2)),
], ids=["s8k_w1024", "mellum_s8k_w1024", "s2k_w512", "s2k_w640",
        "s1k_w96"])
def test_windowed_flash_compiles_for_v5e(one_chip, shape, window, calls):
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def fwd(q, k, v):
        return A.flash_attention(q, k, v, True, None, None, None, False,
                                 window)

    def grad(q, k, v):
        return jax.grad(lambda *a: fwd(*a).astype(jnp.float32).sum(),
                        argnums=(0, 1, 2))(q, k, v)

    text = jax.jit(grad).lower(q, q, q).compile().as_text()
    assert (_mosaic_calls(fwd, q, q, q), text.count(
        'custom_call_target="tpu_custom_call"')) == calls
    # the kernels' names say they work under a window
    assert "singa_flash_fwd" + A.WINDOW_SUFFIX in text


# three call sites of a backward past the fused budget: every Mosaic call
# of the compiled step keeps the scope of the site it stands for, which the
# benchmark's scope table reads
def test_split_backward_keeps_each_site_s_scope_on_v5e(one_chip):
    import re
    q = jax.ShapeDtypeStruct((1, 2, 8192, 128), jnp.bfloat16,
                             sharding=one_chip)

    def grad(q, k, v):
        def loss(q):
            parts = []
            for i in range(3):
                with jax.named_scope(f"layer{i}"):
                    # inputs of its own: equal calls would be one to XLA
                    parts.append(A.flash_attention(q * (i + 1), k, v, True,
                                                   None, None, None, False))
            return sum(p.astype(jnp.float32).sum() for p in parts)
        return jax.grad(loss)(q)

    text = jax.jit(grad).lower(q, q, q).compile().as_text()
    sites = sorted(
        (re.search(r"layer(\d)", op).group(1), name)
        for op, name in re.findall(
            r'custom_call_target="tpu_custom_call".*?op_name="([^"]*/'
            r'(singa_flash_bwd\w*)/[^"]*)"', text))
    assert sites == [(str(i), n) for i in range(3)
                     for n in ("singa_flash_bwd", "singa_flash_bwd_dq")]


# the block-diffusion mask: the SDAR cell's doubled sequence (2 x 4096 in
# blocks of 4: tiles of 1024, the split backward), a short row whose
# backward is fused, and a block length that no band divides (a diagonal
# tile's bands each under a mask over the whole tile)
@pytest.mark.parametrize("shape,block,calls", [
    ((1, 4, 8192, 128), 4, (1, 3)),
    ((1, 4, 2048, 64), 32, (1, 2)),
    ((1, 4, 768, 128), 96, (1, 2)),
], ids=["s8k_b4", "s2k_b32", "s768_b96"])
def test_block_diffusion_flash_compiles_for_v5e(one_chip, shape, block,
                                                calls):
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def fwd(q, k, v):
        return A.flash_attention(q, k, v, False, None, None, None, False,
                                 None, block)

    def grad(q, k, v):
        return jax.grad(lambda *a: fwd(*a).astype(jnp.float32).sum(),
                        argnums=(0, 1, 2))(q, k, v)

    text = jax.jit(grad).lower(q, q, q).compile().as_text()
    assert (_mosaic_calls(fwd, q, q, q), text.count(
        'custom_call_target="tpu_custom_call"')) == calls
    assert "singa_flash_fwd" + A.BLOCKDIFF_SUFFIX in text


# a `_bd` grid step holds a second k block (the q block's noised K / V in
# the forward and dq; a (noised, clean) pair of K, V, dk, dv in dk / dv and
# the fused backward): past a Mosaic call's default scoped VMEM for the
# fused backward in fp32 and for the pair at D = 256, where the calls on one
# block compiled; the `_bd` calls ask for twice the default
@pytest.mark.parametrize("shape,dtype,fused", [
    ((1, 2, 4096, 64), "float32", True),
    ((1, 2, 4096, 256), "bfloat16", False),
], ids=["s4k_d64_fp32_fused", "s4k_d256_split"])
def test_block_diffusion_calls_hold_a_second_k_block_on_v5e(one_chip, shape,
                                                            dtype, fused):
    q = jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=one_chip)
    assert A.flash_plan(shape[2], shape[2], shape[3], False, q.dtype,
                        block_diffusion=4).fused == fused
    text = jax.jit(jax.grad(
        lambda *a: A.flash_attention(*a, False, None, None, None, False,
                                     None, 4).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))).lower(q, q, q).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') \
        == (2 if fused else 3)
    assert "singa_flash_bwd" + A.BLOCKDIFF_SUFFIX in text \
        or "singa_flash_bwd_dkv" + A.BLOCKDIFF_SUFFIX in text


# what ServingEngine builds for GPT-2-small in chip_smoke.py: 8 slots,
# P=2 heads packed per 128-lane row -> Hp=6, Q=P*G=2 query rows per token,
# pages of 16 tokens, 64 pages per sequence (max_ctx 1024), 512 in the pool
_N, _HP, _PD, _P, _PS, _M, _PAGES, _T = 8, 6, 128, 2, 16, 64, 512, 1024


def _decode_operands(sh, kv_rows, kv, q_tokens):
    """(q, K, V, scales-or-None) ShapeDtypeStructs: K/V are
    (`kv_rows`..., PD) pools or caches, bf16 or int8 with fp32
    per-(position, packed head) scales."""
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sh)
    q = sds((_N, _HP, q_tokens * _P, _PD), jnp.bfloat16)
    if kv == "int8":
        return (q, sds(kv_rows + (_PD,), jnp.int8),
                sds(kv_rows + (_P,), jnp.float32))
    return q, sds(kv_rows + (_PD,), jnp.bfloat16), None


@pytest.mark.parametrize("kv,q_tokens", [("bf16", 1), ("int8", 1),
                                         ("bf16", 4)],
                         ids=["bf16", "int8kv", "verify4"])
def test_paged_attention_compiles_for_v5e(one_chip, kv, q_tokens):
    q, pool, scales = _decode_operands(one_chip, (_PAGES, _HP, _PS), kv,
                                       q_tokens)
    table = jax.ShapeDtypeStruct((_N, _M), jnp.int32, sharding=one_chip)
    lens = jax.ShapeDtypeStruct((_N,), jnp.int32, sharding=one_chip)

    def fn(q, k, v, table, lens, *sc):
        ks, vs = sc if sc else (None, None)
        return A._paged_fwd_pallas(q, k, v, table, lens, _PS, 0.125, ks, vs,
                                   1, False, q_tokens)

    sc = (scales, scales) if scales is not None else ()
    assert _has_mosaic_call(fn, q, pool, pool, table, lens, *sc)


@pytest.mark.parametrize("kv,q_tokens", [("bf16", 1), ("int8", 1),
                                         ("int8", 4)],
                         ids=["bf16", "int8kv", "verify4_int8kv"])
def test_flash_decode_compiles_for_v5e(one_chip, kv, q_tokens):
    q, cache, scales = _decode_operands(one_chip, (_N, _HP, _T), kv,
                                        q_tokens)
    lens = jax.ShapeDtypeStruct((_N,), jnp.int32, sharding=one_chip)
    block_t = A._fit_block(_T, 256, floor=8)  # flash_decode's own choice

    def fn(q, k, v, lens, *sc):
        ks, vs = sc if sc else (None, None)
        return A._flash_decode_pallas(q, k, v, lens, 0.125, ks, vs, 1,
                                      False, q_tokens, block_t)

    sc = (scales, scales) if scales is not None else ()
    assert _has_mosaic_call(fn, q, cache, cache, lens, *sc)


# ---- head and loss: what the compiler forms of the logits' size ----------

def test_cross_entropy_forms_nothing_of_the_logits_size_on_v5e(one_chip):
    """GPT-2's head and integer-target loss, forward and backward, at the
    training cell's batch and vocabulary (50,257 is no multiple of 128: the
    compiler lays the logits out sequence-innermost). Of the logits' size
    the compiled program writes the head's matmul and nothing else: no
    relayout for a 2-D view, no log-probability tensor for a gather
    (ISSUE 31: 4.95 ms of an 89 ms step)."""
    import re
    from singa_tpu import autograd
    B, S, D, V = 4, 1024, 128, 50257

    def head_and_loss(h, W, t):
        z = jnp.einsum("bsd,dv->bsv", h, W,
                       preferred_element_type=jnp.float32)
        op = autograd.SoftMaxCrossEntropy()
        loss = op.forward(z, t)
        dz = op.backward(jnp.float32(1.0))[0]
        return (loss, jnp.einsum("bsv,dv->bsd", dz, W),
                jnp.einsum("bsd,bsv->dv", h, dz))

    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    text = jax.jit(head_and_loss).lower(
        sds((B, S, D), jnp.bfloat16), sds((D, V), jnp.bfloat16),
        sds((B, S), jnp.int32)).compile().as_text()
    entry = text[text.index("\nENTRY "):]
    # entry instructions whose result holds an array of the logits' size,
    # views and tuple plumbing aside: (name, opcode, the whole line)
    wrote = [(m.group(1), m.group(3), line) for line in entry.splitlines()
             for m in [re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (.*?) "
                                r"([\w\-]+)\(", line)]
             if m and re.search(r"f32\[(4,1024|4096),50257\]", m.group(2))
             and m.group(3) not in ("get-tuple-element", "bitcast", "tuple")]
    assert len(wrote) == 1, [w[:2] for w in wrote]
    _name, opcode, line = wrote[0]
    assert opcode == "fusion" and "dot_general" in line, line


# ---- the data-parallel step's reductions beside the backward pass ---------

def _gpt_step(optimizer, batch, monkeypatch):
    """(executor of the train step, its call as ShapeDtypeStructs less
    their shardings) of a GPT wide enough that a weight's gradient is no
    small array: (1024, 1024) fp32 is 4 MiB."""
    import numpy as np
    from singa_tpu import models, tensor
    from singa_tpu.device import get_default_device
    dev = get_default_device()
    m = models.create_model("gpt", vocab_size=2048, max_seq=128, dim=1024,
                            num_heads=16, num_layers=1)
    m.set_optimizer(optimizer)
    ids = np.zeros((batch, 128), np.int32)
    m.compile([tensor.from_numpy(ids[:1], device=dev)], is_train=True,
              use_graph=True, amp="bfloat16")
    tx = tensor.from_numpy(ids, device=dev)
    # the kernels' dispatch asks for the backend: the step is for a TPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    m._build_step(type(m).train_one_batch.__wrapped__, (tx, tx), {})
    with m._tracers_kept_out(m._state_tensors) as (state, opt_arrs, rng):
        call = (list(state), list(opt_arrs), rng, [tx.data, tx.data])
    return m, call


def _compiled_step_text(m, call, shardings):
    """The step as `introspect.AotExecutor` stages it, for the described
    devices `shardings` (state, batch) put the arguments on."""
    from singa_tpu import introspect
    ex = m._step_builder(0)
    held, batch = shardings
    sds = lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s)
    state, opt_arrs, rng, inputs = call
    with m._tracers_kept_out(m._state_tensors):
        compiled, _ = introspect._stage(
            ex.fn, ([sds(a, held) for a in state],
                    [sds(a, held) for a in opt_arrs], sds(rng, held),
                    [sds(a, batch) for a in inputs]), ex.compiler_options)
    return ex, compiled.as_text()


def test_dp_step_reduces_beside_the_backward_pass_on_v5e(topo, one_chip,
                                                         monkeypatch):
    """Four described chips: the step is compiled under the communicator's
    options and its gradient reductions come out in the asynchronous form
    (all but the small bucket of biases, norms and positions, and in a
    model this shallow a matrix the scheduler finds no carrier for);
    compiled without
    them every reduction blocks; a one-chip step takes no option and holds
    neither a reduction nor the form. A libtpu that stops honouring an
    option fails here (an unknown option is a compile error; a silent one
    shows as async == 0)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from singa_tpu import introspect, opt
    from singa_tpu.parallel import communicator
    mesh = Mesh(np.array(topo.devices), ("data",))
    on_mesh = (NamedSharding(mesh, P()), NamedSharding(mesh, P("data")))
    m, call = _gpt_step(opt.DistOpt(opt.Adam(lr=1e-4), mesh=mesh), 8,
                        monkeypatch)
    ex, text = _compiled_step_text(m, call, on_mesh)
    assert ex.compiler_options == communicator.OVERLAP_COMPILE_OPTIONS
    got = introspect.all_reduce_summary(text)
    assert got["async"] > 0 and got["collectives"] - got["async"] <= 2, got
    assert got["async_bytes"] > 0.9 * got["bytes"], got
    assert introspect.ASYNC_COLLECTIVE_START in text

    monkeypatch.setattr(communicator, "OVERLAP_COMPILE_OPTIONS", {})
    ex, text = _compiled_step_text(m, call, on_mesh)
    plain = introspect.all_reduce_summary(text)
    assert ex.compiler_options == {}
    assert plain["collectives"] > 0 and plain["async"] == 0, plain
    assert plain["bytes"] == got["bytes"]
    monkeypatch.undo()

    m, call = _gpt_step(opt.Adam(lr=1e-4), 2, monkeypatch)
    ex, text = _compiled_step_text(m, call, (one_chip, one_chip))
    assert ex.compiler_options == {} and "compiler_options" not in ex.static
    assert introspect.all_reduce_summary(text)["collectives"] == 0
    assert introspect.ASYNC_COLLECTIVE_START not in text \
        and "async_collective_name" not in text


# ---- the expert layer's row passes on the rung its rows need -------------

def test_expert_layer_switches_its_row_passes_on_the_rung_on_v5e(
        one_chip, monkeypatch):
    """The dropless expert layer, forward and backward, in bf16 at a size
    the grouped kernel tiles: each of the backward's three passes over the
    sorted buffer's rows is still a `conditional` of four branches under
    `moe` in the compiled text (one turned into a select would run every
    branch), the least branch of the combine's backward gathers R/8 rows of
    the cotangent, and the grouped products are the nine Mosaic calls of
    the layer before the ladder, all in the entry computation
    (`expert_matmul_roofline.train` counts each call under `experts` as
    one product)."""
    import re
    from singa_tpu.parallel import moe
    T, D, F, E, H, k = 1024, 256, 256, 8, 4, 4
    R = T * min(k, H)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def step(*args):
        with jax.named_scope("moe"):
            return jax.value_and_grad(
                lambda *a: jnp.sum(moe.dropless_moe(*a, k)[0]),
                (0, 1, 2, 3, 4))(*args)

    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    text = jax.jit(step).lower(
        sds((T, D), jnp.float32), sds((D, E), jnp.float32),
        sds((H, D, F), jnp.bfloat16), sds((H, D, F), jnp.bfloat16),
        sds((H, F, D), jnp.bfloat16)).compile().as_text()
    entry = text[text.index("\nENTRY "):]
    mosaic = 'custom_call_target="tpu_custom_call"'
    assert text.count(mosaic) == entry.count(mosaic) == 9
    switches = [(m.group(1).split(", "), m.group(2)) for m in re.finditer(
        r" conditional\([^\n]*branch_computations=\{([^}]*)\}[^\n]*"
        r'op_name="([^"]*)"', entry)]
    assert sorted(part for _b, op in switches
                  for part in ("dispatch", "experts", "combine")
                  if f"({part})" in op) == ["combine", "dispatch", "experts"]
    assert all(len(branches) == 4 and "moe/" in op and "transpose(" in op
               for branches, op in switches), switches
    # the combine's backward, least branch: R/8 rows of the cotangent
    # gathered in fp32, R rows of the products' dtype written
    least = next(branches[0].lstrip("%") for branches, op in switches
                 if "(combine)" in op)
    body = text[text.index(f"\n%{least} "):]
    body = body[:body.index("\n}")]
    assert re.search(rf"f32\[{R // 8},{D}\][^\n]* fusion\(", body), body
    assert re.search(rf"bf16\[{R},{D}\]", body), body


def test_short_conv_keeps_no_fp32_copy_of_its_streams_on_v5e(one_chip,
                                                            monkeypatch):
    """The gated short convolution at the LFM2 cell's shape (1 x 16,384 x
    2,048, bf16 products, fp32 taps), forward and all four gradients in one
    program: the chain is the Pallas kernels' two calls, forward and
    backward, which compile inside their scoped VMEM, and the program
    reserves no more than the 806 MB it did when XLA's fusions wrote z out
    in fp32 between them (403 MB: the (.., 3D) product and its gradient)."""
    import re
    from singa_tpu.ops import shortconv
    S, D = 16384, 2048
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    args = (sds((1, S, D), jnp.bfloat16), sds((D, 3 * D), jnp.bfloat16),
            sds((D, 3), jnp.float32), sds((D, D), jnp.bfloat16))

    def grad(*a):
        return jax.grad(lambda *b: shortconv.short_conv(*b).astype(
            jnp.float32).sum(), argnums=(0, 1, 2, 3))(*a)

    # the chain's dispatch asks for the backend: the program is for a TPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = jax.jit(grad).lower(*args).compile()
    calls = re.findall(r'custom_call_target="tpu_custom_call"[^\n]*?'
                       r'op_name="[^"]*/(singa_shortconv_\w+)/',
                       compiled.as_text())
    assert sorted(calls) == ["singa_shortconv_bwd", "singa_shortconv_fwd"]
    assert compiled.memory_analysis().temp_size_in_bytes <= 806e6


def test_gated_delta_layer_compiles_at_the_cell_s_widths_on_v5e(one_chip,
                                                              monkeypatch):
    """The gated delta-rule layer at the Olmo Hybrid cell's widths (hidden
    3,840, 30 heads of 96 / 192, 4 taps, chunks of 64; 1,024 tokens here,
    the cell's 4,096 in ~11 s), forward and every gradient in one program:
    the rule is the Pallas kernels' two calls, forward and backward, and
    nothing of the XLA form is left: no triangular solve, no loop over the
    chunks."""
    import re
    from singa_tpu.ops import gated_delta
    E, H, dk, dv, S = 3840, 30, 96, 192, 1024
    bf, f32 = jnp.bfloat16, jnp.float32
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    args = [sds((1, S, E), bf)] + [sds(s, bf) for s in (
        (E, H * dk), (E, H * dk), (E, H * dv), (E, H), (E, H), (E, H * dv),
        (H * dv, E))] + [sds(s, f32) for s in (
            (H * dk, 4), (H * dk, 4), (H * dv, 4), (H,), (H,), (dv,))]

    def grad(*a):
        return jax.grad(lambda *b: gated_delta.gated_delta_net(
            *b, heads=H).astype(f32).sum(), argnums=tuple(range(14)))(*a)

    # the rule's dispatch asks for the backend: the program is for a TPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = jax.jit(grad).lower(*args).compile().as_text()
    calls = re.findall(r'custom_call_target="tpu_custom_call"[^\n]*?'
                       r'op_name="[^"]*/(singa_delta_\w+)/', text)
    assert sorted(calls) == ["singa_delta_bwd", "singa_delta_fwd"]
    assert "triangular-solve" not in text and " while(" not in text


def test_delta_rule_kernels_reserve_less_than_the_xla_form_on_v5e(
        one_chip, monkeypatch):
    """The chunked rule alone at the cell's shape (1 x 4,096 tokens, 30
    heads of 96 / 192, chunks of 64, fp32), forward and every gradient: the
    kernels compile inside their scoped VMEM, and the program reserves at
    most 60 % of what the XLA form's does (727 against 1,705 MB: the
    kernels keep q, k, v, the gates and the state entering each chunk, where
    autodiff kept the solve's results, the scan's states and the scores)."""
    from singa_tpu.ops import gated_delta
    B, S, H, dk, dv = 1, 4096, 30, 96, 192
    sds = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                              sharding=one_chip)
    args = (sds(B, S, H, dk), sds(B, S, H, dk), sds(B, S, H, dv),
            sds(B, S, H), sds(B, S, H))

    def temps():
        grad = jax.grad(lambda *b: gated_delta.chunked_delta_rule(*b).sum(),
                        argnums=tuple(range(5)))
        c = jax.jit(grad).lower(*args).compile()
        return c.memory_analysis().temp_size_in_bytes, c.as_text().count(
            'custom_call_target="tpu_custom_call"')

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    kernels, n = temps()
    assert n == 2
    monkeypatch.setattr(gated_delta, "delta_rule_plan", lambda *_: None)
    xla, n = temps()
    assert n == 0 and kernels <= 0.6 * xla, (kernels, xla)
