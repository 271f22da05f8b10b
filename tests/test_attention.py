"""Attention stack tests: flash == reference (fwd+grad), ring == full
attention on the 8-device CPU mesh, GPT trains."""

import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from singa_tpu.ops import attention as att


def _qkv(rng, b=2, h=2, s=128, d=32):
    q = rng.standard_normal((b, h, s, d)).astype(np.float32)
    k = rng.standard_normal((b, h, s, d)).astype(np.float32)
    v = rng.standard_normal((b, h, s, d)).astype(np.float32)
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(causal):
    rng = np.random.default_rng(0)
    q, k, v = _qkv(rng)
    ref = att.attention_reference(q, k, v, causal)
    out = att.flash_attention(q, k, v, causal, None, 64, 64, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_grads_match(causal):
    rng = np.random.default_rng(1)
    q, k, v = _qkv(rng, b=1, h=2, s=64, d=16)

    def loss_ref(q, k, v):
        return jnp.sum(att.attention_reference(q, k, v, causal) ** 2)

    def loss_flash(q, k, v):
        return jnp.sum(att.flash_attention(q, k, v, causal, None,
                                           32, 32, True) ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_fl, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3)


def test_flash_fallback_on_odd_shapes():
    rng = np.random.default_rng(2)
    q, k, v = _qkv(rng, s=100)  # 100 % 128 != 0 -> reference fallback
    out = att.flash_attention(q, k, v)
    ref = att.attention_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_full(causal):
    from singa_tpu.parallel import make_mesh
    mesh = make_mesh({"sp": 4})
    rng = np.random.default_rng(3)
    q, k, v = _qkv(rng, b=1, h=2, s=64, d=16)
    ref = att.attention_reference(q, k, v, causal)
    out = att.ring_attention_sharded(q, k, v, mesh, "sp", causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_ring_attention_grads_match():
    from jax.sharding import PartitionSpec as P
    from singa_tpu.parallel import make_mesh
    mesh = make_mesh({"sp": 4})
    rng = np.random.default_rng(4)
    q, k, v = _qkv(rng, b=1, h=1, s=32, d=8)
    spec = P(None, None, "sp", None)

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(spec, spec, spec), out_specs=P(),
                       check_vma=False)
    def ring_loss(q, k, v):
        o = att.ring_attention(q, k, v, "sp", causal=True)
        return jax.lax.psum(jnp.sum(o ** 2), "sp")

    def full_loss(q, k, v):
        return jnp.sum(att.attention_reference(q, k, v, causal=True) ** 2)

    g_ring = jax.grad(ring_loss, argnums=(0, 1, 2))(q, k, v)
    g_full = jax.grad(full_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_full):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3)


def test_gpt_trains(dev):
    from singa_tpu import models, opt, tensor
    rng = np.random.RandomState(0)
    ids = rng.randint(0, 50, (2, 32)).astype(np.int32)
    tgt = np.roll(ids, -1, axis=1).astype(np.int32)
    m = models.create_model("gpt", vocab_size=50, max_seq=32, dim=32,
                            num_heads=4, num_layers=2)
    m.set_optimizer(opt.SGD(lr=0.1))
    tx = tensor.from_numpy(ids, device=dev)
    ty = tensor.from_numpy(tgt, device=dev)
    m.compile([tx], is_train=True, use_graph=True)
    losses = []
    for _ in range(5):
        _, loss = m(tx, ty)
        losses.append(float(loss.numpy()))
    assert np.isfinite(losses).all() and losses[-1] < losses[0]


def test_gpt_seq_parallel_dryrun(dev):
    """GPT with ring attention over an 'sp' axis + DistOpt over 'data':
    the full 2D-mesh training step compiles and runs on the CPU mesh."""
    from jax.sharding import PartitionSpec as P, NamedSharding
    from singa_tpu import models, opt, tensor
    from singa_tpu.parallel import make_mesh

    mesh = make_mesh({"data": 2, "sp": 4})
    rng = np.random.RandomState(0)
    B, S = 2, 32
    ids = rng.randint(0, 50, (B, S)).astype(np.int32)
    tgt = np.roll(ids, -1, axis=1).astype(np.int32)

    m = models.create_model("gpt", vocab_size=50, max_seq=S, dim=32,
                            num_heads=4, num_layers=1, seq_axis="sp")
    sgd = opt.SGD(lr=0.05)

    import jax as _jax
    from singa_tpu import autograd

    # manual shard_map step exercising BOTH axes: batch over 'data',
    # sequence over 'sp' (Model's built-in step wires only 'data')
    params = None

    def build(ids_np):
        tx = tensor.from_numpy(ids_np, device=dev)
        prev = autograd.training
        autograd.training = False
        try:
            m.forward(tx)
        finally:
            autograd.training = prev
        return list(m.get_params().values())

    params = build(ids)
    p_arrs = [p.data for p in params]

    def step(p_arrs, ids_a, tgt_a):
        for p, a in zip(params, p_arrs):
            p.data = a
        autograd.training = True
        try:
            tx = tensor.Tensor(data=ids_a, device=dev, requires_grad=False)
            ty = tensor.Tensor(data=tgt_a, device=dev, requires_grad=False)
            logits = m.forward(tx)
            flat = autograd.reshape(logits, (-1, 50))
            loss = autograd.softmax_cross_entropy(
                flat, autograd.reshape(ty, (-1,)))
            grads = autograd.gradients(loss)
        finally:
            autograd.training = False
        # dp-mean + sp-mean of grads (each sp shard sees the same params)
        gs = []
        for p in params:
            g = grads[p].data
            g = _jax.lax.pmean(_jax.lax.pmean(g, "data"), "sp")
            gs.append(g)
        new_p = [a - 0.05 * g for a, g in zip(p_arrs, gs)]
        return new_p, _jax.lax.pmean(_jax.lax.pmean(loss.data, "data"), "sp")

    data_spec = P("data", "sp")
    stepped = _jax.shard_map(
        step, mesh=mesh,
        in_specs=(P(), data_spec, data_spec),
        out_specs=(P(), P()),
        check_vma=False)
    rep = NamedSharding(mesh, P())
    shard = NamedSharding(mesh, data_spec)
    p_arrs = [_jax.device_put(a, rep) for a in p_arrs]
    ids_m = _jax.device_put(jnp.asarray(ids), shard)
    tgt_m = _jax.device_put(jnp.asarray(tgt), shard)
    new_p, loss = _jax.jit(stepped)(p_arrs, ids_m, tgt_m)
    assert np.isfinite(float(loss))


def test_block_autofit_nonpow2_seq():
    """None-default blocks fit a divisor (S=384: one block, in bands of
    128) so the kernel path keeps working off power-of-two lengths;
    explicit non-tiling blocks keep the documented reference fallback."""
    from singa_tpu.ops import attention as A
    plan = A.flash_plan(384, 384, 32, True, jnp.float32)
    assert plan.ok and plan.fwd[:3] == (384, 384, 128)
    assert plan.bwd[:3] == (384, 384, 128) and plan.fused
    assert not A.flash_plan(384, 384, 32, True, jnp.float32, 256, 256).ok
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.rand(1, 2, 384, 32), jnp.float32)
    out = A.flash_attention(q, q, q, causal=True)
    ref = A.attention_reference(q, q, q, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_backward_block_cap_refits():
    """An explicit block above the unbanded backward's VMEM cap runs in
    bands where a band divides it (768 = 3 x 256) and refits to a divisor
    where none does (1016 -> nothing at or under 512 -> the blockwise
    path), instead of crashing the blockwise fallback on a non-divisor."""
    from singa_tpu.ops import attention as A
    assert A.flash_plan(768, 768, 32, True, jnp.float32, 768, 768).bwd[:3] \
        == (768, 768, 256)
    assert A.flash_plan(1016, 1016, 32, True, jnp.float32).bwd is None
    assert A.flash_plan(1000, 1000, 32, True, jnp.float32).bwd[:3] \
        == (200, 200, 0)     # 500 is not on 8 sublanes
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.rand(1, 2, 768, 32), jnp.float32)
    g = jax.grad(lambda q: A.flash_attention(
        q, q, q, causal=True, block_q=768, block_k=768).sum())(q)
    gr = jax.grad(lambda q: A.attention_reference(
        q, q, q, causal=True).sum())(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr),
                               rtol=2e-3, atol=2e-4)


def test_ring_dispatch_falls_back_when_bwd_blocks_dont_fit():
    """S_local=2032: forward could tile at 1016 but no [128,512] divisor
    exists for the capped backward ring, so dispatch must use the jnp
    path (which has full AD) instead of crashing at grad trace time."""
    from singa_tpu.parallel.mesh import make_mesh
    mesh = make_mesh({"sp": 4})
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.rand(1, 1, 4 * 2032, 16), jnp.float32)
    out = att.ring_attention_sharded(q, q, q, mesh, "sp", causal=True)
    ref = att.attention_reference(q, q, q, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_gqa_ring_attention_matches_serial(dev):
    """GQA composes with ring attention: kv heads repeat per group BEFORE
    the ring, so the rotating K/V shards carry full head counts and the
    sequence-sharded forward matches the serial one."""
    from jax.sharding import PartitionSpec as P, NamedSharding
    from singa_tpu import models, tensor
    from singa_tpu.parallel import make_mesh
    from singa_tpu import autograd
    import jax as _jax

    mesh = make_mesh({"sp": 4})
    rng = np.random.RandomState(3)
    B, S, V = 2, 32, 50
    ids = rng.randint(0, V, (B, S)).astype(np.int32)

    m = models.create_model("gpt", vocab_size=V, max_seq=S, dim=32,
                            num_heads=4, num_kv_heads=2, num_layers=1,
                            seq_axis="sp")
    tx = tensor.from_numpy(ids, device=dev)
    m.compile([tx], is_train=False, use_graph=False)
    m.eval()
    want = m.forward(tx).numpy()      # serial (sp axis unbound)
    params = list(m.get_params().values())
    p_arrs = [p.data for p in params]

    def fwd(p_arrs, ids_a):
        for p, a in zip(params, p_arrs):
            p.data = a
        t = tensor.Tensor(data=ids_a, device=dev, requires_grad=False)
        return m.forward(t).data

    run = _jax.shard_map(fwd, mesh=mesh,
                         in_specs=(P(), P(None, "sp")),
                         out_specs=P(None, "sp"), check_vma=False)
    rep = NamedSharding(mesh, P())
    got = _jax.jit(run)(
        [_jax.device_put(a, rep) for a in p_arrs],
        _jax.device_put(jnp.asarray(ids), NamedSharding(mesh,
                                                        P(None, "sp"))))
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-3,
                               atol=2e-3)


def test_rope_seq_parallel_offset(dev):
    """Under sequence parallelism the Rope op offsets positions by
    axis_index * S_local — the sharded forward must match serial."""
    from jax.sharding import PartitionSpec as P, NamedSharding
    from singa_tpu import models, tensor
    from singa_tpu.parallel import make_mesh
    import jax as _jax

    mesh = make_mesh({"sp": 4})
    rng = np.random.RandomState(9)
    B, S, V = 2, 32, 50
    ids = rng.randint(0, V, (B, S)).astype(np.int32)
    m = models.create_model("gpt", vocab_size=V, max_seq=S, dim=32,
                            num_heads=4, num_layers=1, seq_axis="sp",
                            pos_encoding="rope")
    tx = tensor.from_numpy(ids, device=dev)
    m.compile([tx], is_train=False, use_graph=False)
    m.eval()
    want = m.forward(tx).numpy()
    params = list(m.get_params().values())

    def fwd(p_arrs, ids_a):
        for p, a in zip(params, p_arrs):
            p.data = a
        t = tensor.Tensor(data=ids_a, device=dev, requires_grad=False)
        return m.forward(t).data

    run = _jax.shard_map(fwd, mesh=mesh,
                         in_specs=(P(), P(None, "sp")),
                         out_specs=P(None, "sp"), check_vma=False)
    rep = NamedSharding(mesh, P())
    got = _jax.jit(run)(
        [_jax.device_put(p.data, rep) for p in params],
        _jax.device_put(jnp.asarray(ids), NamedSharding(mesh,
                                                        P(None, "sp"))))
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-3,
                               atol=2e-3)


def test_flash_bwd_fused_matches_split():
    """The fused single-pass backward (dq VMEM scratch) and the split
    dq/dkv kernel pair are alternate lowerings of the same math — the
    fused path serves S*D*4 <= 4MB, the split path long context. Force
    each and require matching gradients (and both match the reference
    vjp)."""
    import singa_tpu.ops.attention as att

    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.standard_normal((2, 3, 256, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 3, 256, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 3, 256, 64)), jnp.float32)

    def grads(*a):
        return jax.grad(
            lambda q_, k_, v_: jnp.sum(
                att.flash_attention(q_, k_, v_, True)), (0, 1, 2))(*a)

    cap = att._FUSED_DQ_BYTES_CAP
    try:
        att._FUSED_DQ_BYTES_CAP = 1 << 60   # force fused
        g_fused = grads(q, k, v)
        att._FUSED_DQ_BYTES_CAP = 0         # force split
        g_split = grads(q, k, v)
    finally:
        att._FUSED_DQ_BYTES_CAP = cap
    g_ref = jax.grad(
        lambda q_, k_, v_: jnp.sum(
            att.attention_reference(q_, k_, v_, True)), (0, 1, 2))(
        q, k, v)
    for gf, gs, gr, name in zip(g_fused, g_split, g_ref,
                                ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gs),
                                   rtol=2e-4, atol=2e-4, err_msg=name)
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=2e-3, atol=2e-3, err_msg=name)


# ---- the causal tile schedule (flash_plan) ---------------------------------

def _flash_vs_reference(shape, dtype, causal, seed):
    """Forward and all three gradients of the default-block kernel
    (interpret mode) against attention_reference in fp32 on the same
    inputs; returns the largest absolute differences."""
    rng = np.random.default_rng(seed)
    q, k, v, w = (jnp.asarray(rng.standard_normal(shape), dtype)
                  for _ in range(4))

    def run(fn, *a):
        out, vjp = jax.vjp(lambda *x: fn(*x, causal), *a)
        return (out,) + vjp(w.astype(out.dtype))

    got = run(att.flash_attention, q, k, v)
    want = run(att.attention_reference,
               *(a.astype(jnp.float32) for a in (q, k, v)))
    return [float(jnp.max(jnp.abs(g.astype(jnp.float32) - r)))
            for g, r in zip(got, want)]


# the training cell's tile geometry with few heads (one 1024 x 1024 grid
# step a head, worked in bands of 256 rows), the
# serving buckets 384 / 512 / 896 (896 = 7 bands of 128), a D = 128 head,
# and K streamed over two blocks (S = 2048)
@pytest.mark.parametrize("shape,dtype,causal", [
    ((1, 2, 1024, 64), "bfloat16", True),
    ((1, 2, 1024, 64), "bfloat16", False),
    ((1, 2, 1024, 64), "float32", True),
    ((1, 2, 1024, 64), "float32", False),
    ((1, 2, 384, 64), "bfloat16", True),
    ((1, 2, 512, 64), "bfloat16", True),
    ((1, 2, 896, 64), "bfloat16", True),
    ((1, 2, 512, 128), "bfloat16", True),
    ((1, 2, 512, 128), "float32", False),
    ((1, 1, 2048, 64), "float32", True),
], ids=lambda p: "x".join(map(str, p)) if isinstance(p, tuple) else str(p))
def test_flash_default_plan_matches_reference(shape, dtype, causal):
    errs = _flash_vs_reference(shape, jnp.dtype(dtype), causal, seed=7)
    # bf16: p and ds are rounded to 8 bits of mantissa for their matmuls;
    # a dropped or doubly-counted sub-tile moves an output by O(0.1-1)
    tol = 2e-4 if dtype == "float32" else 6e-2
    assert max(errs) < tol, dict(zip(("out", "dq", "dk", "dv"), errs))


def test_flash_split_backward_matches_reference():
    """The dq + dkv pair (long rows) over a 2 x 2 grid of banded blocks."""
    cap = att._FUSED_DQ_BYTES_CAP
    try:
        att._FUSED_DQ_BYTES_CAP = 0
        errs = _flash_vs_reference((1, 1, 2048, 64), jnp.float32, True, 8)
    finally:
        att._FUSED_DQ_BYTES_CAP = cap
    assert max(errs) < 2e-4, errs


@pytest.mark.parametrize("args,fwd,bwd", [
    # (sq, sk, d, causal, dtype[, block_q, block_k]) ->
    # (block_q, block_k, band, visited, masked, square) each way
    ((1024, 1024, 64, True, "bfloat16"),
     (1024, 1024, 256, 10, 4, 16), (1024, 1024, 256, 10, 4, 16)),
    ((1024, 1024, 64, False, "bfloat16"),
     (1024, 1024, 256, 16, 0, 16), (1024, 1024, 256, 16, 0, 16)),
    ((896, 896, 64, True, "bfloat16"),
     (896, 896, 128, 28, 7, 49), (896, 896, 128, 28, 7, 49)),
    ((512, 512, 64, True, "bfloat16"),
     (512, 512, 256, 3, 2, 4), (512, 512, 256, 3, 2, 4)),
    ((128, 128, 64, True, "bfloat16"),
     (128, 128, 128, 1, 1, 1), (128, 128, 128, 1, 1, 1)),
    ((2048, 2048, 64, True, "bfloat16"),
     (1024, 1024, 128, 136, 16, 256), (1024, 1024, 256, 36, 8, 64)),
    # explicit blocks are honoured; no band divides 64, so tiles go whole
    ((128, 128, 32, True, "float32", 64, 64),
     (64, 64, 0, 3, 2, 4), (64, 64, 0, 3, 2, 4)),
    ((512, 512, 128, True, "float32", 128, 256),
     (128, 256, 128, 10, 4, 16), (128, 256, 128, 10, 4, 16)),
    ((256, 512, 64, False, "bfloat16", 128, 128),
     (128, 128, 128, 8, 0, 8), (128, 128, 128, 8, 0, 8)),
], ids=lambda p: "-".join(map(str, p)) if len(p) in (5, 7) else None)
def test_flash_plan_table(args, fwd, bwd):
    plan = att.flash_plan(*args[:4], jnp.dtype(args[4]), *args[5:])
    assert plan.ok and tuple(plan.fwd) == fwd and tuple(plan.bwd) == bwd
    for t in (plan.fwd, plan.bwd):
        if args[3]:     # causal: little over half, masks on the diagonal
            assert t.masked <= t.visited < t.square or t.square == 1
        else:
            assert t.visited == t.square and t.masked == 0


def test_flash_plan_causal_1024_visits_under_two_thirds():
    plan = att.flash_plan(1024, 1024, 64, True, jnp.bfloat16)
    for t in (plan.fwd, plan.bwd):
        assert t.visited <= 0.65 * t.square
        # only sub-tiles the diagonal crosses take a mask: one a band
        assert t.masked == 1024 // t.band


@pytest.mark.parametrize("args", [
    (384, 384, 32, True, "float32", 256, 256),   # 256 does not divide 384
    (128, 128, 32, True, "float32", 60, 60),     # not on 8 sublanes
    (100, 100, 32, False, "float32"),            # nothing >= 100 tiles 100
    (1024, 1023, 64, True, "bfloat16"),          # the engine's last bucket
], ids=lambda a: "-".join(map(str, a)))
def test_flash_plan_refuses_blocks_that_do_not_tile(args):
    plan = att.flash_plan(*args[:4], jnp.dtype(args[4]), *args[5:])
    assert not plan.ok and plan.fwd is None and plan.bwd is None


def test_flash_plan_fused_backward_budget():
    """dq for a whole row in VMEM (f32 accumulator + double-buffered
    output) within 6 MB: S = 8192 at D = 64 fuses in bf16 (4 MB) and fp32
    (6 MB); D = 128, or S = 16384, takes the dq + dkv pair."""
    assert att.flash_plan(8192, 8192, 64, True, jnp.bfloat16).fused
    assert att.flash_plan(8192, 8192, 64, True, jnp.float32).fused
    assert not att.flash_plan(8192, 8192, 128, True, jnp.bfloat16).fused
    assert not att.flash_plan(16384, 16384, 64, True, jnp.bfloat16).fused


@pytest.mark.parametrize("causal", [True, False])
def test_flash_tiles_gauge_reads_the_plan(causal):
    """One traced call leaves the schedule it was traced with in
    singa_flash_tiles{site, kind}: readable with no chip."""
    from singa_tpu import observe
    q = jax.ShapeDtypeStruct((1, 2, 1024, 64), jnp.bfloat16)
    jax.eval_shape(jax.grad(lambda q, k, v: att.flash_attention(
        q, k, v, causal).astype(jnp.float32).sum(), (0, 1, 2)), q, q, q)
    g = observe.get_registry().get("singa_flash_tiles")
    plan = att.flash_plan(1024, 1024, 64, causal, jnp.bfloat16)
    for site, t in (("flash_fwd", plan.fwd), ("flash_bwd", plan.bwd)):
        got = tuple(int(g.value(site=site, kind=kind))
                    for kind in ("visited", "masked", "square"))
        assert got == tuple(t[3:]), (site, got, t)
    if causal:
        assert int(g.value(site="flash_fwd", kind="visited")) == 10
        assert int(g.value(site="flash_fwd", kind="masked")) == 4
