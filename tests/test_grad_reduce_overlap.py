"""The data-parallel step's gradient reductions beside the backward pass
(ISSUE 33): what decides that a step is compiled under
`Communicator.overlap_compile_options`, that the options reach every
compile the executor makes and key what it caches, and what
`singa_grad_reduce` reads from a compiled module's text. On the CPU mesh
no option may be attached (an `xla_tpu_*` option is an error there); the
step under the options, for a described v5e, is in test_tpu_compile.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from singa_tpu import introspect, models, observe, opt, tensor
from singa_tpu.device import get_default_device
from singa_tpu.parallel import communicator, data_parallel_mesh

GPT = dict(vocab_size=96, max_seq=16, dim=32, num_heads=2, num_layers=2)


def _gpt(dev, optimizer, weights=None):
    ids = np.random.RandomState(0).randint(0, 96, (8, 16)).astype(np.int32)
    tx = tensor.from_numpy(ids, dev)
    ty = tensor.from_numpy(np.roll(ids, -1, 1), dev)
    m = models.create_model("gpt", **GPT)
    m.set_optimizer(optimizer)
    m.compile([tx], is_train=True, use_graph=True)
    if weights is not None:
        m.set_params(weights)
    return m, tx, ty


def _gauge():
    g = observe.get_registry().get("singa_grad_reduce")
    return g and {k: int(g.value(kind=k)) for k in
                  ("collectives", "async", "bytes", "async_bytes")}


@pytest.fixture
def dev():
    d = get_default_device()
    rng = d.rng_state
    yield d
    d.rng_state = rng


def test_dp_adam_step_off_a_tpu_takes_no_option_and_moves_nothing(dev):
    """Four host devices: the DistOpt(Adam) step of a small GPT applies
    what one device applies to the global batch, every shard ends bit-equal,
    and neither step is compiled under an option."""
    one, tx, ty = _gpt(dev, opt.Adam(lr=1e-2))
    w0 = {k: v.numpy().copy() for k, v in one.get_params().items()}
    dp, _, _ = _gpt(dev, opt.DistOpt(opt.Adam(lr=1e-2),
                                     mesh=data_parallel_mesh(4)), w0)
    for _ in range(2):
        loss1, loss4 = one(tx, ty)[1], dp(tx, ty)[1]
    assert abs(float(loss1.numpy()) - float(loss4.numpy())) < 1e-4
    for k, v in dp.get_params().items():
        np.testing.assert_allclose(v.numpy(), one.get_params()[k].numpy(),
                                   rtol=2e-3, atol=2e-4, err_msg=k)
        shards = [np.asarray(s.data) for s in v.data.addressable_shards]
        assert len(shards) == 4
        assert all(np.array_equal(shards[0], s) for s in shards[1:]), k
    for m in (one, dp):
        ex = m._compiled_step[0]
        assert ex.compiler_options == {}
        assert "compiler_options" not in (ex.static or "")
        assert all(v.run is not None for v in ex._execs.values())


@pytest.mark.parametrize("capture", [False, True],
                         ids=["text_printed_for_it", "text_of_the_capture"])
def test_grad_reduce_gauge_reads_the_compiled_step(dev, capture, tmp_path):
    """On the CPU mesh: the step's all-reduces are there, none of them
    asynchronous, and they move every parameter's gradient in fp32; a
    one-device step sets nothing. With HLO capture on the build reads the
    text it writes out, and the gauge takes the build's record."""
    one, tx, ty = _gpt(dev, opt.Adam(lr=1e-2))
    one(tx, ty)
    assert not _gauge()
    dp, _, _ = _gpt(dev, opt.DistOpt(opt.Adam(lr=1e-2),
                                     mesh=data_parallel_mesh(4)))
    introspect.capture_hlo(tmp_path if capture else None)
    try:
        dp(tx, ty)
    finally:
        introspect.capture_hlo(None)
    rec = introspect.last_build("step")
    assert (rec["all_reduces"] == _gauge() and rec["hlo_path"]) \
        if capture else rec["all_reduces"] is None
    held = sum(int(np.prod(v.shape)) for v in dp.get_params().values())
    got = _gauge()
    assert got["collectives"] > 0 and got["async"] == 0 \
        and got["async_bytes"] == 0
    # the gradients, and the loss's mean
    assert got["bytes"] == 4 * held + 4
    text = dp._compiled_step[0]._execs.popitem()[1].run.as_text()
    assert introspect.all_reduce_summary(text) == got


def test_options_only_for_an_axis_of_tpus():
    class Dev:
        def __init__(self, platform):
            self.platform = platform

    class FakeMesh:
        def __init__(self, platform, n):
            self.devices = np.array([Dev(platform)] * n)
            self.shape = {"data": n}

    def options(platform, n):
        return communicator.Communicator(
            "data", FakeMesh(platform, n)).overlap_compile_options()

    assert options("tpu", 4) == communicator.OVERLAP_COMPILE_OPTIONS
    assert options("tpu", 4) is not communicator.OVERLAP_COMPILE_OPTIONS
    assert options("tpu", 1) == {} and options("cpu", 4) == {} \
        and options("gpu", 4) == {}
    assert communicator.Communicator("data").overlap_compile_options() == {}


# ---- the executor under options -------------------------------------------

CPU_OPTION = {"xla_cpu_enable_fast_min_max": True}


def test_executor_compiles_under_its_options_and_keys_on_them():
    """The options are in the signature's `static` (so in the fingerprint
    the warm store files a build under) and reach `lowered.compile`."""
    f = lambda x: x * 2.0  # noqa: E731
    plain = introspect.AotExecutor(jax.jit(f), "t.opts", static="s")
    under = introspect.AotExecutor(
        jax.jit(f, compiler_options=CPU_OPTION), "t.opts", static="s",
        compiler_options=CPU_OPTION)
    x = jnp.ones((4,))
    a, b = plain.prepare(x), under.prepare(x)
    assert a.run is not None and b.run is not None
    assert a.record["fingerprint"] != b.record["fingerprint"]
    assert plain.static == "s" and "xla_cpu_enable_fast_min_max" in under.static
    np.testing.assert_array_equal(under(x), plain(x))


def test_an_option_the_backend_refuses_fails_the_fall_back_too():
    """The jit fall-back is the same program under the same options: where
    the staged compile is refused for an option, so is jit's."""
    bad = {"xla_no_such_option_at_all": True}
    ex = introspect.AotExecutor(
        jax.jit(lambda x: x + 1.0, compiler_options=bad), "t.bad",
        compiler_options=bad)
    v = ex.prepare(jnp.ones((2,)))
    assert v.run is None        # staging failed: jit owns the signature
    with pytest.raises(Exception, match="xla_no_such_option_at_all"):
        ex.dispatch(v, (jnp.ones((2,)),))


# ---- reading a compiled module's text --------------------------------------

_BLOCKING = """HloModule m
%add (a: f32[], b: f32[]) -> f32[] {
  ROOT %s = f32[] add(%a, %b)
}

ENTRY %main (p: f32[8,4]) -> f32[8,4] {
  %p = f32[8,4]{1,0} parameter(0)
  %all-reduce.1 = f32[8,4]{1,0:T(8,128)} all-reduce(%p), channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%add@ATTR@
  ROOT %t = (f32[2]{0}, bf16[3,2]{1,0}) all-reduce(%x, %y), channel_id=2, to_apply=%add
}
"""

_PAIR = """HloModule m
ENTRY %main (p: f32[16]) -> f32[16] {
  %p = f32[16]{0} parameter(0)
  %all-reduce-start.1 = f32[16]{0} all-reduce-start(%p), channel_id=1, to_apply=%add
  %m = f32[16]{0} multiply(%p, %p)
  ROOT %all-reduce-done.1 = f32[16]{0} all-reduce-done(%all-reduce-start.1)
}
"""

_FUSED = """HloModule m
%fused_computation.1 (q: f32[4,4]) -> (f32[4,4], u32[]) {
  %all-reduce.7 = f32[4,4]{1,0} all-reduce(%q), channel_id=1, to_apply=%add
}

%async_collective_fusion.2 (a: f32[4,4], b: f32[4,4]) -> f32[4,4] {
  %all-reduce.8 = f32[4,4]{1,0} all-reduce(%a), channel_id=1, to_apply=%add
  %dot.1 = f32[4,4]{1,0} convolution(%b, %b)
}

%fused_computation.3 (r: f32[4,4]) -> f32[4,4] {
  %all-reduce.9 = f32[4,4]{1,0} all-reduce(%r), channel_id=1, to_apply=%add
}

ENTRY %main (p: f32[4,4]) -> f32[4,4] {
  %p = f32[4,4]{1,0} parameter(0)
  %async-collective-start.4 = (f32[4,4]{1,0}, u32[]) fusion(%p), kind=kCustom, calls=%fused_computation.1
  %fusion.5 = f32[4,4]{1,0} fusion(%g, %p), kind=kOutput, calls=%async_collective_fusion.2
  %async-collective-done.4 = f32[4,4]{1,0} fusion(%fusion.5), kind=kCustom, calls=%fused_computation.3
  ROOT %all-reduce.10 = (f32[8]{0}, f32[8]{0}) all-reduce(%b1, %b2), channel_id=2, to_apply=%add
}
"""


@pytest.mark.parametrize("text,want", [
    (_BLOCKING.replace("@ATTR@", ""), (2, 0, 8 * 4 * 4 + 2 * 4 + 6 * 2, 0)),
    # a start/done pair the scheduler left adjacent and XLA joined again
    # keeps the start's name: it blocks like any other
    (_BLOCKING.replace(
        "@ATTR@",
        ', frontend_attributes={async_collective_name="all-reduce-start.2"}'),
     (2, 0, 8 * 4 * 4 + 2 * 4 + 6 * 2, 0)),
    (_PAIR, (1, 1, 64, 64)),
    # one reduction in three pieces counts once; the tuple bucket blocks
    (_FUSED, (2, 1, 64 + 64, 64)),
    ("HloModule m\nENTRY %main () -> f32[] {\n  ROOT %c = f32[] constant(0)\n}\n",
     (0, 0, 0, 0)),
], ids=["blocking", "joined_again", "start_done", "collective_fusion",
        "none"])
def test_all_reduce_summary(text, want):
    got = introspect.all_reduce_summary(text)
    assert (got["collectives"], got["async"], got["bytes"],
            got["async_bytes"]) == want
