"""The one executor (`introspect.AotExecutor`): every branch of "stage,
cache, fall back" driven through each of its kinds of user — a training
step and an eval forward (`Model`), and a bare `jax.jit` function (what
the engine, serving and audit hand it).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from singa_tpu import (autograd, introspect, layer, memory, model, observe,
                       opt, tensor, warmstart)
from singa_tpu.device import get_default_device
from singa_tpu.health import load_flight_bundle

FALLBACK = "model.jit_fallback"


class MLP(model.Model):
    def __init__(self):
        super().__init__()
        self.l1 = layer.Linear(16)
        self.relu = layer.ReLU()
        self.l2 = layer.Linear(4)
        self.loss_fn = layer.SoftMaxCrossEntropy()

    def forward(self, x):
        return self.l2(self.relu(self.l1(x)))

    def train_one_batch(self, x, y):
        out = self.forward(x)
        loss = self.loss_fn(out, y)
        self._optimizer(loss)
        return out, loss


def _mlp(dev, graph, weights=None):
    rng = np.random.RandomState(0)
    tx = tensor.from_numpy(rng.randn(8, 10).astype(np.float32), dev)
    ty = tensor.from_numpy(rng.randint(0, 4, 8).astype(np.int32), dev)
    m = MLP()
    m.set_optimizer(opt.SGD(lr=0.1, momentum=0.9))
    # eval_buckets=False: no half-batch probe, so one eval call is one
    # variant
    m.compile([tx], is_train=True, use_graph=graph, eval_buckets=False)
    if weights is not None:
        m.set_params(weights)
    return m, tx, ty


def _weights(m):
    return {k: v.numpy().copy() for k, v in m.get_params().items()}


class _Train:
    """`call()` is one training step's loss; `want` the eager twin's."""
    key = "step"

    def __init__(self, dev):
        self.m, self.tx, self.ty = _mlp(dev, True)
        twin, tx, ty = _mlp(dev, False, _weights(self.m))
        self.want = [float(twin(tx, ty)[1].numpy()) for _ in range(4)]
        self.m.train()
        self.n = 0

    def ex(self):
        return self.m._compiled_step[0]

    def call(self):
        got = float(self.m(self.tx, self.ty)[1].numpy())
        np.testing.assert_allclose(got, self.want[self.n], rtol=1e-5)
        self.n += 1

    def tensors(self):
        return self.m._state_tensors

    def template(self):
        return self.m._out_template_box


class _Eval:
    """`call()` is the graph-mode eval forward; `want` the eager one."""
    key = "eval"

    def __init__(self, dev):
        self.m, self.tx, _ = _mlp(dev, True)
        self.m.eval()
        self.want = self.m.forward(self.tx).numpy()

    def ex(self):
        return self.m._compiled_eval

    def call(self):
        np.testing.assert_allclose(self.m(self.tx).numpy(), self.want,
                                   rtol=1e-5, atol=1e-6)

    def tensors(self):
        return self.m._eval_tensors

    def template(self):
        return self.m._eval_template_box


class _Bare:
    key = "t.bare"

    def __init__(self, dev):
        self._ex = introspect.AotExecutor(
            jax.jit(lambda x: x * 2 + 1), self.key)
        self.x = jnp.arange(8, dtype=jnp.float32)

    def ex(self):
        return self._ex

    def call(self):
        np.testing.assert_allclose(np.asarray(self._ex(self.x)),
                                   np.arange(8) * 2 + 1)


USERS = {"train": _Train, "eval": _Eval, "bare": _Bare}


@pytest.fixture
def spans():
    """The paths of the spans that closed, in order."""
    seen = []
    cb = observe.add_span_listener(lambda path, s, attrs: seen.append(path))
    yield seen
    observe.remove_span_listener(cb)


@pytest.fixture
def builds(monkeypatch):
    """Count `build_compiled` calls; `builds["fail"] = True` makes staging
    fail the way it reports failure: (None, None)."""
    real = introspect.build_compiled
    state = {"n": 0, "fail": False}

    def counted(*a, **k):
        state["n"] += 1
        return (None, None) if state["fail"] else real(*a, **k)

    monkeypatch.setattr(introspect, "build_compiled", counted)
    return state


def _fallbacks(spans):
    return sum(p.split("/")[-1] == FALLBACK for p in spans)


def _variant(user):
    (v,) = user.ex()._execs.values()
    return v


def _raiser(err):
    def run(*_a, **_k):
        raise err
    return run


def _oom_error():
    msg = "RESOURCE_EXHAUSTED: Out of memory allocating 1234 bytes"
    return type("XlaRuntimeError", (RuntimeError,), {})(msg)


# ---- the branches -----------------------------------------------------------

def stages_then_caches(user, builds, spans, tmp_path, monkeypatch):
    user.call()
    assert builds["n"] == 1
    v = _variant(user)
    assert v.run is not None and v.fresh is False and v.cold is False
    assert v.record["key"] == user.key and v.record["phases"]["compile"] > 0
    user.call()
    user.call()
    assert builds["n"] == 1 and len(user.ex()) == 1   # built nothing more
    assert _variant(user) is v and _fallbacks(spans) == 0
    if user.key == "step":
        # the build was recorded as the one compile, the MFU gauge reads
        # the dispatched variant's flops
        reg = observe.get_registry()
        assert reg.get("singa_model_compile_total").value(
            batch_class="8") == 1
        assert reg.get("singa_model_recompile_total") is None
        assert introspect._step_flops == v.flops > 0


def staging_fails(user, builds, spans, tmp_path, monkeypatch):
    builds["fail"] = True
    user.call()
    v = _variant(user)
    assert v.run is None and v.record is None and v.flops == 0.0
    assert _fallbacks(spans) == 1      # jit's cold compile, booked once
    user.call()
    user.call()
    # negative-cached: jit owns the signature, staging is not re-paid
    assert builds["n"] == 1 and _fallbacks(spans) == 1
    if user.key == "step":
        assert introspect._step_flops == 0.0    # MFU gauge off


def executable_rejects(user, builds, spans, tmp_path, monkeypatch):
    user.call()
    v = _variant(user)
    v.run = _raiser(ValueError("argument 3 has shape (9,), compiled (8,)"))
    user.call()                        # falls back inside this call
    assert v.run is None and v.flops == 0.0 and _fallbacks(spans) == 1
    user.call()                        # then jit, with no span
    assert builds["n"] == 1 and _fallbacks(spans) == 1
    if user.key == "step":
        assert introspect._step_flops == 0.0


def out_of_memory(user, builds, spans, tmp_path, monkeypatch):
    user.call()
    memory.install_ledger(out_dir=str(tmp_path))
    v = _variant(user)
    boom = v.run = _raiser(_oom_error())
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        user.call()
    (bundle,) = [f for f in os.listdir(tmp_path)
                 if f.startswith("flight_oom_")]
    head = load_flight_bundle(str(tmp_path / bundle))["header"]
    assert head["reason"] == "oom"
    assert head["oom"]["executable_key"] == user.key
    # no fall-back: jit would re-pay the allocation and die the same way
    assert v.run is boom and _fallbacks(spans) == 0


def _warm_restart(user, tmp_path):
    """A second user of the same program in a process that has the first
    one's export: its first call is a warm-store hit, so its python
    function is never traced by the build."""
    warmstart.enable(str(tmp_path / "warm"))
    user.call()                                     # cold: exports
    assert _variant(user).record["warm"] == warmstart.RESULT_MISS
    introspect.reset()
    return type(user)(user.tx.device)    # its own weights: they are inputs


def warm_hit(user, builds, spans, tmp_path, monkeypatch):
    again = _warm_restart(user, tmp_path)
    dev = again.tx.device
    training = autograd.training
    again.call()
    v = _variant(again)
    assert v.record["warm"] == warmstart.RESULT_HIT and v.run is not None
    # the output template came back from one abstract trace, which left
    # no tracer behind
    assert "t" in again.template() and _fallbacks(spans) == 0
    assert not any(isinstance(t.data, jax.core.Tracer)
                   for t in again.tensors())
    assert not isinstance(dev.rng_state, jax.core.Tracer)
    assert autograd.training is training
    again.call()


def warm_hit_template_lost(user, builds, spans, tmp_path, monkeypatch):
    again = _warm_restart(user, tmp_path)

    def no_abstract_trace(*_a, **_k):
        raise RuntimeError("eval_shape refused")

    with monkeypatch.context() as mp:
        mp.setattr(jax, "eval_shape", no_abstract_trace)
        again.call()     # jit owns the signature: its trace fills the box
    v = _variant(again)
    assert v.run is None and v.flops == 0.0 and _fallbacks(spans) == 1
    assert "t" in again.template()
    assert not any(isinstance(t.data, jax.core.Tracer)
                   for t in again.tensors())
    again.call()
    assert _fallbacks(spans) == 1


CASES = [(b, u) for b in (stages_then_caches, staging_fails,
                          executable_rejects, out_of_memory)
         for u in USERS]
CASES += [(b, u) for b in (warm_hit, warm_hit_template_lost)
          for u in ("train", "eval")]     # only Model has a side channel


@pytest.mark.parametrize(
    "branch,user", CASES, ids=[f"{b.__name__}-{u}" for b, u in CASES])
def test_executor_branch(branch, user, builds, spans, tmp_path, monkeypatch):
    dev = get_default_device()
    rng = dev.rng_state
    try:
        branch(USERS[user](dev), builds, spans, tmp_path, monkeypatch)
    finally:
        dev.rng_state = rng


def test_cache_key_and_signature_fields_reach_the_build(monkeypatch):
    """The caller's key function decides what a cached call looks at; its
    tag / static / names / donated / device and the call's batch_hint go
    into the signature and the build as given."""
    seen = {}
    real_sig = introspect.signature

    def sig(*a, **k):
        seen["sig"] = k
        return real_sig(*a, **k)

    real_build = introspect.build_compiled

    def build(fn, args, key, s, device=None, compiler_options=None):
        seen["build"] = (key, s, device)
        assert not compiler_options    # none was given
        return real_build(fn, args, key, s, device=device)

    monkeypatch.setattr(introspect, "signature", sig)
    monkeypatch.setattr(introspect, "build_compiled", build)
    ex = introspect.AotExecutor(
        jax.jit(lambda s, x: [a + x.sum() for a in s]), "t.keyed",
        names=("state", "arg"), donated=(), tag=3, static="[(1, 'k')]",
        cache_key=lambda a: (tuple(a[1].shape), str(a[1].dtype)))
    state = [jnp.ones((2,)), jnp.ones((3,))]
    v = ex.prepare(state, jnp.ones((4,)), batch_hint=3)
    assert seen["sig"] == {"names": ("state", "arg"), "tag": 3,
                           "static": "[(1, 'k')]", "donated": (),
                           "batch_hint": 3}
    key, s, device = seen["build"]
    assert key == "t.keyed" and device is None and s["batch_hint"] == 3
    assert [leaf[0] for leaf in s["leaves"]] == ["state0", "state1", "arg"]
    assert list(ex._execs) == [((4,), "float32")]
    # the state is not in the key: another one hits the same variant
    assert ex.prepare([jnp.zeros((2,)), jnp.zeros((3,))],
                      jnp.zeros((4,))) is v
    assert ex.prepare(state, jnp.ones((5,))) is not v and len(ex) == 2
    # the default key is every leaf's aval
    plain = introspect.AotExecutor(jax.jit(lambda s, x: x), "t.plain")
    plain.prepare(state, jnp.ones((4,)))
    plain.prepare([jnp.ones((2,)), jnp.ones((9,))], jnp.ones((4,)))
    assert len(plain) == 2
