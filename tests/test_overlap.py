"""Overlap layer (singa_tpu.overlap): the ISSUE-5 tentpole surface.

Device prefetch ring (ordering, sharded/teardown/error semantics, the
fit acceptance A/B: >=50% data_wait cut with bitwise-identical losses
and compile_count==1), async checkpointing (returns-before-durable,
barrier + deferred-error re-raise, load round-trip), and
the step-dispatch fast path (per-variant cache, static-arg guard).
"""

import threading
import time

import numpy as np
import pytest

import jax

from singa_tpu import (goodput, introspect, layer, model, observe, opt,
                       overlap, tensor)
from singa_tpu.device import get_default_device
from singa_tpu.health import HealthError, HealthMonitor


class MLP(model.Model):
    def __init__(self, hidden=32):
        super().__init__()
        self.l1 = layer.Linear(hidden)
        self.r1 = layer.ReLU()
        self.l2 = layer.Linear(hidden)
        self.r2 = layer.ReLU()
        self.l3 = layer.Linear(10)
        self.loss_fn = layer.SoftMaxCrossEntropy()

    def forward(self, x):
        return self.l3(self.r2(self.l2(self.r1(self.l1(x)))))

    def train_one_batch(self, x, y):
        out = self.forward(x)
        loss = self.loss_fn(out, y)
        self._optimizer(loss)
        return out, loss


def _build(dev, batch=32, feat=16, hidden=32, seed=42, health=None):
    """A freshly-initialized compiled model: seeding the device rng
    before init makes two builds bit-identical (the A/B tests rely on
    it)."""
    dev.rng_state = jax.random.PRNGKey(seed)
    rng = np.random.RandomState(0)
    X = rng.randn(batch, feat).astype(np.float32)
    Y = rng.randint(0, 10, batch).astype(np.int32)
    m = MLP(hidden=hidden)
    m.set_optimizer(opt.SGD(lr=0.1, momentum=0.9))
    tx, ty = tensor.from_numpy(X, dev), tensor.from_numpy(Y, dev)
    m.compile([tx], is_train=True, use_graph=True, health=health)
    return m, tx, ty


def _no_prefetch_threads():
    return not any(t.name.startswith("singa-prefetch")
                   for t in threading.enumerate() if t.is_alive())


# ---- DevicePrefetcher ------------------------------------------------------

def test_prefetcher_yields_device_tensors_in_order(dev):
    m, tx, ty = _build(dev)
    src = [(np.full((4, 16), i, np.float32), np.full(4, i, np.int32))
           for i in range(5)]
    with overlap.prefetch_to_device(iter(src), m, size=2) as it:
        got = list(it)
    assert len(got) == 5
    for i, (xb, yb) in enumerate(got):
        assert isinstance(xb, tensor.Tensor)
        assert isinstance(xb.data, jax.Array)  # already on device
        assert float(np.asarray(xb.numpy())[0, 0]) == i  # order preserved
        assert yb.data.dtype == np.int32  # dtype survives the transfer
    assert _no_prefetch_threads()
    reg = observe.get_registry()
    assert reg.get("singa_prefetch_batches_total").value() == 5
    assert reg.get("singa_prefetch_blocked_seconds").count() == 5
    assert reg.get("singa_prefetch_ring_depth") is not None


def test_prefetcher_passes_static_args_through(dev):
    m, tx, ty = _build(dev)
    src = [(tx, ty, "plain", 7)]
    with overlap.prefetch_to_device(iter(src), m) as it:
        x2, y2, s, n = next(it)
    assert isinstance(x2, tensor.Tensor) and isinstance(y2, tensor.Tensor)
    assert s == "plain" and n == 7  # non-arrays untouched
    np.testing.assert_array_equal(x2.numpy(), tx.numpy())


def test_prefetcher_close_on_early_break(dev):
    m, tx, ty = _build(dev)

    def gen():
        for _ in range(100):
            yield (tx, ty)

    pf = overlap.prefetch_to_device(gen(), m, size=2)
    th = pf._thread
    for i, _b in enumerate(pf):
        if i == 1:
            break
    pf.close()
    assert not th.is_alive()
    pf.close()  # idempotent


def test_prefetcher_propagates_source_error(dev):
    m, tx, ty = _build(dev)

    def bad():
        yield (tx, ty)
        raise ValueError("bad source batch")

    pf = overlap.prefetch_to_device(bad(), m)
    next(pf)
    with pytest.raises(ValueError, match="bad source batch"):
        next(pf)
    assert _no_prefetch_threads()
    with pytest.raises(StopIteration):  # raised once, then exhausted
        next(pf)


def test_prefetcher_requires_device_or_model():
    with pytest.raises(ValueError, match="needs a model"):
        overlap.DevicePrefetcher(iter([]))
    m = MLP()  # never compiled: no device yet
    with pytest.raises(ValueError, match="no device"):
        overlap.DevicePrefetcher(iter([]), model=m)


def test_prefetcher_applies_dist_input_sharding(dev):
    """After the first step resolves `_dist_shardings`, prefetched
    batches carry the model's batch sharding, so `_invoke_step`'s put()
    short-circuits (the zero-copy step-path contract)."""
    from singa_tpu.parallel import data_parallel_mesh
    dev.rng_state = jax.random.PRNGKey(0)
    rng = np.random.RandomState(0)
    X = rng.randn(32, 16).astype(np.float32)
    Y = rng.randint(0, 10, 32).astype(np.int32)
    m = MLP()
    m.set_optimizer(opt.DistOpt(opt.SGD(lr=0.1),
                                mesh=data_parallel_mesh(8)))
    tx, ty = tensor.from_numpy(X, dev), tensor.from_numpy(Y, dev)
    m.compile([tx], is_train=True, use_graph=True)
    m(tx, ty)  # builds the step, resolving _dist_shardings
    assert m._dist_shardings is not None
    expect = m._dist_shardings[1]
    with overlap.prefetch_to_device(iter([(X, Y)]), m) as it:
        xb, yb = next(it)
    assert xb.data.sharding == expect
    m(xb, yb)  # the prefetched batch dispatches through the real step


def test_prefetch_producer_spans_not_booked_to_data_wait(dev):
    """A wrapped source's OWN data.wait spans (NumpyBatchIter emits
    them around its queue waits) fire on the producer thread, where
    that time is overlapped with training — suppress_spans keeps them
    out of the goodput ledger, and the iterator's consumer-blocked
    histogram stays quiet too; only the consumer's ring wait books."""
    from singa_tpu import data
    tracker = goodput.install()
    try:
        m, tx, ty = _build(dev)
        sleep_s, n = 0.05, 5

        def src():
            for _ in range(n):
                with observe.span("data.wait"):
                    time.sleep(sleep_s)
                data._record_consumer_wait("numpy", sleep_s)
                yield (tx, ty)

        b0 = tracker.snapshot()["buckets"]["data_wait"]
        with overlap.prefetch_to_device(src(), m, size=2) as it:
            for _ in it:
                time.sleep(sleep_s * 1.5)  # consumer slower: ring full
        booked = tracker.snapshot()["buckets"]["data_wait"] - b0
        # the producer emitted n*sleep_s of span wall time; at most the
        # consumer's first-batch ring wait (~1 sleep) is real stall
        assert booked < 0.5 * n * sleep_s, (booked, n * sleep_s)
        # the "consumer" histogram saw a background thread, not the
        # training loop: nothing recorded
        h = observe.get_registry().get("singa_data_consumer_blocked_seconds")
        assert h is None or h.count(iter="numpy") == 0
    finally:
        goodput.uninstall()


# ---- Model.fit(prefetch_to_device=) acceptance -----------------------------

def test_fit_prefetch_cuts_data_wait_bitwise_identical(dev):
    """ISSUE-5 acceptance: with a deliberately slow iterator,
    prefetch_to_device=2 cuts the data_wait bucket >=50% vs prefetch
    off on the same workload, with bitwise-identical losses and
    compile_count == 1 on the cached path."""
    tracker = goodput.install()
    # fit dispatches steps without waiting for them, so by itself the
    # loop is as fast as its iterator and nothing is there to overlap.
    # Per-step profiling fences every step (the loop of a device-bound
    # job); the model is sized so that fenced step (~50 ms) stays well
    # above the fixed stall, which in turn is large beside the
    # prefetcher's own per-batch cost (~1 ms).
    sleep_s = 0.02
    prev_profiling = (dev.verbosity, dev.skip_iteration)
    dev.SetVerbosity(1)
    dev.SetSkipIteration(0)
    try:
        m_off, tx, ty = _build(dev, batch=512, feat=2048, hidden=2048)
        m_on, _, _ = _build(dev, batch=512, feat=2048, hidden=2048)
        # compile + warm both with the SAME number of steps (the models
        # must enter the measured fits in identical states)
        for mm in (m_off, m_on):
            dev.rng_state = jax.random.PRNGKey(1)
            mm(tx, ty)
            mm(tx, ty)

        class Slow:
            def __iter__(self):
                for _ in range(6):
                    time.sleep(sleep_s)  # the injected host-side stall
                    yield (tx, ty)

        reg = observe.get_registry()
        compiles0 = reg.get("singa_model_compile_total").value(
            batch_class="512")
        dev.rng_state = jax.random.PRNGKey(7)
        b0 = tracker.snapshot()["buckets"]["data_wait"]
        hist_off = m_off.fit(Slow(), epochs=1)
        b1 = tracker.snapshot()["buckets"]["data_wait"]
        dev.rng_state = jax.random.PRNGKey(7)
        hist_on = m_on.fit(Slow(), epochs=1, prefetch_to_device=2)
        b2 = tracker.snapshot()["buckets"]["data_wait"]
        wait_off, wait_on = b1 - b0, b2 - b1
        assert wait_off >= 4 * sleep_s, (wait_off, sleep_s)
        assert wait_on <= 0.5 * wait_off, (wait_on, wait_off)
        # same inputs, same rng stream, same executables -> bitwise equal
        assert hist_on == hist_off
        # cached path: the fits added no compile and no recompile
        assert reg.get("singa_model_compile_total").value(
            batch_class="512") == compiles0
        assert reg.get("singa_model_recompile_total") is None
        assert _no_prefetch_threads()
    finally:
        dev.SetVerbosity(prev_profiling[0])
        dev.SetSkipIteration(prev_profiling[1])
        del dev.step_times[:]
        goodput.uninstall()


def test_fit_prefetch_normal_exit_and_reiteration(dev):
    """Two epochs over a list: the per-epoch prefetcher drains and
    closes; history matches the non-prefetched run on a twin model."""
    m_a, tx, ty = _build(dev, seed=3)
    m_b, _, _ = _build(dev, seed=3)
    batches = [(tx, ty)] * 3
    dev.rng_state = jax.random.PRNGKey(5)
    h_a = m_a.fit(batches, epochs=2)
    dev.rng_state = jax.random.PRNGKey(5)
    h_b = m_b.fit(batches, epochs=2, prefetch_to_device=2)
    assert h_a == h_b
    assert len(h_b) == 2
    assert _no_prefetch_threads()


def test_fit_prefetch_health_halt_closes_prefetcher(dev, tmp_path):
    """HealthError out of fit (halt policy) must not leak the producer
    thread — the finally on the epoch loop closes it."""
    mon = HealthMonitor(policy="halt", out_dir=str(tmp_path))
    m, tx, ty = _build(dev, health=mon)
    X = np.asarray(tx.numpy()).copy()
    X[0, 0] = np.nan
    bad = tensor.from_numpy(X, dev)
    batches = [(tx, ty), (bad, ty), (tx, ty)]
    with pytest.raises(HealthError):
        m.fit(batches, epochs=1, prefetch_to_device=2)
    assert _no_prefetch_threads()


def test_fit_prefetch_skip_step_semantics_unchanged(dev, tmp_path):
    """skip_step under prefetch: the NaN update is still discarded
    in-graph, params roll back, and the loop keeps going."""
    mon = HealthMonitor(policy="skip_step", out_dir=str(tmp_path))
    m, tx, ty = _build(dev, health=mon)
    m(tx, ty)
    before = {k: np.asarray(jax.device_get(v.data))
              for k, v in m.get_params().items()}
    X = np.asarray(tx.numpy()).copy()
    X[0, 0] = np.nan
    bad = tensor.from_numpy(X, dev)
    hist = m.fit([(bad, ty)], epochs=1, prefetch_to_device=2)
    assert mon.last_action == "skip"
    assert len(hist) == 1
    for k, v in m.get_params().items():
        np.testing.assert_array_equal(
            before[k], np.asarray(jax.device_get(v.data)), err_msg=k)
    assert _no_prefetch_threads()


# ---- async checkpointing ---------------------------------------------------

def test_async_save_returns_before_durable_then_roundtrips(dev, tmp_path):
    """The save returns with the write still pending; the barrier makes
    it durable; load_checkpoint restores bit-identical state."""
    m, tx, ty = _build(dev)
    m(tx, ty)
    path = m.save_checkpoint(str(tmp_path / "ck"), step=0)
    # returned with the background write in flight: not yet durable
    assert overlap.pending_checkpoints() == 1
    reg = observe.get_registry()
    assert reg.get("singa_checkpoint_async_pending").value() == 1
    assert reg.get("singa_checkpoint_async_total").value() == 1
    overlap.wait_for_checkpoints()
    assert overlap.pending_checkpoints() == 0
    assert reg.get("singa_checkpoint_async_pending").value() == 0
    m2, _, _ = _build(dev, seed=9)  # different init: restore must win
    m2(tx, ty)
    m2.load_checkpoint(path)
    for k, v in m.get_params().items():
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(v.data)),
            np.asarray(jax.device_get(m2.get_params()[k].data)), err_msg=k)


def test_next_save_barriers_previous(dev, tmp_path):
    m, tx, ty = _build(dev)
    m(tx, ty)
    p0 = m.save_checkpoint(str(tmp_path / "ck"), step=0)
    m.save_checkpoint(str(tmp_path / "ck"), step=1)
    # the second save waited for the first: only ITS write is pending
    assert overlap.pending_checkpoints() == 1
    m.load_checkpoint(p0)  # load barriers the rest + restores save #0
    assert overlap.pending_checkpoints() == 0


def test_load_checkpoint_roundtrips_async_save_resume(dev, tmp_path):
    """Bit-identical resume through an async checkpoint: train 2 steps,
    async-save, train 2 more; restore and replay — identical params."""
    m, tx, ty = _build(dev)
    m(tx, ty)
    m(tx, ty)
    path = m.save_checkpoint(str(tmp_path / "ck"), step=2)
    m(tx, ty)
    m(tx, ty)
    after = {k: np.asarray(jax.device_get(v.data))
             for k, v in m.get_params().items()}
    m2, _, _ = _build(dev, seed=11)
    m2.load_checkpoint(path)  # barrier runs inside
    m2(tx, ty)
    m2(tx, ty)
    for k, v in m2.get_params().items():
        np.testing.assert_array_equal(
            after[k], np.asarray(jax.device_get(v.data)), err_msg=k)


def test_wait_for_checkpoints_reraises_deferred_failure():
    """A background write failure is surfaced by the barrier (chained
    under a RuntimeError naming the path), never swallowed — and the
    pending list is drained so the failure doesn't re-raise forever."""

    class BoomCk:
        def wait_until_finished(self):
            raise OSError("disk full behind your back")

    overlap._register_pending(
        overlap._PendingSave(BoomCk(), "/ckpt/step_9"))
    assert overlap.pending_checkpoints() == 1
    with pytest.raises(RuntimeError, match="step_9") as ei:
        overlap.wait_for_checkpoints()
    assert isinstance(ei.value.__cause__, OSError)
    assert overlap.pending_checkpoints() == 0
    overlap.wait_for_checkpoints()  # drained: the barrier is clean again


def test_overlap_report_constructs_no_checkpointer(monkeypatch):
    """A /statusz scrape of a process that never checkpoints must not
    spin up orbax's resident worker pools: the report reads counters
    only, and the AsyncCheckpointer is built by the first save."""
    monkeypatch.setattr(overlap, "_async_ck", None)
    assert "async-ckpt: pending=0" in overlap.overlap_report()
    assert overlap._async_ck is None   # nothing constructed


def test_async_save_books_only_blocking_portion(dev, tmp_path):
    """Goodput: the checkpoint bucket sees the snapshot + barrier spans,
    and the explicit-sync save books its full write — both via the
    checkpoint.* span names (checkpoint.wait mapped in SPAN_BUCKETS)."""
    assert goodput.SPAN_BUCKETS["checkpoint.wait"] == "checkpoint"
    tracker = goodput.install()
    try:
        m, tx, ty = _build(dev)
        m(tx, ty)
        m.save_checkpoint(str(tmp_path / "ck"), step=0)
        overlap.wait_for_checkpoints()
        snap = tracker.snapshot()
        assert snap["buckets"]["checkpoint"] > 0.0
    finally:
        goodput.uninstall()


# ---- step-dispatch fast path -----------------------------------------------

def test_dispatch_cache_one_variant_per_signature(dev, monkeypatch):
    m, tx, ty = _build(dev)
    m(tx, ty)
    # a repeat step does O(inputs) host work: one key, one dict lookup,
    # and neither of the two functions that walk every state leaf
    calls = []
    for name in ("signature", "build_compiled"):
        real = getattr(introspect, name)
        monkeypatch.setattr(
            introspect, name,
            lambda *a, _n=name, _r=real, **k: calls.append(_n) or _r(*a, **k))
    for _ in range(2):
        m(tx, ty)
    assert calls == []
    (ex,) = m._compiled_step.values()   # one executor a step tag
    assert len(ex) == 1                 # one variant a (tag, signature)
    (variant,) = ex._execs.values()
    assert variant.run is not None and variant.fresh is False
    # a second batch-size class adds exactly one more variant
    X2 = np.zeros((16, 16), np.float32)
    Y2 = np.zeros(16, np.int32)
    m(tensor.from_numpy(X2, dev), tensor.from_numpy(Y2, dev))
    assert len(ex) == 2 and list(m._compiled_step) == [0]
    assert calls == ["signature", "build_compiled"]
    reg = observe.get_registry()
    assert reg.get("singa_model_compile_total").value(batch_class="32") == 1
    assert reg.get("singa_model_compile_total").value(batch_class="16") == 1
    assert reg.get("singa_model_recompile_total").value(
        batch_class="16") == 1


def test_dispatch_fast_path_rejects_changed_static_args(dev):
    class WithFlag(model.Model):
        def __init__(self):
            super().__init__()
            self.l1 = layer.Linear(10)
            self.loss_fn = layer.SoftMaxCrossEntropy()

        def forward(self, x):
            return self.l1(x)

        def train_one_batch(self, x, y, flag):
            loss = self.loss_fn(self.forward(x), y)
            self._optimizer(loss)
            return loss

    dev.rng_state = jax.random.PRNGKey(0)
    rng = np.random.RandomState(0)
    X = rng.randn(8, 16).astype(np.float32)
    Y = rng.randint(0, 10, 8).astype(np.int32)
    m = WithFlag()
    m.set_optimizer(opt.SGD(lr=0.1))
    tx, ty = tensor.from_numpy(X, dev), tensor.from_numpy(Y, dev)
    m.compile([tx], is_train=True, use_graph=True)
    m(tx, ty, 1)
    m(tx, ty, 1)  # same static arg: cached dispatch
    with pytest.raises(ValueError, match="static args"):
        m(tx, ty, 2)  # changed static arg must not be silently ignored
    with pytest.raises(ValueError, match="static args"):
        m(tx, ty)     # arity change either


def test_dispatch_fast_path_losses_match_first_step(dev):
    """The cached dispatch runs the same executable: deterministic rng
    stream means a twin model replaying the same calls matches every
    step, not just the slow-path first one."""
    m1, tx, ty = _build(dev, seed=13)
    m2, _, _ = _build(dev, seed=13)
    dev.rng_state = jax.random.PRNGKey(1)
    l1 = [float(m1(tx, ty)[1].numpy()) for _ in range(4)]
    dev.rng_state = jax.random.PRNGKey(1)
    l2 = [float(m2(tx, ty)[1].numpy()) for _ in range(4)]
    assert l1 == l2


def test_prefetcher_detects_producer_death_without_sentinel(
        dev, monkeypatch):
    """ISSUE-10 bugfix: a producer thread that dies WITHOUT posting its
    error sentinel (interpreter-level death: the try/finally never ran)
    used to park the consumer's ring get() forever. The bounded-wait
    loop now re-checks producer liveness and raises naming the thread
    instead of hanging the epoch."""
    # simulate the hard death: the producer body exits immediately,
    # bypassing the sentinel-posting finally entirely
    monkeypatch.setattr(overlap.DevicePrefetcher, "_produce",
                        lambda self: None)
    pf = overlap.DevicePrefetcher(iter([(1,)]), device=dev)
    pf._thread.join(timeout=5.0)
    assert not pf._thread.is_alive()
    t0 = time.perf_counter()
    with pytest.raises(RuntimeError, match=pf._thread.name):
        next(pf)
    assert time.perf_counter() - t0 < 3.0   # detected, not timed out
    pf.close()


def test_prefetcher_sentinel_death_still_raises_source_error(dev):
    """The ordinary death path (source raises, sentinel posted) keeps
    its contract: the source error is re-raised, not the new
    dead-thread RuntimeError."""

    def bad():
        yield (1,)
        raise ValueError("source exploded")

    pf = overlap.DevicePrefetcher(bad(), device=dev)
    next(pf)
    with pytest.raises(ValueError, match="source exploded"):
        next(pf)
    pf.close()
