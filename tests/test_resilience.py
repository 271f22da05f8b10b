"""Resilience layer: elastic fault-tolerant training (ISSUE-6).

Every recovery path is DRIVEN, not trusted: a deterministic
`resilience.FaultPlan` fails the Nth checkpoint write, delays/fails the
durability barrier, raises (or delivers a real SIGTERM) mid-epoch at
step K — and the tests assert the controller survives each one. The
acceptance test kills a run mid-epoch on the conftest's 8 virtual
devices and auto-resumes it onto a 4-device mesh, matching the
uninterrupted loss curve.
"""

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

import jax  # noqa: E402

from singa_tpu import (health, layer, model as model_mod, observe,  # noqa: E402
                       opt, overlap, resilience, tensor)
from singa_tpu.parallel import data_parallel_mesh  # noqa: E402


@pytest.fixture(autouse=True)
def _fault_hygiene():
    yield
    resilience.clear_fault_plan()


class Net(model_mod.Model):
    def __init__(self):
        super().__init__()
        self.fc1 = layer.Linear(16)
        self.relu = layer.ReLU()
        self.fc2 = layer.Linear(4)
        self.sce = layer.SoftMaxCrossEntropy()

    def forward(self, x):
        return self.fc2(self.relu(self.fc1(x)))

    def train_one_batch(self, x, y):
        loss = self.sce(self.forward(x), y)
        self.optimizer(loss)
        return loss


def _data(seed=7):
    rng = np.random.RandomState(seed)
    X = rng.randn(16, 8).astype(np.float32)
    Y = rng.randint(0, 4, 16).astype(np.int32)
    return X, Y


def _build(dev, n_mesh=8, seed=7, monitor=None):
    """Fresh Net on an `n_mesh`-device data mesh (None = single device),
    deterministically seeded so runs are comparable across builds."""
    dev.rng_state = jax.random.key(seed)
    X, Y = _data(seed)
    m = Net()
    if n_mesh:
        m.set_optimizer(opt.DistOpt(opt.SGD(lr=0.1, momentum=0.9),
                                    mesh=data_parallel_mesh(n_mesh)))
    else:
        m.set_optimizer(opt.SGD(lr=0.1, momentum=0.9))
    tx = tensor.from_numpy(X, dev)
    ty = tensor.from_numpy(Y, dev)
    m.compile([tx], is_train=True, use_graph=True, health=monitor)
    return m, tx, ty


_REF_CACHE = {}


def _ref_losses(dev, steps=8, n_mesh=8, seed=7):
    """Uninterrupted-run loss curve (cached per config: the reference
    arm is identical across tests, no need to retrain it per test)."""
    key = (steps, n_mesh, seed)
    if key not in _REF_CACHE:
        m, tx, ty = _build(dev, n_mesh, seed)
        _REF_CACHE[key] = [float(m(tx, ty).numpy()) for _ in range(steps)]
    return _REF_CACHE[key]


def _mk_complete(ckpt_dir, step):
    """Craft a minimal COMPLETE checkpoint entry (dir + manifest) for
    discovery/retention tests that never restore it."""
    d = os.path.join(str(ckpt_dir), f"step_{step}")
    os.makedirs(d)
    resilience.write_manifest(d, {"kind": "singa_ckpt_manifest",
                                  "version": 1, "step": int(step)})
    return d


# ---- manifests -------------------------------------------------------------

def test_manifest_roundtrip_and_atomicity(dev, tmp_path):
    m, _tx, _ty = _build(dev, n_mesh=None)
    d = tmp_path / "step_4"
    d.mkdir()
    man = resilience.build_manifest(m, step=4, status="ok")
    assert man["mesh"]["n_devices"] == len(jax.devices())
    assert man["params"]["fc1.W"]["shape"] == [8, 16]
    assert man["n_opt_slots"] == len(m._optimizer.state_arrays())
    path = resilience.write_manifest(str(d), man)
    assert path == resilience.manifest_path(str(d))
    assert not os.path.exists(path + ".tmp")  # atomic: tmp replaced away
    got = resilience.read_manifest(str(d))
    assert got["step"] == 4 and got["status"] == "ok"
    assert got["params"] == man["params"]
    assert resilience.is_complete_checkpoint(str(d))


def test_read_manifest_rejects_garbage(tmp_path):
    d = tmp_path / "step_1"
    d.mkdir()
    assert resilience.read_manifest(str(d)) is None          # missing
    mp = resilience.manifest_path(str(d))
    with open(mp, "w") as f:
        f.write("{not json")
    assert resilience.read_manifest(str(d)) is None          # unparseable
    with open(mp, "w") as f:
        json.dump({"kind": "something_else", "step": 1}, f)
    assert resilience.read_manifest(str(d)) is None          # wrong kind
    with open(mp, "w") as f:
        json.dump({"kind": "singa_ckpt_manifest", "step": "x"}, f)
    assert resilience.read_manifest(str(d)) is None          # bad step
    assert not resilience.is_complete_checkpoint(str(d))


def test_validate_manifest_catches_param_mismatch(dev, tmp_path):
    m, _tx, _ty = _build(dev, n_mesh=None)
    man = resilience.build_manifest(m, step=1)
    assert resilience.validate_manifest(man, m) == []
    bad = json.loads(json.dumps(man))
    bad["params"]["fc1.W"]["shape"] = [8, 99]
    problems = resilience.validate_manifest(bad, m)
    assert len(problems) == 1 and "fc1.W" in problems[0]
    bad2 = json.loads(json.dumps(man))
    del bad2["params"]["fc2.b"]
    bad2["params"]["ghost.W"] = {"shape": [1], "dtype": "float32"}
    problems = resilience.validate_manifest(bad2, m)
    assert any("fc2.b" in p for p in problems)
    assert any("ghost.W" in p for p in problems)
    # a mesh delta is NOT a problem — resharding is the feature
    bad3 = json.loads(json.dumps(man))
    bad3["mesh"]["n_devices"] = 1024
    assert resilience.validate_manifest(bad3, m) == []


# ---- discovery & retention -------------------------------------------------

def test_latest_checkpoint_skips_incomplete_and_corrupt(tmp_path):
    assert resilience.latest_checkpoint(str(tmp_path)) is None
    _mk_complete(tmp_path, 2)
    d5 = tmp_path / "step_5"           # half-written: no manifest
    d5.mkdir()
    d9 = tmp_path / "step_9"           # corrupt manifest
    d9.mkdir()
    with open(resilience.manifest_path(str(d9)), "w") as f:
        f.write("{broken")
    got = resilience.latest_checkpoint(str(tmp_path))
    assert got is not None
    path, man = got
    assert path.endswith("step_2") and man["step"] == 2
    allc = resilience.list_checkpoints(str(tmp_path), complete_only=False)
    assert [s for s, _p, _m in allc] == [2, 5, 9]
    assert [s for s, _p, m in allc if m is None] == [5, 9]


def test_keep_last_k(tmp_path):
    for s in (1, 2, 3, 4, 5):
        _mk_complete(tmp_path, s)
    incomplete = tmp_path / "step_9"
    incomplete.mkdir()
    removed = resilience.keep_last_k(str(tmp_path), 2)
    assert sorted(os.path.basename(p) for p in removed) == \
        ["step_1", "step_2", "step_3"]
    left = resilience.list_checkpoints(str(tmp_path))
    assert [s for s, _p, _m in left] == [4, 5]
    assert incomplete.is_dir()         # in-flight writes are never GC'd
    assert resilience.keep_last_k(str(tmp_path), 0) == []
    assert resilience.keep_last_k(str(tmp_path), 5) == []


# ---- save_checkpoint: half-written reclamation (ISSUE-6 satellite) ---------

def test_half_written_step_overwritable_by_default(dev, tmp_path):
    m, tx, ty = _build(dev, n_mesh=None)
    m(tx, ty)
    # a crashed writer's leftover: the step dir exists, no manifest
    stale = tmp_path / "ck" / "step_0"
    stale.mkdir(parents=True)
    (stale / "junk").write_text("half-written")
    path = m.save_checkpoint(str(tmp_path / "ck"), step=0)  # no overwrite=
    overlap.wait_for_checkpoints()
    assert not (stale / "junk").exists()   # step_0 name vacated, rewritten
    # ...but the leftover was set ASIDE, not destroyed (a plain-API
    # save never writes a manifest yet may be a complete checkpoint)
    assert (tmp_path / "ck" / "step_0.reclaimed" / "junk").exists()
    m2, _tx, _ty = _build(dev, n_mesh=None, seed=9)
    m2.load_checkpoint(path)               # restorable: a real checkpoint
    for k, v in m.get_params().items():
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(v.data)),
            np.asarray(jax.device_get(m2.get_params()[k].data)), err_msg=k)


def test_set_aside_checkpoints_bounded(tmp_path):
    """Review fix: reclaiming the same step in a crash-restart loop
    must not grow disk without bound — set_aside_checkpoint keeps the
    newest `keep` set-asides and deletes older ones."""
    base = str(tmp_path / "step_0")
    for i in range(6):
        os.makedirs(base)
        with open(os.path.join(base, "x"), "w", encoding="utf-8") as f:
            f.write(str(i))
        resilience.set_aside_checkpoint(base, ".reclaimed")
        time.sleep(0.01)               # distinct mtimes for the pruner
    aside = [n for n in os.listdir(tmp_path)
             if n.startswith("step_0.reclaimed")]
    assert len(aside) == 3                  # bounded (names recycle)
    survived = set()
    for n in aside:
        with open(str(tmp_path / n / "x"), encoding="utf-8") as f:
            survived.add(f.read())
    assert survived == {"3", "4", "5"}      # ...and the newest survive


def test_complete_step_still_raises_without_overwrite(dev, tmp_path):
    m, tx, ty = _build(dev, n_mesh=None)
    m(tx, ty)
    path = m.save_checkpoint(str(tmp_path / "ck"), step=0)
    overlap.wait_for_checkpoints()
    resilience.write_manifest(path, resilience.build_manifest(m, 0))
    with pytest.raises(ValueError):        # manifested == durable data
        m.save_checkpoint(str(tmp_path / "ck"), step=0)
    overlap.wait_for_checkpoints()
    # explicit overwrite works AND drops the now-stale manifest
    m.save_checkpoint(str(tmp_path / "ck"), step=0, overwrite=True)
    overlap.wait_for_checkpoints()
    assert not resilience.is_complete_checkpoint(path)


def test_load_checkpoint_validates_against_manifest(dev, tmp_path):
    m, tx, ty = _build(dev, n_mesh=None)
    m(tx, ty)
    path = m.save_checkpoint(str(tmp_path / "ck"), step=1)
    overlap.wait_for_checkpoints()
    man = resilience.build_manifest(m, 1)
    man["params"]["fc1.W"]["shape"] = [8, 99]   # wrong model family
    resilience.write_manifest(path, man)
    m2, _tx, _ty = _build(dev, n_mesh=None, seed=9)
    with pytest.raises(ValueError, match="does not fit"):
        m2.load_checkpoint(path)
    m2.load_checkpoint(path, validate=False)    # explicit escape hatch


# ---- fault injection plumbing ----------------------------------------------

def test_fault_plan_matching_is_deterministic():
    plan = resilience.FaultPlan()
    plan.fail("p", nth=2)
    plan.fail("q", step=5)
    plan.fire("p")                       # arrival 1: no match
    with pytest.raises(RuntimeError, match="injected fault"):
        plan.fire("p")                   # arrival 2: fires
    plan.fire("p")                       # consumed (times=1)
    plan.fire("q", step=4)
    with pytest.raises(RuntimeError):
        plan.fire("q", step=5)
    assert plan.count("p") == 3 and plan.count("q") == 2
    assert [k for _pt, _n, k in plan.fired] == ["fail", "fail"]
    # no plan installed -> fault_point is a no-op
    resilience.clear_fault_plan()
    resilience.fault_point("p")


def test_barrier_delay_and_deferred_failure_injection(tmp_path):
    tree = {"a": np.arange(8, dtype=np.float32)}
    overlap.start_async_save(str(tmp_path / "s0"), tree)
    plan = resilience.install_fault_plan(
        resilience.FaultPlan().delay("ckpt.wait", 0.25))
    t0 = time.perf_counter()
    overlap.wait_for_checkpoints()
    assert time.perf_counter() - t0 >= 0.25   # the barrier was delayed
    assert plan.fired and plan.fired[0][2] == "delay"
    # a deferred write failure surfaces at the barrier, naming the path
    overlap.start_async_save(str(tmp_path / "s1"), tree)
    resilience.install_fault_plan(resilience.FaultPlan().fail(
        "ckpt.wait", exc=RuntimeError("deferred write exploded")))
    with pytest.raises(RuntimeError, match="async checkpoint write"):
        overlap.wait_for_checkpoints()
    assert overlap.pending_checkpoints() == 0
    resilience.clear_fault_plan()
    c = observe.get_registry().get("singa_resilience_faults_injected_total")
    assert c.value(kind="delay") == 1 and c.value(kind="fail") == 1


def test_atexit_barrier_prints_deferred_failure(tmp_path):
    """ISSUE-6 satellite: a deferred async-write failure at interpreter
    exit is PRINTED (the atexit barrier re-raises; Python reports it),
    not swallowed — subprocess-based, mirroring test_introspect's CLI
    smoke pattern."""
    script = (
        "import os, sys\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        f"sys.path.insert(0, {_ROOT!r})\n"
        "import numpy as np\n"
        "from singa_tpu import overlap, resilience\n"
        f"overlap.start_async_save(os.path.join({str(tmp_path)!r}, "
        "'ck'), {'a': np.arange(8, dtype=np.float32)})\n"
        "resilience.install_fault_plan(resilience.FaultPlan().fail(\n"
        "    'ckpt.wait', exc=RuntimeError('deferred write exploded')))\n"
        "print('exiting with a pending save')\n")
    out = subprocess.run([sys.executable, "-c", script], cwd=_ROOT,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         capture_output=True, text=True, timeout=300)
    assert "exiting with a pending save" in out.stdout
    assert "deferred write exploded" in out.stderr
    assert "async checkpoint write" in out.stderr   # the barrier's wrap


# ---- the controller: every recovery path -----------------------------------

def test_retry_after_transient_save_failure(dev, tmp_path):
    m, tx, ty = _build(dev)
    plan = resilience.install_fault_plan(
        resilience.FaultPlan().fail("ckpt.save", times=2))
    ctrl = resilience.TrainController(
        m, str(tmp_path / "ck"), save_every_steps=2, retries=3,
        backoff_s=0.01, handle_signals=False)
    report = ctrl.fit([(tx, ty)] * 3, epochs=1)
    assert report["status"] == "completed"
    assert [k for _pt, _n, k in plan.fired] == ["fail", "fail"]
    reg = observe.get_registry()
    assert reg.get("singa_resilience_retries_total").value() == 2
    assert reg.get("singa_resilience_saves_total").value() >= 1
    path, man = resilience.latest_checkpoint(str(tmp_path / "ck"))
    assert man["step"] == 3                # the final save, durable


def test_failed_async_save_never_manifested_complete(dev, tmp_path):
    """Review fix: a deferred async-write failure must leave that save
    UNMANIFESTED. Before the fix it surfaced inside the NEXT save's
    internal barrier, where _retry re-ran save_checkpoint; the retry
    succeeded vacuously (the error was already drained) and the dead
    checkpoint's manifest was flushed as if its bytes had landed —
    discovery would then trust a corrupt checkpoint."""
    m, tx, ty = _build(dev)
    # the step-2 save's deferred write fails at the barrier that
    # settles it (the start of the step-4 save)
    plan = resilience.install_fault_plan(
        resilience.FaultPlan().fail("ckpt.wait", times=1))
    ctrl = resilience.TrainController(
        m, str(tmp_path / "ck"), save_every_steps=2, retries=2,
        backoff_s=0.01, handle_signals=False)
    report = ctrl.fit([(tx, ty)] * 6, epochs=1)
    assert report["status"] == "completed"
    assert [k for _pt, _n, k in plan.fired] == ["fail"]
    # the failed save's dir is on disk but has NO manifest: discovery
    # and retention both ignore it
    s2 = tmp_path / "ck" / "step_2"
    assert s2.is_dir()
    assert not resilience.is_complete_checkpoint(str(s2))
    steps = [s for s, _p, _m in
             resilience.list_checkpoints(str(tmp_path / "ck"))]
    assert steps == [4, 6]
    # the settle consumed the failure outside the retry wrapper: it was
    # dropped (reported), never retried into a vacuous success
    assert observe.get_registry().get(
        "singa_resilience_retries_total").value() == 0


def test_manifest_survives_error_drained_by_another_barrier(dev, tmp_path):
    """Review fix: when ANOTHER actor's wait_for_checkpoints drains the
    shared pending list and consumes a deferred write failure, the
    controller's own (now vacuously clean) barrier must still not
    manifest the dead save — overlap records the failed path past the
    drain (overlap.write_failed) and the settle consults it."""
    ck = str(tmp_path / "ck")
    m, tx, ty = _build(dev, n_mesh=None)
    ctrl = resilience.TrainController(m, ck, handle_signals=False)
    ctrl._step = 1
    ctrl._save()                        # async save, manifest pending
    assert ctrl._pending_manifest is not None
    # an unrelated actor barriers and eats the deferred failure
    resilience.install_fault_plan(resilience.FaultPlan().fail("ckpt.wait"))
    with pytest.raises(RuntimeError, match="async checkpoint write"):
        overlap.wait_for_checkpoints()
    resilience.clear_fault_plan()
    assert overlap.pending_checkpoints() == 0
    assert overlap.write_failed(os.path.join(ck, "step_1"))
    ctrl._settle_pending()              # clean barrier — still no flush
    assert ctrl._pending_manifest is None
    assert resilience.list_checkpoints(ck) == []
    # a fresh save to the same step supersedes the failure record and
    # reclaims the unmanifested debris
    ctrl._last_saved_step = -1
    ctrl._save(final=True)
    _path, man = resilience.latest_checkpoint(ck)
    assert man["step"] == 1


def test_foreign_barrier_failure_does_not_drop_own_manifest(dev, tmp_path):
    """Review fix: when the shared barrier raises for ANOTHER actor's
    save, the controller's own durable save must still be manifested —
    the per-path failure record, not the raise, decides."""
    ck = str(tmp_path / "ck")
    m, tx, ty = _build(dev, n_mesh=None)
    ctrl = resilience.TrainController(m, ck, handle_signals=False)
    ctrl._step = 1
    ctrl._save()                        # our async save: entry 1
    other = str(tmp_path / "other")
    overlap.start_async_save(           # a foreign save: entry 2
        other, {"a": np.arange(8, dtype=np.float32)})
    resilience.install_fault_plan(
        resilience.FaultPlan().fail("ckpt.wait", nth=2))
    ctrl._settle_pending()              # foreign failure reported...
    resilience.clear_fault_plan()
    assert ctrl._pending_manifest is None
    assert overlap.write_failed(other)
    assert not overlap.write_failed(os.path.join(ck, "step_1"))
    _path, man = resilience.latest_checkpoint(ck)
    assert man["step"] == 1             # ...our checkpoint is complete


def test_sync_rewrite_clears_failed_path_record(dev, tmp_path):
    """Review fix: a good SYNCHRONOUS rewrite of a path whose async
    write once failed must supersede the failure record, like a fresh
    async write does — otherwise that step can never be manifested."""
    m, tx, ty = _build(dev, n_mesh=None)
    ck = str(tmp_path / "ck")
    p1 = m.save_checkpoint(ck, step=1, async_save=True)
    resilience.install_fault_plan(resilience.FaultPlan().fail("ckpt.wait"))
    with pytest.raises(RuntimeError, match="async checkpoint write"):
        overlap.wait_for_checkpoints()
    resilience.clear_fault_plan()
    assert overlap.write_failed(p1)
    # the unmanifested debris is reclaimed; the blocking write is
    # durable on return and clears the record
    p2 = m.save_checkpoint(ck, step=1, async_save=False)
    assert p2 == p1
    assert not overlap.write_failed(p1)


def test_preempt_at_already_saved_step_keeps_terminal_status(dev, tmp_path):
    """Review fix: a preemption landing on a step whose cadence save
    already ran (step == _last_saved_step, manifest pending with status
    'ok') must still flush that manifest with status 'preempt' — the
    terminal-status marker is what tooling reads off the manifest."""
    ck = str(tmp_path / "ck")
    m, tx, ty = _build(dev, n_mesh=None)
    resilience.install_fault_plan(resilience.FaultPlan().send_signal(
        "step", signal.SIGTERM, step=3))
    report = resilience.TrainController(
        m, ck, save_every_steps=1, handle_signals=True).fit(
        [(tx, ty)] * 8, epochs=1)
    assert report["status"] == "preempted"
    assert report["final_step"] == 3
    _path, man = resilience.latest_checkpoint(ck)
    assert man["step"] == 3 and man["status"] == "preempt"


def test_save_retries_exhausted_raises(dev, tmp_path):
    m, tx, ty = _build(dev, n_mesh=None)
    resilience.install_fault_plan(
        resilience.FaultPlan().fail("ckpt.save", times=10))
    ctrl = resilience.TrainController(
        m, str(tmp_path / "ck"), save_every_steps=1, retries=2,
        backoff_s=0.01, max_restarts=0, handle_signals=False)
    with pytest.raises(RuntimeError, match="injected fault"):
        ctrl.fit([(tx, ty)] * 2, epochs=1)
    overlap.wait_for_checkpoints()


def test_in_process_restart_after_midepoch_raise(dev, tmp_path):
    """A mid-epoch step failure restores the latest checkpoint and
    replays — the loss curve equals the uninterrupted run's."""
    ref = _ref_losses(dev, steps=8)
    m, tx, ty = _build(dev)
    resilience.install_fault_plan(
        resilience.FaultPlan().fail("step", step=5, times=1))
    ctrl = resilience.TrainController(
        m, str(tmp_path / "ck"), save_every_steps=2, max_restarts=1,
        handle_signals=False)
    report = ctrl.fit([(tx, ty)] * 8, epochs=1)
    assert report["status"] == "completed"
    assert report["restarts"] == 1
    assert observe.get_registry().get(
        "singa_resilience_restarts_total").value() == 1
    got = dict(report["history"])
    assert sorted(got) == list(range(8))
    np.testing.assert_allclose([got[k] for k in range(8)], ref,
                               rtol=1e-6, atol=1e-7)


def test_restart_sees_pending_async_save(dev, tmp_path):
    """Review fix: a crash right after an async save must not lose that
    save to the restart — its manifest was still pending, so the
    restart path settles the write (barrier + manifest flush) before
    scanning, and resumes from the NEWEST checkpoint, not one interval
    back (or, with a single save, none at all)."""
    ref = _ref_losses(dev, steps=8)
    m, tx, ty = _build(dev)
    resilience.install_fault_plan(
        resilience.FaultPlan().fail("step", step=4, times=1))
    ctrl = resilience.TrainController(
        m, str(tmp_path / "ck"), save_every_steps=3, max_restarts=1,
        handle_signals=False)
    report = ctrl.fit([(tx, ty)] * 6, epochs=1)
    assert report["status"] == "completed"
    assert report["restarts"] == 1
    # the ONLY save before the crash was step 3, manifest still pending
    # at the failure: without the settle, resume finds nothing and the
    # restart dies with "no restorable checkpoint"
    assert report["resumed_step"] == 3
    got = dict(report["history"])
    np.testing.assert_allclose([got[k] for k in range(6)], ref[:6],
                               rtol=1e-6, atol=1e-7)


def test_stale_manifested_checkpoint_set_aside_not_deleted(dev, tmp_path):
    """Review fix: a newer MANIFESTED checkpoint whose restore failed
    (possibly transiently) is renamed out of the step_N namespace at
    resume — preserving the data for the operator — instead of being
    rmtree'd; only unmanifested debris is deleted."""
    ck = str(tmp_path / "ck")
    m, tx, ty = _build(dev, n_mesh=None)
    resilience.TrainController(
        m, ck, save_every_steps=2, handle_signals=False).fit(
        [(tx, ty)] * 4, epochs=1)
    # a valid-looking manifest over an EMPTY dir: validation passes
    # (signature matches), the orbax restore itself fails
    bad = tmp_path / "ck" / "step_9"
    bad.mkdir()
    resilience.write_manifest(str(bad),
                              resilience.build_manifest(m, step=9))
    m2, tx, ty = _build(dev, n_mesh=None, seed=9)
    ctrl = resilience.TrainController(
        m2, ck, save_every_steps=2, retries=1, backoff_s=0.01,
        handle_signals=False)
    report = ctrl.fit([(tx, ty)] * 6, epochs=1)
    assert report["status"] == "completed"
    assert report["resumed_step"] == 4
    assert observe.get_registry().get(
        "singa_resilience_corrupt_skipped_total").value() >= 1
    assert not bad.exists()                       # out of discovery's way
    aside = tmp_path / "ck" / "step_9.stale"
    assert aside.is_dir()                         # ...but preserved
    with open(str(aside) + resilience.MANIFEST_SUFFIX) as f:
        assert json.load(f)["step"] == 9          # manifest rode along


def test_restart_budget_exhausted_reraises(dev, tmp_path):
    m, tx, ty = _build(dev, n_mesh=None)
    resilience.install_fault_plan(
        resilience.FaultPlan().fail("step", step=2, times=5))
    ctrl = resilience.TrainController(
        m, str(tmp_path / "ck"), save_every_steps=1, max_restarts=1,
        handle_signals=False)
    with pytest.raises(RuntimeError, match="injected fault"):
        ctrl.fit([(tx, ty)] * 4, epochs=1)
    overlap.wait_for_checkpoints()
    assert observe.get_registry().get(
        "singa_resilience_restarts_total").value() == 1


def test_kill_and_resume_onto_smaller_mesh(dev, tmp_path):
    """THE acceptance test: a run killed mid-epoch on the 8-device mesh
    auto-resumes from the latest VALID checkpoint onto a 4-device mesh
    — corrupt/half-written entries skipped — and the loss curve matches
    the uninterrupted 8-device run within tolerance."""
    ck = str(tmp_path / "ck")
    ref = _ref_losses(dev, steps=8)

    # run 1 (8 devices): dies at step 7. Cadence saves ran at steps 3
    # and 6; step_3's manifest flushed when save 6 ran, step_6's was
    # still pending at the crash -> step_6 is on disk but UNMANIFESTED,
    # so resume must land on step_3.
    m_a, tx, ty = _build(dev, n_mesh=8)
    resilience.install_fault_plan(
        resilience.FaultPlan().fail("step", step=7))
    with pytest.raises(RuntimeError, match="injected fault"):
        resilience.TrainController(
            m_a, ck, save_every_steps=3, max_restarts=0,
            handle_signals=False).fit([(tx, ty)] * 8, epochs=1)
    resilience.clear_fault_plan()
    overlap.wait_for_checkpoints()   # drain the crash's in-flight write

    # sabotage: a corrupt manifest newer than every real checkpoint
    bad = tmp_path / "ck" / "step_99"
    bad.mkdir()
    with open(resilience.manifest_path(str(bad)), "w") as f:
        f.write("{broken")

    # run 2 (4 devices): fresh process-equivalent — new model, SMALLER
    # mesh, same checkpoint dir
    m_b, tx, ty = _build(dev, n_mesh=4)
    ctrl = resilience.TrainController(m_b, ck, save_every_steps=3,
                                      handle_signals=False)
    report = ctrl.fit([(tx, ty)] * 8, epochs=1)
    assert report["status"] == "completed"
    assert report["resumed_step"] == 3
    assert report["final_step"] == 8
    reg = observe.get_registry()
    assert reg.get("singa_resilience_corrupt_skipped_total").value() >= 2
    assert reg.get("singa_resilience_resumed_step").value() == 3
    # the dead timeline was purged on resume: the corrupt step_99 and
    # the unmanifested step_6 can never collide with this run's saves
    assert not bad.exists()
    assert not (tmp_path / "ck" / "step_6").exists() or \
        resilience.is_complete_checkpoint(str(tmp_path / "ck" / "step_6"))
    got = dict(report["history"])
    assert sorted(got) == [3, 4, 5, 6, 7]     # replayed, never re-stepped
    np.testing.assert_allclose([got[k] for k in sorted(got)], ref[3:],
                               rtol=1e-4, atol=1e-5)


def test_preemption_signal_saves_and_resumes(dev, tmp_path):
    """SIGTERM mid-run: the in-flight step finishes, a final checkpoint
    is written + proven durable, fit returns cleanly (status
    "preempted"), and a new incarnation resumes to completion."""
    ck = str(tmp_path / "ck")
    ref = _ref_losses(dev, steps=8)
    prev_handler = signal.getsignal(signal.SIGTERM)
    m, tx, ty = _build(dev)
    resilience.install_fault_plan(resilience.FaultPlan().send_signal(
        "step", signal.SIGTERM, step=3))
    report = resilience.TrainController(
        m, ck, save_every_steps=10, handle_signals=True).fit(
        [(tx, ty)] * 8, epochs=1)
    assert report["status"] == "preempted"
    assert report["final_step"] == 3           # steps 0..2 done, 3 never ran
    assert signal.getsignal(signal.SIGTERM) is prev_handler  # restored
    path, man = resilience.latest_checkpoint(ck)
    assert man["step"] == 3 and man["status"] == "preempt"
    assert observe.get_registry().get(
        "singa_resilience_preempt_total").value() == 1
    resilience.clear_fault_plan()

    m2, tx, ty = _build(dev)
    report2 = resilience.TrainController(
        m2, ck, save_every_steps=10, handle_signals=False).fit(
        [(tx, ty)] * 8, epochs=1)
    assert report2["status"] == "completed"
    assert report2["resumed_step"] == 3
    got = dict(report["history"] + report2["history"])
    np.testing.assert_allclose([got[k] for k in range(8)], ref,
                               rtol=1e-6, atol=1e-7)


def test_fit_rejects_one_shot_iterator(dev, tmp_path):
    """Review fix: a generator-fed controller would silently 'complete'
    at the first restart/resume/epoch re-entry — reject it up front,
    like Model.fit's no-batches guard."""
    m, tx, ty = _build(dev, n_mesh=None)
    ctrl = resilience.TrainController(
        m, str(tmp_path / "ck"), handle_signals=False)
    with pytest.raises(ValueError, match="re-iterable"):
        ctrl.fit((b for b in [(tx, ty)] * 4), epochs=1)


def test_fit_reentry_after_preemption_trains(dev, tmp_path):
    """Review fix: the preemption flag is cleared at fit() entry, so
    calling fit() again on a preempted controller continues training
    instead of instantly returning another stale 'preempted' report."""
    m, tx, ty = _build(dev, n_mesh=None)
    resilience.install_fault_plan(resilience.FaultPlan().send_signal(
        "step", signal.SIGTERM, step=3))
    ctrl = resilience.TrainController(
        m, str(tmp_path / "ck"), save_every_steps=2, handle_signals=True)
    report = ctrl.fit([(tx, ty)] * 6, epochs=1)
    assert report["status"] == "preempted"
    assert report["final_step"] == 3
    resilience.clear_fault_plan()
    report2 = ctrl.fit([(tx, ty)] * 6, epochs=1)
    assert report2["status"] == "completed"
    assert report2["final_step"] == 6


def test_halt_flows_into_save_then_stop(dev, tmp_path):
    """HealthError halt rides the same save-then-stop path: final
    checkpoint (manifest status "halt"), durability barrier, then the
    HealthError propagates with the controller report attached."""
    X, Y = _data()
    mon = health.HealthMonitor(policy="halt", out_dir=str(tmp_path))
    m, tx, ty = _build(dev, n_mesh=None, monitor=mon)
    Xn = X.copy()
    Xn[0, 0] = np.nan
    tnan = tensor.from_numpy(Xn, dev)
    data = [(tx, ty)] * 3 + [(tnan, ty)] + [(tx, ty)] * 2
    ctrl = resilience.TrainController(
        m, str(tmp_path / "ck"), save_every_steps=2, handle_signals=False)
    with pytest.raises(health.HealthError) as ei:
        ctrl.fit(data, epochs=1)
    e = ei.value
    assert e.bundle_path and os.path.exists(e.bundle_path)
    assert e.resilience["status"] == "halted"
    assert e.resilience["final_step"] == 3     # three healthy steps
    path, man = resilience.latest_checkpoint(str(tmp_path / "ck"))
    assert man["step"] == 3 and man["status"] == "halt"
    assert overlap.pending_checkpoints() == 0  # barrier ran on the way out


def test_fit_partial_progress_on_halt(dev, tmp_path):
    """ISSUE-6 satellite: Model.fit must not discard the epoch's loss
    history on a halt — HealthError.partial carries it out."""
    X, Y = _data()
    mon = health.HealthMonitor(policy="halt", out_dir=str(tmp_path))
    m, tx, ty = _build(dev, n_mesh=None, monitor=mon)
    Xn = X.copy()
    Xn[0, 0] = np.nan
    tnan = tensor.from_numpy(Xn, dev)
    with pytest.raises(health.HealthError) as ei:
        m.fit([(tx, ty), (tx, ty), (tnan, ty), (tx, ty)], epochs=1)
    p = ei.value.partial
    assert p is not None and p["epoch"] == 0
    assert p["steps_completed"] == 2 and len(p["losses"]) == 2
    assert np.isfinite(p["last_loss"])
    assert p["losses"][1] == p["last_loss"]


def test_retention_prunes_during_run(dev, tmp_path):
    m, tx, ty = _build(dev, n_mesh=None)
    ctrl = resilience.TrainController(
        m, str(tmp_path / "ck"), save_every_steps=1, keep=2,
        handle_signals=False)
    report = ctrl.fit([(tx, ty)] * 6, epochs=1)
    assert report["status"] == "completed"
    left = resilience.list_checkpoints(str(tmp_path / "ck"))
    assert len(left) == 2 and left[-1][0] == 6


def test_resilience_report_and_statusz_section(dev, tmp_path):
    m, tx, ty = _build(dev, n_mesh=None)
    report = resilience.fit_resilient(
        m, [(tx, ty)] * 2, str(tmp_path / "ck"), save_every_steps=2,
        handle_signals=False)
    assert report["status"] == "completed"
    text = resilience.resilience_report()
    assert "== resilience ==" in text
    assert "status=completed" in text and "saves=" in text
    # and the live surface serves it
    from urllib.request import urlopen

    from singa_tpu import diag
    srv = diag.start_diag_server(port=0)
    try:
        body = urlopen(f"{srv.url}/statusz", timeout=10).read().decode()
        assert "== resilience ==" in body
        assert "resumed_from=0" in body
    finally:
        diag.stop_diag_server()


def test_resume_across_epoch_boundary(dev, tmp_path):
    """The replay cursor spans epochs: 2 epochs x 4 batches killed in
    epoch 1 resumes into epoch 1, not at the start of the stream."""
    ref = _ref_losses(dev, steps=8)
    ck = str(tmp_path / "ck")
    m, tx, ty = _build(dev)
    resilience.install_fault_plan(
        resilience.FaultPlan().fail("step", step=6))
    with pytest.raises(RuntimeError):
        resilience.TrainController(
            m, ck, save_every_steps=2, max_restarts=0,
            handle_signals=False).fit([(tx, ty)] * 4, epochs=2)
    resilience.clear_fault_plan()
    overlap.wait_for_checkpoints()
    m2, tx, ty = _build(dev)
    report = resilience.TrainController(
        m2, ck, save_every_steps=2, handle_signals=False).fit(
        [(tx, ty)] * 4, epochs=2)
    assert report["status"] == "completed"
    assert report["resumed_step"] == 4      # step_4's manifest flushed at 6
    got = dict(report["history"])
    np.testing.assert_allclose([got[k] for k in sorted(got)], ref[4:],
                               rtol=1e-6, atol=1e-7)


@pytest.mark.slow
def test_kill_resume_ab_cli(tmp_path):
    """The tools/kill_resume_suite.sh harness end to end: three real
    subprocesses (baseline, SIGTERM'd, resumed-on-4-devices) and a
    RESILIENCE json record with the loss-curve comparison."""
    out = str(tmp_path / "RESILIENCE_test.json")
    r = subprocess.run(
        [sys.executable, "-m", "singa_tpu.resilience", "--ab",
         "--steps", "12", "--save-every", "3", "--out", out],
        cwd=_ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    with open(out) as f:
        rec = json.load(f)
    assert rec["ok"] is True
    assert rec["killed_status"] == "preempted"
    assert rec["resumed_status"] == "completed"
    assert rec["resumed_step"] > 0
    assert rec["max_abs_loss_delta"] < 1e-4


# ---- retry backoff: decorrelated jitter + total-elapsed cap (ISSUE-10) -----

def test_retry_backoff_uses_decorrelated_jitter(tmp_path, monkeypatch):
    """The backoff sleeps are jittered — drawn from [base, 3 x previous
    sleep], capped — not the lockstep exponential schedule that makes a
    restarted fleet hammer the shared filesystem in unison; every slept
    second lands in singa_resilience_retry_seconds_total."""
    ctrl = resilience.TrainController(
        None, str(tmp_path / "ck"), retries=5, backoff_s=0.01,
        backoff_max_s=0.5, retry_seed=1234, handle_signals=False)
    sleeps = []
    monkeypatch.setattr(time, "sleep", lambda s: sleeps.append(s))
    calls = [0]

    def flaky():
        calls[0] += 1
        if calls[0] <= 3:
            raise OSError("transient")
        return "ok"

    assert ctrl._retry("save", flaky) == "ok"
    assert len(sleeps) == 3
    prev = 0.01
    for s in sleeps:
        assert 0.01 <= s <= min(0.5, max(0.01, prev * 3.0)) + 1e-9
        prev = s
    # jitter, not a fixed schedule: the draws differ (seeded, so this
    # is deterministic) and a different seed gives different sleeps
    assert len({round(s, 9) for s in sleeps}) > 1
    ctrl2 = resilience.TrainController(
        None, str(tmp_path / "ck"), retries=5, backoff_s=0.01,
        backoff_max_s=0.5, retry_seed=99, handle_signals=False)
    sleeps2 = []
    monkeypatch.setattr(time, "sleep", lambda s: sleeps2.append(s))
    calls[0] = 0
    ctrl2._retry("save", flaky)
    assert sleeps2 != sleeps
    reg = observe.get_registry()
    got = reg.get("singa_resilience_retry_seconds_total").value()
    assert got == pytest.approx(sum(sleeps) + sum(sleeps2))
    assert reg.get("singa_resilience_retries_total").value() == 6


def test_retry_jitter_off_keeps_exponential_schedule(tmp_path,
                                                     monkeypatch):
    ctrl = resilience.TrainController(
        None, str(tmp_path / "ck"), retries=3, backoff_s=0.01,
        backoff_mult=2.0, retry_jitter=False, handle_signals=False)
    sleeps = []
    monkeypatch.setattr(time, "sleep", lambda s: sleeps.append(s))

    def always_fails():
        raise OSError("down")

    with pytest.raises(OSError):
        ctrl._retry("save", always_fails)
    assert sleeps == pytest.approx([0.01, 0.02, 0.04])


def test_retry_total_elapsed_cap(tmp_path):
    """max_elapsed_s bounds the retry loop's TOTAL wall time: with
    attempts left, the loop still gives up once the cap is reached —
    a scheduler's grace period does not wait for retries**mult."""
    ctrl = resilience.TrainController(
        None, str(tmp_path / "ck"), retries=1000, backoff_s=0.02,
        retry_jitter=False, max_elapsed_s=0.1, handle_signals=False)
    calls = [0]

    def always_fails():
        calls[0] += 1
        raise OSError("down")

    t0 = time.monotonic()
    with pytest.raises(OSError):
        ctrl._retry("save", always_fails)
    elapsed = time.monotonic() - t0
    assert elapsed < 2.0                   # nowhere near 1000 retries
    assert 1 < calls[0] < 20
    assert any(r.get("event") == "retry_exhausted"
               for r in observe.get_registry().recent)
