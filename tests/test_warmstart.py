"""Zero-compile restarts (ISSUE-20): the warm store round-trips
serialized executables keyed by (name, abstract-signature fingerprint),
classifies every lookup into hit|miss|stale|corrupt, survives corrupt
and fingerprint-mismatched entries by falling back to a fresh compile
that re-exports a clean replacement, evicts beyond keep-last-K, proves
a real cross-process hit in a subprocess, and leaves engine decode
token-identical under warm load."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from singa_tpu import introspect, observe, warmstart

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _fn():
    return jax.jit(lambda x: x * 2 + 1)


def _args():
    return (jnp.arange(8, dtype=jnp.float32),)


# ---- store round-trip -------------------------------------------------------

def test_cold_build_exports_then_warm_build_hits(tmp_path):
    store = warmstart.enable(str(tmp_path / "warm"))
    assert store is not None and warmstart.is_enabled()
    compiled, rec = introspect.build_compiled(_fn(), _args(), "t.fn")
    assert compiled is not None
    assert rec["warm"] == warmstart.RESULT_MISS
    want = np.asarray(_args()[0]) * 2 + 1
    np.testing.assert_allclose(np.asarray(compiled(*_args())), want)
    snap = warmstart.snapshot()
    assert snap["exports"] == 1 and snap["entries"] == 1
    assert snap["lookups"]["miss"] == 1
    # same key + signature again: the store serves the stored blob
    introspect.reset()
    compiled2, rec2 = introspect.build_compiled(_fn(), _args(), "t.fn")
    assert rec2["warm"] == warmstart.RESULT_HIT
    assert rec2["fingerprint"] == rec["fingerprint"]
    np.testing.assert_allclose(np.asarray(compiled2(*_args())), want)
    snap = warmstart.snapshot()
    assert snap["lookups"]["hit"] == 1 and snap["hit_rate"] == 0.5
    # no second export: the hit did not rewrite the entry
    assert snap["exports"] == 1 and snap["entries"] == 1


def test_disabled_store_is_a_clean_noop():
    assert not warmstart.is_enabled()  # conftest isolation
    compiled, rec = introspect.build_compiled(_fn(), _args(), "t.off")
    assert compiled is not None and rec["warm"] is None
    assert warmstart.snapshot()["lookups"] == {
        "hit": 0, "miss": 0, "stale": 0, "corrupt": 0}


def test_fingerprint_differs_by_signature_and_key():
    sig4 = introspect.signature((jnp.zeros(4, jnp.float32),))
    sig8 = introspect.signature((jnp.zeros(8, jnp.float32),))
    assert introspect._sig_fingerprint("k", sig4) \
        != introspect._sig_fingerprint("k", sig8)
    assert introspect._sig_fingerprint("k", sig4) \
        != introspect._sig_fingerprint("k2", sig4)


# ---- integrity fallbacks ----------------------------------------------------

def test_truncated_blob_classifies_corrupt_and_is_replaced(tmp_path):
    warmstart.enable(str(tmp_path / "warm"))
    _, rec = introspect.build_compiled(_fn(), _args(), "t.trunc")
    store = warmstart.get_store()
    bin_path, _meta = store.entry_paths("t.trunc", rec["fingerprint"])
    with open(bin_path, "wb") as f:  # sha-256 mismatch vs the meta
        f.write(b"\x00garbage\x00")
    introspect.reset()
    compiled, rec2 = introspect.build_compiled(_fn(), _args(), "t.trunc")
    assert compiled is not None  # fell back to the fresh compile
    assert rec2["warm"] == warmstart.RESULT_CORRUPT
    want = np.asarray(_args()[0]) * 2 + 1
    np.testing.assert_allclose(np.asarray(compiled(*_args())), want)
    snap = warmstart.snapshot()
    assert snap["lookups"]["corrupt"] == 1
    # the bad entry was deleted and the rebuild re-exported a clean one
    assert snap["exports"] == 2
    blob, result = store.load("t.trunc", rec["fingerprint"])
    assert result == warmstart.RESULT_HIT and blob not in (None, b"")


def test_undeserializable_blob_with_matching_sha_is_corrupt(tmp_path):
    """A blob whose hash verifies but whose bytes jax.export cannot
    deserialize (the deeper corruption) must classify corrupt too —
    caught at the deserialize layer, not the sha check."""
    warmstart.enable(str(tmp_path / "warm"))
    _, rec = introspect.build_compiled(_fn(), _args(), "t.deser")
    store = warmstart.get_store()
    # re-save consistent-but-bogus bytes through the store's own writer
    # so blob sha-256 and meta agree
    assert store.save("t.deser", rec["fingerprint"], b"not-an-export")
    introspect.reset()
    compiled, rec2 = introspect.build_compiled(_fn(), _args(), "t.deser")
    assert compiled is not None
    assert rec2["warm"] == warmstart.RESULT_CORRUPT
    assert warmstart.snapshot()["lookups"]["corrupt"] == 1


def test_fingerprint_mismatch_classifies_stale_and_is_replaced(tmp_path):
    warmstart.enable(str(tmp_path / "warm"))
    _, rec = introspect.build_compiled(_fn(), _args(), "t.stale")
    store = warmstart.get_store()
    _bin, meta_path = store.entry_paths("t.stale", rec["fingerprint"])
    with open(meta_path, encoding="utf-8") as f:
        meta = json.load(f)
    meta["fingerprint"] = "0" * 16  # built for some OTHER signature
    with open(meta_path, "w", encoding="utf-8") as f:
        json.dump(meta, f)
    introspect.reset()
    compiled, rec2 = introspect.build_compiled(_fn(), _args(), "t.stale")
    assert compiled is not None
    assert rec2["warm"] == warmstart.RESULT_STALE
    snap = warmstart.snapshot()
    assert snap["lookups"]["stale"] == 1 and snap["exports"] == 2
    _blob, result = store.load("t.stale", rec["fingerprint"])
    assert result == warmstart.RESULT_HIT


def test_jax_version_mismatch_classifies_stale(tmp_path):
    warmstart.enable(str(tmp_path / "warm"))
    _, rec = introspect.build_compiled(_fn(), _args(), "t.ver")
    store = warmstart.get_store()
    _bin, meta_path = store.entry_paths("t.ver", rec["fingerprint"])
    with open(meta_path, encoding="utf-8") as f:
        meta = json.load(f)
    meta["jax_version"] = "0.0.1"  # a container upgrade ago
    with open(meta_path, "w", encoding="utf-8") as f:
        json.dump(meta, f)
    blob, result = store.load("t.ver", rec["fingerprint"])
    assert blob is None and result == warmstart.RESULT_STALE
    # the distrusted entry is gone: the next lookup is a plain miss
    assert store.load("t.ver", rec["fingerprint"])[1] \
        == warmstart.RESULT_MISS


def test_unparseable_meta_classifies_corrupt(tmp_path):
    warmstart.enable(str(tmp_path / "warm"))
    _, rec = introspect.build_compiled(_fn(), _args(), "t.meta")
    store = warmstart.get_store()
    _bin, meta_path = store.entry_paths("t.meta", rec["fingerprint"])
    with open(meta_path, "w", encoding="utf-8") as f:
        f.write("{not json")
    blob, result = store.load("t.meta", rec["fingerprint"])
    assert blob is None and result == warmstart.RESULT_CORRUPT


# ---- eviction ---------------------------------------------------------------

def test_eviction_keeps_last_k(tmp_path):
    warmstart.enable(str(tmp_path / "warm"), keep=2)
    store = warmstart.get_store()
    for i in range(5):
        path = store.save("t.evict", f"{i:016x}", b"blob-%d" % i)
        assert path is not None
        # mtime is the eviction order; make it strictly increasing
        os.utime(path, (i + 1, i + 1))
    n, nbytes = store.occupancy()
    assert n == 2 and nbytes > 0
    kept = {e["fingerprint"] for e in store.entries()}
    assert kept == {f"{3:016x}", f"{4:016x}"}  # last-2 by mtime
    # eviction never touches other keys' entries
    store.save("t.other", "f" * 16, b"other")
    assert {e["key"] for e in store.entries()} == {"t.evict", "t.other"}


# ---- metrics / reporting ----------------------------------------------------

def test_cache_metrics_and_statusz_section(tmp_path):
    warmstart.enable(str(tmp_path / "warm"))
    introspect.build_compiled(_fn(), _args(), "t.metrics")
    introspect.reset()
    introspect.build_compiled(_fn(), _args(), "t.metrics")
    text = observe.to_prometheus_text()
    assert 'singa_compile_cache_lookups_total{key="t.metrics",' \
        'result="hit"} 1' in text
    assert 'singa_compile_cache_lookups_total{key="t.metrics",' \
        'result="miss"} 1' in text
    assert 'singa_compile_cache_exports_total{key="t.metrics"} 1' in text
    assert "singa_compile_cache_entries 1" in text
    assert "singa_compile_cache_store_bytes" in text
    assert "singa_compile_cache_load_seconds" in text
    rep = warmstart.warm_report()
    assert "== warm start ==" in rep and "hit" in rep
    # the /statusz surface carries the warm section
    import urllib.request
    from singa_tpu import diag
    srv = diag.start_diag_server(port=0)
    try:
        body = urllib.request.urlopen(
            srv.url + "/statusz", timeout=10).read().decode()
    finally:
        diag.stop_diag_server()
    assert "== warm start ==" in body
    # the lookup ring doubles as the warm audit trail
    hist = warmstart.lookup_history()
    assert [h["result"] for h in hist] == ["miss", "hit"]


def test_conftest_isolation_resets_warm_state(tmp_path):
    """The autouse fixture's warmstart.reset() contract: enabling in
    one test must not leak into the next (this pair of asserts runs
    fresh every time), and reset() detaches jax's persistent-cache
    dir."""
    assert not warmstart.is_enabled()
    warmstart.enable(str(tmp_path / "warm"))
    assert jax.config.jax_compilation_cache_dir \
        == os.path.join(str(tmp_path / "warm"), "xla")
    warmstart.reset()
    assert jax.config.jax_compilation_cache_dir is None
    assert warmstart.snapshot()["lookups"] == {
        "hit": 0, "miss": 0, "stale": 0, "corrupt": 0}


_ENV_CACHE_CHILD = textwrap.dedent("""
    import json, os, sys
    sys.path.insert(0, {root!r})
    import jax, jax.numpy as jnp
    from singa_tpu import warmstart
    seen = [jax.config.jax_compilation_cache_dir]
    warmstart.enable({store!r})
    seen.append(jax.config.jax_compilation_cache_dir)
    jax.jit(lambda x: jnp.cumsum(x) * 5)(jnp.arange(16.0)).block_until_ready()
    snap = warmstart.snapshot()
    warmstart.reset()
    seen.append(jax.config.jax_compilation_cache_dir)
    print(json.dumps({{"seen": seen, "xla_cache_dir": snap["xla_cache_dir"]}}))
""")


def test_jax_compilation_cache_dir_is_never_overridden(tmp_path):
    """Where JAX_COMPILATION_CACHE_DIR is set, the XLA cache lives there
    and nowhere else: enable(root) keeps only its serialized executables
    under root, assigns no other cache directory, and reset() puts back
    what the environment asked for, not None. (A subprocess: jax reads
    the variable at import.)"""
    env_dir, store = tmp_path / "from_env", tmp_path / "warm"
    out = subprocess.run(
        [sys.executable, "-c",
         _ENV_CACHE_CHILD.format(root=_ROOT, store=str(store))],
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 JAX_COMPILATION_CACHE_DIR=str(env_dir)),
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert got["seen"] == [str(env_dir)] * 3
    assert got["xla_cache_dir"] == str(env_dir)
    assert os.listdir(env_dir)                    # entries appear under D
    assert not os.path.exists(store / "xla")      # and nowhere else


def test_configure_xla_cache_uses_the_given_dir_without_the_env(tmp_path):
    """Unset, the entry points' fixed directory is the cache; reset()
    (via the store) or a later configure may move it, nothing else."""
    assert "JAX_COMPILATION_CACHE_DIR" not in os.environ  # conftest pops it
    d = str(tmp_path / "fixed")
    try:
        assert warmstart.configure_xla_cache(d) == d
        assert jax.config.jax_compilation_cache_dir == d
        jax.jit(lambda x: x * 7 + 2)(jnp.arange(8.0)).block_until_ready()
        assert os.listdir(d)
    finally:
        warmstart._unconfigure_xla_cache()
    assert jax.config.jax_compilation_cache_dir is None


def test_env_var_enables_store(tmp_path, monkeypatch):
    monkeypatch.setenv(warmstart.ENV_CACHE_DIR, str(tmp_path / "envw"))
    warmstart.reset()  # clear the one-shot env probe
    compiled, rec = introspect.build_compiled(_fn(), _args(), "t.env")
    assert compiled is not None
    assert rec["warm"] == warmstart.RESULT_MISS
    assert warmstart.get_store().root == str(tmp_path / "envw")


# ---- the real process boundary ----------------------------------------------

_CHILD = textwrap.dedent("""
    import json, os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, {root!r})
    import jax, jax.numpy as jnp
    import numpy as np
    from singa_tpu import introspect, warmstart
    warmstart.enable({store!r})
    fn = jax.jit(lambda x: jnp.cumsum(x) * 3)
    args = (jnp.arange(16, dtype=jnp.float32),)
    compiled, rec = introspect.build_compiled(fn, args, "t.sub")
    print(json.dumps({{
        "warm": rec["warm"],
        "fingerprint": rec["fingerprint"],
        "out": np.asarray(compiled(*args)).tolist(),
        "snap": warmstart.snapshot(),
    }}))
""")


def test_cache_hit_across_subprocess_boundary(tmp_path):
    """The acceptance check: two genuinely separate Python processes
    share one store dir; the first exports (miss), the second loads
    (hit) and computes the identical result."""
    store_dir = str(tmp_path / "warm")
    script = _CHILD.format(root=_ROOT, store=store_dir)
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("SINGA_TPU_COMPILE_CACHE", None)
    runs = []
    for _ in range(2):
        r = subprocess.run([sys.executable, "-c", script],
                           capture_output=True, text=True, timeout=300,
                           env=env, cwd=_ROOT)
        assert r.returncode == 0, r.stderr[-2000:]
        runs.append(json.loads(r.stdout.strip().splitlines()[-1]))
    assert runs[0]["warm"] == "miss"
    assert runs[1]["warm"] == "hit"
    assert runs[0]["fingerprint"] == runs[1]["fingerprint"]
    assert runs[0]["out"] == runs[1]["out"]
    assert runs[0]["snap"]["exports"] == 1
    assert runs[1]["snap"]["exports"] == 0  # the hit did not rewrite
    assert runs[1]["snap"]["lookups"]["hit"] == 1


# ---- engine under warm load -------------------------------------------------

def test_engine_tokens_identical_and_no_extra_compiles(tmp_path):
    """Acceptance: engine greedy decode over ONE set of params is
    token-identical with the warm store off, cold, and warm — and the
    warm engine performs no MORE staged builds than the cold one
    (loading can't multiply compiles)."""
    from singa_tpu import engine as eng_mod
    from singa_tpu.router import _build_replica_model
    # one model: each arm spins a fresh engine (fresh AOT staging) over
    # the same params, so any token drift is the warm path's fault
    m = _build_replica_model(61, 32, 1, 24)

    def run_arm():
        e = eng_mod.ServingEngine(m, max_slots=2, page_size=8,
                                  max_ctx=24).start()
        try:
            w = e.submit(np.arange(1, 7, dtype=np.int32), 6)
            assert w.wait(300), "decode stalled"
            toks = list(w.tokens)
        finally:
            e.stop()
        return toks, len(introspect.executable_manifest())

    toks_off, _ = run_arm()
    introspect.reset()
    warmstart.enable(str(tmp_path / "warm"))
    toks_cold, builds_cold = run_arm()
    snap_cold = warmstart.snapshot()
    introspect.reset()
    toks_warm, builds_warm = run_arm()
    snap_warm = warmstart.snapshot()
    assert toks_off == toks_cold == toks_warm
    assert builds_warm <= builds_cold
    assert snap_cold["lookups"]["miss"] > 0
    assert snap_cold["exports"] > 0
    assert snap_warm["lookups"]["hit"] > snap_cold["lookups"]["hit"]


def test_prewarm_builds_every_bucket(tmp_path):
    from singa_tpu import engine as eng_mod
    from singa_tpu.router import _build_replica_model
    m = _build_replica_model(61, 32, 1, 24)
    e = eng_mod.ServingEngine(m, max_slots=2, page_size=8,
                              max_ctx=24).start()
    try:
        buckets, first_wall = e.prewarm((4, 12))
        assert buckets == sorted({e._bucket(4), e._bucket(12)})
        assert first_wall is not None
        import time
        assert abs(first_wall - time.time()) < 300
    finally:
        e.stop()


# ---- typed PRNG keys through the export bridge ------------------------------

def _key_fn():
    # the shape of every training step: a typed key in AND out
    return jax.jit(lambda key, x: (
        jax.random.split(key, 1)[0], x + jax.random.uniform(key, x.shape)))


def _key_args():
    return (jax.random.key(7), jnp.arange(4, dtype=jnp.float32))


def _assert_same_key_outputs(got, want):
    (got_key, got_val), (want_key, want_val) = got, want
    # outputs are typed keys again, not raw uint32 leaking out
    assert jax.dtypes.issubdtype(got_key.dtype, jax.dtypes.prng_key)
    np.testing.assert_array_equal(
        np.asarray(jax.random.key_data(got_key)),
        np.asarray(jax.random.key_data(want_key)))
    np.testing.assert_allclose(np.asarray(got_val), np.asarray(want_val))


def test_typed_key_blob_round_trips_and_is_framed():
    fn, args = _key_fn(), _key_args()
    blob = introspect._serialize_executable(fn, args)
    # a framed blob proves the key-data bridge engaged
    assert blob is not None
    assert blob.startswith(introspect._KEY_BLOB_MAGIC)
    rt = introspect._deserialize_executable(blob)
    assert rt is not None
    _assert_same_key_outputs(rt(*args), fn(*args))


def test_keyless_blob_stays_unframed():
    blob = introspect._serialize_executable(_fn(), _args())
    assert blob is not None
    assert not blob.startswith(introspect._KEY_BLOB_MAGIC)


def test_typed_key_blob_stages_with_device_committed_args():
    """What the bridge is for on the installed jax: a train step's
    dev.rng_state is committed to its device, and an exported module
    that takes the typed key itself refuses to stage with such an
    argument (sharding constraint of rank 0 on the key's tensor<2xui32>).
    Caught here in-process so the fast tier sees it, not only the slow
    cross-process restart below."""
    fn = _key_fn()
    dev = jax.devices()[0]
    args = tuple(jax.device_put(a, dev) for a in _key_args())
    rt = introspect._deserialize_executable(
        introspect._serialize_executable(fn, args))
    compiled, _ = introspect._stage(rt, args)
    _assert_same_key_outputs(compiled(*args), fn(*args))


def test_typed_key_fn_warm_hit_through_build_compiled(tmp_path):
    warmstart.enable(str(tmp_path / "warm"))
    fn, args = _key_fn(), _key_args()
    compiled, rec = introspect.build_compiled(fn, args, "t.keyed")
    assert compiled is not None and rec["warm"] == warmstart.RESULT_MISS
    assert warmstart.snapshot()["exports"] == 1
    want = fn(*args)
    introspect.reset()
    compiled2, rec2 = introspect.build_compiled(fn, args, "t.keyed")
    assert rec2["warm"] == warmstart.RESULT_HIT
    _assert_same_key_outputs(compiled2(*args), want)


@pytest.mark.slow
def test_train_step_warm_restart_matches_cold_losses(tmp_path):
    # the end-to-end claim of the warm store: a warm process's training losses are bit-identical to cold ones
    # (same exported module), with the step executable served from the
    # store — exercised across a REAL process boundary
    script = textwrap.dedent("""
        import json, sys
        sys.path.insert(0, %(repo)r)
        import numpy as np, jax
        from singa_tpu import device, models, opt, tensor, warmstart
        warmstart.enable(sys.argv[1])
        dev = device.best_device()
        rng = np.random.RandomState(0)
        m = models.create_model("mlp", data_size=8, num_classes=4)
        tx = tensor.Tensor(
            data=rng.standard_normal((4, 8)).astype(np.float32), device=dev)
        ty = tensor.from_numpy(rng.randint(0, 4, 4).astype(np.int32),
                               device=dev)
        m.set_optimizer(opt.SGD(lr=0.1))
        m.compile([tx], is_train=True, use_graph=True)
        losses = []
        for _ in range(3):
            out, loss = m(tx, ty)
            losses.append(float(np.asarray(jax.device_get(loss.data))))
        m.eval()
        ev = tensor.to_numpy(m(tx))  # warm-hit eval: template recovery
        snap = warmstart.snapshot()
        print(json.dumps({"losses": losses, "eval_sum": float(ev.sum()),
                          "lookups": snap["lookups"],
                          "exports": snap["exports"]}))
    """) % {"repo": os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))}
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("SINGA_TPU_COMPILE_CACHE", None)
    root = str(tmp_path / "warm")

    def run():
        out = subprocess.run(
            [sys.executable, "-c", script, root], env=env,
            capture_output=True, text=True, timeout=240)
        assert out.returncode == 0, out.stderr[-2000:]
        return json.loads(out.stdout.strip().splitlines()[-1])

    cold, warm = run(), run()
    assert cold["lookups"]["hit"] == 0 and cold["exports"] >= 1
    assert warm["lookups"]["hit"] >= 1
    assert warm["lookups"]["corrupt"] == 0 and warm["lookups"]["stale"] == 0
    assert warm["losses"] == cold["losses"]
    assert warm["eval_sum"] == cold["eval_sum"]
