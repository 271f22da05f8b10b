"""Warm-start layer: zero-compile restarts across process lifetimes.

The source paper's core bet is compile-once-run-forever — trace the
step into a buffered graph and re-execute it every iteration — but the
stack only honored it *within* a process: every `router.spawn_replica`
and watchdog restart re-paid the full trace->lower->compile pipeline
for the per-bucket prefill, decode, and spec executables (ROADMAP
item 1; the cold-start observatory measures exactly this). This module
moves the bet across process lifetimes with two stacked persistence
layers, both rooted under ONE directory (`SINGA_TPU_COMPILE_CACHE` or
`enable(root)`):

1. **XLA persistent compilation cache** (`<root>/xla`, or wherever
   `JAX_COMPILATION_CACHE_DIR` says when it is set): the stock
   `jax_compilation_cache_dir` machinery, configured with the
   `persistent_cache_min_*` knobs opened wide so every executable —
   CPU-test-sized ones included — is written and re-read. This layer
   makes the `compile` phase of a warm restart a disk read.

2. **Serialized executables** (`<root>/exec`): `jax.export`-serialized
   StableHLO per (key, signature-fingerprint), written by
   `introspect.export_executable` after a fresh build and loaded by
   `introspect.load_executable` before staging. This layer removes the
   *Python trace* of the model code: a warm process stages
   `jit(deserialize(blob).call)`, whose trace/lower cost is independent
   of model depth.

The two compose through one staging discipline in
`introspect.build_compiled`: when the store is enabled, a COLD build
exports first and stages through the deserialized round-trip — paying
one compile and seeding the XLA cache with the *exact module* a warm
restart will recompile (the exported module's cache key is stable
across processes; the original function's is not) — and a WARM build
loads the blob and stages it, hitting the XLA disk cache for the
compile. Default behavior (no env var, no `enable`) is bit-unchanged.

Store layout (`<root>/exec/<safe_key>/`):

  <fingerprint>.bin    the serialized executable (jax.export blob)
  <fingerprint>.json   {key, fingerprint, blob_sha256, jax_version,
                        size, ts} — integrity + staleness metadata
  ../manifest.jsonl    append-only export log (the "manifest" a
                       spawning replica is shipped)

Writes are atomic (tmp + fsync + os.replace, the resilience-manifest
pattern), eviction is keep-last-K per key by mtime
(`SINGA_TPU_COMPILE_CACHE_KEEP`, default 8), and every lookup is
classified into the `CACHE_RESULTS` enum:

  hit      blob present, sha-256 verified, deserialized and staged
  miss     no entry for this (key, fingerprint)
  stale    entry present but untrustworthy for THIS process: meta
           fingerprint mismatch or a different jax version (deleted,
           rebuilt fresh, re-exported)
  corrupt  unreadable/truncated blob or meta, sha mismatch, or a blob
           that fails to deserialize/stage (deleted, rebuilt fresh,
           re-exported)

Every classification lands in
`singa_compile_cache_lookups_total{result=,key=}`; exports, evictions
and store occupancy get their own metrics, and `/statusz` gains a
warm-start section (`warm_report`). A corrupt or stale entry can never
break dispatch — the fallback is always the fresh-compile path that
existed before this module.
"""

from __future__ import annotations

import hashlib
import json
import os
import time

from . import observe

# ---- enums (the lint in tools/check_metrics_names.py greps these) ---------

#: Warm-store lookup classifications for
#: `singa_compile_cache_lookups_total{result=...}` — the fixed
#: low-cardinality contract rule 5 of the metrics lint enforces.
CACHE_RESULTS = ("hit", "miss", "stale", "corrupt")
RESULT_HIT = "hit"
RESULT_MISS = "miss"
RESULT_STALE = "stale"
RESULT_CORRUPT = "corrupt"

ENV_CACHE_DIR = "SINGA_TPU_COMPILE_CACHE"
#: jax's own variable for the XLA persistent cache: where it is set, the
#: XLA layer lives there and only the serialized executables stay under
#: the warm store's root.
ENV_XLA_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"
ENV_KEEP = "SINGA_TPU_COMPILE_CACHE_KEEP"
DEFAULT_KEEP = 8

MANIFEST_NAME = "manifest.jsonl"
MAX_LOOKUPS = 256

# ---- state -----------------------------------------------------------------

_store: "WarmStore | None" = None
_xla_dir: "str | None" = None
_lookups: list = []   # ring of {key, fingerprint, result, seconds, ts}
_counts: dict = {}    # result -> count (lifetime of this enable)
_exports = 0
_env_checked = False


def _count_lookup(result: str, key: str):
    assert result in CACHE_RESULTS, result
    if observe.is_enabled():
        observe.counter(
            "singa_compile_cache_lookups_total",
            "warm-store executable lookups by classification "
            "(hit|miss|stale|corrupt)"
        ).inc(result=result, key=key)


def _count_eviction(key: str):
    if observe.is_enabled():
        observe.counter(
            "singa_compile_cache_evictions_total",
            "warm-store entries deleted by keep-last-K eviction"
        ).inc(key=key)


def _count_export(key: str):
    if observe.is_enabled():
        observe.counter(
            "singa_compile_cache_exports_total",
            "serialized executables written to the warm store"
        ).inc(key=key)


def _set_store_gauges():
    if _store is None or not observe.is_enabled():
        return
    n, nbytes = _store.occupancy()
    observe.gauge("singa_compile_cache_entries",
                  "serialized executables currently in the warm store"
                  ).set(float(n))
    observe.gauge("singa_compile_cache_store_bytes",
                  "total on-disk bytes of the warm store's blobs"
                  ).set(float(nbytes))


def note_lookup(key: str, fingerprint: str, result: str,
                seconds: float = 0.0):
    """Record one classified warm-store lookup (introspect calls this
    from `load_executable`; the corrupt-at-staging path re-classifies
    through here too). Guards the enum, feeds the counter, the load
    histogram, and the in-memory ring `snapshot()` reads."""
    assert result in CACHE_RESULTS, result
    _counts[result] = _counts.get(result, 0) + 1
    _lookups.append({"key": key, "fingerprint": fingerprint,
                     "result": result, "seconds": round(seconds, 6),
                     "ts": round(time.time(), 6)})
    del _lookups[:-MAX_LOOKUPS]
    _count_lookup(result, key)
    if result == RESULT_HIT and observe.is_enabled():
        observe.histogram(
            "singa_compile_cache_load_seconds",
            "wall seconds to read + deserialize a warm executable"
        ).observe(seconds, key=key)


def note_export(key: str, fingerprint: str, nbytes: int):
    """Record one serialized-executable write (WarmStore.save calls
    this): export counter + store-occupancy gauges."""
    global _exports
    _exports += 1
    _count_export(key)
    _set_store_gauges()


# ---- the on-disk store ------------------------------------------------------

def _safe_key(key: str) -> str:
    return "".join(c if (c.isalnum() or c in "-_") else "_"
                   for c in key) or "_"


class WarmStore:
    """Serialized-executable store under `<root>/exec`. All writes are
    atomic (tmp + fsync + os.replace); a crash mid-write leaves no
    half entry, so blob presence is a reliable completeness marker.
    Loads classify into CACHE_RESULTS and DELETE untrustworthy entries
    so a bad blob is paid for at most once."""

    def __init__(self, root: str, keep: "int | None" = None):
        self.root = os.path.abspath(root)
        self.exec_dir = os.path.join(self.root, "exec")
        if keep is None:
            try:
                keep = int(os.environ.get(ENV_KEEP, DEFAULT_KEEP))
            except ValueError:
                keep = DEFAULT_KEEP
        self.keep = max(1, int(keep))
        os.makedirs(self.exec_dir, exist_ok=True)

    # -- paths ---------------------------------------------------------------
    def entry_paths(self, key: str, fingerprint: str):
        d = os.path.join(self.exec_dir, _safe_key(key))
        return (os.path.join(d, f"{fingerprint}.bin"),
                os.path.join(d, f"{fingerprint}.json"))

    # -- write ---------------------------------------------------------------
    def save(self, key: str, fingerprint: str, blob: bytes) -> "str | None":
        """Write one entry atomically (blob first, meta second — a meta
        is only ever present next to a complete blob), append the
        manifest line, evict beyond keep-last-K. Returns the blob path,
        or None on any OSError (a read-only store must not break the
        build that tried to populate it)."""
        import jax
        bin_path, meta_path = self.entry_paths(key, fingerprint)
        meta = {"key": key, "fingerprint": fingerprint,
                "blob_sha256": hashlib.sha256(blob).hexdigest(),
                "jax_version": jax.__version__,
                "size": len(blob), "ts": round(time.time(), 6)}
        try:
            os.makedirs(os.path.dirname(bin_path), exist_ok=True)
            self._atomic_write(bin_path, blob)
            self._atomic_write(
                meta_path,
                json.dumps(meta, sort_keys=True).encode("utf-8"))
            with open(os.path.join(self.exec_dir, MANIFEST_NAME), "a",
                      encoding="utf-8") as f:
                f.write(json.dumps(meta, sort_keys=True) + "\n")
        except OSError:
            return None
        self._evict(key)
        note_export(key, fingerprint, len(blob))
        return bin_path

    @staticmethod
    def _atomic_write(path: str, data: bytes):
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            f.write(data)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)

    # -- read ----------------------------------------------------------------
    def load(self, key: str, fingerprint: str):
        """(blob bytes | None, result): `hit` only after the meta parses,
        its fingerprint/jax-version match, AND the blob's sha-256
        verifies. stale/corrupt entries are deleted here so the caller's
        fresh build re-exports a clean replacement."""
        import jax
        bin_path, meta_path = self.entry_paths(key, fingerprint)
        if not os.path.exists(bin_path) and not os.path.exists(meta_path):
            return None, RESULT_MISS
        try:
            with open(meta_path, encoding="utf-8") as f:
                meta = json.load(f)
            if not isinstance(meta, dict):
                raise ValueError("meta is not a dict")
        except (OSError, ValueError):
            self.discard(key, fingerprint)
            return None, RESULT_CORRUPT
        if meta.get("fingerprint") != fingerprint \
                or meta.get("jax_version") != jax.__version__:
            # an entry for this path that was not built for THIS
            # (signature, jax) pair — e.g. a renamed/copied file or a
            # container upgrade — is stale, never trusted
            self.discard(key, fingerprint)
            return None, RESULT_STALE
        try:
            with open(bin_path, "rb") as f:
                blob = f.read()
        except OSError:
            self.discard(key, fingerprint)
            return None, RESULT_CORRUPT
        if hashlib.sha256(blob).hexdigest() != meta.get("blob_sha256"):
            self.discard(key, fingerprint)
            return None, RESULT_CORRUPT
        return blob, RESULT_HIT

    def discard(self, key: str, fingerprint: str):
        """Delete one entry (both files; missing files are fine)."""
        for p in self.entry_paths(key, fingerprint):
            try:
                os.unlink(p)
            except OSError:
                pass
        _set_store_gauges()

    # -- eviction / inventory ------------------------------------------------
    def _evict(self, key: str):
        d = os.path.join(self.exec_dir, _safe_key(key))
        try:
            blobs = sorted(
                (f for f in os.listdir(d) if f.endswith(".bin")),
                key=lambda f: os.path.getmtime(os.path.join(d, f)))
        except OSError:
            return
        for f in blobs[:-self.keep]:
            self.discard(key, f[:-len(".bin")])
            _count_eviction(key)

    def entries(self) -> list:
        """Every complete entry on disk: [{key, fingerprint, size}]."""
        out = []
        try:
            key_dirs = sorted(os.listdir(self.exec_dir))
        except OSError:
            return out
        for kd in key_dirs:
            d = os.path.join(self.exec_dir, kd)
            if not os.path.isdir(d):
                continue
            for f in sorted(os.listdir(d)):
                if not f.endswith(".json"):
                    continue
                try:
                    with open(os.path.join(d, f), encoding="utf-8") as fh:
                        meta = json.load(fh)
                    bin_path = os.path.join(d, f[:-len(".json")] + ".bin")
                    out.append({"key": meta.get("key", kd),
                                "fingerprint": meta.get("fingerprint"),
                                "size": os.path.getsize(bin_path)})
                except (OSError, ValueError):
                    continue
        return out

    def occupancy(self):
        """(entry count, total blob bytes) of the store."""
        es = self.entries()
        return len(es), sum(int(e.get("size") or 0) for e in es)

    def manifest(self) -> list:
        """The append-only export log — what `spawn_replica` ships a
        child so it knows which executables to expect warm."""
        path = os.path.join(self.exec_dir, MANIFEST_NAME)
        out = []
        try:
            with open(path, encoding="utf-8") as f:
                for line in f:
                    try:
                        out.append(json.loads(line))
                    except ValueError:
                        continue
        except OSError:
            pass
        return out


# ---- lifecycle --------------------------------------------------------------

def configure_xla_cache(default_dir: str) -> str:
    """Turn on jax's persistent compilation cache and return the
    directory in effect: the one `JAX_COMPILATION_CACHE_DIR` names when
    it is set — jax reads that variable itself, and nothing here
    overrides it — else `default_dir`. The min-entry-size /
    min-compile-time gates are opened wide so every executable,
    CPU-test-sized ones included, is written and re-read. A later run
    finds the entries only in the same directory, so entry points pass
    a fixed one, never one made from a pid, the time or `tempfile`."""
    import jax
    env_dir = os.environ.get(ENV_XLA_CACHE_DIR)
    os.makedirs(env_dir or default_dir, exist_ok=True)
    if not env_dir:
        jax.config.update("jax_compilation_cache_dir", default_dir)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return env_dir or default_dir


def _unconfigure_xla_cache():
    """Put back what the environment asked for: with
    `JAX_COMPILATION_CACHE_DIR` set the directory was never touched;
    without it the cache goes back to off."""
    import jax
    if not os.environ.get(ENV_XLA_CACHE_DIR):
        jax.config.update("jax_compilation_cache_dir", None)
    # drop the process-wide cache handle so a later enable() with a
    # NEW root actually re-initializes against it (tests enable a
    # fresh tmp dir per test)
    from jax.experimental.compilation_cache import compilation_cache
    compilation_cache.reset_cache()


def enable(root: "str | None" = None, *,
           keep: "int | None" = None) -> "WarmStore | None":
    """Enable the warm-start layer rooted at `root` (default: the
    SINGA_TPU_COMPILE_CACHE env var; None/unset -> stay disabled).
    Idempotent per root. Returns the store (or None when disabled)."""
    global _store, _xla_dir
    if root is None:
        root = os.environ.get(ENV_CACHE_DIR) or None
    if not root:
        return None
    root = os.path.abspath(root)
    if _store is not None and _store.root == root:
        return _store
    _xla_dir = configure_xla_cache(os.path.join(root, "xla"))
    _store = WarmStore(root, keep=keep)
    _set_store_gauges()
    return _store


def maybe_enable_from_env() -> "WarmStore | None":
    """One-shot env probe (introspect.build_compiled calls this on every
    build): enable from SINGA_TPU_COMPILE_CACHE the first time, then
    free until `reset()`."""
    global _env_checked
    if _store is not None:
        return _store
    if _env_checked:
        return None
    _env_checked = True
    return enable()


def get_store() -> "WarmStore | None":
    return _store


def is_enabled() -> bool:
    return _store is not None


def reset():
    """Disable the layer and clear all module state: the store handle,
    the lookup ring/counts, AND jax's persistent-cache configuration
    (dir back to None, in-memory cache handle dropped) — the conftest
    metric-isolation fixture calls this so one test's cache can never
    feed another test a hit."""
    global _store, _xla_dir, _exports, _env_checked
    if _store is not None or _xla_dir is not None:
        _unconfigure_xla_cache()
    _store = None
    _xla_dir = None
    _exports = 0
    _env_checked = False
    _counts.clear()
    del _lookups[:]


# ---- reporting --------------------------------------------------------------

def lookup_history() -> list:
    """Chronological classified lookups ({key, fingerprint, result,
    seconds, ts}) since enable — the warm A/B reads this."""
    return [dict(r) for r in _lookups]


def snapshot() -> dict:
    """One dict for ready-lines / /statusz / WARM rows: enabled flag,
    root, per-result lookup counts, hit rate, exports, and store
    occupancy."""
    counts = {r: int(_counts.get(r, 0)) for r in CACHE_RESULTS}
    total = sum(counts.values())
    snap = {"enabled": _store is not None,
            "root": _store.root if _store is not None else None,
            "xla_cache_dir": _xla_dir,
            "lookups": counts,
            "hit_rate": (counts[RESULT_HIT] / total) if total else None,
            "exports": int(_exports)}
    if _store is not None:
        n, nbytes = _store.occupancy()
        snap["entries"] = n
        snap["store_bytes"] = nbytes
        snap["keep"] = _store.keep
    return snap


def warm_report() -> str:
    """The `== warm start ==` /statusz section."""
    snap = snapshot()
    if not snap["enabled"]:
        return ("== warm start ==\nwarm store not enabled (set "
                f"{ENV_CACHE_DIR} or warmstart.enable(root))")
    c = snap["lookups"]
    hr = snap["hit_rate"]
    lines = [
        "== warm start ==",
        f"store: {snap['root']}  entries {snap.get('entries', 0)}  "
        f"{(snap.get('store_bytes') or 0) / 1e6:.2f} MB  "
        f"keep-last-{snap.get('keep')}",
        f"xla persistent cache: {snap['xla_cache_dir']}",
        "lookups: " + "  ".join(f"{r} {c[r]}" for r in CACHE_RESULTS)
        + (f"  (hit rate {hr * 100.0:.1f}%)" if hr is not None else ""),
        f"exports: {snap['exports']}",
    ]
    for r in lookup_history()[-6:]:
        lines.append(f"  [{r['key']}@{r['fingerprint']}] {r['result']} "
                     f"{r['seconds'] * 1e3:.1f} ms")
    return "\n".join(lines)


__all__ = [
    "CACHE_RESULTS", "RESULT_HIT", "RESULT_MISS", "RESULT_STALE",
    "RESULT_CORRUPT", "ENV_CACHE_DIR", "ENV_KEEP",
    "ENV_XLA_CACHE_DIR", "configure_xla_cache",
    "WarmStore", "enable", "maybe_enable_from_env", "get_store",
    "is_enabled", "reset",
    "note_lookup", "note_export", "lookup_history", "snapshot",
    "warm_report",
]
