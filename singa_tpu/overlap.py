"""Overlap layer: device-side input prefetch + asynchronous checkpointing.

PR 4's goodput ledger (singa_tpu.goodput) *measures* the two big host-side
badput buckets — `data_wait` (the loop blocked fetching the next batch)
and `checkpoint` (the loop blocked on a synchronous orbax write). This
module *reclaims* them, standard TPU-systems practice:

  - `DevicePrefetcher` / `prefetch_to_device(it, model, size)`: a
    background thread pulls host batches from any iterator, ships them to
    the device with `jax.device_put` (resolving the model's input sharding
    from `Model._dist_shardings`, so `_invoke_step`'s put() short-circuit
    makes the step path zero-copy), and keeps a bounded ring of N
    on-device batches — host→HBM transfer for batch k overlaps step k−1's
    execution. Wired as `Model.fit(..., prefetch_to_device=2)`.
    Telemetry: `singa_prefetch_ring_depth` / `singa_prefetch_blocked_
    seconds` / `singa_prefetch_batches_total`; the consumer's ring wait is
    wrapped in a `data.wait` span, so it feeds the existing goodput
    `data_wait` bucket (nested under Model.fit's own fetch span it nets
    out — no double counting).

  - Async checkpointing: `start_async_save` routes an orbax tree through
    `AsyncCheckpointer`. The save call returns after the device→host
    snapshot; the serialize/write overlaps training in orbax's
    background thread.
    `wait_for_checkpoints()` is the barrier: it blocks until every
    in-flight save is durable and RE-RAISES the first deferred write
    failure instead of swallowing it. The barrier is auto-invoked by the
    next `save_checkpoint` / `load_checkpoint` and at interpreter exit
    (atexit), so an error can be delayed but never lost. Goodput books
    only the blocking portions: the snapshot under `checkpoint.save`, the
    barrier wait under `checkpoint.wait` — the overlapped background
    write is exactly the time reclaimed. `singa_checkpoint_async_pending`
    tracks in-flight saves.

Thread hygiene contract (tests/conftest.py enforces it per test): the
prefetcher's thread is a daemon named ``singa-prefetch-*`` and is joined
by `close()` — which `Model.fit` calls on every exit path (normal end,
early break, HealthError) — and no async save may be left pending.
"""

from __future__ import annotations

import atexit
import os
import threading
import time
from collections import deque

import jax

from . import memory, observe, watchdog
from .tensor import Tensor

_END = object()  # ring sentinel: the source iterator is exhausted


class DevicePrefetcher:
    """Bounded background device-transfer ring over any batch iterator.

    `it` yields per-batch values (tuples/lists of Tensors or numpy/jax
    arrays, or a single such value — the shapes `Model.fit` consumes).
    The producer thread moves every array leaf to the device ahead of
    consumption; non-array elements (static args) pass through
    untouched. Yields the same structure with each array leaf re-wrapped
    as a `Tensor` whose `.data` already lives on the device, carrying
    the model's input sharding when one is resolved — so the training
    step's own `device_put` short-circuits and dispatch is zero-copy.

    Single-use iterator. `close()` is idempotent and joins the producer;
    it runs automatically on source exhaustion, on a source error, and
    via `with DevicePrefetcher(...) as it:`. On a multi-process mesh the
    transfer is left to `_invoke_step` (device_put cannot scatter across
    hosts); batches then pass through host-side, still pipelined.
    """

    _ids = iter(range(1_000_000_000))
    _ids_lock = threading.Lock()

    def __init__(self, it, model=None, size=2, device=None):
        if model is None and device is None:
            raise ValueError(
                "DevicePrefetcher needs a model (for its device + input "
                "sharding) or an explicit device")
        self._src = iter(it)
        self._model = model
        self._device = device if device is not None \
            else getattr(model, "_device", None)
        if self._device is None:
            raise ValueError(
                "model has no device yet — call Model.compile first, or "
                "pass device= explicitly")
        self.size = max(1, int(size))
        self._ring = deque()
        self._cond = threading.Condition()
        self._stop = False
        self._err = None
        self._closed = False
        with DevicePrefetcher._ids_lock:
            n = next(DevicePrefetcher._ids)
        # memory-ledger birth-site hook: the on-device batches parked
        # in the ring attribute to the `prefetch_ring` region
        memory.track_prefetcher(self)
        self._thread = threading.Thread(
            target=self._produce, name=f"singa-prefetch-{n}", daemon=True)
        self._thread.start()

    # -- producer side -----------------------------------------------------
    def _input_sharding(self):
        """The model's per-batch input sharding, once the first compiled
        step resolved it (`Model._build_step` sets `_dist_shardings`);
        before that — and for single-device models always — the plain
        device. Resolved per batch: the first epoch's first batch may
        predate the build, later batches pick the sharding up."""
        m = self._model
        if m is not None:
            ds = getattr(m, "_dist_shardings", None)
            if ds is not None:
                return ds[1]  # (replicated, batch-sharded, states, opt)
        return self._device.jax_device

    def _move_leaf(self, x, sharding):
        data = x.data if isinstance(x, Tensor) else x
        if not hasattr(data, "shape") or not hasattr(data, "dtype"):
            return x  # static arg (int flag, string, ...): pass through
        arr = jax.device_put(data, sharding)
        return Tensor(data=arr, device=self._device, requires_grad=False)

    def _move(self, batch):
        if jax.process_count() > 1:
            # multi-host: each process holds the full host batch and
            # _invoke_step builds the addressable shards itself —
            # device_put here could not scatter across hosts
            return batch
        sh = self._input_sharding()
        if isinstance(batch, (tuple, list)):
            return type(batch)(self._move_leaf(v, sh) for v in batch)
        return self._move_leaf(batch, sh)

    def _produce(self):
        # the source's OWN spans (a wrapped NumpyBatchIter emits
        # data.wait around its queue waits) must not fire on this
        # thread: they would book overlapped producer time into the
        # goodput `data_wait` bucket this ring exists to drain — only
        # the consumer's ring wait is real stall time
        with observe.suppress_spans():
            self._produce_loop()

    def _produce_loop(self):
        try:
            while True:
                with self._cond:
                    while len(self._ring) >= self.size and not self._stop:
                        self._cond.wait(0.2)
                    if self._stop:
                        return
                try:
                    batch = next(self._src)
                except StopIteration:
                    return
                moved = self._move(batch)
                with self._cond:
                    if self._stop:
                        return
                    self._ring.append(moved)
                    observe.record_prefetch(depth=len(self._ring),
                                            produced=True)
                    self._cond.notify_all()
        except BaseException as e:  # noqa: BLE001 — relayed to consumer
            self._err = e
        finally:
            with self._cond:
                self._ring.append(_END)
                self._cond.notify_all()

    # -- consumer side -----------------------------------------------------
    def __iter__(self):
        return self

    def __next__(self):
        t0 = time.perf_counter()
        from . import resilience
        # the ring wait IS host data-stall time: span -> goodput
        # `data_wait` (nets out under Model.fit's own fetch span); the
        # watchdog arms the data_wait deadline over it, and `data.next`
        # is the deterministic FaultPlan hook for a wedged fetch
        with observe.span("data.wait"), watchdog.guard("data_wait"):
            resilience.fault_point("data.next")
            with self._cond:
                while not self._ring:
                    if self._closed:
                        # close() drained the ring (and the _END
                        # sentinel with it): the iteration is over, not
                        # a wait-forever
                        raise StopIteration
                    t = self._thread
                    if t is not None and not t.is_alive():
                        # the producer died WITHOUT posting its _END
                        # sentinel (interpreter-level death: its
                        # try/finally never ran). Checked under the
                        # ring lock, so a sentinel posted just before
                        # death was already seen — an unbounded wait
                        # here would park the training loop forever.
                        raise RuntimeError(
                            f"prefetch producer thread {t.name!r} died "
                            "without posting a sentinel; the ring will "
                            "never fill — see its traceback on stderr")
                    self._cond.wait(0.2)
                item = self._ring[0]
                if item is _END:
                    err = self._err
                    self._err = None  # raise once; later next() just stops
                else:
                    self._ring.popleft()
                    depth = len(self._ring)
                    self._cond.notify_all()
        if item is _END:
            self.close()
            if err is not None:
                raise err
            raise StopIteration
        observe.record_prefetch(depth=depth,
                                blocked_s=time.perf_counter() - t0)
        return item

    def close(self, timeout: float = 5.0):
        """Stop the producer and join it. Idempotent; called on every
        `Model.fit` exit path. A producer mid-`next(source)` finishes
        that fetch first (the source cannot be interrupted), so the join
        is bounded, not indefinite."""
        with self._cond:
            if self._closed:
                return
            self._closed = True
            self._stop = True
            self._cond.notify_all()
        t = self._thread
        if t is not None and t is not threading.current_thread():
            t.join(timeout=timeout)
        with self._cond:
            self._ring.clear()
            observe.record_prefetch(depth=0)
        memory.untrack(memory.REGION_PREFETCH_RING, self)

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        self.close()
        return False

    def __del__(self):  # backstop only; never joins
        try:
            with self._cond:
                self._stop = True
                self._cond.notify_all()
        except Exception:
            pass


def prefetch_to_device(it, model, size: int = 2, device=None):
    """Wrap `it` in a started `DevicePrefetcher` bound to `model`'s
    device + input sharding. Use as a context manager (or call
    `.close()`) so an abandoned iteration reaps the producer thread:

        with prefetch_to_device(iter(batches), model, size=2) as dit:
            for batch in dit:
                model(*batch)
    """
    return DevicePrefetcher(it, model=model, size=size, device=device)


# ---- async checkpointing ---------------------------------------------------

_ckpt_lock = threading.Lock()
_pending: "list[_PendingSave]" = []
# paths whose deferred write failed at a barrier — outlives the barrier
# that drained them (see write_failed); a fresh save to the path clears it
_failed_paths: "set[str]" = set()
_async_ck = None       # cached orbax AsyncCheckpointer (built on first save)
_atexit_installed = False


class _PendingSave:
    """One in-flight async save: the checkpointer whose background write
    must be awaited, and the path it is writing (for error messages)."""

    def __init__(self, checkpointer, path):
        self.checkpointer = checkpointer
        self.path = path

    def wait(self):
        self.checkpointer.wait_until_finished()


def _get_async_checkpointer():
    """The process's orbax AsyncCheckpointer, built on first save only:
    constructing one spins up orbax's resident worker threads, which a
    process that never checkpoints must not pay for."""
    global _async_ck
    with _ckpt_lock:
        if _async_ck is None:
            import orbax.checkpoint as ocp
            _async_ck = ocp.AsyncCheckpointer(
                ocp.StandardCheckpointHandler())
        return _async_ck


def _atexit_barrier():
    # a deferred write error surfacing here (traceback at exit) beats
    # silently losing the checkpoint. Printed explicitly: the bare
    # "Exception ignored in atexit callback" report drops the chained
    # __cause__, which is exactly the part naming WHY the write failed
    # (regression-tested by tests/test_resilience.py via a subprocess)
    try:
        wait_for_checkpoints()
    except BaseException:
        import traceback
        traceback.print_exc()
        raise


def _register_pending(entry, blocking_s=None):
    global _atexit_installed
    with _ckpt_lock:
        _pending.append(entry)
        n = len(_pending)
        if not _atexit_installed:
            _atexit_installed = True
            atexit.register(_atexit_barrier)
    observe.record_ckpt_async(n, blocking_s=blocking_s)
    return entry


def pending_checkpoints() -> int:
    """Number of async saves started but not yet confirmed durable."""
    with _ckpt_lock:
        return len(_pending)


def write_failed(path: str) -> bool:
    """True when a deferred async write to `path` failed at some past
    barrier. The record survives the `wait_for_checkpoints` that
    drained it, so an actor OTHER than the one that raised can still
    learn the outcome — the resilience controller consults this before
    manifesting a checkpoint complete, closing the window where a
    second actor's barrier consumes the error and a later, vacuously
    clean barrier looks like success. A new `start_async_save` to the
    same path clears the record."""
    with _ckpt_lock:
        return os.path.abspath(path) in _failed_paths


def clear_write_failed(path: str):
    """Forget a recorded write failure for `path` — call only once a
    later write to it is proven durable. `start_async_save` clears on
    starting a superseding write; `Model.save_checkpoint`'s synchronous
    branch clears after its blocking write finishes."""
    with _ckpt_lock:
        _failed_paths.discard(os.path.abspath(path))


def wait_for_checkpoints():
    """Barrier: block until every in-flight async save is durable.
    Re-raises the first deferred write failure (remaining saves are
    still awaited first, so one bad save cannot orphan the others).
    Auto-invoked by the next `Model.save_checkpoint` /
    `load_checkpoint` and at interpreter exit; call it explicitly
    before treating a checkpoint as safe to depend on."""
    global _async_ck
    with _ckpt_lock:
        entries = list(_pending)
        del _pending[:]
    if not entries:
        return
    errors = []
    # the barrier wait is the checkpoint path's only remaining blocking
    # portion: span -> goodput `checkpoint`
    from . import resilience  # lazy: no module-level cycle
    # the watchdog arms the ckpt_wait deadline over the whole barrier:
    # a write that will never land (dead filesystem, wedged orbax
    # thread) breaches here instead of blocking the caller forever
    with observe.span("checkpoint.wait"), watchdog.guard("ckpt_wait"):
        for e in entries:
            try:
                # deterministic stand-in for a deferred write failure /
                # a slow durability barrier (tests drive both through
                # resilience.FaultPlan; no-op without a plan installed)
                resilience.fault_point("ckpt.wait", path=e.path)
                e.wait()
            except BaseException as err:  # noqa: BLE001 — re-raised below
                errors.append((e, err))
    observe.record_ckpt_async(pending_checkpoints())
    if errors:
        # the failed checkpointer's state is suspect: drop the cache so
        # the next save builds a fresh one
        with _ckpt_lock:
            _failed_paths.update(os.path.abspath(e.path)
                                 for e, _ in errors)
            if _async_ck is not None and any(e.checkpointer is _async_ck
                                 for e, _ in errors):
                try:
                    _async_ck.close()
                except Exception:
                    pass
                _async_ck = None
        e, err = errors[0]
        raise RuntimeError(
            f"async checkpoint write to {e.path} failed "
            f"({len(errors)} of {len(entries)} pending save(s) failed)"
        ) from err


def start_async_save(path: str, tree, force: bool = False):
    """Begin an async orbax save of `tree` under `path`. Blocks only
    for the device→host snapshot (booked under the `checkpoint.save`
    span); the serialize/write runs in orbax's background thread until
    `wait_for_checkpoints`. Synchronous failures (existing directory
    without `force`) raise immediately, exactly like the sync path."""
    import orbax.checkpoint as ocp
    ck = _get_async_checkpointer()
    save_args = ocp.args.StandardSave(tree)
    t0 = time.perf_counter()
    # a fresh write supersedes any recorded failure for this path
    clear_write_failed(path)
    # span -> goodput `checkpoint`: ONLY the blocking snapshot portion;
    # the watchdog's ckpt_save deadline arms over it (a wedged
    # device->host snapshot is a hang like any other)
    with observe.span("checkpoint.save"), watchdog.guard("ckpt_save"):
        ck.save(path, args=save_args, force=force)
    _register_pending(_PendingSave(ck, path),
                      blocking_s=time.perf_counter() - t0)


# ---- /statusz section ------------------------------------------------------

def overlap_report() -> str:
    """Text block for /statusz: prefetch ring + async-ckpt state."""
    reg = observe.get_registry()
    lines = ["== overlap =="]
    depth = reg.get("singa_prefetch_ring_depth")
    moved = reg.get("singa_prefetch_batches_total")
    blocked = reg.get("singa_prefetch_blocked_seconds")
    if moved is None and depth is None:
        lines.append("prefetch: not in use")
    else:
        lines.append(
            f"prefetch: ring_depth={int(depth.value()) if depth else 0} "
            f"batches_moved={int(moved.value()) if moved else 0} "
            f"consumer_blocked_s="
            f"{blocked.sum() if blocked else 0.0:.3f}")
    started = reg.get("singa_checkpoint_async_total")
    blk = reg.get("singa_checkpoint_async_blocking_seconds")
    lines.append(
        f"async-ckpt: pending={pending_checkpoints()} "
        f"started={int(started.value()) if started else 0} "
        f"blocking_s_sum={blk.sum() if blk else 0.0:.3f}")
    return "\n".join(lines)


__all__ = [
    "DevicePrefetcher", "prefetch_to_device",
    "start_async_save", "wait_for_checkpoints", "pending_checkpoints",
    "write_failed", "clear_write_failed",
    "overlap_report",
]
