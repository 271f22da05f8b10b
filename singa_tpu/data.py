"""Data loading utilities (ref python/singa/data.py).

`ImageBatchIter` keeps the reference's API (start/next/end, multiprocess
prefetch into a bounded queue). On TPU the host-side pipeline matters more
than on GPU — the chip stalls if the host can't feed it — so there is also
`NumpyBatchIter` for in-memory arrays with background prefetch, used by the
examples. A C-accelerated record reader lives in singa_tpu.io (native/).

Both iterators are stall-instrumented end to end (the `data_wait` goodput
bucket's ground truth):
  - consumer-blocked time: `singa_data_consumer_blocked_seconds{iter=...}`
    plus an `observe.span("data.wait")` around the blocking wait, so the
    goodput tracker attributes it even outside `Model.fit` (nested fit
    spans net out — no double counting),
  - producer batch-build time: `singa_data_producer_batch_seconds` —
    `ImageBatchIter`'s worker is a separate *process*, so its build time
    rides the queue payload and is recorded consumer-side,
  - queue depth: `singa_data_queue_depth` / `singa_data_prefetch_depth`.
    One series per iterator KIND (`iter=image|numpy`, the lint's
    low-cardinality contract), so with several live iterators of the
    same kind the gauges reflect the most recent writer — read the
    blocked-time histograms (cumulative) when that matters.
"""

from __future__ import annotations

import os
import queue as _queue
import random
import threading
import time
from multiprocessing import Event, Process, Queue

import numpy as np

from . import observe, watchdog


def _record_consumer_wait(kind: str, seconds: float, depth=None):
    if not observe.is_enabled():
        return
    if observe.spans_suppressed():
        # this "consumer" is a background thread (the overlap
        # prefetcher driving us under suppress_spans): its queue waits
        # are overlapped with training, not training-loop stall time
        return
    observe.histogram(
        "singa_data_consumer_blocked_seconds",
        "wall seconds the training loop spent blocked on the next batch"
    ).observe(seconds, iter=kind)
    if depth is not None:
        observe.gauge(
            "singa_data_queue_depth",
            "prefetched batches ready in the iterator queue"
        ).set(float(depth), iter=kind)


def _record_producer_batch(kind: str, seconds: float):
    if not observe.is_enabled():
        return
    observe.histogram(
        "singa_data_producer_batch_seconds",
        "wall seconds the producer spent building one batch"
    ).observe(seconds, iter=kind)


class ImageBatchIter:
    """Iterate an image-list file, yielding (images_NCHW_uint8, labels).

    Args mirror the reference (data.py:64): img_list_file lines are
    "<path><delimiter><meta>"; image_transform(full_path) -> list of
    augmented PIL images.
    """

    def __init__(self, img_list_file, batch_size, image_transform,
                 shuffle=True, delimiter=' ', image_folder=None, capacity=10):
        self.img_list_file = img_list_file
        self.queue = Queue(capacity)
        self.batch_size = batch_size
        self.image_transform = image_transform
        self.shuffle = shuffle
        self.delimiter = delimiter
        self.image_folder = image_folder
        self.stop_flag = Event()  # shared with the worker process
        self.p = None
        with open(img_list_file, 'r') as fd:
            self.num_samples = len(fd.readlines())
        if self.num_samples < batch_size:
            # the worker's epoch loop could never assemble a single
            # batch: it would spin re-shuffling forever while __next__
            # blocks on an eternally-empty queue
            raise ValueError(
                f"batch_size {batch_size} exceeds the {self.num_samples} "
                f"sample(s) in {img_list_file}")

    def start(self):
        if self.p is not None and self.p.is_alive():
            # restarting for a new epoch stream while the previous worker
            # is alive: stop it first — two workers would interleave
            # batches into one queue and the old process would leak
            self.end()
        # end() (a previous epoch's, or the stop above) left the flag
        # set and possibly a stale in-flight batch in the queue; a fresh
        # worker needs both cleared
        self.stop_flag.clear()
        while not self.queue.empty():
            try:
                self.queue.get_nowait()
            except _queue.Empty:
                break
        self.p = Process(target=self.run, daemon=True)
        self.p.start()

    def __next__(self):
        assert self.p is not None, 'call start before next'
        if self.stop_flag.is_set():
            # end() was called: the queue may still hold a stale batch
            # (its drain races the worker's in-flight put) — don't
            # serve it, the iteration is over
            raise StopIteration
        # blocking get (no 10ms poll spin): wake as soon as a batch
        # lands, and notice a dead worker instead of hanging forever.
        # The watchdog arms its data_wait deadline over the same wait
        # (`data.next` is the deterministic FaultPlan hook).
        t0 = time.perf_counter()
        from . import resilience
        with observe.span("data.wait"), watchdog.guard("data_wait"):
            resilience.fault_point("data.next")
            while True:
                try:
                    item = self.queue.get(timeout=0.2)
                    break
                except _queue.Empty:
                    if not self.p.is_alive():
                        # the worker's feeder thread may still be
                        # flushing its last batch into the pipe: one
                        # final drain before declaring the data lost
                        try:
                            item = self.queue.get(timeout=0.2)
                            break
                        except _queue.Empty:
                            if self.stop_flag.is_set():
                                # deliberate shutdown (end()), not a
                                # crash: the iteration is simply over
                                raise StopIteration from None
                            raise RuntimeError(
                                f"ImageBatchIter worker process died "
                                f"(exitcode {self.p.exitcode}) with the "
                                "queue empty — check the image list / "
                                "transform; see the worker's stderr for "
                                "its traceback") from None
        try:
            depth = self.queue.qsize()
        except NotImplementedError:  # macOS multiprocessing queues
            depth = None
        _record_consumer_wait("image", time.perf_counter() - t0, depth)
        x, y, produce_s = item
        _record_producer_batch("image", produce_s)
        return x, y

    next = __next__

    def __iter__(self):
        return self

    def end(self):
        if self.p is not None:
            self.stop_flag.set()
            # drain so a blocked queue.put in the worker can finish cleanly
            while not self.queue.empty():
                self.queue.get_nowait()
            self.p.join(timeout=1.0)
            if self.p.is_alive():
                self.p.terminate()

    def run(self):
        samples = []
        with open(self.img_list_file, 'r') as fd:
            for line in fd:
                path, meta = line.strip().split(self.delimiter, 1)
                samples.append((path, meta))
        while not self.stop_flag.is_set():
            if self.shuffle:
                random.shuffle(samples)
            i = 0
            while i + self.batch_size <= len(samples) \
                    and not self.stop_flag.is_set():
                t0 = time.perf_counter()
                xs, ys = [], []
                for path, meta in samples[i:i + self.batch_size]:
                    full = os.path.join(self.image_folder, path) \
                        if self.image_folder else path
                    for img in self.image_transform(full):
                        arr = np.asarray(img, dtype=np.float32)
                        if arr.ndim == 2:
                            arr = arr[:, :, None]
                        xs.append(arr.transpose(2, 0, 1))
                        ys.append(meta)
                x = np.stack(xs)
                try:
                    y = np.asarray([int(v) for v in ys], np.int32)
                except ValueError:
                    y = ys  # non-integer meta: hand back raw strings
                # build time rides the payload: the worker is another
                # process, so it cannot feed this process's registry
                self.queue.put((x, y, time.perf_counter() - t0))
                i += self.batch_size


class NumpyBatchIter:
    """Shuffled mini-batches over in-memory arrays with a bounded
    background prefetch thread (default depth 2 — enough to hide
    host-side augmentation behind device steps; raise `prefetch` when
    the transform is spiky)."""

    def __init__(self, x, y, batch_size, transform=None, shuffle=True,
                 seed=0, drop_last=True, prefetch=2):
        assert len(x) == len(y)
        self.x, self.y = x, y
        self.bs = batch_size
        self.transform = transform
        self.shuffle = shuffle
        self.rng = np.random.RandomState(seed)
        self.prefetch = max(1, int(prefetch))
        n = len(x) // batch_size if drop_last else -(-len(x) // batch_size)
        self.num_batches = n
        self._producer_thread = None  # last epoch's producer (tests/join)
        self._producer_lock = None    # its condition + stop flag, kept so
        self._producer_stop = None    # a re-iteration can reap it

    def _stop_producer(self, timeout=2.0):
        """Stop-and-join the previous epoch's producer thread, if one is
        still alive (the consumer abandoned the generator without
        closing it). Re-iterating must not stack producers: the old one
        would sit parked on its condition until interpreter exit."""
        t = self._producer_thread
        if t is None or not t.is_alive():
            return
        lock, stop = self._producer_lock, self._producer_stop
        if lock is not None:
            with lock:
                stop[0] = True
                lock.notify_all()
        t.join(timeout=timeout)

    def __len__(self):
        return self.num_batches

    def _make(self, order, b):
        sel = order[b * self.bs:(b + 1) * self.bs]
        xb = self.x[sel]
        if self.transform is not None:
            xb = self.transform(xb)
        return xb, self.y[sel]

    def __iter__(self):
        self._stop_producer()  # a previous epoch's live producer first
        order = np.arange(len(self.x))
        if self.shuffle:
            self.rng.shuffle(order)
        nxt = {}
        lock = threading.Condition()
        stop = [False]  # set when the consumer abandons the iterator early
        self._producer_lock = lock
        self._producer_stop = stop
        if observe.is_enabled():
            observe.gauge(
                "singa_data_prefetch_depth",
                "configured prefetch depth of the iterator queue"
            ).set(float(self.prefetch), iter="numpy")

        def producer():
            for b in range(self.num_batches):
                if stop[0]:  # abandoned: don't build batches nobody wants
                    return
                t0 = time.perf_counter()
                batch = self._make(order, b)
                _record_producer_batch("numpy", time.perf_counter() - t0)
                with lock:
                    while (b in nxt or len(nxt) >= self.prefetch) \
                            and not stop[0]:
                        lock.wait()
                    if stop[0]:
                        return
                    nxt[b] = batch
                    lock.notify_all()

        t = self._producer_thread = threading.Thread(
            target=producer, name="singa-data-producer", daemon=True)
        t.start()
        try:
            for b in range(self.num_batches):
                t0 = time.perf_counter()
                with observe.span("data.wait"), \
                        watchdog.guard("data_wait"):
                    from . import resilience
                    resilience.fault_point("data.next")
                    with lock:
                        while b not in nxt:
                            # same dead-producer guard as ImageBatchIter:
                            # a transform that raises kills the thread
                            # without notifying, and an untimed wait
                            # would park the training loop forever
                            if not t.is_alive():
                                raise RuntimeError(
                                    "NumpyBatchIter producer thread died "
                                    f"before batch {b} — the transform "
                                    "raised; see its traceback on stderr")
                            lock.wait(timeout=0.2)
                        batch = nxt.pop(b)
                        depth = len(nxt)
                        lock.notify_all()
                _record_consumer_wait(
                    "numpy", time.perf_counter() - t0, depth)
                yield batch
        finally:
            with lock:
                stop[0] = True
                lock.notify_all()
            # reap the producer: an abandoned iterator must not leave a
            # thread parked on the condition until interpreter exit. A
            # producer mid-transform can't be interrupted — bounded
            # join, and the daemon thread finishes its batch on its own
            t.join(timeout=1.0)


def block_diffusion_noise(rng, shape, block, rate_min=1e-3):
    """The noising draw of block-diffusion training (models/sdar.py) for a
    batch of `shape` = (batch, seq), seq a multiple of `block`: a rate t_k
    uniform on [rate_min, 1] for each block of `block` positions, each
    position masked with its block's rate. Returns (masked (batch, seq)
    int32, 1 where the token is replaced by [MASK]; weight (batch, seq)
    float32 = masked / t_k: the masked-diffusion objective's weight under
    the linear schedule; rates (batch, seq / block) float32). `rng`: a
    numpy Generator; a user's loop and the benchmark's driver draw it here
    alike."""
    batch, seq = shape
    assert seq % block == 0, (seq, block)
    rates = rng.uniform(rate_min, 1.0, (batch, seq // block))
    t = np.repeat(rates, block, axis=1)
    masked = rng.random((batch, seq)) < t
    return (masked.astype(np.int32), (masked / t).astype(np.float32),
            rates.astype(np.float32))
