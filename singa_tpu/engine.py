"""Continuous-batching serving engine with a paged KV cache.

`serving.py` decodes one STATIC batch at a time: every caller shares one
(B, S0, max_new) shape, a 32-token request reserves max-length KV HBM,
and a finished sequence keeps burning its decode slot until the whole
batch drains. That is not how production traffic arrives. This module is
the request-level engine above the same decode math:

  - **Admission queue**: `submit()` enqueues a request (its own prompt
    length, its own max_new, optional deadlines) and returns a handle;
    a background decode thread (``singa-serve-<n>``) owns the loop.

  - **Continuous (in-flight) batching**: the engine keeps a FIXED-SHAPE
    slot batch of `max_slots` sequences driven by an active mask —
    finished sequences are evicted and queued ones admitted BETWEEN
    decode-scan syncs, with no recompile (Orca-style iteration-level
    scheduling; the decode executable compiles once).

  - **Paged KV cache**: a fixed pool of HBM pages shared by all slots,
    with a host-side page table per sequence. Pages are allocated at
    admission (ceil((S0 + max_new) / page_size) pages — the request's
    own worst case, not the engine's) and freed at eviction, so
    heterogeneous lengths stop paying max-length HBM (vLLM's
    PagedAttention, on the Pallas kernel in ops.attention).

  - **Wired into the substrate**: the page pool registers as the
    `kv_cache` region in singa_tpu.memory (pool occupancy lands on
    /memz and reconciles against the live-byte total), every decode
    sync runs under `watchdog.guard("decode")` (hang detection rides
    the existing ladder; compile-tainted guards never breach), the
    engine enforces per-request admission-to-first-token and total
    deadlines itself (outcome "timeout"), and per-request queue-delay /
    TTFT / tokens-per-sec land in `observe` with live slot occupancy
    and page-pool utilization on /statusz (`serving_report`).

Decode is GREEDY (temperature 0) — the paged==dense token-for-token
agreement test is the engine's correctness anchor, and greedy is what
it can anchor. The math is `serving._DecodeCore.paged_token_step`:
the same qkv/rope/pack helpers as the dense path by construction.
"""

from __future__ import annotations

import threading
import time
from collections import deque

import numpy as np

from . import observe
from .slo import (PHASE_ADMIT, PHASE_DECODE, PHASE_FIRST_TOKEN,
                  PHASE_PREFILL, PHASE_QUEUE, PHASE_SUBMIT,
                  PHASE_TERMINAL)

#: every terminal state a request can reach (the `outcome=` label on
#: singa_serve_requests_total is proven against this tuple by
#: tools/check_metrics_names.py rule 5).
REQUEST_OUTCOMES = ("completed", "evicted", "rejected", "timeout")
OUTCOME_COMPLETED = "completed"
OUTCOME_EVICTED = "evicted"
OUTCOME_REJECTED = "rejected"
OUTCOME_TIMEOUT = "timeout"

#: KV-cache storage modes — kept in lockstep with serving.KV_DTYPES
#: (test-enforced): the `kv_dtype=` label on singa_serve_kv_pool_bytes
#: is proven against this module-level tuple by
#: tools/check_metrics_names.py rule 5.
KV_DTYPES = ("fp", "int8", "int4")


_metrics_cache: "dict | None" = None


def _metrics():
    # observe.counter/gauge/histogram spelled out so the static lint
    # sees every registration; outcome= values are members of
    # REQUEST_OUTCOMES (asserted at the single record site). Memoized
    # against one sentinel lookup — this runs per submit/finish/sync on
    # the serving hot path, and 14 locked registry lookups per call is
    # repeated work — but revalidated so a conftest registry reset
    # (which drops the old metric objects) rebuilds instead of feeding
    # orphans no /metrics scrape would ever see.
    global _metrics_cache
    c = _metrics_cache
    if c is not None and observe.get_registry().get(
            "singa_serve_requests_total") is c["requests"]:
        return c
    _metrics_cache = c = {
        "requests": observe.counter(
            "singa_serve_requests_total",
            "engine requests finished, by terminal outcome"),
        "admitted": observe.counter(
            "singa_serve_admitted_total",
            "requests admitted into a decode slot"),
        "tokens": observe.counter(
            "singa_serve_tokens_total",
            "tokens generated by the serving engine"),
        "steps": observe.counter(
            "singa_serve_steps_total",
            "engine decode steps executed (all slots, per step)"),
        "prefills": observe.counter(
            "singa_serve_prefills_total",
            "engine prefill calls (one per admission)"),
        "queue_depth": observe.gauge(
            "singa_serve_queue_depth",
            "requests waiting in the admission queue"),
        "occupancy": observe.gauge(
            "singa_serve_slot_occupancy",
            "decode slots currently active"),
        "slots": observe.gauge(
            "singa_serve_slots",
            "decode slots the engine was built with"),
        "pages_in_use": observe.gauge(
            "singa_serve_pages_in_use",
            "KV-cache pages currently allocated to sequences"),
        "pages": observe.gauge(
            "singa_serve_page_pool_pages",
            "KV-cache pages in the pool (capacity)"),
        "queue_delay": observe.histogram(
            "singa_serve_queue_delay_seconds",
            "submit-to-admission wall seconds per request"),
        "ttft": observe.histogram(
            "singa_serve_ttft_seconds",
            "submit-to-first-token wall seconds per request"),
        "request_s": observe.histogram(
            "singa_serve_request_seconds",
            "submit-to-terminal wall seconds per request"),
        "rate": observe.gauge(
            "singa_serve_request_tokens_per_sec",
            "last completed request's generation rate"),
        "kv_pool_bytes": observe.gauge(
            "singa_serve_kv_pool_bytes",
            "target KV page-pool bytes by cache storage mode (the "
            "draft model's fp pool is reported separately via "
            "engine.report draft_pool_bytes)"),
    }
    return c


def pctile(xs, p):
    """Nearest-rank percentile over an unsorted sequence (None when
    empty) — one rank convention for serving_report's /statusz TTFT
    numbers and every harness that reports the same requests."""
    xs = sorted(xs)
    return xs[min(len(xs) - 1, int(p * len(xs)))] if xs else None


class EngineRequest:
    """Handle for one submitted request. `wait()`/`result()` block until
    the request reaches a terminal outcome; `tokens` holds whatever was
    generated (partial on eviction/timeout), `outcome` one of
    REQUEST_OUTCOMES. `events` is the phase-stamped trace timeline
    (slo.REQUEST_PHASES; perf_counter stamps — the clock the fleet
    handshake aligns), appended by `mark()` at every lifecycle
    transition and serialized into the engine's timeline ring at the
    terminal."""

    __slots__ = ("id", "prompt", "max_new", "deadline_s",
                 "ttft_deadline_s", "submitted", "admitted",
                 "first_token_ts", "finished_ts", "outcome", "tokens",
                 "slot", "pages", "detail", "events", "syncs",
                 "last_slot", "trace", "synthetic", "_done")

    def __init__(self, rid, prompt, max_new, deadline_s, ttft_deadline_s):
        self.id = rid
        self.prompt = prompt
        self.max_new = int(max_new)
        self.deadline_s = deadline_s
        self.ttft_deadline_s = ttft_deadline_s
        self.trace = None  # router-minted trace-context id (or None)
        self.synthetic = False  # audit canary/replay probe, not real load
        self.submitted = time.monotonic()
        self.admitted = None
        self.first_token_ts = None
        self.finished_ts = None
        self.outcome = None
        self.tokens: "list[int]" = []
        self.slot = None
        self.pages: "list[int]" = []
        self.detail = None
        self.events: "list[tuple]" = []
        self.syncs: "list[int]" = []
        self.last_slot = None
        self._done = threading.Event()
        self.mark(PHASE_SUBMIT)

    def mark(self, phase: str, **info):
        """Stamp one lifecycle event onto the timeline. Appends are
        GIL-atomic; readers take the ENGINE's locked-copy surfaces
        (timelines()) rather than iterating a live request's list."""
        self.events.append((phase, time.perf_counter(), info or None))

    def done(self) -> bool:
        return self._done.is_set()

    def wait(self, timeout=None) -> bool:
        return self._done.wait(timeout)

    def result(self, timeout=None) -> np.ndarray:
        """The full (prompt + generated) token sequence. Raises on a
        non-completed outcome (the partial `tokens` stay readable)."""
        if not self._done.wait(timeout):
            raise TimeoutError(f"request {self.id} still in flight")
        if self.outcome != OUTCOME_COMPLETED:
            raise RuntimeError(
                f"request {self.id} finished {self.outcome}"
                + (f": {self.detail}" if self.detail else ""))
        return np.concatenate(
            [np.asarray(self.prompt, np.int32),
             np.asarray(self.tokens, np.int32)])

    @property
    def ttft_s(self):
        if self.first_token_ts is None:
            return None
        return self.first_token_ts - self.submitted


class ServingEngine:
    """The request-level continuous-batching engine over one model.

    `max_slots` bounds in-flight sequences (the fixed decode-batch
    shape); `page_size` tokens per KV page; `num_pages` the pool size
    (default: enough for every slot at `max_ctx` — pass less to trade
    occupancy for HBM); `steps_per_sync` decode steps per host sync (the
    admission/eviction cadence); `eos_id` stops a sequence early;
    `ttft_deadline_s` bounds admission-to-first-token (queued requests
    past it finish "timeout"). Decode is greedy.
    """

    _seq = 0
    _seq_lock = threading.Lock()

    def __init__(self, model, *, max_slots=4, page_size=8, num_pages=None,
                 max_ctx=None, dtype=None, kv_dtype=None,
                 moe_capacity_factor=None, steps_per_sync=4, eos_id=None,
                 prompt_buckets=None, queue_limit=128,
                 ttft_deadline_s=None, poll_interval_s=0.01,
                 use_kernel=None, timeline_capacity=256,
                 draft_model=None, spec_k=0):
        import jax

        from . import serving
        if kv_dtype not in (None, "int8", "int4"):
            raise ValueError(
                f"kv_dtype {kv_dtype!r} not in (None,'int8','int4')")
        if (draft_model is None) != (not spec_k):
            raise ValueError("speculative decoding needs BOTH "
                             "draft_model and spec_k >= 1")
        if draft_model is not None \
                and draft_model.vocab_size < model.vocab_size:
            raise ValueError("draft vocab must cover the target's")
        self.model = model
        self.max_slots = int(max_slots)
        self.page_size = int(page_size)
        self.max_ctx = int(max_ctx if max_ctx is not None else model.max_seq)
        if self.max_ctx > model.max_seq:
            raise ValueError(f"max_ctx {self.max_ctx} exceeds the "
                             f"model's max_seq {model.max_seq}")
        self.dtype = dtype
        self.kv_dtype = kv_dtype
        self.steps_per_sync = max(1, int(steps_per_sync))
        self.eos_id = eos_id
        self.queue_limit = int(queue_limit)
        self.ttft_deadline_s = ttft_deadline_s
        self.poll_interval_s = float(poll_interval_s)
        self.use_kernel = use_kernel

        # one _DecodeCore shared with the dense path's math (S0 unused
        # on the paged step; T = max_ctx bounds positions)
        self.core = serving._decode_core(
            model, 0, self.max_ctx, moe_capacity_factor,
            kv_dtype=kv_dtype)
        # speculative decoding: the draft model gets its OWN page pools
        # (fp cache — draft proposals gate acceptance, not correctness)
        # indexed by the SAME page table, so allocation stays 1:1
        self.draft_model = draft_model
        self.spec_k = int(spec_k or 0)
        self.dcore = None if draft_model is None else \
            serving._decode_core(draft_model, 0, self.max_ctx,
                                 moe_capacity_factor, kv_dtype=None)
        self.max_pages_per_seq = -(-self.max_ctx // self.page_size)
        if num_pages is None:
            num_pages = self.max_slots * self.max_pages_per_seq
        self.num_pages = int(num_pages)
        maxp = self.max_ctx - 1
        if prompt_buckets is None:
            b, prompt_buckets = 16, []
            while b < maxp:
                prompt_buckets.append(b)
                b *= 2
            prompt_buckets.append(maxp)
        self.prompt_buckets = sorted(set(
            min(int(b), maxp) for b in prompt_buckets))
        if not self.prompt_buckets or self.prompt_buckets[-1] < maxp:
            # the largest bucket must cover every admissible prompt
            # (submit validates s0 <= max_ctx - 1): a user-supplied
            # list topping out below that would send an over-bucket
            # prompt into _admit_one's fixed-size pad and kill the
            # decode thread
            self.prompt_buckets.append(maxp)

        # host-side state (touched only under _lock)
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._queue: "deque[EngineRequest]" = deque()
        self._slots: "list[EngineRequest | None]" = [None] * self.max_slots
        self._free_pages = list(range(self.num_pages))
        N, M = self.max_slots, self.max_pages_per_seq
        self._tok = np.zeros(N, np.int32)
        self._lens = np.zeros(N, np.int32)
        self._limits = np.zeros(N, np.int32)
        self._active = np.zeros(N, bool)
        self._ptab = np.zeros((N, M), np.int32)
        self._stop = threading.Event()
        self._draining = False
        # requests popped from the queue but not yet seated in a slot
        # (prefill runs outside the lock) — a graceful drain must wait
        # for these too, or it tears down around a half-admitted
        # request and evicts it
        self._admitting = 0
        self._thread = None
        self._pools = None
        self._dpools = None
        self._draft_params = None
        self._steps = 0
        # spec-decoding economics (host-side running totals; fed from
        # each sync's device counters, surfaced on serving_report)
        self._spec = {"drafted": 0, "accepted": 0, "bonus": 0,
                      "rounds": 0}
        self._finished = {o: 0 for o in REQUEST_OUTCOMES}
        self._recent_ttft: "deque[float]" = deque(maxlen=256)
        self._recent_qdelay: "deque[float]" = deque(maxlen=256)
        # request-level observability (singa_tpu.slo): finished request
        # timelines, decode-sync records (for the trace's decode-step
        # slices + flow links), and terminal stamps for the RPS gauge —
        # all appended under _lock, read via locked-copy accessors
        self._timelines: "deque[dict]" = deque(
            maxlen=int(timeline_capacity))
        self._sync_ring: "deque[dict]" = deque(maxlen=512)
        self._sync_id = 0
        self._recent_done: "deque[float]" = deque(maxlen=512)
        self._donate = jax.default_backend() == "tpu"
        self._build_executables()

    # -- pools ---------------------------------------------------------------
    def _alloc_pools(self, core, model):
        import jax.numpy as jnp
        c = core
        D = c.E // c.H
        Hp = c.Hkv // c.P
        PD = c.P * D
        ps = self.page_size
        n = self.num_pages
        cd = jnp.bfloat16 if self.dtype in ("bfloat16", "int8") \
            else jnp.float32
        qw = PD // 2 if c.kv4 else PD
        qd = jnp.uint8 if c.kv4 else jnp.int8
        pools = []
        for _ in range(len(model.blocks)):
            if c.kvq:
                pools.append((
                    (jnp.zeros((n, Hp, ps, qw), qd),
                     jnp.zeros((n, Hp, ps, c.P), jnp.float32)),
                    (jnp.zeros((n, Hp, ps, qw), qd),
                     jnp.zeros((n, Hp, ps, c.P), jnp.float32))))
            else:
                pools.append((jnp.zeros((n, Hp, ps, PD), cd),
                              jnp.zeros((n, Hp, ps, PD), cd)))
        return pools

    def _pool_arrays(self):
        """memory.py kv_cache provider: the CURRENT pool buffers (they
        change identity every donated step, so re-read per snapshot).
        Includes the draft model's page pools under spec decoding —
        draft KV is KV-cache HBM like any other."""
        import jax
        out = []
        for pools in (self._pools, self._dpools):
            if pools is not None:
                out.extend(jax.tree_util.tree_leaves(pools))
        return out

    def _draft_param_arrays(self):
        """memory.py params provider for the DRAFT model's decode tree
        (the target's decode params stay whatever region its training
        build attributed them to; the draft exists only for serving)."""
        import jax
        p = self._draft_params
        return jax.tree_util.tree_leaves(p) if p is not None else ()

    def pool_bytes(self) -> int:
        """Bytes in the TARGET page pool only — the kv_dtype= label on
        singa_serve_kv_pool_bytes describes THIS pool's storage mode,
        and the report's draft-overhead line already shows the (fp)
        draft pool via draft_pool_bytes. The memory-ledger provider
        (_pool_arrays) stays inclusive: draft KV is kv_cache HBM."""
        import jax
        p = self._pools
        return sum(int(getattr(a, "nbytes", 0) or 0)
                   for a in (jax.tree_util.tree_leaves(p)
                             if p is not None else ()))

    def draft_pool_bytes(self) -> int:
        import jax
        dp = self._dpools
        return sum(int(getattr(a, "nbytes", 0) or 0)
                   for a in (jax.tree_util.tree_leaves(dp)
                             if dp is not None else ()))

    def draft_param_bytes(self) -> int:
        return sum(int(getattr(a, "nbytes", 0) or 0)
                   for a in self._draft_param_arrays())

    # -- executables ---------------------------------------------------------
    def _build_executables(self):
        import jax
        import jax.numpy as jnp
        from jax import lax

        from . import introspect, serving
        core = self.core
        dcore = self.dcore
        ps = self.page_size
        n_pages = self.num_pages
        N = self.max_slots
        H = self.steps_per_sync
        K = self.spec_k
        eos = self.eos_id
        use_kernel = self.use_kernel

        def scatter_prefill(c, kvs, pools, pvec, off):
            """Scatter one model's per-block prompt K/V rows into its
            page pools (quantized or fp, per the core's mode)."""
            Sb = pvec.shape[0]
            new_pools = []
            for (k, v), pool in zip(kvs, pools):
                if c.kvq:
                    (K8, Ks), (V8, Vs) = pool
                    k8, ks = c._quant_kv(k, 1, Sb)
                    v8, vs = c._quant_kv(v, 1, Sb)
                    K8 = K8.at[pvec, :, off, :].set(
                        k8[0].swapaxes(0, 1), mode="drop")
                    Ks = Ks.at[pvec, :, off, :].set(
                        ks[0].swapaxes(0, 1), mode="drop")
                    V8 = V8.at[pvec, :, off, :].set(
                        v8[0].swapaxes(0, 1), mode="drop")
                    Vs = Vs.at[pvec, :, off, :].set(
                        vs[0].swapaxes(0, 1), mode="drop")
                    new_pools.append(((K8, Ks), (V8, Vs)))
                else:
                    K, V = pool
                    K = K.at[pvec, :, off, :].set(
                        c._pack(k, 1, Sb)[0].swapaxes(0, 1),
                        mode="drop")
                    V = V.at[pvec, :, off, :].set(
                        c._pack(v, 1, Sb)[0].swapaxes(0, 1),
                        mode="drop")
                    new_pools.append((K, V))
            return new_pools

        def prefill_head(p, prompt, true_len):
            h, kvs = core.prefill_parts(p, prompt, 1)
            hl = lax.dynamic_slice_in_dim(h, true_len - 1, 1,
                                          axis=1)[:, 0]
            logits = core.head(p, hl)                         # (1, V)
            tok0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)[0]
            nf = jnp.sum((~jnp.isfinite(logits)).astype(jnp.int32))
            return kvs, tok0, nf

        # Device scopes: every instruction of a prefill program sits under
        # `prefill.b<bucket>`, of the decode program under `decode`, and
        # inside them under the layers' own names (serving._DecodeCore).
        def prefill_fn(p, pools, prompt, true_len, pages):
            Sb = prompt.shape[1]
            with jax.named_scope(f"prefill.b{Sb}"):
                kvs, tok0, nf = prefill_head(p, prompt, true_len)
                t = jnp.arange(Sb)
                # positions past the true prompt scatter out of bounds
                # and are DROPPED — the padded bucket tail never reaches
                # a page
                pvec = jnp.where(t < true_len, pages[t // ps], n_pages)
                off = t % ps
                return scatter_prefill(core, kvs, pools, pvec, off), \
                    tok0, nf

        def spec_prefill_fn(p, pd, pools, dpools, prompt, true_len,
                            pages):
            Sb = prompt.shape[1]
            with jax.named_scope(f"prefill.b{Sb}"):
                kvs, tok0, nf = prefill_head(p, prompt, true_len)
                # the draft prefills the SAME prompt into its own pools
                # (its logits are unused — the first token is the
                # target's)
                _hd, dkvs = dcore.prefill_parts(pd, prompt, 1)
                t = jnp.arange(Sb)
                pvec = jnp.where(t < true_len, pages[t // ps], n_pages)
                off = t % ps
                return (scatter_prefill(core, kvs, pools, pvec, off),
                        scatter_prefill(dcore, dkvs, dpools, pvec, off),
                        tok0, nf)

        @jax.named_scope("decode")
        def decode_fn(p, pools, tok, page_table, lens, limits, active):
            def step(carry, _):
                pools, tok, lens, active, nf = carry
                logits, pools = core.paged_token_step(
                    p, tok, pools, page_table, lens, active, N, ps,
                    n_pages, use_kernel=use_kernel)
                nf = nf + jnp.sum(((~jnp.isfinite(logits))
                                   & active[:, None]).astype(jnp.int32))
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                emit_tok, emit_mask = nxt, active
                new_lens = jnp.where(active, lens + 1, lens)
                alive = active & (new_lens < limits)
                if eos is not None:
                    alive = alive & (nxt != eos)
                tok = jnp.where(active, nxt, tok)
                return (pools, tok, new_lens, alive, nf), \
                    (emit_tok, emit_mask)

            carry = (pools, tok, lens, active, jnp.int32(0))
            (pools, tok, lens, active, nf), (toks, masks) = lax.scan(
                step, carry, None, length=H)
            return pools, tok, lens, active, toks, masks, nf

        @jax.named_scope("decode")
        def spec_decode_fn(p, pd, pools, dpools, tok, page_table, lens,
                           limits, active):
            """H speculative verify rounds per sync: draft K proposals
            sequentially (small model, per-slot positions), verify all
            of them in ONE target forward (K+1-token causal ladder),
            commit the longest accepted prefix + the target's own next
            token. Emits (H, N, K+1) candidate tokens with per-round
            per-slot take counts — the host unpacks the ragged
            emission. Greedy-exact: every committed token is the
            target's argmax given the committed prefix."""
            nidx = jnp.arange(N)
            jj = jnp.arange(K + 1)[None, :]

            def round_(carry, _):
                pools, dpools, tok, lens, active, nf, dr, ac, bo = carry

                def dstep(dc, j):
                    dpools, dt = dc
                    lg, dpools = dcore.paged_verify_step(
                        pd, dt[:, None], dpools, page_table, lens + j,
                        active, N, ps, n_pages, 1,
                        use_kernel=use_kernel,
                        write_limits=limits + 1)
                    nxt = jnp.argmax(
                        lg[:, 0].astype(jnp.float32),
                        axis=-1).astype(jnp.int32)
                    return (dpools, nxt), nxt

                # K+1 draft steps for K proposals: the extra step
                # writes the draft's KV row for the bonus position —
                # without it a fully-accepted round leaves a ZERO hole
                # in the draft pool that degrades every later
                # proposal (see build_spec_decode's draft loop)
                (dpools, _), drafts = lax.scan(
                    dstep, (dpools, tok), jnp.arange(K + 1))
                drafts = drafts[:K].T                    # (N, K)
                feed = jnp.concatenate([tok[:, None], drafts], axis=1)
                logits, pools = core.paged_verify_step(
                    p, feed, pools, page_table, lens, active, N, ps,
                    n_pages, K + 1, use_kernel=use_kernel,
                    write_limits=limits + 1)
                g = jnp.argmax(logits.astype(jnp.float32),
                               axis=-1).astype(jnp.int32)  # (N, K+1)
                match = (g[:, :K] == drafts).astype(jnp.int32)
                a = jnp.sum(jnp.cumprod(match, axis=1), axis=1)
                rem = limits - lens
                take = jnp.where(active,
                                 jnp.minimum(a + 1, rem), 0)
                has_eos = jnp.zeros((N,), bool)
                if eos is not None:
                    iseos = (g == eos) & (jj < take[:, None])
                    has_eos = jnp.any(iseos, axis=1)
                    first = jnp.argmax(iseos, axis=1)
                    take = jnp.where(has_eos,
                                     jnp.minimum(take, first + 1),
                                     take)
                nf = nf + jnp.sum(((~jnp.isfinite(logits))
                                   & (jj < take[:, None])[..., None])
                                  .astype(jnp.int32))
                new_lens = lens + take
                alive = active & (new_lens < limits) & ~has_eos
                tok = jnp.where(active,
                                g[nidx, jnp.clip(take - 1, 0, K)],
                                tok)
                n_act = jnp.sum(active.astype(jnp.int32))
                dr = dr + K * n_act
                # a round truncated by the token budget or an eos
                # inside the accepted prefix commits ONLY accepted
                # draft tokens (take <= a): the bonus token exists
                # only when the full a+1 window committed
                bo_i = ((take > 0) & (take > a)).astype(jnp.int32)
                ac = ac + jnp.sum(take - bo_i)
                bo = bo + jnp.sum(bo_i)
                return (pools, dpools, tok, new_lens, alive, nf, dr,
                        ac, bo), (g, take)

            zero = jnp.int32(0)
            carry = (pools, dpools, tok, lens, active, zero, zero,
                     zero, zero)
            (pools, dpools, tok, lens, active, nf, dr, ac, bo), \
                (toks, takes) = lax.scan(round_, carry, None, length=H)
            return (pools, dpools, tok, lens, active, toks, takes, nf,
                    dr, ac, bo)

        if dcore is None:
            don = (1,) if self._donate else ()
            dkw = {"donate_argnums": don} if don else {}
            self._prefill = introspect.AotExecutor(
                jax.jit(prefill_fn, **dkw), "serving.engine_prefill",
                names=("params", "pools", "prompt", "true_len",
                       "pages"), donated=don)
            self._decode = introspect.AotExecutor(
                jax.jit(decode_fn, **dkw), "serving.engine_step",
                names=("params", "pools", "tok", "page_table", "lens",
                       "limits", "active"), donated=don)
        else:
            # spec executables get their OWN introspect keys: the
            # draft-bearing programs have different signatures and HLO
            # fingerprints, and a recompile must blame the right one
            don = (2, 3) if self._donate else ()
            dkw = {"donate_argnums": don} if don else {}
            self._prefill = introspect.AotExecutor(
                jax.jit(spec_prefill_fn, **dkw),
                "serving.engine_spec_prefill",
                names=("params", "draft_params", "pools", "dpools",
                       "prompt", "true_len", "pages"), donated=don)
            self._decode = introspect.AotExecutor(
                jax.jit(spec_decode_fn, **dkw),
                "serving.engine_spec_step",
                names=("params", "draft_params", "pools", "dpools",
                       "tok", "page_table", "lens", "limits",
                       "active"), donated=don)

    # -- lifecycle -----------------------------------------------------------
    def start(self) -> "ServingEngine":
        """Allocate the page pool, register it as the memory ledger's
        kv_cache provider, and start the decode thread. Idempotent."""
        from . import memory, serving
        with self._lock:
            if self._thread is not None:
                return self
            self._params = serving.decode_state(self.model, self.dtype)
            self._pools = self._alloc_pools(self.core, self.model)
            if self.dcore is not None:
                self._draft_params = serving.decode_state(
                    self.draft_model, self.dtype)
                self._dpools = self._alloc_pools(self.dcore,
                                                 self.draft_model)
            self._stop.clear()
            with ServingEngine._seq_lock:
                ServingEngine._seq += 1
                n = ServingEngine._seq
            self._thread = threading.Thread(
                target=self._loop, name=f"singa-serve-{n}", daemon=True)
        memory.register_provider(memory.REGION_KV_CACHE, self,
                                 self._pool_arrays)
        if self.dcore is not None:
            # draft decode params are serving-only buffers: attribute
            # them as params so /memz shows where the draft's HBM went
            memory.register_provider(memory.REGION_PARAMS, self,
                                     self._draft_param_arrays)
        with _registry_lock:
            _engines.append(self)
        m = _metrics()
        m["slots"].set(float(self.max_slots))
        m["pages"].set(float(self.num_pages))
        m["pages_in_use"].set(0.0)
        kvl = self.kv_dtype or "fp"
        assert kvl in KV_DTYPES, self.kv_dtype
        m["kv_pool_bytes"].set(float(self.pool_bytes()), kv_dtype=kvl)
        self._thread.start()
        return self

    def stop(self, drain_outcome: str = OUTCOME_EVICTED, *,
             drain: bool = False, drain_timeout_s: float = 300.0):
        """Stop the decode thread (joined), finish every in-flight and
        queued request as `drain_outcome` (partial tokens retained),
        free the pool, and unregister the kv_cache provider.
        Idempotent.

        With `drain=True` the stop is GRACEFUL: new submissions are
        refused ("engine draining"), queued-but-unadmitted requests are
        pulled back and RETURNED to the caller (outcome still None —
        resubmit them elsewhere; the serving router does exactly this on
        a rolling restart), and the decode loop keeps running until
        every in-flight slot finishes naturally (bounded by
        `drain_timeout_s`) before the normal teardown. A graceful stop
        of a healthy engine therefore produces no "evicted" terminals.
        Returns the handed-back request list (empty unless drain=True).
        """
        from . import memory
        handed_back: "list[EngineRequest]" = []
        if drain:
            with self._lock:
                if self._thread is not None and not self._stop.is_set():
                    # refuse new admissions, pull the queue back BEFORE
                    # the loop's next admission pass can seat it, then
                    # let the in-flight slots decode to completion
                    self._draining = True
                    handed_back = list(self._queue)
                    self._queue.clear()
                    self._cond.notify_all()
            for req in handed_back:
                req.mark(PHASE_QUEUE, handed_back=True)
            deadline = time.monotonic() + float(drain_timeout_s)
            while time.monotonic() < deadline:
                with self._lock:
                    busy = (any(r is not None for r in self._slots)
                            or self._admitting > 0)
                    alive = self._thread is not None \
                        and self._thread.is_alive()
                if not busy or not alive:
                    break
                time.sleep(min(self.poll_interval_s, 0.05))
        with self._lock:
            t = self._thread
            self._thread = None
            self._stop.set()
            self._cond.notify_all()
        if t is not None:
            t.join(timeout=30.0)
        with self._lock:
            drain = [r for r in self._slots if r is not None]
            drain += list(self._queue)
            self._queue.clear()
            self._slots = [None] * self.max_slots
            self._active[:] = False
        for req in drain:
            self._finish(req, drain_outcome)
        with self._lock:
            self._free_pages = list(range(self.num_pages))
            self._pools = None
            self._dpools = None
            self._draft_params = None
            self._draining = False
        memory.unregister_provider(memory.REGION_KV_CACHE, self)
        memory.unregister_provider(memory.REGION_PARAMS, self)
        with _registry_lock:
            if self in _engines:
                _engines.remove(self)
        if observe.is_enabled():
            m = _metrics()
            m["pages_in_use"].set(0.0)
            m["occupancy"].set(0.0)
            m["queue_depth"].set(0.0)
        return handed_back

    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    # -- submission ----------------------------------------------------------
    def submit(self, prompt, max_new, deadline_s=None,
               ttft_deadline_s=None, trace_ctx=None,
               synthetic=False) -> EngineRequest:
        """Enqueue one request: `prompt` a 1-D int sequence, `max_new`
        tokens to generate. Returns the handle immediately; a full
        queue, an over-length request, or a stopped engine REJECTS it
        (handle already terminal). Greedy decode only. `trace_ctx`
        carries a router-minted fleet-unique trace id through the
        request's timeline, so the merged fleet trace links this
        replica's slices into the cross-process flow. `synthetic` marks
        audit canary/replay probes: they ride the identical decode path
        but are excluded from demand-facing accounting (SLO attainment,
        capacity forecast, admitted-RPS) via the timeline flag."""
        ids = np.asarray(prompt, np.int32).reshape(-1)
        with ServingEngine._seq_lock:
            ServingEngine._seq += 1
            rid = ServingEngine._seq
        req = EngineRequest(
            rid, ids, max_new, deadline_s,
            self.ttft_deadline_s if ttft_deadline_s is None
            else ttft_deadline_s)
        if trace_ctx is not None:
            req.trace = str(trace_ctx)
        if synthetic:
            req.synthetic = True
        npages = -(-(len(ids) + int(max_new)) // self.page_size)
        reason = None
        if len(ids) < 1 or int(max_new) < 1:
            reason = "prompt and max_new must both be >= 1"
        elif len(ids) + int(max_new) > self.max_ctx:
            reason = (f"prompt {len(ids)} + max_new {int(max_new)} "
                      f"exceeds max_ctx {self.max_ctx}")
        elif npages > self.num_pages:
            reason = (f"request needs {npages} pages, pool holds "
                      f"{self.num_pages}")
        if reason is None:
            with self._lock:
                # the liveness check must share the lock with stop()'s
                # shutdown (_thread = None precedes the queue drain
                # under this lock), or a submit racing stop() could
                # append AFTER the drain and never reach a terminal
                # outcome
                if self._thread is None or self._stop.is_set() \
                        or not self._thread.is_alive():
                    reason = "engine not running"
                elif self._draining:
                    reason = "engine draining (graceful stop)"
                elif len(self._queue) >= self.queue_limit:
                    reason = f"admission queue full ({self.queue_limit})"
                else:
                    self._queue.append(req)
                    req.mark(PHASE_QUEUE, depth=len(self._queue))
                    self._cond.notify_all()
        if reason is not None:
            req.detail = reason
            self._finish(req, OUTCOME_REJECTED)
        elif observe.is_enabled():
            _metrics()["queue_depth"].set(float(len(self._queue)))
        return req

    def prewarm(self, lens, max_new: int = 2, timeout_s: float = 600.0):
        """Drive one tiny synthetic request through every prefill bucket
        that the prompt lengths in `lens` map to, forcing the
        prefill/decode/spec executables to build (or load from the warm
        store) BEFORE the replica advertises ready. Returns
        `(buckets, first_token_wall)` — the sorted bucket list exercised
        and the wall-clock timestamp of the FIRST token the engine ever
        produced, which the router's ready line turns into
        spawn-to-first-token. Raises
        RuntimeError when a bucket's warmup request stalls past
        `timeout_s` or terminates without producing a token (a replica
        that cannot decode must fail loud at spawn, not at first real
        traffic). Warmup requests are synthetic: they ride the identical
        staging path but stay out of demand-facing accounting."""
        buckets = sorted({self._bucket(int(s)) for s in lens})
        first_token_wall = None
        for b in buckets:
            n = max(1, min(int(b), self.max_ctx - int(max_new)))
            w = self.submit(np.zeros(n, np.int32) + 1, int(max_new),
                            synthetic=True)
            if not w.wait(float(timeout_s)):
                raise RuntimeError(
                    f"prewarm (bucket {b}) stalled after {timeout_s}s")
            if w.outcome != OUTCOME_COMPLETED or w.first_token_ts is None:
                raise RuntimeError(
                    f"prewarm (bucket {b}) failed: {w.outcome} "
                    f"({w.detail})")
            if first_token_wall is None:
                # first_token_ts is monotonic-clock; rebase to wall
                # time so the parent (a different process) can subtract
                # its own spawn timestamp
                first_token_wall = (float(w.first_token_ts)
                                    + (time.time() - time.monotonic()))
        return buckets, first_token_wall

    # -- terminal bookkeeping ------------------------------------------------
    def _timeline_of(self, req: EngineRequest, outcome: str) -> dict:
        """The ring entry for one terminal request (caller holds
        _lock): the phase-stamped events, the decode syncs it rode, and
        the derived latency summary the SLO tracker evaluates."""
        total_s = req.finished_ts - req.submitted
        rate = None
        if outcome == OUTCOME_COMPLETED and req.tokens and total_s > 0:
            rate = len(req.tokens) / total_s
        return {
            "id": req.id,
            "outcome": outcome,
            "trace": req.trace,
            "synthetic": bool(req.synthetic),
            "prompt_tokens": int(len(req.prompt)),
            "new_tokens": len(req.tokens),
            "slot": req.last_slot,
            "queue_delay_s": round(req.admitted - req.submitted, 6)
            if req.admitted else None,
            "ttft_s": round(req.ttft_s, 6)
            if req.ttft_s is not None else None,
            "total_s": round(total_s, 6),
            "tokens_per_sec": round(rate, 3)
            if rate is not None else None,
            "detail": req.detail,
            "events": [(p, round(float(t), 7), info)
                       for p, t, info in list(req.events)],
            "syncs": list(req.syncs),
        }

    def _finish(self, req: EngineRequest, outcome: str):
        assert outcome in REQUEST_OUTCOMES, outcome
        with self._lock:
            # submit()-time rejects land here on the caller's thread,
            # everything else on the engine thread — one gate for both
            if req.outcome is not None:
                return
            req.outcome = outcome
            req.finished_ts = time.monotonic()
            self._finished[outcome] = self._finished.get(outcome, 0) + 1
            req.mark(PHASE_TERMINAL, outcome=outcome)
            self._recent_done.append(req.finished_ts)
            timeline = self._timeline_of(req, outcome)
            self._timelines.append(timeline)
        if observe.is_enabled():
            m = _metrics()
            m["requests"].inc(outcome=outcome)
            total_s = req.finished_ts - req.submitted
            m["request_s"].observe(total_s)
            if outcome == OUTCOME_COMPLETED and req.tokens and total_s > 0:
                m["rate"].set(len(req.tokens) / total_s)
            observe.get_registry().emit({
                "kind": "serve_request", "id": req.id,
                "outcome": outcome,
                "prompt_tokens": int(len(req.prompt)),
                "new_tokens": len(req.tokens),
                "queue_delay_s": round(req.admitted - req.submitted, 6)
                if req.admitted else None,
                "ttft_s": round(req.ttft_s, 6)
                if req.ttft_s is not None else None,
                "total_s": round(total_s, 6),
                "detail": req.detail,
            })
        for cb in tuple(_request_listeners):
            try:
                cb(req, timeline)
            except Exception:
                pass  # a listener must never break the serving path
        req._done.set()

    def _bucket(self, s0: int) -> int:
        for b in self.prompt_buckets:
            if b >= s0:
                return b
        return self.prompt_buckets[-1]

    # -- the decode loop -----------------------------------------------------
    def _evict(self, req: EngineRequest, outcome: str):
        """Free a slot (caller holds no lock): pages back to the pool,
        slot state cleared, request finished."""
        with self._lock:
            slot = req.slot
            if slot is not None and self._slots[slot] is req:
                self._slots[slot] = None
                self._active[slot] = False
                self._lens[slot] = 0
                self._tok[slot] = 0
                self._ptab[slot, :] = 0
            self._free_pages.extend(req.pages)
            req.pages = []
            req.slot = None
        self._finish(req, outcome)

    def _admit_one(self, req: EngineRequest, slot: int, pages) -> bool:
        """Run prefill for `req` into `slot` (pages already allocated).
        Returns False when the request finished at prefill (max_new==1
        or an immediate eos)."""
        import jax
        import jax.numpy as jnp

        from . import watchdog
        s0 = len(req.prompt)
        bucket = self._bucket(s0)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :s0] = req.prompt
        # prefill scatters only the s0 prompt rows, so it needs only the
        # pages covering the BUCKET — a fixed length per bucket, keeping
        # the prefill signature (and compile) one-per-bucket instead of
        # one per (bucket, max_new) combination
        page_arr = np.zeros(-(-bucket // self.page_size), np.int32)
        n_pref = min(len(pages), len(page_arr))
        page_arr[:n_pref] = pages[:n_pref]
        req.admitted = time.monotonic()
        req.last_slot = slot
        req.mark(PHASE_ADMIT, slot=slot, pages=len(pages))
        req.mark(PHASE_PREFILL, bucket=bucket)
        with watchdog.guard("decode", stage="engine_prefill"), \
                observe.span("serving.engine_prefill", bucket=bucket,
                             prompt_tokens=s0):
            if self.dcore is not None:
                pools, dpools, tok0, nf = self._prefill(
                    self._params, self._draft_params, self._pools,
                    self._dpools, jnp.asarray(padded), np.int32(s0),
                    jnp.asarray(page_arr))
                self._dpools = dpools
            else:
                pools, tok0, nf = self._prefill(
                    self._params, self._pools, jnp.asarray(padded),
                    np.int32(s0), jnp.asarray(page_arr))
            tok0 = int(jax.device_get(tok0))
        self._pools = pools
        req.first_token_ts = time.monotonic()
        req.tokens.append(tok0)
        req.mark(PHASE_FIRST_TOKEN, tokens=1)
        if observe.is_enabled():
            m = _metrics()
            m["admitted"].inc()
            m["prefills"].inc()
            m["tokens"].inc()
            m["queue_delay"].observe(req.admitted - req.submitted)
            m["ttft"].observe(req.first_token_ts - req.submitted)
            nfv = int(jax.device_get(nf))
            if nfv:
                from . import health
                health.record_nan_logits(nfv, "engine")
        self._recent_ttft.append(req.first_token_ts - req.submitted)
        self._recent_qdelay.append(req.admitted - req.submitted)
        done = (req.max_new == 1
                or (self.eos_id is not None and tok0 == self.eos_id))
        if done:
            self._evict(req, OUTCOME_COMPLETED)
            return False
        with self._lock:
            self._slots[slot] = req
            req.slot = slot
            self._tok[slot] = tok0
            self._lens[slot] = s0
            # the produced token at cache length L is the
            # (L - S0 + 1)-th generated one: the last real token lands
            # when new_lens reaches S0 + max_new - 1
            self._limits[slot] = s0 + req.max_new - 1
            self._active[slot] = True
            self._ptab[slot, :] = 0
            self._ptab[slot, :len(pages)] = pages
        return True

    def _admission_pass(self):
        """Expire stale queued requests, then admit FIFO while a free
        slot and enough pages exist (head-of-line blocking by design —
        fairness over fragmentation)."""
        now = time.monotonic()
        # TTFT deadlines: ANY queued request that can no longer reach
        # its first token in time finishes "timeout" unslotted — not
        # just the head, or a deadline behind a page-starved head
        # would never fire
        with self._lock:
            expired = [r for r in self._queue
                       if r.ttft_deadline_s is not None
                       and now - r.submitted > r.ttft_deadline_s]
            for r in expired:
                self._queue.remove(r)
        for r in expired:
            r.detail = ("admission-to-first-token deadline "
                        f"{r.ttft_deadline_s}s exceeded")
            self._finish(r, OUTCOME_TIMEOUT)
        while True:
            todo = None
            with self._lock:
                if not self._queue:
                    break
                try:
                    slot = self._slots.index(None)
                except ValueError:
                    break
                req = self._queue[0]
                npages = -(-(len(req.prompt) + req.max_new)
                           // self.page_size)
                if len(self._free_pages) < npages:
                    break
                self._queue.popleft()
                pages = [self._free_pages.pop()
                         for _ in range(npages)]
                req.pages = pages
                self._admitting += 1
                todo = (req, slot, pages)
            try:
                self._admit_one(*todo)
            finally:
                with self._lock:
                    self._admitting -= 1
        if observe.is_enabled():
            m = _metrics()
            with self._lock:
                m["queue_depth"].set(float(len(self._queue)))
                m["pages_in_use"].set(
                    float(self.num_pages - len(self._free_pages)))
                m["occupancy"].set(float(np.sum(self._active)))

    def _loop(self):
        try:
            self._loop_body()
        except BaseException as exc:  # noqa: BLE001 — the loop thread
            # must never die silently: every in-flight and queued
            # request would keep outcome=None and block its caller's
            # wait()/result() forever, with the pages still allocated.
            # Drain everything as "evicted" (detail carries the error)
            # and let stop() find a clean engine.
            detail = f"engine decode loop died: {type(exc).__name__}: " \
                     f"{exc}"
            # flag shutdown BEFORE draining: submit() checks _stop, so
            # no new request can slip into the queue after the drain
            # while this thread is still unwinding (is_alive() alone
            # races the interpreter's thread teardown)
            self._stop.set()
            with self._lock:
                drain = [r for r in self._slots if r is not None]
                drain += list(self._queue)
                self._queue.clear()
                self._slots = [None] * self.max_slots
                self._active[:] = False
                for r in drain:
                    self._free_pages.extend(r.pages)
                    r.pages = []
                    r.slot = None
            for r in drain:
                r.detail = r.detail or detail
                self._finish(r, OUTCOME_EVICTED)
            observe.get_registry().emit(
                {"kind": "serve", "event": "loop_error",
                 "detail": detail})
            raise

    def _loop_body(self):
        import jax

        from . import resilience, watchdog
        while not self._stop.is_set():
            # evict sequences whose total deadline passed mid-decode
            now = time.monotonic()
            with self._lock:
                overdue = [r for r in self._slots
                           if r is not None and r.deadline_s is not None
                           and now - r.submitted > r.deadline_s]
            for req in overdue:
                req.detail = f"deadline {req.deadline_s}s exceeded"
                self._evict(req, OUTCOME_TIMEOUT)
            self._admission_pass()
            with self._lock:
                any_active = bool(np.any(self._active))
                if not any_active:
                    if self._stop.is_set():
                        break
                    self._cond.wait(timeout=self.poll_interval_s)
                    continue
                tok = self._tok.copy()
                ptab = self._ptab.copy()
                lens = self._lens.copy()
                limits = self._limits.copy()
                active = self._active.copy()
            sync_t0 = time.perf_counter()
            spec = self.dcore is not None
            dr = ac = bo = 0
            with watchdog.guard("decode", slots=int(np.sum(active))), \
                    observe.span("serving.engine_step",
                                 slots=int(np.sum(active)),
                                 steps=self.steps_per_sync,
                                 queue=len(self._queue)):
                resilience.fault_point("serving.engine_step",
                                       slots=int(np.sum(active)))
                if spec:
                    out = self._decode(
                        self._params, self._draft_params, self._pools,
                        self._dpools, tok, ptab, lens, limits, active)
                    (pools, dpools, tok_d, lens_d, act_d, toks_d,
                     takes_d, nf_d, dr_d, ac_d, bo_d) = out
                    (tok_new, lens_new, act_new, toks, takes, nf, dr,
                     ac, bo) = jax.device_get(
                        (tok_d, lens_d, act_d, toks_d, takes_d, nf_d,
                         dr_d, ac_d, bo_d))
                else:
                    out = self._decode(self._params, self._pools, tok,
                                       ptab, lens, limits, active)
                    pools, tok_d, lens_d, act_d, toks_d, masks_d, nf \
                        = out
                    tok_new, lens_new, act_new, toks, masks, nf = \
                        jax.device_get((tok_d, lens_d, act_d, toks_d,
                                        masks_d, nf))
            sync_dur = time.perf_counter() - sync_t0
            self._pools = pools
            if spec:
                self._dpools = dpools
                # ragged emission: round h committed takes[h, i] of the
                # K+1 candidates toks[h, i] for slot i (prefix order)
                new_by_slot = [[] for _ in range(self.max_slots)]
                for h in range(toks.shape[0]):
                    th, kh = toks[h], takes[h]
                    for i in range(self.max_slots):
                        t = int(kh[i])
                        if t:
                            new_by_slot[i].extend(
                                int(x) for x in th[i, :t])
            else:
                new_by_slot = [
                    [int(t) for t, mk in zip(toks[:, i], masks[:, i])
                     if mk] for i in range(self.max_slots)]
            emitted = 0
            finished = []
            with self._lock:
                self._sync_id += 1
                sid = self._sync_id
                if spec:
                    self._spec["drafted"] += int(dr)
                    self._spec["accepted"] += int(ac)
                    self._spec["bonus"] += int(bo)
                    self._spec["rounds"] += self.steps_per_sync
                for i in range(self.max_slots):
                    req = self._slots[i]
                    if req is None or not active[i]:
                        continue
                    new = new_by_slot[i]
                    req.tokens.extend(new)
                    emitted += len(new)
                    self._lens[i] = lens_new[i]
                    self._active[i] = act_new[i]
                    self._tok[i] = tok_new[i]
                    # per-sync decode progress on the request timeline:
                    # which decode-step slice it rode (the trace's flow
                    # link) and the tokens generated so far
                    req.mark(PHASE_DECODE, tokens=len(req.tokens),
                             sync=sid)
                    req.syncs.append(sid)
                    if not act_new[i]:
                        finished.append(req)
                self._sync_ring.append({
                    "sync": sid, "t0": round(sync_t0, 7),
                    "dur": round(sync_dur, 7),
                    "tid": threading.get_ident(),
                    "slots": int(np.sum(active)),
                    "steps": self.steps_per_sync, "tokens": emitted})
                self._steps += self.steps_per_sync
            for req in finished:
                self._evict(req, OUTCOME_COMPLETED)
            if spec and (dr or bo):
                from . import serving
                serving.record_spec(int(dr), int(ac), int(bo),
                                    self.steps_per_sync)
            if observe.is_enabled():
                m = _metrics()
                m["steps"].inc(self.steps_per_sync)
                if emitted:
                    m["tokens"].inc(emitted)
                if int(nf):
                    from . import health
                    health.record_nan_logits(int(nf), "engine")
                with self._lock:
                    m["occupancy"].set(float(np.sum(self._active)))
                    m["pages_in_use"].set(
                        float(self.num_pages - len(self._free_pages)))

    # -- reporting -----------------------------------------------------------
    def timelines(self) -> "list[dict]":
        """LOCKED copy of the finished-request timeline ring (oldest
        first). Diag handler threads and the fleet shard writer read
        while the decode thread appends — iterating the deque raw
        races the append (RuntimeError: deque mutated during
        iteration)."""
        with self._lock:
            return list(self._timelines)

    def active_timelines(self) -> "list[dict]":
        """Timeline-shaped dicts for the IN-FLIGHT requests (queued +
        slotted), outcome None and no terminal event — what the fleet
        shard publishes so a replica killed mid-request leaves a record
        of the work it had done (the failover trace's victim track).
        Locked copy like timelines(); the event lists are snapshotted
        (appends are GIL-atomic, the list() copy is the safe read)."""
        with self._lock:
            live = [r for r in self._slots if r is not None]
            live.extend(self._queue)
        out = []
        for req in live:
            if req.outcome is not None:
                continue  # finished while we copied
            out.append({
                "id": req.id,
                "outcome": None,
                "trace": req.trace,
                "prompt_tokens": int(len(req.prompt)),
                "new_tokens": len(req.tokens),
                "slot": req.last_slot,
                "ttft_s": round(req.ttft_s, 6)
                if req.ttft_s is not None else None,
                "detail": req.detail,
                "events": [(p, round(float(t), 7), info)
                           for p, t, info in list(req.events)],
                "syncs": list(req.syncs),
            })
        return out

    def sync_records(self) -> "list[dict]":
        """LOCKED copy of the decode-sync ring — the trace's
        decode-step slices and the flow-link targets."""
        with self._lock:
            return list(self._sync_ring)

    def recent_ttfts(self) -> "list[float]":
        with self._lock:
            return list(self._recent_ttft)

    def rps(self, window_s: float = 10.0) -> float:
        """Terminal requests per second over the trailing window. When
        the stamp ring is FULL and its oldest entry is younger than the
        window, the ring covers less than `window_s` — divide by the
        covered span instead, or a hot replica (>maxlen/window req/s)
        would read as capped at ~51 rps on the very surfaces a router
        balances with."""
        now = time.monotonic()
        with self._lock:
            n = sum(1 for t in self._recent_done
                    if now - t <= window_s)
            full = len(self._recent_done) == self._recent_done.maxlen
            oldest = self._recent_done[0] if self._recent_done else None
        span = window_s
        if full and oldest is not None and now - oldest < window_s:
            span = max(now - oldest, 1e-6)
        return n / span

    def decode_tok_s(self, window_s: float = 10.0) -> "float | None":
        """Decoded tokens per second over the trailing window of the
        sync ring — the measured rate the capacity model holds against
        the roofline's bytes-per-token bandwidth floor. None before
        the first sync lands in the window (no rate, not a zero). The
        same short-span correction as `rps`: when the ring covers less
        than `window_s`, divide by the covered span."""
        now = time.perf_counter()
        with self._lock:
            recs = [r for r in self._sync_ring
                    if now - r["t0"] <= window_s]
        if not recs:
            return None
        span = min(window_s, max(now - recs[0]["t0"], 1e-6))
        return sum(r["tokens"] for r in recs) / span

    def report(self) -> dict:
        with self._lock:
            active = int(np.sum(self._active))
            qd = len(self._queue)
            free = len(self._free_pages)
            spec = dict(self._spec)
        # locked copy: report() now runs on fleet-writer and diag
        # threads while the decode thread appends
        ttfts = self.recent_ttfts()
        tok_s = self.decode_tok_s()
        pct = pctile
        return {
            "running": self.running(),
            "slots": self.max_slots,
            "active": active,
            "queue_depth": qd,
            "pages_total": self.num_pages,
            "pages_in_use": self.num_pages - free,
            "page_size": self.page_size,
            "pool_bytes": self.pool_bytes(),
            "steps": self._steps,
            "rps": round(self.rps(), 3),
            "decode_tok_s": round(tok_s, 3)
            if tok_s is not None else None,
            "finished": dict(self._finished),
            "ttft_p50_s": pct(ttfts, 0.5),
            "ttft_p99_s": pct(ttfts, 0.99),
            "kv_dtype": self.kv_dtype,
            "max_ctx": self.max_ctx,
            "spec_k": self.spec_k or None,
            "spec": spec,
            "spec_acceptance": (spec["accepted"] / spec["drafted"])
            if spec["drafted"] else None,
            "draft_params_bytes": self.draft_param_bytes(),
            "draft_pool_bytes": self.draft_pool_bytes(),
        }


# ---- module registry (the conftest teardown contract) ----------------------

_engines: "list[ServingEngine]" = []
_registry_lock = threading.Lock()

# Terminal-request listeners: `cb(req, timeline)` fires once per request
# reaching a terminal outcome (on whichever thread finished it), AFTER
# the engine's own bookkeeping and BEFORE the handle's done-event is
# set, so a caller woken by wait() observes the listener's effects.
# singa_tpu.slo's SLOTracker is the first subscriber.
_request_listeners: list = []


def add_request_listener(cb):
    """Register `cb(req, timeline)` on every terminal request.
    Exceptions are swallowed — a listener must never break serving."""
    _request_listeners.append(cb)
    return cb


def remove_request_listener(cb):
    """Unregister (equality match, like observe's listeners)."""
    _request_listeners[:] = [c for c in _request_listeners if c != cb]


def request_listeners() -> list:
    """The registered terminal-request listeners (the conftest leak
    check reads this after slo.reset())."""
    return list(_request_listeners)


def clear_request_listeners():
    """Drop every listener (test-teardown escape hatch)."""
    _request_listeners.clear()


def get_engines() -> "list[ServingEngine]":
    return list(_engines)


def reset():
    """Stop every live engine (decode threads joined, queues drained as
    'evicted', pools freed, kv_cache providers unregistered) — the test
    conftest calls this per test so suites never leak singa-serve-*
    threads or page pools."""
    for e in get_engines():
        e.stop()


def serving_report() -> str:
    """The /statusz `== serving ==` section: per-engine slot occupancy,
    page-pool utilization, queue depth and outcome counts."""
    lines = ["== serving =="]

    def _router_rows():
        # the control-plane view rides along whenever a router is
        # installed — in a coordinator process that is often the ONLY
        # serving state (the engines live in replica subprocesses)
        try:
            from . import router
            return router.serving_lines()
        except Exception:
            return []

    engines = get_engines()
    if not engines:
        rows = _router_rows()
        if rows:
            lines.extend(rows)
        else:
            lines.append(
                "no ServingEngine running "
                "(singa_tpu.engine.ServingEngine(model).start())")
        return "\n".join(lines)
    lines.extend(_router_rows())
    for i, e in enumerate(engines):
        r = e.report()
        lines.append(
            f"engine {i}: slots {r['active']}/{r['slots']} active, "
            f"pages {r['pages_in_use']}/{r['pages_total']} "
            f"(x{r['page_size']} tok, {r['pool_bytes'] / 1e6:.2f} MB), "
            f"queue {r['queue_depth']}, steps {r['steps']}"
            + (f", kv {r['kv_dtype']}" if r["kv_dtype"] else ""))
        fin = r["finished"]
        lines.append("  requests: " + ", ".join(
            f"{o} {fin.get(o, 0)}" for o in REQUEST_OUTCOMES))
        if r["ttft_p50_s"] is not None:
            lines.append(
                f"  ttft p50 {r['ttft_p50_s'] * 1e3:.1f} ms"
                + (f", p99 {r['ttft_p99_s'] * 1e3:.1f} ms"
                   if r["ttft_p99_s"] is not None else "")
                + f", rps {r['rps']:.2f}")
        else:
            # zero terminal requests: say so explicitly instead of
            # leaning on pctile's empty-list None (a fresh engine's
            # /statusz must read as "no data", not render nothing)
            lines.append("  ttft: no data (0 admitted requests)")
        # speculative-decoding economics: acceptance + draft overhead,
        # with the same explicit no-data convention as the TTFT line
        if r["spec_k"]:
            sp = r["spec"]
            if sp["drafted"]:
                acc = r["spec_acceptance"]
                lines.append(
                    f"  spec acceptance {acc * 100.0:.1f}% "
                    f"(k={r['spec_k']}, drafted {sp['drafted']}, "
                    f"accepted {sp['accepted']}, bonus {sp['bonus']}, "
                    f"wasted {sp['drafted'] - sp['accepted']})")
                lines.append(
                    f"  spec draft overhead: params "
                    f"{r['draft_params_bytes'] / 1e6:.2f} MB, kv pool "
                    f"{r['draft_pool_bytes'] / 1e6:.2f} MB, "
                    f"{(1.0 - acc) * 100.0:.1f}% of drafted tokens "
                    "wasted")
            else:
                lines.append(
                    f"  spec acceptance: no data (0 verify rounds, "
                    f"k={r['spec_k']})")
        else:
            lines.append("  spec: off (no draft model)")
    return "\n".join(lines)


__all__ = [
    "REQUEST_OUTCOMES", "KV_DTYPES", "ServingEngine", "EngineRequest",
    "pctile",
    "get_engines", "reset", "serving_report",
    "add_request_listener", "remove_request_listener",
    "request_listeners", "clear_request_listeners",
]
