"""Model API with trace-once graph buffering.

Reference parity: python/singa/model.py — `ModelMeta.buffer_operation`
(model.py:41-100) makes the *first* `train_one_batch` call trace all ops
into the C++ `Graph`, then replays `dev.RunGraph(sequential)` every
iteration; `compile()` (:156-184) runs a dummy forward to shape-infer and
init params; `save_states/load_states` use zip(npz + json) (:244-354).

TPU-native redesign: "trace once, replay" IS `jax.jit`: the first call
builds a functional step (model states + optimizer states threaded through,
buffers donated so params update in place), compiles it with XLA, and every
later call replays the executable with zero Python op dispatch. Distributed
training shard_maps the same step over a mesh so DistOpt's `lax.psum` calls
bind to the data axis — the XLA analog of submitting NCCL ops as graph
nodes (communicator.cc:175-186). A batch output of that step comes back as
one global array sharded over the data axis, each device holding the rows it
computed; the step gathers nothing, a read (`Tensor.numpy`) does.
"""

from __future__ import annotations

import contextlib
import io
import json
import os
import time
import zipfile

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from . import autograd
from . import goodput
from . import health
from . import introspect
from . import memory
from . import observe
from . import watchdog
from .layer import Layer, LayerMeta
from .tensor import Tensor


def _input_avals(arrs):
    return tuple((tuple(a.shape), str(a.dtype)) for a in arrs)


def _flatten_out(out):
    """Flatten nested tuples/lists/dicts of Tensors -> (leaves, rebuild)."""
    leaves = []

    def build_template(o):
        if isinstance(o, Tensor):
            leaves.append(o)
            return ("T", len(leaves) - 1)
        if isinstance(o, (tuple, list)):
            return ("L", type(o).__name__, [build_template(v) for v in o])
        if isinstance(o, dict):
            return ("D", {k: build_template(v) for k, v in o.items()})
        return ("C", o)

    template = build_template(out)
    return leaves, template


def _rebuild_out(template, tensors):
    kind = template[0]
    if kind == "T":
        return tensors[template[1]]
    if kind == "L":
        seq = [_rebuild_out(t, tensors) for t in template[2]]
        return tuple(seq) if template[1] == "tuple" else seq
    if kind == "D":
        return {k: _rebuild_out(v, tensors) for k, v in template[1].items()}
    return template[1]


class ModelMeta(LayerMeta):
    def __new__(mcs, name, bases, attrs):
        if "train_one_batch" in attrs:
            attrs["train_one_batch"] = ModelMeta.buffer_operation(
                attrs["train_one_batch"])
        return super().__new__(mcs, name, bases, attrs)

    @staticmethod
    def buffer_operation(func):
        """First call in graph mode builds + compiles the step; replays
        after (mirrors model.py:57-93)."""

        def wrapper(self, *args, **kwargs):
            if self._device is None:
                raise RuntimeError(
                    "call Model.compile([inputs], ...) before training — "
                    "params are shape-inferred from the compile inputs "
                    "(ref model.py:156)")
            if not (self.graph_mode and self.training):
                if getattr(self, "_health_monitor", None) is not None \
                        and self.training:
                    return self._eager_health_step(func, args, kwargs)
                return func(self, *args, **kwargs)
            if self._compiled_step is None:
                self._build_step(func, args, kwargs)
            return self._invoke_step(args)

        wrapper.__wrapped__ = func
        return wrapper


class Model(Layer, metaclass=ModelMeta):
    """Base user model: subclass, define `forward` and (optionally)
    `train_one_batch` (ref model.py:103)."""

    def __init__(self, name=None):
        super().__init__(name)
        self.training = True
        self.graph_mode = True
        self.sequential = False
        self._optimizer = None
        self._device = None
        self._compiled_step = None   # step tag -> introspect.AotExecutor
        self._compiled_eval = None   # one AotExecutor around the forward
        self._step_stats = {"compile_s": 0.0, "steps": 0}
        self._health_monitor = None
        self._health_steps = 0

    # ---- configuration (ref model.py:185-243) ----------------------------
    def set_optimizer(self, opt):
        self._optimizer = opt

    def set_health_monitor(self, monitor):
        """Attach (or detach, with None) a health.HealthMonitor. The
        monitor's policy is STATIC in the compiled step (skip_step bakes
        an in-graph conditional commit into the executable), so any
        already-compiled step is dropped and rebuilt on the next call."""
        prev = self._health_monitor
        self._health_monitor = monitor
        self._compiled_step = None
        if monitor is not None:
            health.set_active_monitor(monitor)  # /healthz finds it here
        elif prev is not None and health.active_monitor() is prev:
            # detaching clears the process registration only when it is
            # ours — another model's live monitor keeps serving /healthz
            health.set_active_monitor(None)
        return monitor

    @property
    def optimizer(self):
        return self._optimizer

    def graph(self, mode=True, sequential=False):
        """Turn graph (jit) execution on/off after compile
        (ref model.py:224). `sequential=True` is the serial debug mode
        (jax.disable_jit), mirroring the reference's RunInSerial."""
        if mode == self.graph_mode and sequential == self.sequential:
            return  # idempotent: keep the compiled executables
        self.graph_mode = mode
        self.sequential = sequential
        if isinstance(self._compiled_step, dict):
            self._compiled_step = {}   # drop stale-flag executables
        self._compiled_eval = None

    def compile(self, inputs, is_train=True, use_graph=False,
                sequential=False, pipeline_axis=None, n_micro=1,
                pipeline_schedule="gpipe", amp=None,
                eval_buckets="auto", health=None):
        """Dummy forward with concrete inputs to init all params
        (ref model.py:156-184).

        pipeline_axis/n_micro: mesh axis + microbatch count for pipeline
        execution; consumed by pipeline-capable models (e.g.
        models.transformer.PipelinedGPT) at param-init time.
        pipeline_schedule: "gpipe" (autodiff through the forward scan; all
        microbatch residuals live until backward) or "1f1b" (fused
        fwd+bwd interleave with in-schedule loss; in-flight activations
        bounded by ~2*stages, stage vjp rematerialized).

        amp: compute dtype for mixed-precision training ("bfloat16"):
        fp32 master weights with differentiable casts at matmul/conv
        boundaries; normalizations and losses stay fp32.

        eval_buckets: pad varying eval batch sizes to power-of-two buckets
        (O(log B) compiled variants instead of a retrace per size). Only
        valid when forward's outputs are all per-sample — a forward that
        reduces over the batch dim would average in the padding. Default
        "auto": the first eval call detects whether every output is
        per-sample (leading dim == batch) and enables bucketing for later
        batch sizes only if so; True forces it (loud error on
        non-per-sample outputs), False disables it."""
        assert len(inputs) > 0 and isinstance(inputs[0], Tensor)
        self._device = inputs[0].device
        self.graph_mode = use_graph
        self.sequential = sequential
        assert pipeline_schedule in ("gpipe", "1f1b"), pipeline_schedule
        self.pipeline_axis = pipeline_axis
        self.n_micro = n_micro
        self.pipeline_schedule = pipeline_schedule
        if amp in ("bf16", True):
            amp = "bfloat16"
        self.amp = amp
        self.eval_buckets = eval_buckets
        if health is not None:
            # a health.HealthMonitor instance; True means "default
            # monitor, warn policy", False detaches. Routed through
            # set_health_monitor so re-compiling an already-trained
            # model drops the stale executables (the policy is baked
            # into the compiled step).
            from . import health as _health
            if health is False:
                self.set_health_monitor(None)
            elif health is True:
                self.set_health_monitor(_health.HealthMonitor())
            elif isinstance(health, _health.HealthMonitor):
                self.set_health_monitor(health)
            else:
                raise TypeError(
                    f"health= expects a health.HealthMonitor, True, "
                    f"False, or None; got {type(health).__name__}")
        prev = autograd.training
        autograd.training = False  # init pass builds no tape
        try:
            # every parameter made and initialised, eagerly: one small
            # program an operator, each compiled (or read from the cache)
            # under this span (introspect's singa_xla_compile_seconds)
            with observe.span("model.init"):
                self.forward(*inputs)
        finally:
            autograd.training = prev
        self.train(is_train)
        if self._optimizer is not None:
            with observe.span("opt.setup"):
                self._optimizer.setup(self.get_params().values())

    def train(self, mode: bool = True):
        self.training = mode
        autograd.training = mode

    def eval(self):
        self.train(False)

    # ---- default hooks ---------------------------------------------------
    def forward(self, *args, **kwargs):
        raise NotImplementedError

    def train_one_batch(self, *args, **kwargs):
        raise NotImplementedError

    def __call__(self, *args, **kwargs):
        prev_cd = autograd.compute_dtype
        if getattr(self, "amp", None) is not None:
            autograd.compute_dtype = self.amp  # eager path; jitted steps
        try:                                   # set it at trace time too
            if self.training:
                return self.train_one_batch(*args, **kwargs)
            if self.graph_mode and self._device is not None and not kwargs \
                    and all(isinstance(a, Tensor) for a in args):
                # span -> the goodput `eval` bucket (a first-call AOT
                # build nests an introspect.build span, netted out)
                with observe.span("model.eval"):
                    return self._eval_step(args)
            return self.forward(*args, **kwargs)
        finally:
            autograd.compute_dtype = prev_cd

    # ---- the jitted step -------------------------------------------------
    def _build_step(self, func, example_args, kwargs):
        # span -> the goodput `compile` bucket (trace prep; the XLA
        # backend build itself lands under introspect.build)
        with observe.span("model.build"):
            self._build_step_impl(func, example_args, kwargs)

    def _build_step_impl(self, func, example_args, kwargs):
        from .opt import DistOpt  # local import to avoid cycle

        t0 = time.perf_counter()
        opt = self._optimizer
        if opt is not None:
            with observe.span("opt.setup"):
                opt.setup(self.get_params().values())
        # memory-ledger birth-site hook: params (re-read per snapshot —
        # donation replaces the buffers every step) and the retained
        # step inputs the flight recorder would snapshot
        memory.track_model(self)
        # shard_map whenever a multi-device mesh is attached — the data
        # axis may be size 1 when the mesh is carved for tp/pp only
        dist = (isinstance(opt, DistOpt)
                and opt.communicator.mesh is not None
                and opt.communicator.mesh.size > 1)
        if dist:
            # Expert-parallel layers REQUIRE the gradient reduction to
            # cover their ep axis (tuple DistOpt axis): reducing over data
            # alone leaves each ep rank's replicated expert tables updated
            # from only its own slice grads — silent divergence, so refuse.
            mesh_axes = set(opt.communicator.mesh.shape.keys())
            red_axes = set(opt.axis if isinstance(opt.axis, tuple)
                           else (opt.axis,))
            stack = [self]
            while stack:
                lyr = stack.pop()
                stack.extend(getattr(lyr, "_layers", {}).values())
                ep = getattr(lyr, "ep_axis", None)
                if (ep is not None and hasattr(lyr, "num_experts")
                        and ep in mesh_axes and ep not in red_axes):
                    raise ValueError(
                        f"MoE layer routes experts over mesh axis '{ep}' "
                        f"but DistOpt reduces only over {sorted(red_axes)}"
                        f"; expert gradients would diverge across '{ep}'. "
                        f"Use DistOpt(axis={tuple(sorted(red_axes) + [ep])}"
                        f", mesh=mesh)")

        states = self.get_states()
        state_tensors = list(states.values())
        param_ids = {id(t) for t in self.get_params().values()}
        aux_idx = [i for i, t in enumerate(state_tensors)
                   if id(t) not in param_ids]
        dev = self._device
        monitor = self._health_monitor
        health_on = monitor is not None
        group_of = self._health_groups() if health_on else None
        # skip_step bakes an in-graph conditional commit into the step:
        # params/opt state select their PRE-step values when the agreed
        # nonfinite flag fires (donation is input->output aliasing, so
        # the old buffers are legal select operands)
        skip_in_graph = health_on and monitor.policy == "skip_step"

        tensor_pos = [i for i, a in enumerate(example_args)
                      if isinstance(a, Tensor)]
        static_args = {i: a for i, a in enumerate(example_args)
                       if not isinstance(a, Tensor)}
        self._tensor_pos = tensor_pos
        self._static_args = static_args
        # dispatch fast path: the per-step static-arg guard re-checks
        # values against these without rebuilding/comparing dicts
        self._n_call_args = len(example_args)
        self._static_items = tuple(sorted(static_args.items()))
        static_repr = repr(sorted(
            (i, repr(v)) for i, v in static_args.items()))
        out_template_box = {}

        def make_step(tag):
            """Build + jit the step for one static step-tag. Tag 0 is the
            only tag for ordinary optimizers; DistOpt's partial-update
            strategy rotates tags so each compiled variant contains ONLY
            its parameter partition's collectives (true bandwidth rotation,
            unlike a runtime mask — resolves the opt.py partial NOTE)."""

            def step(state_arrs, opt_arrs, rng, input_arrs):
                if opt is not None:
                    opt._partial_static_idx = tag
                # Device scopes of the step outside its layers: `rng`
                # here, `opt` (opt.py), `health` (health.py), `amp_cast`
                # (autograd.ComputeCast)
                if dist:
                    # flattened rank (communicator handles tuple axes for
                    # multi-axis reductions like DP+EP)
                    with jax.named_scope("rng"):
                        dev.rng_state = jax.random.fold_in(
                            rng, opt.communicator.rank())
                else:
                    dev.rng_state = rng
                for t, a in zip(state_tensors, state_arrs):
                    t.data = a
                if opt is not None and opt_arrs:
                    opt.load_state_arrays(opt_arrs)
                call_args = []
                j = 0
                for i in range(len(example_args)):
                    if i in static_args:
                        call_args.append(static_args[i])
                    else:
                        call_args.append(Tensor(data=input_arrs[j],
                                                device=dev,
                                                requires_grad=False))
                        j += 1
                autograd.training = True
                prev_cd = autograd.compute_dtype
                autograd.compute_dtype = getattr(self, "amp", None)
                col = None
                if health_on:
                    col = health.StepStatsCollector(group_of)
                    health._set_collector(col)
                try:
                    out = func(self, *call_args, **kwargs)
                finally:
                    if health_on:
                        health._set_collector(None)
                    autograd.compute_dtype = prev_cd
                    if opt is not None:
                        # trace-time tag must not leak into later EAGER
                        # partial updates (they rotate via a host counter)
                        opt._partial_static_idx = None
                out_leaves, template = _flatten_out(out)
                out_template_box["t"] = template
                outs = [o.data for o in out_leaves]
                if dist:
                    # scalars (loss): averaged across shards, replicated.
                    # Batched outputs stay where they were made: each
                    # leaves as this shard's rows of one global array
                    # (out_specs P(opt.axis)), so the step runs no gather;
                    # a read (Tensor.numpy) assembles the global batch.
                    # out_specs is fixed before the trace, so the two
                    # kinds go back as two lists and the mask restores
                    # the leaves' order (_invoke_step).
                    sharded = [o.ndim > 0 for o in outs]
                    out_template_box["sharded"] = sharded
                    outs = ([lax.pmean(o, opt.axis)
                             for o, s in zip(outs, sharded) if not s],
                            [o for o, s in zip(outs, sharded) if s])
                    observe.record_step_outputs(
                        batch_sharded=len(outs[1]),
                        mean_reduced=len(outs[0]))
                new_states = [t.data for t in state_tensors]
                if dist:
                    # non-param states (BN running stats) differ per shard:
                    # average them (syncBN-style) so the replicated
                    # out-spec holds
                    for i in aux_idx:
                        new_states[i] = lax.pmean(new_states[i], opt.axis)
                new_opt = opt.state_arrays() if opt is not None else []
                hstats = {}
                if health_on:
                    hstats = col.finalize(
                        comm=opt.communicator if dist else None)
                    if skip_in_graph:
                        # conditional commit: the whole update — params,
                        # aux states, opt slots, the step counter — rolls
                        # back atomically on every shard (the flag is the
                        # agreed cross-host verdict)
                        new_states = health.apply_skip(
                            hstats, state_arrs, new_states)
                        new_opt = health.apply_skip(
                            hstats, opt_arrs, new_opt)
                with jax.named_scope("rng"):
                    new_rng = jax.random.split(rng, 1)[0] if dist \
                        else dev.rng_state
                return new_states, new_opt, new_rng, outs, hstats

            if dist:
                from jax.sharding import PartitionSpec as P
                mesh = opt.communicator.mesh
                wrapped = jax.shard_map(
                    step, mesh=mesh,
                    in_specs=(state_in, opt_in, P(), P(opt.axis)),
                    out_specs=(state_in, opt_in, P(),
                               (P(), P(opt.axis)), P()),
                    check_vma=False)
                # a step that reduces gradients over TPUs is compiled so
                # that the reductions travel under the backward pass; {}
                # on one device, off a TPU, and for every other step
                options = opt.communicator.overlap_compile_options()
            else:
                wrapped = step
                options = {}
            # The cache key is O(#inputs): a step's signature changes only
            # with its inputs, or with len(opt_arrs) — the sparse DistOpt
            # strategies GROW their optimizer state (new residual slots)
            # between steps.
            return introspect.AotExecutor(
                jax.jit(wrapped, donate_argnums=(0, 1),
                        compiler_options=options), "step",
                names=("state", "opt", "rng", "arg"), donated=(0, 1),
                tag=tag, static=static_repr, device=dev,
                cache_key=lambda a: (_input_avals(a[3]), len(a[1])),
                compiler_options=options)

        self._dist_shardings = None
        state_in = opt_in = None
        if dist:
            from jax.sharding import PartitionSpec as P, NamedSharding
            mesh = opt.communicator.mesh
            assert mesh is not None, \
                "DistOpt needs a mesh for multi-device training"

            def sanitize(spec):
                """Drop spec axes the mesh doesn't carry: a model built
                with tp_axis="tp" but trained on a {data, pp} mesh keeps
                those params REPLICATED (the layer forwards gate their
                collectives on axis_bound, so the math degrades to the
                serial path consistently)."""
                if spec is None:
                    return None
                axes = set(mesh.shape.keys())
                out = []
                for el in spec:
                    if el is None:
                        out.append(None)
                    elif isinstance(el, tuple):
                        kept = tuple(a for a in el if a in axes)
                        out.append(kept if kept else None)
                    else:
                        out.append(el if el in axes else None)
                if not any(e is not None for e in out):
                    return None
                return P(*out)

            # TP-sharded params (Tensor.spec set by tp_axis layers) enter
            # the shard_map partitioned; everything else is replicated. A
            # plain P() prefix is kept in the no-TP case so strategies with
            # dynamically growing optimizer state (sparse residuals) still
            # pytree-match.
            sanitized = [sanitize(getattr(t, "spec", None))
                         for t in state_tensors]
            state_specs = [s or P() for s in sanitized]
            has_tp = any(s is not None for s in sanitized)
            if has_tp:
                state_in = state_specs
                opt_in = [sanitize(s) or P() for s in opt.state_specs()]
                self._dist_shardings = (
                    NamedSharding(mesh, P()),
                    NamedSharding(mesh, P(opt.axis)),
                    [NamedSharding(mesh, s) for s in state_specs],
                    [NamedSharding(mesh, s) for s in opt_in],
                )
            else:
                state_in = opt_in = P()
                self._dist_shardings = (NamedSharding(mesh, P()),
                                        NamedSharding(mesh, P(opt.axis)),
                                        None, None)
        self._state_tensors = state_tensors
        self._out_template_box = out_template_box
        self._step_builder = make_step
        # step tag -> the executor that stages, caches and dispatches
        # that tag's jitted step, one variant an abstract signature
        self._compiled_step = {}
        self._step_stats["compile_s"] = time.perf_counter() - t0

    def _static_mismatch(self, args):
        """Rebuild the full dict comparison only to phrase the error —
        the per-step guard already proved a mismatch (or a change in
        which positions carry Tensors)."""
        cur_static = {i: a for i, a in enumerate(args)
                      if not isinstance(a, Tensor)}
        raise ValueError(
            f"graph mode compiled with static args {self._static_args}, "
            f"got {cur_static}; non-Tensor arguments cannot change "
            "between calls (recompile by resetting the model, or run "
            "with use_graph=False)")

    def _invoke_step(self, args):
        opt = self._optimizer
        dev = self._device
        # non-Tensor args (dist_option, spars, ...) are baked into the
        # compiled step at trace time; changing them later must not be
        # silently ignored. Positions were fixed at build time, so the
        # hot path re-checks values in place instead of building and
        # comparing a fresh dict every step.
        if len(args) != self._n_call_args:
            self._static_mismatch(args)
        for i, v in self._static_items:
            a = args[i]
            if isinstance(a, Tensor) or a != v:
                self._static_mismatch(args)
        for i in self._tensor_pos:
            if not isinstance(args[i], Tensor):
                self._static_mismatch(args)
        state_arrs = [t.data for t in self._state_tensors]
        opt_arrs = opt.state_arrays() if opt is not None else []
        input_arrs = [args[i].data for i in self._tensor_pos]
        self._last_input_arrs = input_arrs
        rng = dev.rng_state
        if self._dist_shardings is not None:
            # replicate (or TP-shard) states over the mesh, shard the batch
            # on the data axis (a no-op after step 1: outputs already carry
            # these shardings, so only fresh host batches actually move)
            rep, shard, state_sh, opt_sh = self._dist_shardings

            def put(a, sh):
                if getattr(a, "sharding", None) == sh:
                    return a
                if isinstance(a, jax.Array) and not a.is_fully_addressable:
                    # already a global array (a previous step's output);
                    # re-putting is impossible and unnecessary
                    return a
                if jax.process_count() > 1:
                    # multi-host: device_put cannot scatter across hosts.
                    # Every process holds the FULL host value (params init
                    # from a shared seed, batches fed as global arrays), so
                    # each builds its addressable shards by indexing into
                    # it — correct for replicated AND partitioned specs.
                    if jnp.issubdtype(getattr(a, "dtype", None),
                                      jax.dtypes.prng_key):
                        # typed keys can't pass np.asarray; ship the raw
                        # key data (rng shardings are replicated, so the
                        # spec is rank-agnostic)
                        kd = np.asarray(jax.random.key_data(a))
                        g = jax.make_array_from_callback(
                            kd.shape, sh, lambda idx: kd[idx])
                        return jax.random.wrap_key_data(g)
                    host = np.asarray(a)
                    return jax.make_array_from_callback(
                        host.shape, sh, lambda idx: host[idx])
                return jax.device_put(a, sh)

            if state_sh is None:
                state_arrs = [put(a, rep) for a in state_arrs]
                opt_arrs = [put(a, rep) for a in opt_arrs]
            else:
                state_arrs = [put(a, s)
                              for a, s in zip(state_arrs, state_sh)]
                opt_arrs = [put(a, s)
                            for a, s in zip(opt_arrs, opt_sh)]
            rng = put(rng, rep)
            input_arrs = [put(a, shard) for a in input_arrs]
        tag = opt.step_tag() if opt is not None else 0
        ex = self._compiled_step.get(tag)
        if ex is None:
            ex = self._compiled_step[tag] = self._step_builder(tag)
        obs = observe.is_enabled()
        bs = None
        if input_arrs and getattr(input_arrs[0], "ndim", 0):
            bs = input_arrs[0].shape[0]
        call = (state_arrs, opt_arrs, rng, input_arrs)
        # The explicit trace -> lower -> compile staging of a new (tag,
        # signature) happens here, before the step span opens, so compile
        # time lands at build/retrace time and never in a step's. A repeat
        # step pays one O(#inputs) key and one dict lookup. sequential is
        # RunGraph(sequential=true) parity (ref device.cc / SURVEY §2.1):
        # ops run one by one, eagerly, for op-level python breakpoints and
        # immediate error locations — nothing is staged or cached.
        variant = None if self.sequential else self._staged(
            ex, call, bs, self._state_tensors, self._out_template_box)
        if obs:
            if variant is not None and variant.fresh:
                # jit retraces exactly when the executor builds: a compile
                # (the first ever) or a recompile (new batch-size class /
                # step tag)
                observe.record_compile(
                    bs, recompile=sum(
                        map(len, self._compiled_step.values())) > 1,
                    donated_bytes=sum(
                        int(getattr(a, "nbytes", 0))
                        for a in (*state_arrs, *opt_arrs)))
                if self._dist_shardings is not None \
                        and variant.run is not None:
                    # what the compiler made of the step's reductions
                    observe.record_grad_reduce(
                        introspect.all_reduces_of(variant))
            t_obs = time.perf_counter()
        profiling = (dev.verbosity > 0 and
                     self._step_stats["steps"] >= dev.skip_iteration)
        if profiling:
            if dev.cost_analysis is None and dev.verbosity >= 2:
                dev.cost_analysis = self.step_cost_analysis() \
                    if self._step_stats["steps"] > 0 else {}
            t0 = time.perf_counter()
        # span -> the goodput `step` bucket (held pending until the
        # health verdict below, so a discarded update reclassifies to
        # `health_skip`); covers dispatch and, when profiling, the fence.
        # The watchdog guard arms the `step` deadline over the same
        # region (nested no-op when a TrainController's outer guard is
        # already armed); a cold jit fallback's span (the executor's)
        # taints the entry, so first-compile time neither breaches nor
        # calibrates. tag attr: the regress detector baselines each
        # optimizer-tag variant separately (different tags dispatch
        # different executables with different per-step costs)
        with watchdog.guard("step"), observe.span("model.step", tag=tag):
            if variant is None:
                with jax.disable_jit():
                    new_states, new_opt, new_rng, outs, hstats = \
                        ex.fn(*call)
            else:
                new_states, new_opt, new_rng, outs, hstats = \
                    ex.dispatch(variant, call)
            # the MFU gauge must use the DISPATCHED variant's flops, not
            # the most recently built one (a partial-batch build would
            # otherwise skew later full-batch readings); 0 when jit owns
            # the signature (or nothing is compiled) disables the gauge
            introspect.note_step_flops(variant.flops if variant else 0)
            if profiling:
                jax.block_until_ready(new_states)
                fenced = time.perf_counter() - t0
                dev.step_times.append(fenced)
                observe.record_step_fenced(fenced)
            if self._health_monitor is not None and hstats:
                # fetch the stats INSIDE the span: on an async backend
                # this is the step's sync point, so the span records the
                # device step's real wall time (not just dispatch) —
                # without a monitor or profiling, only dispatch time is
                # attributable and the remainder lands in `other`
                hstats = jax.device_get(hstats)
        for t, a in zip(self._state_tensors, new_states):
            t.data = a
        if opt is not None and new_opt:
            opt.load_state_arrays(new_opt)
        if self._dist_shardings is not None and (
                not isinstance(new_rng, jax.Array)
                or new_rng.is_fully_addressable):
            # un-replicate the key so later eager/single-device work (fresh
            # param init, eval) doesn't inherit a mesh sharding. (On a
            # multi-host mesh the key is not addressable here; it stays
            # global and step feeds consume it in place.)
            new_rng = jax.device_put(new_rng, dev.jax_device)
        dev.rng_state = new_rng
        self._step_stats["steps"] += 1
        if obs:
            observe.record_step(time.perf_counter() - t_obs,
                                batch=bs, tag=tag, device=dev)
        if self._health_monitor is not None:
            # stats were fetched (and the step thereby fenced) inside
            # the model.step span above; this feed is host-side only
            action = self._health_feed(hstats, self._last_input_arrs,
                                       in_graph_skip=True, fetched=True)
            if action == "skip":
                # the update was discarded in-graph: this step's wall
                # time produced nothing — move it out of `step`
                goodput.mark_step_skipped()
        sharded = self._out_template_box.get("sharded")
        if sharded is not None:
            # the data-parallel step's two lists, back in the leaves' order
            reduced, batch = map(iter, outs)
            outs = [next(batch if s else reduced) for s in sharded]
        tensors = [Tensor(data=a, device=dev, requires_grad=False)
                   for a in outs]
        return _rebuild_out(self._out_template_box["t"], tensors)

    @contextlib.contextmanager
    def _tracers_kept_out(self, tensors):
        """Tracing the step or the eval forward assigns tracers into the
        state Tensors, the optimizer's arrays and dev.rng_state, and sets
        autograd.training: snapshot them, yield the snapshots (state
        arrays, optimizer arrays, rng key), and put them back so no tracer
        escapes into later eager work."""
        opt = self._optimizer
        dev = self._device
        state = [t.data for t in tensors]
        opt_arrs = list(opt.state_arrays()) if opt is not None else []
        rng = dev.rng_state
        training = autograd.training
        try:
            yield state, opt_arrs, rng
        finally:
            autograd.training = training
            dev.rng_state = rng
            for t, a in zip(tensors, state):
                t.data = a
            if opt is not None and opt_arrs:
                opt.load_state_arrays(opt_arrs)

    def _staged(self, ex, call, batch_hint, tensors, template_box):
        """The executor's variant for `call`, built on first sight. After
        a warm-store hit the executable came back deserialized, so the
        python function was never traced and the out-template side
        channel is empty: one abstract trace (no lower/compile) recovers
        it. If even that fails, plain jit owns the signature — its first
        dispatch traces the function and fills the template."""
        variant = ex.prepare(*call, batch_hint=batch_hint)
        if variant.fresh and variant.run is not None \
                and "t" not in template_box:
            try:
                with self._tracers_kept_out(tensors):
                    jax.eval_shape(ex.fn, *call)
            except Exception:
                ex.give_to_jit(variant)
        return variant

    # ---- training health (singa_tpu.health) ------------------------------
    def _health_groups(self):
        """{id(param): layer group} — the first path component of the
        param's get_params() name ("l1.W" -> "l1"), the granularity the
        per-group norm/ratio stats aggregate at."""
        return {id(t): name.split(self.sep, 1)[0]
                for name, t in self.get_params().items()}

    def _health_feed(self, hstats, input_arrs, in_graph_skip,
                     fetched=False):
        mon = self._health_monitor
        self._health_steps += 1
        # _invoke_step fetches the stats inside the model.step span (the
        # fetch IS the step fence); don't traverse the tree a second time
        host = hstats if fetched else (
            jax.device_get(hstats) if hstats else {})
        host = host or {}
        provider = None
        if input_arrs is not None and mon.snapshot_batch:
            provider = lambda: [np.asarray(jax.device_get(a))  # noqa: E731
                                for a in input_arrs]
        return mon.on_step(host, step=self._health_steps,
                           batch_provider=provider,
                           amp=getattr(self, "amp", None) is not None,
                           in_graph_skip=in_graph_skip)

    def _eager_health_step(self, func, args, kwargs):
        """Eager-mode health: the same collector, finalized eagerly.
        skip_step's rollback is part of the compiled step, so eager
        anomalies get warn/halt semantics only (in_graph_skip=False).
        Single-process scope: finalize runs with no communicator —
        eager mode cannot execute mesh collectives anyway (psum outside
        a shard_mapped step has no bound axis), so eager + DistOpt at
        world_size > 1 is out of scope here as it is for training."""
        col = health.StepStatsCollector(self._health_groups())
        health._set_collector(col)
        try:
            out = func(self, *args, **kwargs)
        finally:
            health._set_collector(None)
        self._health_feed(col.finalize(),
                          [a.data for a in args if isinstance(a, Tensor)],
                          in_graph_skip=False)
        return out

    # ---- minimal training loop -------------------------------------------
    def fit(self, data, epochs=1, verbose=0, prefetch_to_device=0):
        """Host-side training loop over `data`, an iterable of per-batch
        argument tuples for `train_one_batch` (re-iterated each epoch, so
        pass a list/dataset, not a one-shot generator). Returns the list
        of per-epoch mean losses (by convention the second element of the
        step's return, or the whole return when it is a single Tensor).

        prefetch_to_device=N wraps each epoch's iterator in an
        overlap.DevicePrefetcher: a background thread moves up to N
        batches to the device (with the model's input sharding) ahead of
        consumption, so host batch assembly and host->device transfer
        overlap the previous step's execution instead of serializing
        into the goodput `data_wait` bucket. The prefetcher is closed on
        every exit path — normal end of epoch, an early break, or a
        HealthError raised out of the loop.

        This is where the health layer meets the loop: every step feeds
        the attached HealthMonitor (skip_step discards bad updates
        in-graph without breaking the loop; halt raises HealthError out
        of fit with the flight-recorder bundle already on disk AND the
        epoch's partial progress attached as `HealthError.partial` —
        {"epoch", "steps_completed", "losses", "last_loss"} — so a
        supervising controller can log/checkpoint what the epoch did
        achieve instead of losing it with the raise)."""
        history = []
        _end = object()
        for epoch in range(epochs):
            losses = []
            with observe.span("model.fit_epoch", epoch=epoch):
                it = iter(data)
                prefetcher = None
                if prefetch_to_device:
                    from . import overlap
                    prefetcher = overlap.DevicePrefetcher(
                        it, model=self, size=int(prefetch_to_device))
                    it = prefetcher
                try:
                    while True:
                        # fetch wait measured per batch: the host-side
                        # pipeline stall signal (goodput `data_wait`; an
                        # iterator's own data.wait span nests, nets
                        # out). The watchdog arms the `data_wait`
                        # deadline over the same wait; `data.next` is
                        # its deterministic FaultPlan hook.
                        with observe.span("data.wait"), \
                                watchdog.guard("data_wait"):
                            from . import resilience
                            resilience.fault_point("data.next")
                            batch = next(it, _end)
                        if batch is _end:
                            break
                        if not isinstance(batch, (tuple, list)):
                            batch = (batch,)
                        out = self(*batch)
                        loss = out[1] if isinstance(out, (tuple, list)) \
                            and len(out) > 1 else out
                        if isinstance(loss, Tensor):
                            # keep the device scalar; fetch once per
                            # epoch so the loop stays async-dispatched
                            losses.append(loss.data)
                except health.HealthError as e:
                    # a mid-epoch halt must not discard the epoch's loss
                    # history: surface the partial progress on the error
                    # (one transfer, same as the happy path below)
                    vals = [float(np.asarray(a))
                            for a in jax.device_get(losses)]
                    e.partial = {
                        "epoch": epoch,
                        "steps_completed": len(vals),
                        "losses": vals,
                        "last_loss": vals[-1] if vals else None,
                    }
                    raise
                finally:
                    if prefetcher is not None:
                        prefetcher.close()
            if not losses:
                raise ValueError(
                    f"fit epoch {epoch} saw no batches - `data` must be "
                    "re-iterable across epochs (a list, not a generator)")
            # ONE transfer for the whole epoch (was one device_get per
            # element — a host<->device round-trip per step)
            vals = [float(np.asarray(a)) for a in jax.device_get(losses)]
            mean = sum(vals) / len(vals)
            history.append(mean)
            if verbose:
                print(f"epoch {epoch}: loss {mean:.6f} "
                      f"({len(vals)} steps)")
        return history

    def lower_step(self, tag=0):
        """Re-lower a compiled step variant for inspection (HLO text, cost
        analysis). Lowering re-traces the step, which assigns tracers into
        dev.rng_state and the state Tensors as a side effect — snapshot and
        restore them so no tracer escapes into later eager work."""
        if not self._compiled_step or \
                getattr(self, "_last_input_arrs", None) is None:
            return None
        ex = self._compiled_step.get(tag)
        if ex is None:
            return None
        # lower from the PRE-replication snapshots and restore those:
        # leaving mesh-committed arrays in globally shared state would
        # poison later single-device work
        with self._tracers_kept_out(self._state_tensors) as (
                state_arrs, opt_arrs, rng):
            if self._dist_shardings is not None:
                rep, _, state_sh, opt_sh = self._dist_shardings
                state_arrs = [jax.device_put(a, s) for a, s in
                              zip(state_arrs, state_sh)] if state_sh else \
                    [jax.device_put(a, rep) for a in state_arrs]
                opt_arrs = [jax.device_put(a, s) for a, s in
                            zip(opt_arrs, opt_sh)] if opt_sh else \
                    [jax.device_put(a, rep) for a in opt_arrs]
                rng = jax.device_put(rng, rep)
            return ex.fn.lower(state_arrs, opt_arrs, rng,
                               self._last_input_arrs)

    def step_cost_analysis(self):
        """XLA cost analysis of the compiled training step (flops, bytes
        accessed, ...) — the TPU analog of the reference's per-node
        profiling tables (scheduler.cc:240-295). Requires at least one
        graph-mode train call. Returns {} if unavailable."""
        try:
            lowered = self.lower_step()
            if lowered is None:
                return {}
            ca = lowered.compile().cost_analysis()
            return ca[0] if isinstance(ca, list) else (ca or {})
        except Exception:
            return {}

    # ---- jitted inference (graph mode for eval; the reference replays its
    # buffered graph for eval too, model.py:94-100) ------------------------
    def _eval_step(self, args):
        if self._compiled_eval is None:
            states = self.get_states()
            eval_tensors = list(states.values())
            template_box = {}

            def efwd(state_arrs, input_arrs):
                # host-side trace counter: jit re-runs this body only on a
                # retrace, so tests can assert bucketing avoids retraces
                self._eval_trace_count = \
                    getattr(self, "_eval_trace_count", 0) + 1
                for t, a in zip(eval_tensors, state_arrs):
                    t.data = a
                prev = autograd.training
                prev_cd = autograd.compute_dtype
                autograd.training = False
                autograd.compute_dtype = getattr(self, "amp", None)
                try:
                    out = self.forward(*[Tensor(data=a, device=self._device,
                                                requires_grad=False)
                                         for a in input_arrs])
                finally:
                    autograd.training = prev
                    autograd.compute_dtype = prev_cd
                leaves, template = _flatten_out(out)
                template_box["t"] = template
                return [o.data for o in leaves]

            self._eval_tensors = eval_tensors
            self._eval_template_box = template_box
            # one executable an abstract input signature (compile-phase
            # timing + recompile blame), keyed on the inputs alone
            self._compiled_eval = introspect.AotExecutor(
                jax.jit(efwd), "eval", names=("state", "arg"),
                cache_key=lambda a: _input_avals(a[1]))
        ex = self._compiled_eval

        def run(concrete, arrs, nb):
            """`nb` is the PRE-padding batch, so a bucket crossing blames
            the true sizes."""
            if self.sequential:
                # serial debug mode applies to inference too (RunInSerial)
                with jax.disable_jit():
                    return ex.fn(concrete, arrs)
            call = (concrete, arrs)
            return ex.dispatch(
                self._staged(ex, call, nb, self._eval_tensors,
                             self._eval_template_box), call)

        # batch-shape bucketing: pad the batch dim up to the next power of
        # two so varying eval sizes (e.g. the last partial batch) reuse
        # O(log B) compiled variants instead of retracing per size. Only
        # sound when every output is per-sample (leading dim == batch); a
        # forward that reduces over the batch would see the zero padding —
        # so the default "auto" mode probes the first (unbucketed) call's
        # output shapes and enables bucketing only when they are all
        # per-sample; compile(eval_buckets=True) forces it.
        arrs = [a.data for a in args]
        nb = arrs[0].shape[0] if arrs and arrs[0].ndim > 0 else None
        mode = getattr(self, "eval_buckets", "auto")
        enabled = (mode is True or
                   (mode == "auto"
                    and getattr(self, "_eval_per_sample", None) is True))
        bucket = None
        if enabled and nb is not None \
                and nb > 0 and all(
                a.ndim > 0 and a.shape[0] == nb for a in arrs):
            bucket = 1
            while bucket < nb:
                bucket *= 2
            if bucket != nb:
                arrs = [jnp.concatenate(
                    [a, jnp.zeros((bucket - nb,) + a.shape[1:], a.dtype)])
                    for a in arrs]
            else:
                bucket = None
        # tracing assigns tracers into the state Tensors; the guard puts
        # the real arrays back so later eager/train calls see concrete
        # buffers
        with self._tracers_kept_out(self._eval_tensors) as (concrete, _, _):
            outs = run(concrete, arrs, nb)
            if bucket is not None:
                # the eval_buckets contract is "every output is
                # per-sample"; enforce it loudly (ValueError, not assert:
                # -O must not turn this back into silent truncation of a
                # fixed-size output that merely matches the bucket)
                for o in outs:
                    if o.ndim == 0 or o.shape[0] != bucket:
                        raise ValueError(
                            f"eval_buckets requires per-sample outputs; "
                            f"got shape {o.shape} with batch bucket "
                            f"{bucket} (compile with eval_buckets=False "
                            f"to retrace per shape instead)")
                outs = [o[:nb] for o in outs]
            elif mode == "auto" and nb is not None and \
                    getattr(self, "_eval_per_sample", None) is not False \
                    and nb not in getattr(self, "_eval_probed_nbs", ()):
                # auto-detect on unbucketed calls. Shape alone is not
                # proof — a batch-coupled output (softmax over axis 0) is
                # batch-shaped too — so PROBE semantics: re-run on the
                # first half of the batch and require out(x[:h]) ==
                # out(x)[:h]. The probe re-runs once per NEW batch-size
                # class (a coupling that was numerically invisible at one
                # size may not be at another), and a failed re-probe
                # permanently disables bucketing rather than silently
                # zero-padding a coupled model.
                shaped = all(o.ndim > 0 and o.shape[0] == nb for o in outs)
                ok = False
                if shaped and nb > 1:
                    h = nb // 2
                    try:
                        houts = run(concrete, [a[:h] for a in arrs], h)
                        ok = all(
                            np.allclose(np.asarray(jax.device_get(ho)),
                                        np.asarray(jax.device_get(o))[:h],
                                        rtol=1e-5, atol=1e-6)
                            for ho, o in zip(houts, outs))
                    except Exception:
                        ok = False
                if not hasattr(self, "_eval_probed_nbs"):
                    self._eval_probed_nbs = set()
                self._eval_probed_nbs.add(nb)
                self._eval_per_sample = shaped and ok
        tensors = [Tensor(data=a, device=self._device, requires_grad=False)
                   for a in outs]
        return _rebuild_out(self._eval_template_box["t"], tensors)

    # ---- checkpointing (ref model.py:244-354) ----------------------------
    def save_states(self, fpath: str, aux_states: dict | None = None):
        """zip(tensor_dict.npz + states_attr.json), same layout as the
        reference so checkpoints are inspectable with stdlib tools."""
        states = {k: t.numpy() for k, t in self.get_states().items()}
        if aux_states:
            for k, v in aux_states.items():
                states[f"aux.{k}"] = np.asarray(
                    v.numpy() if isinstance(v, Tensor) else v)
        attrs = {k: {"shape": list(v.shape), "dtype": str(v.dtype)}
                 for k, v in states.items()}
        # span -> the goodput `checkpoint` bucket, same as the orbax path
        with observe.span("checkpoint.save"):
            npz_buf = io.BytesIO()
            np.savez(npz_buf, **states)
            with zipfile.ZipFile(fpath, "w") as zf:
                zf.writestr("tensor_dict.npz", npz_buf.getvalue())
                zf.writestr("states_attr.json", json.dumps(attrs))
        observe.record_checkpoint_bytes(
            sum(int(v.nbytes) for v in states.values()))

    # ---- full training checkpoints (orbax) -------------------------------
    # save_states/load_states keep the reference's zip(npz+json) layout
    # for MODEL states; these save the full TRAINING state — params,
    # layer states, optimizer state, the device RNG — through orbax,
    # which writes sharded jax.Arrays per-shard (no host gather): the
    # pod-scale checkpoint path the zip format cannot be.
    def save_checkpoint(self, ckpt_dir: str, step: int = 0,
                        overwrite: bool = False, async_save: bool = True):
        """Write a resumable training checkpoint under `ckpt_dir/step_N`.
        Captures model states, optimizer state (slot buffers + step
        counter) and the device PRNG stream, so training resumed from it
        is bit-identical to uninterrupted training (tests/test_model.py::
        test_checkpoint_resume_equivalence). An existing COMPLETE step_N
        directory (one carrying a `step_N.manifest.json` sibling, the
        resilience layer's durability marker) raises unless
        `overwrite=True`; an existing step_N WITHOUT a manifest —
        usually an interrupted, half-written save — is reclaimed by
        default: renamed aside as `step_N.reclaimed` (data preserved,
        since a plain-API save never writes a manifest and may be a
        complete checkpoint) so a restarted job never wedges on its
        predecessor's debris.

        async_save=True (the default) routes the write through orbax's
        AsyncCheckpointer: the call returns once
        the device->host snapshot is taken and the serialize/write
        overlaps training. The bytes are durable only after
        `singa_tpu.overlap.wait_for_checkpoints()` — auto-invoked by the
        next save, by `load_checkpoint`, and at interpreter exit — which
        also re-raises any deferred write failure. Pass async_save=False
        for the blocking write."""
        import jax
        import orbax.checkpoint as ocp
        from . import overlap
        from .device import get_default_device
        # barrier on the previous async save: at most one write is in
        # flight, and its deferred error surfaces HERE, not never
        overlap.wait_for_checkpoints()
        dev = self._device or get_default_device()
        rng = dev.rng_state
        if jnp.issubdtype(getattr(rng, "dtype", None), jax.dtypes.prng_key):
            rng = jax.random.key_data(rng)
        # RAW arrays throughout (no np.asarray): optimizer slots of
        # sharded params are themselves sharded jax.Arrays and orbax
        # writes them per-shard — a host gather here would defeat the
        # point (and fail outright on non-addressable multi-host arrays)
        opt_tree = {}
        res_tree = {}
        if self._optimizer is not None:
            opt_tree = {f"s{i}": a for i, a in
                        enumerate(self._optimizer.state_arrays())}
            # sparse error-feedback residuals are per-DEVICE state under a
            # replicated spec: save every device's buffer, not device 0's
            get_stacks = getattr(self._optimizer,
                                 "residual_device_stacks", None)
            if get_stacks is not None:
                res_tree = {f"r{i}": v for i, v in get_stacks().items()}
        tree = {
            "model": {k: t.data for k, t in self.get_states().items()},
            "opt": opt_tree,
            "res": res_tree,
            "rng": rng,
        }
        path = os.path.join(os.path.abspath(ckpt_dir), f"step_{step}")
        if os.path.isdir(path):
            from . import resilience
            if not overwrite \
                    and not resilience.is_complete_checkpoint(path):
                # no manifest == not PROVEN complete: usually the
                # controller's crashed-writer debris, but possibly a
                # fine checkpoint written by this plain API (which
                # never writes manifests). Vacate the step_N name by
                # setting the old dir ASIDE (any manifest file rides
                # along) instead of destroying it — a restarted job
                # never wedges on its predecessor's leftovers, and
                # nothing durable is ever silently lost.
                resilience.set_aside_checkpoint(path, ".reclaimed")
            elif overwrite:
                # a stale manifest must not mark the in-flight rewrite
                # as complete (discovery keys on manifest presence)
                try:
                    os.remove(resilience.manifest_path(path))
                except OSError:
                    pass
        nbytes = sum(int(getattr(a, "nbytes", 0) or 0)
                     for a in jax.tree_util.tree_leaves(tree))
        if async_save:
            # blocking portion only (the snapshot) is spanned inside
            # start_async_save; the background write is the overlap
            overlap.start_async_save(path, tree, force=overwrite)
            observe.record_checkpoint_bytes(nbytes)
            return path
        ck = ocp.StandardCheckpointer()
        # span -> the goodput `checkpoint` bucket; the watchdog arms
        # the ckpt_save deadline over the blocking write
        with observe.span("checkpoint.save"), \
                watchdog.guard("ckpt_save"):
            ck.save(path, tree, force=overwrite)
            ck.wait_until_finished()
        # this blocking write is durable here: it supersedes any
        # recorded async-write failure for the same path
        overlap.clear_write_failed(path)
        observe.record_checkpoint_bytes(nbytes)
        return path

    def _restore_template(self, path):
        """Abstract restore targets carrying THIS process's current
        shardings, so orbax reads only the shards each host addresses —
        the multi-host restore path (every process calls load_checkpoint
        with the same path; arrays come back sharded exactly as the live
        training state is). Leaves whose live counterpart does not exist
        yet (sparse residual stacks, the rng key-data) fall back to the
        checkpoint's own metadata with a replicated sharding."""
        import jax
        import orbax.checkpoint as ocp

        def sds(a):
            return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                        sharding=a.sharding)

        mesh = None
        if self._optimizer is not None:
            mesh = getattr(
                getattr(self._optimizer, "communicator", None),
                "mesh", None)

        def meta_leaf(m):
            # replicated target: correct on one host, and on a pod every
            # host holds the full (small) array
            if mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec
                return jax.ShapeDtypeStruct(
                    tuple(m.shape), np.dtype(m.dtype),
                    sharding=NamedSharding(mesh, PartitionSpec()))
            return jax.ShapeDtypeStruct(tuple(m.shape), np.dtype(m.dtype))

        meta = ocp.StandardCheckpointer().metadata(os.path.abspath(path))
        # newer orbax wraps the tree in CheckpointMetadata.item_metadata;
        # older releases return the tree directly
        meta = getattr(meta, "item_metadata", meta)
        tpl = {
            "model": {k: sds(t.data)
                      for k, t in self.get_states().items()},
            "opt": {}, "res": {},
            "rng": meta_leaf(meta["rng"]),
        }
        if self._optimizer is not None and meta.get("opt"):
            self._optimizer.setup(self.get_params().values())
            tpl["opt"] = {f"s{i}": sds(a) for i, a in
                          enumerate(self._optimizer.state_arrays())}
        tpl["res"] = {k: meta_leaf(m)
                      for k, m in (meta.get("res") or {}).items()}
        return tpl

    def load_checkpoint(self, path: str, validate: bool = True):
        """Restore a `save_checkpoint` directory (a .../step_N path) into
        this model + its optimizer + the device RNG. The model must be
        built/compiled first so params exist, but NOT to the same
        topology: the restore template carries the LIVE training state's
        shardings, so orbax reshards the saved arrays onto whatever mesh
        this process runs — a checkpoint saved on an 8-device mesh
        restores onto 4 (or onto a single device) with the training
        state intact (tests/test_resilience.py::
        test_kill_and_resume_onto_smaller_mesh). Under `jax.distributed`
        every process calls this with the same path and receives only
        its own shards — no host ever gathers the full arrays.

        With `validate` (default) and a `step_N.manifest.json` sibling
        present (the resilience layer writes one per durable save), the
        manifest's parameter signature is checked against this model
        first — a shape/dtype mismatch raises ValueError naming the
        offending params instead of orbax failing midway through a
        partial restore; topology differences are allowed (that is the
        resharding path) and reported as a `resilience` event.
        Optimizer state (including sparse error-feedback residuals saved
        before/after their order existed) resumes exactly; bit-identical
        continuation is asserted single-process by tests/test_model.py::
        test_checkpoint_resume_equivalence and across 2 processes by
        examples/multihost/ckpt_2proc.py (the CI leg)."""
        import jax
        import orbax.checkpoint as ocp
        from . import overlap, resilience
        # barrier: an async save of THIS path (or any other) must be
        # durable before restore reads it — and its deferred error must
        # surface here rather than restore racing a half-written dir
        overlap.wait_for_checkpoints()
        manifest = resilience.read_manifest(path)
        if validate and manifest is not None:
            problems = resilience.validate_manifest(manifest, self)
            if problems:
                raise ValueError(
                    f"checkpoint {path} does not fit this model: "
                    + "; ".join(problems))
            saved = (manifest.get("mesh") or {}).get("n_devices")
            live = len(jax.devices())
            if saved and saved != live:
                observe.get_registry().emit(
                    {"kind": "resilience", "event": "reshard_restore",
                     "path": path, "saved_devices": saved,
                     "live_devices": live})
        ck = ocp.StandardCheckpointer()
        with observe.span("checkpoint.load"):
            tree = ck.restore(os.path.abspath(path),
                              self._restore_template(path))
        # direct buffer assignment: the restored arrays already carry the
        # live shardings (template), so no host round-trip — required on
        # multi-host, where np.asarray of a global array would throw
        states = self.get_states()
        for k, v in tree["model"].items():
            states[k].data = v
        if self._optimizer is not None and tree.get("opt"):
            # (setup already ran while building the restore template, so
            # the positional slot order below cannot misalign)
            opt_tree = tree["opt"]
            arrs = [opt_tree[f"s{i}"] for i in range(len(opt_tree))]
            self._optimizer.load_state_arrays(arrs)
            load_stacks = getattr(self._optimizer,
                                  "load_residual_device_stacks", None)
            if load_stacks is not None and tree.get("res"):
                load_stacks({int(k[1:]): np.asarray(v)
                             for k, v in tree["res"].items()})
        from .device import get_default_device
        dev = self._device or get_default_device()
        dev.rng_state = jax.random.wrap_key_data(
            jnp.asarray(np.asarray(tree["rng"]), jnp.uint32))
        self._compiled_step = None  # drop stale executable state binding
        return self

    def load_states(self, fpath: str) -> dict:
        # span -> the goodput `checkpoint` bucket; covers set_states too
        # (the host->device transfer is part of the restore, as on the
        # orbax path)
        with observe.span("checkpoint.load"):
            with zipfile.ZipFile(fpath, "r") as zf:
                with zf.open("tensor_dict.npz") as f:
                    loaded = dict(np.load(io.BytesIO(f.read())))
            aux = {k[len("aux."):]: v for k, v in loaded.items()
                   if k.startswith("aux.")}
            model_states = {k: v for k, v in loaded.items()
                            if not k.startswith("aux.")}
            self.set_states(model_states)
            self._compiled_step = None  # drop stale executable binding
        return aux
