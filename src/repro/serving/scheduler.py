"""Continuous batching scheduler: a rolling mixed-timestep batch.

``ServingEngine.flush()`` is *lockstep*: requests coalesce into one
batch that enters and leaves the sampler together, so the batch runs
below capacity whenever requests arrive staggered — a late request waits
a full ``num_steps`` dispatch.  :class:`ContinuousScheduler` keeps the
batch **rolling** instead (vLLM-style): every tick advances all resident
rows one Euler step via ``core.sampling.sample_ensemble_step``, with
each row at its *own* ``t_idx``; requests join at the next step boundary
as soon as a row frees, finished rows are sliced out and resolved
immediately, and the compiled step program never retraces on churn
(capacity-stable shapes, per-row tables as gathers).

Layering:

* **admission control** — requests queue FIFO (by ``PendingRequest.seq``,
  the engine's global submission counter); a request is admitted when its
  shape bucket has ``batch_size`` free rows.  Queue depth is bounded:
  ``submit`` raises :class:`QueueBackpressure` past ``max_queue_depth``
  (callers shed load instead of growing an unbounded host queue), and a
  request wider than a bucket (``batch_size > max_resident``) is rejected
  outright as unschedulable.
* **shape bucketing** — buckets are keyed by the conditioning signature
  (text present + trailing text shape) and, on an elastic engine, the
  membership epoch the request was admitted under.  Each bucket owns one
  :class:`~repro.serving.batch.RollingBatch` of fixed ``max_resident``
  capacity, so every tick reuses one compiled program per bucket
  whatever joins or leaves.
* **state machine** — ``PendingRequest.state`` walks QUEUED → RESIDENT →
  DONE, or → FAILED after ``engine.max_request_requeues`` automatic
  re-queues (same policy as ``flush``); a failing bucket re-queues its
  residents in **seq order**.
* **snapshot semantics** — a bucket pins its admission-time membership
  tuple, so hot add/evict during flight cannot change in-flight outputs
  (epoch-keyed buckets compose with PR 6's elastic membership: a new
  epoch simply opens a new bucket while the old one drains).
* **observability** — ``metrics`` (``repro.serving.metrics``) records
  queue-wait and end-to-end latency per request in seconds and scheduler
  steps; a tick that resolves a request folds the percentile snapshot
  into ``engine.stats`` (``latency_p50_s`` …) and :meth:`line` renders
  the one-line summary the serve CLI prints.  Before each compiled tick
  the scheduler counts the earlier ticks the device has not finished
  (``engine.stats["ticks_in_flight"]``, history in ``in_flight``).  Each
  phase of a tick runs in a profiler span (``sched.admit``,
  ``sched.advance``, ``sched.collect``, ``sched.publish``) and each
  request's submit, admission and resolution in one carrying its ``seq``
  (``request.submit``, ``request.admit``, ``request.resolve``); with
  the profiler off a span costs about a microsecond of host time.
* **resilience hooks** — three overridable no-op seams
  (:meth:`_admission_blocked`, :meth:`_on_admit`,
  :meth:`_accept_result`) let ``repro.serving.resilience``'s
  :class:`~repro.serving.resilience.ResilientScheduler` layer request
  deadlines, step watchdogs, expert circuit breakers, and a
  crash-recoverable journal on top of this class without forking the
  tick loop.

Bitwise parity: a row admitted at tick ``n`` sees exactly the step
sequence a dedicated ``generate`` call with its key would run (row
independence — see ``sample_ensemble_step``), proven in
``tests/test_continuous.py``.
"""

from __future__ import annotations

import collections
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import sample_ensemble_step
from repro.launch.sharding import mesh_scope, rolling_state_shardings
from repro.serving.batch import RollingBatch, draw_noise
from repro.serving.metrics import LatencyRecorder, RequestTiming


class AdmissionError(RuntimeError):
    """A request the admission controller can never schedule."""


class QueueBackpressure(AdmissionError):
    """Queue depth hit ``max_queue_depth`` — shed load and retry later."""


#: readings of the ticks-in-flight counter the scheduler keeps
IN_FLIGHT_RING = 1024


class ContinuousScheduler:
    """Rolling mixed-timestep scheduler over a ``ServingEngine``.

    Construction validates the engine against the rolling hot path's
    restrictions (routed engine, per-sample strategy, step-fused) so
    misconfiguration fails at build time, not at the first tick.

    ``clock`` is injectable for deterministic latency tests.
    """

    def __init__(
        self,
        engine,
        *,
        max_resident: int = 8,
        max_queue_depth: int = 256,
        steps_per_tick: int = 1,
        clock=time.perf_counter,
    ) -> None:
        if max_resident < 1:
            raise ValueError(f"max_resident must be >= 1, got {max_resident}")
        if steps_per_tick < 1:
            raise ValueError(
                f"steps_per_tick must be >= 1, got {steps_per_tick}"
            )
        cfg = engine.sampler
        if cfg.strategy not in ("top1", "topk"):
            raise ValueError(
                f"continuous batching requires per-sample routing "
                f"(strategy 'top1' or 'topk'); got {cfg.strategy!r}"
            )
        if not cfg.step_fused:
            raise ValueError(
                "continuous batching runs on the step-fused hot path "
                "only; construct the engine with step_fused=True"
            )
        if engine.engine not in ("auto", "routed"):
            raise ValueError(
                f"continuous batching requires the routed engine; got "
                f"engine={engine.engine!r}"
            )
        if engine.param_store is None or len(engine.experts) <= 1:
            raise ValueError(
                "continuous batching needs a homogeneous ensemble of "
                ">= 2 experts (stacked param store)"
            )
        self.engine = engine
        self.max_resident = max_resident
        self.max_queue_depth = max_queue_depth
        #: Euler steps each compiled tick advances in ONE launch (an
        #: in-program ``lax.scan`` over the identical fused-step body).
        #: Joins/leaves still happen at step boundaries — a tick
        #: boundary IS a step boundary — but admission granularity
        #: coarsens to every ``steps_per_tick`` steps.  On hosts where
        #: a compiled launch has a large fixed cost (CPU: ~10 ms per
        #: launch vs ~2 ms per in-scan step), this amortizes the launch
        #: the same way the lockstep scan does; rows that finish
        #: mid-tick freeze at the sentinel inside the launch, so the
        #: math is unchanged.
        self.steps_per_tick = steps_per_tick
        self.clock = clock
        self.metrics = LatencyRecorder()
        #: ticks dispatched and not finished on the device, read before
        #: each compiled tick (newest last)
        self.in_flight: collections.deque[int] = collections.deque(
            maxlen=IN_FLIGHT_RING)
        self.step_count = 0
        K = len(engine.experts)
        self.k_slots = 1 if cfg.strategy == "top1" else min(cfg.top_k, K)
        self._queue: list = []                       # QUEUED, seq order
        self._buckets: dict[tuple, RollingBatch] = {}
        self._timings: dict[int, RequestTiming] = {}

    # -- submission ---------------------------------------------------------

    def submit(self, key, text_emb=None, batch_size: int | None = None):
        """Enqueue a request; returns the engine's ``PendingRequest``.

        Noise derives from the request's own key at admission, so the
        resolved samples are bitwise what ``generate`` with that key
        would produce.  Raises :class:`QueueBackpressure` when the host
        queue is full and :class:`AdmissionError` when ``batch_size``
        exceeds ``max_resident`` (it could never fit a bucket).
        """
        from repro.launch.serve import PendingRequest

        eng = self.engine
        if batch_size is None:
            batch_size = text_emb.shape[0] if text_emb is not None else 1
        if text_emb is not None and text_emb.shape[0] != batch_size:
            raise ValueError(
                f"text_emb batch {text_emb.shape[0]} != batch_size "
                f"{batch_size}"
            )
        if batch_size > self.max_resident:
            raise AdmissionError(
                f"batch_size {batch_size} > max_resident "
                f"{self.max_resident}: the request can never fit a "
                f"rolling bucket — split it or raise max_resident"
            )
        if len(self._queue) >= self.max_queue_depth:
            raise QueueBackpressure(
                f"scheduler queue is full ({self.max_queue_depth} "
                f"requests waiting); retry after step() drains it"
            )
        with TraceAnnotation("request.submit") as span:
            req = PendingRequest(
                key=key, text_emb=eng._cached_cond(text_emb),
                batch_size=batch_size, _membership=eng._membership(),
            )
            req.seq = eng._next_seq()
            span.set_metadata(seq=req.seq)
            self._timings[req.seq] = RequestTiming(
                submit_t=self.clock(), submit_step=self.step_count
            )
            self._queue.append(req)
            eng.stats["requests"] += 1
        return req

    # -- scheduling tick ----------------------------------------------------

    def step(self) -> int:
        """One scheduler tick: admit → advance every bucket one Euler
        step → resolve finished requests.  Returns the number resolved."""
        self.step_count += 1
        with TraceAnnotation("sched.admit"):
            self._admit()
        for sig, bucket in list(self._buckets.items()):
            if bucket.num_resident == 0:
                continue
            try:
                self._advance(bucket)
            except Exception as e:          # noqa: BLE001 — isolate bucket
                self._fail_bucket(sig, bucket, e)
        with TraceAnnotation("sched.collect"):
            resolved = self._collect()
        self._gc_buckets()
        if resolved:                # the snapshot moves only on resolution
            with TraceAnnotation("sched.publish"):
                self.engine.stats.update(self.metrics.snapshot())
        self.engine.stats["scheduler_steps"] = self.step_count
        return resolved

    def run_until_idle(self, max_steps: int = 100_000) -> int:
        """Tick until queue and buckets are empty; returns total
        resolved.  ``max_steps`` bounds a livelocked loop loudly."""
        total = 0
        while self._queue or self.num_resident:
            if self.step_count >= max_steps:
                raise RuntimeError(
                    f"scheduler not idle after {max_steps} steps: "
                    f"queued={len(self._queue)} "
                    f"resident={self.num_resident}"
                )
            total += self.step()
        return total

    # -- introspection ------------------------------------------------------

    @property
    def num_resident(self) -> int:
        return sum(b.num_resident for b in self._buckets.values())

    @property
    def queue_depth(self) -> int:
        return len(self._queue)

    def max_pending_wait_steps(self) -> int:
        """Steps the oldest still-queued request has waited (0 if none);
        the liveness signal ``analysis.sanitize.check_scheduler_liveness``
        bounds."""
        waits = [
            self.step_count - self._timings[r.seq].submit_step
            for r in self._queue
        ]
        return max(waits, default=0)

    def line(self) -> str:
        """One-line scheduler summary (the serve CLI prints it).

        Percentile fields are absent from the snapshot until the first
        request resolves (empty-window percentiles are None, not 0.0 —
        see ``metrics.percentile``), so the line degrades to "-" rather
        than printing garbage or raising on a cold scheduler."""
        s = self.metrics.snapshot()

        def f(key, scale=1.0, fmt=".0f"):
            v = s.get(key)
            return "-" if v is None else format(v * scale, fmt)

        return (
            f"scheduler: step={self.step_count} "
            f"resident={self.num_resident}/{self.max_resident} "
            f"queued={len(self._queue)} "
            f"inflight={self.engine.stats.get('ticks_in_flight', 0)} "
            f"done={self.metrics.completed} "
            f"({s['throughput_img_s']:.1f} img/s) "
            f"wait p50={f('queue_wait_p50_steps')} "
            f"p95={f('queue_wait_p95_steps')} steps "
            f"e2e p50={f('latency_p50_s', 1e3)} "
            f"p95={f('latency_p95_s', 1e3)} ms"
        )

    # -- internals ----------------------------------------------------------

    def _sig(self, req) -> tuple:
        has_text = req.text_emb is not None
        tail = tuple(req.text_emb.shape[1:]) if has_text else ()
        epoch = req._membership[0] if req._membership is not None else -1
        return (has_text, tail, epoch)

    def _admit(self) -> None:
        """FIFO admission with per-bucket head-of-line blocking: a
        request that doesn't fit blocks later requests of the SAME
        bucket (fairness within a shape class) but not other buckets."""
        eng = self.engine
        blocked: set[tuple] = set()
        rest: list = []
        for req in self._queue:
            sig = self._sig(req)
            if sig in blocked or self._admission_blocked(sig):
                rest.append(req)
                continue
            bucket = self._buckets.get(sig)
            if bucket is None:
                bucket = self._make_bucket(sig, req)
                self._buckets[sig] = bucket
            if bucket.free_count() < req.batch_size:
                blocked.add(sig)
                rest.append(req)
                continue
            with TraceAnnotation("request.admit", seq=req.seq):
                noise = draw_noise(
                    req.key, (req.batch_size,) + eng.latent_shape
                )
                bucket.admit(req, noise)
            req.state = "RESIDENT"
            tm = self._timings[req.seq]
            tm.admit_t = self.clock()
            tm.admit_step = self.step_count
            # Deterministic refresh-work accounting, mirroring
            # _count_plan_refreshes: each admitted request refreshes its
            # routing slots ceil(S/R) times over its life.
            r = max(1, eng.sampler.plan_refresh_every)
            eng.stats["plan_refreshes"] += -(-eng.sampler.num_steps // r)
            self._on_admit(req, bucket)
        self._queue = rest

    # -- resilience hooks (no-ops here; ResilientScheduler overrides) -------

    def _admission_blocked(self, sig: tuple) -> bool:
        """Extra per-bucket admission gate (e.g. retry backoff windows)."""
        return False

    def _on_admit(self, req, bucket: RollingBatch) -> None:
        """Called once per admitted request (e.g. journal the admit)."""

    def _accept_result(self, bucket: RollingBatch, req, out, rows) -> bool:
        """Vet a finished request's latents before it resolves DONE.

        ``rows`` are the bucket rows the request occupied (already
        released).  Return False to veto: the hook owns the terminal
        state + bookkeeping and ``_collect`` skips the DONE path."""
        return True

    def _make_bucket(self, sig: tuple, req) -> RollingBatch:
        has_text, tail, _epoch = sig
        return RollingBatch(
            capacity=self.max_resident,
            latent_shape=self.engine.latent_shape,
            k_slots=self.k_slots,
            num_steps=self.engine.sampler.num_steps,
            text_tail=tail if has_text else None,
            membership=req._membership,
        )

    def _advance(self, bucket: RollingBatch) -> None:
        eng = self.engine
        inflight = self._count_in_flight()
        with TraceAnnotation("sched.advance", inflight=inflight):
            has_text = bucket.text is not None
            fn = self._get_rolling_compiled(has_text, bucket.text_tail)
            text = bucket.text if has_text \
                else jnp.zeros((0,), jnp.float32)        # static filler
            args = eng._sampler_args(bucket.membership)
            if eng.elastic:
                eng._note_degraded(args[1], steps=1)
            out = fn(bucket.x, bucket.t_idx, bucket.slot_idx,
                     bucket.slot_w, text, *args)
        bucket.x, bucket.t_idx, bucket.slot_idx, bucket.slot_w = out
        bucket.unfinished.append(bucket.t_idx)
        bucket.advance_host(self.steps_per_tick)

    def _count_in_flight(self) -> int:
        """Ticks dispatched and not yet finished on the device, over all
        buckets; recorded in ``in_flight`` and published as
        ``engine.stats["ticks_in_flight"]``.  A tick's ``t_idx`` output
        (never donated) reports ``is_ready()`` once the device has run
        it; ticks of one bucket finish in order.  Never blocks."""
        n = 0
        for bucket in self._buckets.values():
            q = bucket.unfinished
            while q and q[0].is_ready():
                q.popleft()
            n += len(q)
        self.in_flight.append(n)
        self.engine.stats["ticks_in_flight"] = n
        return n

    def _get_rolling_compiled(self, has_text: bool, text_tail):
        """Jitted rolling step, cached in the engine's compiled-fn cache
        (one trace per bucket shape — ``stats['traces']`` counts it,
        same contract ``assert_no_retrace`` audits)."""
        eng = self.engine
        key = ("rolling", self.max_resident, self.steps_per_tick,
               eng.latent_shape, eng.sampler, eng.engine, has_text,
               text_tail)
        fn = eng._compiled.get(key)
        if fn is not None:
            return fn
        B = self.max_resident
        shape = (B,) + eng.latent_shape
        latent_sharding = None
        plan_sharding = None
        jit_kwargs: dict = {}
        if eng.mesh is not None:
            from repro.launch.sharding import dispatch_plan_sharding

            latent_sharding, row_state = rolling_state_shardings(
                eng.mesh, shape
            )
            plan_sharding = dispatch_plan_sharding(eng.mesh)
            lat_spec = latent_sharding.spec
            batch_sharded = len(lat_spec) > 0 and lat_spec[0] is not None
            text_spec = P("data") if (has_text and batch_sharded) else P()
            jit_kwargs["in_shardings"] = (
                latent_sharding,                      # x
                row_state,                            # t_idx
                row_state,                            # slot_idx
                row_state,                            # slot_w
                NamedSharding(eng.mesh, text_spec),   # text
                *eng._sampler_arg_shardings(),
            )

        spt = self.steps_per_tick

        def _tick(one_step, x, t_idx, slot_idx, slot_w):
            """Advance ``steps_per_tick`` fused steps in one launch.

            ``spt == 1`` calls the step body directly (the canonical
            single-step program the parity suite pins down);
            ``spt > 1`` runs the identical body under ``lax.scan``.
            The barrier between iterations is load-bearing for bitwise
            parity: XLA fully unrolls short constant-trip loops and
            would then fuse/reassociate arithmetic ACROSS the step
            boundary (ulp drift vs separate launches); pinning each
            iteration's outputs restores launch-boundary semantics
            while keeping the launch-cost amortization."""
            if spt == 1:
                return one_step((x, t_idx, slot_idx, slot_w))

            def body(carry, _):
                return jax.lax.optimization_barrier(one_step(carry)), None

            carry, _ = jax.lax.scan(
                body, (x, t_idx, slot_idx, slot_w), None, length=spt
            )
            return carry

        def _step(x, t_idx, slot_idx, slot_w, text, params, store,
                  router_fn, tables, cmap):
            eng.stats["traces"] += 1   # runs at trace time only
            cond = {"text_emb": text} if has_text else None
            null = {"text_emb": None} if has_text else None

            def one_step(carry):
                x, t_idx, slot_idx, slot_w = carry
                return sample_ensemble_step(
                    eng.experts, params, router_fn,
                    x, t_idx, slot_idx, slot_w,
                    cond=cond, null_cond=null, config=eng.sampler,
                    engine=eng.engine, stacked_params=store,
                    latent_sharding=latent_sharding,
                    plan_sharding=plan_sharding,
                    coeff_tables=tables, cluster_map=cmap,
                )

            with mesh_scope(eng.mesh):
                return _tick(one_step, x, t_idx, slot_idx, slot_w)

        # The latent buffer is donated (aliased into the step output);
        # row state is tiny and kept undonated for host re-inspection.
        donate = () if jax.default_backend() == "cpu" else (0,)
        fn = jax.jit(_step, donate_argnums=donate, **jit_kwargs)
        eng._compiled[key] = fn
        return fn

    def _collect(self) -> int:
        """Resolve every request whose rows all reached the grid end."""
        resolved = 0
        for bucket in self._buckets.values():
            if bucket.num_resident == 0:
                continue
            # Pure host computation (t_host mirror): completion never
            # forces a device sync, so ticks pipeline asynchronously and
            # only result() materialization blocks.
            for req in bucket.finished_requests():
                with TraceAnnotation("request.resolve", seq=req.seq):
                    resolved += self._resolve(bucket, req)
        return resolved

    def _resolve(self, bucket: RollingBatch, req) -> int:
        """Hand a finished request its latents; 1 if it resolved DONE."""
        rows = bucket.rows_of(req.seq)
        out = bucket.resolve(req)
        if not self._accept_result(bucket, req, out, rows):
            return 0
        req._result = out
        req.done = True
        req.state = "DONE"
        tm = self._timings.pop(req.seq)
        now = self.clock()
        self.metrics.observe(
            queue_wait_s=tm.admit_t - tm.submit_t,
            e2e_s=now - tm.submit_t,
            queue_wait_steps=tm.admit_step - tm.submit_step,
            e2e_steps=self.step_count - tm.submit_step,
            images=req.batch_size,
            now=now,
        )
        return 1

    def _fail_bucket(self, sig: tuple, bucket: RollingBatch, e) -> None:
        """Isolate a failing bucket: release + re-queue its residents in
        seq (submission) order, FAILED past the re-queue budget; the
        bucket itself is dropped (its buffers may be poisoned)."""
        eng = self.engine
        for req in bucket.resident_requests():
            bucket.release(req)
            req.requeues += 1
            if req.requeues > eng.max_request_requeues:
                req.state = "FAILED"
                req.error = e
                eng.stats["failed_requests"] += 1
                self._timings.pop(req.seq, None)
            else:
                req.state = "QUEUED"
                eng.stats["request_requeues"] += 1
                self._queue.append(req)
        self._queue.sort(key=lambda r: r.seq)
        del self._buckets[sig]

    def _gc_buckets(self) -> None:
        """Drop drained stale-epoch buckets; complete DRAINING slots
        (retire_expert) once nothing in flight references them."""
        eng = self.engine
        if not eng.elastic:
            return
        for sig in [
            s for s, b in self._buckets.items()
            if b.num_resident == 0 and s[2] != eng.membership_epoch
        ]:
            del self._buckets[sig]
        if not self._queue and self.num_resident == 0:
            for i, h in enumerate(eng.expert_health):
                if h == "DRAINING":
                    eng.expert_health[i] = "EVICTED"
