"""Batched packet-serving engine for generated data-plane pipelines.

The LM ``ServeEngine`` (serve/engine.py) batches token requests into fixed
decode slots; ``PacketServeEngine`` is its data-plane sibling: it
micro-batches incoming packets into a FIXED batch shape and pushes them
through ONE compiled program — a ``CompiledDag`` (whole-DAG jit from
core.chaining), a single ``Pipeline``, or a stateful
``flowstate.StatefulPipeline``.  The fixed shape means the XLA executable
is compiled exactly once; ragged tails are zero-padded and the padding
verdicts sliced off, so steady-state serving never re-traces.

Overlap pipelining (docs/pipeline_ir.md#serving-performance-contract):
the engine keeps up to ``depth`` batches in flight — batch N+1 is staged
(copied into a reusable ring of pinned staging buffers) and dispatched
while batch N still computes.  Compiled pipelines expose ``dispatch``
(launch, no blocking device→host copy) and JAX's async dispatch does the
overlap.  A dispatch also starts the copy of its verdicts to the host
(``copy_to_host_async`` on a lazy device result), so that copy runs while
later batches are staged and launched; results are still *taken* only
when ``flush()``/stream consumption needs them, in FIFO (arrival) order
(``stats()["early_copies"]`` counts the batches whose copy began early).
Steady-state serving performs zero per-batch staging allocations.
``ServeStats`` separates host dispatch time (``dispatch_s``) from
per-batch pipeline latency (dispatch → result ready) and accumulates
``wall_s`` as the *active serving span*, so pkt/s stays honest under
overlap instead of crediting hidden device time.

Stateful serving: a ``StatefulPipeline`` threads a per-flow register file
(``FlowState``) through every batch.  The engine owns the state between
batches, feeds padded rows with ``valid=0`` so they NEVER touch the
register table, and applies batches strictly in arrival order — the
in-flight chain is sequentialized by the state dependency itself (each
dispatch consumes the previous dispatch's device-resident state), so
overlap never reorders updates (property-tested in
tests/test_packet_engine.py under depth>1).

Typical use::

    dag = chaining.compile_dag(ad > tc, result)
    eng = PacketServeEngine(dag, feature_dim=7, max_batch=512,
                            backend="pallas", depth=2)
    eng.submit(packets)           # any [n, F] chunk, any n
    verdicts = eng.flush()        # all pending verdicts, in arrival order
    print(eng.stats())            # includes which backend served

    sp = StatefulPipeline(stages, backend="pallas")
    eng = PacketServeEngine(sp, feature_dim=4, max_batch=512)
    # per-flow registers update per packet; eng.state is the live table

Multi-device serving is ``repro.serve.sharded.ShardedPacketServeEngine``.
"""

from __future__ import annotations

import collections
import dataclasses
import threading
import time
from typing import Any, Callable, Iterable, Iterator

import numpy as np


@dataclasses.dataclass
class ServeStats:
    packets: int = 0
    batches: int = 0
    pad_packets: int = 0           # zero-rows added to fill the last batch
    # hot-swap accounting (docs/pipeline_ir.md#hot-swap-contract): each
    # installed swap records its end-to-end latency (swap() request ->
    # ring-boundary install, warm-up compile included) and the packet
    # offset of the boundary — packets [0, off) were served by the model
    # before the swap, packets [off, ...) by the model after it
    swaps: int = 0
    swap_lat_s: list = dataclasses.field(default_factory=list)
    swap_pkt_offsets: list = dataclasses.field(default_factory=list)
    # batch count per serving engine, accumulated at dispatch time so the
    # split stays correct across hot swaps that change the backend
    backend_counts: dict = dataclasses.field(default_factory=dict)
    # active serving span: dispatch of a batch -> its result materialized,
    # with overlapping in-flight windows merged (never double-counted), so
    # packets / wall_s is honest throughput under depth>1 overlap
    wall_s: float = 0.0
    # host time spent staging + launching batches (the synchronous part of
    # serving); under overlap this is much smaller than wall_s
    dispatch_s: float = 0.0
    # batches whose verdict copy to the host began at dispatch (a lazy
    # device result); a numpy result is already on the host and not counted
    early_copies: int = 0
    backend: str = "interpret"     # engine the compiled pipeline runs on
    # the fused flow launch's classifier (StatefulPipeline.fused_suffix):
    # kind, trees, depth, node visits per packet, packed parameter bytes
    fused_suffix: dict = dataclasses.field(default_factory=dict)
    depth: int = 1                 # dispatch-pipeline depth (in-flight cap)
    shards: int = 1                # devices serving (ShardedPacketServeEngine)
    # trailing window of per-batch latencies: bounded so a long-running
    # engine keeps O(1) memory and stats() cost (percentiles are over the
    # most recent LAT_WINDOW batches).  A batch's latency is dispatch ->
    # result ready: under overlap it includes in-flight queueing, which is
    # what a packet actually waits.
    batch_lat_s: collections.deque = dataclasses.field(
        default_factory=lambda: collections.deque(maxlen=ServeStats.LAT_WINDOW)
    )

    LAT_WINDOW = 4096

    @property
    def pkt_per_s(self) -> float:
        if self.batches == 0:
            return 0.0             # nothing served yet: rate is 0, not 0/0
        return self.packets / max(self.wall_s, 1e-9)

    def _lat_ms(self, q: float) -> float:
        if not self.batch_lat_s:
            return 0.0             # nothing served: percentiles are 0, not nan
        v = float(np.percentile(np.asarray(self.batch_lat_s), q)) * 1e3
        return v if np.isfinite(v) else 0.0

    @property
    def lat_p50_ms(self) -> float:
        """Median per-batch pipeline latency (padding included)."""
        return self._lat_ms(50)

    @property
    def lat_p95_ms(self) -> float:
        return self._lat_ms(95)

    @property
    def lat_p99_ms(self) -> float:
        return self._lat_ms(99)

    @property
    def backend_batches(self) -> dict:
        """Batch count per serving engine, accumulated per dispatched
        batch — across a hot swap the old and new engines keep separate
        counts.  A DAG mixing engines per-model reports as "mixed" here
        with the per-model detail on ``CompiledDag.model_backends``."""
        if self.backend_counts:
            return dict(self.backend_counts)
        return {self.backend: self.batches} if self.batches else {}

    def count_batch(self, backend: str, n: int, pad: int = 0) -> None:
        """Record one dispatched batch of ``n`` real rows on ``backend``."""
        self.batches += 1
        self.packets += n
        self.pad_packets += pad
        self.backend_counts[backend] = \
            self.backend_counts.get(backend, 0) + 1

    def record_swap(self, lat_s: float) -> None:
        """Record one installed hot swap at the current packet offset."""
        self.swaps += 1
        self.swap_lat_s.append(float(lat_s))
        self.swap_pkt_offsets.append(int(self.packets))

    def as_dict(self) -> dict:
        return {
            "packets": self.packets,
            "batches": self.batches,
            "pad_packets": self.pad_packets,
            "wall_s": round(self.wall_s, 6),
            "dispatch_s": round(self.dispatch_s, 6),
            "early_copies": self.early_copies,
            "pkt_per_s": round(self.pkt_per_s, 1),
            "lat_p50_ms": round(self.lat_p50_ms, 4),
            "lat_p95_ms": round(self.lat_p95_ms, 4),
            "lat_p99_ms": round(self.lat_p99_ms, 4),
            "backend": self.backend,
            "backend_batches": self.backend_batches,
            "fused_suffix": dict(self.fused_suffix),
            "depth": self.depth,
            "shards": self.shards,
            "swaps": self.swaps,
            "swap_lat_ms": [round(s * 1e3, 3) for s in self.swap_lat_s],
            "swap_pkt_offsets": [int(p) for p in self.swap_pkt_offsets],
        }


@dataclasses.dataclass
class _InFlight:
    """One dispatched-but-unfetched batch."""

    n: int                         # real (non-padding) rows
    out: Any                       # device array (lazy) or numpy (ready)
    t0: float                      # dispatch start
    ready: float | None            # completion time if known at dispatch
    batch: int = 0                 # the engine's batch ordinal
    perm: Any = None               # sharded stateful: per-shard row indices


class _CompiledPipeline:
    """numpy front-end over a ``stageir.CompiledStages`` recompile."""

    def __init__(self, compiled):
        self._compiled = compiled
        self.backend = compiled.backend

    def dispatch(self, X: np.ndarray):
        """Launch without forcing the device->host copy."""
        return self._compiled(X)

    def __call__(self, X: np.ndarray) -> np.ndarray:
        return np.asarray(self.dispatch(X), np.int32)


def _rebind_backend(pipeline, backend: str):
    """Recompile ``pipeline`` for the requested execution engine.

    A ``CompiledDag`` or ``flowstate.StatefulPipeline`` recompiles itself
    (``with_backend``); a ``codegen.Pipeline`` recompiles its stage list;
    a bare callable has no stage list to lower, so the request degrades to
    serving it as-is (the interpreter fallback the stats then report)."""
    from repro.core import stageir

    if backend not in stageir.EXEC_BACKENDS:
        raise KeyError(f"backend must be one of {stageir.EXEC_BACKENDS}")
    if hasattr(pipeline, "with_backend"):            # chaining.CompiledDag
        return pipeline.with_backend(backend)
    if hasattr(pipeline, "stages"):                  # codegen.Pipeline
        return _CompiledPipeline(
            stageir.compile_stages(pipeline.stages, backend=backend)
        )
    return pipeline


def _pipeline_backend(pipeline) -> str:
    """The engine a compiled pipeline reports it actually serves on."""
    from repro.core import stageir

    backend = getattr(pipeline, "backend", "interpret")
    if backend not in stageir.REPORT_BACKENDS:
        backend = "interpret"          # e.g. Pipeline.backend == "taurus"
    if hasattr(pipeline, "compiled_backend"):        # codegen.Pipeline
        backend = pipeline.compiled_backend
    return backend


def _backend_stats_key(pipeline, backend: str) -> str:
    """Per-batch accounting key: the serving engine, annotated with the
    fused-path decline reason when a stateful pipeline asked for the
    single-launch fused kernel and fell back to the split path
    (``StatefulPipeline.fallback_reason``) — so ``backend_counts`` says
    not just WHERE batches served but WHY the fused launch declined."""
    reason = getattr(pipeline, "fallback_reason", None)
    return f"{backend}({reason})" if reason else backend


class PacketServeEngine:
    """Micro-batching front-end over one compiled pipeline/DAG callable.

    ``pipeline`` may be a ``codegen.Pipeline``, a ``chaining.CompiledDag``
    or any ``[n, F] -> verdicts`` callable.  ``backend`` optionally
    recompiles the pipeline for a specific execution engine:

    * ``backend=None`` (default) serves the callable as given;
    * ``backend="pallas"`` lowers kernel-eligible pipelines onto fused
      Pallas kernel launches (docs/pipeline_ir.md#pallas-lowering-contract)
      — a whole kernel-eligible DAG onto ONE megakernel launch
      (``"pallas-fused-dag"``) — and **falls back to the interpreter**
      when Pallas is unavailable, the stage sequence is outside the kernel
      envelope, or the callable carries no stage list to recompile;
    * ``backend="interpret"`` forces the jitted stage-walk engine.

    ``depth`` is the dispatch-pipeline depth: up to ``depth`` batches stay
    in flight before the engine blocks on the oldest result (``depth=1``
    reproduces strictly synchronous serving; the default ``2`` is the
    double-buffered pipeline — stage/dispatch batch N+1 while N computes).
    Results are only materialized on ``flush()``/stream consumption, and
    verdicts always come back in arrival order regardless of depth.

    Stateful pipelines (``flowstate.StatefulPipeline``, or anything with
    an ``init_state()``/``(state, X, valid)`` shape) thread a per-flow
    register file through the engine: pass ``state=`` to resume an
    existing table or leave it None to start empty.  Padded rows carry
    ``valid=0`` and never touch the registers; batches apply strictly in
    arrival order — the state dependency itself sequentializes the
    in-flight chain, so overlap is safe.  A pipeline with a trailing
    ``Mitigate`` stage also threads its action table through the same
    state; dropped packets come back as ``flowstate.MITIGATED`` (-1)
    verdicts (docs/pipeline_ir.md#mitigation-contract).

    ``stats()["backend"]`` / ``["backend_batches"]`` report the engine that
    actually served each batch after any fallback; ``lat_p50_ms`` /
    ``lat_p95_ms`` / ``lat_p99_ms`` are per-batch pipeline latency
    percentiles and ``dispatch_s`` the host-side dispatch time.

    ``telemetry`` attaches the unified observability plane
    (docs/pipeline_ir.md#telemetry-contract): ``None``/``True`` create a
    fresh enabled ``repro.telemetry.Telemetry``, ``False`` disables
    recording entirely, and an existing instance is shared (several
    engines reporting into one plane).  Recording happens host-side at
    dispatch-ring boundaries only — counters/spans per dispatched batch,
    flow-table health scans at flush boundaries, operator events (hot
    swaps, backend fallbacks) into the journal — so the compiled
    programs and the overlap pipeline are untouched.  Read it back via
    ``engine.telemetry()``."""

    def __init__(self, pipeline: Callable[[np.ndarray], np.ndarray], *,
                 feature_dim: int, max_batch: int = 256,
                 backend: str | None = None, state=None, depth: int = 2,
                 telemetry=None):
        requested_backend = backend
        if backend is not None:
            pipeline = _rebind_backend(pipeline, backend)
        self.pipeline = pipeline
        # engine provenance: "interpret" unless the callable says otherwise
        self.backend = _pipeline_backend(pipeline)
        self._backend_key = _backend_stats_key(pipeline, self.backend)
        self.feature_dim = int(feature_dim)
        self.max_batch = int(max_batch)
        self.depth = max(1, int(depth))
        self._stateful = state is not None or hasattr(pipeline, "init_state")
        if self._stateful and state is None:
            state = pipeline.init_state()
        self.state = state
        # ``dispatch`` launches without the device->host copy; callables
        # without one are served as-is (their results are simply ready at
        # dispatch time and the overlap is a no-op)
        self._dispatch_fn = getattr(pipeline, "dispatch", pipeline)
        self._queue: collections.deque[np.ndarray] = collections.deque()
        self._pending = 0
        self._inflight: collections.deque[_InFlight] = collections.deque()
        # reusable staging ring: depth+1 pinned buffers so the buffer being
        # filled is never one an in-flight batch may still alias
        self._staging = [
            np.zeros((self.max_batch, self.feature_dim), np.float32)
            for _ in range(self.depth + 1)
        ]
        self._valid_staging = [
            np.zeros((self.max_batch,), np.int32)
            for _ in range(self.depth + 1)
        ]
        self._staging_i = 0
        self._mark: float | None = None   # active-span bookkeeping
        # hot-swap plumbing: swap() (any thread) prepares a new pipeline
        # and parks it here; the serving path installs it at the next
        # dispatch-ring boundary (docs/pipeline_ir.md#hot-swap-contract)
        self._swap_lock = threading.Lock()
        self._pending_swap: tuple | None = None
        self.stats_ = ServeStats(backend=self.backend, depth=self.depth)
        self._init_telemetry(telemetry, requested_backend)
        self._note_lowering(pipeline)
        with self._span("serve.warm_up", cat="compile",
                        backend=self.backend):
            self._warm_up()

    # --------------------------------------------------------- telemetry

    def telemetry(self):
        """The attached ``repro.telemetry.Telemetry`` plane (None when
        constructed with ``telemetry=False``)."""
        return self._tel

    # slot-segmentation stats are recomputed host-side from the packet
    # rows — ~50us of numpy per batch that would contend with XLA's CPU
    # threads; sampling every Nth batch (first included) keeps the
    # schedule-shape picture while holding the telemetry overhead
    # inside the 97% throughput budget.  Tests set 1 for exact counts.
    TELEMETRY_SEG_SAMPLE = 8

    def _init_telemetry(self, telemetry, requested_backend) -> None:
        """Resolve the plane and pre-bind every hot-path handle ONCE, so
        per-batch recording is a few attribute adds (no name lookups,
        no locks — see repro.telemetry.metrics)."""
        from repro import telemetry as T

        self._tel = T.resolve(telemetry)
        # the ``serve.*`` vocabulary (docs/pipeline_ir.md#telemetry-
        # contract): ``_span`` for what the ring keeps, ``_annotate`` for
        # the phases of a batch, named only while a profile is taken.
        # Without a plane a span only times its block (``dispatch_s``)
        self._span = (T.untraced if self._tel is None
                      else self._tel.tracer.span)
        self._annotate = (T.unannotated if self._tel is None
                          else T.annotate)
        self._tel_flowkey = None
        self._tel_slots = 0
        self._backend_children: dict[str, Any] = {}
        self._health_keys = None       # previous flush-boundary key scan
        self._health_marked = 0        # previous marked-flow count
        self._seg_n = 0                # segmentation sampling tick
        if self._tel is None:
            return
        m = self._tel.metrics
        self._tm = {
            "packets": m.counter(
                "serve_packets_total", "real packets dispatched").default,
            "batches": m.counter(
                "serve_batches_total", "micro-batches dispatched").default,
            "early_copies": m.counter(
                "serve_early_host_copies_total",
                "batches whose verdict copy to the host began at "
                "dispatch").default,
            "pad": m.counter(
                "serve_pad_packets_total",
                "zero rows added to fill fixed batch shapes").default,
            "swaps": m.counter(
                "serve_swaps_total", "hot swaps installed").default,
            "mitigated": m.counter(
                "serve_mitigated_packets_total",
                "packets dropped/limited by the action table").default,
            "dispatch_ms": m.histogram(
                "serve_dispatch_ms",
                "host time staging + launching one batch").default,
            "batch_lat_ms": m.histogram(
                "serve_batch_latency_ms",
                "dispatch -> result ready, per batch").default,
            "swap_lat_ms": m.histogram(
                "serve_swap_latency_ms",
                "swap request -> ring-boundary install").default,
            "lockstep": m.counter(
                "flow_lockstep_batches_total",
                "sampled stateful batches retired mostly by the "
                "compacted lockstep rounds"
            ).default,
            "drain": m.counter(
                "flow_drain_batches_total",
                "sampled stateful batches with a drain-heavy traffic "
                "shape (served in-kernel by the compacted drain)"
            ).default,
            "deep_pkts": m.counter(
                "flow_deep_packets_total",
                "packets deeper than PAR_ROUNDS in a same-slot chain "
                "(sampled batches)"
            ).default,
            "max_chain": m.gauge(
                "flow_batch_max_chain",
                "deepest same-slot chain of the last dispatched batch"
            ).default,
            "overflow": m.counter(
                "serve_route_overflow_total",
                "rows pushed back to the queue head because their "
                "shard's sub-batch filled (sharded routing)"
            ).default,
            "resharded": m.gauge(
                "serve_resharded_step_args",
                "sharded step arguments (tables, rows, mask) not placed "
                "with the step's input sharding, so the launch re-slices "
                "or copies them between devices (set at warm-up and swap "
                "install)"
            ).default,
        }
        self._backend_counter = m.counter(
            "serve_backend_batches_total",
            "batches per execution backend actually serving")
        m.gauge("serve_depth", "dispatch-pipeline depth").default.set(
            self.depth)
        self._resolve_flow_telemetry(self.pipeline)
        # a fused-envelope decline (reason carried on the pipeline) is a
        # fallback even when the split path still serves on "pallas"
        reason = getattr(self.pipeline, "fallback_reason", None)
        if reason or (requested_backend == "pallas"
                      and self.backend in ("interpret", "mixed")):
            ev = {"requested": requested_backend or "pallas",
                  "actual": self.backend, "engine": type(self).__name__}
            if reason:
                ev["reason"] = reason
            self._tel.journal.emit("backend_fallback", **ev)

    def _resolve_flow_telemetry(self, pipeline) -> None:
        """Grab the FlowKey stage (if any) so per-batch slot-collision
        stats can be recomputed host-side from the packet rows."""
        if self._tel is None or not self._stateful:
            return
        stages = getattr(pipeline, "stages", None)
        spec = getattr(pipeline, "spec", None)
        if stages is None or spec is None:
            return
        from repro.core import stageir

        fk = next((s for s in stages if isinstance(s, stageir.FlowKey)),
                  None)
        if fk is not None:
            self._tel_flowkey = fk
            self._tel_slots = int(spec.n_slots)
            # pre-bind the segmentation helpers off the hot path
            from repro.flowstate.registers import hash_slot_np
            from repro.telemetry import batch_segmentation

            self._hash_slot_np = hash_slot_np
            self._batch_segmentation = batch_segmentation

    def _seg_tick(self) -> bool:
        """True on the sampled batches (every TELEMETRY_SEG_SAMPLE-th,
        first included) whose slot segmentation gets recomputed."""
        self._seg_n += 1
        return self._seg_n % self.TELEMETRY_SEG_SAMPLE == 1 \
            or self.TELEMETRY_SEG_SAMPLE == 1

    def _record_dispatch(self, rows: np.ndarray, n: int, pad: int,
                         dispatch_s: float, slots=None) -> None:
        """Per-batch hot-path recording: counters, the dispatch-time
        histogram and (stateful pipelines) the slot-segmentation
        statistics mirroring the fused kernel's lockstep-vs-drain
        schedule split.  ``slots`` is the
        precomputed per-row slot vector (sharded routing already holds
        the keys), ``None`` to compute here on sampled batches, or
        ``False`` when the caller sampled the batch OUT."""
        tm = self._tm
        tm["packets"].inc(n)
        tm["batches"].inc(1)
        if pad:
            tm["pad"].inc(pad)
        child = self._backend_children.get(self.backend)
        if child is None:
            child = self._backend_children[self.backend] = \
                self._backend_counter.labels(backend=self.backend)
        child.inc(1)
        tm["dispatch_ms"].observe(dispatch_s * 1e3)
        if self._tel_flowkey is not None and slots is not False:
            if slots is None:
                if not self._seg_tick():
                    return
                slots = self._hash_slot_np(
                    self._tel_flowkey.apply_keys_np(rows), self._tel_slots)
            seg = self._batch_segmentation(slots)
            (tm["drain"] if seg["drain_heavy"] else tm["lockstep"]).inc(1)
            if seg["n_deep"]:
                tm["deep_pkts"].inc(seg["n_deep"])
            tm["max_chain"].set(seg["max_chain"])

    def _scan_flow_health(self) -> None:
        """Flush-boundary health scan of the live register file(s): one
        [S] key compare per table — occupancy/insert/eviction gauges and
        the mitigation engage/release journal events."""
        if self._tel is None or not self._stateful or self.state is None:
            return
        from repro.telemetry import table_health

        with self._span("serve.health_scan"):
            h = table_health(self.state, self._health_keys)
        self._health_keys = h.pop("keys")
        m = self._tel.metrics
        m.gauge("flow_occupied_slots",
                "occupied register-file slots").default.set(h["occupied"])
        m.gauge("flow_occupancy_frac",
                "occupied / total slots").default.set(
            round(h["occupancy_frac"], 6))
        if h["inserts"]:
            m.counter("flow_inserts_total",
                      "slots going empty -> occupied between scans"
                      ).default.inc(h["inserts"])
        if h["evictions"]:
            m.counter("flow_evictions_total",
                      "occupied slots whose key changed between scans "
                      "(collision evictions)").default.inc(h["evictions"])
        if h["mit_slots"]:
            m.gauge("flow_mit_occupied",
                    "occupied action-table slots").default.set(
                h["mit_occupied"])
            m.gauge("flow_mit_marked",
                    "flows past the mitigation threshold").default.set(
                h["mit_marked"])
            delta = h["mit_marked"] - self._health_marked
            if delta > 0:
                self._tel.journal.emit(
                    "mitigation_engage", flows=delta,
                    marked=h["mit_marked"],
                    pkt_offset=int(self.stats_.packets))
            elif delta < 0:
                self._tel.journal.emit(
                    "mitigation_release", flows=-delta,
                    marked=h["mit_marked"],
                    pkt_offset=int(self.stats_.packets))
            self._health_marked = h["mit_marked"]

    def _warm_up(self) -> None:
        """Compile the executable so steady-state timing excludes it."""
        zeros = np.zeros((self.max_batch, self.feature_dim), np.float32)
        if self._stateful:
            # all-invalid warm-up batch: compiles without touching
            # registers; adopt the returned state (identical values) so
            # donated input buffers are never reused
            out = self.pipeline(self.state, zeros,
                                np.zeros(self.max_batch, np.int32))
            self.state = out[0]
        else:
            np.asarray(self.pipeline(zeros))

    # ------------------------------------------------------------ intake

    def submit(self, packets: np.ndarray) -> None:
        """Enqueue a [n, F] chunk of packets (any n >= 1).

        The chunk is copied: callers typically reuse one read buffer per
        chunk, and the queue may hold rows across several flushes."""
        with self._annotate("serve.submit"):
            pkts = np.array(packets, np.float32)   # always copies
        if pkts.ndim == 1:
            pkts = pkts[None, :]
        if pkts.shape[1] != self.feature_dim:
            raise ValueError(
                f"expected {self.feature_dim} features, got {pkts.shape[1]}"
            )
        self._queue.append(pkts)
        self._pending += len(pkts)

    @property
    def pending(self) -> int:
        return self._pending

    @property
    def in_flight(self) -> int:
        """Batches dispatched but not yet materialized."""
        return len(self._inflight)

    # ----------------------------------------------------------- serving

    def _take(self, n: int) -> np.ndarray:
        """Pop exactly n rows off the queue head (views where possible).

        When a split leaves only a small residual of a large parent chunk
        on the queue, the residual is copied: a view would retain the
        whole parent buffer for as long as the rows sit queued."""
        taken, got = [], 0
        while got < n:
            head = self._queue[0]
            need = n - got
            if len(head) <= need:
                taken.append(self._queue.popleft())
                got += len(head)
            else:
                taken.append(head[:need])
                rest = head[need:]
                if len(rest) * 4 < len(head):   # retained <25% of parent
                    rest = rest.copy()
                self._queue[0] = rest
                got = n
        self._pending -= n
        return taken[0] if len(taken) == 1 else np.concatenate(taken, 0)

    def _requeue_front(self, rows: np.ndarray) -> None:
        """Push rows back to the queue head (sharded overflow path)."""
        self._queue.appendleft(rows)
        self._pending += len(rows)

    def _next_staging(self) -> tuple[np.ndarray, np.ndarray]:
        buf = self._staging[self._staging_i]
        valid = self._valid_staging[self._staging_i]
        self._staging_i = (self._staging_i + 1) % len(self._staging)
        return buf, valid

    def _dispatch_batch(self, n: int) -> int:
        """Stage + launch the next ``n`` queued rows as one batch; returns
        the rows dispatched.  Spans: ``serve.stage`` (swap boundary,
        queue take, staging copy, valid mask), ``serve.dispatch`` (the
        ``dispatch_s`` interval; a stateful pipeline's ``dispatch``
        opens ``serve.put`` inside it for the rows and mask to the
        device, the rest is the launch), then ``serve.record``."""
        k = self.stats_.batches
        pad = self.max_batch - n
        with self._annotate("serve.stage", batch=k):
            self._maybe_install_swap()     # dispatch-ring boundary
            rows = self._take(n)
            buf, valid = self._next_staging()
            buf[:n] = rows
            if pad:
                buf[n:] = 0.0
            if self._stateful:
                valid[:n] = 1
                if pad:
                    valid[n:] = 0
        with self._span("serve.dispatch", batch=k) as d:
            if self._stateful:
                self.state, out = self._dispatch_fn(self.state, buf, valid)
            else:
                out = self._dispatch_fn(buf)
        if not self._inflight:
            self._mark = d.t0              # new active-serving span
        # a numpy result was computed synchronously inside the dispatch
        # call; anything else is a lazy device handle fetched later
        ready = d.t1 if isinstance(out, np.ndarray) else None
        self.stats_.dispatch_s += d.t1 - d.t0
        self.stats_.count_batch(self._backend_key, n, pad)
        if self._tel is not None:
            with self._annotate("serve.record", batch=k):
                self._record_dispatch(rows, n, pad, d.t1 - d.t0)
        self._enqueue(_InFlight(n, out, d.t0, ready, k))
        return n

    def _enqueue(self, f: _InFlight) -> None:
        """Append a dispatched batch to the in-flight FIFO, first starting
        the copy of its verdicts to the host when the result is a lazy
        device array: the fetch then finds the copy done, or under way,
        instead of starting it and waiting out its latency."""
        start_copy = getattr(f.out, "copy_to_host_async", None)
        if start_copy is not None:
            start_copy()
            self.stats_.early_copies += 1
            if self._tel is not None:
                self._tm["early_copies"].inc(1)
        self._inflight.append(f)

    # ---------------------------------------------------------- hot swap

    def swap(self, pipeline, *, backend: str | None = None) -> None:
        """Install ``pipeline`` at the next dispatch-ring boundary.

        Zero-downtime model replacement (the hot-swap contract,
        docs/pipeline_ir.md#hot-swap-contract): the new pipeline is
        compiled and warmed HERE, off the serving hot path — typically on
        a background retrain thread — then parked; the serving loop
        installs it between two dispatches, so in-flight batches finish
        on the old model, no batch is dropped or reordered, and from the
        recorded boundary (``stats()["swap_pkt_offsets"]``) on every
        verdict comes from the new model.

        Stateful engines carry the live ``FlowState`` across the swap
        bit-identically when the new pipeline shares the old
        ``FlowStateSpec``; a changed spec migrates the table through the
        documented re-key path (``flowstate.registers.migrate_state``).
        Swapping between stateless and stateful pipelines is an error —
        that is a different engine, not a new model."""
        t_req = time.perf_counter()
        if backend is not None:
            pipeline = _rebind_backend(pipeline, backend)
        stateful = hasattr(pipeline, "init_state")
        if stateful != self._stateful:
            raise ValueError(
                "hot swap cannot change statefulness: engine is "
                f"{'stateful' if self._stateful else 'stateless'}, new "
                f"pipeline is {'stateful' if stateful else 'stateless'}"
            )
        actual = _pipeline_backend(pipeline)
        with self._span("serve.swap_prepare", cat="swap", backend=actual):
            payload = self._prepare_swap(pipeline)
        if self._tel is not None:
            reason = getattr(pipeline, "fallback_reason", None)
            if reason or (backend == "pallas"
                          and actual in ("interpret", "mixed")):
                ev = {"requested": backend or "pallas", "actual": actual,
                      "engine": type(self).__name__, "during": "swap"}
                if reason:
                    ev["reason"] = reason
                self._tel.journal.emit("backend_fallback", **ev)
        with self._swap_lock:
            self._pending_swap = (payload, t_req)

    @property
    def swap_pending(self) -> bool:
        return self._pending_swap is not None

    def _prepare_swap(self, pipeline) -> dict:
        """Compile + warm the new pipeline on throwaway inputs so the
        install itself is O(1) — never a recompile on the serving path."""
        zeros = np.zeros((self.max_batch, self.feature_dim), np.float32)
        if self._stateful:
            # throwaway table: the live state is NOT touched until install
            out = pipeline(pipeline.init_state(), zeros,
                           np.zeros(self.max_batch, np.int32))
            np.asarray(out[1])
        else:
            np.asarray(pipeline(zeros))
        return {"pipeline": pipeline}

    def _maybe_install_swap(self) -> None:
        # lock-free fast path: this runs at EVERY ring boundary, and the
        # single attribute read is atomic — the lock is only needed to
        # claim an actually-parked swap
        if self._pending_swap is None:
            return
        with self._swap_lock:
            pending, self._pending_swap = self._pending_swap, None
        if pending is None:
            return
        payload, t_req = pending
        old_backend = self.backend
        to = _pipeline_backend(payload["pipeline"])
        with self._span("serve.swap_install", cat="swap",
                        **{"from": old_backend, "to": to}) as sp:
            self._install_swap(payload)
        lat_s = sp.t1 - t_req
        self.stats_.record_swap(lat_s)
        if self._tel is not None:
            self._tm["swaps"].inc(1)
            self._tm["swap_lat_ms"].observe(lat_s * 1e3)
            self._tel.journal.emit(
                "hot_swap", lat_ms=round(lat_s * 1e3, 3),
                pkt_offset=int(self.stats_.packets),
                old_backend=old_backend, new_backend=self.backend,
                engine=type(self).__name__)

    def _note_lowering(self, pipeline) -> None:
        """Take the fused launch's classifier counters into ``stats()``
        and its lowering into the ring as a one-off ``serve.lower`` span
        (on the pipeline's own stamps) with the counters as arguments."""
        counters = getattr(pipeline, "fused_suffix", None) or {}
        self.stats_.fused_suffix = dict(counters)
        stamps = getattr(pipeline, "lowered_at", None)
        if counters and stamps and self._tel is not None:
            self._tel.tracer.record("serve.lower", *stamps, cat="compile",
                                    args=dict(counters))

    def _install_swap(self, payload: dict) -> None:
        pipeline = payload["pipeline"]
        self._carry_state(pipeline)
        self._note_lowering(pipeline)
        self.pipeline = pipeline
        self.backend = _pipeline_backend(pipeline)
        self._backend_key = _backend_stats_key(pipeline, self.backend)
        self._dispatch_fn = getattr(pipeline, "dispatch", pipeline)
        # segmentation stats must track the NEW pipeline's FlowKey/spec
        self._resolve_flow_telemetry(pipeline)

    def _carry_state(self, pipeline) -> None:
        """Same spec: registers carry over bit-identically (the live
        arrays are simply kept).  Changed spec: the documented re-key
        migration (see the hot-swap contract).  Pipelines that know their
        own state shape (``StatefulPipeline.adopt_state``) own the whole
        carry — including the mitigation action table, which follows the
        same rules (docs/pipeline_ir.md#mitigation-contract)."""
        if not self._stateful:
            return
        adopt = getattr(pipeline, "adopt_state", None)
        if adopt is not None:
            self.state = adopt(self.state)
            return
        new_spec = getattr(pipeline, "spec", None)
        old_spec = getattr(self.state, "spec", None)
        if new_spec is None or old_spec is None or new_spec == old_spec:
            return
        from repro.flowstate.registers import migrate_state

        self.state = migrate_state(self.state, new_spec)

    def _fetch_one(self) -> np.ndarray:
        """Materialize the oldest in-flight batch (FIFO: arrival order)."""
        f = self._inflight.popleft()
        with self._annotate("serve.fetch", batch=f.batch):
            v = np.asarray(f.out)      # blocks until the host copy is in
            end = f.ready if f.ready is not None else time.perf_counter()
            self.stats_.batch_lat_s.append(end - f.t0)
            if self._mark is not None:
                self.stats_.wall_s += max(0.0, end - self._mark)
                self._mark = max(self._mark, end) if self._inflight else None
            if self._tel is not None:
                self._tm["batch_lat_ms"].observe((end - f.t0) * 1e3)
                self._tel.tracer.record(
                    "serve.batch", f.t0, end,
                    args={"batch": f.batch, "backend": self.backend,
                          "rows": f.n})
            if f.perm is not None:
                out = self._unshard(v, f)
            else:
                out = v[:f.n]
                # a plain-numpy pipeline may return a VIEW of its input —
                # i.e. of a reusable staging buffer the next dispatch will
                # overwrite; copy so returned verdicts can never be
                # corrupted in place (device-array results are fresh
                # buffers and never alias the ring)
                if isinstance(f.out, np.ndarray) and any(
                    np.shares_memory(out, buf) for buf in self._staging
                ):
                    out = out.copy()
            self._count_mitigated(out)
        return out

    def _count_mitigated(self, verdicts: np.ndarray) -> None:
        """Count action-table drops (MITIGATED sentinels) in a fetched
        batch — only mitigated pipelines can emit them."""
        if self._tel is None or getattr(self.state, "mit_spec", None) is None:
            return
        dropped = int(np.sum(verdicts < 0))
        if dropped:
            self._tm["mitigated"].inc(dropped)

    def _unshard(self, v: np.ndarray, f: _InFlight) -> np.ndarray:
        raise NotImplementedError      # ShardedPacketServeEngine only

    def flush(self) -> np.ndarray:
        """Serve everything pending; verdicts come back in arrival order."""
        outs = []
        while self._pending:
            while len(self._inflight) >= self.depth:
                outs.append(self._fetch_one())
            self._dispatch_batch(min(self.max_batch, self._pending))
        while self._inflight:
            outs.append(self._fetch_one())
        # the ring is drained: a boundary — install any pending swap even
        # when no further traffic arrives, so a swap never sits parked
        # past a flush
        self._maybe_install_swap()
        self._scan_flow_health()       # flush-boundary table scan
        if not outs:
            return np.zeros((0,), np.int32)
        return outs[0] if len(outs) == 1 else np.concatenate(outs, 0)

    def serve_stream(self, chunks: Iterable[np.ndarray]
                     ) -> Iterator[np.ndarray]:
        """Pull-through mode: yield verdicts per full micro-batch as the
        input stream arrives (tail flushed at end).  With ``depth>1`` the
        next micro-batch dispatches before the previous result is
        consumed, so the device never sits idle between yields."""
        for chunk in chunks:
            self.submit(chunk)
            while self._pending >= self.max_batch:
                while len(self._inflight) >= self.depth:
                    yield self._fetch_one()
                self._dispatch_batch(self.max_batch)
        if self._pending or self._inflight:
            tail = self.flush()
            if len(tail):
                yield tail

    def stats(self) -> dict:
        return self.stats_.as_dict()
