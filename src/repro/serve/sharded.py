"""Multi-device packet serving: shard each micro-batch across devices.

``ShardedPacketServeEngine`` extends ``PacketServeEngine`` with a
``jax.shard_map`` serving step over a 1-D ``("data",)`` mesh:

* **Stateless pipelines** split every fixed-shape micro-batch evenly —
  device *d* serves the contiguous row slice ``[d*b, (d+1)*b)`` — so
  verdict order is trivially arrival order and the per-device program is
  exactly the single-device executable (Pallas kernels included).

* **Stateful pipelines** keep one *private register table per device* and
  route packets by flow key (key-partitioned hashing: a second
  multiplicative mix of the FNV flow key, independent of the in-table
  slot hash) so every flow always lands on the same device's table.
  Rows are routed host-side in arrival order; a device whose sub-batch
  fills forces the overflow rows back onto the queue head, so per-flow
  update order is preserved exactly.  Verdicts are scattered back to
  arrival positions before they leave the engine.  A mitigated pipeline
  (trailing ``Mitigate`` stage) threads its per-device ACTION tables the
  same way — both tables key on the same flow key, so a flow's detection
  row and action row always live on the same device
  (docs/pipeline_ir.md#mitigation-contract).

* On a **one-device host** the engine degrades to the plain
  ``PacketServeEngine`` serving path (no mesh, no routing) — same
  results, same stats vocabulary (``stats()["shards"] == 1``).

The dispatch-pipeline ``depth`` machinery (overlap, lazy fetch, staging
ring) is inherited unchanged; the sharded step is just a different
launch.  Each batch's rows and mask, and the tables, are placed with the
step's own input sharding (every device receives its sub-batch straight
from the host), so the launch re-slices nothing and copies nothing
between devices.  See docs/pipeline_ir.md#serving-performance-contract.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from repro.serve.packet_engine import (
    PacketServeEngine,
    _CompiledPipeline,
    _InFlight,
    _rebind_backend,
)

# key-partitioned hashing: mix the (already FNV-folded) flow key once more
# with a Knuth multiplicative constant and take high bits, so the shard
# index stays independent of the table's slot index (hash & (S-1)) and a
# skewed low-bit key pattern cannot pile flows onto one device
_SHARD_MIX = np.uint32(0x9E3779B1)


def shard_of_key(keys: np.ndarray, n_shards: int) -> np.ndarray:
    """[B] int32 flow keys -> [B] shard ids in [0, n_shards)."""
    with np.errstate(over="ignore"):
        mixed = keys.astype(np.uint32) * _SHARD_MIX
    return ((mixed >> np.uint32(16)) % np.uint32(n_shards)).astype(np.int64)


def route_prefix(shard_ids: np.ndarray, n_shards: int, capacity: int
                 ) -> tuple[int, list]:
    """Largest arrival-order prefix that fits per-shard ``capacity``.

    Returns ``(m, perm)``: the first ``m`` rows fit, and ``perm[s]`` lists
    the original row indices (ascending = arrival order) that shard ``s``
    serves.  Row ``m`` is the first whose shard is already full — rows
    behind it must wait so per-flow order never inverts."""
    ranks = np.empty(len(shard_ids), np.int64)
    for s in range(n_shards):
        mask = shard_ids == s
        ranks[mask] = np.arange(int(mask.sum()))
    over = ranks >= capacity
    m = int(np.argmax(over)) if over.any() else len(shard_ids)
    ids = shard_ids[:m]
    perm = [np.flatnonzero(ids == s) for s in range(n_shards)]
    return m, perm


@dataclasses.dataclass
class ShardedFlowState:
    """Per-device register tables, stacked on a leading shard axis.

    Mitigated pipelines add the per-device ACTION tables (``mit_*``
    fields, None otherwise) — the same state vocabulary as
    ``flowstate.MitigatedFlowState``, one table per shard."""

    spec: object
    keys: object                   # [D, S] int32
    regs: object                   # [D, S, W] f32
    mit_spec: object = None        # flowstate.MitigationSpec | None
    mit_keys: object = None        # [D, Sm] int32
    mit_regs: object = None        # [D, Sm, 2] f32

    @property
    def n_shards(self) -> int:
        return int(np.shape(self.keys)[0])

    @property
    def occupied(self) -> int:
        return int(np.sum(np.asarray(self.keys) >= 0))

    @property
    def mitigated_flows(self) -> int:
        """Marked action-table slots across every shard."""
        if self.mit_spec is None:
            return 0
        mk = np.asarray(self.mit_keys)
        hits = np.asarray(self.mit_regs)[..., 0]
        return int(np.sum((mk >= 0) & (hits >= self.mit_spec.threshold)))

    def arrays(self) -> tuple:
        """The stacked state arrays, in ``step_fn`` argument order."""
        if self.mit_spec is None:
            return (self.keys, self.regs)
        return (self.keys, self.regs, self.mit_keys, self.mit_regs)

    def with_arrays(self, arrays: tuple) -> "ShardedFlowState":
        """Rebuild around fresh stacked arrays (one serving step's out)."""
        if self.mit_spec is None:
            return ShardedFlowState(self.spec, *arrays)
        return ShardedFlowState(self.spec, arrays[0], arrays[1],
                                self.mit_spec, arrays[2], arrays[3])


class ShardedPacketServeEngine(PacketServeEngine):
    """``PacketServeEngine`` that serves each micro-batch across devices.

    ``devices`` defaults to ``jax.devices()``; ``max_batch`` is rounded up
    to a multiple of the device count (the per-device sub-batch is
    ``max_batch // n_shards``).  ``min_shards`` is the degradation
    threshold: with fewer devices the engine serves exactly like the base
    class (tests pass ``min_shards=1`` to exercise the sharded step on a
    one-device host).  Pipelines with no traceable program (bare numpy
    callables) also degrade — shard_map needs something to trace.

    Stateful serving keeps ``n_shards`` private register tables
    (``ShardedFlowState``); feasibility charges one table per device.
    Cross-flow interleaving ACROSS devices is not defined (each table only
    sees its own flows), but per-flow update order is exactly arrival
    order — the single-table ordering guarantee, per flow."""

    def __init__(self, pipeline, *, feature_dim: int, max_batch: int = 256,
                 backend: str | None = None, state=None, depth: int = 2,
                 devices=None, min_shards: int = 2, telemetry=None):
        import jax

        if backend is not None:
            pipeline = _rebind_backend(pipeline, backend)
        devices = list(devices) if devices is not None else jax.devices()
        self.devices = devices
        n = len(devices)
        traceable = _traceable_fn(pipeline)
        # a multi-table stateful pipeline has no single flow key to
        # partition on — its tables key the same packet differently, so a
        # flow cannot be pinned to one device's tables; degrade to the
        # single-device serving path rather than split state incorrectly
        multi_table = getattr(pipeline, "n_tables", 1) > 1
        self.sharded = (n >= max(1, int(min_shards))
                        and traceable is not None and not multi_table)
        if not self.sharded:
            super().__init__(pipeline, feature_dim=feature_dim,
                             max_batch=max_batch, state=state, depth=depth,
                             telemetry=telemetry)
            return

        self.n_shards = n
        self._sub_batch = -(-int(max_batch) // n)       # ceil
        stateful = hasattr(pipeline, "init_state")
        self._sharding, self._sharded_fn = _build_sharded_step(
            traceable, devices, n_state=_n_state(pipeline) if stateful else 0
        )
        if stateful:
            from repro.core import stageir

            self._flowkey = next(s for s in pipeline.stages
                                 if isinstance(s, stageir.FlowKey))
            if state is None:
                state = _init_sharded_state(pipeline, self._sharding)
        super().__init__(pipeline, feature_dim=feature_dim,
                         max_batch=self._sub_batch * n, state=state,
                         depth=depth, telemetry=telemetry)
        if not self._stateful:
            self._dispatch_fn = self._sharded_fn
        self.stats_.shards = n
        if self._tel is not None:
            self._tel.metrics.gauge(
                "serve_shards", "devices serving").default.set(n)

    # --------------------------------------------------------- overrides

    def _warm_up(self) -> None:
        if not self.sharded:
            return super()._warm_up()
        zeros = np.zeros((self.max_batch, self.feature_dim), np.float32)
        if self._stateful:
            self._gauge_resharded()
            state, out = self._launch_stateful(
                zeros, np.zeros(self.max_batch, np.int32))
            self.state = state
            np.asarray(out)
        else:
            np.asarray(self._sharded_fn(zeros))

    def _dispatch_batch(self, n: int) -> int:
        if not self.sharded or not self._stateful:
            return super()._dispatch_batch(n)
        with self._annotate("serve.stage", batch=self.stats_.batches):
            self._maybe_install_swap()     # dispatch-ring boundary
            rows = self._take(n)
        return self._dispatch_routed(rows)

    def _dispatch_routed(self, rows: np.ndarray) -> int:
        """Stateful sharding: route rows to their flow's device table.

        Spans: ``serve.route`` (flow keys, shard ids, the prefix that
        fits, the overflow pushed back), a second ``serve.stage`` (the
        per-shard scatter into the staging buffer), then the base
        engine's ``serve.dispatch`` (holding ``serve.put``; the rest is
        the launch) and ``serve.record``."""
        k = self.stats_.batches
        annotate = self._annotate
        with annotate("serve.route", batch=k):
            keys = self._flowkey.apply_keys_np(rows)
            shard_ids = shard_of_key(keys, self.n_shards)
            m, perm = route_prefix(shard_ids, self.n_shards, self._sub_batch)
            if m < len(rows):
                if self._tel is not None:
                    self._tm["overflow"].inc(len(rows) - m)
                self._requeue_front(rows[m:].copy())
            rows = rows[:m]

        b = self._sub_batch
        with annotate("serve.stage", batch=k):
            buf, valid = self._next_staging()
            x = buf.reshape(self.n_shards, b, self.feature_dim)
            v = valid.reshape(self.n_shards, b)
            x[:] = 0.0
            v[:] = 0
            for s, idx in enumerate(perm):
                x[s, :len(idx)] = rows[idx]
                v[s, :len(idx)] = 1

        with self._span("serve.dispatch", batch=k) as d:
            self.state, out = self._launch_stateful(buf, valid)
        if not self._inflight:
            self._mark = d.t0
        self.stats_.dispatch_s += d.t1 - d.t0
        self.stats_.count_batch(self._backend_key, m, self.max_batch - m)
        if self._tel is not None:
            with annotate("serve.record", batch=k):
                slots = False          # sampled out unless the tick fires
                if self._seg_tick():
                    # the flow keys are already in hand: fold the shard id
                    # into the slot so same-slot chains on DIFFERENT
                    # devices never merge (each device walks its own table)
                    n_slots = int(self.state.spec.n_slots)
                    slots = (shard_ids[:m] * n_slots
                             + self._hash_slot_np(keys[:m], n_slots))
                self._record_dispatch(rows, m, self.max_batch - m,
                                      d.t1 - d.t0, slots=slots)
        self._enqueue(_InFlight(m, out, d.t0, None, k, perm=perm))
        return m

    def _launch_stateful(self, buf: np.ndarray, valid: np.ndarray):
        """One sharded stateful step over the stacked register tables;
        the copy of rows and mask to the devices is ``serve.put``."""
        with self._annotate("serve.put"):
            x, v = self._place(buf, valid)
        outs = self._sharded_fn(*self.state.arrays(), x, v)
        return self.state.with_arrays(outs[:-1]), outs[-1]

    def _place(self, buf: np.ndarray, valid: np.ndarray) -> tuple:
        """Rows and mask as the step reads them: the host buffers viewed
        as ``[n_shards, sub_batch, ...]`` and put in one batched call, so
        each device receives its own sub-batch straight from the host."""
        import jax

        b = self._sub_batch
        return jax.device_put(
            (buf.reshape(self.n_shards, b, self.feature_dim),
             valid.reshape(self.n_shards, b)), self._sharding)

    def _gauge_resharded(self) -> None:
        """Set ``serve_resharded_step_args``: the step arguments (tables,
        then a batch's rows and mask) whose sharding is not the step's
        input sharding — each one the launch would re-slice or copy
        between devices.  Warm-up and swap install only."""
        if self._tel is None:
            return
        args = (*self.state.arrays(), *self._place(
            np.zeros((self.max_batch, self.feature_dim), np.float32),
            np.zeros(self.max_batch, np.int32)))
        self._tm["resharded"].set(sum(
            not a.sharding.is_equivalent_to(self._sharding, a.ndim)
            for a in args))

    def _unshard(self, v: np.ndarray, f: _InFlight) -> np.ndarray:
        """Scatter per-shard outputs (verdicts, or feature rows when the
        classifier suffix emits vectors) back to arrival positions."""
        out = np.empty((f.n,) + v.shape[2:], v.dtype)
        for s, idx in enumerate(f.perm):
            out[idx] = v[s, :len(idx)]
        return out

    # ---------------------------------------------------------- hot swap

    def _prepare_swap(self, pipeline) -> dict:
        """Build + warm the NEW shard_map step off the serving path.

        The swap must keep the engine sharded: a pipeline shard_map cannot
        trace (a bare callable) is rejected rather than silently degrading
        a multi-device engine to one device mid-stream.  Stateful swaps
        must also keep the flow-key columns — the shard a flow lives on is
        a pure function of its key, so changed key columns would strand
        rows on the wrong device's table (re-key across shards is a
        restart, not a swap — see the hot-swap contract).  Swapping
        mitigation in or out is fine: the step signature grows or loses
        the action-table arrays, and the rebuilt shard_map step matches."""
        if not self.sharded:
            return super()._prepare_swap(pipeline)
        traceable = _traceable_fn(pipeline)
        if traceable is None:
            raise ValueError(
                "cannot hot-swap an untraceable pipeline into a sharded "
                "engine (shard_map needs a traceable program)"
            )
        if getattr(pipeline, "n_tables", 1) > 1:
            raise ValueError(
                "cannot hot-swap a multi-table pipeline into a sharded "
                "engine (flows are key-partitioned on ONE flow key)"
            )
        payload = {"pipeline": pipeline}
        sharding, fn = _build_sharded_step(
            traceable, self.devices,
            n_state=_n_state(pipeline) if self._stateful else 0,
        )
        payload["sharding"], payload["fn"] = sharding, fn
        b = self._sub_batch
        if self._stateful:
            from repro.core import stageir

            flowkey = next(s for s in pipeline.stages
                           if isinstance(s, stageir.FlowKey))
            if tuple(flowkey.key_cols) != tuple(self._flowkey.key_cols):
                raise ValueError(
                    "sharded hot swap must preserve FlowKey.key_cols "
                    f"(flows are key-partitioned across shards): "
                    f"{tuple(self._flowkey.key_cols)} -> "
                    f"{tuple(flowkey.key_cols)}"
                )
            payload["flowkey"] = flowkey
            tmp = _init_sharded_state(pipeline, sharding)
            import jax

            x, v = jax.device_put(
                (np.zeros((self.n_shards, b, self.feature_dim), np.float32),
                 np.zeros((self.n_shards, b), np.int32)), sharding)
            np.asarray(fn(*tmp.arrays(), x, v)[-1])
        else:
            np.asarray(fn(np.zeros((self.max_batch, self.feature_dim),
                                   np.float32)))
        return payload

    def _install_swap(self, payload: dict) -> None:
        if not self.sharded:
            return super()._install_swap(payload)
        super()._install_swap(payload)
        self._sharded_fn = payload["fn"]
        self._sharding = payload["sharding"]
        if self._stateful:
            self._flowkey = payload["flowkey"]
            self._gauge_resharded()
        else:
            self._dispatch_fn = self._sharded_fn

    def _carry_state(self, pipeline) -> None:
        if not (self.sharded and self._stateful):
            return super()._carry_state(pipeline)
        import jax.numpy as jnp

        new_spec = getattr(pipeline, "spec", None)
        if new_spec is None:
            return
        old = self.state
        if new_spec == old.spec:
            keys, regs = old.keys, old.regs
        else:
            from repro.flowstate.registers import FlowState, migrate_state

            ks, rs = [], []
            for d in range(self.n_shards):  # re-key each shard's table
                m = migrate_state(
                    FlowState(old.spec,
                              jnp.asarray(np.asarray(old.keys)[d]),
                              jnp.asarray(np.asarray(old.regs)[d])),
                    new_spec,
                )
                ks.append(np.asarray(m.keys))
                rs.append(np.asarray(m.regs))
            keys = jnp.asarray(np.stack(ks))
            regs = jnp.asarray(np.stack(rs))

        new_mit = getattr(pipeline, "mitigation", None)
        if new_mit is None:
            self.state = ShardedFlowState(new_spec, keys, regs)
            return
        from repro.flowstate.mitigation import migrate_mitigation

        old_mit = old.mit_spec
        if old_mit == new_mit:             # bit-identical carry-over
            mk, mr = old.mit_keys, old.mit_regs
        elif old_mit is None:              # mitigation swapped IN: empty
            from repro.flowstate.mitigation import MIT_WIDTH

            mk = jnp.full((self.n_shards, new_mit.n_slots), -1, jnp.int32)
            mr = jnp.zeros((self.n_shards, new_mit.n_slots, MIT_WIDTH),
                           jnp.float32)
        else:                              # re-key each shard's table
            ks, rs = [], []
            for d in range(self.n_shards):
                k1, r1 = migrate_mitigation(
                    np.asarray(old.mit_keys)[d],
                    np.asarray(old.mit_regs)[d], old_mit, new_mit,
                )
                ks.append(np.asarray(k1))
                rs.append(np.asarray(r1))
            mk = jnp.asarray(np.stack(ks))
            mr = jnp.asarray(np.stack(rs))
        self.state = ShardedFlowState(new_spec, keys, regs, new_mit, mk, mr)


def _n_state(pipeline) -> int:
    """Leading state arrays of the pipeline's traceable step (2 for plain
    flow state; 4 with a mitigation action table)."""
    return int(getattr(pipeline, "n_state_arrays", 2))


def _traceable_fn(pipeline):
    """The jnp program shard_map wraps, or None (degrade to base engine)."""
    from repro.core import stageir

    if hasattr(pipeline, "step_fn"):                 # StatefulPipeline
        return pipeline.step_fn
    if hasattr(pipeline, "fn"):                      # chaining.CompiledDag
        return pipeline.fn
    if isinstance(pipeline, _CompiledPipeline):
        return pipeline._compiled.fn
    if getattr(pipeline, "_compiled", None) is not None:  # codegen.Pipeline
        return pipeline._compiled.fn
    if hasattr(pipeline, "stages"):                  # Pipeline w/ custom run
        return stageir.compile_stages(pipeline.stages).fn
    return None


def _build_sharded_step(traceable, devices, *, n_state: int):
    """jit(shard_map(...)) over a 1-D ("data",) mesh of ``devices``.

    ``n_state`` is the number of leading per-device state arrays the
    traceable step threads (0 = stateless; 2 = flow tables; 4 = flow +
    mitigation action tables) — the step signature is ``(*state, x,
    valid) -> (*state', verdicts)`` with every array sharded on its
    leading axis.  Returns ``(sharding, step)``: ``sharding`` is that
    input sharding, for placing arguments where the step reads them."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(devices), ("data",))
    sharding = NamedSharding(mesh, P("data"))

    if n_state:
        def flow_serve_step(*args):
            # each program sees its shard with the leading axis dropped,
            # and returns it re-added: [1, …]; the name is the step's
            # stable name in a profile, as on one device
            outs = traceable(*(a[0] for a in args))
            return tuple(o[None] for o in outs)

        fn = jax.shard_map(
            flow_serve_step, mesh=mesh,
            in_specs=(P("data"),) * (n_state + 2),
            out_specs=(P("data"),) * (n_state + 1),
            check_vma=False,
        )
        return sharding, jax.jit(fn)

    fn = jax.shard_map(lambda x: traceable(x), mesh=mesh,
                       in_specs=(P("data"),), out_specs=P("data"),
                       check_vma=False)
    jitted = jax.jit(fn)

    def dispatch(buf):
        return jitted(jax.device_put(buf, sharding))

    return sharding, dispatch


def _init_sharded_state(pipeline, sharding) -> ShardedFlowState:
    """Empty per-device tables, each made on its own device by one
    program partitioned with ``sharding`` (nothing is copied there)."""
    import jax
    import jax.numpy as jnp

    n = sharding.mesh.size
    spec = pipeline.spec
    mit = getattr(pipeline, "mitigation", None)

    def empty():
        tables = (jnp.full((n, spec.n_slots), -1, jnp.int32),
                  jnp.zeros((n, spec.n_slots, spec.width), jnp.float32))
        if mit is None:
            return tables
        from repro.flowstate.mitigation import MIT_WIDTH

        return tables + (
            jnp.full((n, mit.n_slots), -1, jnp.int32),
            jnp.zeros((n, mit.n_slots, MIT_WIDTH), jnp.float32))

    tables = jax.jit(empty, out_shardings=sharding)()
    if mit is None:
        return ShardedFlowState(spec, *tables)
    return ShardedFlowState(spec, *tables[:2], mit, *tables[2:])
