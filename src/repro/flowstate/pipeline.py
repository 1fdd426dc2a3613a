"""Stateful pipeline compilation: flow registers + classifier in ONE jit.

``StatefulPipeline`` is the serving artifact for a stage list that starts
with the stateful prefix ``[FlowKey, RegisterUpdate]`` (core.stageir) —
or, in the multi-table DAG form, SEVERAL such groups feeding one
classifier: per fixed-shape batch it derives flow keys, updates the
register file(s), reads each packet's post-update feature row(s), and
runs the stateless classifier suffix — all inside one jitted step, so
steady-state serving never re-traces and the register state threads
through as explicit arrays (no Python-side mutation).

A trailing ``Mitigate`` stage (docs/pipeline_ir.md#mitigation-contract)
closes the loop: the classifier's verdicts feed a per-flow action table
keyed by the same flow key (the FIRST table's key in the multi-table
form), and marked flows' packets come back as ``mitigation.MITIGATED``
instead of a verdict.  The action table threads through the SAME jitted
step as two extra state arrays, so mitigation inherits every serving
guarantee — arrival order, overlap safety, hot-swap state carry.

Backend selection mirrors the stateless contract
(docs/pipeline_ir.md#flow-state-contract):

  * under ``backend="pallas"`` the WHOLE pipeline — every table, the
    classifier (MLP / MAT / centroid / forest suffixes) AND the
    mitigation action table — lowers onto the single-launch fused kernel
    (kernels/fused_flow) when it matches the fused envelope, reported as
    ``"pallas-fused-flow"``; when it declines, ``fallback_reason`` keeps
    the honest reason string (surfaced by the engines' stats/journal);
  * otherwise each PREFIX lowers onto the flow-update Pallas kernel
    (kernels/flow_update) when its table fits the kernel envelope, else
    the jnp scan reference — bit-identical either way;
  * and the SUFFIX lowers through
    ``core.pallas_backend.lower_stages_pallas`` under the existing Pallas
    lowering contract, else the jitted stage walk.

``backend`` reports what actually serves: ``"pallas-fused-flow"`` for
the single launch (mitigated or not), ``"pallas"`` when the split parts
all lowered, ``"interpret"`` when none did, ``"mixed"`` otherwise —
never the engine that was merely requested.  On the split path the
mitigation scan runs as shared jnp (``lower_mitigation`` serves
``"interpret"``), so a split-path mitigated pipeline whose detection
half runs on Pallas reports ``"mixed"``.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import stageir
from repro.flowstate.registers import (
    FlowState,
    FlowStateSpec,
    MultiFlowState,
    init_state,
    migrate_state,
)
from repro.telemetry.trace import annotate


class StatefulPipeline:
    """Compiled stateful serving pipeline.

    Callable as ``state', verdicts = pipe(state, X, valid=None)`` where
    ``X`` is a [B, F] packet batch and ``valid`` masks ragged-batch
    padding rows (masked rows never touch the register file and their
    verdicts are meaningless — the engine slices them off).  Rows are
    applied in arrival order; see the flow-state contract for the
    eviction/ordering guarantees."""

    def __init__(self, stages: list[stageir.Stage], *,
                 backend: str = "interpret", fuse: bool = True):
        if backend not in stageir.EXEC_BACKENDS:
            raise KeyError(f"backend must be one of {stageir.EXEC_BACKENDS}")
        from repro.core import pallas_backend

        self.stages = list(stages)
        self.requested_backend = backend
        self.fuse = bool(fuse)
        rest, mit = stageir.split_mitigation(self.stages)
        n_fk = sum(isinstance(s, stageir.FlowKey) for s in rest)
        if n_fk > 1:
            groups, suffix = stageir.split_stateful_multi(rest)
            fused_prefix = groups
        else:
            prefix, suffix = stageir.split_stateful(rest)
            groups = [(prefix[0], prefix[1], None)]
            fused_prefix = prefix
        self.groups = groups
        self.n_tables = len(groups)
        self.specs: tuple = tuple(g[1].spec for g in groups)
        self.spec: FlowStateSpec = self.specs[0]
        self.mitigation = mit.spec if mit is not None else None
        self.feature_dim = None          # any F the key/update cols allow

        run_suffix = (stageir.fuse_pipeline_stages(suffix) if fuse
                      else list(suffix))

        # single-launch form first: the whole pipeline — every table, the
        # classifier AND the action table — as ONE Pallas kernel
        # (kernels/fused_flow) when backend="pallas" and the post-peephole
        # shape matches the fused envelope.  Bit-identical to the split
        # composition below by the flow-state + mitigation contracts,
        # reported honestly as "pallas-fused-flow"; on decline,
        # `fallback_reason` keeps the honest reason string.
        step = None
        self.fused = False
        self.fallback_reason: str | None = None
        # the fused launch's classifier as counters
        # (``pallas_backend.suffix_counters``) and the perf_counter stamps
        # of its lowering: the engines report both
        self.fused_suffix: dict | None = None
        self.lowered_at: tuple | None = None
        if backend == "pallas" and fuse:
            t0 = time.perf_counter()
            step = pallas_backend.lower_stateful_fused(
                fused_prefix, run_suffix, mit)
            self.lowered_at = (t0, time.perf_counter())
            if step is None:
                self.fallback_reason = \
                    pallas_backend.fused_flow_decline_reason(
                        fused_prefix, run_suffix, mit)
        if step is not None:
            self.fused = True
            self.fused_suffix = step.suffix_counters
            self.flow_backend = self.classifier_backend = "pallas"
            self.mitigation_backend = ("pallas" if mit is not None
                                       else None)
        else:
            flows = [
                pallas_backend.lower_stateful([fk, ru], backend)
                for fk, ru, _ in groups
            ]
            flow_kinds = {kind for _, kind in flows}
            self.flow_backend = (flow_kinds.pop() if len(flow_kinds) == 1
                                 else "mixed")
            suffix_fn = None
            if backend == "pallas" and run_suffix:
                suffix_fn = pallas_backend.lower_stages_pallas(run_suffix)
            self.classifier_backend = ("pallas" if suffix_fn is not None
                                       else "interpret")
            if suffix_fn is None:
                def suffix_fn(feats, _s=run_suffix):
                    return stageir.apply_stages(_s, feats)

            readouts = tuple(g[2] for g in groups)  # WindowStats | None

            def step(*args, _flows=tuple(f for f, _ in flows),
                     _ws=readouts, _cls=suffix_fn):
                x, valid = args[-2], args[-1]
                outs, zs = [], []
                for t, flow in enumerate(_flows):
                    k2, r2, feats = flow(args[2 * t], args[2 * t + 1],
                                         x, valid)
                    outs += [k2, r2]
                    zs.append(_ws[t].apply(feats) if _ws[t] is not None
                              else feats)
                z = zs[0] if len(zs) == 1 else jnp.concatenate(zs, 1)
                return (*outs, _cls(z))

            if mit is not None:
                # split fallback: the action table appends two more state
                # arrays and the verdict rewrite to the very same jitted
                # step — the flow key is re-derived from the packet rows
                # (cheap vectorized FNV), so detection and action tables
                # stay keyed identically
                mit_fn, self.mitigation_backend = \
                    pallas_backend.lower_mitigation(mit)
                base = step

                def step(*args, _base=base, _mit=mit_fn,
                         _fk=groups[0][0]):
                    x, valid = args[-2], args[-1]
                    mkeys, mregs = args[-4], args[-3]
                    out = _base(*args[:-4], x, valid)
                    mkeys, mregs, v = _mit(mkeys, mregs,
                                           _fk.apply_keys(x), out[-1],
                                           valid)
                    return (*out[:-1], mkeys, mregs, v)
            else:
                self.mitigation_backend = None

        # the raw traceable step: what ShardedPacketServeEngine wraps in
        # shard_map over per-device register tables.  Its name is the
        # step's stable name in a profile (``jit_flow_serve_step``),
        # whichever form above built it.
        step.__name__ = step.__qualname__ = "flow_serve_step"
        self.step_fn = step
        # donate the register buffers on accelerator backends: the update
        # rewrites the whole table every step, so the input buffers are
        # dead the moment the step is dispatched — steady-state serving
        # then allocates no new table per batch.  (No-op on CPU, where XLA
        # does not support donation; callers must treat a dispatched-into
        # FlowState as consumed — the engine always adopts the returned
        # state.)
        donate = (tuple(range(self.n_state_arrays))
                  if jax.default_backend() != "cpu" else ())
        self._step = jax.jit(step, donate_argnums=donate)
        self._ones_valid: dict[int, object] = {}  # per-batch-size cache

    @property
    def n_state_arrays(self) -> int:
        """Leading state arrays of ``step_fn``: (keys, regs) per table
        plus the action table's (mit_keys, mit_regs) when mitigation is
        on — what the sharded engine partitions per device."""
        return 2 * self.n_tables + (2 if self.mitigation is not None else 0)

    @property
    def backend(self) -> str:
        """The engine that actually serves, after any fallback:
        ``"pallas-fused-flow"`` when the whole pipeline (mitigation
        included) runs as one kernel launch, else ``"pallas"`` /
        ``"interpret"`` / ``"mixed"`` for the split composition.  On the
        split path the interpret-only mitigation scan counts as one of
        the parts — a Pallas detection half plus scan mitigation reports
        ``"mixed"``."""
        kinds = {self.flow_backend, self.classifier_backend}
        if self.mitigation_backend is not None:
            kinds.add(self.mitigation_backend)
        if self.fused and len(kinds) == 1:
            return "pallas-fused-flow"
        if self.fused:
            return "mixed"
        return kinds.pop() if len(kinds) == 1 else "mixed"

    def with_backend(self, backend: str) -> "StatefulPipeline":
        """Recompile for another engine (what PacketServeEngine's
        ``backend=`` uses).  Preserves the ``fuse`` flag — an unfused
        pipeline must not silently come back fused."""
        return StatefulPipeline(self.stages, backend=backend,
                                fuse=self.fuse)

    def init_state(self):
        if self.n_tables > 1:
            bases = [init_state(s) for s in self.specs]
            kl = tuple(b.keys for b in bases)
            rl = tuple(b.regs for b in bases)
            if self.mitigation is None:
                return MultiFlowState(self.specs, kl, rl)
            from repro.flowstate.mitigation import init_mitigation

            mk, mr = init_mitigation(self.mitigation)
            return MultiFlowState(self.specs, kl, rl, self.mitigation,
                                  mk, mr)
        if self.mitigation is None:
            return init_state(self.spec)
        from repro.flowstate.mitigation import (
            MitigatedFlowState,
            init_mitigation,
        )

        base = init_state(self.spec)
        mk, mr = init_mitigation(self.mitigation)
        return MitigatedFlowState(self.spec, base.keys, base.regs,
                                  self.mitigation, mk, mr)

    def _adopt_mitigation(self, state):
        """Action-table half of ``adopt_state`` -> (mit_keys, mit_regs)."""
        from repro.flowstate.mitigation import (
            init_mitigation,
            migrate_mitigation,
        )

        old_mit = getattr(state, "mit_spec", None)
        if old_mit is None:
            return init_mitigation(self.mitigation)
        if old_mit == self.mitigation:
            return state.mit_keys, state.mit_regs
        return migrate_mitigation(state.mit_keys, state.mit_regs,
                                  old_mit, self.mitigation)

    def adopt_state(self, state):
        """Carry another pipeline's live state into THIS pipeline's state
        shape — the hot-swap install path (both engines call this).

        Detection table(s): same spec carries the arrays bit-identically;
        a changed spec migrates through the documented re-key path
        (``registers.migrate_state``).  Action table: same mitigation
        spec carries bit-identically (marked flows stay marked across the
        swap); a changed spec re-keys (``mitigation.migrate_mitigation``);
        swapping mitigation IN starts an empty table; swapping it OUT
        drops the table (the engine stops enforcing).  Swapping between a
        single-table and a multi-table pipeline (or changing the table
        count) starts the detection tables fresh — there is no defined
        correspondence between the table sets — while the action table
        still carries by the rules above."""
        if getattr(state, "spec", None) is None:
            return state                 # opaque state: engine's problem
        if self.n_tables > 1:
            old_specs = getattr(state, "specs", None)
            kl, rl = [], []
            if old_specs is not None and len(old_specs) == self.n_tables:
                for t, spec in enumerate(self.specs):
                    if old_specs[t] == spec:
                        kl.append(state.keys_list[t])
                        rl.append(state.regs_list[t])
                    else:
                        m = migrate_state(
                            FlowState(old_specs[t], state.keys_list[t],
                                      state.regs_list[t]), spec)
                        kl.append(m.keys)
                        rl.append(m.regs)
            else:
                for spec in self.specs:   # table-count change: fresh start
                    b = init_state(spec)
                    kl.append(b.keys)
                    rl.append(b.regs)
            if self.mitigation is None:
                return MultiFlowState(self.specs, tuple(kl), tuple(rl))
            mk, mr = self._adopt_mitigation(state)
            return MultiFlowState(self.specs, tuple(kl), tuple(rl),
                                  self.mitigation, mk, mr)
        if getattr(state, "specs", None) is not None \
                and len(state.specs) > 1:
            base = init_state(self.spec)  # multi -> single: fresh start
            keys, regs = base.keys, base.regs
        elif state.spec == self.spec:
            keys, regs = state.keys, state.regs
        else:
            m = migrate_state(FlowState(state.spec, state.keys, state.regs),
                              self.spec)
            keys, regs = m.keys, m.regs
        if self.mitigation is None:
            return FlowState(self.spec, keys, regs)
        from repro.flowstate.mitigation import MitigatedFlowState

        mk, mr = self._adopt_mitigation(state)
        return MitigatedFlowState(self.spec, keys, regs, self.mitigation,
                                  mk, mr)

    def _state_arrays(self, state) -> list:
        if self.n_tables > 1:
            arrs = []
            for k, r in zip(state.keys_list, state.regs_list):
                arrs += [k, r]
        else:
            arrs = [state.keys, state.regs]
        if self.mitigation is not None:
            arrs += [state.mit_keys, state.mit_regs]
        return arrs

    def _wrap_state(self, outs):
        """Step outputs (state arrays ++ verdicts) -> (state, verdicts)."""
        nt = self.n_tables
        if nt > 1:
            kl = tuple(outs[2 * t] for t in range(nt))
            rl = tuple(outs[2 * t + 1] for t in range(nt))
            if self.mitigation is None:
                return MultiFlowState(self.specs, kl, rl), outs[-1]
            return MultiFlowState(self.specs, kl, rl, self.mitigation,
                                  outs[2 * nt], outs[2 * nt + 1]), outs[-1]
        if self.mitigation is None:
            return FlowState(self.spec, outs[0], outs[1]), outs[-1]
        from repro.flowstate.mitigation import MitigatedFlowState

        return (MitigatedFlowState(self.spec, outs[0], outs[1],
                                   self.mitigation, outs[2], outs[3]),
                outs[-1])

    def dispatch(self, state, X, valid=None):
        """Launch one step WITHOUT forcing the device->host copy: returns
        ``(state', verdict_device_array)``.  The async serving path
        (PacketServeEngine depth>1) chains dispatches through the returned
        state — the state dependency sequentializes in-flight batches —
        and materializes verdicts lazily at flush time.  The copy of rows
        and mask to the device is named ``serve.put`` in a profile."""
        with annotate("serve.put"):
            X = jnp.asarray(X, jnp.float32)
            if valid is None:
                B = int(X.shape[0])
                valid = self._ones_valid.get(B)
                if valid is None:       # device-resident, reused every step
                    valid = self._ones_valid.setdefault(
                        B, jnp.ones((B,), jnp.int32))
            valid = jnp.asarray(valid, jnp.int32)
        outs = self._step(*self._state_arrays(state), X, valid)
        return self._wrap_state(outs)

    def __call__(self, state, X, valid=None):
        state, verdicts = self.dispatch(state, X, valid)
        return state, np.asarray(verdicts)

    def __repr__(self):
        mit = (f", mitigation={self.mitigation.mode!r}"
               if self.mitigation is not None else "")
        tabs = f", tables={self.n_tables}" if self.n_tables > 1 else ""
        return (f"StatefulPipeline(slots={self.spec.n_slots}, "
                f"width={self.spec.width}, backend={self.backend!r}, "
                f"flow={self.flow_backend!r}, "
                f"classifier={self.classifier_backend!r}{mit}{tabs})")
