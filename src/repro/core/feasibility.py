"""Feasibility oracle + per-platform resource models (paper §3.2.2, §3.3).

The paper tests every BO-suggested model against (a) the physical resources
of the target (CUs/MUs on Taurus, MATs on Tofino, LUT/FF/BRAM on FPGA) and
(b) network performance constraints (throughput, latency), using a
compiler/simulator in the loop (SARA, P4 Studio, Vivado).  None of those
toolchains exist here, so each platform implements an *analytic* resource
model calibrated to the magnitudes the paper reports (Table 2/5), plus — for
the TPU platform — the real XLA compiler in the loop (jit + cost_analysis),
which is this repo's faithful analogue of "compile in the loop".

The oracle stays a black box to the BO: config in, verdict out (§3.2.3).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

# ------------------------------------------------------------------ report


@dataclasses.dataclass
class FeasibilityReport:
    feasible: bool
    reasons: list[str]                 # why infeasible (empty if feasible)
    resources: dict[str, float]        # platform-specific usage
    latency_ns: float
    throughput_pps: float              # packets/second the mapping sustains

    def merge(self, other: "FeasibilityReport") -> "FeasibilityReport":
        """Co-residency on one target: resources add, latency adds (chain),
        throughput is the min (paper §3.2.1 consistency rule)."""
        res = dict(self.resources)
        for k, v in other.resources.items():
            res[k] = res.get(k, 0) + v
        return FeasibilityReport(
            feasible=self.feasible and other.feasible,
            reasons=self.reasons + other.reasons,
            resources=res,
            latency_ns=self.latency_ns + other.latency_ns,
            throughput_pps=min(self.throughput_pps, other.throughput_pps),
        )


# ---------------------------------------------------------------- topology
#
# All shape/parameter accounting is read off the stage IR: a topology is
# lowered to shape-only StageSpecs (core.stageir.lower_topology) and every
# platform model below consumes stage metadata instead of re-deriving
# layer shapes per backend.


def _dense_specs(algorithm: str, topology: dict):
    from repro.core.stageir import lower_topology

    return lower_topology(algorithm, topology, form="dense")


def _mat_specs(algorithm: str, topology: dict):
    from repro.core.stageir import lower_topology

    return lower_topology(algorithm, topology, form="mat")


def dnn_layers(topology: dict) -> list[tuple[int, int]]:
    """(n_in, n_out) per dense layer, via the stage IR."""
    from repro.core.stageir import spec_layers

    return spec_layers(_dense_specs("dnn", topology))


def topology_params(algorithm: str, topology: dict) -> int:
    from repro.core.stageir import spec_params

    return spec_params(_dense_specs(algorithm, topology))


# ------------------------------------------------------------------ Taurus
#
# Plasticine-style grid of Compute Units (VEC-lane SIMD MAC pipes) and
# Memory Units (small SRAM banks).  Constants calibrated so the paper's
# Table-2 models land at the reported scale (203-param DNN ~ 24 CU / 48 MU).


@dataclasses.dataclass
class TaurusModel:
    rows: int = 16
    cols: int = 16
    vec: int = 8              # MAC lanes per CU
    mu_words: int = 6         # effective words per MU allocation unit
    clock_ghz: float = 1.0    # pipeline clock
    max_ii: int = 8           # max initiation interval the mapper will try

    @property
    def total_cu(self) -> int:
        return self.rows * self.cols

    @property
    def total_mu(self) -> int:
        return self.rows * self.cols

    def _layer_costs(self, layers: list[tuple[int, int]], ii: int):
        # NB: estimate_batch vectorizes these exact formulas — keep the two
        # in lockstep (tests/test_dse_parallel.py pins check == check_batch)
        cus = mus = 0
        stages = 0
        for n_in, n_out in layers:
            macs = n_in * n_out
            cus += max(1, math.ceil(macs / (self.vec * ii)))
            words = macs + n_out + 2 * n_out  # weights + bias + dbl-buffered act
            mus += max(1, math.ceil(words / self.mu_words))
            stages += 1 + math.ceil(math.log2(max(n_in, 2)))  # map + reduce tree
        return cus, mus, stages

    def estimate(self, algorithm: str, topology: dict) -> dict:
        """-> {cu, mu, latency_ns, throughput_pps(ii=1..), ii_options}."""
        from repro.core.stageir import spec_layers

        specs = _dense_specs(algorithm, topology)
        if algorithm == "tree":
            # comparator chain: ~1 CU per 2 nodes, 1 MU per 4 nodes
            tree = specs[0]
            n = tree.params
            depth = tree.extra[0]
            return {
                "options": [{
                    "ii": 1,
                    "cu": max(1, n // 2),
                    "mu": max(1, n // 4),
                    "latency_ns": depth / self.clock_ghz,
                    "throughput_pps": self.clock_ghz * 1e9,
                }]
            }
        # every compute stage (dense layer / centroid table) maps to a
        # map x reduce-tree template occupying CUs at the chosen II
        layers = spec_layers(specs)

        options = []
        for ii in range(1, self.max_ii + 1):
            cu, mu, stages = self._layer_costs(layers, ii)
            options.append({
                "ii": ii,
                "cu": cu,
                "mu": mu,
                "latency_ns": stages / self.clock_ghz,
                "throughput_pps": self.clock_ghz * 1e9 / ii,
            })
        return {"options": options}

    def estimate_batch(self, algorithm: str, topologies: list[dict]
                       ) -> list[dict]:
        """``estimate`` for a whole candidate batch in one numpy pass.

        Every topology is lowered to stage specs once; the per-layer
        CU/MU/stage costs for ALL candidates and ALL initiation intervals
        are then computed on padded [B, L] arrays (padding masked out, so a
        phantom layer never charges the max(1, ...) floor).  Exactly
        equivalent to mapping ``estimate`` (tested), just without the
        per-candidate Python re-derivation.
        """
        from repro.core.stageir import spec_layers

        if algorithm == "tree" or not topologies:
            return [self.estimate(algorithm, t) for t in topologies]
        import numpy as np

        layer_lists = [
            spec_layers(_dense_specs(algorithm, t)) for t in topologies
        ]
        B = len(layer_lists)
        L = max(len(ls) for ls in layer_lists)
        n_in = np.zeros((B, L), np.int64)
        n_out = np.zeros((B, L), np.int64)
        mask = np.zeros((B, L), bool)
        for b, ls in enumerate(layer_lists):
            for i, (fi, fo) in enumerate(ls):
                n_in[b, i], n_out[b, i], mask[b, i] = fi, fo, True
        macs = n_in * n_out
        words = macs + 3 * n_out          # weights + bias + dbl-buffered act
        stages = np.where(
            mask,
            1 + np.ceil(np.log2(np.maximum(n_in, 2))).astype(np.int64),
            0,
        ).sum(1)
        out: list[dict] = [{"options": []} for _ in range(B)]
        for ii in range(1, self.max_ii + 1):
            cus = np.where(
                mask, np.maximum(1, -(-macs // (self.vec * ii))), 0
            ).sum(1)
            mus = np.where(
                mask, np.maximum(1, -(-words // self.mu_words)), 0
            ).sum(1)
            for b in range(B):
                out[b]["options"].append({
                    "ii": ii,
                    "cu": int(cus[b]),
                    "mu": int(mus[b]),
                    "latency_ns": int(stages[b]) / self.clock_ghz,
                    "throughput_pps": self.clock_ghz * 1e9 / ii,
                })
        return out


# ----------------------------------------------------------------- MAT/PISA
#
# IIsy-style mapping rules (paper §4, §5.2.2):
#   KMeans:  one MAT per cluster
#   SVM:     one MAT per feature
#   Tree:    one MAT per tree level
#   LogReg:  one MAT per feature (per-feature LUT of partial scores)
#   DNN:     N2Net-style, ~12 MATs per layer [86]


@dataclasses.dataclass
class MATModel:
    num_tables: int = 12
    stage_ns: float = 25.0          # per-MAT pipeline latency
    line_rate_pps: float = 1e9      # Tofino line rate is fixed by the ASIC
    dnn_mats_per_layer: int = 12
    register_bytes: int = 4 * 2**20  # stateful register SRAM per pipeline

    def mats_for(self, algorithm: str, topology: dict) -> int:
        """Table count read off the MAT-form stage specs (IIsy rules)."""
        specs = _mat_specs(algorithm, topology)
        if algorithm == "kmeans":
            # one MAT per cluster: the LUT stage's output arity
            return next(s for s in specs if s.kind == "lut_gather").n_out
        if algorithm in ("svm", "logreg"):
            # one per-feature score table
            return next(s for s in specs if s.kind == "lut_gather").n_in
        if algorithm == "tree":
            # one MAT per tree level
            return specs[0].extra[0]
        if algorithm == "dnn":
            # N2Net-style folding: ~12 MATs per dense layer
            n_dense = sum(1 for s in specs if s.kind == "dense")
            return self.dnn_mats_per_layer * n_dense
        raise KeyError(algorithm)


# -------------------------------------------------------------------- FPGA
#
# P4-SDNet / Alveo U250-scale linear model: LUTs dominate (they hold model
# parameters [Table 5]), FFs pipeline them, BRAM holds feature buffers.


@dataclasses.dataclass
class FPGAModel:
    total_luts: int = 1_728_000     # Alveo U250
    total_ffs: int = 3_456_000
    total_bram: int = 2_688
    luts_per_param: float = 55.0    # calibrated to Table 5 deltas
    ffs_per_param: float = 25.0
    base_bram: int = 112            # loopback shell (4.15% of U250)
    clock_mhz: float = 322.0        # CMAC-domain clock

    def estimate(self, algorithm: str, topology: dict) -> dict:
        from repro.core.stageir import spec_layers, spec_params

        specs = _dense_specs(algorithm, topology)
        params = spec_params(specs)
        depth = (
            len(spec_layers(specs)) * 6
            if algorithm in ("dnn", "logreg") else 8
        )
        return {
            "luts": int(params * self.luts_per_param),
            "ffs": int(params * self.ffs_per_param),
            "bram": self.base_bram,
            "latency_ns": depth * 1e3 / self.clock_mhz,
            "throughput_pps": self.clock_mhz * 1e6,  # 1 pkt/clk, line-limited
        }


# --------------------------------------------------------------------- TPU
#
# Beyond-paper target: a TPU core serving the fused-MLP Pallas pipeline
# (kernels/fused_mlp).  Feasibility = VMEM fit; performance = 3-term
# roofline over the padded kernel shapes.  ``xla_oracle=True`` additionally
# jit-compiles the generated pipeline and reads cost_analysis() — the
# literal "compiler in the loop" of the paper, with XLA playing SARA.


@dataclasses.dataclass
class TPUModel:
    vmem_bytes: int = 64 * 2**20          # VMEM working-set budget
    hbm_bytes: int = 16 * 2**30           # device memory (v5e: 16 GiB)
    peak_flops: float = 197e12            # bf16
    hbm_bw: float = 819e9
    batch: int = 256                       # serving batch per launch
    launch_overhead_us: float = 3.0

    def estimate(self, algorithm: str, topology: dict) -> dict:
        from repro.core.stageir import spec_layers
        from repro.kernels.fused_mlp.kernel import LANE, vmem_bytes

        # each compute stage is one MXU tile-op of the fused kernel; tree
        # lowers to a predicated select chain, counted as one launch stage
        n_layers = max(1, len(spec_layers(_dense_specs(algorithm, topology))))
        vmem = vmem_bytes(n_layers, self.batch)
        flops_per_pkt = n_layers * 2 * LANE * LANE  # padded MXU tiles
        bytes_per_pkt = 2 * LANE * 4                # stream in + out, f32
        t_compute = flops_per_pkt / self.peak_flops
        t_mem = bytes_per_pkt / self.hbm_bw
        t_pkt = max(t_compute, t_mem)
        launch = self.launch_overhead_us * 1e-6
        thr = self.batch / (self.batch * t_pkt + launch)
        lat = (self.batch * t_pkt + launch) * 1e9
        return {
            "vmem_bytes": vmem,
            "flops_per_pkt": flops_per_pkt,
            "latency_ns": lat,
            "throughput_pps": thr,
        }


# -------------------------------------------------------------- flow state
#
# The per-flow register file (repro.flowstate) is a CO-RESIDENT on the
# target: its slot/SRAM budget is charged like any other resource and
# composed with a model's report via FeasibilityReport.merge (the same
# §3.2.1 consistency rule multi-app chaining uses) — resources add,
# latency adds, throughput is the min.  The shape numbers are read off the
# shape-only stage specs (stageir.flowstate_specs), never re-derived here.


def flowstate_report(spec, platform_kind: str = "taurus", model: Any = None
                     ) -> FeasibilityReport:
    """Resource/latency report for one flow register file on one target.

    ``spec`` is a ``flowstate.FlowStateSpec``; ``model`` optionally
    overrides the platform resource model (defaults match the paper-scale
    calibrations above)."""
    from repro.core.stageir import flowstate_specs, spec_params

    from repro.kernels.flow_update import envelope_reason, vmem_bytes

    words = spec_params(flowstate_specs(spec))
    return _register_table_report(
        words, platform_kind, model, what="flow registers",
        tpu=lambda m: (vmem_bytes(spec.width, m.batch),
                       _tpu_table_bytes(spec.n_slots, spec.width),
                       envelope_reason(spec.n_slots, spec.width,
                                       len(spec.hist_sizes))),
    )


def mitigation_report(spec, platform_kind: str = "taurus", model: Any = None
                      ) -> FeasibilityReport:
    """Resource/latency report for one mitigation ACTION table — the
    per-flow drop/rate-limit registers a trailing ``Mitigate`` stage
    keeps (docs/pipeline_ir.md#mitigation-contract).

    ``spec`` is a ``flowstate.MitigationSpec``.  The action table is a
    second register file co-resident with the detection table, so it is
    charged through the SAME per-platform register model and composed via
    ``FeasibilityReport.merge`` — mitigation SRAM is never free.  On the
    TPU target the action table FOLDS INTO the fused flow launch
    (``kernels/fused_flow.mitigation_phase``): the table stays in HBM and
    the launch stages the batch's mitigation columns into VMEM."""
    from repro.core.stageir import mitigation_specs, spec_params
    from repro.kernels.flow_update import envelope_reason
    from repro.kernels.flow_update.kernel import LANE

    words = spec_params(mitigation_specs(spec))
    return _register_table_report(
        words, platform_kind, model, what="mitigation registers",
        # the six lane-padded [B, 1] mitigation columns (double-buffered)
        # plus the [B, B] verdict permutation of the fused launch
        tpu=lambda m: (2 * 6 * m.batch * LANE * 4 + m.batch ** 2 * 4,
                       _tpu_table_bytes(spec.n_slots, spec.width),
                       envelope_reason(spec.n_slots, spec.width, 0)),
    )


def _tpu_table_bytes(n_slots: int, width: int) -> int:
    """HBM bytes of one register table on the TPU: f32 rows padded to
    whole 8-word groups plus the int32 key vector.  The v5e serving
    step's ``memory_analysis`` stores the 28-word flow-ddos rows as 32
    words (narrower rows may take less: this is an upper bound); donated,
    the table is updated in place."""
    return n_slots * (-(-width // 8) * 8 + 1) * 4


def _register_table_report(words: int, platform_kind: str, model: Any, *,
                           what: str, tpu) -> FeasibilityReport:
    """Shared per-platform charging for one register table of ``words``
    32-bit words (stored keys included) — the flow-state detection table
    and the mitigation action table go through the same rules.  ``tpu(m)``
    gives the TPU charge: (kernel VMEM working set, table HBM bytes, the
    kernel envelope's refusal or None)."""
    nbytes = words * 4
    reasons: list[str] = []

    if platform_kind == "taurus":
        m = model or TaurusModel()
        # register rows live in MU SRAM banks; hash + update occupy a
        # couple of CU ALU slots; one table read + write per packet
        mu = max(1, math.ceil(words / m.mu_words))
        cu = 2
        if mu > m.total_mu:
            reasons.append(
                f"{what} need {mu} MU > {m.total_mu} available"
            )
        return FeasibilityReport(
            feasible=not reasons, reasons=reasons,
            resources={"cu": cu, "mu": mu, "register_words": words},
            latency_ns=4 / m.clock_ghz,    # hash, read, update, write-back
            throughput_pps=m.clock_ghz * 1e9,
        )
    if platform_kind == "tofino":
        m = model or MATModel()
        if nbytes > m.register_bytes:
            reasons.append(
                f"{what} need {nbytes} B > {m.register_bytes} B "
                "register SRAM"
            )
        return FeasibilityReport(
            feasible=not reasons, reasons=reasons,
            resources={"mats": 1, "register_bytes": nbytes},
            latency_ns=2 * m.stage_ns,     # hash stage + register stage
            throughput_pps=m.line_rate_pps,
        )
    if platform_kind == "fpga":
        m = model or FPGAModel()
        bram = max(1, math.ceil(nbytes / 4608))   # 36Kb BRAM blocks
        if bram + m.base_bram > m.total_bram:
            reasons.append(
                f"{what} need {bram} BRAM > "
                f"{m.total_bram - m.base_bram} available"
            )
        return FeasibilityReport(
            feasible=not reasons, reasons=reasons,
            resources={"bram": bram, "register_bytes": nbytes},
            latency_ns=3 * 1e3 / m.clock_mhz,     # hash, read, write
            throughput_pps=m.clock_mhz * 1e6,
        )
    if platform_kind == "tpu":
        m = model or TPUModel()
        vmem, hbm, refusal = tpu(m)
        if refusal:
            reasons.append(f"{what}: {refusal}")
        if vmem > m.vmem_bytes:
            reasons.append(
                f"{what} need {vmem} B VMEM > {m.vmem_bytes} budget"
            )
        if hbm > m.hbm_bytes:
            reasons.append(
                f"{what} need {hbm} B HBM > {m.hbm_bytes} budget"
            )
        launch = m.launch_overhead_us * 1e-6
        return FeasibilityReport(
            feasible=not reasons, reasons=reasons,
            resources={"vmem_bytes": vmem, "hbm_bytes": hbm,
                       "register_words": words},
            latency_ns=launch * 1e9,
            throughput_pps=m.batch / launch,
        )
    raise KeyError(f"no flow-state model for platform {platform_kind!r}")
