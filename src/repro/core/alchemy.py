"""Alchemy: the embedded DSL and frontend of Homunculus (paper §3.1).

Constructs (paper Table 1):

  Model({...})            objectives, algorithm list, data loader
  @DataLoader             dataset loading/preprocessing wrapper
  Platforms.Taurus() ...  backend target + resource/performance constraints
  m1 > m2                 sequential composition
  m1 | m2                 parallel composition
                          (natural chains work: ``m1 > m2 > m3`` builds the
                          3-stage Seq — Python's chained-comparison
                          evaluation is intercepted via ``Seq.__bool__``)
  platform < {...}        constraint operator (sugar for .constrain)
  IOMap / @IOMapper       wiring between composed models

A program is exactly the paper's Figure-3 shape::

    import homunculus
    from homunculus.alchemy import DataLoader, Model, Platforms

    @DataLoader
    def wrapper_func():
        ...
        return {"data": {"train": tnx, "test": tsx},
                "labels": {"train": tny, "test": tsy}}

    model_spec = Model({"optimization_metric": ["f1"],
                        "algorithm": ["dnn"],
                        "name": "anomaly_detection",
                        "data_loader": wrapper_func})
    platform = Platforms.Taurus()
    platform.constrain(performance={"throughput": 1, "latency": 500},
                       resources={"rows": 16, "cols": 16})
    platform.schedule(model_spec)
    homunculus.generate(platform)
"""

from __future__ import annotations

import dataclasses
import sys
import threading
from typing import Any, Callable

import numpy as np

from repro.core import feasibility as feas
from repro.data.netdata import Dataset

# ----------------------------------------------------------------- loaders


def DataLoader(fn: Callable) -> Callable:
    """Decorator: normalize a user loader to a repro Dataset.

    Accepts either a ``Dataset`` or the paper's dict form
    {"data": {"train", "test"}, "labels": {"train", "test"}}.
    """

    def wrapper(*a, **kw) -> Dataset:
        out = fn(*a, **kw)
        if isinstance(out, Dataset):
            return out
        data, labels = out["data"], out["labels"]
        tnx = np.asarray(data["train"], np.float32)
        tsx = np.asarray(data["test"], np.float32)
        tny = np.asarray(labels["train"], np.int32)
        tsy = np.asarray(labels["test"], np.int32)
        ncls = int(max(tny.max(), tsy.max())) + 1
        names = out.get(
            "feature_names", [f"f{i}" for i in range(tnx.shape[1])]
        )
        return Dataset(
            name=out.get("name", fn.__name__),
            train_x=tnx, train_y=tny, test_x=tsx, test_y=tsy,
            feature_names=list(names), num_classes=ncls,
        )

    wrapper.__wrapped__ = fn
    wrapper._is_dataloader = True
    return wrapper


def IOMapper(io_ins: list[str], io_outs: list[str]) -> Callable:
    """Decorator: declare a mapping function's input/output port names."""

    def deco(fn):
        fn._io_ins = list(io_ins)
        fn._io_outs = list(io_outs)
        return fn

    return deco


@dataclasses.dataclass
class IOMap:
    """Connects model inputs/outputs (paper Table 1)."""

    mapper_func: Callable  # (features, upstream_outputs) -> features

    def __call__(self, features, upstream):
        return self.mapper_func(features, upstream)


def passthrough_iomap(features, upstream):
    return features


# ------------------------------------------------------------ composition
#
# Python *chains* bare comparisons: ``m1 > m2 > m3`` evaluates as
# ``(m1 > m2) and (m2 > m3)`` — naively the left Seq is silently dropped.
# The fix: when Python truth-tests an intermediate ``Seq`` (the ``and``),
# ``Seq.__bool__`` records (seq, last operand); the very next ``__gt__``
# on that same operand extends the recorded Seq instead of starting a new
# one, so natural chains build the full DAG.  Safety rails — a record is
# only left when BOTH hold:
#   * the truth-tested Seq is an unnamed temporary (CPython refcount ==
#     eval stack + bool arg + getrefcount arg), so a variable-bound Seq
#     (``s = a > b; if s: ...``) never records; and
#   * the truth-test executes at the implicit ``and`` of a chained
#     comparison — JUMP_IF_*_OR_POP up to CPython 3.11, ``COPY 1;
#     POP_JUMP_IF_FALSE`` on 3.12 — so ``if a > b: ...`` (a bare
#     POP_JUMP_*) and ``bool(a > b)`` (CALL) never record either;
# and the record is consume-once, cleared by the next ``>``.  Both rails
# are CPython-specific; ``_natural_chain_selfcheck`` probes the behavior
# at import and warns (advising parentheses) where it does not hold.
# (Caveat: tools that rewrite chained comparisons into non-short-circuit
# form — e.g. pytest's assertion rewriter INSIDE an ``assert`` expression —
# bypass the __bool__ hook; build the DAG in a plain statement there.)

class _ChainState(threading.local):
    """Per-thread pending records — concurrent DAG building in threads must
    not cross-contaminate chains.  A STACK, not a slot: the right operand
    of a chain may itself be a parenthesized chain (``a > b > (c > d > e)``)
    whose inner record must coexist with the outer one."""

    def __init__(self):
        self.recs: list = []   # [(seq, last_operand, window), ...]


_CHAIN = _ChainState()
_CHAIN_DEPTH = 8    # pathological-nesting backstop
_OPMAP = __import__("dis").opmap
_CHAIN_OPS = frozenset(
    op for name, op in _OPMAP.items()
    if name in ("JUMP_IF_FALSE_OR_POP", "JUMP_IF_TRUE_OR_POP")
)
# CPython 3.12 dropped those opcodes: the chain's implicit and is
# ``COPY 1; POP_JUMP_IF_FALSE``, and the COPY holds one more stack
# reference on the truth-tested temporary.  (3.13 truth-tests at TO_BOOL,
# which no rail matches: the self-checks warn there.)
_COPY_FORM = not _CHAIN_OPS and "COPY" in _OPMAP and "TO_BOOL" not in _OPMAP
if _COPY_FORM:
    _CHAIN_OPS = frozenset({_OPMAP["POP_JUMP_IF_FALSE"]})
# CPython refcount of a stack temporary seen by __bool__
_TEMP_REFS = 4 if _COPY_FORM else 3


def _chain_window():
    """If the ``__bool__`` 3 frames up executes a chain's implicit and,
    return (frame_id, lasti, jump_target) — the consuming ``__gt__`` must
    run in that frame strictly inside (lasti, target].  None = not a chain.

    Two bytecode checks make this precise on CPython <= 3.12:
      * the current opcode is the chain's JUMP_IF_*_OR_POP (on 3.12, a
        POP_JUMP_IF_FALSE right after ``COPY 1``); and
      * the jump targets a ``ROT_TWO``/``SWAP 2; POP_TOP`` cleanup block —
        ONLY chained comparisons emit that epilogue; a plain ``and``/``or``
        jumps to the end of its expression instead, so value-producing
        conjunctions like ``(a > b) and f(b > c)`` never record.
    Bytecode eras with no recognised chain opcode and non-CPython frame
    layouts degrade to a permissive window (refcount rail only; the
    import-time self-checks warn there)."""
    if not _CHAIN_OPS:
        return (None, 0, sys.maxsize)
    try:
        import dis

        f = sys._getframe(2)
        if f.f_code.co_code[f.f_lasti] not in _CHAIN_OPS:
            return None
        ins = [i for i in dis.get_instructions(f.f_code)
               if i.opname != "EXTENDED_ARG"]
        at = {i.offset: k for k, i in enumerate(ins)}
        k = at.get(f.f_lasti)
        if k is None:
            return (id(f), f.f_lasti, sys.maxsize)
        if _COPY_FORM and not (k > 0 and ins[k - 1].opname == "COPY"
                               and ins[k - 1].arg == 1):
            return None         # a statement jump: the value is not kept
        target = ins[k].argval
        t = at.get(target)
        if not (t is not None and t + 1 < len(ins)
                and (ins[t].opname == "ROT_TWO"
                     or (ins[t].opname == "SWAP" and ins[t].arg == 2))
                and ins[t + 1].opname == "POP_TOP"):
            return None         # an and/or jump, not a chain epilogue
        return (id(f), f.f_lasti, target)
    except Exception:  # pragma: no cover - permissive on odd interpreters
        return (None, 0, sys.maxsize)


def _chain_take(left_operand):
    """Pop the newest pending chain whose last operand is ``left_operand``
    AND whose bytecode window (between the chain's implicit-and jump and
    its target) covers this ``>`` — a later, unrelated ``>`` on the same
    operand falls outside and never absorbs a record.

    Mismatching records stay put: a parenthesized operand like
    ``a > b > (c > d)`` runs inner compositions between the outer record
    and the outer extending ``__gt__``.  The window, not eager clearing,
    is what expires records (same-frame records past their window are
    pruned here)."""
    try:
        f = sys._getframe(2)
        here = (id(f), f.f_lasti)
    except Exception:  # pragma: no cover
        here = None
    recs = _CHAIN.recs
    for i in range(len(recs) - 1, -1, -1):
        node, operand, (fid, lo, hi) = recs[i]
        if here is not None and fid == here[0] and here[1] > hi:
            del recs[i]          # same frame, past its window: stale
            continue
        if operand is left_operand:
            in_window = (fid is None or here is None
                         or (fid == here[0] and lo < here[1] <= hi))
            if in_window:
                del recs[i:]     # consume; inner records above are done
                return node
    return None


class _Composable:
    def __gt__(self, other):  # m1 > m2 : sequential
        other = _as_node(other)
        chained = _chain_take(self)
        if chained is not None:
            return Seq(chained.children + [other])
        return Seq([self, other])

    def __or__(self, other):  # m1 | m2 : parallel
        # NB: must not clear _CHAIN — ``a > b > (c | d)`` evaluates this
        # mid-chain, after Seq.__bool__ and before the extending __gt__
        return Par([self, _as_node(other)])


def _as_node(x):
    if isinstance(x, (Seq, Par, Model)):
        return x
    raise TypeError(f"cannot compose {type(x)}")


@dataclasses.dataclass
class Seq(_Composable):
    children: list

    def __gt__(self, other):
        other = _as_node(other)
        chained = _chain_take(self)
        if chained is not None:
            return Seq(chained.children + [other])
        return Seq(self.children + [other])

    def __bool__(self):
        # truth-tested mid-chain (the implicit ``and``): remember this Seq
        # so the next ``>`` on our last operand extends it — but only when
        # we are an unnamed temporary AND the call site is a chain's
        # JUMP_IF opcode; ``if seq:`` / ``bool(seq)`` are user truth-tests
        if sys.getrefcount(self) <= _TEMP_REFS:
            window = _chain_window()
            if window is not None:
                _CHAIN.recs.append((self, self.children[-1], window))
                del _CHAIN.recs[:-_CHAIN_DEPTH]
        return True

    def leaves(self) -> list["Model"]:
        out = []
        for c in self.children:
            out += c.leaves() if isinstance(c, (Seq, Par)) else [c]
        return out

    def describe(self) -> str:
        return " > ".join(
            f"({c.describe()})" if isinstance(c, (Seq, Par)) else c.name
            for c in self.children
        )


@dataclasses.dataclass
class Par(_Composable):
    children: list

    def __or__(self, other):
        return Par(self.children + [_as_node(other)])

    def __bool__(self):
        # never part of a chained comparison (| is a binary operator);
        # pending records expire via their bytecode window, not here
        return True

    def leaves(self) -> list["Model"]:
        out = []
        for c in self.children:
            out += c.leaves() if isinstance(c, (Seq, Par)) else [c]
        return out

    def describe(self) -> str:
        return " | ".join(
            f"({c.describe()})" if isinstance(c, (Seq, Par)) else c.name
            for c in self.children
        )


# ------------------------------------------------------------------- Model


class Model(_Composable):
    """User intent for one data-plane ML model (paper §3.1.1)."""

    def __init__(self, spec: dict):
        self.spec = dict(spec)
        self.name: str = spec.get("name", "model")
        self.metrics: list[str] = list(spec.get("optimization_metric", ["f1"]))
        self.algorithms: list[str] | None = (
            list(spec["algorithm"]) if spec.get("algorithm") else None
        )
        loader = spec["data_loader"]
        if not getattr(loader, "_is_dataloader", False):
            loader = DataLoader(loader)
        self._loader = loader
        self._data: Dataset | None = None
        self.iomap: IOMap = IOMap(passthrough_iomap)

    @property
    def objective(self) -> str:
        return self.metrics[0]

    def data(self) -> Dataset:
        if self._data is None:
            self._data = self._loader()
        return self._data

    def with_iomap(self, iomap: IOMap) -> "Model":
        self.iomap = iomap
        return self

    def leaves(self) -> list["Model"]:
        return [self]

    def describe(self) -> str:
        return self.name

    def __repr__(self):
        return f"Model({self.name!r}, metric={self.objective})"


def _natural_chain_selfcheck() -> bool:
    """Probe whether un-parenthesized chaining works on this interpreter."""
    a, b, c = (Model.__new__(Model) for _ in range(3))
    chain = a > b > c
    return isinstance(chain, Seq) and len(chain.children) == 3


def _chain_rails_selfcheck() -> bool:
    """Probe the safety rails: a truth-tested temporary must NOT leak into
    the next composition (fails on bytecode eras with no chain opcode,
    e.g. CPython 3.12, where the rails degrade to refcount-only)."""
    a, b, c = (Model.__new__(Model) for _ in range(3))
    if a > b:
        pass
    probe = b > c
    return len(probe.children) == 2


NATURAL_CHAINS_OK = _natural_chain_selfcheck()
CHAIN_RAILS_OK = _chain_rails_selfcheck()
if not (NATURAL_CHAINS_OK and CHAIN_RAILS_OK):  # pragma: no cover
    import warnings

    warnings.warn(
        "this Python implementation degrades Alchemy's chained-comparison "
        "interception ("
        + ("chains mis-parse" if not NATURAL_CHAINS_OK
           else "truth-tests can leak into later compositions")
        + "): prefer the parenthesized form (m1 > m2) > m3",
        RuntimeWarning,
        stacklevel=2,
    )


# --------------------------------------------------------------- Platforms


class Platform:
    """A physical data-plane target + its constraints (paper Table 1)."""

    kind: str = "abstract"

    def __init__(self):
        self.performance: dict[str, float] = {}
        self.resources: dict[str, float] = {}
        self.scheduled = None  # Model | Seq | Par
        self.generated = None  # filled by homunculus.generate

    # -- constraint API: .constrain(...) and the paper's `<` operator
    def constrain(self, performance: dict | None = None,
                  resources: dict | None = None, **kw):
        performance = performance or kw.get("performance") or {}
        resources = resources or kw.get("resources") or {}
        self.performance.update(performance)
        self.resources.update(resources)
        self._apply_resources()
        return self

    def __lt__(self, cons: dict):
        return self.constrain(
            performance=cons.get("performance"),
            resources=cons.get("resources"),
        )

    def _apply_resources(self):
        pass

    def schedule(self, node):
        """Install a Model or a composition DAG on this platform."""
        self.scheduled = _as_node(node)
        return self

    # -- constraint targets (None = unconstrained)
    @property
    def min_throughput_pps(self) -> float | None:
        thr = self.performance.get("throughput")
        return thr * 1e9 if thr is not None else None  # paper unit: GPkt/s

    @property
    def max_latency_ns(self) -> float | None:
        return self.performance.get("latency")  # paper unit: ns

    # -- to be provided per platform
    def check(self, algorithm: str, topology: dict) -> feas.FeasibilityReport:
        raise NotImplementedError

    def check_batch(self, algorithm: str, topologies: list[dict]
                    ) -> list[feas.FeasibilityReport]:
        """Feasibility verdicts for a whole candidate batch.  Platforms with
        a vectorizable resource model override this (Taurus reads the stage
        metadata of the entire batch in one numpy pass); the base form just
        maps ``check``."""
        return [self.check(algorithm, t) for t in topologies]

    def supported_algorithms(self) -> list[str]:
        raise NotImplementedError


class TaurusPlatform(Platform):
    kind = "taurus"

    def __init__(self):
        super().__init__()
        self.model = feas.TaurusModel()

    def _apply_resources(self):
        r = self.resources
        self.model = feas.TaurusModel(
            rows=int(r.get("rows", self.model.rows)),
            cols=int(r.get("cols", self.model.cols)),
        )

    def supported_algorithms(self) -> list[str]:
        return ["dnn", "logreg", "svm", "kmeans"]

    def check(self, algorithm, topology) -> feas.FeasibilityReport:
        return self._verdict(self.model.estimate(algorithm, topology))

    def check_batch(self, algorithm, topologies
                    ) -> list[feas.FeasibilityReport]:
        return [self._verdict(est)
                for est in self.model.estimate_batch(algorithm, topologies)]

    def _verdict(self, est: dict) -> feas.FeasibilityReport:
        budget_cu = self.model.total_cu
        budget_mu = self.model.total_mu
        min_thr = self.min_throughput_pps
        max_lat = self.max_latency_ns
        # pick the lowest-II (highest-throughput) option that fits; the
        # CU <-> II tradeoff is the paper's "loop iterations vs line rate"
        for opt in est["options"]:
            fits = opt["cu"] <= budget_cu and opt["mu"] <= budget_mu
            fast = min_thr is None or opt["throughput_pps"] >= min_thr
            slow = max_lat is not None and opt["latency_ns"] > max_lat
            if fits and fast and not slow:
                return feas.FeasibilityReport(
                    True, [],
                    {"cu": opt["cu"], "mu": opt["mu"], "ii": opt["ii"]},
                    opt["latency_ns"], opt["throughput_pps"],
                )
        o = est["options"][0]
        reasons = []
        if o["cu"] > budget_cu:
            reasons.append(f"CU {o['cu']} > {budget_cu}")
        if o["mu"] > budget_mu:
            reasons.append(f"MU {o['mu']} > {budget_mu}")
        if min_thr is not None and o["throughput_pps"] < min_thr:
            reasons.append("throughput below line rate at feasible II")
        if max_lat is not None and o["latency_ns"] > max_lat:
            reasons.append(f"latency {o['latency_ns']}ns > {max_lat}ns")
        if not reasons:
            reasons.append("no II in 1..max_ii satisfies all constraints")
        return feas.FeasibilityReport(
            False, reasons, {"cu": o["cu"], "mu": o["mu"], "ii": o["ii"]},
            o["latency_ns"], o["throughput_pps"],
        )


class TofinoPlatform(Platform):
    kind = "tofino"

    def __init__(self):
        super().__init__()
        self.model = feas.MATModel()

    def _apply_resources(self):
        r = self.resources
        self.model = feas.MATModel(
            num_tables=int(r.get("tables", self.model.num_tables)),
        )

    def supported_algorithms(self) -> list[str]:
        return ["kmeans", "svm", "tree", "logreg"]

    def check(self, algorithm, topology) -> feas.FeasibilityReport:
        mats = self.model.mats_for(algorithm, topology)
        lat = mats * self.model.stage_ns
        thr = self.model.line_rate_pps
        reasons = []
        if mats > self.model.num_tables:
            reasons.append(f"MATs {mats} > {self.model.num_tables}")
        if self.max_latency_ns is not None and lat > self.max_latency_ns:
            reasons.append(f"latency {lat}ns > {self.max_latency_ns}ns")
        if (self.min_throughput_pps is not None
                and thr < self.min_throughput_pps):
            reasons.append("line rate below required throughput")
        return feas.FeasibilityReport(
            not reasons, reasons, {"mats": mats}, lat, thr
        )


class FPGAPlatform(Platform):
    kind = "fpga"

    def __init__(self):
        super().__init__()
        self.model = feas.FPGAModel()

    def _apply_resources(self):
        r = self.resources
        self.model = feas.FPGAModel(
            total_luts=int(r.get("luts", self.model.total_luts)),
            total_ffs=int(r.get("ffs", self.model.total_ffs)),
            total_bram=int(r.get("bram", self.model.total_bram)),
        )

    def supported_algorithms(self) -> list[str]:
        return ["dnn", "logreg", "svm", "kmeans", "tree"]

    def check(self, algorithm, topology) -> feas.FeasibilityReport:
        e = self.model.estimate(algorithm, topology)
        reasons = []
        if e["luts"] > self.model.total_luts:
            reasons.append(f"LUTs {e['luts']} > {self.model.total_luts}")
        if e["ffs"] > self.model.total_ffs:
            reasons.append(f"FFs {e['ffs']} > {self.model.total_ffs}")
        if self.max_latency_ns is not None and e["latency_ns"] > self.max_latency_ns:
            reasons.append(f"latency {e['latency_ns']:.0f}ns > {self.max_latency_ns}ns")
        if (self.min_throughput_pps is not None
                and e["throughput_pps"] < self.min_throughput_pps):
            reasons.append("clock-limited throughput below requirement")
        return feas.FeasibilityReport(
            not reasons, reasons,
            {"luts": e["luts"], "ffs": e["ffs"], "bram": e["bram"]},
            e["latency_ns"], e["throughput_pps"],
        )


class TPUPlatform(Platform):
    """Beyond-paper backend: fused-Pallas per-packet pipeline on a TPU core."""

    kind = "tpu"

    def __init__(self):
        super().__init__()
        self.model = feas.TPUModel()

    def _apply_resources(self):
        r = self.resources
        self.model = feas.TPUModel(
            vmem_bytes=int(r.get("vmem_bytes", self.model.vmem_bytes)),
            batch=int(r.get("batch", self.model.batch)),
        )

    def supported_algorithms(self) -> list[str]:
        return ["dnn", "logreg", "svm", "kmeans"]

    def check(self, algorithm, topology) -> feas.FeasibilityReport:
        e = self.model.estimate(algorithm, topology)
        reasons = []
        if e["vmem_bytes"] > self.model.vmem_bytes:
            reasons.append(
                f"VMEM {e['vmem_bytes']} > {self.model.vmem_bytes}"
            )
        if self.max_latency_ns is not None and e["latency_ns"] > self.max_latency_ns:
            reasons.append(f"latency {e['latency_ns']:.0f}ns > {self.max_latency_ns}ns")
        if (self.min_throughput_pps is not None
                and e["throughput_pps"] < self.min_throughput_pps):
            reasons.append(
                f"roofline throughput {e['throughput_pps']:.2e} pps "
                f"< {self.min_throughput_pps:.2e}"
            )
        return feas.FeasibilityReport(
            not reasons, reasons,
            {"vmem_bytes": e["vmem_bytes"]},
            e["latency_ns"], e["throughput_pps"],
        )


class Platforms:
    """Factory namespace, as the paper spells it: Platforms.Taurus()."""

    Taurus = TaurusPlatform
    Tofino = TofinoPlatform
    FPGA = FPGAPlatform
    TPU = TPUPlatform
