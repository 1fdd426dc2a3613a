"""Compile the main-path kernels for a TPU v5e chip, with no chip attached.

Interpret mode (every other test) runs the Pallas kernels through the
CPU interpreter at 8-lane tiles; it cannot see what the TPU compiler
refuses: unsupported vector gathers, misaligned slices, VMEM overruns.
Here each kernel — and each stateful serving step around one — is
lowered and compiled for a *described* ``v5e:2x2`` topology at the
chip's 128-lane tiles and the serving batch, and the executable must
hold a Mosaic kernel (``tpu_custom_call``).  Nothing runs.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU compiler's library at a time.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BATCH = 512
N_SLOTS = 2048


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    """Lower as the program would on the chip: kernels pick their TPU
    form (tiles, interpret=False) from ``jax.default_backend()``."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _spec(a, sharding):
    """An array or a ``jax.ShapeDtypeStruct`` -> its shape on ``sharding``."""
    return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)


def _compile(fn, args, sharding, donate=()):
    specs = [_spec(a, sharding) for a in args]
    compiled = jax.jit(fn, donate_argnums=donate).lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


# ----------------------------------------------------- stateless kernels


def _mlp(rng, widths):
    ws = [jnp.asarray(rng.normal(size=(a, b)) * 0.1, jnp.float32)
          for a, b in zip(widths[:-1], widths[1:])]
    bs = [jnp.zeros((b,), jnp.float32) for b in widths[1:]]
    return ws, bs


@pytest.mark.parametrize("kernel", ["fused_mlp_classify", "fused_dag",
                                    "mat_classify"])
def test_stateless_kernel_compiles(kernel, one_chip, on_tpu):
    from repro.kernels import fused_mlp as fm
    from repro.kernels import mat_lut

    rng = np.random.default_rng(0)
    x = np.zeros((BATCH, 28), np.float32)
    if kernel == "fused_mlp_classify":
        ws, bs = _mlp(rng, [28, 16, 8, 2])
        _compile(lambda x: fm.fused_mlp_classify(x, ws, bs,
                                                 interpret=False),
                 [x], one_chip)
    elif kernel == "fused_dag":
        models = [_mlp(rng, [28, 16, 2]), _mlp(rng, [28, 32, 8, 3])]
        stacks = []
        for ws, bs in models:
            stacks += list(fm.pack_params(ws, bs, fm.LANE))
        plan = ("seq", (("model", 0), ("model", 1)))
        _compile(lambda x: fm.fused_dag(
            x, tuple(stacks), n_layers=(2, 3), n_classes=(2, 3),
            lanes=(fm.LANE, fm.LANE), plan=plan, interpret=False),
            [x], one_chip)
    else:
        edges = np.sort(rng.random((28, 7)), axis=1).astype(np.float32)
        tables = rng.random((28, 8, 4)).astype(np.float32)
        lmap = np.asarray([0, 1, 1, 0], np.int32)
        _compile(lambda x: mat_lut.mat_classify(
            x, edges, tables, lmap, interpret=False), [x], one_chip)


# ------------------------------------------------------ stateful steps


FORMS = ("mlp", "mat", "centroid", "mit-shared", "mit-separate",
         "two-table")


def _form_stages(form: str, *, n_slots: int, seed: int):
    """A stateful pipeline of each form the fused flow launch serves: the
    ``traffic.flow_feature_stages`` prefix and a suffix with seeded random
    parameters (real shapes, no training) — an MLP, MAT or centroid
    suffix; the MAT suffix with a drop table of the flow table's slot
    count (``mit-shared``) or a rate-limit table of half of it
    (``mit-separate``); or a second, port-keyed table feeding one MLP
    (``two-table``)."""
    from repro.core import stageir
    from repro.data import traffic
    from repro.flowstate import MitigationSpec
    from repro.flowstate.registers import FlowStateSpec

    rng = np.random.default_rng(seed)

    def mlp(widths):
        ws = [(rng.normal(size=(a, b)) * 0.1).astype(np.float32)
              for a, b in zip(widths[:-1], widths[1:])]
        return [stageir.FusedMLP(ws, [np.zeros(b, np.float32)
                                      for b in widths[1:]]),
                stageir.Reduce("argmax")]

    (fk, ru, ws), _ = traffic.flow_feature_stages(n_slots=n_slots)
    n_in = ws.n_out
    if form == "mlp":
        return [fk, ru, ws] + mlp([n_in, 16, 8, 2])
    if form == "centroid":
        return [fk, ru, ws,
                stageir.FeatureSelect(np.asarray([0, 2, 3, 7], np.int32)),
                stageir.CentroidDistance(
                    rng.random((5, 4)).astype(np.float32)),
                stageir.Reduce("argmin"),
                stageir.LabelMap(np.asarray([0, 1, 0, 1, 1], np.int32))]
    if form == "two-table":
        spec2 = FlowStateSpec(n_slots=n_slots // 4, n_counters=2,
                              n_ewma=1, hist_sizes=(4,))
        fk2 = stageir.FlowKey(key_cols=(traffic.COL_PORT,),
                              n_slots=spec2.n_slots)
        ru2 = stageir.RegisterUpdate(
            spec2, counter_cols=(traffic.COL_LEN,),
            ewma_cols=(traffic.COL_IPT,), hist_cols=(traffic.COL_LEN,),
            hist_edges=(np.asarray([100.0, 500.0, 1000.0]),))
        ws2 = stageir.WindowStats(spec2, mode="hist")
        return [fk, ru, ws, fk2, ru2, ws2] + mlp([n_in + ws2.n_out, 16, 2])
    edges = np.sort(rng.random((n_in, 7)), axis=1).astype(np.float32)
    tables = rng.random((n_in, 8, 4)).astype(np.float32)
    stages = [fk, ru, ws, stageir.Quantize(edges), stageir.LUTGather(tables),
              stageir.Reduce("argmax"),
              stageir.LabelMap(np.asarray([0, 1, 1, 0], np.int32))]
    if form == "mit-shared":
        stages.append(stageir.Mitigate(MitigationSpec(n_slots=n_slots,
                                                      threshold=6)))
    elif form == "mit-separate":
        stages.append(stageir.Mitigate(MitigationSpec(
            n_slots=n_slots // 2, mode="rate_limit", threshold=6)))
    return stages


def _state_shapes(pipe) -> list:
    """The step's state arguments as shapes: no table is allocated."""
    from repro.flowstate.mitigation import MIT_WIDTH

    tables = [(s.n_slots, s.width) for s in pipe.specs]
    if pipe.mitigation is not None:
        tables.append((pipe.mitigation.n_slots, MIT_WIDTH))
    out = []
    for n, w in tables:
        out += [jax.ShapeDtypeStruct((n,), jnp.int32),
                jax.ShapeDtypeStruct((n, w), jnp.float32)]
    return out


def _compile_step(pipe, sharding):
    from repro.data import traffic

    state = _state_shapes(pipe)
    x = np.zeros((BATCH, len(traffic.COLUMNS)), np.float32)
    valid = np.ones((BATCH,), np.int32)
    return _compile(pipe.step_fn, [*state, x, valid], sharding,
                    donate=tuple(range(len(state))))


@pytest.mark.parametrize("form", FORMS)
def test_fused_flow_step_compiles(form, one_chip, on_tpu):
    """The whole serving step — segmentation prelude, the fused
    ``fused_flow_serve`` launch, table scatter — for every fused form."""
    from repro.flowstate import StatefulPipeline

    pipe = StatefulPipeline(
        _form_stages(form, n_slots=N_SLOTS, seed=1),
        backend="pallas")
    assert pipe.backend == "pallas-fused-flow", pipe.fallback_reason
    _compile_step(pipe, one_chip)


def test_flow_update_compiles(one_chip, on_tpu):
    """The split-path flow update kernel at the flow-ddos shapes."""
    from repro.kernels.flow_update import flow_update

    W, H = 28, 2
    args = [np.full((N_SLOTS,), -1, np.int32),
            np.zeros((N_SLOTS, W), np.float32),
            np.zeros((BATCH,), np.int32), np.zeros((BATCH, 4), np.float32),
            np.zeros((BATCH, H), np.int32), np.ones((BATCH,), np.int32)]
    _compile(lambda *a: flow_update(*a, n_counters=2, n_ewma=2,
                                    alpha=0.125, interpret=False),
             args, one_chip, donate=(0, 1))


def test_largest_table_fits_one_chip(one_chip, on_tpu):
    """At ``MAX_SLOTS`` the flow-ddos step compiles for one v5e and its
    tables (donated, so updated in place) fit the chip's memory."""
    from repro.flowstate import StatefulPipeline
    from repro.kernels.flow_update import MAX_SLOTS

    stages = _form_stages("mit-shared", n_slots=MAX_SLOTS, seed=2)
    pipe = StatefulPipeline(stages, backend="pallas")
    assert pipe.backend == "pallas-fused-flow", pipe.fallback_reason
    mem = _compile_step(pipe, one_chip).memory_analysis()
    used = mem.argument_size_in_bytes + mem.output_size_in_bytes \
        - mem.alias_size_in_bytes + mem.temp_size_in_bytes
    assert used < 16 * 2**30 // 2


@pytest.mark.parametrize("log2_slots", [26, 27])
def test_table_compile_ceiling(log2_slots, one_chip, on_tpu, monkeypatch):
    """The fused step's table is bounded by device memory, not by the
    kernel: 2^26 flow-ddos slots (8.25 GiB, updated in place) compile for
    one v5e and 2^27 exceed its HBM.  ``MAX_SLOTS`` sits below this
    ceiling, at the largest table ``chip_smoke.py`` serves and checks at
    a quarter occupancy."""
    from repro.flowstate import StatefulPipeline
    from repro.kernels.flow_update import ops

    monkeypatch.setattr(ops, "MAX_SLOTS", 1 << log2_slots)
    pipe = StatefulPipeline(
        _form_stages("mlp", n_slots=1 << log2_slots, seed=3),
        backend="pallas")
    assert pipe.backend == "pallas-fused-flow", pipe.fallback_reason
    if log2_slots == 26:
        _compile_step(pipe, one_chip)
    else:
        with pytest.raises(Exception, match="hbm"):
            _compile_step(pipe, one_chip)
