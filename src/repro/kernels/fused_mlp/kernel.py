"""Pallas TPU kernel: whole per-packet DNN fused into ONE kernel launch.

This is the TPU-native translation of the paper's Taurus MapReduce pipeline
(Fig. 5): the paper stitches dot-product map/reduce templates into layers and
layers into a pipeline with double-buffered SRAM between stages.  On TPU the
equivalent is a single Pallas kernel where

  * every layer's weights are resident in VMEM for the whole launch (the
    "on-chip memory" of the MapReduce grid; weights never re-read from HBM),
  * the batch is tiled into MXU-aligned blocks (block_b x 128) that stream
    through HBM -> VMEM double-buffering (pallas_call pipelines the grid),
  * layer widths are zero-padded to the 128-lane MXU tile so each layer is
    exactly one 128x128 MXU matmul per batch tile -- a "CU" in our resource
    model (core.feasibility) is one such tile-op.

Zero padding is self-masking: padded weight columns/rows are 0 and padded
biases are 0, so padded activations stay identically 0 through ReLU chains.
Because the pad lanes are exact zeros, the padded matmul is bit-identical
to the unpadded one, so the lane width is a pure tuning knob: the padded
entry points accept any ``lane`` (the Pallas serving backend snaps it to
the model width in interpret mode instead of paying 128-wide tiles on CPU;
on TPU it stays ``LANE`` = the MXU tile).

Grid: (B / block_b,).  VMEM working set = L*lane*lane*4 B of weights
(+2 batch tiles), which core.feasibility checks against the VMEM budget.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 128  # MXU/VREG lane width: TPU layer widths pad to this
DEFAULT_BLOCK_B = 256
# Interpret-mode (CPU) batch tile for fused-DAG launches: the emulated
# grid loop is pure overhead there, so one big tile covers the whole
# micro-batch.  On TPU a single launch streams the grid regardless of the
# tile size, so the DAG keeps the single-model DEFAULT_BLOCK_B (smaller
# VMEM tiles, same launch count); dag_vmem_bytes is the resident set the
# lowering budgets either way.
DAG_BLOCK_B = 1024


def snap_lane(widths: list[int], *, interpret: bool) -> int:
    """Lane width for a model whose widest layer is max(widths).

    On TPU (interpret=False) this is always ``LANE`` — the MXU tile.  In
    interpret mode (CPU) padding to 128 only burns FLOPs, so snap to the
    smallest multiple of 8 covering the model instead (bit-identical: pad
    lanes are exact zeros either way)."""
    if not interpret:
        return LANE
    return min(LANE, max(8, -(-max(widths) // 8) * 8))


def _kernel(x_ref, w_ref, b_ref, o_ref, *, n_layers: int):
    """x_ref: [block_b, LANE]; w_ref: [L, LANE, LANE]; b_ref: [L, LANE]."""
    h = x_ref[...].astype(jnp.float32)
    for l in range(n_layers):  # static unroll: the whole DNN in one launch
        w = w_ref[l].astype(jnp.float32)
        h = jnp.dot(h, w, preferred_element_type=jnp.float32,
                    precision=jax.lax.Precision.HIGHEST)
        h = h + b_ref[l][None, :]
        if l < n_layers - 1:
            h = jnp.maximum(h, 0.0)
    o_ref[...] = h.astype(o_ref.dtype)


def _classify_kernel(x_ref, w_ref, b_ref, o_ref, *, n_layers: int,
                     num_classes: int):
    """Fused MLP + argmax: class ids leave the kernel, logits never touch
    HBM.  Padded lanes >= num_classes are masked to -inf before the argmax,
    so the result equals argmax over the first num_classes logits."""
    h = x_ref[...].astype(jnp.float32)
    for l in range(n_layers):
        w = w_ref[l].astype(jnp.float32)
        h = jnp.dot(h, w, preferred_element_type=jnp.float32,
                    precision=jax.lax.Precision.HIGHEST)
        h = h + b_ref[l][None, :]
        if l < n_layers - 1:
            h = jnp.maximum(h, 0.0)
    lane = jax.lax.broadcasted_iota(jnp.int32, h.shape, 1)
    h = jnp.where(lane < num_classes, h, -jnp.inf)
    cls = jnp.argmax(h, axis=1).astype(jnp.int32)
    o_ref[...] = jnp.broadcast_to(cls[:, None], o_ref.shape)


@functools.partial(
    jax.jit, static_argnames=("n_layers", "num_classes", "block_b",
                              "interpret")
)
def fused_mlp_classify_padded(
    x_pad: jax.Array,     # [B_pad, LANE]
    w_stack: jax.Array,   # [L, LANE, LANE]
    b_stack: jax.Array,   # [L, LANE]
    *,
    n_layers: int,
    num_classes: int,
    block_b: int = DEFAULT_BLOCK_B,
    interpret: bool = False,
) -> jax.Array:
    """-> [B_pad, lane] int32, class id broadcast across lanes (take col 0)."""
    B, lane = x_pad.shape
    assert B % block_b == 0
    grid = (B // block_b,)
    return pl.pallas_call(
        functools.partial(
            _classify_kernel, n_layers=n_layers, num_classes=num_classes
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, lane), lambda i: (i, 0)),
            pl.BlockSpec((n_layers, lane, lane), lambda i: (0, 0, 0)),
            pl.BlockSpec((n_layers, lane), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, lane), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, lane), jnp.int32),
        interpret=interpret,
    )(x_pad, w_stack, b_stack)


# ------------------------------------------------------- cross-model DAG
#
# A whole Seq/Par DAG of MLP-shaped models executed as ONE kernel launch:
# every model's weight stack is resident in VMEM for the launch, each model
# runs its statically-unrolled layer chain on the same input tile, and the
# DAG's gating/merge ops (Seq short-circuit as where-masks, Par or/and as
# max/min) apply in-kernel on the int32 verdicts — so chained models cost
# one HBM round trip total instead of one per model.
#
# The DAG structure is a *plan*: nested hashable tuples
#   ("model", i)                   leaf — verdict of model i
#   ("seq", (p0, p1, ...))         gate: flagged packets keep their verdict
#   ("or"|"and", (p0, p1, ...))    parallel merge: max / min
# traced statically into the kernel, mirroring chaining.compile_dag.


def eval_dag_plan(plan: tuple, verdicts: list) -> jax.Array:
    """Fold per-model verdicts through the DAG plan (traceable; used both
    inside the kernel and by reference implementations)."""
    kind = plan[0]
    if kind == "model":
        return verdicts[plan[1]]
    parts = [eval_dag_plan(p, verdicts) for p in plan[1]]
    if kind == "seq":
        out = parts[0]
        for nxt in parts[1:]:
            out = jnp.where(out > 0, out, nxt)
        return out
    if kind == "or":
        return functools.reduce(jnp.maximum, parts)
    if kind == "and":
        return functools.reduce(jnp.minimum, parts)
    raise KeyError(f"unknown DAG plan node {kind!r}")


def _dag_kernel(x_ref, *refs, n_layers: tuple, n_classes: tuple,
                lanes: tuple, plan: tuple):
    """refs = (w_0, b_0, w_1, b_1, ..., o_ref): one (weights, biases) stack
    pair per model, the int32 verdict tile last.  Each model runs at its
    OWN snapped lane (``lanes[i]``) on a static slice of the input tile —
    the fused launch then does exactly the per-model path's FLOPs (on TPU
    every lane is the 128-wide MXU tile and the slices are no-ops)."""
    o_ref = refs[-1]
    h0 = x_ref[...].astype(jnp.float32)
    verdicts = []
    for i, n_l in enumerate(n_layers):
        w_ref, b_ref = refs[2 * i], refs[2 * i + 1]
        h = h0[:, :lanes[i]]
        for l in range(n_l):
            w = w_ref[l].astype(jnp.float32)
            h = jnp.dot(h, w, preferred_element_type=jnp.float32,
                        precision=jax.lax.Precision.HIGHEST)
            h = h + b_ref[l][None, :]
            if l < n_l - 1:
                h = jnp.maximum(h, 0.0)
        lane_ids = jax.lax.broadcasted_iota(jnp.int32, h.shape, 1)
        h = jnp.where(lane_ids < n_classes[i], h, -jnp.inf)
        verdicts.append(jnp.argmax(h, axis=1).astype(jnp.int32))
    v = eval_dag_plan(plan, verdicts)
    o_ref[...] = jnp.broadcast_to(v[:, None], o_ref.shape)


@functools.partial(
    jax.jit, static_argnames=("n_layers", "n_classes", "lanes", "plan",
                              "block_b", "interpret")
)
def fused_dag_padded(
    x_pad: jax.Array,     # [B_pad, max(lanes)]
    *stacks: jax.Array,   # per model: w [L_i, lane_i, lane_i], b [L_i, lane_i]
    n_layers: tuple,
    n_classes: tuple,
    lanes: tuple,
    plan: tuple,
    block_b: int = DAG_BLOCK_B,
    interpret: bool = False,
) -> jax.Array:
    """-> [B_pad, max(lanes)] int32, DAG verdict broadcast (take col 0)."""
    B, x_lane = x_pad.shape
    assert B % block_b == 0
    assert len(stacks) == 2 * len(n_layers)
    assert x_lane == max(lanes)
    grid = (B // block_b,)
    in_specs = [pl.BlockSpec((block_b, x_lane), lambda i: (i, 0))]
    for n_l, lane in zip(n_layers, lanes):
        in_specs.append(
            pl.BlockSpec((n_l, lane, lane), lambda i: (0, 0, 0))
        )
        in_specs.append(pl.BlockSpec((n_l, lane), lambda i: (0, 0)))
    return pl.pallas_call(
        functools.partial(_dag_kernel, n_layers=n_layers,
                          n_classes=n_classes, lanes=lanes, plan=plan),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_b, x_lane), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, x_lane), jnp.int32),
        interpret=interpret,
    )(x_pad, *stacks)


def dag_vmem_bytes(n_layers: tuple, lanes: tuple,
                   block_b: int = DEFAULT_BLOCK_B) -> int:
    """VMEM working set of the fused-DAG launch: every chained model's
    weight stack resident at once (each at its own lane), plus the
    double-buffered batch tiles at the widest lane.  The lowering gates
    DAG fusion on this fitting ``DAG_VMEM_BUDGET`` — oversized DAGs fall
    back to per-model launches instead of failing at Mosaic lowering."""
    weights = sum(n_l * (lane * lane + lane) * 4
                  for n_l, lane in zip(n_layers, lanes))
    tiles = 2 * 2 * block_b * max(lanes) * 4
    return weights + tiles


# matches the TPU platform's working-set budget (core.feasibility
# TPUModel.vmem_bytes): the megakernel must leave the envelope honestly
# rather than claim a launch that cannot be resident
DAG_VMEM_BUDGET = 64 * 2**20


def pad_to_lane(arr: jax.Array, axis: int, lane: int = LANE) -> jax.Array:
    n = arr.shape[axis]
    pad = (-n) % lane
    if pad == 0:
        return arr
    widths = [(0, 0)] * arr.ndim
    widths[axis] = (0, pad)
    return jnp.pad(arr, widths)


def pack_params(weights: list[jax.Array], biases: list[jax.Array],
                lane: int = LANE) -> tuple[jax.Array, jax.Array]:
    """Zero-pad every layer to [lane, lane] and stack: -> ([L,lane,lane],
    [L,lane]).  Requires every layer dim <= lane (per-packet models are)."""
    ws, bs = [], []
    for w, b in zip(weights, biases):
        assert w.shape[0] <= lane and w.shape[1] <= lane, (
            f"fused_mlp supports layer dims <= {lane}, got {w.shape}"
        )
        ws.append(pad_to_lane(pad_to_lane(w, 0, lane), 1, lane))
        bs.append(pad_to_lane(b, 0, lane))
    return jnp.stack(ws).astype(jnp.float32), jnp.stack(bs).astype(jnp.float32)


@functools.partial(
    jax.jit, static_argnames=("n_layers", "block_b", "interpret")
)
def fused_mlp_padded(
    x_pad: jax.Array,     # [B_pad, LANE]
    w_stack: jax.Array,   # [L, LANE, LANE]
    b_stack: jax.Array,   # [L, LANE]
    *,
    n_layers: int,
    block_b: int = DEFAULT_BLOCK_B,
    interpret: bool = False,
) -> jax.Array:
    B, lane = x_pad.shape
    assert B % block_b == 0
    grid = (B // block_b,)
    return pl.pallas_call(
        functools.partial(_kernel, n_layers=n_layers),
        grid=grid,
        in_specs=[
            # batch tile streams; index_map in block units
            pl.BlockSpec((block_b, lane), lambda i: (i, 0)),
            # weights: whole stack resident in VMEM every grid step
            pl.BlockSpec((n_layers, lane, lane), lambda i: (0, 0, 0)),
            pl.BlockSpec((n_layers, lane), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, lane), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, lane), x_pad.dtype),
        interpret=interpret,
    )(x_pad, w_stack, b_stack)


def vmem_bytes(n_layers: int, block_b: int = DEFAULT_BLOCK_B,
               lane: int = LANE) -> int:
    """VMEM working set the kernel claims (feasibility input)."""
    weights = n_layers * lane * lane * 4 + n_layers * lane * 4
    tiles = 2 * 2 * block_b * lane * 4  # double-buffered in + out tiles
    return weights + tiles
