"""Public op: fused per-packet MLP inference.

``fused_mlp(x, weights, biases)`` pads/packs, launches the Pallas kernel
(interpret=True on CPU — the TPU path is the same kernel compiled by
Mosaic), and slices the logits back to the true class count.

This is the executable artifact the Homunculus Taurus backend emits
(core.codegen.TaurusBackend): the generated pipeline closure calls this op
with the trained weights baked in.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.fused_mlp.kernel import (
    DAG_BLOCK_B,
    DEFAULT_BLOCK_B,
    LANE,
    eval_dag_plan,
    fused_dag_padded,
    fused_mlp_classify_padded,
    fused_mlp_padded,
    pack_params,
    pad_to_lane,
)
from repro.kernels.fused_mlp.ref import mlp_ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def envelope_reason(n_in: int, weights, lane: int = LANE) -> str | None:
    """Why an MLP is outside the fused kernel's envelope (None = inside):
    every layer must fit one ``lane``-wide tile."""
    widths = [int(n_in)] + [int(w.shape[1]) for w in weights]
    if max(widths) > lane:
        return f"layer width {max(widths)} exceeds the {lane}-lane tile"
    return None


def _prepare(x, weights, interpret, block_b, lane):
    """Shared kernel preamble for both entry points -> (x_pad, block_b,
    interpret, lane); raises ``ValueError`` outside the envelope."""
    if interpret is None:
        interpret = not _on_tpu()
    if lane is None:
        lane = LANE
    B, F = x.shape
    reason = envelope_reason(F, weights, lane)
    if reason:
        raise ValueError(reason)
    block_b = min(block_b, max(8, B))
    pad_b = (-B) % block_b
    x_pad = pad_to_lane(jnp.pad(x, ((0, pad_b), (0, 0))), 1, lane)
    return x_pad, block_b, interpret, lane


def fused_mlp(
    x: jax.Array,
    weights: list[jax.Array],
    biases: list[jax.Array],
    *,
    block_b: int = DEFAULT_BLOCK_B,
    interpret: bool | None = None,
    lane: int | None = None,
) -> jax.Array:
    """x: [B, F] -> logits [B, num_classes].

    ``lane`` is the padded layer width (default the 128-wide MXU tile);
    the Pallas serving backend passes ``kernel.snap_lane`` so CPU interpret
    mode runs model-sized tiles.  Numerics are lane-independent: pad lanes
    are exact zeros."""
    x_pad, block_b, interpret, lane = _prepare(x, weights, interpret,
                                               block_b, lane)
    w_stack, b_stack = pack_params(weights, biases, lane)
    out = fused_mlp_padded(
        x_pad, w_stack, b_stack,
        n_layers=len(weights), block_b=block_b, interpret=interpret,
    )
    return out[:x.shape[0], :weights[-1].shape[1]]


def fused_mlp_classify(
    x: jax.Array,
    weights: list[jax.Array],
    biases: list[jax.Array],
    *,
    block_b: int = DEFAULT_BLOCK_B,
    interpret: bool | None = None,
    lane: int | None = None,
) -> jax.Array:
    """x: [B, F] -> class ids [B] int32, argmax fused into the kernel.

    Same ``lane`` contract as :func:`fused_mlp`."""
    x_pad, block_b, interpret, lane = _prepare(x, weights, interpret,
                                               block_b, lane)
    w_stack, b_stack = pack_params(weights, biases, lane)
    out = fused_mlp_classify_padded(
        x_pad, w_stack, b_stack,
        n_layers=len(weights), num_classes=weights[-1].shape[1],
        block_b=block_b, interpret=interpret,
    )
    return out[:x.shape[0], 0]


def fused_mlp_reference(x, weights, biases):
    return mlp_ref(x, weights, biases)


def fused_dag(
    x: jax.Array,
    stacks: tuple,
    *,
    n_layers: tuple,
    n_classes: tuple,
    lanes: tuple,
    plan: tuple,
    block_b: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Whole-DAG megakernel entry: x [B, F] -> verdicts [B] int32.

    ``stacks`` is the flat tuple of per-model (w_stack, b_stack) pairs,
    each packed at its model's own snapped lane (``lanes[i]``); ``plan``
    the static DAG structure (see ``kernel.eval_dag_plan``).  One
    ``pallas_call`` for the entire chained/parallel model DAG: weights for
    ALL models resident in VMEM, gating applied in-kernel on int32
    verdicts.  The batch tile is ``DAG_BLOCK_B`` in interpret mode (the
    emulated grid loop is pure overhead on CPU, so one tile covers the
    micro-batch) and the single-model ``DEFAULT_BLOCK_B`` on TPU (one
    launch streams the grid either way; smaller tiles keep the VMEM
    working set down), clamped to the padded batch."""
    if interpret is None:
        interpret = not _on_tpu()
    if block_b is None:
        block_b = DAG_BLOCK_B if interpret else DEFAULT_BLOCK_B
    B = x.shape[0]
    block_b = min(block_b, max(8, B))
    pad_b = (-B) % block_b
    x_pad = pad_to_lane(jnp.pad(x, ((0, pad_b), (0, 0))), 1, max(lanes))
    out = fused_dag_padded(
        x_pad, *stacks, n_layers=n_layers, n_classes=n_classes,
        lanes=lanes, plan=plan, block_b=block_b, interpret=interpret,
    )
    return out[:B, 0]


def fused_dag_reference(x, models: list, plan: tuple) -> jax.Array:
    """jnp oracle for the megakernel: per-model MLP+argmax, plan folded on
    the verdicts.  ``models`` is a list of (weights, biases) lists."""
    verdicts = [
        jnp.argmax(mlp_ref(x, w, b), -1).astype(jnp.int32)
        for w, b in models
    ]
    return eval_dag_plan(plan, verdicts)
