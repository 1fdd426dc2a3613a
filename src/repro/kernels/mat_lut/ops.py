"""Public op: fused MAT (quantized-LUT) pipeline inference.

``mat_classify(x, edges, tables, label_map)`` pads/packs, launches the
Pallas kernel (interpret=True on CPU — the TPU path is the same kernel
compiled by Mosaic), and returns int32 verdicts.  This is the executable
artifact the Pallas serving backend (core.pallas_backend) emits for
Tofino-style Quantize -> LUTGather -> Reduce -> LabelMap stage pipelines.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.mat_lut.kernel import (
    DEFAULT_BLOCK_B,
    LANE,
    mat_pipeline_padded,
)
from repro.kernels.mat_lut.ref import mat_pipeline_ref

# kernel envelope: per-feature MATs are unrolled statically, tables must
# sit in VMEM, verdict lanes in one tile
MAX_FEATURES = 64
MAX_BINS = 1024


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _snap(n: int, tile: int) -> int:
    return max(tile, -(-n // tile) * tile)


def envelope_reason(tables, label_map) -> str | None:
    """Why a MAT pipeline is outside the kernel's envelope (None =
    inside): too many features/bins for resident VMEM tables, or more
    classes/labels than one lane tile."""
    F, bins, C = (int(n) for n in tables.shape)
    K = int(label_map.shape[0])
    if F > MAX_FEATURES:
        return f"{F} features exceed MAX_FEATURES={MAX_FEATURES}"
    if bins > MAX_BINS:
        return f"{bins} bins exceed MAX_BINS={MAX_BINS}"
    if max(C, K) > LANE:
        return f"{max(C, K)} classes exceed the {LANE}-lane tile"
    return None


def mat_classify(
    x: jax.Array,          # [B, F] f32
    edges: jax.Array,      # [F, BINS-1]
    tables: jax.Array,     # [F, BINS, C]
    label_map: jax.Array,  # [K] int
    *,
    use_min: bool = False,
    block_b: int = DEFAULT_BLOCK_B,
    interpret: bool | None = None,
) -> jax.Array:
    """x: [B, F] -> verdicts [B] int32, the whole MAT pipeline fused.

    Raises ``ValueError`` outside the kernel envelope (the lowering
    declines such tables first, so there is no silent reference path)."""
    if interpret is None:
        interpret = not _on_tpu()
    reason = envelope_reason(tables, label_map)
    if reason:
        raise ValueError(reason)
    B, F = x.shape
    bins, C = tables.shape[1], tables.shape[2]
    K = label_map.shape[0]
    # CPU interpret mode snaps pads to 8-wide tiles; TPU pads last dims to
    # the full 128 lane (second-to-last / leading dims only need sublanes)
    tile = 8 if interpret else LANE
    block_b = min(block_b, max(8, B))
    pad_b = (-B) % block_b
    x_pad = jnp.pad(
        jnp.asarray(x, jnp.float32),
        ((0, pad_b), (0, _snap(F, tile) - F)),   # features are x's LAST dim
    )
    e_pad = _snap(edges.shape[1], tile)
    edges_pad = jnp.pad(
        jnp.asarray(edges, jnp.float32),
        ((0, _snap(F, 8) - F), (0, e_pad - edges.shape[1])),
        constant_values=jnp.inf,      # padded edges never count into buckets
    )
    c_pad = _snap(C, tile)
    tables_pad = jnp.pad(
        jnp.asarray(tables, jnp.float32),
        ((0, _snap(F, 8) - F), (0, _snap(bins, tile) - bins),
         (0, c_pad - C)),
    )
    lmap_pad = jnp.pad(
        jnp.asarray(label_map, jnp.float32), (0, _snap(K, tile) - K)
    )[None, :]
    out = mat_pipeline_padded(
        x_pad, edges_pad, tables_pad, lmap_pad,
        n_features=F, n_classes=C, use_min=use_min,
        block_b=block_b, interpret=interpret,
    )
    return out[:B, 0]


def mat_classify_reference(x, edges, tables, label_map, *, use_min=False):
    return mat_pipeline_ref(x, edges, tables, label_map, use_min=use_min)
