"""Pallas TPU kernel: Mamba S6 selective scan with VMEM-resident state.

TPU adaptation of the CUDA selective-scan (Mamba) kernel.  The GPU version
keys on warp-level shuffles for the intra-block scan; TPUs have no warp
shuffles, but they have something better for this access pattern: a large
VMEM scratch that persists across sequential grid steps.  So:

  * grid = (B, S / chunk) with the chunk dim minor (TPU grids execute
    sequentially) — the recurrent state h [di, N] lives in VMEM scratch and
    is carried across chunks *without ever touching HBM*.  A naive XLA
    lowering materializes h [B, S, di, N] (seq_len x d_state larger than the
    activations themselves) in HBM; this kernel's HBM traffic is exactly
    inputs + outputs.
  * within a chunk the recurrence is a VPU elementwise loop over time steps
    (dA_t * h + dBx_t) with the [di, N] state resident in vector registers /
    VMEM; the y readout contracts over N via an MXU-free elementwise-sum
    (N = 16 << 128 lanes, so a matmul would waste the MXU anyway).

The log-space cumprod trick (chunked associative form, used by the XLA twin
in models/ssm.py) is deliberately NOT used here: dA = exp(dt*A) < 1 decays,
and chunk-length cumprods underflow fp32 for large |dt*A| — the sequential
VMEM loop is both exact and bandwidth-optimal.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scan_kernel(
    dA_ref,    # [1, chunk, di, N]
    dBx_ref,   # [1, chunk, di, N]
    c_ref,     # [1, chunk, N]
    h0_ref,    # [1, di, N]
    y_ref,     # out [1, chunk, di]
    hout_ref,  # out [1, di, N]
    h_scr,     # scratch [di, N] f32 (persists across chunk grid steps)
    *,
    chunk: int,
    num_chunks: int,
):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = h0_ref[0]

    def step(t, h):
        dA_t = dA_ref[0, t]      # [di, N]
        dBx_t = dBx_ref[0, t]
        h = dA_t * h + dBx_t
        c_t = c_ref[0, t]        # [N]
        y_t = jnp.sum(h * c_t[None, :], axis=-1)  # [di]
        y_ref[pl.ds(0, 1), pl.ds(t, 1), :] = y_t[None, None, :]
        return h

    h = jax.lax.fori_loop(0, chunk, step, h_scr[...])
    h_scr[...] = h

    @pl.when(ci == num_chunks - 1)
    def _final():
        hout_ref[0] = h


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def selective_scan_pallas(
    deltaA: jax.Array,   # [B, S, di, N] f32
    deltaBx: jax.Array,  # [B, S, di, N] f32
    C: jax.Array,        # [B, S, N] f32
    h0: jax.Array,       # [B, di, N] f32
    *,
    chunk: int = 64,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    B, S, di, N = deltaA.shape
    chunk = min(chunk, S)
    assert S % chunk == 0, f"S={S} must divide chunk={chunk}"
    num_chunks = S // chunk
    grid = (B, num_chunks)

    kernel = functools.partial(
        _scan_kernel, chunk=chunk, num_chunks=num_chunks
    )
    y, h_final = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, di, N), lambda b, ci: (b, ci, 0, 0)),
            pl.BlockSpec((1, chunk, di, N), lambda b, ci: (b, ci, 0, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, ci: (b, ci, 0)),
            pl.BlockSpec((1, di, N), lambda b, ci: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, di), lambda b, ci: (b, ci, 0)),
            pl.BlockSpec((1, di, N), lambda b, ci: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, di), jnp.float32),
            jax.ShapeDtypeStruct((B, di, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((di, N), jnp.float32)],
        interpret=interpret,
    )(deltaA, deltaBx, C, h0)
    return y, h_final
