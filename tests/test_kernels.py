"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracle.

Each kernel is swept over shapes/dtypes with hypothesis and asserted
allclose against its ref.py.  Tolerances scale with depth/accumulation
length (fp32 reduce-order drift).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.flash_attention import attention_ref, flash_attention
from repro.kernels.fused_mlp import fused_mlp
from repro.kernels.fused_mlp.ref import mlp_ref
from repro.kernels.selective_scan import selective_scan, selective_scan_ref

HSET = settings(max_examples=12, deadline=None)


# ------------------------------------------------------------- fused_mlp


@given(
    f=st.integers(2, 64),
    c=st.integers(2, 16),
    b=st.integers(1, 300),
    depth=st.integers(0, 6),
    dtype=st.sampled_from(["float32", "bfloat16"]),
    data=st.data(),
)
@HSET
def test_fused_mlp_matches_oracle(f, c, b, depth, dtype, data):
    widths = [f] + [
        data.draw(st.sampled_from([4, 8, 16, 32, 64, 128]))
        for _ in range(depth)
    ] + [c]
    rng = np.random.default_rng(data.draw(st.integers(0, 2**31)))
    ws = [
        jnp.asarray(rng.normal(size=(widths[i], widths[i + 1])) * 0.3, dtype)
        for i in range(len(widths) - 1)
    ]
    bs = [
        jnp.asarray(rng.normal(size=(widths[i + 1],)) * 0.1, dtype)
        for i in range(len(widths) - 1)
    ]
    x = jnp.asarray(rng.normal(size=(b, f)), dtype)
    out = fused_mlp(x, ws, bs)
    ref = mlp_ref(x, ws, bs)
    assert out.shape == (b, c)
    tol = 1e-2 if dtype == "bfloat16" else 3e-4 * max(1, len(ws))
    scale = float(jnp.max(jnp.abs(ref))) + 1.0
    np.testing.assert_allclose(
        np.asarray(out, np.float32) / scale,
        np.asarray(ref, np.float32) / scale,
        atol=tol,
    )


def test_fused_mlp_wide_fallback():
    """Widths beyond the 128-lane envelope: the kernel op refuses with the
    reason, and the interpreter's stage walk falls back to the XLA
    reference."""
    from repro.core.stageir import FusedMLP

    rng = np.random.default_rng(0)
    ws = [jnp.asarray(rng.normal(size=(200, 64)), jnp.float32) * 0.1,
          jnp.asarray(rng.normal(size=(64, 3)), jnp.float32) * 0.1]
    bs = [jnp.zeros((64,)), jnp.zeros((3,))]
    x = jnp.asarray(rng.normal(size=(17, 200)), jnp.float32)
    with pytest.raises(ValueError, match="128-lane tile"):
        fused_mlp(x, ws, bs)
    out = FusedMLP([np.asarray(w) for w in ws],
                   [np.asarray(b) for b in bs]).apply(x)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(mlp_ref(x, ws, bs)), rtol=1e-5, atol=1e-5
    )


# -------------------------------------------------------- flash_attention


@given(
    b=st.integers(1, 2),
    sq=st.integers(4, 80),
    kext=st.integers(0, 64),
    hk=st.sampled_from([(1, 1), (2, 1), (4, 2), (4, 4), (8, 2)]),
    d=st.sampled_from([16, 32, 64]),
    causal=st.booleans(),
    window=st.sampled_from([0, 0, 16, 40]),
    dtype=st.sampled_from(["float32", "bfloat16"]),
    seed=st.integers(0, 2**31),
)
@HSET
def test_flash_attention_matches_oracle(
    b, sq, kext, hk, d, causal, window, dtype, seed
):
    h, k = hk
    skv = sq + kext
    q_offset = kext  # realistic: queries start after the cached prefix
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, sq, h, d)), dtype)
    kk = jnp.asarray(rng.normal(size=(b, skv, k, d)), dtype)
    v = jnp.asarray(rng.normal(size=(b, skv, k, d)), dtype)
    kw = dict(causal=causal, window=window, q_offset=q_offset)
    out = flash_attention(q, kk, v, block_q=16, block_k=16, **kw)
    ref = attention_ref(q, kk, v, **kw)
    tol = 3e-2 if dtype == "bfloat16" else 2e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol
    )


def test_flash_attention_matches_chunked_xla_twin():
    """The XLA chunked path (used by the dry-run) == the kernel semantics."""
    from repro.models.attention import chunked_attention

    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(2, 48, 4, 32)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 48, 2, 32)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 48, 2, 32)), jnp.float32)
    ref = attention_ref(q, k, v, causal=True)
    xla = chunked_attention(q, k, v, causal=True, kv_chunk=16)
    ker = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(xla), np.asarray(ref), atol=2e-5)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(ref), atol=2e-5)


# --------------------------------------------------------- selective_scan


@given(
    b=st.integers(1, 3),
    nchunks=st.integers(1, 4),
    chunk=st.sampled_from([8, 16, 32]),
    di=st.sampled_from([8, 16, 32, 64]),
    n=st.sampled_from([4, 8, 16]),
    seed=st.integers(0, 2**31),
)
@HSET
def test_selective_scan_matches_oracle(b, nchunks, chunk, di, n, seed):
    s = nchunks * chunk
    rng = np.random.default_rng(seed)
    dt = jnp.asarray(rng.uniform(0.01, 2.0, size=(b, s, di, 1)), jnp.float32)
    a = -jnp.asarray(rng.uniform(0.1, 3.0, size=(1, 1, di, n)), jnp.float32)
    dA = jnp.exp(dt * a)
    dBx = jnp.asarray(rng.normal(size=(b, s, di, n)), jnp.float32) * 0.2
    c = jnp.asarray(rng.normal(size=(b, s, n)), jnp.float32)
    h0 = jnp.asarray(rng.normal(size=(b, di, n)), jnp.float32) * 0.1
    y, h = selective_scan(dA, dBx, c, h0, chunk=chunk)
    yr, hr = selective_scan_ref(dA, dBx, c, h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), atol=1e-4)


def test_selective_scan_xla_twin_matches_oracle():
    """models.ssm chunked associative scan == sequential oracle."""
    from repro.models.ssm import _ssm_scan_chunked

    rng = np.random.default_rng(2)
    b, s, di, n = 2, 64, 32, 16
    dt = jnp.asarray(rng.uniform(0.01, 1.5, size=(b, s, di, 1)), jnp.float32)
    a = -jnp.asarray(rng.uniform(0.1, 2.0, size=(1, 1, di, n)), jnp.float32)
    dA = jnp.exp(dt * a)
    dBx = jnp.asarray(rng.normal(size=(b, s, di, n)), jnp.float32) * 0.2
    c = jnp.asarray(rng.normal(size=(b, s, n)), jnp.float32)
    h0 = jnp.zeros((b, di, n), jnp.float32)
    y, h = _ssm_scan_chunked(dA, dBx, c, h0, chunk=16)
    yr, hr = selective_scan_ref(dA, dBx, c, h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=1e-3)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), atol=1e-3)


def test_vmem_budget_accounting():
    from repro.kernels.fused_mlp.kernel import LANE, vmem_bytes

    v1 = vmem_bytes(1)
    v10 = vmem_bytes(10)
    assert v10 - v1 == 9 * (LANE * LANE * 4 + LANE * 4)


# --------------------------------------------------------- binarized_gemm


@given(
    b=st.integers(1, 64),
    k=st.integers(2, 200),
    n=st.integers(1, 64),
    seed=st.integers(0, 2**31),
)
@HSET
def test_binarized_gemm_bit_exact(b, k, n, seed):
    """±1 int8-MXU GEMM == sign(x) @ sign(w) exactly (N2Net primitive)."""
    from repro.kernels.binarized_gemm import binarized_gemm, binarized_gemm_ref

    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(b, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    out = binarized_gemm(x, w, block=16)
    ref = binarized_gemm_ref(x, w)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref, np.int32))
    # parity structure: result has the same parity as k
    assert np.all((np.asarray(out) - k) % 2 == 0)
