"""C2: the NTX offload model — interpreter, AGU math, Table 2 counts."""

import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis")
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import ntx
from repro.lower.rules import conv2d_fwd_template, matmul_template


@settings(max_examples=50, deadline=None)
@given(
    st.lists(st.integers(1, 9), min_size=5, max_size=5),
    st.lists(st.integers(-50, 50), min_size=5, max_size=5),
)
def test_strides_steps_roundtrip(loops, strides):
    steps = ntx.strides_to_steps(strides, loops)
    back = ntx.steps_to_strides(steps, loops)
    assert back == list(strides)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 5), st.integers(1, 5), st.integers(1, 6))
def test_interpreter_matmul(m, n, k):
    rng = np.random.RandomState(m * 100 + n * 10 + k)
    a = rng.randn(m, k).astype(np.float32)
    b = rng.randn(k, n).astype(np.float32)
    mem = np.zeros(500, np.float32)
    mem[: m * k] = a.ravel()
    mem[100 : 100 + k * n] = b.ravel()
    cmd = matmul_template(m, n, k, 0, 100, 300)
    out = ntx.ntx_execute(cmd, mem)
    ref = a.astype(np.float64) @ b.astype(np.float64)
    np.testing.assert_allclose(out[300 : 300 + m * n].reshape(m, n), ref, rtol=1e-5)


def test_interpreter_wide_beats_fpu():
    """wide=True (PCS model) must beat wide=False (fp32 FPU) vs fp64."""
    rng = np.random.RandomState(0)
    k = 4096
    a = (rng.randn(1, k) * 10.0 ** rng.uniform(-3, 3, (1, k))).astype(np.float32)
    b = rng.randn(k, 1).astype(np.float32)
    mem = np.zeros(3 * k + 10, np.float32)
    mem[:k] = a.ravel()
    mem[k : 2 * k] = b.ravel()
    cmd = matmul_template(1, 1, k, 0, k, 3 * k)
    ref = np.dot(a.astype(np.float64), b.astype(np.float64))[0, 0]
    wide = ntx.ntx_execute(cmd, mem, wide=True)[3 * k]
    fpu = ntx.ntx_execute(cmd, mem, wide=False)[3 * k]
    assert abs(wide - ref) <= abs(fpu - ref)


def test_table2_offload_counts():
    """Exact reproduction of paper Table 2."""
    rows = [
        (ntx.ConvShape(7, 7, 3, 112, 112, 64), 802_816, 64, 147, 1_843_968),
        (ntx.ConvShape(3, 3, 64, 56, 56, 192), 602_112, 192, 576, 1_806_336),
        (ntx.ConvShape(1, 1, 256, 28, 28, 64), 50_176, 64, 256, 200_704),
        (ntx.ConvShape(1, 1, 512, 14, 14, 192), 37_632, 192, 512, 100_352),
    ]
    for conv, ns_off, ntx_off, ns_cyc, ntx_cyc in rows:
        assert ntx.offload_count(conv, **ntx.NS_LOOPS) == ns_off
        assert ntx.offload_count(conv, **ntx.NTX_LOOPS) == ntx_off
        assert ntx.busy_cycles_per_offload(conv, **ntx.NS_LOOPS) == ns_cyc
        assert ntx.busy_cycles_per_offload(conv, **ntx.NTX_LOOPS) == ntx_cyc


def test_conv_command_matches_numpy():
    rng = np.random.RandomState(3)
    ih, iw, ci, kh, kw = 7, 8, 3, 3, 2
    x = rng.randn(ih, iw, ci).astype(np.float32)
    w = rng.randn(kh, kw, ci).astype(np.float32)
    mem = np.zeros(2000, np.float32)
    mem[: x.size] = x.ravel()
    mem[500 : 500 + w.size] = w.ravel()
    cmd = conv2d_fwd_template(ih, iw, ci, kh, kw, 1, 0, 500, 1000)
    out = ntx.ntx_execute(cmd, mem)
    oh, ow = ih - kh + 1, iw - kw + 1
    got = out[1000 : 1000 + oh * ow].reshape(oh, ow)
    want = np.zeros((oh, ow), np.float32)
    for i in range(oh):
        for j in range(ow):
            want[i, j] = float((x[i : i + kh, j : j + kw] * w).sum())
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_command_semantics_match_pallas_matmul():
    """C2 closed loop: the NtxCommand interpreter and the Pallas ntx_matmul
    kernel compute the same contraction (offload model == TPU kernel)."""
    import jax.numpy as jnp

    from repro.kernels import ops

    rng = np.random.RandomState(11)
    m, n, k = 8, 6, 12
    a = rng.randn(m, k).astype(np.float32)
    b = rng.randn(k, n).astype(np.float32)
    mem = np.zeros(1000, np.float32)
    mem[: m * k] = a.ravel()
    mem[200 : 200 + k * n] = b.ravel()
    cmd = matmul_template(m, n, k, 0, 200, 500)
    want = ntx.ntx_execute(cmd, mem)[500 : 500 + m * n].reshape(m, n)
    got = ops.matmul(jnp.asarray(a), jnp.asarray(b), backend="interpret")
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)
