"""Unit tests for ft/compress.py and the shared int8 quantizer it now
re-exports from kernels/quant.py — round-trip error bounds, the
explicit all-zero-row guard and metric-space radius bounds (the
cross-pod mean itself is exercised on an 8-device mesh in
test_distributed.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ft.compress import dequantize_int8, quantize_int8
from repro.kernels import quant


def test_quantize_roundtrip_error_bound():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((32, 128)).astype(np.float32) * 5)
    q, s = quantize_int8(x)
    assert q.dtype == jnp.int8
    deq = dequantize_int8(q, s)
    # per-row max-abs scaling → absolute error ≤ scale/2 per element
    err = np.max(np.abs(np.asarray(deq - x)), axis=-1)
    bound = np.asarray(s)[:, 0]
    assert np.all(err <= bound), (err, bound)


def test_compress_quantizer_is_the_shared_one():
    """ft/compress and the kernels must quantize through one function:
    the re-export is identity, not a copy that could drift."""
    assert quantize_int8 is quant.quantize_int8
    assert dequantize_int8 is quant.dequantize_int8


def test_quantize_all_zero_row_guard():
    """All-zero rows get scale exactly 0.0 (not the historic 1e-20
    denormal floor): q == 0, dequant == exact zeros, radius == 0."""
    x = jnp.zeros((3, 16), jnp.float32)
    q, s = quantize_int8(x)
    np.testing.assert_array_equal(np.asarray(q), 0)
    assert np.all(np.asarray(s) == 0.0)          # exactly 0.0, not tiny
    np.testing.assert_array_equal(np.asarray(dequantize_int8(q, s)), 0.0)
    for metric in ("l2", "l2sq", "l1"):
        r = quant.quant_row_radius(s[:, 0], 16, metric)
        np.testing.assert_array_equal(np.asarray(r), 0.0)
    # mixed batch: zero rows keep the exact-zero guarantee alongside
    # normal rows, and sub-denormal rows never produce inf/NaN (XLA may
    # flush them to zero — then scale is exactly 0.0, same as zero rows,
    # consistent with what the FTZ exact kernel sees)
    x2 = jnp.asarray(np.array([[0.0] * 8,
                               [1e-42] * 8,
                               [3.0] + [0.0] * 7], np.float32))
    q2, s2 = quantize_int8(x2)
    deq2 = np.asarray(dequantize_int8(q2, s2))
    assert np.all(np.isfinite(deq2))
    np.testing.assert_array_equal(deq2[0], 0.0)
    err = np.abs(deq2 - np.asarray(x2))
    live = np.asarray(s2)[:, 0] > 0.0
    assert np.all(err[live] <= np.asarray(s2)[live] * quant.ELEM_ERR)


@pytest.mark.parametrize("metric", ["l2", "l2sq", "l1"])
def test_quant_row_radius_bounds_roundtrip_distance(metric):
    """The per-row radius must dominate the metric distance between a
    row and its dequantized image — the triangle-inequality ingredient
    of every certified lower bound downstream."""
    rng = np.random.default_rng(7)
    scales = np.array([1e-3, 1.0, 50.0], np.float32)
    x = rng.standard_normal((len(scales), 24, 48)).astype(np.float32)
    x = (x * scales[:, None, None]).reshape(-1, 48)
    rows = quant.quantize_rows(jnp.asarray(x), metric)
    deq = np.asarray(dequantize_int8(rows.q, rows.scale))
    diff = deq - x
    if metric == "l1":
        d = np.abs(diff).sum(-1)
    else:
        d = np.sqrt((diff * diff).sum(-1))   # radius is in distance units
    assert np.all(d <= np.asarray(rows.radius) + 1e-30), metric


def test_crosspod_leaf_has_no_removed_api_calls():
    """Regression: _crosspod_leaf called jax.lax.axis_size, removed from
    the installed JAX — it needs no axis size, as the gathered leading
    dim carries it."""
    import inspect

    from repro.ft import compress
    assert "jax.lax.axis_size" not in inspect.getsource(
        compress._crosspod_leaf)
