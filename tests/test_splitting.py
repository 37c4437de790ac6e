"""Tests for the f32 mantissa splitting (paper Eq. 37-38, 43-44).

Property-based (hypothesis) residual-bound sweeps live in
test_property_based.py; here are fixed-value versions plus the overflow-mode
contrast, so the module runs even where hypothesis is not installed.
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import splitting

jax.config.update("jax_platform_name", "cpu")

# Spans the normalized f32 range incl. awkward points (near-bf16-midpoints,
# tiny/huge magnitudes, both signs).
_FIXED = np.array([1.0, -1.0, 1e-30, -1e30, 3.14159265, -2.7182818,
                   65504.0, 1.0009765625, -1.0000001, 6e4, 1e-2,
                   123456.789, -0.333333343], dtype=np.float32)


def test_bf16_split_bits_equal_cast_round_trip():
    """The split rounds with reduce_precision (which XLA on TPU keeps);
    on bf16 its bits are those of the f32 -> bf16 -> f32 round trip."""
    rng = np.random.default_rng(0)
    a = np.concatenate([_FIXED, (rng.standard_normal(4096)
                                 * 10.0 ** rng.uniform(-38, 38, 4096))
                        .astype(np.float32)])
    hi, lo = splitting.split_fp32_bf16(jnp.asarray(a))
    hi_cast = jnp.asarray(a).astype(jnp.bfloat16)
    lo_cast = (jnp.asarray(a) - hi_cast.astype(jnp.float32)).astype(
        jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(hi, np.float32),
                                  np.asarray(hi_cast, np.float32))
    np.testing.assert_array_equal(np.asarray(lo, np.float32),
                                  np.asarray(lo_cast, np.float32))


def test_fp16_split_flushes_fp16_subnormals_into_lo():
    """reduce_precision has no fp16 subnormals: below fp16's smallest normal
    (2^-14) hi is 0 and lo = fp16(a * 2^11) carries the value, where the
    paper's cast-based split (and the Pallas kernels) keep a subnormal hi.
    From 2^-14 up, hi is a's fp16 rounding as before."""
    tiny = np.array([3e-5, -1e-6, 6.0e-5, -2.0**-15, 1e-7], np.float32)
    normal = np.array([2.0**-14, -2.0**-14, 1.0], np.float32)
    hi, lo = splitting.split_fp32_fp16(jnp.asarray(np.concatenate([tiny,
                                                                   normal])))
    hi, lo = np.asarray(hi), np.asarray(lo)
    n = len(tiny)
    assert not np.any(hi[:n]) and np.all(tiny.astype(np.float16) != 0)
    np.testing.assert_array_equal(lo[:n], (tiny * 2.0**11).astype(np.float16))
    np.testing.assert_array_equal(hi[n:], normal.astype(np.float16))
    np.testing.assert_array_equal(lo[n:], np.zeros(len(normal), np.float16))


def test_bf16_split_residual_bound():
    """|a - hi - lo| <= u_bf16^2 * |a| (Eq. 44's A_Delta bound, bf16 form)."""
    a = jnp.asarray(_FIXED)
    hi, lo = splitting.split_fp32_bf16(a)
    resid = np.abs(np.asarray(a - splitting.merge_split(hi, lo)))
    u = 2.0**-8  # bf16 unit roundoff
    assert np.all(resid <= u * u * np.abs(_FIXED) + 1e-38)


def test_fp16_split_residual_bound():
    """Paper Eq. (44): |A_Delta| <= u_f16^2 |A| for in-range values."""
    in_range = _FIXED[(np.abs(_FIXED) >= 1e-2) & (np.abs(_FIXED) <= 6e4)]
    a = jnp.asarray(in_range)
    hi, lo = splitting.split_fp32_fp16(a)
    resid = np.abs(np.asarray(a - splitting.merge_split(hi, lo)))
    u = 2.0**-11
    assert np.all(resid <= u * u * np.abs(in_range) + 1e-30)


def test_bf16_3term_strictly_better():
    a = jnp.asarray(_FIXED)
    hi, mid, lo = splitting.split_fp32_bf16_3(a)
    r3 = np.abs(np.asarray(
        a - hi.astype(jnp.float32) - mid.astype(jnp.float32)
        - lo.astype(jnp.float32)))
    u = 2.0**-8
    assert np.all(r3 <= u**3 * np.abs(_FIXED) + 1e-38)


def test_fp16_overflow_mode():
    """bf16 split survives values beyond fp16 range; fp16 split does not
    (paper §5.1.1 Cauchy failure, DESIGN.md hardware-adaptation note)."""
    a = jnp.asarray([1e6, -3e8], dtype=jnp.float32)
    hi16, _ = splitting.split_fp32_fp16(a)
    assert np.all(np.isinf(np.asarray(hi16, np.float32)))
    hib, lob = splitting.split_fp32_bf16(a)
    assert np.all(np.isfinite(np.asarray(hib, np.float32)))
    err = np.asarray(a - splitting.merge_split(hib, lob))
    assert np.all(np.abs(err) <= 2.0**-16 * np.abs(np.asarray(a)))
