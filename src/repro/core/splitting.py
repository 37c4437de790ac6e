"""FP32 mantissa splitting for mixed-precision GEMM (paper Eq. 37-40, TPU-adapted).

The paper splits an FP32 matrix A into two FP16 matrices (hi + 2^-11 * lo) so the
product A_f32 @ B_f16 can run on FP16 Tensor Cores with f32-level accuracy.

TPU adaptation (see DESIGN.md §2): the MXU's native low-precision input is bf16
(e8m7).  bf16 shares FP32's 8-bit exponent, so

  * no 2^11 scaling of the correction term is needed (the residual is directly
    representable as a normalized bf16 except at the very bottom of the f32
    range), and
  * there is no overflow failure mode (the paper's Cauchy-matrix failure with
    FP16 disappears).

We keep a paper-faithful FP16 path (with the 2^11 scaling) for fidelity
experiments and for the error-bound comparison in the benchmarks.
"""

from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp

SplitFormat = Literal["bf16", "fp16"]

# 2^11 scaling from paper Eq. (38): FP16 has 10 explicit mantissa bits, and the
# residual A - fl16(A) lives ~11 bits below A's exponent, which can underflow in
# e5m10.  Scaling by 2^11 renormalizes it into FP16 range.
FP16_SCALE = 2.0**11
FP16_INV_SCALE = 2.0**-11


def _round_to(a: jax.Array, dtype) -> jax.Array:
    """``a`` rounded to nearest in ``dtype``'s precision, kept in f32.

    A ``reduce_precision`` op, not an f32 -> ``dtype`` -> f32 round trip: XLA
    on TPU may skip such a round trip (excess precision), which makes the
    split's low term ``a - hi`` zero and the split GEMM a single low-precision
    pass.  For bf16 the bits equal the round trip's; for fp16, values in
    fp16's subnormal range round to zero and the low term carries them."""
    fi = jnp.finfo(dtype)
    return jax.lax.reduce_precision(a, exponent_bits=fi.nexp,
                                    mantissa_bits=fi.nmant)


def split_fp32_bf16(a: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Split f32 ``a`` into (hi, lo) bf16 with a ~ hi + lo.

    hi = RN_bf16(a); lo = RN_bf16(a - f32(hi)).  Because bf16 has f32's exponent
    range, lo needs no rescaling (hardware adaptation vs. paper Eq. 38).
    The residual a - hi - lo carries ~0.25 bit of mantissa on average
    (paper §4.3 / [34]).
    """
    a = a.astype(jnp.float32)
    hi = _round_to(a, jnp.bfloat16)
    return hi.astype(jnp.bfloat16), (a - hi).astype(jnp.bfloat16)


def split_fp32_fp16(a: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Paper-faithful Eq. (37)-(38) split: a ~ hi + lo * 2^-11, hi/lo in fp16.

    Raises no error on overflow: values outside fp16 range become inf, exactly
    reproducing the paper's §5.1.1 Cauchy failure mode (used in benchmarks).
    """
    a = a.astype(jnp.float32)
    hi = _round_to(a, jnp.float16)
    return hi.astype(jnp.float16), ((a - hi) * FP16_SCALE).astype(jnp.float16)


def split_fp32_bf16_3(a: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """3-term bf16 split: a ~ hi + mid + lo, carrying ~24 mantissa bits.

    TPU-specific accuracy ladder (DESIGN.md §2): bf16 carries 8 bits per term,
    so the paper's 2-term structure yields ~16 effective bits (measured rel.
    err ~2.5e-6); the 3-term variant restores full f32-level accuracy at 3/2
    the MXU work (still half of XLA's 6-pass f32 emulation).
    """
    a = a.astype(jnp.float32)
    hi = _round_to(a, jnp.bfloat16)
    mid = _round_to(a - hi, jnp.bfloat16)
    lo = a - hi - mid
    return (hi.astype(jnp.bfloat16), mid.astype(jnp.bfloat16),
            lo.astype(jnp.bfloat16))


def split_fp32(a: jax.Array, fmt: SplitFormat = "bf16") -> tuple[jax.Array, jax.Array]:
    if fmt == "bf16":
        return split_fp32_bf16(a)
    if fmt == "fp16":
        return split_fp32_fp16(a)
    raise ValueError(f"unknown split format {fmt!r}")


def merge_split(hi: jax.Array, lo: jax.Array) -> jax.Array:
    """Inverse of split_fp32 (up to the ~0.25-bit residual)."""
    if hi.dtype == jnp.float16:
        return hi.astype(jnp.float32) + lo.astype(jnp.float32) * FP16_INV_SCALE
    return hi.astype(jnp.float32) + lo.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("fmt",))
def split_residual(a: jax.Array, fmt: SplitFormat = "bf16") -> jax.Array:
    """The A_Delta term of paper Eq. (43): what the 2-term split cannot carry."""
    hi, lo = split_fp32(a, fmt)
    return a.astype(jnp.float32) - merge_split(hi, lo)
