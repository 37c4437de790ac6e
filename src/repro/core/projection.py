"""Mixed-precision random projection (the paper's core primitive).

``Y = A @ Omega`` with A in f32 and Omega stored in a low-precision format.
Methods:

  * ``f32``          — baseline: full f32 GEMM (paper's cuBLAS SGEMM role).
  * ``lowp_single``  — single-pass low-precision GEMM: both operands cast to
                       bf16, one MXU pass (paper's "TF32 GEMM" role: fast but
                       lossy — degrades RandNLA accuracy, shown in Fig. 7).
  * ``shgemm``       — the paper's method: A split hi+lo, Omega in bf16/fp16,
                       two MXU passes, f32-level accuracy (Eq. 40).
  * ``shgemm_pallas``— same math via the Pallas TPU kernel (kernels/shgemm.py).
  * ``shgemm_fused`` — zero-HBM sketching: Omega is generated inside the
                       Pallas kernel from a PRNG key (kernels/shgemm_fused.py)
                       and never materialized.  Only ``sketch`` (key-based)
                       takes it; ``project`` (Omega-based) refuses it, since
                       a materialized Omega leaves nothing to fuse.

Random matrices: Gaussian (stored f32/bf16/fp16), Achlioptas sparse {-1,0,+1}
(Eq. 5), very-sparse (Li et al., s = sqrt(n) of the data dimension), and
SRHT (structured — ``sketch(dist="srht")`` applies in O(n log n) via
core/structured.py and never runs a GEMM at all).
"""

from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp

from repro.core.splitting import FP16_INV_SCALE, split_fp32

ProjectionMethod = Literal["f32", "lowp_single", "shgemm", "shgemm3",
                           "shgemm_pallas", "shgemm_fused"]
SketchDist = Literal["gaussian", "achlioptas", "very_sparse", "srht"]


# ---------------------------------------------------------------------------
# Random matrix generation
# ---------------------------------------------------------------------------

def gaussian(key: jax.Array, shape: tuple[int, ...], dtype=jnp.bfloat16) -> jax.Array:
    """N(0,1) Gaussian matrix generated in f32, RN-rounded to ``dtype``.

    Per paper §3.2 the rounded matrix has mean 0 and variance alpha_Y != 1,
    but Theorems 4/5 show the Halko bound is variance-invariant, so no
    rescaling is needed.  Beyond-paper: fp8 storage (e4m3/e5m2) is supported
    — the paper's Table 1 shows both formats keep >100 representable values
    within 2 sigma and negligible overflow, and our Fig. 3 sweep confirms
    projection accuracy down to 2 mantissa bits.
    """
    g = jax.random.normal(key, shape, dtype=jnp.float32)
    return g.astype(dtype)


def gaussian_fp8(key: jax.Array, shape: tuple[int, ...],
                 variant: str = "e4m3") -> jax.Array:
    """fp8-stored Gaussian random matrix (1/4 the HBM of f32 Omega)."""
    dt = jnp.float8_e4m3fn if variant == "e4m3" else jnp.float8_e5m2
    return gaussian(key, shape, dtype=dt)


def achlioptas_sparse(key: jax.Array, shape: tuple[int, ...], s: float = 3.0,
                      dtype=jnp.bfloat16) -> jax.Array:
    """Achlioptas sparse random matrix, Eq. (5), WITHOUT the sqrt(s) scale
    (paper §3.4: the scale cancels because only the orthonormal basis of the
    projection is used).  Entries in {-1, 0, +1} are exact in any format whose
    mantissa has the implicit bit — including fp8."""
    u = jax.random.uniform(key, shape, dtype=jnp.float32)
    v = jnp.where(u < 1.0 / (2.0 * s), -1.0, jnp.where(u < 1.0 / s, 1.0, 0.0))
    return v.astype(dtype)


def very_sparse(key: jax.Array, shape: tuple[int, ...],
                s: float | None = None, dtype=jnp.bfloat16) -> jax.Array:
    """Li et al. very sparse projection: s = sqrt(n) with n the DATA
    dimension (Omega's global row count).  The default is resolved through
    the fused kernel's ``_resolve_s`` (f64 ``math.sqrt``) so both paths
    share a bitwise-identical threshold; callers generating a partial row
    block must pass the global dimension's ``s`` explicitly."""
    from repro.kernels import shgemm_fused as _f
    return achlioptas_sparse(key, shape,
                             s=_f._resolve_s("very_sparse", s, shape[0]),
                             dtype=dtype)


def materialize_omega(key: jax.Array, shape: tuple[int, int], *,
                      dist: SketchDist = "gaussian", s: float | None = None,
                      dtype=jnp.bfloat16) -> jax.Array:
    """The legacy jax.random Omega for ``dist`` — the single dispatch shared
    by ``sketch`` and the streaming subsystem's non-fused partial-width
    updates (repro.stream), so the two can never draw different streams.

    ``s`` overrides the sparse dists' sparsity parameter (same semantics as
    ``fused_omega``/``ops.shgemm_fused``: explicit s wins, so partial tiles
    can match a one-shot sketch with non-default sparsity).  For ``srht``
    the dense matrix is the counter-lattice oracle from core/structured.py
    — identical to what the O(n log n) apply path implicitly applies.
    """
    if dist == "gaussian":
        return gaussian(key, shape, dtype=dtype)
    if dist == "achlioptas":
        return achlioptas_sparse(key, shape, s=(3.0 if s is None else s),
                                 dtype=dtype)
    if dist == "very_sparse":
        return very_sparse(key, shape, s=s, dtype=dtype)
    if dist == "srht":
        from repro.core import structured as _s
        return _s.srht_omega(key, shape, dtype=dtype)
    raise ValueError(f"unknown sketch distribution {dist!r}")


def fused_omega(key: jax.Array, shape: tuple[int, int], *,
                dist: SketchDist = "gaussian", s: float | None = None,
                dtype=jnp.bfloat16) -> jax.Array:
    """Materialize the exact Omega the fused kernel generates in VMEM.

    Bit-identical to the in-kernel stream (counter-based hash on the global
    element lattice — kernels/shgemm_fused.py's determinism contract), so
    consumers that need Omega downstream of the sketch (Nystrom, gradient
    compression) can pair it with a ``shgemm_fused`` projection, and tests
    can compare fused vs materialized paths exactly.
    """
    from repro.kernels import shgemm_fused as _f  # deferred: core stays light
    return _f.reference_omega(key, shape, dist=dist, s=s, dtype=dtype)


# ---------------------------------------------------------------------------
# Projection kernels
# ---------------------------------------------------------------------------

def _dot_f32(a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def _dot_mxu(a_lowp: jax.Array, b_lowp: jax.Array) -> jax.Array:
    """One MXU pass: low-precision inputs, f32 accumulation (TPU semantics).

    fp16 is not an MXU input format on TPU v5e: at default precision XLA
    feeds it through one bf16 pass, which drops 3 of its 11 bits.  fp16
    operands therefore take ``HIGHEST``, whose bf16 passes keep their
    products exact (DESIGN.md §2)."""
    fp16 = jnp.float16 in (a_lowp.dtype, b_lowp.dtype)
    return jnp.dot(a_lowp, b_lowp, preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST if fp16 else None)


def shgemm_jnp(a_f32: jax.Array, b_lowp: jax.Array) -> jax.Array:
    """Paper Eq. (37)-(40) on the MXU: C = A_hi.B + A_lo.B, f32 accumulation.

    ``b_lowp`` must already be bf16 or fp16 (it is the stored random matrix).
    With bf16 the correction term needs no 2^-11 rescale (DESIGN.md §2); with
    fp16 we apply the paper's exact scaling.
    """
    fmt = "fp16" if b_lowp.dtype == jnp.float16 else "bf16"
    hi, lo = split_fp32(a_f32, fmt)
    main = _dot_mxu(hi, b_lowp)
    corr = _dot_mxu(lo, b_lowp)
    if fmt == "fp16":
        return main + corr * FP16_INV_SCALE
    return main + corr


@functools.partial(jax.jit, static_argnames=("method",))
def project(a: jax.Array, omega: jax.Array,
            method: ProjectionMethod = "shgemm") -> jax.Array:
    """Y = A @ Omega with the selected mixed-precision strategy."""
    if omega.dtype in (jnp.float8_e4m3fn, jnp.float8_e5m2):
        # fp8 Omega is storage-only; MXU consumes bf16 (e8m7 superset of both)
        omega = omega.astype(jnp.bfloat16)
    if method == "f32":
        return _dot_f32(a, omega)
    if method == "lowp_single":
        return _dot_mxu(a.astype(jnp.bfloat16), omega.astype(jnp.bfloat16))
    if method == "shgemm":
        return shgemm_jnp(a.astype(jnp.float32), omega)
    if method == "shgemm3":
        # 3-term bf16 split: f32-level accuracy, 3 MXU passes (DESIGN.md §2).
        from repro.core.splitting import split_fp32_bf16_3
        hi, mid, lo = split_fp32_bf16_3(a)
        b = omega.astype(jnp.bfloat16)
        return (_dot_mxu(hi, b) + _dot_mxu(mid, b) + _dot_mxu(lo, b))
    if method == "shgemm_pallas":
        from repro.kernels import ops  # deferred: keeps core import-light
        return ops.shgemm(a.astype(jnp.float32), omega)
    if method == "shgemm_fused":
        raise ValueError(
            "project() takes a materialized Omega, so there is nothing for "
            "method='shgemm_fused' to fuse: call sketch(key, a, p, "
            "method='shgemm_fused') to generate Omega in the kernel, or "
            "project(..., method='shgemm_pallas') for the materialized "
            "Pallas kernel")
    raise ValueError(f"unknown projection method {method!r}")


@functools.partial(jax.jit, static_argnames=("p", "method", "dist", "s",
                                             "omega_dtype"))
def sketch(key: jax.Array, a: jax.Array, p: int, *,
           method: ProjectionMethod = "shgemm",
           dist: SketchDist = "gaussian", s: float | None = None,
           omega_dtype=jnp.bfloat16) -> jax.Array:
    """Y = A @ Omega(key)[a.shape[1], p] without the caller materializing
    Omega.

    This is the key-based front door for all RandNLA consumers (rsvd, hosvd,
    lstsq, galore):

      * ``dist="srht"`` — structured fast path: sign-flip + FWHT + column
        gather (core/structured.py), O(n log n) adds and NO (n x p) GEMM,
        regardless of ``method`` (there is no GEMM for the method to run;
        the heavy operand the mixed-precision split targets never exists).
      * ``method="shgemm_fused"`` — Omega costs zero HBM bytes: tiles are
        hashed into VMEM inside the Pallas kernel.
      * any other method — Omega is generated with the classic jax.random
        stream exactly as the consumers did before and fed to ``project``,
        so legacy results are unchanged.

    ``s`` (static) overrides the sparse dists' sparsity on BOTH the fused
    and legacy paths — previously only the fused kernel accepted it, so the
    two front doors silently diverged for non-default sparsity.
    """
    if dist == "srht":
        from repro.core import structured as _s
        return _s.srht_sketch(key, a, p)
    if method == "shgemm_fused":
        from repro.kernels import ops
        return ops.shgemm_fused(a.astype(jnp.float32), key, p, dist=dist,
                                s=s, omega_dtype=omega_dtype)
    omega = materialize_omega(key, (a.shape[1], p), dist=dist, s=s,
                              dtype=omega_dtype)
    return project(a, omega, method=method)
