"""SHGEMM Pallas TPU kernel: C_f32 = A_f32 @ B_lowp with on-the-fly splitting.

TPU-native adaptation of the paper's §4 kernel (DESIGN.md §2):

  * A is read from HBM as f32 tiles into VMEM; the hi/lo split (paper
    Eq. 37-38) happens **in VMEM on the VPU** — fused with the matmul, so the
    split costs no extra HBM traffic and no extra HBM residency (the paper's
    CUDA kernel does the same split in registers, §4.2 / Fig. 4).
  * B (the random matrix) is stored in bf16 (fp16 path kept for fidelity) —
    half the HBM bytes of an f32 B.
  * Two MXU passes per tile (hi@B, lo@B) accumulate into an f32 VMEM scratch
    accumulator; the K grid axis is `arbitrary` (sequential) so the
    accumulator carries across K steps.  f32 accumulation with RN is the MXU
    default — the paper's RZ-avoidance has no TPU analogue and is not needed.

Grid: (M/bm, N/bn, K/bk), K innermost.  Block shapes default to MXU-aligned
(128-multiples); VMEM footprint per grid step is
bm*bk*4 (A) + bk*bn*2 (B) + bm*bn*4 (acc) + bm*bn*4 (out) bytes
(double-buffered by the pipeline: ~2x for in/out blocks).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.splitting import FP16_INV_SCALE, FP16_SCALE

# Default tile sizes: MXU is 128x128; (8, 128) f32 VMEM tiling.  (256,256,512)
# keeps the working set ~1.1 MB (~2.2 MB double-buffered) << 16 MB VMEM while
# amortizing the VPU split over a deep K tile.  See EXPERIMENTS.md §Perf for
# the block-shape hillclimb.
DEFAULT_BM = 256
DEFAULT_BN = 256
DEFAULT_BK = 512


def _shgemm_kernel(a_ref, b_ref, o_ref, acc_ref, *, lowp_dtype, terms):
    """One (bm, bn) output tile, iterated over the sequential K grid axis."""
    @pl.when(pl.program_id(2) == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]  # (bm, bk) f32
    b = b_ref[...]  # (bk, bn) lowp
    # Paper Eq. (37)-(38), TPU form: split on the VPU, fused with the matmul;
    # one MXU pass per split term, f32 accumulation (preferred_element_type).
    acc = jnp.zeros_like(acc_ref)
    resid = a
    for t in range(terms):
        part = resid.astype(lowp_dtype)
        resid = resid - part.astype(jnp.float32)
        if lowp_dtype == jnp.float16 and t == 0 and terms > 1:
            resid = resid * FP16_SCALE  # paper's e5 renormalization
        term = jnp.dot(part, b, preferred_element_type=jnp.float32)
        if lowp_dtype == jnp.float16 and t == 1:
            term = term * FP16_INV_SCALE
        acc = acc + term
    acc_ref[...] += acc

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _store():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "terms", "interpret"))
def shgemm_pallas(a: jax.Array, b: jax.Array, *, bm: int = DEFAULT_BM,
                  bn: int = DEFAULT_BN, bk: int = DEFAULT_BK, terms: int = 2,
                  interpret: bool = False) -> jax.Array:
    """C[m,n] = A[m,k] @ B[k,n]; A f32, B bf16/fp16, C f32.

    Shapes must be multiples of the block sizes — ``ops.shgemm`` pads
    arbitrary shapes before calling this.
    """
    m, k = a.shape
    k2, n = b.shape
    if k != k2:
        raise ValueError(f"contraction mismatch: {a.shape} @ {b.shape}")
    if a.dtype != jnp.float32:
        raise TypeError(f"A must be f32, got {a.dtype}")
    if b.dtype not in (jnp.bfloat16, jnp.float16):
        raise TypeError(f"B must be bf16/fp16, got {b.dtype}")
    if m % bm or n % bn or k % bk:
        raise ValueError(f"shapes {(m, k, n)} not divisible by blocks {(bm, bk, bn)}")
    if terms not in (1, 2, 3) or (terms == 3 and b.dtype == jnp.float16):
        raise ValueError(f"terms={terms} unsupported for {b.dtype}")

    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        functools.partial(_shgemm_kernel, lowp_dtype=b.dtype, terms=terms),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(a, b)


def vmem_bytes(bm: int, bn: int, bk: int, b_dtype=jnp.bfloat16,
               fused: bool = False) -> int:
    """Claimed VMEM working set for a block configuration (double-buffered
    in/out blocks + single accumulator).

    ``fused``: the fused-RNG kernel (shgemm_fused.py) streams no B block from
    HBM, but holds the generated tile (f32 scratch pre-rounding) in VMEM,
    single-buffered.
    """
    b_bytes = jnp.dtype(b_dtype).itemsize
    if fused:
        return (2 * (bm * bk * 4 + bm * bn * 4) + bm * bn * 4
                + bk * bn * (4 + b_bytes))
    return 2 * (bm * bk * 4 + bk * bn * b_bytes + bm * bn * 4) + bm * bn * 4
