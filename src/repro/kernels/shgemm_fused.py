"""Fused RNG+SHGEMM Pallas kernel: C_f32 = A_f32 @ Omega(key) with Omega
generated **inside** the kernel — zero HBM bytes and zero HBM bandwidth for
the random matrix.

The materialized-Omega kernel (shgemm.py) already halves Omega's HBM traffic
by storing it in bf16; at rSVD-typical aspect ratios Omega reads are still
~40% of the projection's HBM bytes.  The logical limit of the paper's idea is
to never materialize Omega at all: each (bk, bn) tile of the random matrix is
generated in VMEM on the VPU, rounded to bf16/fp16, and consumed by the same
hi/lo two-pass MXU accumulation (paper Eq. 37-40).  HBM traffic drops to A
reads + C writes alone.

Determinism contract (DESIGN.md §9):

  * Every Omega element is a pure function of ``(key, row, col)`` — a
    counter-based hash over the **global** element lattice, not a sequential
    stream.  The bits are therefore invariant to the grid schedule, to the
    block shape ``(bm, bn, bk)``, and to padding.  (The uint32 *bits* are
    bit-exact on any backend; the Gaussian float samples go through
    log/cos, which XLA does not promise bit-identical across backends or
    versions — sparse dists use only exact float ops and stay bit-exact.)
  * Consequently C is bit-identical across block configurations that share
    ``bk`` (f32 accumulation order over K is fixed by ``bk``); across
    different ``bk`` results differ only by f32 summation order (~1 ulp).
  * ``reference_omega`` reproduces the in-kernel samples exactly with plain
    jnp ops, so ``shgemm(a, reference_omega(key, ...))`` with equal blocks is
    bit-identical to the fused kernel — the property the tests pin down.

Why not ``pltpu.prng_random_bits``?  The hardware PRNG's stream layout
depends on the shape of each request, so per-tile draws would make the bits a
function of the block shape, breaking the contract above (and it has no
interpret-mode story for the CPU CI).  The counter hash below runs on the
VPU's uint32 lanes either way; two murmur3 finalizer rounds per 32-bit word
give full avalanche, which is plenty for JL sketching (cf. Squares/Philox,
which these moment- and rSVD-level tests cannot distinguish from true i.i.d.).

Distributions: ``gaussian`` (Box–Muller from two hashed 24-bit uniforms, so
mean 0 / variance 1 exactly in distribution), ``achlioptas`` (paper Eq. 5
thresholding, entries {-1, 0, +1} without the sqrt(s) scale — §3.4), and
``very_sparse`` (Li et al., s = sqrt(k), k the DATA dimension — Omega's
global row count, not a tile's local extent; see ``_resolve_s``).

Structured families (SRHT, Khatri–Rao) live in ``core/structured.py`` on the
same counter lattice; their apply paths bypass the GEMM entirely, so this
kernel rejects them (``ops.shgemm_fused`` raises with a pointer).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.splitting import FP16_INV_SCALE, FP16_SCALE

SKETCH_DISTS = ("gaussian", "achlioptas", "very_sparse")

# murmur3 finalizer constants + golden-ratio lane salts.
_M1 = 0x85EBCA6B
_M2 = 0xC2B2AE35
_ROW_SALT = 0x9E3779B9
_COL_SALT = 0x7F4A7C15
_STREAM_SALT = 0x632BE59B

_TWO_NEG_24 = float(2.0**-24)
_TWO_NEG_25 = float(2.0**-25)


def _fmix32(h: jax.Array) -> jax.Array:
    """murmur3 finalizer: full avalanche on a uint32 word."""
    h = h ^ (h >> 16)
    h = h * jnp.uint32(_M1)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(_M2)
    h = h ^ (h >> 16)
    return h


def counter_bits(k0: jax.Array, k1: jax.Array, rows: jax.Array,
                 cols: jax.Array, stream: int) -> jax.Array:
    """Avalanched uint32 for each (row, col) lattice point of draw ``stream``.

    Pure function of (key, global indices) — the determinism contract's core.
    """
    hr = _fmix32(rows.astype(jnp.uint32) * jnp.uint32(_ROW_SALT) + k0)
    hc = _fmix32(cols.astype(jnp.uint32) * jnp.uint32(_COL_SALT) + k1
                 + jnp.uint32(stream) * jnp.uint32(_STREAM_SALT))
    return _fmix32(hr ^ (hc * jnp.uint32(_M1)))


def _uniform24(bits: jax.Array, offset: float = 0.0) -> jax.Array:
    """Top 24 bits -> f32 uniform on [0,1) (+offset shifts off exact zero).

    The conversion goes through int32: Mosaic has no uint32 -> f32 cast, and
    a 24-bit value is non-negative in int32 and exact in f32, so the result
    is the same on every backend."""
    return ((bits >> 8).astype(jnp.int32).astype(jnp.float32) * _TWO_NEG_24
            + offset)


def sample_tile(k0: jax.Array, k1: jax.Array, rows: jax.Array,
                cols: jax.Array, *, dist: str, s: float) -> jax.Array:
    """f32 samples (pre-rounding) for the global index tiles rows x cols.

    ``rows``/``cols`` are broadcast-compatible int32 index arrays; runs
    unchanged inside the kernel (VPU) and on the host (reference_omega).
    """
    if dist == "gaussian":
        u1 = _uniform24(counter_bits(k0, k1, rows, cols, 0), _TWO_NEG_25)
        u2 = _uniform24(counter_bits(k0, k1, rows, cols, 1))
        r = jnp.sqrt(-2.0 * jnp.log(u1))
        return r * jnp.cos((2.0 * math.pi) * u2)
    if dist in ("achlioptas", "very_sparse"):
        u = _uniform24(counter_bits(k0, k1, rows, cols, 0))
        return jnp.where(u < 1.0 / (2.0 * s), -1.0,
                         jnp.where(u < 1.0 / s, 1.0, 0.0)).astype(jnp.float32)
    raise ValueError(f"unknown sketch distribution {dist!r}")


def key_words(key: jax.Array) -> jax.Array:
    """(1, 2) uint32 words from a jax PRNG key (typed or raw uint32)."""
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    data = key.astype(jnp.uint32).reshape(-1)
    if data.shape[0] == 1:
        data = jnp.stack([data[0], data[0] ^ jnp.uint32(_ROW_SALT)])
    return data[:2].reshape(1, 2)


def _resolve_s(dist: str, s: float | None, k: int) -> float:
    """Sparsity parameter for the sign dists.

    An EXPLICIT ``s`` always wins — callers sketching a partial row block
    (streamed column tiles, Psi streams) must pass the s of the GLOBAL data
    dimension or the tile would silently draw from a different distribution
    than the one-shot sketch.  Defaults: Achlioptas s=3; very_sparse
    s = sqrt(k) with k the data dimension = Omega's (global) row count
    (Li et al. 2006) — computed in f64 ``math.sqrt`` everywhere so the
    threshold is bitwise-shared across the legacy and fused paths.
    """
    if s is not None:
        return float(s)
    if dist == "very_sparse":
        return float(math.sqrt(k))
    return 3.0


def reference_omega(key: jax.Array, shape: tuple[int, int], *,
                    dist: str = "gaussian", s: float | None = None,
                    dtype=jnp.float32, row_offset=0, col_offset=0) -> jax.Array:
    """Materialize the exact Omega the fused kernel consumes (oracle path).

    Used by the agreement tests, by consumers that need Omega downstream
    anyway (Nystrom, gradient compression), and by anyone who wants the
    fused stream without the fused kernel.

    ``row_offset``/``col_offset`` (int or traced scalar) shift the global
    element lattice: the result equals ``reference_omega(key, big)[r0:, c0:]``
    restricted to ``shape`` — the block-regeneration property the streaming
    subsystem (repro.stream) is built on.
    """
    k, n = shape
    kw = key_words(key)
    rows = (jnp.arange(k, dtype=jnp.int32)[:, None]
            + jnp.asarray(row_offset, jnp.int32))
    cols = (jnp.arange(n, dtype=jnp.int32)[None, :]
            + jnp.asarray(col_offset, jnp.int32))
    vals = sample_tile(kw[0, 0], kw[0, 1], rows, cols, dist=dist,
                       s=_resolve_s(dist, s, k))
    return vals.astype(dtype)


def _fused_kernel(key_ref, offs_ref, a_ref, o_ref, acc_ref, *, store_dtype,
                  lowp_dtype, terms, dist, s, bn, bk):
    """One (bm, bn) output tile over the sequential K axis; the B tile is
    hashed into existence in VMEM instead of streamed from HBM."""
    @pl.when(pl.program_id(2) == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    k0 = key_ref[0, 0]
    k1 = key_ref[0, 1]
    # Global element lattice for this (j, kk) tile: bits depend on the
    # absolute indices only, never on the block shape or grid order.  The
    # SMEM offsets shift the lattice so a streamed tile draws exactly the
    # (row_offset+i, col_offset+j) block of the one-shot Omega.
    rows = (offs_ref[0, 0] + pl.program_id(2) * bk
            + jax.lax.broadcasted_iota(jnp.int32, (bk, bn), 0))
    cols = (offs_ref[0, 1] + pl.program_id(1) * bn
            + jax.lax.broadcasted_iota(jnp.int32, (bk, bn), 1))
    # Round through the storage format (fp8 study path: store_dtype=e4m3/e5m2,
    # consumed as bf16 — exactly what project() does with a materialized fp8
    # Omega), then to the MXU input dtype.
    b = sample_tile(k0, k1, rows, cols, dist=dist, s=s)
    if store_dtype != lowp_dtype:
        b = b.astype(store_dtype)
    b = b.astype(lowp_dtype)

    a = a_ref[...]  # (bm, bk) f32
    # Same hi/lo split + two-pass MXU accumulation as shgemm.py.
    acc = jnp.zeros_like(acc_ref)
    resid = a
    for t in range(terms):
        part = resid.astype(lowp_dtype)
        resid = resid - part.astype(jnp.float32)
        if lowp_dtype == jnp.float16 and t == 0 and terms > 1:
            resid = resid * FP16_SCALE
        term = jnp.dot(part, b, preferred_element_type=jnp.float32)
        if lowp_dtype == jnp.float16 and t == 1:
            term = term * FP16_INV_SCALE
        acc = acc + term
    acc_ref[...] += acc

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _store():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit,
                   static_argnames=("n", "bm", "bn", "bk", "terms", "dist",
                                    "s", "store_dtype", "lowp_dtype",
                                    "interpret"))
def shgemm_fused_pallas(a: jax.Array, key2: jax.Array, n: int, *,
                        bm: int, bn: int, bk: int, terms: int = 2,
                        dist: str = "gaussian", s: float = 3.0,
                        store_dtype=None, lowp_dtype=jnp.bfloat16,
                        offsets: jax.Array | None = None,
                        interpret: bool = False) -> jax.Array:
    """C[m, n] = A[m, k] @ Omega(key)[k+r0, n+c0]; Omega never touches HBM.

    Shapes must be multiples of the block sizes — ``ops.shgemm_fused`` pads
    arbitrary shapes before calling this (A's zero pad rows null out the
    extra generated Omega rows, so padding never changes the result).

    ``offsets`` is a (1, 2) int32 array ``[[row_offset, col_offset]]``
    shifting the generated Omega's global lattice (dynamic — may be traced,
    e.g. inside a scan over streamed tiles).  None means (0, 0).
    """
    m, k = a.shape
    if offsets is None:
        offsets = jnp.zeros((1, 2), jnp.int32)
    if offsets.shape != (1, 2) or offsets.dtype != jnp.int32:
        raise ValueError(f"offsets must be (1, 2) int32, got "
                         f"{offsets.shape}/{offsets.dtype}")
    if a.dtype != jnp.float32:
        raise TypeError(f"A must be f32, got {a.dtype}")
    if key2.shape != (1, 2) or key2.dtype != jnp.uint32:
        raise ValueError(f"key2 must be (1, 2) uint32, got "
                         f"{key2.shape}/{key2.dtype}")
    if lowp_dtype not in (jnp.bfloat16, jnp.float16):
        raise TypeError(f"Omega dtype must be bf16/fp16, got {lowp_dtype}")
    if m % bm or n % bn or k % bk:
        raise ValueError(f"shapes {(m, k, n)} not divisible by blocks "
                         f"{(bm, bk, bn)}")
    if terms not in (1, 2, 3) or (terms == 3 and lowp_dtype == jnp.float16):
        raise ValueError(f"terms={terms} unsupported for {lowp_dtype}")
    if dist not in SKETCH_DISTS:
        raise ValueError(f"unknown sketch distribution {dist!r}")
    if store_dtype is None:
        store_dtype = lowp_dtype

    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        functools.partial(_fused_kernel, store_dtype=store_dtype,
                          lowp_dtype=lowp_dtype, terms=terms,
                          dist=dist, s=s, bn=bn, bk=bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 2), lambda i, j, kk: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 2), lambda i, j, kk: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(key2, offsets, a)


def hbm_bytes_modeled(m: int, n: int, k: int, *, fused: bool,
                      b_dtype=jnp.bfloat16) -> int:
    """Modeled HBM traffic of one projection: A reads + C writes, plus Omega
    reads only on the materialized path — the BENCH_shgemm.json metric."""
    traffic = m * k * 4 + m * n * 4
    if not fused:
        traffic += k * n * jnp.dtype(b_dtype).itemsize
    return traffic
