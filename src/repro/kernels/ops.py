"""Public jit'd wrappers around the Pallas kernels.

``shgemm(a, b)`` handles arbitrary shapes/dtypes: pads to block multiples,
dispatches to the Pallas kernel, strips padding.  Every wrapper here runs its
kernel compiled on a TPU backend and in interpret mode on any other
(``resolve_interpret``); an explicit ``interpret=`` wins.  ``shgemm`` is
the drop-in used by core/projection.py's "shgemm_pallas" method and by the
serving/optimizer layers.

``shgemm_fused(a, key, n)`` is the zero-HBM-Omega variant: the random matrix
is generated inside the kernel from ``key`` (kernels/shgemm_fused.py), so the
projection's HBM traffic is A reads + C writes alone.

Block selection for both goes through ``kernels/autotune.py``: tuned blocks
from the persistent cache when the shape has been autotuned, otherwise the
shrink-to-fit heuristic.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import autotune as _tune
from repro.kernels import shgemm as _k
from repro.kernels import shgemm_fused as _kf


def resolve_interpret(interpret: bool | None) -> bool:
    """Compiled on a TPU backend, interpret mode everywhere else; an explicit
    bool wins (the chip-compile tests pass False on a CPU host)."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _refuse_compiled_fp16(dtype, interpret: bool) -> None:
    """fp16 Omega does not compile in either Pallas kernel on TPU v5e: Mosaic
    refuses the f16 vector load (materialized kernel) and the f16
    ``tpu.pack_subelements`` (fused kernel).  Refuse it loudly instead of
    converting; interpret mode keeps fp16 for CPU validation."""
    if jnp.dtype(dtype) == jnp.float16 and not interpret:
        raise ValueError(
            "fp16 Omega does not compile in the Pallas kernels on TPU: store "
            "Omega in bfloat16 (omega_dtype=jnp.bfloat16), or take the "
            "paper's fp16 path through XLA with method='shgemm'")


def _pad_to(x: jax.Array, m0: int, m1: int) -> jax.Array:
    p0 = (-x.shape[0]) % m0
    p1 = (-x.shape[1]) % m1
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)))
    return x


@functools.partial(jax.jit, static_argnames=("blocks", "terms", "interpret"))
def _shgemm_padded(a, b, blocks, terms, interpret):
    bm, bn, bk = blocks
    m, n = a.shape[0], b.shape[1]
    ap = _pad_to(a, bm, bk)
    bp = _pad_to(b, bk, bn)
    c = _k.shgemm_pallas(ap, bp, bm=bm, bn=bn, bk=bk, terms=terms,
                         interpret=interpret)
    return c[:m, :n]


def shgemm(a: jax.Array, b: jax.Array, *, blocks: tuple[int, int, int] | None = None,
           terms: int = 2, interpret: bool | None = None) -> jax.Array:
    """C_f32 = A_f32 @ B_lowp for arbitrary shapes.

    B may be bf16 (TPU-native) or fp16 (paper-faithful path; interpret mode
    only — compiled, it raises, see ``_refuse_compiled_fp16``).  A is cast
    to f32 if needed.  On non-TPU backends the kernel runs in interpret mode
    (Python evaluation of the kernel body) for bit-accurate validation.

    Block resolution happens OUTSIDE the jit boundary (the wrapper itself is
    not jitted; the padded kernel call is): jit retraces when the resolved
    blocks change, so autotune cache updates take effect on the next call
    instead of being baked into a stale trace.
    """
    a = a.astype(jnp.float32)
    if b.dtype not in (jnp.bfloat16, jnp.float16):
        b = b.astype(jnp.bfloat16)
    m, k = a.shape
    k2, n = b.shape
    if k != k2:
        raise ValueError(f"contraction mismatch: {a.shape} @ {b.shape}")
    interpret = resolve_interpret(interpret)
    _refuse_compiled_fp16(b.dtype, interpret)
    if blocks is None:
        blocks = _tune.pick_blocks(m, n, k, b_dtype=b.dtype, terms=terms,
                                   interpret=interpret)
    return _shgemm_padded(a, b, tuple(blocks), terms, interpret)


def shgemm_nt(a: jax.Array, b_t: jax.Array, **kw) -> jax.Array:
    """C = A @ B_t^T (B stored transposed, e.g. row-major random matrices)."""
    return shgemm(a, b_t.T, **kw)


def _validate_offset(name: str, value, unit: int) -> None:
    """Block-alignment check for concrete offsets (clear error, per the
    streaming contract DESIGN.md §10).  Traced offsets skip the check — the
    caller (repro.stream) owns the alignment discipline there."""
    if isinstance(value, (int, np.integer)):
        if value < 0:
            raise ValueError(f"{name}={value} must be >= 0")
        if value % unit:
            raise ValueError(
                f"{name}={value} is not a multiple of the {unit}-wide kernel "
                f"block on that axis; streamed tiles must be block-aligned "
                f"with the one-shot lattice (pass blocks=... explicitly to "
                f"pick a compatible tiling, or align the offset)")


def shgemm_fused(a: jax.Array, key: jax.Array, n: int, *,
                 dist: str = "gaussian", omega_dtype=jnp.bfloat16,
                 blocks: tuple[int, int, int] | None = None, terms: int = 2,
                 s: float | None = None, row_offset=0, col_offset=0,
                 interpret: bool | None = None) -> jax.Array:
    """C_f32 = A_f32 @ Omega(key)[k, n] with Omega generated in-kernel.

    Arbitrary shapes: A is zero-padded to block multiples; pad rows of A null
    the extra generated Omega rows and pad columns are sliced off, so the
    result is independent of the padding (and of the block shape — see the
    determinism contract in kernels/shgemm_fused.py).

    ``omega_dtype`` may be an fp8 format: samples are rounded through fp8 in
    the kernel and consumed as bf16 by the MXU, matching
    ``project(a, fused_omega(key, ..., dtype=fp8))`` exactly (fp8 Omega is
    storage-only everywhere in this repo).  Like ``shgemm``, block
    resolution runs outside the jit boundary so autotune updates apply.

    ``row_offset``/``col_offset`` shift the generated Omega's global index
    lattice: the call consumes ``Omega(key)[row_offset:row_offset+k,
    col_offset:col_offset+n]`` of the one-shot random matrix without ever
    materializing or slicing it — the primitive behind repro.stream and the
    per-shard Omega row-blocks in core/distributed.py.  A concrete int
    ``row_offset`` must be a multiple of the resolved ``bk`` so streamed
    K-accumulation tiles the one-shot K-chunking exactly; ``col_offset``
    is unconstrained (any value >= 0): the N-axis tiling never touches the
    per-element summation order, and the lattice is element-pure, so the
    call reproduces the one-shot columns bit for bit at any offset — the
    property adaptive sketch widening (stream.SketchState.widen) relies
    on.  Traced offsets (scan carries) are accepted unchecked.  NOTE: for
    ``dist="very_sparse"`` with a nonzero row_offset (or any partial-width
    row tile), pass the GLOBAL data dimension's ``s`` explicitly — the
    default is derived from this call's local k, i.e. a different
    distribution than the one-shot sketch.
    """
    if dist in ("srht", "khatri_rao"):
        raise ValueError(
            f"dist={dist!r} is a structured family with no GEMM to fuse — "
            f"use core.projection.sketch (SRHT O(n log n) apply path) or "
            f"core.structured.KhatriRaoOmega instead of the fused kernel")
    a = a.astype(jnp.float32)
    m, k = a.shape
    store_dtype = jnp.dtype(omega_dtype).type
    if store_dtype in (jnp.float8_e4m3fn, jnp.float8_e5m2):
        compute_dtype = jnp.bfloat16  # e8m7 superset of both fp8 formats
    elif store_dtype in (jnp.bfloat16, jnp.float16):
        compute_dtype = store_dtype
    else:
        raise TypeError(f"omega_dtype must be bf16/fp16/fp8, got {omega_dtype}")
    interpret = resolve_interpret(interpret)
    _refuse_compiled_fp16(compute_dtype, interpret)
    if blocks is None:
        blocks = _tune.pick_blocks(m, n, k, b_dtype=compute_dtype,
                                   terms=terms, fused=True,
                                   interpret=interpret)
    bm, bn, bk = blocks
    _validate_offset("row_offset", row_offset, bk)
    # unit=1: only the >= 0 check — N-axis block boundaries never affect
    # the K-summation order, so any column offset consumes exactly
    # Omega[:, c0:c0+n] of the one-shot lattice (see docstring)
    _validate_offset("col_offset", col_offset, 1)
    offsets = jnp.stack([jnp.asarray(row_offset, jnp.int32),
                         jnp.asarray(col_offset, jnp.int32)]).reshape(1, 2)
    n_pad = n + (-n) % bn
    c = _kf.shgemm_fused_pallas(
        _pad_to(a, bm, bk), _kf.key_words(key), n_pad, bm=bm, bn=bn, bk=bk,
        terms=terms, dist=dist, s=_kf._resolve_s(dist, s, k),
        store_dtype=store_dtype, lowp_dtype=compute_dtype,
        offsets=offsets, interpret=interpret)
    return c[:m, :n]


def flash_attention(q, k, v, *, causal: bool = True, scale=None,
                    interpret: bool | None = None):
    """Padded/dispatching wrapper over kernels.flash_attention: pads S to a
    block multiple (the pad kv sit above the causal diagonal, and the pad
    rows are sliced off).  Non-causal input must already be a block
    multiple: the kernel has no kv mask, so pad kv would enter the
    softmax."""
    from repro.kernels import flash_attention as fa
    interpret = resolve_interpret(interpret)
    b, s, h, hd = q.shape
    block = 128 if s >= 128 else max(8, s)
    pad = (-s) % block
    if pad and not causal:
        raise ValueError(
            f"non-causal flash attention needs S to be a multiple of the "
            f"{block}-row kv block, got S={s}: pad kv would enter the "
            f"softmax — use models.layers.attention for ragged shapes")
    if pad:
        widths = ((0, 0), (0, pad), (0, 0), (0, 0))
        q = jnp.pad(q, widths)
        k = jnp.pad(k, widths)
        v = jnp.pad(v, widths)  # pad kv sit above the causal diagonal
    out = fa.flash_attention(q, k, v, causal=causal, scale=scale,
                             block_q=block, block_kv=block,
                             interpret=interpret)
    return out[:, :s]


def factored_decode_attention(q, k, v, k_us, k_vt, v_us, v_vt, comp_len,
                              write_pos, *, scale, cap: float = 0.0,
                              block_kv: int | None = None,
                              interpret: bool | None = None):
    """Dispatching wrapper over kernels.factored_decode (DESIGN.md §16).

    Same signature/semantics as the jnp oracle
    ``models.layers.factored_decode_attention`` (which stays the default
    serve path); this runs the fused Pallas kernel instead, in interpret
    mode off-TPU.  ``block_kv`` comes from the autotune cache
    (``pick_decode_block``) unless given explicitly.
    """
    from repro.kernels import factored_decode as fd
    interpret = resolve_interpret(interpret)
    b, _, h, hd = q.shape
    skv, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    r = k_us.shape[-1]
    if block_kv is None:
        block_kv = _tune.pick_decode_block(skv, g, hd, r, interpret=interpret)
    return fd.factored_decode_attention(
        q, k, v, k_us, k_vt, v_us, v_vt, comp_len, write_pos,
        scale=scale, cap=cap, block_kv=block_kv, interpret=interpret)
