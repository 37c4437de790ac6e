"""Fused factored-decode-attention Pallas kernel (DESIGN.md §16).

Single-token decode over a serving slot whose KV prefix has been compressed
(DESIGN.md §12): rows [0, comp_len_b) exist only as rank-r factors
K ~ us_k·vt_k / V ~ us_v·vt_v (the dense cache rows there are zeroed), the
tail (comp_len_b <= i <= write_pos) lives in the dense cache, and ONE softmax
spans both regions.  The jnp reference (`models.layers.factored_decode_attention`)
is the oracle this kernel is validated against; it stays the default path.

Why a kernel (ROADMAP "Pallas factored-decode-attention kernel"): the jnp
path materializes full (B, KV, G, S) score/prob tensors and — structure
aside — reads every dense cache row even for positions that are factored or
beyond ``write_pos``.  This kernel, built on the blockwise online-softmax
idiom of ``kernels/flash_attention.py``:

  * iterates kv blocks innermost over a (B*KV, n_kv_blocks) grid with the
    running (m, l, acc) softmax state in VMEM scratch — the (S,) score row
    never exists whole;
  * scores the factored prefix via the two skinny GEMMs
    ``(q·vt_k^T)·us_k^T`` without ever materializing K, and accumulates the
    prefix value contraction in factor space (``acc_f += p·us_v``, one
    ``acc_f·vt_v`` at the end) — per-block FLOPs O(G·r + bkv·r) instead of
    O(bkv·hd);
  * skips work with ``pl.when`` on the per-slot ``comp_len`` (SMEM) and the
    ``write_pos`` clock (SMEM): blocks entirely beyond ``write_pos`` issue
    nothing (no HBM read of that K/V block), all-prefix blocks skip the
    dense GEMM, all-dense blocks skip the factored GEMMs — a dense-only
    batch row (comp_len == 0) never touches the factor operands at all.

Validated in interpret mode against the jnp oracle over GQA/softcap/
comp_len sweeps (tests/test_factored_decode_kernel.py, <= 1e-5 on f32).
The serve path uses it when ``cfg.use_flash_kernel`` is set; block size
comes from ``kernels/autotune.py`` (``pick_decode_block``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fdec_kernel(comp_ref, wp_ref, q_ref, k_ref, v_ref, kus_ref, kvt_ref,
                 vus_ref, vvt_ref, o_ref, s_ref, m_ref, l_ref, accd_ref,
                 accf_ref, *, scale, cap, block_kv, kv_heads):
    """Grid: (B*KV, n_kv); kv innermost ('arbitrary').

    q_ref: (1, G, hd) — G = q heads per kv head.  k/v_ref: (1, bkv, hd);
    kus/vus_ref: (1, bkv, r); kvt/vvt_ref: (1, r, hd).  comp_ref (B,) and
    wp_ref (1,): int32 scalar-prefetch operands in SMEM (per-slot
    compressed-prefix length, slot clock).
    Scratch: s (1, G, bkv) block scores; m/l (1, G, 1); acc_d (1, G, hd);
    acc_f (1, G, r) — the prefix value contraction stays rank-r until the
    final ``acc_f·vt_v`` in the epilogue.
    """
    ik = pl.program_id(1)
    comp = comp_ref[pl.program_id(0) // kv_heads]
    wp = wp_ref[0]
    start = ik * block_kv
    g = q_ref.shape[1]
    pos = start + jax.lax.broadcasted_iota(jnp.int32, (g, block_kv), 1)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        accd_ref[...] = jnp.zeros_like(accd_ref)
        accf_ref[...] = jnp.zeros_like(accf_ref)

    # Block classification against the slot's (comp_len, write_pos) state.
    # A block whose first position is past the clock is fully masked: no
    # score GEMM, no softmax update, no HBM read beyond the (already
    # scheduled) block fetch.  Within live blocks, the factored GEMMs run
    # only if the block overlaps [0, comp) and the dense GEMM only if it
    # overlaps [comp, wp] — mutually exclusive except for the single
    # boundary block.
    in_range = start <= wp
    has_fact = jnp.logical_and(in_range, start < comp)
    has_dense = jnp.logical_and(in_range, start + block_kv > comp)

    @pl.when(in_range)
    def _zero_scores():
        s_ref[...] = jnp.zeros_like(s_ref)

    @pl.when(has_dense)
    def _dense_scores():
        q = q_ref[0].astype(jnp.float32)                 # (G, hd)
        k = k_ref[0].astype(jnp.float32)                 # (bkv, hd)
        sd = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
        s_ref[0] = jnp.where(pos >= comp, sd, s_ref[0])

    @pl.when(has_fact)
    def _factored_scores():
        # q·K^T = (q·vt_k^T)·us_k^T: two skinny GEMMs, K never materialized
        q = q_ref[0].astype(jnp.float32)                 # (G, hd)
        kvt = kvt_ref[0].astype(jnp.float32)             # (r, hd)
        kus = kus_ref[0].astype(jnp.float32)             # (bkv, r)
        qv = jax.lax.dot_general(q, kvt, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        sf = jax.lax.dot_general(qv, kus, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
        s_ref[0] = jnp.where(pos < comp, sf, s_ref[0])

    @pl.when(in_range)
    def _online_update():
        s = s_ref[0]
        if cap > 0:
            s = jnp.tanh(s / cap) * cap
        valid = pos <= wp
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[0]                                # (G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new) * valid.astype(jnp.float32)
        l_ref[0] = l_ref[0] * alpha + jnp.sum(p, -1, keepdims=True)
        is_pre = (pos < comp).astype(jnp.float32)
        vus = vus_ref[0].astype(jnp.float32)             # (bkv, r)
        v = v_ref[0].astype(jnp.float32)                 # (bkv, hd)
        accf_ref[0] = accf_ref[0] * alpha + jax.lax.dot_general(
            p * is_pre, vus, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        accd_ref[0] = accd_ref[0] * alpha + jax.lax.dot_general(
            p * (1.0 - is_pre), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[0] = m_new

    @pl.when(ik == pl.num_programs(1) - 1)
    def _finish():
        vvt = vvt_ref[0].astype(jnp.float32)             # (r, hd)
        out = jax.lax.dot_general(accf_ref[0], vvt, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        out = out + accd_ref[0]
        o_ref[0] = (out / jnp.maximum(l_ref[0], 1e-30)).astype(o_ref.dtype)


def _pad_seq(x: jax.Array, axis: int, to: int) -> jax.Array:
    pad = to - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("scale", "cap", "block_kv",
                                             "interpret"))
def factored_decode_attention(q, k, v, k_us, k_vt, v_us, v_vt, comp_len,
                              write_pos, *, scale: float, cap: float = 0.0,
                              block_kv: int = 256, interpret: bool = False):
    """q: (B, 1, H, hd); k/v: (B, S, KV, hd); k_us/v_us: (B, KV, S, r);
    k_vt/v_vt: (B, KV, r, hd); comp_len: (B,) int32; write_pos: scalar
    (traced — the serve decode clock).  Returns (B, 1, H, hd) in q.dtype.

    S is zero-padded to a ``block_kv`` multiple inside; padded positions sit
    beyond ``write_pos`` so the validity mask (and the block-skip predicate)
    removes them — the result is independent of the padding.
    """
    b, sq, h, hd = q.shape
    assert sq == 1, f"decode kernel is single-token; got S_q={sq}"
    skv, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    r = k_us.shape[-1]
    s_pad = skv + (-skv) % block_kv

    # one grid row per (batch slot, kv head) — same layout as flash_attention
    qr = q.reshape(b, kvh, g, hd).reshape(b * kvh, g, hd)
    kr = _pad_seq(k, 1, s_pad).transpose(0, 2, 1, 3).reshape(b * kvh, s_pad, hd)
    vr = _pad_seq(v, 1, s_pad).transpose(0, 2, 1, 3).reshape(b * kvh, s_pad, hd)
    kus = _pad_seq(k_us, 2, s_pad).reshape(b * kvh, s_pad, r)
    vus = _pad_seq(v_us, 2, s_pad).reshape(b * kvh, s_pad, r)
    kvt = k_vt.reshape(b * kvh, r, hd)
    vvt = v_vt.reshape(b * kvh, r, hd)
    # whole-array SMEM operands via scalar prefetch: Mosaic refuses a (1, 1)
    # SMEM block over a (B, 1) array (the (8, 128) block rule)
    comp = comp_len.astype(jnp.int32).reshape(b)
    wp = jnp.asarray(write_pos, jnp.int32).reshape(1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b * kvh, s_pad // block_kv),
        in_specs=[
            pl.BlockSpec((1, g, hd), lambda bh, ik, *_: (bh, 0, 0)),
            pl.BlockSpec((1, block_kv, hd), lambda bh, ik, *_: (bh, ik, 0)),
            pl.BlockSpec((1, block_kv, hd), lambda bh, ik, *_: (bh, ik, 0)),
            pl.BlockSpec((1, block_kv, r), lambda bh, ik, *_: (bh, ik, 0)),
            pl.BlockSpec((1, r, hd), lambda bh, ik, *_: (bh, 0, 0)),
            pl.BlockSpec((1, block_kv, r), lambda bh, ik, *_: (bh, ik, 0)),
            pl.BlockSpec((1, r, hd), lambda bh, ik, *_: (bh, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, g, hd), lambda bh, ik, *_: (bh, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, g, block_kv), jnp.float32),   # block scores
            pltpu.VMEM((1, g, 1), jnp.float32),          # running max
            pltpu.VMEM((1, g, 1), jnp.float32),          # running sum
            pltpu.VMEM((1, g, hd), jnp.float32),         # dense-tail acc
            pltpu.VMEM((1, g, r), jnp.float32),          # factored acc
        ],
    )
    out = pl.pallas_call(
        functools.partial(_fdec_kernel, scale=scale, cap=cap,
                          block_kv=block_kv, kv_heads=kvh),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * kvh, g, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(comp, wp, qr, kr, vr, kus, kvt, vus, vvt)

    return out.reshape(b, kvh, g, hd).reshape(b, 1, h, hd)
