"""Causal flash attention Pallas TPU kernel (blockwise online softmax).

Motivation (EXPERIMENTS.md §Perf): after the collective-term campaign the
prefill cells are compute-bound, and the probe M/H ratios show the jnp
blockwise attention still *computes* every (q, kv) block — the causal upper
triangle is masked, not skipped.  This kernel:

  * runs a (batch*kv_heads, n_q_blocks, n_kv_blocks) grid whose kv axis is
    iterated innermost; fully-masked blocks are SKIPPED via pl.when (no MXU
    issue, no HBM read of that K/V block) — exactly 2x fewer attention FLOPs
    and bytes for causal sequences;
  * keeps the online-softmax running (m, l, acc) state in VMEM scratch so
    the (S, S) score matrix never exists anywhere;
  * supports GQA natively: q blocks carry the group dim, K/V load once per
    kv head.

Validated in interpret mode against ref.flash_attention_ref (and the model's
jnp blockwise attention) over shape/window sweeps.  The model uses it when
``cfg.use_flash_kernel`` is set (TPU deployment path); the dry-run probe
keeps the jnp path so HLO cost analysis stays transparent (Pallas custom
calls are opaque to it — roofline would undercount).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale, block_q, block_kv, causal):
    """Grid: (BH, n_q, n_kv); kv innermost ('arbitrary').
    q_ref: (G, block_q, hd) — G = q heads per kv head (GQA group).
    k_ref/v_ref: (block_kv, hd).  Scratch: m,l (G, block_q, 1), acc like q.
    """
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # causal: kv block strictly above the q block's diagonal is skipped
    # entirely — no MXU work for that block.
    run = (not causal) or (ik * block_kv <= iq * block_q + block_q - 1)

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32)            # (G, bq, hd)
        k = k_ref[0].astype(jnp.float32)            # (bkv, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((2,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_q, block_kv), 1)
            k_pos = ik * block_kv + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_q, block_kv), 2)
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)

        m_prev = m_ref[0]                            # (G, bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                       # (G, bq, bkv)
        l_ref[0] = l_ref[0] * alpha + jnp.sum(p, -1, keepdims=True)
        acc_ref[0] = acc_ref[0] * alpha + jax.lax.dot_general(
            p, v, (((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[0] = m_new

    @pl.when(ik == pl.num_programs(2) - 1)
    def _finish():
        o_ref[0] = (acc_ref[0] /
                    jnp.maximum(l_ref[0], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q",
                                             "block_kv", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, scale: float = None,
                    block_q: int = 256, block_kv: int = 256,
                    interpret: bool = False):
    """q: (B, S, H, hd); k/v: (B, S, KV, hd) -> (B, S, H, hd).

    S must divide by the block sizes (ops-level callers pad).  GQA handled by
    folding the group dim into the q block.
    """
    b, s, h, hd = q.shape
    kv = k.shape[2]
    g = h // kv
    if scale is None:
        scale = hd ** -0.5
    assert s % block_q == 0 and s % block_kv == 0, (s, block_q, block_kv)

    # (B*KV, G, S, hd) layout: one grid row per (batch, kv head)
    qr = q.reshape(b, s, kv, g, hd).transpose(0, 2, 3, 1, 4) \
        .reshape(b * kv, g, s, hd)
    kr = k.transpose(0, 2, 1, 3).reshape(b * kv, s, hd)
    vr = v.transpose(0, 2, 1, 3).reshape(b * kv, s, hd)

    grid = (b * kv, s // block_q, s // block_kv)
    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, block_q=block_q,
                          block_kv=block_kv, causal=causal),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, g, block_q, hd),
                         lambda bh, iq, ik: (bh, 0, iq, 0)),
            pl.BlockSpec((1, block_kv, hd), lambda bh, iq, ik: (bh, ik, 0)),
            pl.BlockSpec((1, block_kv, hd), lambda bh, iq, ik: (bh, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, g, block_q, hd),
                               lambda bh, iq, ik: (bh, 0, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * kv, g, s, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, g, block_q, 1), jnp.float32),
            pltpu.VMEM((1, g, block_q, 1), jnp.float32),
            pltpu.VMEM((1, g, block_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qr, kr, vr)

    return out.reshape(b, kv, g, s, hd).transpose(0, 3, 1, 2, 4) \
        .reshape(b, s, h, hd)
