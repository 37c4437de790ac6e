"""Plain float32 forward pass of a dense GQA decoder (Qwen3: RMSNorm,
q/k norm, rotary positions, gated SiLU MLP, tied embeddings), in
jax.numpy, importing nothing of the program under test.

It reads the benchmark's weights by name, in the layout the serving
program takes them: ``layers/p0/<leaf>`` leaves stacked over layers.  Norm
gains are stored as an offset from one (``x * (1 + g)``), which is the
published RMSNorm with weight ``1 + g``.  Every product runs in float32 at
``HIGHEST``.  ``quantize_fp8`` gives the control: the same weights
rounded per output channel to fp8, the precision just below bfloat16.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
LAYER = "layers/p0/"


class Dims(NamedTuple):
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    rope_theta: float
    eps: float

    @classmethod
    def of(cls, model: dict) -> "Dims":
        return cls(model["num_hidden_layers"], model["num_attention_heads"],
                   model["num_key_value_heads"], model["head_dim"],
                   float(model["rope_theta"]), float(model["rms_norm_eps"]))


def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * (1.0 + g)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freq = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freq          # (T, half)
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


@functools.partial(jax.jit, static_argnames=("dims",))
def forward(params: dict, tokens, dims: Dims):
    """Logits (T, vocab) in float32 for one sequence ``tokens`` (T,); a
    position sees itself and the positions before it, so padding at the
    end changes nothing before it."""
    f32 = lambda w: w.astype(jnp.float32)                    # noqa: E731
    t = tokens.shape[0]
    pos = jnp.arange(t)
    emb = f32(params["embed/tokens"])
    x = emb[tokens]
    causal = pos[None, :] <= pos[:, None]
    groups = dims.heads // dims.kv_heads
    layer = {k[len(LAYER):]: v for k, v in params.items()
             if k.startswith(LAYER)}

    def block(x, p):
        p = {k: f32(v) for k, v in p.items()}
        h = _rms(x, p["norm1/scale"], dims.eps)
        q = jnp.einsum("td,dnh->tnh", h, p["attn/wq"], precision=HI)
        k = jnp.einsum("td,dnh->tnh", h, p["attn/wk"], precision=HI)
        v = jnp.einsum("td,dnh->tnh", h, p["attn/wv"], precision=HI)
        q = _rope(_rms(q, p["attn/q_norm"], dims.eps), pos, dims.rope_theta)
        k = _rope(_rms(k, p["attn/k_norm"], dims.eps), pos, dims.rope_theta)
        k = jnp.repeat(k, groups, axis=1)        # query head n reads kv n//G
        v = jnp.repeat(v, groups, axis=1)
        s = jnp.einsum("qnh,knh->nqk", q, k, precision=HI) \
            / jnp.sqrt(jnp.float32(dims.head_dim))
        s = jnp.where(causal[None], s, -jnp.inf)
        o = jnp.einsum("nqk,knh->qnh", jax.nn.softmax(s, axis=-1), v,
                       precision=HI)
        x = x + jnp.dot(o.reshape(t, -1), p["attn/wo"], precision=HI)
        h = _rms(x, p["norm2/scale"], dims.eps)
        g = jnp.dot(h, p["mlp/w_gate"], precision=HI)
        u = jnp.dot(h, p["mlp/w_up"], precision=HI)
        x = x + jnp.dot(jax.nn.silu(g) * u, p["mlp/w_down"], precision=HI)
        return x, None

    x, _ = lax.scan(block, x, layer)
    x = _rms(x, f32(params["final_norm/scale"]), dims.eps)
    return jnp.dot(x, emb.T, precision=HI)


# axes each matmul weight is reduced over for a per-output-channel scale
# (leading layer axis excluded)
_INPUT_AXES = {"attn/wq": (0,), "attn/wk": (0,), "attn/wv": (0,),
               "attn/wo": (0,), "mlp/w_gate": (0,), "mlp/w_up": (0,),
               "mlp/w_down": (0,)}


def _fp8(w, axes):
    # e4m3 rounding by reduce_precision (4 exponent, 3 mantissa bits), in
    # its IEEE range (max 240): XLA on a TPU may drop a f32 -> fp8 -> f32
    # round trip as excess precision
    scale = jnp.max(jnp.abs(w), axis=axes, keepdims=True) / 240.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return lax.reduce_precision(w / scale, exponent_bits=4,
                                mantissa_bits=3) * scale


@jax.jit
def quantize_fp8(params: dict) -> dict:
    """The weights with every matmul weight (and the tied embedding) rounded
    to fp8 e4m3 per output channel, scaled to its range, and returned in
    float32; norm gains unchanged."""
    out = {}
    for name, w in params.items():
        leaf = name[len(LAYER):] if name.startswith(LAYER) else None
        w32 = w.astype(jnp.float32)
        if leaf in _INPUT_AXES:
            out[name] = _fp8(w32, tuple(a + 1 for a in _INPUT_AXES[leaf]))
        elif name == "embed/tokens":
            out[name] = _fp8(w32, (1,))
        else:
            out[name] = w
    return out


@jax.jit
def served_gap(ref_logits, served, valid):
    """Widest gap by which a served token's reference logit lies below the
    reference's best at its position: ``ref_logits`` (T, V), ``served``
    (T,) the token served after each position, ``valid`` (T,) which
    positions carry one."""
    best = jnp.max(ref_logits, axis=-1)
    got = jnp.take_along_axis(ref_logits, served[:, None], axis=-1)[:, 0]
    return jnp.max(jnp.where(valid, best - got, 0.0))


@jax.jit
def first_choice_gap(ref_logits, other_logits, valid):
    """The same gap for the token that ``other_logits`` puts first."""
    return served_gap(ref_logits, jnp.argmax(other_logits, axis=-1), valid)
