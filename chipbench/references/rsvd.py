"""Plain randomized SVD (paper Algorithm 1) and the paper's test matrices,
in jax.numpy, importing nothing of the program under test.

The reference runs every product in float32 at ``HIGHEST`` (on a TPU a
float32 product is otherwise one bf16 pass).  It draws the Omega that the
library's default projection draws from the same key: an N(0, 1) float32
Gaussian rounded to bfloat16.  ``dot`` swaps in another product: the
control is this same algorithm at the precision just below ``HIGHEST``,
three bf16 passes (``high`` on a TPU; ``bf16x3``, the same passes written
out, on a backend where ``Precision.HIGH`` is float32).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax


def dot_f32(a, b):
    return jnp.dot(a, b, precision=lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def _bf16(x):
    # reduce_precision, not an astype round trip: XLA on a TPU may drop a
    # f32 -> bf16 -> f32 round trip as excess precision
    return lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def dot_bf16x3(a, b):
    """a @ b in three bf16 passes (hi.hi + hi.lo + lo.hi, f32 sums): what
    ``Precision.HIGH`` does on a TPU, written out so that it does the same
    on any backend."""
    a_hi, b_hi = _bf16(a), _bf16(b)
    a_lo, b_lo = _bf16(a - a_hi), _bf16(b - b_hi)

    def one(x, y):
        return jnp.dot(x.astype(jnp.bfloat16), y.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
    return one(a_hi, b_hi) + (one(a_hi, b_lo) + one(a_lo, b_hi))


def dot_high(a, b):
    """a @ b at ``Precision.HIGH``: three bf16 passes on a TPU (elsewhere
    it may be float32; use ``dot_bf16x3`` there)."""
    return jnp.dot(a, b, precision=lax.Precision.HIGH,
                   preferred_element_type=jnp.float32)


DOTS = {"f32": dot_f32, "high": dot_high, "bf16x3": dot_bf16x3}


def singular_values_exp(n: int, rank: int, s_p: float) -> jax.Array:
    """The paper's A_exp spectrum: s_i = 2^(-alpha i), alpha = log2(1/s_p)/p."""
    i = jnp.arange(n, dtype=jnp.float32)
    return jnp.exp2(-(jnp.log2(1.0 / s_p) / rank) * i)


def haar(key, n: int):
    """A Haar-distributed n x n orthogonal matrix: the polar factor of a
    Gaussian matrix, by Newton-Schulz iteration (matmuls only; a QR of
    4096 x 4096 takes seconds on a TPU).  bf16 passes bring the singular
    values near one, then float32 passes converge to float32 rounding."""
    g = jax.random.normal(key, (n, n), jnp.float32)
    v = jnp.ones((n,), jnp.float32)
    for _ in range(8):                     # power iteration for ||g||_2
        v = dot_f32(g.T, dot_f32(g, v))
        v = v / jnp.linalg.norm(v)
    x = g / (1.1 * jnp.linalg.norm(dot_f32(g, v)))
    eye = jnp.eye(n, dtype=jnp.float32)

    def step(x, dot):
        return 1.5 * x - 0.5 * dot(x, dot(x.T, x))

    def far(c):
        x, i = c
        return (jnp.max(jnp.abs(dot_f32(x.T, x) - eye)) > 1e-2) & (i < 200)

    def fast(c):
        x, i = c
        return step(x, lambda a, b: jnp.dot(
            a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32)), i + 1

    x, _ = lax.while_loop(far, fast, (x, 0))
    for _ in range(5):
        x = step(x, dot_f32)
    return x


@functools.partial(jax.jit, static_argnames=("n", "rank", "s_p", "count"))
def paper_matrices(key, *, n: int, rank: int, s_p: float, count: int):
    """``count`` n x n float32 matrices U diag(s) V^T with the A_exp
    spectrum and Haar U, V, made on the device in one call (a tuple, so
    that using one is not a slice of them all)."""
    s = singular_values_exp(n, rank, s_p)

    def one(k):
        k1, k2 = jax.random.split(k)
        return dot_f32(haar(k1, n) * s[None, :], haar(k2, n).T)
    return tuple(one(k) for k in jax.random.split(key, count))


@functools.partial(jax.jit, static_argnames=("rank", "oversample", "dot"))
def rsvd(key, a, *, rank: int, oversample: int, dot: str = "f32"):
    """Algorithm 1: Y = A Omega, Q = qr(Y), B = Q^T A, svd(B), U = Q U_B."""
    mm = DOTS[dot]
    n = a.shape[1]
    p = min(rank + oversample, min(a.shape))
    omega = jax.random.normal(key, (n, p), jnp.float32).astype(jnp.bfloat16)
    q, _ = jnp.linalg.qr(mm(a, omega.astype(jnp.float32)))
    u_b, s, vt = jnp.linalg.svd(mm(q.T, a), full_matrices=False)
    return mm(q, u_b)[:, :rank], s[:rank], vt[:rank]


@jax.jit
def compare(a, u, s, vt, u_ref, s_ref, vt_ref):
    """The numbers a factorization is held to against the reference's on
    the same A and Omega:

    * ``residual_ratio``: ||A - U S V^T||_F over the reference's;
    * ``sv_gap``: max_i |s_i - s_ref_i| / s_ref_1;
    * ``u_orth``: max |U^T U - I|, how far U is from orthonormal."""
    def residual(u, s, vt):
        return jnp.linalg.norm(a - dot_f32(u * s[None, :], vt))
    eye = jnp.eye(u.shape[1], dtype=jnp.float32)
    return {
        "residual_ratio": residual(u, s, vt) / residual(u_ref, s_ref, vt_ref),
        "sv_gap": jnp.max(jnp.abs(s - s_ref)) / s_ref[0],
        "u_orth": jnp.max(jnp.abs(dot_f32(u.T, u) - eye)),
    }
