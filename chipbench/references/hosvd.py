"""Plain RP-HOSVD (paper Algorithm 2) and the paper's low-multilinear-rank
test tensors (Algorithm 3), in jax.numpy, importing nothing of the program
under test.

Every product is float32 at ``HIGHEST`` (the products of
``references/rsvd.py``, which imports nothing of the program either).  Two
departures from the paper's description, both the configuration's:

* each mode's Omega is an N(0, 1) float32 Gaussian rounded to bfloat16, the
  Omega that the pinned projection draws from the same key (the paper's is
  a half-precision Gaussian), so that the reference and the program project
  onto the same subspace;
* the test tensor gets Gaussian noise of ``noise`` times its Frobenius norm.
  On the exactly low-rank tensor float32 sits at its rounding floor and
  ratios against it say little; with the noise each mode has a rank-``J``
  range that the projection has to find.

``dot`` swaps in another product for the whole algorithm: the control is
this same algorithm at the precision just below ``HIGHEST``, three bf16
passes (``high`` on a TPU; ``bf16x3`` on a backend where
``Precision.HIGH`` is float32).  On a bfloat16 Omega three bf16 passes
compute what the two-term split computes, so the control departs from the
program in the core contraction, not in the projection.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from chipbench.references.rsvd import DOTS, _bf16, dot_f32


def unfold(t, mode: int):
    """Mode-``mode`` unfolding (I_mode, prod of the others), the other
    modes' indices in their order, the first slowest."""
    return jnp.moveaxis(t, mode, 0).reshape(t.shape[mode], -1)


def mode_product(t, m, mode: int, dot=dot_f32):
    """T x_mode M for M of shape (J, I_mode)."""
    rest = [s for i, s in enumerate(t.shape) if i != mode]
    out = dot(m, unfold(t, mode)).reshape(m.shape[0], *rest)
    return jnp.moveaxis(out, 0, mode)


def test_tensor(key, dims, ranks, pad: int, noise: float):
    """Algorithm 3 plus noise: a U(-1, 1) core of shape ``ranks`` taken to
    ``dims`` by one rank-(J_i - pad) map per mode, each the product of two
    U(-1, 1) matrices (I_i x (J_i - pad), (J_i - pad) x J_i); then
    N(0, 1) noise scaled to ``noise`` times the tensor's Frobenius norm."""
    keys = jax.random.split(key, 2 * len(dims) + 2)
    t = jax.random.uniform(keys[0], tuple(ranks), jnp.float32, -1.0, 1.0)
    for i, (n, r) in enumerate(zip(dims, ranks)):
        left = jax.random.uniform(keys[2 * i + 1], (n, r - pad),
                                  jnp.float32, -1.0, 1.0)
        right = jax.random.uniform(keys[2 * i + 2], (r - pad, r),
                                   jnp.float32, -1.0, 1.0)
        t = mode_product(t, dot_f32(left, right), i)
    g = jax.random.normal(keys[-1], tuple(dims), jnp.float32)
    return t + (noise * jnp.linalg.norm(t) / jnp.linalg.norm(g)) * g


@functools.partial(jax.jit, static_argnames=("dims", "ranks", "pad",
                                             "noise", "count"))
def test_tensors(key, *, dims: tuple, ranks: tuple, pad: int, noise: float,
                 count: int):
    """``count`` test tensors made on the device in one call (a tuple, so
    that using one is not a slice of them all)."""
    return tuple(test_tensor(k, dims, ranks, pad, noise)
                 for k in jax.random.split(key, count))


@functools.partial(jax.jit, static_argnames=("ranks", "dot"))
def rp_hosvd(key, a, *, ranks: tuple, dot: str = "f32"):
    """Algorithm 2: per mode i, W_i = A_(i) Omega_i and Q_i = qr(W_i), with
    Omega_i of shape (prod_{j != i} I_j, J_i) drawn from the i-th of
    ``ndim`` keys split from ``key``; then the core G = A x_1 Q_1^T ...
    x_N Q_N^T.  Returns (G, (Q_1, ..., Q_N))."""
    mm = DOTS[dot]
    keys = jax.random.split(key, a.ndim)
    factors = []
    for i, r in enumerate(ranks):
        cols = math.prod(a.shape) // a.shape[i]
        # rounded to bf16 by reduce_precision: XLA on a TPU may drop an
        # astype round trip, and the reference would keep the f32 Omega
        omega = _bf16(jax.random.normal(keys[i], (cols, r), jnp.float32))
        q, _ = jnp.linalg.qr(mm(unfold(a, i), omega))
        factors.append(q)
    return contract(a, [q.T for q in factors], mm), tuple(factors)


def contract(t, mats, dot=dot_f32):
    """T x_1 M_1 ... x_N M_N."""
    for i, m in enumerate(mats):
        t = mode_product(t, m, i, dot)
    return t


@jax.jit
def compare(a, core, factors, core_ref, factors_ref):
    """The numbers a decomposition is held to against the reference's on the
    same tensor and Omegas:

    * ``residual_ratio``: ||A - X||_F over ||A - X_ref||_F, where X is the
      reconstruction G x_k Q_k: a gross check;
    * ``recon_gap``: ||X - X_ref||_F / ||A||_F.  It compares the
      reconstructions, so column signs and rotations inside a mode's range
      cannot trip it.  The two-term split's rounding turns the noise
      directions of each mode's range a little, so it reads the projection's
      precision: a one-pass bf16 projection reads far above it;
    * ``core_gap``: ||G - A x_1 Q_1^T ... x_N Q_N^T||_F / ||A||_F, the core
      against the float32 contraction of A with the decomposition's own
      factors: the precision of line 5 of Algorithm 2, which
      ``recon_gap``'s spread from the projection would hide;
    * ``q_orth``: the largest over modes of max |Q_k^T Q_k - I|."""
    x, x_ref = contract(core, factors), contract(core_ref, factors_ref)
    exact = contract(a, [q.T for q in factors])
    orth = [jnp.max(jnp.abs(dot_f32(q.T, q)
                            - jnp.eye(q.shape[1], dtype=jnp.float32)))
            for q in factors]
    norm = jnp.linalg.norm(a)
    return {
        "residual_ratio": (jnp.linalg.norm(a - x)
                           / jnp.linalg.norm(a - x_ref)),
        "recon_gap": jnp.linalg.norm(x - x_ref) / norm,
        "core_gap": jnp.linalg.norm(core - exact) / norm,
        "q_orth": jnp.max(jnp.stack(orth)),
    }
