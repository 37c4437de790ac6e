"""Random-projection gradient compression for data-parallel all-reduce.

The DP gradient all-reduce of a 2-D weight's gradient g (d_out x d_in) is
replaced by the all-reduce of a rank-r sketch Omega^T g (Omega: d_out x r,
bf16, the paper's low-precision Gaussian — regenerated from a shared seed on
every host, so Omega itself is NEVER communicated).  After the reduce, the
sketch is un-projected (Omega Omega^T g / alpha-ish scale) and an error-
feedback residual keeps the compression unbiased over time:

    e_{t}   <- g_t + e_{t-1}              (accumulate what was lost)
    sketch  <- Omega^T e_t                (r/d_out of the bytes on the wire)
    g_hat   <- Omega sketch / r           (JL-style unbiased estimate)
    e_t     <- e_t - g_hat                (residual carried forward)

Wire bytes shrink by d_out/r.  This is the paper's random projection applied
to the distributed-optimization layer (DESIGN.md §4.2).
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.projection import (ProjectionMethod, fused_omega, gaussian,
                                   project)


class CompressionState(NamedTuple):
    residual: Any    # error-feedback pytree (matrices only)
    step: jax.Array


def _compressible(g) -> bool:
    return g.ndim == 2 and g.shape[0] >= 256


def init_state(grads) -> CompressionState:
    res = jax.tree.map(
        lambda g: jnp.zeros_like(g) if _compressible(g) else None, grads)
    return CompressionState(res, jnp.zeros((), jnp.int32))


def _draw_basis(key, i: int, d: int, rank: int,
                method: ProjectionMethod) -> jax.Array:
    """The per-leaf orthonormal basis Q for one optimizer step — the single
    source of truth shared by the one-shot and microbatch-streaming paths
    (their equivalence depends on drawing the identical Q)."""
    r = min(rank, d)
    # Omega is regenerated from the shared seed on every host; hosts in
    # a DP group run the same binary on the same backend, so either
    # generator agrees across the group.  The fused method's counter
    # stream (kernels/shgemm_fused.py) additionally does not change
    # between jax releases (the jax.random Gaussian stream may), which
    # matters for error-feedback state carried across restarts/upgrades.
    if method == "shgemm_fused":
        omega = fused_omega(jax.random.fold_in(key, i), (d, r),
                            dtype=jnp.float32)
    else:
        omega = gaussian(jax.random.fold_in(key, i), (d, r),
                         dtype=jnp.float32)
    # Orthonormalize so (I - QQ^T) is a contraction — raw Omega Omega^T/r
    # has spectral radius (1+sqrt(d/r))^2 and the EF residual diverges.
    q_basis, _ = jnp.linalg.qr(omega)               # (d, r), O(d r^2)
    return q_basis


def _gemm_method(method: ProjectionMethod) -> ProjectionMethod:
    """Projection method for a GEMM against the materialized basis Q: the
    fused method has no Omega left to generate there, so its GEMM is the
    materialized Pallas kernel."""
    return "shgemm_pallas" if method == "shgemm_fused" else method


def compress_and_reduce(grads, state: CompressionState, *, rank: int = 32,
                        axis_name: Optional[str] = None,
                        method: ProjectionMethod = "shgemm",
                        seed: int = 42):
    """Returns (reduced_grads, new_state).

    With ``axis_name`` (inside shard_map/pmap): sketches are psum'd over the
    DP axis.  Without: single-host mode (sketch/unsketch still applied, which
    is also how the unit tests validate the estimator).
    """
    step = state.step + 1
    key = jax.random.fold_in(jax.random.PRNGKey(seed), step)

    def leaf(g, e, i):
        if e is None:
            return (jax.lax.psum(g, axis_name) if axis_name else g), None
        # Q is stored/applied in bf16: the projection Q^T acc is the
        # paper's mixed-precision GEMM.
        q_basis = _draw_basis(key, i, g.shape[0], rank, method)
        q_low = q_basis.astype(jnp.bfloat16)
        acc = g.astype(jnp.float32) + e
        # sketch: (r, d_in) — mixed-precision projection of acc^T
        sketch = project(acc.T, q_low, method=_gemm_method(method)).T
        if axis_name:
            sketch = jax.lax.psum(sketch, axis_name)
            n_dp = jax.lax.psum(1, axis_name)
        else:
            n_dp = 1
        g_hat = jnp.dot(q_basis, sketch) / n_dp
        new_e = acc - g_hat * n_dp
        return g_hat.astype(g.dtype), new_e

    flat_g, treedef = jax.tree_util.tree_flatten(grads)
    flat_e = treedef.flatten_up_to(state.residual)
    outs = [leaf(g, e, i) for i, (g, e) in enumerate(zip(flat_g, flat_e))]
    reduced = treedef.unflatten([o[0] for o in outs])
    new_res = treedef.unflatten([o[1] for o in outs])
    return reduced, CompressionState(new_res, step)


# ---------------------------------------------------------------------------
# Streaming microbatch accumulation (repro.stream's linearity, applied to
# gradient sketches): instead of materializing the summed gradient before
# sketching, each microbatch's rank-r sketch Q^T g_j is accumulated as it is
# produced — the projection GEMM is spread across microbatches, the DP
# all-reduce happens ONCE on the accumulated sketch, and the per-microbatch
# gradients can be freed immediately.  Equivalent to
# ``compress_and_reduce(sum_j g_j, state)`` up to f32 summation order
# (sketches are linear in g).
# ---------------------------------------------------------------------------

class MicrobatchSketch(NamedTuple):
    bases: Any       # per-leaf (d, r) f32 orthonormal Q (None: incompressible)
    sketches: Any    # per-leaf (r, d_in) accumulated Q^T (e + sum g_j)
    raw: Any         # per-leaf accumulated raw grads for incompressible leaves
    residual: Any    # per-leaf e + sum_j g_j so far (the EF accumulator)
    like: Any        # per-leaf () dtype witness of the gradient leaves
    step: jax.Array
    n_micro: jax.Array


def begin_accumulation(state: CompressionState, grads_like, *,
                       rank: int = 32,
                       method: ProjectionMethod = "shgemm",
                       seed: int = 42) -> MicrobatchSketch:
    """Open a gradient-accumulation window for the optimizer step after
    ``state.step``.

    ``grads_like`` supplies the gradient pytree structure/shapes (pass the
    first microbatch or a zeros pytree; its values are ignored).  The
    per-leaf basis Q is drawn exactly as ``compress_and_reduce`` would for
    this step, and the sketch accumulators start at Q^T e — the error-
    feedback term — so ``finish_accumulation`` reproduces its math.
    """
    step = state.step + 1
    key = jax.random.fold_in(jax.random.PRNGKey(seed), step)

    def leaf(g, e, i):
        if e is None:
            return None, None, jnp.zeros_like(g), None
        q_basis = _draw_basis(key, i, g.shape[0], rank, method)
        sketch = project(e.T, q_basis.astype(jnp.bfloat16),
                         method=_gemm_method(method)).T
        return q_basis, sketch, None, e

    flat_g, treedef = jax.tree_util.tree_flatten(grads_like)
    flat_e = treedef.flatten_up_to(state.residual)
    outs = [leaf(g, e, i) for i, (g, e) in enumerate(zip(flat_g, flat_e))]
    unf = lambda j: treedef.unflatten([o[j] for o in outs])  # noqa: E731
    like = jax.tree.map(lambda g: jnp.zeros((), g.dtype), grads_like)
    return MicrobatchSketch(bases=unf(0), sketches=unf(1), raw=unf(2),
                            residual=unf(3), like=like, step=step,
                            n_micro=jnp.zeros((), jnp.int32))


def accumulate_microbatch(ms: MicrobatchSketch, grads, *,
                          method: ProjectionMethod = "shgemm"
                          ) -> MicrobatchSketch:
    """Absorb one microbatch's gradients: compressible leaves add the
    mixed-precision sketch Q^T g (the paper's hot GEMM, streamed) and fold
    g into the EF accumulator; incompressible leaves accumulate raw."""
    flat_g, treedef = jax.tree_util.tree_flatten(grads)
    flat = list(zip(flat_g, treedef.flatten_up_to(ms.bases),
                    treedef.flatten_up_to(ms.sketches),
                    treedef.flatten_up_to(ms.raw),
                    treedef.flatten_up_to(ms.residual)))
    outs = []
    for g, q, s, raw, acc in flat:
        if q is None:
            outs.append((None, None, raw + g, None))
            continue
        g32 = g.astype(jnp.float32)
        s = s + project(g32.T, q.astype(jnp.bfloat16),
                        method=_gemm_method(method)).T
        outs.append((q, s, None, acc + g32))
    unf = lambda j: treedef.unflatten([o[j] for o in outs])  # noqa: E731
    return MicrobatchSketch(bases=unf(0), sketches=unf(1), raw=unf(2),
                            residual=unf(3), like=ms.like, step=ms.step,
                            n_micro=ms.n_micro + 1)


def finish_accumulation(ms: MicrobatchSketch, *,
                        axis_name: Optional[str] = None):
    """Close the window: all-reduce the accumulated sketches (the only
    wire traffic for compressible leaves), reconstruct g_hat, update the
    error-feedback residual.  Returns ``(reduced_grads, CompressionState)``
    — drop-in for ``compress_and_reduce``'s result on the summed gradient.
    """
    flat_q, treedef = jax.tree_util.tree_flatten(ms.bases,
                                                 is_leaf=lambda x: x is None)
    flat = list(zip(flat_q, treedef.flatten_up_to(ms.sketches),
                    treedef.flatten_up_to(ms.raw),
                    treedef.flatten_up_to(ms.residual),
                    treedef.flatten_up_to(ms.like)))
    outs = []
    for q, s, raw, acc, like in flat:
        if q is None:
            outs.append(((jax.lax.psum(raw, axis_name) if axis_name
                          else raw), None))
            continue
        if axis_name:
            s = jax.lax.psum(s, axis_name)
            n_dp = jax.lax.psum(1, axis_name)
        else:
            n_dp = 1
        g_hat = jnp.dot(q, s) / n_dp
        new_e = acc - g_hat * n_dp
        outs.append((g_hat.astype(like.dtype), new_e))
    reduced = treedef.unflatten([o[0] for o in outs])
    new_res = treedef.unflatten([o[1] for o in outs])
    return reduced, CompressionState(new_res, ms.step)


def wire_bytes(grads, rank: int = 32) -> tuple[int, int]:
    """(uncompressed, compressed) bytes per DP reduce — the claim."""
    full = comp = 0
    for g in jax.tree.leaves(grads):
        full += g.size * 4
        if _compressible(g):
            comp += min(rank, g.shape[0]) * g.shape[1] * 4
        else:
            comp += g.size * 4
    return full, comp
