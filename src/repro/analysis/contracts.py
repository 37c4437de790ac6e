"""The repo's contract catalog: which entry points are traced with which
passes, and the precision allowlists that encode the paper's rules.

Every contract is a named zero-arg callable returning findings; the CLI
runs the whole catalog (plus the AST lint) on every PR.  Shapes are tiny —
tracing is abstract, and the properties proven (jaxpr structure, index-map
injectivity) are shape-independent — so the full catalog runs in seconds
on CPU.

Adding an invariant: write a function returning ``list[Finding]``, add it
to ``CONTRACTS``, and document the rule id in DESIGN.md §18.  Do NOT add a
one-off assert in a test instead — the point of the subsystem is that
contracts run against the *current* library entry points on every change,
not against a frozen copy of yesterday's trace.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro.analysis.findings import Finding
from repro.analysis.jaxpr_passes import determinism, dtype_flow, no_gemm
from repro.analysis.pallas_audit import audit_pallas

__all__ = ["CONTRACTS", "run_repo_contracts"]


def _key():
    return jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# SRHT: the structured apply must never run a GEMM (DESIGN.md §17)
# ---------------------------------------------------------------------------

def srht_no_gemm() -> list[Finding]:
    from repro.core import projection as proj
    a = jnp.zeros((16, 32), jnp.float32)
    out: list[Finding] = []
    for method in ("f32", "shgemm", "shgemm_fused"):
        out.extend(no_gemm(
            lambda key, a, m=method: proj.sketch(key, a, 8, dist="srht",
                                                 method=m),
            _key(), a, what=f"sketch(dist='srht', method='{method}')"))
    return out


# ---------------------------------------------------------------------------
# dtype flow: where precision may be lowered (the paper's SHGEMM contract)
# ---------------------------------------------------------------------------

# bf16 mode (repo default): A may be split into bf16 terms, the Omega
# stream (everything derived from the key) may be stored bf16.  Nothing may
# touch f16, and the accumulator path has no allowlisted downcast at all.
_BF16_ALLOW = (
    ("A", "float32", "bfloat16"),
    ("key", "float32", "bfloat16"),
)

# fp16 mode: the paper's Eq. 37-40 splits A into *scaled* f16 terms, so
# A->f16 and key->f16 are the sanctioned casts there.
_FP16_ALLOW = (
    ("A", "float32", "float16"),
    ("key", "float32", "float16"),
)


def sketch_dtype_flow() -> list[Finding]:
    from repro.core import projection as proj
    a = jnp.zeros((16, 32), jnp.float32)
    out: list[Finding] = []
    for method in ("f32", "shgemm", "lowp_single", "shgemm_fused"):
        out.extend(dtype_flow(
            lambda key, a, m=method: proj.sketch(key, a, 8, method=m),
            _key(), a, labels={0: "key", 1: "A"}, allow=_BF16_ALLOW,
            what=f"sketch(method='{method}', omega_dtype=bf16)"))
    out.extend(dtype_flow(
        lambda key, a: proj.sketch(key, a, 8, method="shgemm",
                                   omega_dtype=jnp.float16),
        _key(), a, labels={0: "key", 1: "A"}, allow=_FP16_ALLOW,
        what="sketch(method='shgemm', omega_dtype=f16)"))
    return out


def stream_update_dtype_flow() -> list[Finding]:
    """The streaming hot path inherits the same precision contract: a row
    tile absorbed by SketchState.update may lower precision only on the
    split terms and the Omega stream."""
    from repro.stream import state as st
    a_tile = jnp.zeros((8, 32), jnp.float32)

    def run(key, tile):
        s = st.init(key, 32, 8, max_rows=8, method="shgemm",
                    omega_dtype=jnp.bfloat16)
        return st.update(s, tile, 0).y

    return dtype_flow(run, _key(), a_tile, labels={0: "key", 1: "A"},
                      allow=_BF16_ALLOW, what="stream.update(shgemm)")


# ---------------------------------------------------------------------------
# determinism: library entry points may only consume caller-provided keys
# ---------------------------------------------------------------------------

def sketch_determinism() -> list[Finding]:
    from repro.core import projection as proj
    a = jnp.zeros((16, 32), jnp.float32)
    out: list[Finding] = []
    for method, dist in (("shgemm", "gaussian"), ("shgemm_fused", "gaussian"),
                         ("f32", "srht")):
        out.extend(determinism(
            lambda key, a, m=method, d=dist: proj.sketch(key, a, 8,
                                                         method=m, dist=d),
            _key(), a, what=f"sketch(method='{method}', dist='{dist}')"))
    return out


# ---------------------------------------------------------------------------
# Pallas kernel audits (DESIGN.md §9/§16 BlockSpec contracts)
# ---------------------------------------------------------------------------

def shgemm_fused_audit() -> list[Finding]:
    from repro.kernels import shgemm_fused as f
    a = jnp.zeros((256, 256), jnp.float32)
    k2 = jnp.zeros((1, 2), jnp.uint32)
    # (1, 2) SMEM scalars: the packed key and the (row, col) lattice offsets
    return audit_pallas(
        lambda a, k2: f.shgemm_fused_pallas(a, k2, 256, bm=128, bn=128,
                                            bk=128),
        a, k2, what="kernels/shgemm_fused.py", smem_widths=(2,))


def factored_decode_audit() -> list[Finding]:
    from repro.kernels import factored_decode as fd
    b, kvh, g, hd, r, s = 2, 2, 2, 8, 4, 256
    q = jnp.zeros((b, 1, g * kvh, hd), jnp.float32)
    k = jnp.zeros((b, s, kvh, hd), jnp.float32)
    v = jnp.zeros((b, s, kvh, hd), jnp.float32)
    us = jnp.zeros((b, kvh, s, r), jnp.float32)
    vt = jnp.zeros((b, kvh, r, hd), jnp.float32)
    comp = jnp.zeros((b,), jnp.int32)
    return audit_pallas(
        lambda *xs: fd.factored_decode_attention(
            *xs, write_pos=s - 1, scale=hd ** -0.5, block_kv=128),
        q, k, v, us, vt, us, vt, comp,
        what="kernels/factored_decode.py", scalar_prefetch=2)


# ---------------------------------------------------------------------------
# gauge audit: no weak-typed promotion into the streamed accumulators
# (the serve/stream dtype-pinning audit — DESIGN.md §18.3)
# ---------------------------------------------------------------------------

def stream_b_accumulation_weak_audit() -> list[Finding]:
    """The B = QᵀA accumulation is the f32 summation whose order and dtype
    the resume contract pins (DESIGN.md §14); a weak Python scalar mixing
    into it would let promotion semantics (and x64 flags) change the
    summation dtype silently."""
    from repro.core.rsvd import _dot
    q = jnp.zeros((16, 4), jnp.float32)
    blk = jnp.zeros((8, 12), jnp.float32)

    def accumulate(q, blk):
        b = jnp.zeros((q.shape[1], 12), jnp.float32)
        return b + _dot(q[:8].T, blk)

    return dtype_flow(accumulate, q, blk, labels={0: "A", 1: "A"},
                      allow=_BF16_ALLOW, report_weak=True,
                      what="resilience B-phase accumulation")


CONTRACTS: dict[str, Callable[[], list[Finding]]] = {
    "srht-no-gemm": srht_no_gemm,
    "sketch-dtype-flow": sketch_dtype_flow,
    "stream-update-dtype-flow": stream_update_dtype_flow,
    "sketch-determinism": sketch_determinism,
    "shgemm-fused-audit": shgemm_fused_audit,
    "factored-decode-audit": factored_decode_audit,
    "stream-b-weak-audit": stream_b_accumulation_weak_audit,
}


def run_repo_contracts(names: list[str] | None = None) -> list[Finding]:
    out: list[Finding] = []
    for name, contract in CONTRACTS.items():
        if names is not None and name not in names:
            continue
        try:
            out.extend(contract())
        except Exception as e:  # a contract that cannot trace is a finding
            out.append(Finding(
                rule="CONTRACT-ERROR", file=name, line=0,
                message=f"contract {name!r} failed to run: {e!r}",
                hint="the traced entry point changed shape/signature — "
                     "update the contract in analysis/contracts.py"))
    return out
