"""Pallas kernel auditor: BlockSpec/grid proofs for the repo's kernels.

Traces an entry point (abstractly — nothing runs), finds every
``pallas_call`` equation, and checks its structural contracts against the
``GridMapping`` the call was lowered with:

* **Output-block disjointness** (rule ``PL-WRITE-ALIAS``): enumerating the
  grid, no two grid points that differ in a *parallel* axis may map to the
  same output block.  Revisits along ``arbitrary`` (sequential) axes are
  the legal accumulation pattern (`shgemm_fused`'s k loop, the decode
  kernel's kv loop); a collision across parallel axes means two
  potentially-concurrent grid steps write the same output window — silent
  data races on a real backend, order-dependent results in interpret mode.
  An output index map that reads a scalar-prefetch operand depends on
  run-time values the enumeration cannot see, so it is a finding too.
* **SMEM scalar shape** (rule ``PL-SMEM-SHAPE``): SMEM blocks must be tiny
  2-D scalars — ``(1, w)`` with ``w`` within the audited width (1 by
  default; `shgemm_fused` declares width 2 for its ``(key, offsets)``
  pairs) — and the kernel must take exactly the declared number of
  scalar-prefetch operands, each a whole rank-1 array (`factored_decode`
  declares two: ``comp_len`` and ``write_pos``).  A wide or high-rank SMEM
  operand is almost always a misplaced tensor that belongs in VMEM.

The index maps are evaluated with ``jax.extend.core.jaxpr_as_fun`` over the
full grid product (scalar-prefetch operands, proven unread, are passed as
zeros), so audits should trace *small* shapes (a 2x2x2 grid proves the
same structural property as a 256^3 one).
"""

from __future__ import annotations

import itertools
from typing import Callable, Iterator, Sequence

import jax
import jax.extend.core as jc
import numpy as np

from repro.analysis.findings import Finding
from repro.analysis.jaxpr_passes import iter_eqns

__all__ = ["audit_pallas", "pallas_calls", "MAX_GRID_POINTS"]

MAX_GRID_POINTS = 65536


def pallas_calls(fn: Callable, *args) -> Iterator[jc.JaxprEqn]:
    jaxpr = jax.make_jaxpr(fn)(*args)
    for eqn in iter_eqns(jaxpr.jaxpr):
        if eqn.primitive.name == "pallas_call":
            yield eqn


def _dimension_semantics(eqn, n_axes: int) -> tuple[str, ...]:
    """parallel/arbitrary per grid axis; unknown -> all parallel (the
    conservative choice: more pairs must prove disjoint)."""
    cp = eqn.params.get("compiler_params") or {}
    sem = getattr(cp.get("mosaic_tpu"), "dimension_semantics", None)
    if sem:
        return tuple(str(getattr(d, "value", d)).lower() for d in sem)
    return ("parallel",) * n_axes


def _reads_prefetch(bm, n_grid: int) -> bool:
    """Whether ``bm``'s index map uses any operand past the grid indices
    (the scalar-prefetch refs)."""
    jaxpr = bm.index_map_jaxpr.jaxpr
    used = {v for e in jaxpr.eqns for v in e.invars if isinstance(v, jc.Var)}
    used |= {v for v in jaxpr.outvars if isinstance(v, jc.Var)}
    return any(v in used for v in jaxpr.invars[n_grid:])


def _eval_index_map(bm, point: Sequence[int]) -> tuple[int, ...]:
    cj = bm.index_map_jaxpr
    extra = [np.zeros(v.aval.shape, v.aval.dtype)
             for v in cj.jaxpr.invars[len(point):]]
    out = jc.jaxpr_as_fun(cj)(*point, *extra)
    return tuple(int(x) for x in out)


def _is_smem(bm) -> bool:
    aval = getattr(bm, "block_aval", None)
    space = getattr(aval, "memory_space", None)
    return space is not None and "smem" in str(space).lower()


def audit_pallas(fn: Callable, *args, what: str = "kernel",
                 smem_widths: Sequence[int] = (1,),
                 scalar_prefetch: int = 0,
                 max_grid_points: int = MAX_GRID_POINTS) -> list[Finding]:
    """Audit every pallas_call reachable from ``fn(*args)``; returns
    findings (empty = every contract proven for the traced grid).
    ``scalar_prefetch`` is the number of scalar-prefetch operands each
    kernel declares."""
    findings: list[Finding] = []
    n_calls = 0
    for eqn in pallas_calls(fn, *args):
        n_calls += 1
        gm = eqn.params["grid_mapping"]
        grid = tuple(int(g) for g in gm.grid)
        name = eqn.params.get("name_and_src_info", None)
        kname = getattr(name, "name", None) or what
        sem = _dimension_semantics(eqn, len(grid))
        par_axes = [i for i, s in enumerate(sem) if s == "parallel"]

        # --- SMEM scalar shapes -----------------------------------------
        for bm in gm.block_mappings:
            if not _is_smem(bm):
                continue
            shape = tuple(int(getattr(s, "block_size", s))
                          for s in bm.block_shape)
            ok = (len(shape) == 2 and shape[0] == 1
                  and shape[1] in tuple(smem_widths))
            if not ok:
                findings.append(Finding(
                    rule="PL-SMEM-SHAPE", file=what, line=0,
                    message=(f"SMEM operand ({bm.origin}) of {kname} has "
                             f"block shape {shape}; audited widths are "
                             f"(1, {'/'.join(map(str, smem_widths))})"),
                    hint="SMEM holds scalars: use the kernel's declared "
                         "(1, w) block, pass the whole rank-1 array by "
                         "scalar prefetch, or move the operand to VMEM",
                    match=f"{what}:smem:{bm.origin}:{shape}"))

        # --- scalar-prefetch operands -----------------------------------
        pre = [tuple(v.aval.shape) for v in eqn.invars[gm.slice_index_ops]]
        if len(pre) != scalar_prefetch or any(len(s) != 1 for s in pre):
            findings.append(Finding(
                rule="PL-SMEM-SHAPE", file=what, line=0,
                message=(f"{kname} takes {len(pre)} scalar-prefetch "
                         f"operand(s) of shapes {pre}; audited "
                         f"{scalar_prefetch} of rank 1"),
                hint="scalar prefetch places whole arrays in SMEM: keep "
                     "them rank-1 and declare their count in the contract",
                match=f"{what}:prefetch:{pre}"))

        # --- output-block disjointness ----------------------------------
        total = 1
        for g in grid:
            total *= g
        if total > max_grid_points:
            findings.append(Finding(
                rule="PL-WRITE-ALIAS", file=what, line=0,
                message=(f"grid {grid} of {kname} too large to enumerate "
                         f"({total} > {max_grid_points}) — audit with a "
                         "smaller traced shape"),
                hint="contracts are structural: a tiny grid proves the "
                     "same index-map property",
                match=f"{what}:grid_too_large"))
            continue
        out_mappings = [bm for bm in gm.block_mappings
                        if str(bm.origin) == "outputs"
                        or "output" in str(bm.origin)]
        for oi, bm in enumerate(out_mappings):
            if _reads_prefetch(bm, len(grid)):
                findings.append(Finding(
                    rule="PL-WRITE-ALIAS", file=what, line=0,
                    message=(f"output {oi} of {kname}: the index map reads "
                             "a scalar-prefetch operand, so its blocks "
                             "depend on run-time values the audit cannot "
                             "enumerate"),
                    hint="index outputs by grid position only, or audit "
                         "this kernel by another means",
                    match=f"{what}:prefetch_index:out{oi}"))
                continue
            seen: dict[tuple, tuple] = {}
            aliased = False
            for point in itertools.product(*[range(g) for g in grid]):
                block = _eval_index_map(bm, point)
                key = tuple(point[i] for i in par_axes)
                prev = seen.setdefault(block, key)
                if prev != key:
                    findings.append(Finding(
                        rule="PL-WRITE-ALIAS", file=what, line=0,
                        message=(f"output {oi} of {kname}: grid points "
                                 f"{prev} and {key} (parallel axes "
                                 f"{par_axes} of grid {grid}) both write "
                                 f"block {block}"),
                        hint="make the output index_map injective over the "
                             "parallel axes, or mark the revisited axis "
                             "'arbitrary' and accumulate via a scratch ref "
                             "with a pl.when-guarded store",
                        match=f"{what}:alias:out{oi}"))
                    aliased = True
                    break
            if aliased:
                continue
    if n_calls == 0:
        findings.append(Finding(
            rule="PL-WRITE-ALIAS", file=what, line=0,
            message=f"no pallas_call found tracing {what}",
            hint="the audit entry point no longer reaches the kernel — "
                 "update the contract",
            match=f"{what}:no_pallas_call"))
    return findings
