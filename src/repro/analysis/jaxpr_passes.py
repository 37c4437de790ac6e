"""Jaxpr structural passes: no_gemm, dtype_flow, determinism.

Each pass traces a library entry point with ``jax.make_jaxpr`` (abstract —
nothing executes) and walks the closed jaxpr, recursing into call-like
primitives (pjit, scan, while, cond, custom_* and the Pallas kernel body),
to enforce a structural contract:

* :func:`no_gemm` — the traced program contains no matrix-multiply
  primitive.  Generalizes the SRHT jaxpr assert (DESIGN.md §17): the
  structured apply path must be adds/gathers only, so an accidental
  ``dot_general`` sneaking into ``sketch(dist="srht")`` is a contract
  break, not a perf regression to be found later.
* :func:`dtype_flow` — labels designated inputs (A, the key/Omega stream,
  ...) and propagates the labels through the dataflow; every float
  *downcast* (a ``convert_element_type`` to a narrower float dtype) along
  a labeled path must appear in the contract's allowlist.  This pins the
  paper's precision story mechanically: Omega may live in bf16/fp16, A may
  be split to bf16 terms, but a stray ``f32 -> f16`` on the A path (or any
  f64 appearance) fails the pass.  ``report_weak=True`` additionally
  reports weak-typed promotions into labeled float paths — the audit mode
  behind the serve/stream gauge pinning.
* :func:`determinism` — flags nondeterminism hazards: ``random_seed``
  inside the traced program (a PRNG key seeded from a constant instead of
  passed in — unkeyed randomness), random draws whose key derives only
  from constants, and accumulating float scatters without
  ``unique_indices`` (atomics-nondeterministic on GPU backends).

All passes return plain ``Finding`` lists; ``file:line`` anchors come from
the equation's user source info, so a finding points at the repo line that
introduced the offending op.
"""

from __future__ import annotations

from typing import Callable, Iterable, Iterator, Optional, Sequence

import jax
import jax.extend.core as jc
import jax.numpy as jnp

from repro.analysis.findings import Finding

__all__ = ["no_gemm", "dtype_flow", "determinism", "iter_eqns",
           "CastEvent", "GEMM_PRIMS", "NONDET_SCATTER_PRIMS"]

GEMM_PRIMS = ("dot_general", "conv_general_dilated")

# accumulating scatters: order-dependent float atomics on GPU backends
NONDET_SCATTER_PRIMS = ("scatter-add", "scatter-mul")

_FLOAT_BITS = {"bfloat16": 16, "float16": 16, "float32": 32, "float64": 64,
               "float8_e4m3fn": 8, "float8_e5m2": 8}


def _src(eqn) -> tuple[str, int]:
    """(file, line) of the user frame that emitted this equation."""
    try:
        import jax._src.source_info_util as siu
        frame = siu.user_frame(eqn.source_info)
        if frame is not None:
            return frame.file_name, frame.start_line
    except Exception:
        pass
    return "<traced>", 0


def _subjaxprs(eqn) -> Iterator[jc.Jaxpr]:
    for v in eqn.params.values():
        if isinstance(v, jc.ClosedJaxpr):
            yield v.jaxpr
        elif isinstance(v, jc.Jaxpr):
            yield v
        elif isinstance(v, (tuple, list)):
            for x in v:
                if isinstance(x, jc.ClosedJaxpr):
                    yield x.jaxpr
                elif isinstance(x, jc.Jaxpr):
                    yield x


def iter_eqns(jaxpr: jc.Jaxpr) -> Iterator[jc.JaxprEqn]:
    """All equations, recursing into sub-jaxprs (pjit bodies, scan/cond
    branches, custom_jvp calls, Pallas kernel bodies)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _subjaxprs(eqn):
            yield from iter_eqns(sub)


def _trace(fn: Callable, *args) -> jc.ClosedJaxpr:
    return jax.make_jaxpr(fn)(*args)


def _align_operands(eqn, sub: jc.Jaxpr):
    """(sub_invar, eqn_invar) pairs for label/taint propagation into a
    sub-jaxpr.  Operands align from the *start* (pjit/scan/while pass
    operands positionally; a Pallas kernel's extra trailing invars are its
    output/scratch refs), except ``cond``, whose branches drop the leading
    predicate operand."""
    operands = eqn.invars
    if eqn.primitive.name == "cond":
        operands = operands[1:]
    return zip(sub.invars, operands)


# ---------------------------------------------------------------------------
# no_gemm
# ---------------------------------------------------------------------------

def no_gemm(fn: Callable, *args, denied: Sequence[str] = GEMM_PRIMS,
            what: str = "program") -> list[Finding]:
    """Assert the traced program is GEMM-free (rule ``JAX-NO-GEMM``)."""
    findings = []
    jaxpr = _trace(fn, *args)
    for eqn in iter_eqns(jaxpr.jaxpr):
        if eqn.primitive.name in denied:
            file, line = _src(eqn)
            findings.append(Finding(
                rule="JAX-NO-GEMM", file=file, line=line,
                message=(f"{eqn.primitive.name} in {what} contracted to be "
                         "GEMM-free"),
                hint=("structured applies must use adds/gathers only "
                      "(DESIGN.md §17); if a GEMM is intentional, trace a "
                      "different entry point or drop the contract"),
                match=f"{what}:{eqn.primitive.name}"))
    return findings


# ---------------------------------------------------------------------------
# dtype_flow
# ---------------------------------------------------------------------------

class CastEvent:
    """One dtype cast observed on a labeled path (diagnostic record —
    ``dtype_flow`` returns these via ``events_out`` for reporting)."""

    def __init__(self, labels: frozenset, src_dtype: str, dst_dtype: str,
                 file: str, line: int):
        self.labels, self.src, self.dst = labels, src_dtype, dst_dtype
        self.file, self.line = file, line

    def __repr__(self):
        labs = ",".join(sorted(self.labels)) or "<const>"
        return f"CastEvent({labs}: {self.src}->{self.dst} @{self.file}:{self.line})"


def _is_float(name: str) -> bool:
    return name in _FLOAT_BITS


def _is_downcast(src: str, dst: str) -> bool:
    return (_is_float(src) and _is_float(dst)
            and _FLOAT_BITS[dst] < _FLOAT_BITS[src])


def _label_env_flow(jaxpr: jc.Jaxpr, init: dict, on_eqn) -> None:
    """Propagate label sets through a jaxpr's dataflow.

    ``init`` maps invars -> frozenset(labels); every eqn's outvars get the
    union of its invars' labels; ``on_eqn(eqn, labels_of)`` is called per
    equation (before recursion) with a lookup for operand labels.  Call-like
    primitives recurse with labels mapped positionally onto the sub-jaxpr's
    invars (aligned from the end, which matches pjit exactly and scan /
    while closely enough for label purposes).
    """
    env: dict = dict(init)

    def labels_of(atom) -> frozenset:
        if isinstance(atom, jc.Literal):
            return frozenset()
        return env.get(atom, frozenset())

    for eqn in jaxpr.eqns:
        on_eqn(eqn, labels_of)
        in_labels = frozenset().union(*[labels_of(v) for v in eqn.invars]) \
            if eqn.invars else frozenset()
        for out in eqn.outvars:
            env[out] = in_labels
        for sub in _subjaxprs(eqn):
            sub_init = {sv: labels_of(ov) for sv, ov in
                        _align_operands(eqn, sub)}
            _label_env_flow(sub, sub_init, on_eqn)


def dtype_flow(fn: Callable, *args,
               labels: Optional[dict[int, str]] = None,
               allow: Iterable[tuple[str, str, str]] = (),
               forbid_f64: bool = True,
               report_weak: bool = False,
               what: str = "program",
               events_out: Optional[list] = None) -> list[Finding]:
    """Report every float downcast along labeled paths; fail on casts not
    in ``allow`` (rule ``JAX-DTYPE-CAST``) and on any float64 appearance
    (rule ``JAX-F64``).

    ``labels`` maps positional arg index -> label name (unlabeled args and
    constants carry no label and their downcasts are checked against the
    ``"*"`` wildcard only).  ``allow`` entries are ``(label, src, dst)``
    dtype-name triples; ``("*", src, dst)`` allows the cast on every path.
    With ``report_weak``, weak-typed float operands mixing into labeled
    float arithmetic are reported as ``JAX-WEAK-PROMOTE`` — advisory, used
    by the gauge-pinning audit.
    """
    labels = labels or {}
    allow = set(allow)
    findings: list[Finding] = []
    jaxpr = _trace(fn, *args)

    flat_labels = {}
    for i, v in enumerate(jaxpr.jaxpr.invars):
        if i in labels:
            flat_labels[v] = frozenset({labels[i]})

    def allowed(labs: frozenset, src: str, dst: str) -> bool:
        # strictest-label-wins: a value carrying several labels may only be
        # downcast if every label's contract allows it
        if ("*", src, dst) in allow:
            return True
        if not labs:
            return False
        return all((l, src, dst) in allow for l in labs)

    def on_eqn(eqn, labels_of):
        name = eqn.primitive.name
        if name == "convert_element_type":
            src_aval = eqn.invars[0].aval
            src = str(src_aval.dtype)
            dst = str(jnp.dtype(eqn.params["new_dtype"]))
            labs = labels_of(eqn.invars[0])
            file, line = _src(eqn)
            if events_out is not None and (_is_float(src) or _is_float(dst)):
                events_out.append(CastEvent(labs, src, dst, file, line))
            if _is_downcast(src, dst) and not allowed(labs, src, dst):
                path = ",".join(sorted(labs)) or "<unlabeled>"
                findings.append(Finding(
                    rule="JAX-DTYPE-CAST", file=file, line=line,
                    message=(f"{src} -> {dst} downcast on the [{path}] path "
                             f"of {what} is not in the precision allowlist"),
                    hint=("precision may only be lowered where the contract "
                          "says so (Omega storage, split terms — DESIGN.md "
                          "§18); add an allowlist entry only with a numerics "
                          "argument"),
                    match=f"{what}:{path}:{src}->{dst}"))
        if forbid_f64:
            for v in eqn.outvars:
                aval = getattr(v, "aval", None)
                if aval is not None and str(getattr(aval, "dtype", "")) \
                        == "float64":
                    file, line = _src(eqn)
                    findings.append(Finding(
                        rule="JAX-F64", file=file, line=line,
                        message=f"float64 value produced by {name} in {what}",
                        hint="the repo runs x64-disabled; f64 on device is "
                             "always an accident (host-side math.sqrt is "
                             "fine)",
                        match=f"{what}:f64:{name}"))
        if report_weak and name in ("add", "sub", "mul", "div", "max", "min"):
            avals = [getattr(v, "aval", None) for v in eqn.invars]
            weak = [a for a in avals if a is not None
                    and getattr(a, "weak_type", False)
                    and _is_float(str(a.dtype))]
            strong = [v for v, a in zip(eqn.invars, avals) if a is not None
                      and not getattr(a, "weak_type", False)
                      and _is_float(str(a.dtype))]
            if weak and strong:
                labs = frozenset().union(*[labels_of(v) for v in strong])
                if labs:
                    file, line = _src(eqn)
                    path = ",".join(sorted(labs))
                    findings.append(Finding(
                        rule="JAX-WEAK-PROMOTE", file=file, line=line,
                        message=(f"weak-typed float scalar mixes into the "
                                 f"[{path}] path of {what} at {name}"),
                        hint="pin the scalar with an explicit dtype "
                             "(jnp.float32(x)) so promotion cannot drift "
                             "with x64 flags",
                        match=f"{what}:{path}:weak:{name}"))

    _label_env_flow(jaxpr.jaxpr, flat_labels, on_eqn)
    return findings


# ---------------------------------------------------------------------------
# determinism
# ---------------------------------------------------------------------------

def determinism(fn: Callable, *args, what: str = "program") -> list[Finding]:
    """Flag nondeterminism hazards (rules ``JAX-UNKEYED``, ``JAX-NONDET``).

    Unkeyed randomness = a ``random_seed`` equation inside the traced
    program (a key created from a baked-in constant — the caller cannot
    vary or reproduce the stream), or a random-bits draw whose key operands
    derive only from constants.  Nondeterministic primitives = accumulating
    float scatters without ``unique_indices`` (GPU atomics are
    order-nondeterministic).
    """
    findings: list[Finding] = []
    jaxpr = _trace(fn, *args)

    # mark which vars derive from the entry point's inputs
    from_input: set = set(jaxpr.jaxpr.invars)

    def walk(jx: jc.Jaxpr, inputs: set) -> None:
        derived = set(inputs)
        for eqn in jx.eqns:
            name = eqn.primitive.name
            any_input = any((not isinstance(v, jc.Literal)) and v in derived
                            for v in eqn.invars)
            if name == "random_seed":
                file, line = _src(eqn)
                findings.append(Finding(
                    rule="JAX-UNKEYED", file=file, line=line,
                    message=(f"PRNG key seeded inside {what} — the "
                             "randomness is not keyed by any input"),
                    hint="thread a jax.Array key through the entry point "
                         "(fold_in for substreams) instead of calling "
                         "PRNGKey/key in library code",
                    match=f"{what}:random_seed"))
            elif name in ("random_bits", "threefry2x32") and not any_input:
                file, line = _src(eqn)
                findings.append(Finding(
                    rule="JAX-UNKEYED", file=file, line=line,
                    message=(f"random draw in {what} whose key derives only "
                             "from constants"),
                    hint="derive the key from a caller-provided input",
                    match=f"{what}:const_key:{name}"))
            elif name in NONDET_SCATTER_PRIMS:
                unique = eqn.params.get("unique_indices", False)
                dt = str(eqn.outvars[0].aval.dtype) if eqn.outvars else ""
                if not unique and _is_float(dt):
                    file, line = _src(eqn)
                    findings.append(Finding(
                        rule="JAX-NONDET", file=file, line=line,
                        message=(f"accumulating float scatter ({name}) "
                                 f"without unique_indices in {what} — "
                                 "atomics order is backend-nondeterministic"),
                        hint="use unique indices, a segment_sum with "
                             "deterministic layout, or sort-then-reduce",
                        match=f"{what}:{name}"))
            if any_input:
                derived.update(eqn.outvars)
            for sub in _subjaxprs(eqn):
                sub_inputs = {sv for sv, ov in _align_operands(eqn, sub)
                              if (not isinstance(ov, jc.Literal))
                              and ov in derived}
                walk(sub, sub_inputs)

    walk(jaxpr.jaxpr, from_input)
    return findings
