"""Device time of a jitted program by the named scopes inside it.

The program puts each line of an algorithm under a ``jax.named_scope``
(``repro.tracing.SCOPES``).  A scope lives only in the compiled program's
metadata: each HLO instruction's ``op_name`` names the scopes it was traced
under (``jit(rsvd)/rsvd.qr/jit(qr)/geqrf``).  A device trace names each
operation's event by its instruction (``%custom-call.196 = ...``).  So the
compiled program's HLO text maps the trace's operations to scopes.

The benchmark's own trace of the window is reduced to op names without
their instruction numbers (``chipbench/trace.py``), so the readers here
take a short trace of their own after the window: the program the cell's
driver names, compiled at the cell's arguments and called back to back as
the window calls it.  A driver names it in ``scoped_call(i) -> (jitted
function, array arguments, static arguments)``, call ``i`` of that trace;
the function's scopes are ``repro.tracing.SCOPES[<its name>]`` and its
programs in the trace are ``jit_<its name>``.
"""

from __future__ import annotations

import bisect
import re
import shutil
from collections import Counter, defaultdict, deque
from pathlib import Path

from chipbench.trace import module_base, self_times

ROOT = Path(__file__).resolve().parents[1]
REPS = 20                     # calls in the scopes' own trace

_DEFINITION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=(.*)$")
_METADATA = re.compile(r"\bmetadata=\{[^}]*\}")
_OP_NAME = re.compile(r"\bop_name=\"([^\"]*)\"")
_OPERAND = re.compile(r"%([\w.\-]+)")
_EVENT = re.compile(r"^%?([^\s=]+)")


def instruction_scopes(hlo_text: str, scopes) -> dict[str, str]:
    """Instruction name -> the innermost of ``scopes`` on its ``op_name``.
    An instruction with no scope of its own (a copy, slice or concatenation
    the compiler added, with no metadata) takes the scope of its nearest
    consumer with one, the earliest in the schedule among the nearest
    (through instructions like it); one that feeds no scoped instruction
    has none."""
    wanted = set(scopes)
    own, users, order = {}, defaultdict(list), {}
    for i, line in enumerate(hlo_text.splitlines()):
        m = _DEFINITION.match(line)
        if m is None:
            continue
        name, rest = m.groups()
        order[name] = i
        meta = _METADATA.search(rest)
        op = _OP_NAME.search(meta.group(0)) if meta else None
        inside = [p for p in op.group(1).split("/") if p in wanted] \
            if op else []
        if inside:
            own[name] = inside[-1]
        for operand in _OPERAND.findall(_METADATA.sub("", rest)):
            users[operand].append(name)

    def consumer_scope(name: str) -> str | None:
        seen, queue = {name}, deque([name])
        while queue:
            for user in sorted(users.get(queue.popleft(), ()),
                               key=lambda u: order.get(u, 0)):
                if user in own:
                    return own[user]
                if user not in seen:
                    seen.add(user)
                    queue.append(user)
        return None

    out = dict(own)
    for name in order:
        if name not in own:
            scope = consumer_scope(name)
            if scope is not None:
                out[name] = scope
    return out


def instruction(event_name: str) -> str:
    """``%fusion.902 = f32[...] fusion(...)`` -> ``fusion.902``."""
    m = _EVENT.match(event_name)
    return m.group(1) if m else event_name


def self_time_by_instruction(path, module: str) -> tuple[int, dict]:
    """Executions of the programs named ``module`` (``jit_rsvd``) in the
    trace at ``path``, and each instruction's device seconds inside them,
    less the time of the operations it contains (a loop's body)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    runs, events = 0, []
    for plane in data.planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        if "XLA Modules" not in lines or "XLA Ops" not in lines:
            continue
        spans = sorted((e.start_ns, e.start_ns + e.duration_ns)
                       for e in lines["XLA Modules"].events
                       if module_base(e.name) == module)
        starts = [s for s, _ in spans]
        runs += len(spans)
        for e in lines["XLA Ops"].events:
            a, b = e.start_ns, e.start_ns + e.duration_ns
            i = bisect.bisect_right(starts, a) - 1
            if i >= 0 and b <= spans[i][1]:
                events.append((a, b, instruction(e.name)))
    return runs, {k: v * 1e-9 for k, v in self_times(events).items()}


def scope_ms(runs: int, seconds: dict, scope_of: dict) -> dict[str, float]:
    """Device milliseconds per execution by scope; ``""`` collects the
    instructions under no scope (copies the compiler added, say)."""
    out: Counter = Counter()
    for name, s in seconds.items():
        out[scope_of.get(name, "")] += 1e3 * s / runs
    return dict(out)


_MEASURED: dict[int, dict | None] = {}


def scope_ms_of(run) -> dict[str, float] | None:
    """Device ms per call of the driver's scoped program by scope at the
    cell's arguments, from a trace of ``REPS`` calls made after the window
    (measured once per run).  None without a traced run, for a driver with
    no scoped program, or for a program that carries no scopes."""
    if run.trace is None:
        return None
    if id(run) not in _MEASURED:
        _MEASURED[id(run)] = _measure(run.driver)
    return _MEASURED[id(run)]


def scoped_program(driver):
    """``(module, compiled, scope_of, calls)`` for the driver's scoped
    program: its programs' name in a trace, the program compiled at call 0's
    arguments, each HLO instruction's scope and the array arguments of the
    ``REPS`` calls; None where the driver names no program or the program
    has no scopes in ``repro.tracing.SCOPES``."""
    from repro import tracing
    if not hasattr(driver, "scoped_call"):
        return None
    calls = [driver.scoped_call(i) for i in range(REPS)]
    fn, args, static = calls[0]
    name = fn.__name__
    if name not in tracing.SCOPES:
        return None
    compiled = fn.lower(*args, **static).compile()
    scope_of = instruction_scopes(compiled.as_text(), tracing.SCOPES[name])
    if not scope_of:
        return None
    return f"jit_{name}", compiled, scope_of, [a for _, a, _ in calls]


def _measure(driver) -> dict[str, float] | None:
    import jax
    program = scoped_program(driver)
    if program is None:
        return None
    module, compiled, scope_of, calls = program
    out_dir = ROOT / ".chipbench" / "scopes"
    shutil.rmtree(out_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.block_until_ready(compiled(*calls[0]))
    jax.profiler.start_trace(str(out_dir), profiler_options=opts)
    try:
        for args in calls:
            jax.block_until_ready(compiled(*args))
    finally:
        jax.profiler.stop_trace()
    files = sorted(out_dir.glob("**/*.xplane.pb"))
    try:
        runs, seconds = (self_time_by_instruction(files[-1], module)
                         if files else (0, {}))
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)
    return scope_ms(runs, seconds, scope_of) if runs else None


def read_scope(run, scope: str) -> float | None:
    """One scope's device ms per call of the driver's scoped program; None
    where the program has no such scope."""
    ms = scope_ms_of(run)
    return None if ms is None or scope not in ms else ms[scope]
