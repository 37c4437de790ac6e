"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device
numbers, with nothing but JAX's own reader.

What a TPU trace holds, as read here:

* one plane ``/device:TPU:<i>`` per chip, with the line ``XLA Ops`` (every
  operation's execution; a loop or conditional contains the operations of
  its body, on the same line) and the line ``XLA Modules`` (one event per
  program execution, named ``jit_<function>(<program id>)``, with a
  ``run_id`` stat);
* the host plane ``/host:CPU``: the benchmark's spans as ``chipbench.<name>``
  annotations, and the runtime's ``DoEnqueueProgram`` events, whose
  ``run_id`` ties each program execution to when the host enqueued it.

All of it is on one clock.  The window is the ``chipbench.window`` span.
"""

from __future__ import annotations

import bisect
import re
from collections import Counter, defaultdict
from pathlib import Path

from chipbench.spans import PREFIX

_OP_NAME = re.compile(r"%?([A-Za-z_][\w\-]*?)(?:\.\d+)?(?:\s|=|$)")
_MODULE_NAME = re.compile(r"([^()]+)")


def op_name(event_name: str) -> str:
    """``%convolution_convert_fusion.2 = f32[...] ...`` -> its HLO op name
    without the numeric suffix: ``convolution_convert_fusion``."""
    m = _OP_NAME.match(event_name)
    return m.group(1) if m else event_name[:40]


def module_base(event_name: str) -> str:
    """``jit_run(8081028625803501406)`` -> ``jit_run``."""
    m = _MODULE_NAME.match(event_name)
    return m.group(1) if m else event_name


def union_length(intervals) -> tuple[float, list[tuple[float, float]]]:
    """Total length of the union of (start, end) intervals, and the merged
    intervals in order."""
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return sum(b - a for a, b in merged), [tuple(x) for x in merged]


def self_times(events) -> dict:
    """Per op name, the time its executions spent in themselves and not in
    an operation they contain (events: (start, end, name))."""
    out: Counter = Counter()
    stack: list[list] = []              # [end, name, duration, child time]

    def close(item):
        out[item[1]] += item[2] - item[3]

    for a, b, name in sorted(events, key=lambda e: (e[0], -(e[1] - e[0]))):
        while stack and stack[-1][0] <= a:
            close(stack.pop())
        if stack:
            stack[-1][3] += b - a
        stack.append([b, name, b - a, 0.0])
    while stack:
        close(stack.pop())
    return dict(out)


class Summary:
    """The device numbers of one traced window (seconds throughout)."""

    def __init__(self, *, window, busy, spans, modules, modules_all,
                 ops_self, op_time, gaps, chips):
        self.window_ns = window
        self.window_s = (window[1] - window[0]) * 1e-9
        self.busy_s = busy * 1e-9
        self.spans = spans                # name -> [(start, end)] in ns
        self.modules = modules            # name -> [executions, seconds,
        #                                            owning span]
        self.modules_all = modules_all    # the same over the whole trace
        self.ops_self = ops_self          # op name -> self seconds
        self.op_time = op_time            # op name -> seconds (with nesting)
        self.gaps = gaps                  # [(seconds, span)] longest first
        self.chips = chips

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def module_time(self, base: str, *, whole_trace: bool = False
                    ) -> tuple[int, float]:
        """Executions and device seconds of the programs named ``base``
        (``jit_sketch``), over every program id: inside the window, or
        anywhere in the trace (a program timed after the window closed)."""
        n = s = 0
        table = self.modules_all if whole_trace else self.modules
        for name, (k, t, _) in table.items():
            if module_base(name) == base:
                n, s = n + k, s + t
        return n, s

    def span_device_time(self, span: str) -> tuple[int, float]:
        """Executions and device seconds of the programs that the host
        enqueued, for the most part, inside spans named ``span``."""
        n = s = 0
        for k, t, owner in self.modules.values():
            if owner == span:
                n, s = n + k, s + t
        return n, s

    def kernel_time(self, name: str) -> tuple[int, float]:
        """Executions and device seconds of the operation ``name`` (a
        Pallas kernel's name), from the op time table."""
        return self.op_time.get(name, (0, 0.0))

    def breakdown(self) -> dict:
        ops = sorted(self.ops_self.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[span, s] for s, span in self.gaps[:10]]}


def reduce(path: Path | str) -> Summary:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    spans: dict[str, list] = defaultdict(list)
    enqueued: dict[int, float] = {}
    devices = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            devices.append(plane)
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    spans[e.name[len(PREFIX):]].append(
                        (e.start_ns, e.start_ns + e.duration_ns))
                elif e.name == "DoEnqueueProgram":
                    rid = dict(e.stats).get("run_id")
                    if rid is not None:
                        enqueued[int(rid)] = e.start_ns
    windows = spans.pop("window", [])
    if len(windows) != 1:
        raise ValueError(f"trace holds {len(windows)} window spans, not 1")
    w0, w1 = windows[0]

    # innermost benchmark span at a time: spans sorted by start
    flat = sorted((a, b, name) for name, ivs in spans.items()
                  for a, b in ivs)
    starts = [f[0] for f in flat]

    def span_at(t: float) -> str:
        i = bisect.bisect_right(starts, t)
        best = None
        for a, b, name in reversed(flat[max(0, i - 64):i]):
            if a <= t <= b and (best is None or b - a < best[1] - best[0]):
                best = (a, b, name)
        return best[2] if best else "no span"

    busy_total = 0.0
    modules: dict[str, list] = {}
    modules_all: dict[str, list] = {}
    owners: dict[str, Counter] = defaultdict(Counter)
    op_events, gaps = [], []
    chips = 0
    for plane in devices:
        lines = {ln.name: ln for ln in plane.lines}
        ops = lines.get("XLA Ops")
        if ops is None:
            continue
        ivs = []
        for e in ops.events:
            a, b = e.start_ns, e.start_ns + e.duration_ns
            if b <= w0 or a >= w1:
                continue
            ivs.append((max(a, w0), min(b, w1)))
            op_events.append((a, b, op_name(e.name)))
        if not ivs:
            continue
        chips += 1
        busy, merged = union_length(ivs)
        busy_total += busy
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append(((b - a) * 1e-9, span_at((a + b) / 2)))
        mod_line = lines.get("XLA Modules")
        for e in (mod_line.events if mod_line is not None else ()):
            a, b = e.start_ns, e.start_ns + e.duration_ns
            rec = modules_all.setdefault(e.name, [0, 0.0, None])
            rec[0] += 1
            rec[1] += e.duration_ns * 1e-9
            if b <= w0 or a >= w1:
                continue
            rec = modules.setdefault(e.name, [0, 0.0, None])
            rec[0] += 1
            rec[1] += (min(b, w1) - max(a, w0)) * 1e-9
            rid = dict(e.stats).get("run_id")
            if rid is not None and int(rid) in enqueued:
                owners[e.name][span_at(enqueued[int(rid)])] += 1
    if not chips:
        raise ValueError("no operation ran on a device inside the window")
    for name, rec in modules.items():
        if owners[name]:
            rec[2] = owners[name].most_common(1)[0][0]
    op_time: dict[str, list] = {}
    for a, b, name in op_events:
        rec = op_time.setdefault(name, [0, 0.0])
        rec[0] += 1
        rec[1] += (b - a) * 1e-9
    ops_self = {k: v * 1e-9 / chips for k, v in self_times(op_events).items()}
    gaps.sort(key=lambda g: -g[0])
    return Summary(window=(w0, w1), busy=busy_total / chips, spans=dict(spans),
                   modules=modules, modules_all=modules_all,
                   ops_self=ops_self,
                   op_time={k: tuple(v) for k, v in op_time.items()},
                   gaps=gaps, chips=chips)
