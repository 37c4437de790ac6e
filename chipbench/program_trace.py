"""The program's own spans in a profiler trace, and the device's idle gaps
labelled by what the host was doing in them.

    python chipbench/program_trace.py <file.xplane.pb>

The program marks its host work with ``repro.<name>`` annotations
(``repro.tracing.SPANS``), on the profiler's clock; a span's metadata
(the scheduler step's counters, say) comes back as the event's stats.  An
idle gap of the device is labelled by the innermost event that covers most
of it: a program span, else one of JAX's dispatch events
(``PjitFunction(<fn>)``), else the benchmark's span at the gap's middle, as
``chipbench/trace.py`` labels it.  Only the window (the ``chipbench.window``
span, or the device's first to last operation) counts.
"""

from __future__ import annotations

import bisect
import sys
from collections import Counter, defaultdict
from pathlib import Path

if __package__ in (None, ""):         # run as a script from the checkout
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench.spans import PREFIX as HARNESS  # noqa: E402
from chipbench.trace import union_length  # noqa: E402

PROGRAM = "repro."
DISPATCH = "PjitFunction("
# the counters each ``scheduler.step`` span carries
STEP_COUNTERS = ("prompt_tokens", "catch_up_tokens", "decode_slots",
                 "readbacks")


class _Intervals:
    """Events of one kind, for the innermost one covering most of a gap."""

    def __init__(self, events):
        self.events = sorted(events)            # (start, end, label)
        self.starts = [e[0] for e in self.events]
        self.longest = max((b - a for a, b, _ in self.events), default=0)

    def covering_most(self, a: float, b: float):
        """Label of the shortest event that covers over half of [a, b]."""
        best = None
        i = bisect.bisect_left(self.starts, b)
        while i > 0:
            i -= 1
            s, e, label = self.events[i]
            if s < a - self.longest:
                break
            if 2 * (min(b, e) - max(a, s)) > b - a and (
                    best is None or e - s < best[1] - best[0]):
                best = (s, e, label)
        return best[2] if best else None

    def at(self, t: float):
        """Label of the shortest event that holds ``t``."""
        best = None
        i = bisect.bisect_right(self.starts, t)
        while i > 0:
            i -= 1
            s, e, label = self.events[i]
            if s < t - self.longest:
                break
            if e >= t and (best is None or e - s < best[1] - best[0]):
                best = (s, e, label)
        return best[2] if best else None


class ProgramTrace:
    """The program's spans and the labelled idle gaps of one trace (ns)."""

    def __init__(self, *, window, spans, gaps):
        self.window_ns = window
        self.spans = spans        # name -> [(start, end, stats)]
        self.gaps = gaps          # [(seconds, label)] longest first

    def in_window(self, name: str) -> list:
        """The spans ``name`` begun inside the window."""
        w0, w1 = self.window_ns
        return [s for s in self.spans.get(name, []) if w0 <= s[0] <= w1]

    def idle_by_label(self) -> dict[str, float]:
        """Idle seconds in the window by the label of their gaps."""
        out: Counter = Counter()
        for seconds, label in self.gaps:
            out[label] += seconds
        return dict(out.most_common())

    def step_stats(self) -> dict[str, float] | None:
        """Over the ``scheduler.step`` spans begun in the window: host ms
        per step (its length less the ``model_step.readback`` time inside
        it), readbacks per step, and the catch-up share (%) of the tokens
        through prefill, from the steps' counters."""
        steps = self.in_window("scheduler.step")
        if not steps:
            return None
        reads = sorted(self.spans.get("model_step.readback", []))
        starts = [r[0] for r in reads]
        host = 0.0
        for a, b, _ in steps:
            i = bisect.bisect_left(starts, a)
            waited = 0.0
            while i < len(reads) and reads[i][0] < b:
                waited += min(b, reads[i][1]) - reads[i][0]
                i += 1
            host += b - a - waited
        tot = Counter()
        for _, _, stats in steps:
            tot.update({k: int(stats[k]) for k in STEP_COUNTERS
                        if k in stats})
        through = tot["prompt_tokens"] + tot["catch_up_tokens"]
        return {
            "host_step_ms": 1e-6 * host / len(steps),
            "readbacks_per_step": tot["readbacks"] / len(steps),
            "catchup_share": (100.0 * tot["catch_up_tokens"] / through
                              if through else None),
        }


def read(path) -> ProgramTrace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    spans: dict[str, list] = defaultdict(list)
    dispatch, harness, busy = [], [], []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    busy.extend((e.start_ns, e.start_ns + e.duration_ns)
                                for e in line.events)
            continue
        for line in plane.lines:
            for e in line.events:
                a, b = e.start_ns, e.start_ns + e.duration_ns
                if e.name.startswith(PROGRAM):
                    spans[e.name[len(PROGRAM):]].append(
                        (a, b, dict(e.stats)))
                elif e.name.startswith(DISPATCH):
                    dispatch.append((a, b, e.name))
                elif e.name.startswith(HARNESS):
                    harness.append((a, b, e.name[len(HARNESS):]))
    windows = [(a, b) for a, b, n in harness if n == "window"]
    if windows:
        window = windows[0]
    elif busy:
        window = (min(a for a, _ in busy), max(b for _, b in busy))
    else:
        raise ValueError("the trace holds no window and no device operation")
    w0, w1 = window
    _, merged = union_length([(max(a, w0), min(b, w1)) for a, b in busy
                              if b > w0 and a < w1])
    tiers = [_Intervals((a, b, PROGRAM + n) for n, ivs in spans.items()
                        for a, b, _ in ivs),
             _Intervals(dispatch)]
    outer = _Intervals(e for e in harness if e[2] != "window")
    edges = [w0] + [x for iv in merged for x in iv] + [w1]
    gaps = []
    for a, b in zip(edges[::2], edges[1::2]):
        if b <= a:
            continue
        label = next((lab for lab in (t.covering_most(a, b) for t in tiers)
                      if lab is not None), None)
        gaps.append(((b - a) * 1e-9,
                     label or outer.at((a + b) / 2) or "no span"))
    gaps.sort(key=lambda g: -g[0])
    return ProgramTrace(window=window, spans=dict(spans), gaps=gaps)


def main(argv=None) -> int:
    import json
    path, = argv if argv is not None else sys.argv[1:]
    pt = read(path)
    idle = pt.idle_by_label()
    total = sum(idle.values())
    print(json.dumps({
        "window_s": (pt.window_ns[1] - pt.window_ns[0]) * 1e-9,
        "idle_s": total,
        "idle_by_label": {k: [v, 100.0 * v / total] for k, v in idle.items()},
        "longest_gaps": pt.gaps[:10],
        "spans": {k: len(pt.in_window(k)) for k in sorted(pt.spans)},
        "steps": pt.step_stats(),
    }, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
