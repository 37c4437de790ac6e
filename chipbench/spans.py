"""Host spans that the benchmark puts around its calls into each layer.

A span is a name and two readings of the host clock.  In a traced run each
span is also a ``jax.profiler.TraceAnnotation`` named ``chipbench.<name>``,
so the trace reduction can line device time up with what the host was
doing.  A traced run records the last seconds of the window only
(``trace_tail``): a whole window's trace takes minutes to write and read.
"""

from __future__ import annotations

import contextlib
import time
from collections import defaultdict

PREFIX = "chipbench."


class Spans:
    def __init__(self):
        self.annotate = False
        self.intervals: dict[str, list[tuple[float, float]]] = \
            defaultdict(list)
        self._open: dict[str, tuple] = {}
        self._tail = None
        self.trace_t0 = None       # host clock when the trace began

    def trace_tail(self, window_s: float, tail_s: float, start) -> None:
        """Call ``start`` (which starts the profiler) at the first span
        begun in the last ``tail_s`` of a ``window_s`` window; from then on
        spans are annotated, and the traced part of the window is the
        annotated span ``window``."""
        self._tail = (max(0.0, window_s - tail_s), start)

    def _annotation(self, name: str):
        import jax
        ctx = jax.profiler.TraceAnnotation(PREFIX + name)
        ctx.__enter__()
        return ctx

    def begin(self, name: str) -> None:
        """Open a span that another call closes (``end``)."""
        now = time.perf_counter()
        if name == "window":
            self._window_t0 = now
        if (self._tail is not None and not self.annotate
                and now >= self._window_t0 + self._tail[0]):
            self._tail[1]()
            self.annotate, self.trace_t0 = True, time.perf_counter()
            if name != "window":       # annotate the window's traced part
                t0, _ = self._open["window"]
                self._open["window"] = (t0, self._annotation("window"))
            now = time.perf_counter()
        ctx = self._annotation(name) if self.annotate else None
        self._open[name] = (now, ctx)

    def end(self, name: str) -> None:
        """Close the span ``name``; a span never opened is ignored."""
        if name not in self._open:
            return
        t0, ctx = self._open.pop(name)
        self.intervals[name].append((t0, time.perf_counter()))
        if ctx is not None:
            ctx.__exit__(None, None, None)

    @contextlib.contextmanager
    def __call__(self, name: str):
        self.begin(name)
        try:
            yield
        finally:
            self.end(name)


class CompileCounter:
    """Counts the programs JAX lowers in this process (a compile, or a
    load from the persistent cache) by its monitoring events."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax
        self.names: list[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    @property
    def count(self) -> int:
        return len(self.names)

    def _on(self, event: str, duration: float, *, fun_name: str = "?",
            **_) -> None:
        if event == self.EVENT:
            self.names.append(fun_name)
