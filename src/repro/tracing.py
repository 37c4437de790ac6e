"""The program's spans and device scopes, on the profiler's clock.

A span is a ``jax.profiler.TraceAnnotation`` named ``repro.<name>``: a host
event in the profiler's trace, on the same clock as the device's events.
It is recorded only while a profiler session runs; otherwise entering and
leaving it costs well under a microsecond and records nothing.  There is no
switch and no buffer of the program's own: the trace is the sink.  Work that
exists only to fill a span's metadata is guarded by ``enabled()``.

A scope is a ``jax.named_scope`` inside a jitted program.  It changes the
``op_name`` metadata of the instructions traced under it and nothing else,
so a device trace's operations can be grouped by the line of the algorithm
they belong to (the compiled program's HLO text maps each instruction to
its scope).
"""

from __future__ import annotations

import jax

PREFIX = "repro."

# every host span the program emits (without the prefix)
SPANS = (
    "scheduler.step",          # one Scheduler.step; the step's counters
    "scheduler.admit",         # one admission: rid, slot, queue_wait_ms
    "scheduler.prefill",       # one prompt chunk: rid, slot, tokens
    "scheduler.catch_up",      # one catch-up token: rid, slot
    "scheduler.promote",       # moving ready slots into the decode set
    "scheduler.decode",        # the batched decode step and its emits: slots
    "model_step.prefill_rows",   # host preparation and enqueue
    "model_step.decode_logits",  # host preparation and enqueue
    "model_step.readback",     # every device-to-host read on the step path
    "model_step.begin_slot",
    "model_step.compress",     # swapping a slot's dense prefix for factors
)

# the counters each ``scheduler.step`` span carries as metadata, and
# ``ServeMetrics.sample`` receives every step
STEP_COUNTERS = ("prompt_tokens", "catch_up_tokens", "decode_slots",
                 "readbacks")

# every device scope, by the jitted program that carries it
SCOPES = {
    "rsvd": ("rsvd.sketch", "rsvd.power", "rsvd.qr", "rsvd.project_b",
             "rsvd.small_svd", "rsvd.lift_u"),
    "rp_hosvd": ("hosvd.project", "hosvd.factor", "hosvd.core"),
    "rp_sthosvd": ("hosvd.project", "hosvd.factor", "hosvd.core"),
}


def span(name: str, **attrs) -> jax.profiler.TraceAnnotation:
    """The host span ``repro.<name>`` with ``attrs`` as its metadata."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **attrs)


def enabled() -> bool:
    """Whether a profiler session is recording spans now."""
    return jax.profiler.TraceAnnotation.is_enabled()
