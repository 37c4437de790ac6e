"""The program's spans, counters and device scopes (``repro.tracing``).

A ``Scheduler`` over the smoke qwen3 model, stepped under a CPU profiler
session, emits the ``repro.*`` spans with their metadata; its step
counters add up to what the scheduler itself did; with no session nothing
is recorded and ``ServeMetrics`` still counts.  ``rsvd``, ``rp_hosvd`` and
``rp_sthosvd`` carry their named scopes in the compiled program, and the
scopes change nothing of ``rsvd``'s optimized HLO but its metadata.
"""

import contextlib
import re
from collections import Counter
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import tracing
from repro.configs.base import smoke_config
from repro.core import hosvd, rsvd
from repro.models import registry as R
from repro.models import transformer as T
from repro.serve.metrics import ServeMetrics, format_slo_table
from repro.serve.model_step import ModelStep
from repro.serve.scheduler import Scheduler

jax.config.update("jax_platform_name", "cpu")


def _scheduler():
    cfg = smoke_config(R.get_arch("qwen3-0.6b"))
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    return Scheduler(ModelStep(cfg, params, slots=2, max_seq=48),
                     prefill_chunk=4)


def _serve(sch):
    """A staggered pair: the second request is admitted while the first
    decodes, so it prefills, then catches up with the decode clock."""
    sch.submit(0, [3, 1, 4], 14)
    for _ in range(6):
        sch.step()
    sch.submit(1, [5, 9, 2, 6, 5, 3], 6)
    while sch.queue or sch._live():
        sch.step()


def _program_events(directory: Path) -> dict[str, list]:
    """name -> [(start, end, stats)] of the ``repro.*`` events of the one
    trace written under ``directory``."""
    path, = directory.glob("**/*.xplane.pb")
    out: dict[str, list] = {}
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(tracing.PREFIX):
                    out.setdefault(e.name[len(tracing.PREFIX):], []).append(
                        (e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)))
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    sch = _scheduler()
    directory = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(directory)):
        _serve(sch)
    return sch, _program_events(directory)


def test_spans_are_named_in_spans(traced):
    _, events = traced
    assert set(events) <= set(tracing.SPANS)
    compress = {"model_step.compress"}       # no cell of this test compresses
    assert set(tracing.SPANS) - set(events) == compress


def test_step_counters_add_up_to_the_scheduler(traced):
    sch, events = traced
    steps = events["scheduler.step"]
    tot = Counter()
    for _, _, stats in steps:
        assert set(stats) == set(tracing.STEP_COUNTERS)
        tot.update(stats)
    done = sch.finished
    assert len(done) == 2 and not any(r.evicted for r in done)
    # every output token: the first from the prompt's last chunk, then
    # catch-up tokens, then one per slot of each batched decode step
    assert sum(len(r.out) for r in done) == (
        len(done) + tot["catch_up_tokens"] + tot["decode_slots"])
    assert tot["prompt_tokens"] == sum(len(r.prompt) for r in done)
    assert tot["catch_up_tokens"] > 0
    assert tot["readbacks"] == sch.model.readbacks == len(
        events["model_step.readback"])
    assert tot["catch_up_tokens"] == len(events["scheduler.catch_up"])
    assert tot["decode_slots"] == sum(
        s["slots"] for _, _, s in events["scheduler.decode"])
    assert tot["prompt_tokens"] == sum(
        s["tokens"] for _, _, s in events["scheduler.prefill"])
    m = sch.metrics
    assert (m.prompt_tokens, m.catch_up_tokens, m.decode_slots,
            m.readbacks) == (tot["prompt_tokens"], tot["catch_up_tokens"],
                             tot["decode_slots"], tot["readbacks"])


@pytest.mark.parametrize("inner", ["model_step.readback",
                                   "model_step.prefill_rows",
                                   "model_step.decode_logits",
                                   "scheduler.admit"])
def test_step_work_nests_inside_a_step(traced, inner):
    _, events = traced
    steps = sorted((a, b) for a, b, _ in events["scheduler.step"])
    for a, b, _ in events[inner]:
        assert any(s <= a and b <= e for s, e in steps), (inner, a)


def test_admissions_carry_request_slot_and_queue_wait(traced):
    _, events = traced
    admits = events["scheduler.admit"]
    assert sorted(s["rid"] for _, _, s in admits) == [0, 1]
    for _, _, s in admits:
        assert s["slot"] in (0, 1) and s["queue_wait_ms"] >= 0


def test_no_session_records_nothing_and_metrics_still_count(tmp_path):
    assert not tracing.enabled()
    sch = _scheduler()
    _serve(sch)
    with jax.profiler.trace(str(tmp_path)):
        jnp.ones(4).block_until_ready()
    assert _program_events(tmp_path) == {}
    s = sch.metrics.summary()
    m = sch.metrics
    assert m.readbacks == sch.model.readbacks > 0
    assert m.catch_up_tokens > 0 and m.prompt_tokens == 9
    assert s["catch_up_share"] == pytest.approx(
        m.catch_up_tokens / (m.catch_up_tokens + m.prompt_tokens))
    assert s["slots_per_decode_step"] == pytest.approx(
        m.decode_slots / m.decode_steps)
    assert 1 <= s["slots_per_decode_step"] <= 2
    assert s["readbacks_per_step"] == pytest.approx(
        m.readbacks / len(m.queue_depth_samples))


def test_summary_and_table_report_the_step_counters():
    m = ServeMetrics()
    m.sample(0, 2, prompt_tokens=6, catch_up_tokens=2, decode_slots=0,
             readbacks=3)
    m.sample(0, 2, decode_slots=2, readbacks=1)
    m.sample(1, 1)                 # a hook that passes no counters
    s = m.summary()
    assert s["catch_up_share"] == pytest.approx(0.25)
    assert s["slots_per_decode_step"] == pytest.approx(2.0)
    assert s["readbacks_per_step"] == pytest.approx(4 / 3)
    table = format_slo_table(s)
    for label in ("catch-up share of prefill", "slots per decode step",
                  "readbacks per step"):
        assert label in table
    assert ServeMetrics().summary()["catch_up_share"] == 0.0


def test_model_step_programs_have_names():
    cfg = smoke_config(R.get_arch("qwen3-0.6b"))
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    model = ModelStep(cfg, params, slots=2, max_seq=16)
    assert model._prefill_one.__name__ == "prefill_chunk"
    assert model._decode_masked.__name__ == "decode_masked"


# -- device scopes ---------------------------------------------------------

def _strip(hlo: str) -> str:
    """Optimized HLO text without metadata and without the stack-frame
    tables (source files, lines, functions)."""
    hlo = re.sub(r",?\s*metadata=\{[^}]*\}", "", hlo)
    return "\n".join(
        ln for ln in hlo.splitlines()
        if not re.match(r"(\d|FileNames|FunctionNames|FileLocations|"
                        r"StackFrames)", ln))


def _rsvd_hlo(power_iters=0):
    key = jax.random.PRNGKey(0)
    a = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    return rsvd.rsvd.lower(key, a, 16, oversample=10,
                           power_iters=power_iters).compile().as_text()


def _hosvd_hlo(fn):
    key = jax.random.PRNGKey(0)
    a = jax.ShapeDtypeStruct((16, 12, 8), jnp.float32)
    return fn.lower(key, a, (4, 3, 2)).compile().as_text()


def test_rsvd_carries_a_scope_per_line():
    text = _rsvd_hlo(power_iters=1)
    for scope in tracing.SCOPES["rsvd"]:
        assert f"/{scope}/" in text, scope


@pytest.mark.parametrize("name", ["rp_hosvd", "rp_sthosvd"])
def test_hosvd_carries_a_scope_per_mode_step(name):
    text = _hosvd_hlo(getattr(hosvd, name))
    for scope in tracing.SCOPES[name]:
        assert f"/{scope}/" in text, (name, scope)


@pytest.mark.parametrize("power_iters", [0, 1])
def test_rsvd_hlo_is_unchanged_but_for_metadata(monkeypatch, power_iters):
    scoped = _rsvd_hlo(power_iters)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    jax.clear_caches()
    try:
        plain = _rsvd_hlo(power_iters)
    finally:
        jax.clear_caches()
    assert "rsvd.qr" in scoped and "rsvd.qr" not in plain
    assert _strip(scoped) == _strip(plain)


def test_span_is_a_trace_annotation_that_records_nothing_off():
    with tracing.span("scheduler.step", readbacks=1) as sp:
        assert isinstance(sp, jax.profiler.TraceAnnotation)
        assert not sp.is_enabled()
    assert np.all([n.count(".") == 1 for n in tracing.SPANS])
