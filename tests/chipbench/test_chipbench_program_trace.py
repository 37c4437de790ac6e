"""The readers of the program's spans and scopes, on synthetic traces.

``program_trace.read`` collects the ``repro.*`` host spans with their
metadata and labels each idle gap of the device by the innermost event
covering most of it: a program span, else a dispatch event, else the
benchmark's span at its middle.  ``trace.reduce`` reads the same trace as
before.  ``scopes`` maps HLO instructions to named scopes and sums the
self time of each scope's operations per program execution; its three
readers return nothing where nothing was measured.
"""

from __future__ import annotations

from types import SimpleNamespace

import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from chipbench import program_trace, scopes, trace
from chipbench.run import ROOT, load_module
from chipbench.spans import Spans
from repro.tracing import SCOPES

RSVD_SCOPES = SCOPES["rsvd"]

US = 1000          # ns


def _event(meta: dict, name: str, start_ns: int, end_ns: int,
           stats: dict | None = None) -> str:
    mid = meta.setdefault(name, len(meta) + 1)
    body = "".join(
        f' stats {{ metadata_id: {_STATS[k]} int64_value: {v} }}'
        for k, v in (stats or {}).items())
    return (f"events {{ metadata_id: {mid} offset_ps: {start_ns * 1000} "
            f"duration_ps: {(end_ns - start_ns) * 1000}{body} }}")


_STATS = {k: i + 1 for i, k in enumerate(
    ["prompt_tokens", "catch_up_tokens", "decode_slots", "readbacks",
     "run_id"])}


def _plane(pid: int, name: str, lines: dict[str, list]) -> str:
    meta: dict = {}
    body = []
    for i, (line, events) in enumerate(lines.items()):
        evs = " ".join(_event(meta, *e) for e in events)
        body.append(f'lines {{ id: {i + 1} name: "{line}" timestamp_ns: 0 '
                    f'{evs} }}')
    body += [f'event_metadata {{ key: {v} value {{ id: {v} name: "{k}" }} }}'
             for k, v in meta.items()]
    body += [f'stat_metadata {{ key: {v} value {{ id: {v} name: "{k}" }} }}'
             for k, v in _STATS.items()]
    return f'planes {{ id: {pid} name: "{name}" {" ".join(body)} }}'


STEP1 = {"prompt_tokens": 6, "catch_up_tokens": 2, "decode_slots": 1,
         "readbacks": 2}
STEP2 = {"prompt_tokens": 0, "catch_up_tokens": 0, "decode_slots": 2,
         "readbacks": 1}
HOST = {"python": [
    ("chipbench.window", 0, 200 * US),
    ("chipbench.step", 10 * US, 60 * US),
    ("chipbench.decode_step", 115 * US, 170 * US),
    ("repro.scheduler.step", 10 * US, 90 * US, STEP1),
    ("repro.model_step.readback", 30 * US, 35 * US),
    ("repro.model_step.readback", 50 * US, 78 * US),
    ("PjitFunction(argmax)", 98 * US, 109 * US),
    ("repro.scheduler.step", 170 * US, 195 * US, STEP2),
    ("repro.model_step.readback", 180 * US, 185 * US),
    ("DoEnqueueProgram", 12 * US, 13 * US, {"run_id": 7}),
]}
DEVICE = {
    "XLA Modules": [("jit_rsvd(11)", 0, 20 * US, {"run_id": 7}),
                    ("jit_other(12)", 45 * US, 55 * US)],
    "XLA Ops": [("%fusion.1 = f32[4] fusion()", 0, 20 * US),
                ("%fusion.1 = f32[4] fusion()", 45 * US, 55 * US),
                ("%copy.2 = f32[4] copy()", 80 * US, 100 * US),
                ("%copy.2 = f32[4] copy()", 110 * US, 120 * US),
                ("%fusion.3 = f32[4] fusion()", 160 * US, 200 * US)],
}
# idle: [20, 45] in a scheduler step, [55, 80] mostly in a readback,
# [100, 110] mostly in a dispatch, [120, 160] in no program span
GAPS = {"repro.scheduler.step": 25e-6, "repro.model_step.readback": 25e-6,
        "PjitFunction(argmax)": 10e-6, "decode_step": 40e-6}


def _write(tmp_path, planes: list[str]):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(
        " ".join(planes)))
    return path


@pytest.fixture()
def synthetic(tmp_path):
    return _write(tmp_path, [_plane(1, "/host:CPU", HOST),
                             _plane(2, "/device:TPU:0", DEVICE)])


def test_program_spans_and_their_metadata(synthetic):
    pt = program_trace.read(synthetic)
    assert pt.window_ns == (0, 200 * US)
    steps = pt.spans["scheduler.step"]
    assert [s[2] for s in steps] == [STEP1, STEP2]
    assert len(pt.spans["model_step.readback"]) == 3
    assert set(pt.spans) == {"scheduler.step", "model_step.readback"}


def test_gaps_take_the_innermost_event_covering_most(synthetic):
    pt = program_trace.read(synthetic)
    assert pt.idle_by_label() == pytest.approx(GAPS)
    assert pt.gaps[0] == pytest.approx((40e-6, "decode_step"))


def test_step_stats_from_the_step_spans(synthetic):
    st = program_trace.read(synthetic).step_stats()
    # step 1: 80 us less 5 + 28 us of readback; step 2: 25 less 5
    assert st["host_step_ms"] == pytest.approx(1e-3 * (47 + 20) / 2)
    assert st["readbacks_per_step"] == pytest.approx(1.5)
    assert st["catchup_share"] == pytest.approx(25.0)


def test_trace_reduce_reads_the_same_trace_as_before(synthetic):
    s = trace.reduce(synthetic)
    assert s.window_ns == (0, 200 * US)
    assert sum(g for g, _ in s.gaps) == pytest.approx(sum(GAPS.values()))
    # the benchmark's spans label the gaps at their middle, as they did
    assert sorted(label for _, label in s.gaps) == sorted(
        ["step", "no span", "no span", "decode_step"])
    assert s.modules["jit_rsvd(11)"][2] == "step"
    assert set(s.spans) == {"step", "decode_step"}


def test_no_window_span_takes_the_device_extent(tmp_path):
    host = {"python": [e for e in HOST["python"]
                       if e[0] != "chipbench.window"]}
    pt = program_trace.read(_write(tmp_path, [
        _plane(1, "/host:CPU", host), _plane(2, "/device:TPU:0", DEVICE)]))
    assert pt.window_ns == (0, 200 * US)
    with pytest.raises(ValueError, match="no window"):
        program_trace.read(_write(tmp_path, [_plane(1, "/host:CPU", host)]))


def test_the_command_prints_the_split(synthetic, capsys):
    import json
    assert program_trace.main([str(synthetic)]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["idle_by_label"]["decode_step"][0] == pytest.approx(40e-6)
    assert out["spans"]["scheduler.step"] == 2


# -- scopes -------------------------------------------------------------------

HLO = """\
HloModule jit_rsvd
%fused_computation (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %neg.9 = f32[4]{0} negate(f32[4]{0} %p), metadata={op_name="jit(rsvd)/rsvd.sketch/neg"}
}
ENTRY %main (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %copy.5 = f32[4]{0} copy(f32[4]{0} %a)
  %fusion.1 = f32[4]{0} fusion(f32[4]{0} %copy.5), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(rsvd)/rsvd.sketch/jit(sketch)/dot_general" source_file="p.py" source_line=3}
  %custom-call.2 = f32[4]{0} custom-call(f32[4]{0} %fusion.1), custom_call_target="Qr", metadata={op_name="jit(rsvd)/rsvd.qr/jit(qr)/geqrf"}
  %copy.3 = f32[4]{0} copy(f32[4]{0} %custom-call.2)
  ROOT %while.4 = f32[4]{0} while(f32[4]{0} %copy.3), condition=%c, body=%b, metadata={op_name="jit(rsvd)/while"}
}
"""


def test_instruction_scopes_from_hlo_text():
    # a copy with no metadata takes its consumer's scope; one that feeds
    # no scoped instruction (copy.3 -> while.4) has none
    assert scopes.instruction_scopes(HLO, RSVD_SCOPES) == {
        "p": "rsvd.sketch", "neg.9": "rsvd.sketch", "a": "rsvd.sketch",
        "copy.5": "rsvd.sketch", "fusion.1": "rsvd.sketch",
        "custom-call.2": "rsvd.qr"}
    assert scopes.instruction("%custom-call.2 = f32[4] custom-call()") == \
        "custom-call.2"
    assert scopes.instruction("fusion.1") == "fusion.1"


def test_self_time_per_instruction_inside_the_module(tmp_path):
    device = {
        "XLA Modules": [("jit_rsvd(1)", 0, 100 * US),
                        ("jit_rsvd(1)", 200 * US, 300 * US),
                        ("jit_sketch(2)", 400 * US, 500 * US)],
        "XLA Ops": [("%fusion.1 = f32[4]", 0, 30 * US),
                    ("%while.4 = f32[4] while()", 30 * US, 100 * US),
                    ("%custom-call.2 = f32[4]", 40 * US, 90 * US),
                    ("%fusion.1 = f32[4]", 200 * US, 240 * US),
                    ("%custom-call.2 = f32[4]", 240 * US, 300 * US),
                    ("%fusion.1 = f32[4]", 400 * US, 500 * US)],
    }
    path = _write(tmp_path, [_plane(1, "/device:TPU:0", device)])
    runs, seconds = scopes.self_time_by_instruction(path, "jit_rsvd")
    assert runs == 2
    assert seconds == pytest.approx(
        {"fusion.1": 70e-6, "while.4": 20e-6, "custom-call.2": 110e-6})
    ms = scopes.scope_ms(runs, seconds,
                         scopes.instruction_scopes(HLO, RSVD_SCOPES))
    assert ms == pytest.approx({"rsvd.sketch": 0.035, "rsvd.qr": 0.055,
                                "": 0.010})
    assert scopes.self_time_by_instruction(path, "jit_absent") == (0, {})


READERS = {"sketch_ms.rsvd": "rsvd.sketch", "qr_ms.rsvd": "rsvd.qr",
           "small_svd_ms.rsvd": "rsvd.small_svd"}


def _reader(name):
    return load_module(ROOT / "chipbench" / "metrics" / f"{name}.py",
                       f"chipbench_metric_{name}")


@pytest.mark.parametrize("name", sorted(READERS))
def test_scope_readers_on_a_fake_run(name, monkeypatch):
    run = SimpleNamespace(trace=object())
    by_scope = {"rsvd.sketch": 0.27, "rsvd.qr": 2.5, "rsvd.small_svd": 2.4,
                "": 0.01}
    monkeypatch.setitem(scopes._MEASURED, id(run), by_scope)
    assert _reader(name).read(run) == by_scope[READERS[name]]
    silent = SimpleNamespace(trace=object())      # a program with no scopes
    monkeypatch.setitem(scopes._MEASURED, id(silent), None)
    assert _reader(name).read(silent) is None
    assert _reader(name).read(SimpleNamespace(trace=None)) is None


def test_scope_measurement_without_a_device_reads_nothing():
    """On the CPU the profiler writes no device plane: the readers return
    nothing, raise nothing and leave no trace behind."""
    import jax
    config = {"rank": 8, "oversample": 4, "power_iters": 0,
              "method": "shgemm", "dist": "gaussian",
              "omega_dtype": "bfloat16"}
    mats = [jax.random.normal(jax.random.PRNGKey(i), (64, 64), jnp.float32)
            for i in range(2)]
    driver = load_module(ROOT / "chipbench" / "drivers" / "rsvd.py",
                         "chipbench_driver_rsvd").Driver(
        config=config, traffic={"kind": "closed_loop"}, seed=3,
        spans=Spans(), trace=False)
    driver.mats = mats
    run = SimpleNamespace(trace=object(), config=config, driver=driver)
    try:
        assert _reader("qr_ms.rsvd").read(run) is None
    finally:
        scopes._MEASURED.pop(id(run), None)
    assert not (ROOT / ".chipbench" / "scopes").exists()
