"""The trace reduction, on a trace recorded on a TPU v5e: three rSVD calls
at the paper's shape in ``chipbench.rsvd`` spans, then three sketches in
``chipbench.sketch`` spans, inside one ``chipbench.window`` span.  The
committed file keeps the device plane and the host plane without the
Python function events (``data/rsvd_window.xplane.pb.gz``)."""

from __future__ import annotations

import gzip
import shutil
from pathlib import Path

import pytest

from chipbench import trace

DATA = Path(__file__).parent / "data" / "rsvd_window.xplane.pb.gz"


@pytest.fixture(scope="module")
def summary(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    with gzip.open(DATA) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return trace.reduce(path)


def test_window_and_busy(summary):
    assert summary.chips == 1
    assert summary.window_s == pytest.approx(0.04882716)
    assert 0 < summary.busy_s < summary.window_s
    assert summary.idle_share == pytest.approx(
        1 - summary.busy_s / summary.window_s)


def test_module_times(summary):
    n, s = summary.module_time("jit_rsvd")
    assert n == 3 and 0.015 < s < 0.025
    n, s = summary.module_time("jit_sketch")
    assert n == 3 and 0 < s < 0.002
    assert summary.module_time("jit_absent") == (0, 0)
    assert summary.module_time("jit_rsvd", whole_trace=True)[0] == 3


def test_programs_belong_to_the_span_that_enqueued_them(summary):
    assert summary.span_device_time("rsvd")[0] >= 3
    assert summary.span_device_time("sketch")[0] >= 3
    owners = {trace.module_base(k): v[2] for k, v in summary.modules.items()}
    assert owners["jit_rsvd"] == "rsvd"
    assert owners["jit_sketch"] == "sketch"


def test_breakdown_shape(summary):
    b = summary.breakdown()
    assert set(b) == {"device_ops", "idle_gaps"}
    for key in b:
        assert 0 < len(b[key]) <= 10
        assert all(isinstance(n, str) and v > 0 for n, v in b[key])
    total_idle = sum(g for g, _ in summary.gaps)
    assert total_idle == pytest.approx(summary.window_s - summary.busy_s,
                                       rel=1e-6)


def test_union_and_self_times():
    total, merged = trace.union_length([(0, 2), (1, 3), (5, 6), (6, 7)])
    assert total == 5 and merged == [(0, 3), (5, 7)]
    # a loop (0-10) holding two ops: its self time excludes them
    st = trace.self_times([(0, 10, "while"), (1, 3, "fusion"),
                           (4, 8, "fusion"), (12, 13, "copy")])
    assert st == {"while": 4, "fusion": 6, "copy": 1}


def test_names():
    assert trace.op_name("%convolution_convert_fusion.2 = f32[16] fusion(")\
        == "convolution_convert_fusion"
    assert trace.op_name("%factored_decode_attention.5 = bf16[1] custom-call"
                         ) == "factored_decode_attention"
    assert trace.module_base("jit_run(8081028625803501406)") == "jit_run"


def test_trace_without_device_work_is_refused(tmp_path):
    import jax
    import jax.numpy as jnp
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("chipbench.window"):
            jnp.ones(4).block_until_ready()
    path = next(tmp_path.glob("**/*.xplane.pb"))
    with pytest.raises(ValueError, match="no operation ran on a device"):
        trace.reduce(path)
