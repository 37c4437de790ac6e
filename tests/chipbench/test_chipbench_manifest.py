"""BENCHMARK.json against the benchmark's rules, and the harness finding a
new driver, configuration, traffic mix and metric from new files alone:
the cell's run, its tiny counterpart, its control and its scoped program."""

from __future__ import annotations

import io
import json
import re
import shutil
from contextlib import redirect_stdout
from pathlib import Path

import pytest
from conftest import make_tiny_root, tiny_mismatches

ROOT = Path(__file__).resolve().parents[2]
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
METRIC_KEYS = {"name", "unit", "better", "source"}
CELLS = {w["name"]: w for w in MANIFEST["workloads"]}
CONFIGS = {c["name"]: c for c in MANIFEST["configs"]}
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]


def _cells_of(metric: dict) -> list[str]:
    return metric.get("workloads", list(CELLS))


def _line(text: str, limit: int = 200) -> bool:
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_top_level_keys_and_paths():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(MANIFEST["paths"]) <= 16
    for p in MANIFEST["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (ROOT / p).is_dir()
    cmd = MANIFEST["command"]
    assert 1 <= len(cmd) <= 32 and all(_line(w) for w in cmd)
    for word in cmd[1:]:
        assert not word.startswith("/") and ".." not in word
        assert any(word.startswith(p + "/") for p in MANIFEST["paths"])
    assert isinstance(MANIFEST["run_seconds"], int)
    assert 1 <= MANIFEST["run_seconds"] <= 51
    # a full check of 24 cells must fit its time: 2 + 14 x cells runs of
    # run_seconds + 60, 2 x 90 s of compiling per cell, 1200 s spare
    runs = 2 + 14 * 24
    assert (runs * (MANIFEST["run_seconds"] + 60) + 24 * 180 + 1200
            <= 43200)
    assert len(json.dumps(MANIFEST)) <= 64 * 1024


def test_names_units_and_lines():
    names = [c["name"] for c in MANIFEST["configs"]]
    names += [w["name"] for w in MANIFEST["workloads"]]
    names += [m["name"] for m in METRICS]
    assert all(NAME.match(n) for n in names)
    for group in (MANIFEST["configs"], MANIFEST["workloads"], METRICS):
        assert len({x["name"] for x in group}) == len(group)
    for m in METRICS:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for w in MANIFEST["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert _line(w["why"]) and w["chips"] in (1, 4)
    for c in MANIFEST["configs"]:
        assert _line(c["why"]) and _line(c["source"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
    for m in MANIFEST["per_layer"]:
        assert _line(m["layer"])


def test_entries_have_exactly_their_keys():
    for c in MANIFEST["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in MANIFEST["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in MANIFEST["end_to_end"]:
        assert set(m) - {"workloads"} == METRIC_KEYS | {"bound"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.25
    for m in MANIFEST["per_layer"]:
        assert set(m) - {"workloads"} == METRIC_KEYS | {"layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


def test_configurations_and_cells():
    files = [c["file"] for c in MANIFEST["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in MANIFEST["workloads"]}
    assert used == set(CONFIGS)
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(w["chips"] == 4 for w in MANIFEST["workloads"])
    assert four <= max(1, len(CELLS) // 2)
    for c in MANIFEST["configs"]:
        assert any(c["file"].startswith(p + "/") for p in MANIFEST["paths"])
        assert c["file"] == f"chipbench/configs/{c['name']}.json"
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert (ROOT / "chipbench" / "drivers" / f"{cfg['kind']}.py"
                ).is_file()
        assert cfg["limits"] and cfg["reduced"] == c["reduced"]
    for w in MANIFEST["workloads"]:
        assert (ROOT / "chipbench" / "traffic" / f"{w['traffic']}.json"
                ).is_file()


def test_every_metric_has_a_reader_and_its_cells_exist():
    for m in METRICS:
        assert set(_cells_of(m)) <= set(CELLS), m["name"]
        if m["name"] != "setup_s":
            assert (ROOT / "chipbench" / "metrics" / f"{m['name']}.py"
                    ).is_file(), m["name"]


def test_each_cell_reports_enough_and_moves_are_reported():
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for cell in CELLS:
        mine = [n for n, m in e2e.items() if cell in _cells_of(m)]
        assert "setup_s" in mine and len(mine) >= 2, cell
        assert any(cell in _cells_of(m) for m in MANIFEST["per_layer"]), cell
    layers: dict[str, set] = {}
    for m in MANIFEST["per_layer"]:
        assert m["moves"] in e2e
        for cell in _cells_of(m):
            assert cell in _cells_of(e2e[m["moves"]]), (m["name"], cell)
        layers.setdefault(m["layer"].split()[-1], set()).add(m["layer"])
    # one layer (named by its module), one spelling
    assert all(len(v) == 1 for v in layers.values()), layers


def test_every_cell_has_one_tiny_counterpart():
    """Each cell runs on the CPU through its tiny counterpart
    ``tests/chipbench/tiny/<cell>.json``, and each such file stands for a
    cell."""
    problems = tiny_mismatches()
    assert not problems, "; ".join(problems)


def test_shares_of_a_peak_are_named_for_it():
    for m in MANIFEST["per_layer"]:
        if m["name"].split(".")[0].endswith("_roofline") or \
                "mfu" in m["name"]:
            assert m["unit"] == "%" and m["better"] == "higher"


DUMMY_DRIVER = '''
class Driver:
    def __init__(self, *, config, traffic, seed, spans, trace):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.spans = spans
    def setup(self):
        pass
    def window(self, seconds):
        with self.spans("window"):
            self.done = self.traffic["calls"] * self.config["size"]
    attempted, failed = 3, 0
    def release(self):
        pass
    def verify(self):
        return [("exact", 0.0, self.config["limits"]["exact"])]
'''
DUMMY_E2E = "def read(run):\n    return run.driver.done + 0.5\n"
DUMMY_LAYER = "def read(run):\n    return run.driver.seed / 2\n"
DUMMY_SILENT = "def read(run):\n    return None\n"


class _Summary:
    busy_s, window_s = 0.5, 1.0

    def breakdown(self):
        return {"device_ops": [["op", 0.5]], "idle_gaps": [["span", 0.5]]}


@pytest.mark.parametrize("trace", [0, 1])
def test_new_files_alone_are_found_by_name(tmp_path, monkeypatch, trace):
    """A driver, configuration, traffic mix and two metrics that exist only
    as new files in a checkout run without an edit to any harness file."""
    root = tmp_path
    shutil.copytree(ROOT / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = root / "chipbench"
    (bench / "drivers" / "dummy.py").write_text(DUMMY_DRIVER)
    (bench / "configs" / "dummy-cfg.json").write_text(json.dumps(
        {"kind": "dummy", "size": 7, "limits": {"exact": 0.0}}))
    (bench / "traffic" / "dummy-mix.json").write_text(json.dumps(
        {"kind": "closed_loop", "calls": 6}))
    (bench / "metrics" / "dummy_rate.py").write_text(DUMMY_E2E)
    (bench / "metrics" / "dummy_layer.x.py").write_text(DUMMY_LAYER)
    (bench / "metrics" / "dummy_silent.x.py").write_text(DUMMY_SILENT)
    manifest = json.loads(json.dumps(MANIFEST))
    manifest["configs"].append({"name": "dummy-cfg", "source": "none",
                                "file": "chipbench/configs/dummy-cfg.json",
                                "reduced": [], "why": "test"})
    manifest["workloads"].append({"name": "dummy.cell", "config": "dummy-cfg",
                                  "traffic": "dummy-mix", "chips": 1,
                                  "why": "test"})
    manifest["end_to_end"].append({"name": "dummy_rate", "unit": "1/s",
                                   "better": "higher", "bound": 0.05,
                                   "source": "host_clock",
                                   "workloads": ["dummy.cell"]})
    for name in ("dummy_layer.x", "dummy_silent.x"):
        manifest["per_layer"].append({
            "name": name, "unit": "ms", "better": "lower",
            "source": "program_counter", "layer": "dummy", "moves":
            "dummy_rate", "workloads": ["dummy.cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))

    from chipbench import run
    from chipbench import trace as trace_mod
    monkeypatch.setattr(trace_mod, "reduce", lambda path: _Summary())
    out = io.StringIO()
    with redirect_stdout(out):
        rc = run.main(["--workload", "dummy.cell", "--seed", "12",
                       "--seconds", "1", "--trace", str(trace)],
                      root=root, require_chip=False)
    assert rc == 0
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    assert result["correct"] is True
    assert list(result)[-1] == "checks"
    assert result["checks"] == {"exact": {"value": 0.0, "limit": 0.0}}
    if trace:
        assert result["metrics"] == {"dummy_layer.x": {"value": 6.0,
                                                       "unit": "ms"}}
        assert result["device"]["busy_s"] == 0.5
        assert result["breakdown"]["device_ops"] == [["op", 0.5]]
    else:
        assert set(result["metrics"]) == {"setup_s", "dummy_rate"}
        assert result["metrics"]["dummy_rate"]["value"] == 42.5


SCOPED_DRIVER = '''
import time

import jax
import jax.numpy as jnp


@jax.jit
def dummy_square(x):
    with jax.named_scope("dummy.square"):
        return x * x


class Driver:
    control = "halved"

    def __init__(self, *, config, traffic, seed, spans, trace):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.spans = spans

    def _x(self, i):
        return jnp.full((self.config["size"],), float(i % 7), jnp.float32)

    def scoped_call(self, i):
        return dummy_square, (self._x(i),), {}

    def setup(self):
        jax.block_until_ready(dummy_square(self._x(0)))

    def window(self, seconds):
        self.calls = self.traffic["calls"]
        with self.spans("window"):
            t0 = time.perf_counter()
            self.out = [dummy_square(self._x(i)) for i in range(self.calls)]
            jax.block_until_ready(self.out)
            self.window_s = time.perf_counter() - t0

    @property
    def attempted(self):
        return self.calls

    failed = 0

    def release(self):
        pass

    def readings(self, control=None):
        scale = 0.5 if control == "halved" else 1.0
        gaps = [jnp.max(jnp.abs(scale * y - self._x(i) ** 2))
                for i, y in enumerate(self.out)]
        return {"gap": max(float(g) for g in gaps)}

    def verify(self):
        got = self.readings()
        return [(k, got[k], float(v))
                for k, v in self.config["limits"].items()]
'''
SCOPED_READER = ("from chipbench import scopes\n\n\ndef read(run):\n"
                 "    return scopes.read_scope(run, 'dummy.square')\n")
TINY_DUMMY = {"name": "tiny.dummy", "control": "halved",
              "config_name": "tiny-dummy",
              "config": {"kind": "dummy_scoped", "size": 8,
                         "limits": {"gap": 0.0}},
              "traffic_name": "tiny-dummy-mix",
              "traffic": {"kind": "closed_loop", "calls": 5}}


def _new_kind_source(src: Path) -> Path:
    """A copy of the repo's benchmark files with a new kind added as new
    files and entries appended to BENCHMARK.json: a driver with a control
    and a scoped program, a configuration, a traffic mix, a cell appended
    to ``factor_ms``'s cells, a per-layer metric and the tiny counterpart."""
    shutil.copytree(ROOT / "chipbench", src / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(ROOT / "tests" / "chipbench" / "tiny",
                    src / "tests" / "chipbench" / "tiny")
    bench = src / "chipbench"
    (bench / "drivers" / "dummy_scoped.py").write_text(SCOPED_DRIVER)
    (bench / "configs" / "dummy-cfg.json").write_text(json.dumps(
        {"kind": "dummy_scoped", "size": 4096, "limits": {"gap": 0.0}}))
    (bench / "traffic" / "dummy-mix.json").write_text(json.dumps(
        {"kind": "closed_loop", "calls": 50}))
    (bench / "metrics" / "dummy_ms.scoped.py").write_text(SCOPED_READER)
    (src / "tests" / "chipbench" / "tiny" / "dummy.cell.json").write_text(
        json.dumps(TINY_DUMMY))
    manifest = json.loads(json.dumps(MANIFEST))
    manifest["configs"].append({"name": "dummy-cfg", "source": "none",
                                "file": "chipbench/configs/dummy-cfg.json",
                                "reduced": [], "why": "test"})
    manifest["workloads"].append({"name": "dummy.cell", "config": "dummy-cfg",
                                  "traffic": "dummy-mix", "chips": 1,
                                  "why": "test"})
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    e2e["factor_ms"]["workloads"].append("dummy.cell")
    manifest["per_layer"].append({
        "name": "dummy_ms.scoped", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "dummy", "moves": "factor_ms",
        "workloads": ["dummy.cell"]})
    (src / "BENCHMARK.json").write_text(json.dumps(manifest))
    return src


def test_new_kind_reaches_its_tiny_cell_control_and_scopes(
        tmp_path, monkeypatch):
    """A new kind that exists only as new files and appended entries builds
    a tiny checkout, runs, gives its control's readings and has its
    program's instructions mapped to its scope, with no edit to a file of
    the harness or of its tests."""
    from chipbench import control, run, scopes
    from chipbench import trace as trace_mod
    from repro import tracing
    src = _new_kind_source(tmp_path / "src")
    before = tiny_mismatches()            # the repo's own, if it has any
    assert tiny_mismatches(src) == before
    root = make_tiny_root(tmp_path / "checkout", src)
    tiny = json.loads((root / "BENCHMARK.json").read_text())
    names = [w["name"] for w in tiny["workloads"]]
    assert "tiny.dummy" in names
    e2e = {m["name"]: m for m in tiny["end_to_end"]}
    assert e2e["factor_ms"]["workloads"][-1] == "tiny.dummy"
    assert tiny["per_layer"][-1]["workloads"] == ["tiny.dummy"]

    monkeypatch.setitem(tracing.SCOPES, "dummy_square", ("dummy.square",))
    monkeypatch.setattr(trace_mod, "reduce", lambda path: _Summary())
    seen = {}
    for trace in (0, 1):
        out = io.StringIO()
        with redirect_stdout(out):
            rc = run.main(["--workload", "tiny.dummy", "--seed",
                           str(2 ** 40 + 5), "--seconds", "1", "--trace",
                           str(trace)], root=root, require_chip=False,
                          driver_hook=lambda d: seen.update(driver=d))
        assert rc == 0
        result = json.loads(out.getvalue().strip().splitlines()[-1])
        assert result["correct"] is True and result["attempted"] == 5
        assert result["checks"] == {"gap": {"value": 0.0, "limit": 0.0}}
        # on the CPU the scopes' own trace has no device plane to read
        assert set(result["metrics"]) == (
            set() if trace else {"setup_s", "factor_ms"})

    got = control.readings(root, "tiny.dummy", 3, 1.0)
    assert got["control"]["kind"] == "halved"
    assert got["program"]["gap"] == 0.0 < got["control"]["gap"]

    module, compiled, scope_of, calls = scopes.scoped_program(seen["driver"])
    assert module == "jit_dummy_square" and len(calls) == scopes.REPS
    assert scope_of == scopes.instruction_scopes(compiled.as_text(),
                                                 ("dummy.square",))
    assert set(scope_of.values()) == {"dummy.square"}

    # without its tiny file the cell is named by the one manifest check,
    # and the tiny checkout of the other cells still builds
    (src / "tests" / "chipbench" / "tiny" / "dummy.cell.json").unlink()
    assert set(tiny_mismatches(src)) - set(before) == {
        "cell dummy.cell has no tiny counterpart "
        "tests/chipbench/tiny/dummy.cell.json"}
    rest = json.loads((make_tiny_root(tmp_path / "rest", src)
                       / "BENCHMARK.json").read_text())
    assert [w["name"] for w in rest["workloads"]] == [
        n for n in names if n != "tiny.dummy"]
