"""BENCHMARK.json against the benchmark's rules, and the harness finding a
new driver, configuration, traffic mix and metric from new files alone."""

from __future__ import annotations

import io
import json
import re
import shutil
from contextlib import redirect_stdout
from pathlib import Path

import pytest

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
