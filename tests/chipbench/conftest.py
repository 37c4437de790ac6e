"""Fixtures for the chip benchmark's tests: a checkout holding the real
harness with tiny configurations, so that a whole run fits the CPU.

Each cell of ``BENCHMARK.json`` has a tiny counterpart in
``tests/chipbench/tiny/<cell>.json``: the tiny cell's ``name``, its
configuration and traffic mix inline (``config``, ``traffic``) under the
names they take in the tiny checkout (``config_name``, ``traffic_name``),
and the ``control`` that its CPU test runs.  The tiny checkout and the
tests that take ``tiny_cell`` are built from those files alone.
"""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
TINY = Path("tests") / "chipbench" / "tiny"


def tiny_files(src: Path = ROOT) -> dict[str, dict]:
    """Cell name -> its tiny counterpart, for every file under ``src``'s
    ``tests/chipbench/tiny/``, whether or not the cell exists."""
    return {p.name[:-len(".json")]: json.loads(p.read_text())
            for p in sorted((src / TINY).glob("*.json"))}


def tiny_mismatches(src: Path = ROOT) -> list[str]:
    """What keeps ``src``'s cells and tiny counterparts from matching one to
    one: cells with no tiny file, tiny files of no cell, and tiny names,
    configurations or traffic mixes given twice."""
    cells = [w["name"] for w in
             json.loads((src / "BENCHMARK.json").read_text())["workloads"]]
    tiny = tiny_files(src)
    out = [f"cell {c} has no tiny counterpart {TINY / (c + '.json')}"
           for c in cells if c not in tiny]
    out += [f"{TINY / (c + '.json')} is the counterpart of no cell of "
            f"BENCHMARK.json" for c in tiny if c not in cells]
    for key in ("name", "config_name", "traffic_name"):
        names = [t[key] for t in tiny.values()]
        out += [f"tiny {key} {n} is given twice" for n in sorted(set(names))
                if names.count(n) > 1]
    return out


def tiny_counterparts(src: Path = ROOT) -> list[dict]:
    """The tiny counterparts of ``src``'s cells, each with the cell it
    stands for under ``cell``, in the order of the file names; a cell with
    no tiny file has none here (``tiny_mismatches`` names it)."""
    cells = {w["name"] for w in
             json.loads((src / "BENCHMARK.json").read_text())["workloads"]}
    return [dict(t, cell=c) for c, t in tiny_files(src).items() if c in cells]


def make_tiny_root(dest: Path, src: Path = ROOT) -> Path:
    """A checkout with ``src``'s harness and BENCHMARK.json's metrics, whose
    cells are the tiny counterparts of ``src``'s cells."""
    shutil.copytree(src / "chipbench", dest / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    tiny = tiny_counterparts(src)
    for t in tiny:
        (dest / "chipbench" / "configs" / f"{t['config_name']}.json"
         ).write_text(json.dumps(t["config"]))
        (dest / "chipbench" / "traffic" / f"{t['traffic_name']}.json"
         ).write_text(json.dumps(t["traffic"]))
    manifest = json.loads((src / "BENCHMARK.json").read_text())
    real = {t["cell"]: t["name"] for t in tiny}
    manifest["workloads"] = [
        {"name": t["name"], "config": t["config_name"],
         "traffic": t["traffic_name"], "chips": 1, "why": "tiny"}
        for t in tiny]
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [real[w] for w in m["workloads"] if w in real]
    (dest / "BENCHMARK.json").write_text(json.dumps(manifest))
    return dest


def pytest_generate_tests(metafunc):
    """A test that takes ``tiny_cell`` runs once for each tiny counterpart,
    under the tiny cell's name."""
    if "tiny_cell" in metafunc.fixturenames:
        tiny = tiny_counterparts()
        metafunc.parametrize("tiny_cell", tiny, ids=[t["name"] for t in tiny])


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory) -> Path:
    return make_tiny_root(tmp_path_factory.mktemp("checkout"))
