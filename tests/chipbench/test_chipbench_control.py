"""The control comes out not correct at a size a test can hold: the plain
reference one precision below the configuration's, put in the program's
place (``chipbench/control.py``), against the same limits that the
program's own readings pass, for every tiny cell with the control its
tiny file names.  rSVD: three bf16 passes written out (``bf16x3``; on the
CPU ``Precision.HIGH`` is float32, on the chip the control runs ``high``
itself); serving: fp8 weights."""

from __future__ import annotations

import json

from chipbench import control


def test_control_fails_where_the_program_passes(tiny_root, tiny_cell):
    cell, kind = tiny_cell["name"], tiny_cell["control"]
    manifest = json.loads((tiny_root / "BENCHMARK.json").read_text())
    config = {w["name"]: w for w in manifest["workloads"]}[cell]["config"]
    limits = json.loads((tiny_root / "chipbench" / "configs" /
                         f"{config}.json").read_text())["limits"]
    # a window long enough that, even on a loaded host, the check's sample
    # is full: fewer finished requests give the fp8 control fewer tokens
    for seed in (1, 2, 3):
        got = control.readings(tiny_root, cell, seed, 3.0, kind)
        assert all(got["program"][k] <= lim for k, lim in limits.items())
        assert any(got["control"][k] > lim for k, lim in limits.items()), \
            got
