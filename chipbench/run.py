"""Run one cell of the chip benchmark once.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The harness is driven by data.  It finds the cell in ``BENCHMARK.json``,
loads ``chipbench/configs/<config>.json`` and ``chipbench/traffic/<mix>.json``,
imports the driver ``chipbench/drivers/<kind>.py`` that the configuration's
``kind`` names and, for every metric the cell reports, the reader
``chipbench/metrics/<metric>.py``.  A driver also names its control
(``control.py``) and the program its scope readers trace (``scopes.py``).
So a new cell is new files (configuration, traffic mix, driver, metric
readers and its tiny counterpart ``tests/chipbench/tiny/<cell>.json``) and
entries appended to BENCHMARK.json.  The harness names no cell or kind, and
its tests find the cells from those files; a test names a cell only where it
checks one kind, or the look for a chip, on purpose.

One run: set-up (inputs and weights made on the device from the seed, every
shape the cell uses compiled or loaded from the persistent cache), then a
window of ``--seconds``, then the comparison with the plain reference that
decides ``correct``.  ``--trace 0`` prints the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics from a profiler trace of the window.
The last line of standard output is one JSON object; the numbers compared
and their limits are the last lines of standard error and the last key of
that object.  A run that finds no TPU, or fewer chips than the cell asks
for, exits 1 and prints no result.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()      # set-up is timed from the start of the process

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
TRACE_SECONDS = 10.0          # a traced run records the window's last 10 s


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    """Import one file of the benchmark by its path."""
    if not path.is_file():
        raise FileNotFoundError(f"no such benchmark file: {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(root: Path, workload: str):
    """The cell ``workload`` of ``root``'s BENCHMARK.json, with its
    configuration, its traffic mix and its driver's module; None for the
    cell when the manifest has no such cell."""
    manifest = _load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        return manifest, None, None, None, None
    cell = cells[workload]
    here = root / "chipbench"
    config = _load_json(here / "configs" / f"{cell['config']}.json")
    traffic = _load_json(here / "traffic" / f"{cell['traffic']}.json")
    driver = load_module(here / "drivers" / f"{config['kind']}.py",
                         f"chipbench_driver_{config['kind']}")
    return manifest, cell, config, traffic, driver


def cell_metrics(manifest: dict, cell: str, group: str) -> list[dict]:
    """The metrics of ``group`` (end_to_end or per_layer) this cell reports:
    those that list it, or list no cells at all."""
    return [m for m in manifest[group]
            if cell in m.get("workloads", [cell])]


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, *, root: Path = ROOT, require_chip: bool = True,
         driver_hook=None) -> int:
    """One run.  ``require_chip=False`` skips the look for a TPU (tests on
    the CPU); ``driver_hook(driver)``, called after set-up, lets a test
    break the timed path underneath."""
    args = parse_args(argv)
    for path in (root / "src", root):      # the program, and the harness
        if str(path) not in sys.path:
            sys.path.insert(0, str(path))
    here = root / "chipbench"
    trace_dir = root / ".chipbench" / "trace"
    manifest, cell, config, traffic, drv_mod = load_cell(root, args.workload)
    if cell is None:
        print(f"run: no cell {args.workload!r} in BENCHMARK.json",
              file=sys.stderr)
        return 2
    group = "per_layer" if args.trace else "end_to_end"
    metrics = cell_metrics(manifest, cell["name"], group)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if require_chip and (dev.platform != "tpu"
                         or len(devices) < cell["chips"]):
        print(f"run: cell {cell['name']} needs {cell['chips']} TPU chip(s); "
              f"JAX found {len(devices)} {dev.platform} device(s)",
              file=sys.stderr)
        return 1

    from repro.launch.compile_cache import configure_compile_cache
    from chipbench import spans as spans_mod
    from chipbench import counts

    # JAX's persistent cache lives in this checkout (set before jax was
    # imported, below in __main__); every program of the cell is cached
    cache_dir = None
    if require_chip:
        cache_dir = configure_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = spans_mod.CompileCounter()
    peaks = counts.peaks(dev.device_kind) if require_chip else None

    readers = {m["name"]: load_module(here / "metrics" / f"{m['name']}.py",
                                      f"chipbench_metric_{m['name']}")
               for m in metrics if m["name"] != "setup_s"}
    spans = spans_mod.Spans()
    driver = drv_mod.Driver(config=config, traffic=traffic, seed=args.seed,
                            spans=spans, trace=bool(args.trace))
    driver.setup()
    if driver_hook is not None:
        driver_hook(driver)
    setup_s = time.perf_counter() - T0
    before = compiles.count

    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        # host annotations and runtime events, no Python function tracing
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        spans.trace_tail(args.seconds, TRACE_SECONDS, lambda: (
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)))
    try:
        driver.window(args.seconds)     # the driver marks the window span
    finally:
        if spans.annotate:
            jax.profiler.stop_trace()
    in_window = compiles.count - before
    print(f"run: {in_window} program(s) compiled or loaded inside the "
          f"window; compile cache {cache_dir}", file=sys.stderr)
    for line in getattr(driver, "notes", lambda tail_s: [])(TRACE_SECONDS):
        print(line, file=sys.stderr)

    stats = dev.memory_stats() or {}
    peak_bytes = stats.get("peak_bytes_in_use")
    driver.release()
    checks = driver.verify()

    summary = None
    if args.trace:
        from chipbench import trace as trace_mod
        files = sorted(trace_dir.glob("**/*.xplane.pb"))
        if not files:
            raise RuntimeError(f"the profiler wrote no trace under "
                               f"{trace_dir}")
        summary = trace_mod.reduce(files[-1])
        shutil.rmtree(trace_dir, ignore_errors=True)

    # what a metric reader sees: the cell, its files, the driver after its
    # window, the host spans, the reduced trace and the chip's peaks
    run = SimpleNamespace(
        cell=cell, config=config, traffic=traffic, driver=driver,
        spans=spans, trace=summary, peaks=peaks, setup_s=setup_s)
    values = {}
    for m in metrics:
        v = setup_s if m["name"] == "setup_s" else readers[m["name"]].read(run)
        if v is None:
            if group == "end_to_end":
                raise RuntimeError(f"end-to-end metric {m['name']} read "
                                   f"nothing in cell {cell['name']}")
            continue
        values[m["name"]] = {"value": float(v), "unit": m["unit"]}

    correct = all(math.isfinite(v) and v <= lim for _, v, lim in checks)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes}
    out = {"correct": correct, "attempted": driver.attempted,
           "failed": driver.failed, "metrics": values, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = summary.breakdown()
    out["checks"] = {name: {"value": v, "limit": lim}
                     for name, v, lim in checks}
    for name, v, lim in checks:
        print(f"check {name}: {v!r} (limit {lim!r}) "
              f"{'ok' if math.isfinite(v) and v <= lim else 'FAILED'}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    # the compile cache goes inside the checkout, never where the
    # environment points: the two sides of a comparison share nothing
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    sys.exit(main())
