"""Readings that a cell's limits are set from: the program's own numbers
and its control's, on several seeds, in one process.

    python chipbench/control.py --workload <cell> --seconds <s> --seeds <n> [<n> ...]

For each seed it runs the cell as ``run.py`` does (set-up, a window of
``--seconds`` at the cell's own load), then prints one JSON line with the
numbers compared for what the window produced and for the control: the
plain reference computed one precision below the configuration's, put in
the program's place.  The driver names its control in its ``control``
attribute (rSVD ``high``: three bf16 passes for float32 at ``HIGHEST``;
serving ``fp8``: fp8 weights for bfloat16).  Benchmark runs never run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def readings(root: Path, workload: str, seed: int, seconds: float,
             control: str | None = None) -> dict:
    """Run ``workload`` once with ``seed`` and return the program's numbers
    and the control's: ``control``, else the one its driver names."""
    from chipbench import run as runmod
    from chipbench.spans import Spans
    _, cell, config, traffic, mod = runmod.load_cell(root, workload)
    if cell is None:
        raise KeyError(f"no cell {workload!r} in {root / 'BENCHMARK.json'}")
    drv = mod.Driver(config=config, traffic=traffic, seed=seed,
                     spans=Spans(), trace=False)
    drv.setup()
    drv.window(seconds)
    drv.release()
    kind = control or drv.control
    out = {"seed": seed, "program": drv.readings(),
           "control": {"kind": kind, **drv.readings(kind)}}
    del drv
    gc.collect()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("control: JAX found no TPU", file=sys.stderr)
        return 1
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    for seed in args.seeds:
        t0 = time.perf_counter()
        out = readings(ROOT, args.workload, seed, args.seconds)
        out["wall_s"] = time.perf_counter() - t0
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
