"""Driver for ``kind: rsvd``: the library's randomized SVD, called by one
caller in a closed loop over matrices resident on the device.

Set-up makes ``resident`` matrices of the configuration's family from the
seed in one jitted call and compiles ``repro.core.rsvd.rsvd`` with the
projection method, Omega family and Omega type that the configuration
pins (the reference draws the same Omega from the same key, so the cell
does not follow a later change of the library's default).  The window calls it back to back, each call on the next
matrix with a fresh key, each ending in ``block_until_ready``.  A reservoir
drawn from the seed keeps ``sample`` of the window's factorizations; once
the window has closed they are compared with the plain float32 Algorithm 1
on the same A and the same Omega.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from chipbench import loadgen
from chipbench.references import rsvd as ref


class Driver:
    control = "high"          # the reference in three bf16 passes (readings)

    def __init__(self, *, config: dict, traffic: dict, seed: int, spans,
                 trace: bool):
        if traffic["kind"] != "closed_loop":
            raise ValueError(f"rsvd driver takes closed_loop traffic, not "
                             f"{traffic['kind']!r}")
        self.cfg, self.traffic, self.seed = config, traffic, seed
        self.spans, self.trace = spans, trace
        self.calls = 0
        self.window_s = None
        self.kept: list = []

    def _key(self, i: int):
        return jnp.asarray(loadgen.key_words(self.seed, 1000 + i))

    def _program(self, i: int, key) -> tuple:
        """``repro.core.rsvd.rsvd`` on resident matrix ``i`` (modulo their
        count) with ``key``: the jitted function, its array arguments, its
        static arguments."""
        from repro.core import rsvd as program
        c = self.cfg
        return (program.rsvd, (key, self.mats[i % len(self.mats)]),
                {"rank": c["rank"], "oversample": c["oversample"],
                 "power_iters": c["power_iters"], **self._projection()})

    def _call(self, i: int):
        fn, args, static = self._program(i, self._key(i))
        return fn(*args, **static)

    def scoped_call(self, i: int) -> tuple:
        """Call ``i`` of the scope readers' own trace (``scopes.py``): the
        window's program at its arguments, with keys it never draws."""
        return self._program(i, jnp.asarray(
            loadgen.key_words(self.seed, 5000 + i)))

    def _projection(self) -> dict:
        c = self.cfg
        return {"method": c["method"], "dist": c["dist"],
                "omega_dtype": getattr(jnp, c["omega_dtype"])}

    def setup(self) -> None:
        c = self.cfg
        self.mats = ref.paper_matrices(
            jnp.asarray(loadgen.key_words(self.seed, 0)), n=c["n"],
            rank=c["rank"], s_p=c["s_p"], count=self.traffic["resident"])
        jax.block_until_ready(self.mats)
        for i in (-1, -2):        # keys the window never draws
            jax.block_until_ready(self._call(i))
        if self.trace:
            self.sketch()

    def sketch(self, reps: int = 1) -> None:
        """The library's sketch alone, at the cell's shape and with the
        cell's projection: the projection layer timed from outside."""
        from repro.core import projection as proj
        c = self.cfg
        p = min(c["rank"] + c["oversample"], c["n"])
        for i in range(reps):
            with self.spans("sketch"):
                jax.block_until_ready(proj.sketch(
                    self._key(-3 - i), self.mats[i % len(self.mats)], p,
                    **self._projection()))

    def window(self, seconds: float) -> None:
        rng = loadgen.rng_for(self.seed, 2)
        size = int(self.traffic["sample"])
        self.spans.begin("window")
        t0 = time.perf_counter()
        i = 0
        while True:
            with self.spans("rsvd"):
                res = self._call(i)
                jax.block_until_ready(res)
            # reservoir sampling: every call equally likely to be kept
            if i < size:
                self.kept.append((i, res))
            else:
                j = int(rng.integers(0, i + 1))
                if j < size:
                    self.kept[j] = (i, res)
            i += 1
            if time.perf_counter() - t0 >= seconds:
                break
        self.window_s = time.perf_counter() - t0
        self.spans.end("window")
        self.calls = i
        if self.trace:
            self.sketch(reps=int(self.traffic.get("sketch_reps", 20)))

    @property
    def attempted(self) -> int:
        return self.calls

    @property
    def failed(self) -> int:
        return 0

    def release(self) -> None:
        """Nothing of the program outlives the window but the kept results."""

    def readings(self, dot: str | None = None) -> dict[str, float]:
        """The worst of each number over the kept factorizations, against
        the reference on the same A and Omega.  With ``dot`` the reference
        computed with that product stands in the program's place: the
        control."""
        c = self.cfg
        worst: dict[str, float] = {}
        for i, res in self.kept:
            a = self.mats[i % len(self.mats)]
            key = self._key(i)
            r = ref.rsvd(key, a, rank=c["rank"], oversample=c["oversample"])
            if dot is not None:
                res = ref.rsvd(key, a, rank=c["rank"],
                               oversample=c["oversample"], dot=dot)
            for k, v in ref.compare(a, *res, *r).items():
                worst[k] = max(worst.get(k, float("-inf")), float(v))
        return worst

    def verify(self) -> list[tuple[str, float, float]]:
        worst = self.readings()
        return [(k, worst[k], float(v)) for k, v in self.cfg["limits"].items()]
