"""Driver for ``kind: hosvd``: the library's RP-HOSVD, called by one caller
in a closed loop over tensors resident on the device.

Set-up makes ``resident`` Algorithm 3 tensors with the configuration's
noise from the seed in one jitted call and compiles
``repro.core.hosvd.rp_hosvd`` with the configuration's ranks and the
projection method, Omega family and Omega type that it pins (the reference
draws the same Omegas from the same key, so the cell does not follow a
later change of the library's default).  The window calls it back to back,
each call on the next tensor with a fresh key, each ending in
``block_until_ready``.  A reservoir drawn from the seed keeps ``sample`` of
the window's decompositions; once the window has closed they are compared
with the plain float32 Algorithm 2 on the same tensor and the same Omegas.
The traffic's ``sketch_reps`` is not used: the projection is read inside
the call, by its scope.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from chipbench import loadgen
from chipbench.references import hosvd as ref


class Driver:
    control = "high"          # the reference in three bf16 passes (readings)

    def __init__(self, *, config: dict, traffic: dict, seed: int, spans,
                 trace: bool):
        if traffic["kind"] != "closed_loop":
            raise ValueError(f"hosvd driver takes closed_loop traffic, not "
                             f"{traffic['kind']!r}")
        self.cfg, self.traffic, self.seed = config, traffic, seed
        self.spans, self.trace = spans, trace
        self.ranks = tuple(config["ranks"])
        self.calls = 0
        self.window_s = None
        self.kept: list = []

    def _key(self, i: int):
        return jnp.asarray(loadgen.key_words(self.seed, 1000 + i))

    def _program(self, i: int, key) -> tuple:
        """``repro.core.hosvd.rp_hosvd`` on resident tensor ``i`` (modulo
        their count) with ``key``: the jitted function, its array arguments,
        its static arguments."""
        from repro.core import hosvd as program
        c = self.cfg
        return (program.rp_hosvd, (key, self.tensors[i % len(self.tensors)]),
                {"ranks": self.ranks, "method": c["method"],
                 "dist": c["dist"],
                 "omega_dtype": getattr(jnp, c["omega_dtype"])})

    def _call(self, i: int):
        fn, args, static = self._program(i, self._key(i))
        return fn(*args, **static)

    def scoped_call(self, i: int) -> tuple:
        """Call ``i`` of the scope readers' own trace (``scopes.py``): the
        window's program at its arguments, with keys it never draws."""
        return self._program(i, jnp.asarray(
            loadgen.key_words(self.seed, 5000 + i)))

    def setup(self) -> None:
        c = self.cfg
        self.tensors = ref.test_tensors(
            jnp.asarray(loadgen.key_words(self.seed, 0)),
            dims=tuple(c["dims"]), ranks=self.ranks, pad=c["pad"],
            noise=c["noise"], count=self.traffic["resident"])
        jax.block_until_ready(self.tensors)
        for i in (-1, -2):        # keys the window never draws
            jax.block_until_ready(self._call(i))

    def window(self, seconds: float) -> None:
        rng = loadgen.rng_for(self.seed, 2)
        size = int(self.traffic["sample"])
        self.spans.begin("window")
        t0 = time.perf_counter()
        i = 0
        while True:
            with self.spans("hosvd"):
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

    @property
    def attempted(self) -> int:
        return self.calls

    @property
    def failed(self) -> int:
        return 0

    def release(self) -> None:
        """Nothing of the program outlives the window but the kept results."""

    def readings(self, dot: str | None = None) -> dict[str, float]:
        """The worst of each number over the kept decompositions, against
        the reference on the same tensor and Omegas.  With ``dot`` the
        reference computed with that product stands in the program's place:
        the control."""
        worst: dict[str, float] = {}
        for i, (core, factors) in self.kept:
            a = self.tensors[i % len(self.tensors)]
            key = self._key(i)
            r = ref.rp_hosvd(key, a, ranks=self.ranks)
            if dot is not None:
                core, factors = ref.rp_hosvd(key, a, ranks=self.ranks,
                                             dot=dot)
            for k, v in ref.compare(a, core, factors, *r).items():
                worst[k] = max(worst.get(k, float("-inf")), float(v))
        return worst

    def verify(self) -> list[tuple[str, float, float]]:
        worst = self.readings()
        return [(k, worst[k], float(v)) for k, v in self.cfg["limits"].items()]
