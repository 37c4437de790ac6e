"""Tests for the contract-checker subsystem (src/repro/analysis).

Covers the acceptance contract from DESIGN.md §18: each engine fires on a
seeded violation (tests/fixture_analysis_violations.py holds one per rule),
stays silent on the sanctioned pattern, the baseline round-trips, the CLI
gates correctly, and the repo's own contract catalog + lint run clean
against the checked-in baseline.
"""

import json
import sys
from pathlib import Path

import pytest

import jax
import jax.numpy as jnp

from repro.analysis import findings as F
from repro.analysis.cli import RULE_DOCS, main as cli_main
from repro.analysis.contracts import CONTRACTS, run_repo_contracts
from repro.analysis.jaxpr_passes import determinism, dtype_flow, no_gemm
from repro.analysis.lint import CHECKERS, lint_file, lint_paths
from repro.analysis.pallas_audit import audit_pallas

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO / "tests"))
import fixture_analysis_violations as fx  # noqa: E402

_BF16_ALLOW = (("A", "float32", "bfloat16"), ("key", "float32", "bfloat16"))


def _rules(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# jaxpr passes on the seeded fixtures
# ---------------------------------------------------------------------------

class TestNoGemm:
    def test_fires_on_gemm_in_srht_style_apply(self):
        got = no_gemm(fx.bad_srht_apply, jax.random.PRNGKey(0),
                      jnp.zeros((8, 16), jnp.float32),
                      what="fixture srht")
        assert _rules(got) == {"JAX-NO-GEMM"}
        assert any("dot_general" in f.message for f in got)

    def test_clean_on_gemm_free_program(self):
        got = no_gemm(lambda x: (x + 1.0) * 2.0,
                      jnp.zeros((8,), jnp.float32), what="add")
        assert got == []

    def test_custom_denylist(self):
        got = no_gemm(lambda x: jnp.cumsum(x), jnp.zeros((8,), jnp.float32),
                      denied=("cumsum",), what="cumsum")
        assert _rules(got) == {"JAX-NO-GEMM"}


class TestDtypeFlow:
    def test_fires_on_f16_cast_on_a_path(self):
        got = dtype_flow(fx.bad_a_downcast,
                         jnp.zeros((8, 16), jnp.float32),
                         jnp.zeros((16, 4), jnp.float32),
                         labels={0: "A", 1: "key"}, allow=_BF16_ALLOW,
                         what="fixture downcast")
        assert _rules(got) == {"JAX-DTYPE-CAST"}
        # the A->f16 cast is the violation; the key->bf16 cast is allowlisted
        assert any("float16" in f.message for f in got)

    def test_clean_when_cast_is_allowlisted(self):
        got = dtype_flow(lambda a: a.astype(jnp.bfloat16),
                         jnp.zeros((8,), jnp.float32),
                         labels={0: "A"}, allow=_BF16_ALLOW, what="ok cast")
        assert got == []

    def test_fires_on_f64(self):
        def to64(a):
            return a.astype(jnp.float64)
        got = dtype_flow(to64, jnp.zeros((8,), jnp.float32),
                         labels={0: "A"}, allow=_BF16_ALLOW, what="f64")
        # without x64 enabled jax silently keeps f32, so accept either the
        # explicit JAX-F64 finding or a clean pass when the cast is a no-op
        assert _rules(got) <= {"JAX-F64"}

    def test_upcast_never_flagged(self):
        got = dtype_flow(lambda a: a.astype(jnp.float32),
                         jnp.zeros((8,), jnp.bfloat16),
                         labels={0: "A"}, allow=(), what="upcast")
        assert got == []


class TestDeterminism:
    def test_fires_on_unkeyed_randomness(self):
        got = determinism(fx.bad_unkeyed, jnp.zeros((8,), jnp.float32),
                          what="fixture unkeyed")
        assert _rules(got) == {"JAX-UNKEYED"}

    def test_clean_on_caller_keyed_randomness(self):
        got = determinism(
            lambda key, x: x + jax.random.normal(key, x.shape),
            jax.random.PRNGKey(0), jnp.zeros((8,), jnp.float32),
            what="keyed")
        assert got == []


# ---------------------------------------------------------------------------
# Pallas auditor
# ---------------------------------------------------------------------------

class TestPallasAudit:
    def test_fires_on_write_aliasing_blockspec(self):
        got = audit_pallas(fx.bad_alias_kernel,
                           jnp.zeros((16, 16), jnp.float32),
                           what="fixture alias")
        assert "PL-WRITE-ALIAS" in _rules(got)

    def test_clean_on_disjoint_output_blocks(self):
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def good(x):
            return pl.pallas_call(
                fx._copy_kernel,
                grid=(2, 2),
                in_specs=[pl.BlockSpec((8, 8), lambda i, j: (i, j))],
                out_specs=pl.BlockSpec((8, 8), lambda i, j: (i, j)),
                out_shape=jax.ShapeDtypeStruct((16, 16), jnp.float32),
                compiler_params=pltpu.CompilerParams(
                    dimension_semantics=("parallel", "parallel")),
                interpret=True,
            )(x)

        got = audit_pallas(good, jnp.zeros((16, 16), jnp.float32),
                           what="good kernel")
        assert got == []

    def test_reports_missing_pallas_call(self):
        got = audit_pallas(lambda x: x + 1.0,
                           jnp.zeros((8,), jnp.float32), what="no kernel")
        assert len(got) == 1 and "pallas_call" in got[0].message

    @staticmethod
    def _prefetch_kernel(out_map):
        """(16, 16) copy over a (2,) parallel grid with one scalar-prefetch
        operand ``rows``; ``out_map(i, rows)`` picks the output block."""
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def kernel(rows_ref, x_ref, o_ref):
            o_ref[...] = x_ref[...]

        def fn(rows, x):
            return pl.pallas_call(
                kernel,
                grid_spec=pltpu.PrefetchScalarGridSpec(
                    num_scalar_prefetch=1, grid=(2,),
                    in_specs=[pl.BlockSpec((8, 16), lambda i, r: (i, 0))],
                    out_specs=pl.BlockSpec((8, 16), out_map)),
                out_shape=jax.ShapeDtypeStruct((16, 16), jnp.float32),
                compiler_params=pltpu.CompilerParams(
                    dimension_semantics=("parallel",)),
                interpret=True,
            )(rows, x)

        return fn

    @pytest.mark.parametrize("rows_shape,declared,rules", [
        ((2,), 1, set()),
        ((2,), 0, {"PL-SMEM-SHAPE"}),
        ((1, 2), 1, {"PL-SMEM-SHAPE"}),
    ])
    def test_scalar_prefetch_count_and_rank(self, rows_shape, declared,
                                            rules):
        fn = self._prefetch_kernel(lambda i, r: (i, 0))
        got = audit_pallas(fn, jnp.zeros(rows_shape, jnp.int32),
                           jnp.zeros((16, 16), jnp.float32),
                           what="prefetch kernel", scalar_prefetch=declared)
        assert _rules(got) == rules

    def test_fires_on_output_index_map_reading_prefetch(self):
        # zeros for ``rows`` would "prove" (rows[i], 0) disjoint or aliased
        # on values the kernel never sees: the audit must refuse it
        fn = self._prefetch_kernel(lambda i, r: (r[i], 0))
        got = audit_pallas(fn, jnp.zeros((2,), jnp.int32),
                           jnp.zeros((16, 16), jnp.float32),
                           what="prefetch kernel", scalar_prefetch=1)
        assert _rules(got) == {"PL-WRITE-ALIAS"}
        assert "scalar-prefetch" in got[0].message


# ---------------------------------------------------------------------------
# AST lint on the seeded fixtures
# ---------------------------------------------------------------------------

class TestLint:
    def test_fixture_module_exact_rule_ids(self):
        got = lint_file(REPO / "tests" / "fixture_analysis_violations.py")
        assert _rules(got) == {"LINT-ATOMIC-IO", "LINT-NP-RANDOM",
                               "LINT-WALLCLOCK", "LINT-INT-TRACER"}

    def test_f64_fixture_fires_only_in_kernel_scope(self):
        kernel_fixture = REPO / "tests" / "kernels" / "fixture_f64.py"
        assert _rules(lint_file(kernel_fixture)) == {"LINT-F64-LITERAL"}
        # same source outside a kernels/ dir is not in scope for the rule
        outside = lint_file(REPO / "tests" / "fixture_analysis_violations.py",
                            checkers=("LINT-F64-LITERAL",))
        assert outside == []

    def test_findings_carry_anchor_and_hint(self):
        got = lint_file(REPO / "tests" / "fixture_analysis_violations.py")
        for f in got:
            assert f.line > 0 and f.match and f.hint
            assert f.file.endswith("fixture_analysis_violations.py")

    def test_atomic_io_module_itself_exempt(self):
        got = lint_file(REPO / "src" / "repro" / "_atomic_io.py",
                        checkers=("LINT-ATOMIC-IO",))
        assert got == []

    def test_jax_random_not_flagged_as_np_random(self, tmp_path):
        p = tmp_path / "ok.py"
        p.write_text("import jax\n\n"
                     "def f(key, n):\n"
                     "    return jax.random.uniform(key, (n,))\n")
        assert lint_file(p) == []

    def test_every_lint_rule_documented(self):
        for rule in CHECKERS:
            assert rule in RULE_DOCS


# ---------------------------------------------------------------------------
# acceptance criterion: the seeded fixture set produces exactly the
# expected rule ids, one engine sweep end to end
# ---------------------------------------------------------------------------

def test_fixture_violations_produce_expected_rule_set():
    findings = []
    findings += no_gemm(fx.bad_srht_apply, jax.random.PRNGKey(0),
                        jnp.zeros((8, 16), jnp.float32), what="fx")
    findings += dtype_flow(fx.bad_a_downcast,
                           jnp.zeros((8, 16), jnp.float32),
                           jnp.zeros((16, 4), jnp.float32),
                           labels={0: "A", 1: "key"}, allow=_BF16_ALLOW,
                           what="fx")
    findings += determinism(fx.bad_unkeyed, jnp.zeros((8,), jnp.float32),
                            what="fx")
    findings += audit_pallas(fx.bad_alias_kernel,
                             jnp.zeros((16, 16), jnp.float32), what="fx")
    findings += lint_file(REPO / "tests" / "fixture_analysis_violations.py")
    findings += lint_file(REPO / "tests" / "kernels" / "fixture_f64.py")
    assert _rules(findings) == {
        "JAX-NO-GEMM", "JAX-DTYPE-CAST", "JAX-UNKEYED", "PL-WRITE-ALIAS",
        "LINT-ATOMIC-IO", "LINT-NP-RANDOM", "LINT-WALLCLOCK",
        "LINT-INT-TRACER", "LINT-F64-LITERAL",
    }


# ---------------------------------------------------------------------------
# baseline mechanics
# ---------------------------------------------------------------------------

class TestBaseline:
    def _finding(self):
        return F.Finding(rule="LINT-WALLCLOCK", file="src/x.py", line=3,
                         message="m", hint="h", match="t0 = time.time()")

    def test_entry_without_reason_rejected(self, tmp_path):
        p = tmp_path / "b.json"
        p.write_text(json.dumps({"findings": [
            {"rule": "LINT-WALLCLOCK", "file": "src/x.py",
             "match": "t0 = time.time()"}]}))
        with pytest.raises(ValueError, match="reason"):
            F.load_baseline(p)

    def test_roundtrip_suppresses_matching_finding(self, tmp_path):
        f = self._finding()
        doc = F.baseline_doc([f])
        doc["findings"][0]["reason"] = "startup timestamp, not a duration"
        p = tmp_path / "b.json"
        p.write_text(json.dumps(doc))
        baseline = F.load_baseline(p)
        new, accepted = F.split_baselined([f], baseline)
        assert new == [] and accepted == [f]
        assert baseline.stale_entries([f]) == []

    def test_match_is_line_number_drift_proof(self, tmp_path):
        f = self._finding()
        doc = F.baseline_doc([f])
        doc["findings"][0]["reason"] = "r"
        p = tmp_path / "b.json"
        p.write_text(json.dumps(doc))
        drifted = F.Finding(rule=f.rule, file=f.file, line=99,
                            message=f.message, match=f.match)
        new, accepted = F.split_baselined([drifted], F.load_baseline(p))
        assert new == []

    def test_stale_entry_surfaces(self, tmp_path):
        p = tmp_path / "b.json"
        p.write_text(json.dumps({"findings": [
            {"rule": "LINT-WALLCLOCK", "file": "gone.py", "match": "x",
             "reason": "fixed long ago"}]}))
        baseline = F.load_baseline(p)
        assert len(baseline.stale_entries([self._finding()])) == 1

    def test_missing_baseline_file_raises(self, tmp_path):
        with pytest.raises(FileNotFoundError):
            F.load_baseline(tmp_path / "nope.json")


# ---------------------------------------------------------------------------
# CLI end to end
# ---------------------------------------------------------------------------

class TestCli:
    @pytest.fixture(autouse=True)
    def _no_ci_summary(self, monkeypatch):
        monkeypatch.delenv("GITHUB_STEP_SUMMARY", raising=False)

    def _bad_file(self, tmp_path):
        p = tmp_path / "bad.py"
        p.write_text("import time\n\n"
                     "def f():\n"
                     "    return time.time()\n")
        return p

    def test_exit_1_on_new_finding(self, tmp_path, capsys):
        assert cli_main([str(self._bad_file(tmp_path)), "--lint-only"]) == 1
        assert "LINT-WALLCLOCK" in capsys.readouterr().out

    def test_baseline_gates_to_exit_0(self, tmp_path, capsys):
        bad = self._bad_file(tmp_path)
        b = tmp_path / "baseline.json"
        assert cli_main([str(bad), "--lint-only",
                         "--write-baseline", str(b)]) == 0
        doc = json.loads(b.read_text())
        assert doc["findings"] and all(e["reason"] for e in doc["findings"])
        assert cli_main([str(bad), "--lint-only", "--baseline", str(b)]) == 0
        out = capsys.readouterr().out
        assert "0 new finding(s), 1 baselined" in out

    def test_json_format(self, tmp_path, capsys):
        assert cli_main([str(self._bad_file(tmp_path)), "--lint-only",
                         "--format", "json"]) == 1
        doc = json.loads(capsys.readouterr().out)
        assert doc["new"][0]["rule"] == "LINT-WALLCLOCK"

    def test_list_rules(self, capsys):
        assert cli_main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        for rule in RULE_DOCS:
            assert rule in out

    def test_github_step_summary_written(self, tmp_path, monkeypatch):
        summary = tmp_path / "summary.md"
        monkeypatch.setenv("GITHUB_STEP_SUMMARY", str(summary))
        cli_main([str(self._bad_file(tmp_path)), "--lint-only"])
        assert "repro.analysis" in summary.read_text()


# ---------------------------------------------------------------------------
# the repo itself is clean under its checked-in baseline
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_repo_contract_catalog_clean():
    findings = run_repo_contracts()
    assert findings == [], F.render_text(findings)


def test_repo_lint_clean_under_baseline(monkeypatch):
    monkeypatch.chdir(REPO)
    findings = lint_paths(["src/repro", "benchmarks"])
    baseline = F.load_baseline(REPO / "analysis_baseline.json")
    new, _ = F.split_baselined(findings, baseline)
    assert new == [], F.render_text(new)
    assert baseline.stale_entries(findings) == []


def test_contract_catalog_names_are_stable():
    assert set(CONTRACTS) == {
        "srht-no-gemm", "sketch-dtype-flow", "stream-update-dtype-flow",
        "sketch-determinism", "shgemm-fused-audit", "factored-decode-audit",
        "stream-b-weak-audit",
    }
