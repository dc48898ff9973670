"""dslint static-analysis plane (ISSUE 6): Engine A HLO rules, Engine B AST
rules, suppression comments, baseline round-trip, CLI exit codes — and the
tier-1 gate itself: the real compiled gpt2-tiny train step and both serving
executables must be lint-clean, and the package must lint clean against the
committed baseline.

Every rule has a seeded-violation case (fires) and a clean equivalent
(quiet), per the acceptance criteria.
"""

import json
import os
import subprocess
import sys
import textwrap
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu import analysis as dsa
from deepspeed_tpu.analysis import hlo_rules as H
from deepspeed_tpu.analysis.ast_rules import lint_source
from deepspeed_tpu.analysis.baseline import Baseline
from deepspeed_tpu.tools import dslint

warnings.filterwarnings("ignore")

pytestmark = pytest.mark.lint

REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def rules_of(findings):
    return [f.rule for f in findings]


# ---------------------------------------------------------------------------
# Engine A: fixture HLO snippets per rule (positive + clean)
# ---------------------------------------------------------------------------

def _hlo(body, alias=""):
    header = f"HloModule fixture, is_scheduled=true{alias}"
    return header + "\n\nENTRY %main.1 (p0: f32[64]) -> f32[64] {\n" + body + "\n}\n"


class TestNoUnexpectedAllgather:
    BIG_AG = "  %ag = f32[524288]{0} all-gather(f32[65536]{0} %p0), dimensions={0}"

    def test_fires_below_stage3(self):
        ctx = H.RuleContext(program="t", zero_stage=1, allgather_min_bytes=1 << 20)
        fs = H.rule_no_unexpected_allgather(_hlo(self.BIG_AG), ctx)
        assert rules_of(fs) == ["no-unexpected-allgather"]
        assert "stage-1" in fs[0].message and fs[0].line > 0

    def test_quiet_at_stage3(self):
        ctx = H.RuleContext(program="t", zero_stage=3)
        assert H.rule_no_unexpected_allgather(_hlo(self.BIG_AG), ctx) == []

    def test_quiet_below_threshold_and_async_done(self):
        small = "  %ag = f32[128]{0} all-gather(f32[16]{0} %p0), dimensions={0}"
        ctx = H.RuleContext(program="t", zero_stage=0)
        assert H.rule_no_unexpected_allgather(_hlo(small), ctx) == []
        done = ("  %agd = f32[524288]{0} all-gather-done((f32[65536]{0}, "
                "f32[524288]{0}) %ags)")
        assert H.rule_no_unexpected_allgather(_hlo(done), ctx) == []

    def test_declared_plan_sizes_exempt(self):
        # the compressed bucket all-gather IS the plan: exact size allowed
        ctx = H.RuleContext(
            program="t", zero_stage=1,
            allowed_collective_sizes=frozenset({524288 * 4}),
        )
        assert H.rule_no_unexpected_allgather(_hlo(self.BIG_AG), ctx) == []

    def test_async_start_counts(self):
        start = ("  %ags = (f32[65536]{0}, f32[524288]{0}) "
                 "all-gather-start(f32[65536]{0} %p0), dimensions={0}")
        ctx = H.RuleContext(program="t", zero_stage=0)
        assert rules_of(H.rule_no_unexpected_allgather(_hlo(start), ctx)) == [
            "no-unexpected-allgather"
        ]


class TestDonationHonored:
    PARAMS = (
        "  %p0 = f32[1024,1024]{1,0} parameter(0)\n"
        "  %p1 = f32[1024,1024]{1,0} parameter(1)\n"
        "  %small = f32[8]{0} parameter(2)"
    )

    def test_exact_shape_aliased_is_clean(self):
        txt = _hlo(self.PARAMS,
                   alias=", input_output_alias={ {0}: (0, {}, may-alias) }")
        ctx = H.RuleContext(program="t",
                            expect_aliased_shapes=[("f32", "1024,1024")])
        assert H.rule_donation_honored(txt, ctx) == []

    def test_missing_alias_fires(self):
        txt = _hlo(self.PARAMS)  # no alias table at all
        ctx = H.RuleContext(program="t",
                            expect_aliased_shapes=[("f32", "1024,1024")])
        fs = H.rule_donation_honored(txt, ctx)
        assert rules_of(fs) == ["donation-honored"]
        assert "HBM" in fs[0].message

    def test_duplicate_shape_needs_two_aliases(self):
        # the serving pools share one shape: one alias is NOT enough
        txt = _hlo(self.PARAMS,
                   alias=", input_output_alias={ {0}: (0, {}, may-alias) }")
        ctx = H.RuleContext(program="t",
                            expect_aliased_shapes=[("f32", "1024,1024")] * 2)
        fs = H.rule_donation_honored(txt, ctx)
        assert rules_of(fs) == ["donation-honored"]
        assert "1/2" in fs[0].message
        both = _hlo(self.PARAMS, alias=", input_output_alias={ {0}: (0, {}, "
                    "may-alias), {1}: (1, {}, may-alias) }")
        assert H.rule_donation_honored(both, ctx) == []

    def test_fraction_mode(self):
        txt_bad = _hlo(self.PARAMS)
        ctx = H.RuleContext(program="t", min_alias_fraction=0.5,
                            min_donatable_param_bytes=1 << 14)
        assert rules_of(H.rule_donation_honored(txt_bad, ctx)) == [
            "donation-honored"
        ]
        txt_ok = _hlo(self.PARAMS, alias=", input_output_alias={ {0}: (0, {}, "
                      "may-alias), {1}: (1, {}, may-alias) }")
        assert H.rule_donation_honored(txt_ok, ctx) == []

    def test_disabled_context_checks_nothing(self):
        assert H.rule_donation_honored(_hlo(self.PARAMS),
                                       H.RuleContext(program="t")) == []


class TestNoFp32Upcast:
    F32_DOT = ("  %dot.1 = f32[64,64]{1,0} dot(f32[64,128]{1,0} %a, "
               "f32[128,64]{1,0} %b), lhs_contracting_dims={1}, "
               "rhs_contracting_dims={0}")
    BF16_DOT = ("  %dot.2 = bf16[64,64]{1,0} dot(bf16[64,128]{1,0} %a, "
                "bf16[128,64]{1,0} %b), lhs_contracting_dims={1}, "
                "rhs_contracting_dims={0}")

    def test_fires_on_f32_dot_in_bf16_program(self):
        ctx = H.RuleContext(program="t", expected_dtype="bf16")
        fs = H.rule_no_fp32_upcast(_hlo(self.F32_DOT), ctx)
        assert rules_of(fs) == ["no-fp32-upcast"]
        assert "f32[" in fs[0].message

    def test_quiet_on_bf16_dot_and_without_expectation(self):
        ctx = H.RuleContext(program="t", expected_dtype="bf16")
        assert H.rule_no_fp32_upcast(_hlo(self.BF16_DOT), ctx) == []
        none_ctx = H.RuleContext(program="t", expected_dtype=None)
        assert H.rule_no_fp32_upcast(_hlo(self.F32_DOT), none_ctx) == []

    def test_allowlisted_metadata_is_deliberate_mixed_precision(self):
        line = self.F32_DOT + ', metadata={op_name="jit(f)/softmax_qk/dot"}'
        ctx = H.RuleContext(program="t", expected_dtype="bf16")
        assert H.rule_no_fp32_upcast(_hlo(line), ctx) == []

    @pytest.mark.xfail(
        strict=True, raises=AssertionError,
        reason="ROADMAP D14: introspect.operand_shapes reads typed shapes "
        "inside the call parens, and jax 0.9.0 prints operands by name only "
        "(`dot(%a.1, %b.1)`), so the rule sees no operand and cannot fire. "
        "Repair the reader (operand shapes through parse_named_instruction's "
        "symbol table), then remove this mark.",
    )
    def test_operand_shapes_on_the_installed_grammar(self):
        """The rule on text the installed compiler prints, not on the
        hand-written lines above: a bf16 program with one f32 dot."""
        def f(x, w, v):
            h = jnp.tanh(x @ w)
            return h.astype(jnp.float32) @ v

        x = jnp.ones((64, 128), jnp.bfloat16)
        w = jnp.ones((128, 256), jnp.bfloat16)
        v = jnp.ones((256, 32), jnp.float32)
        txt = jax.jit(f).lower(x, w, v).compile().as_text()
        f32_dots = [
            line for line in txt.splitlines()
            if H.parse_instruction(line)[0] == "dot" and "= f32[64,32]" in line
        ]
        if len(f32_dots) != 1:  # the program is not the one this test means
            pytest.fail(f"expected one f32[64,32] dot in the compiled text: {f32_dots}")
        ctx = H.RuleContext(program="t", expected_dtype="bf16")
        fs = H.rule_no_fp32_upcast(txt, ctx)
        assert "no-fp32-upcast" in rules_of(fs)


class TestCollectiveOverlap:
    SYNC_AR = ("  %ar = f32[262144]{0} all-reduce(f32[262144]{0} %p0), "
               "to_apply=%add")
    ASYNC = ("  %ags = (f32[262144]{0}, f32[2097152]{0}) "
             "all-gather-start(f32[262144]{0} %p0), dimensions={0}")

    def test_sync_collective_fires_under_overlap_flags(self):
        ctx = H.RuleContext(program="t", overlap_expected=True)
        fs = H.rule_collective_overlap(_hlo(self.SYNC_AR), ctx)
        assert rules_of(fs) == ["collective-overlap"]
        assert "T3" in fs[0].message

    def test_async_pairs_and_no_expectation_stay_quiet(self):
        ctx = H.RuleContext(program="t", overlap_expected=True)
        assert H.rule_collective_overlap(_hlo(self.ASYNC), ctx) == []
        off = H.RuleContext(program="t", overlap_expected=False)
        assert H.rule_collective_overlap(_hlo(self.SYNC_AR), off) == []

    def test_small_sync_collective_below_floor_is_noise(self):
        tiny = "  %ar = f32[16]{0} all-reduce(f32[16]{0} %p0), to_apply=%add"
        ctx = H.RuleContext(program="t", overlap_expected=True)
        assert H.rule_collective_overlap(_hlo(tiny), ctx) == []


class TestStaticShapes:
    def test_budget_modes(self):
        ctx = H.RuleContext(program="serving")
        assert H.check_program_budget(2, 2, ctx, exact=True) == []
        assert rules_of(H.check_program_budget(3, 2, ctx, exact=True)) == [
            "static-shapes"
        ]
        # the serving contract is EXACT: fewer programs is as wrong as more
        assert rules_of(H.check_program_budget(1, 2, ctx, exact=True)) == [
            "static-shapes"
        ]
        assert H.check_program_budget(3, 4, ctx) == []
        fs = H.check_program_budget(9, 4, ctx)
        assert rules_of(fs) == ["static-shapes"]
        assert "recompilation" in fs[0].message


# ---------------------------------------------------------------------------
# Engine A on REAL compiled programs (acceptance: donation + replication
# verified against actual executables, not just fixtures)
# ---------------------------------------------------------------------------

class TestHloRulesOnRealPrograms:
    def test_donation_rule_on_real_donated_and_undonated_jit(self):
        def step(state, x):
            return state + x, (state * x).sum()

        state = jnp.ones((256, 256))
        x = jnp.ones((256, 256))
        ctx = H.RuleContext(program="step",
                            expect_aliased_shapes=[("f32", "256,256")])
        donated = jax.jit(step, donate_argnums=(0,)).lower(state, x).compile()
        assert H.verify_compiled(donated, ctx) == []
        # the seeded violation for the HLO rule — waive the AST rule so this
        # test file itself lints clean under `dslint --changed`
        # dslint: disable=missing-donate-argnums
        undonated = jax.jit(step).lower(state, x).compile()
        fs = H.verify_compiled(undonated, ctx)
        assert "donation-honored" in rules_of(fs)


# ---------------------------------------------------------------------------
# Engine B: AST rule unit cases
# ---------------------------------------------------------------------------

def lint(src, **kw):
    findings, waived = lint_source(textwrap.dedent(src), path="t.py", **kw)
    return findings, waived


class TestHostSyncRules:
    def test_item_in_hot_step_fires(self):
        fs, _ = lint("""
            class ServingEngine:
                def step(self):
                    return self.loss.item()
        """)
        assert rules_of(fs) == ["host-sync-in-step"]
        assert fs[0].symbol == "ServingEngine.step"

    def test_same_code_in_cold_function_is_quiet(self):
        fs, _ = lint("""
            class ServingEngine:
                def shutdown(self):
                    return self.loss.item()
        """)
        assert fs == []

    def test_device_get_and_block_until_ready_fire(self):
        fs, _ = lint("""
            import jax
            class ServingEngine:
                def step(self, out):
                    jax.block_until_ready(out)
                    return jax.device_get(out)
        """)
        assert sorted(rules_of(fs)) == ["host-sync-in-step"] * 2

    def test_np_asarray_flags_only_jax_arguments(self):
        fs, _ = lint("""
            import numpy as np, jax
            class ServingEngine:
                def step(self, prompt):
                    a = np.asarray(prompt, np.int32)      # host data: fine
                    b = np.asarray(jax.random.PRNGKey(0)) # device sync: not
                    return a, b
        """)
        assert rules_of(fs).count("host-sync-in-step") == 1

    def test_host_sync_in_traced_via_decorator_and_scan_body(self):
        fs, _ = lint("""
            import jax
            @jax.jit
            def step_fn(x):
                return float(jax.device_get(x))
        """)
        assert "host-sync-in-traced" in rules_of(fs)
        fs, _ = lint("""
            import jax
            from jax import lax
            def outer(xs):
                def body(c, x):
                    return c + x.item(), None
                return lax.scan(body, 0.0, xs)
        """)
        assert "host-sync-in-traced" in rules_of(fs)

    def test_clean_traced_function_is_quiet(self):
        fs, _ = lint("""
            import jax, jax.numpy as jnp
            @jax.jit
            def step_fn(x):
                return jnp.tanh(x) * 2
        """)
        assert fs == []


class TestTracerBranch:
    def test_branch_on_traced_value_fires(self):
        fs, _ = lint("""
            import jax, jax.numpy as jnp
            @jax.jit
            def step_fn(x):
                if jnp.any(jnp.isnan(x)):
                    return x * 0
                return x
        """)
        assert "tracer-branch" in rules_of(fs)

    def test_static_python_branch_is_quiet(self):
        # branching on a static config value is the normal trace-time
        # specialization pattern — must NOT flag
        fs, _ = lint("""
            import jax, jnp
            @jax.jit
            def step_fn(x, temperature=0.0):
                if not temperature or temperature <= 0.0:
                    return x
                return x / temperature
        """)
        assert rules_of(fs) == []

    def test_reduction_attr_in_while_fires(self):
        fs, _ = lint("""
            import jax
            @jax.jit
            def step_fn(x):
                while x.sum() > 0:
                    x = x - 1
                return x
        """)
        assert "tracer-branch" in rules_of(fs)


class TestJnpInHotLoop:
    def test_device_dispatch_in_hot_function_fires(self):
        fs, _ = lint("""
            import jax.numpy as jnp
            class ServingEngine:
                def step(self):
                    return self.exec(jnp.asarray(self.tokens))
        """)
        assert rules_of(fs) == ["jnp-in-hot-loop"]

    def test_numpy_and_host_side_jax_are_quiet(self):
        fs, _ = lint("""
            import numpy as np, jax
            class ServingEngine:
                def step(self):
                    jax.tree.map(lambda x: x, self.state)
                    return self.exec(np.asarray(self.tokens))
        """)
        assert fs == []

    def test_custom_hot_patterns(self):
        src = """
            import jax.numpy as jnp
            class Worker:
                def spin(self):
                    return jnp.zeros(4)
        """
        fs, _ = lint(src)
        assert fs == []  # not hot by default
        fs, _ = lint(src, hot_patterns=["Worker.spin"])
        assert rules_of(fs) == ["jnp-in-hot-loop"]


class TestMissingDonate:
    def test_step_like_jit_without_donate_fires(self):
        fs, _ = lint("""
            import jax
            def train_step(state, batch):
                return state
            compiled = jax.jit(train_step)
        """)
        assert rules_of(fs) == ["missing-donate-argnums"]

    def test_with_donate_and_non_step_names_quiet(self):
        fs, _ = lint("""
            import jax
            def train_step(state, batch):
                return state
            def helper(x):
                return x
            a = jax.jit(train_step, donate_argnums=(0,))
            b = jax.jit(helper)
        """)
        assert fs == []


class TestUnstableCacheKey:
    def test_id_key_fires_on_subscript_and_get(self):
        fs, _ = lint("""
            def lookup(cache, params):
                cache[id(params)] = 1
                return cache.get(id(params))
        """)
        assert rules_of(fs) == ["unstable-cache-key"] * 2

    def test_unhashable_literal_key_fires(self):
        fs, _ = lint("""
            def store(cache, shape):
                cache[[1, 2]] = shape
        """)
        assert rules_of(fs) == ["unstable-cache-key"]

    def test_tuple_keys_and_non_cache_names_quiet(self):
        fs, _ = lint("""
            def lookup(cache, registry, x):
                cache[(x.shape, str(x.dtype))] = 1
                registry[id(x)] = 2  # not a cache name
        """)
        assert fs == []


class TestSuppression:
    def test_same_line_and_line_above(self):
        fs, waived = lint("""
            class ServingEngine:
                def step(self):
                    a = self.loss.item()  # dslint: disable=host-sync-in-step
                    # dslint: disable=host-sync-in-step
                    b = self.loss.item()
                    return a + b
        """)
        assert fs == [] and waived == 2

    def test_justification_block_above(self):
        fs, waived = lint("""
            class ServingEngine:
                def step(self):
                    # dslint: disable=host-sync-in-step — the scheduler must
                    # read the token to retire the slot (multi-line note)
                    return self.tok.item()
        """)
        assert fs == [] and waived == 1

    def test_wrong_rule_does_not_suppress(self):
        fs, waived = lint("""
            class ServingEngine:
                def step(self):
                    return self.loss.item()  # dslint: disable=tracer-branch
        """)
        assert rules_of(fs) == ["host-sync-in-step"] and waived == 0

    def test_bare_disable_silences_all(self):
        fs, waived = lint("""
            import jax.numpy as jnp
            class ServingEngine:
                def step(self):
                    return jnp.zeros(3), self.loss.item()  # dslint: disable
        """)
        assert fs == [] and waived == 2


# ---------------------------------------------------------------------------
# baseline: add / expire round-trip
# ---------------------------------------------------------------------------

class TestBaseline:
    def test_missing_file_is_empty(self, tmp_path):
        bl = Baseline.load(str(tmp_path / "nope.json"))
        assert len(bl) == 0

    def test_add_expire_round_trip(self, tmp_path):
        path = str(tmp_path / "bl.json")
        f1 = dsa.Finding(rule="r1", severity="error", message="m",
                         path="a.py", line=3, symbol="f", snippet="x.item()")
        f2 = dsa.Finding(rule="r2", severity="warning", message="m",
                         path="b.py", line=9, symbol="g", snippet="jnp.zeros(1)")
        bl = Baseline.load(path)
        bl.path = path
        bl.update([f1, f2])
        bl.save()
        bl2 = Baseline.load(path)
        assert len(bl2) == 2
        new, known, stale = bl2.split([f1])
        assert new == [] and known == [f1]
        assert stale == [f2.fingerprint()]  # f2 fixed → entry expires
        bl2.update([f1])
        bl2.save()
        assert len(Baseline.load(path)) == 1

    def test_fingerprint_survives_line_drift_not_content_change(self):
        f = dsa.Finding(rule="r", severity="error", message="m",
                        path="a.py", line=3, symbol="f", snippet="x.item()")
        moved = dsa.Finding(rule="r", severity="error", message="m",
                            path="a.py", line=99, symbol="f", snippet="x.item()")
        edited = dsa.Finding(rule="r", severity="error", message="m",
                             path="a.py", line=3, symbol="f", snippet="y.item()")
        assert f.fingerprint() == moved.fingerprint()
        assert f.fingerprint() != edited.fingerprint()

    def test_corrupt_baseline_raises_value_error(self, tmp_path):
        path = tmp_path / "bl.json"
        path.write_text("{not json")
        with pytest.raises(ValueError, match="corrupt"):
            Baseline.load(str(path))


# ---------------------------------------------------------------------------
# CLI: exit codes 0 clean / 1 new findings / 2 usage
# ---------------------------------------------------------------------------

BAD_SRC = textwrap.dedent("""
    import jax
    class ServingEngine:
        def step(self):
            return jax.device_get(self.tokens)
""")

CLEAN_SRC = "def helper(x):\n    return x + 1\n"


class TestCli:
    def test_exit_codes_and_baseline_gate(self, tmp_path, monkeypatch, capsys):
        monkeypatch.chdir(tmp_path)
        (tmp_path / "bad.py").write_text(BAD_SRC)
        (tmp_path / "clean.py").write_text(CLEAN_SRC)
        assert dslint.main(["clean.py"]) == 0
        assert dslint.main(["bad.py"]) == 1
        assert "host-sync-in-step" in capsys.readouterr().out
        # record the debt → gate passes, but reports the known finding
        assert dslint.main(["bad.py", "--update-baseline"]) == 0
        assert dslint.main(["bad.py"]) == 0
        out = capsys.readouterr().out
        assert "(baselined)" in out
        # a NEW violation still fails against the recorded baseline
        (tmp_path / "bad.py").write_text(
            BAD_SRC + "\n\ndef train_step(s):\n    return s\n"
            "import jax\nj = jax.jit(train_step)\n"
        )
        assert dslint.main(["bad.py"]) == 1
        # fixing everything leaves stale entries; --update-baseline expires
        (tmp_path / "bad.py").write_text(CLEAN_SRC)
        assert dslint.main(["bad.py"]) == 0
        assert "stale" in capsys.readouterr().out
        assert dslint.main(["bad.py", "--update-baseline"]) == 0
        assert len(Baseline.load(".dslint-baseline.json")) == 0

    def test_usage_errors(self, tmp_path, monkeypatch):
        monkeypatch.chdir(tmp_path)
        assert dslint.main([]) == 2  # no paths, no --changed
        (tmp_path / "broken.py").write_text("def oops(:\n")
        assert dslint.main(["broken.py"]) == 2  # unparseable
        # a typo'd path must NOT pass the gate by scanning nothing
        assert dslint.main(["no_such_dir/"]) == 2
        assert dslint.main(["missing.py"]) == 2
        (tmp_path / ".dslint-baseline.json").write_text("{corrupt")
        (tmp_path / "ok.py").write_text(CLEAN_SRC)
        assert dslint.main(["ok.py"]) == 2  # corrupt baseline

    def test_json_report_and_list_rules(self, tmp_path, monkeypatch, capsys):
        monkeypatch.chdir(tmp_path)
        (tmp_path / "bad.py").write_text(BAD_SRC)
        assert dslint.main(["bad.py", "--json", "--no-baseline"]) == 1
        doc = json.loads(capsys.readouterr().out)
        assert doc["findings_total"] == 1
        assert doc["new"][0]["rule"] == "host-sync-in-step"
        assert dslint.main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        for rule in list(dsa.HLO_RULES) + list(dsa.AST_RULES):
            assert rule in out

    def test_package_lints_clean_against_committed_baseline(self):
        """THE tier-1 CI gate: `dslint deepspeed_tpu/` exits 0 on the repo."""
        pkg = os.path.join(REPO_ROOT, "deepspeed_tpu")
        baseline = os.path.join(REPO_ROOT, dsa.DEFAULT_BASELINE_NAME)
        assert os.path.exists(baseline), "committed baseline missing"
        report = dslint.collect([pkg], baseline_path=baseline)
        new = report["new"]
        assert new == [], "NEW dslint findings:\n" + "\n".join(
            f.render() for f in new
        )
        # the hot-path cleanup (ISSUE 6 satellite): serving/ and the train
        # engine carry ZERO baselined debt — fixed or justified inline
        for f in report["known"]:
            assert not f.path.startswith("deepspeed_tpu/serving/"), f.render()
            assert f.path != "deepspeed_tpu/runtime/engine.py", f.render()

    def test_changed_mode_smoke(self):
        # --changed needs git; in this repo it must not crash and must
        # return a gate-style code (no new findings in changed files → 0/1)
        rc = dslint.main(["--changed"])
        assert rc in (0, 1)

    def test_changed_files_resolve_from_a_subdirectory(self, monkeypatch):
        # git prints repo-root-relative paths; from a subdir cwd the gate
        # must still see the changed files instead of passing vacuously
        files_from_root = dslint._git_changed_files()
        monkeypatch.chdir(os.path.join(REPO_ROOT, "docs"))
        files_from_sub = dslint._git_changed_files()
        assert files_from_sub == files_from_root
        assert all(os.path.exists(f) for f in files_from_sub)

    def test_config_section_drives_the_cli(self, tmp_path, monkeypatch, capsys):
        monkeypatch.chdir(tmp_path)
        (tmp_path / "w.py").write_text(
            "import jax.numpy as jnp\n"
            "class Worker:\n"
            "    def spin(self):\n"
            "        return jnp.zeros(4)\n"
        )
        cfg = tmp_path / "ds_config.json"
        assert dslint.main(["w.py", "--no-baseline"]) == 0  # not hot by default
        cfg.write_text(json.dumps(
            {"analysis": {"hot_function_patterns": ["Worker.spin"]}}
        ))
        assert dslint.main(["w.py", "--no-baseline", "--config", str(cfg)]) == 1
        assert "jnp-in-hot-loop" in capsys.readouterr().out
        cfg.write_text(json.dumps({"analysis": {"enabled": False}}))
        assert dslint.main(["w.py", "--config", str(cfg)]) == 0
        cfg.write_text("{not json")
        assert dslint.main(["w.py", "--config", str(cfg)]) == 2
        # analysis.baseline names the gate file when --baseline is absent
        (tmp_path / "bad.py").write_text(BAD_SRC)
        cfg.write_text(json.dumps({"analysis": {"baseline": "my_bl.json"}}))
        assert dslint.main(
            ["bad.py", "--config", str(cfg), "--update-baseline"]
        ) == 0
        assert os.path.exists(tmp_path / "my_bl.json")
        assert dslint.main(["bad.py", "--config", str(cfg)]) == 0


# ---------------------------------------------------------------------------
# the pytest gate on the REAL programs (acceptance pins)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def gpt2_tiny_cfg():
    from deepspeed_tpu.models import gpt2

    return gpt2.get_config("gpt2-tiny", attn_impl="jnp")


@pytest.fixture(scope="module")
def serving_engine(gpt2_tiny_cfg):
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.models import gpt2

    params = gpt2.init_params(gpt2_tiny_cfg, jax.random.PRNGKey(0))
    eng = InferenceEngine(
        gpt2.make_module(gpt2_tiny_cfg), params=params, dtype=jnp.float32
    )
    return eng.serve({
        "max_slots": 4, "page_size": 4, "num_pages": 64,
        "max_prompt_len": 12, "max_new_tokens": 8,
        "kv_cache_dtype": "float32",
    })


@pytest.fixture(scope="module")
def train_engine(gpt2_tiny_cfg):
    from deepspeed_tpu.models import gpt2
    from deepspeed_tpu.parallel.topology import MeshSpec
    from deepspeed_tpu.runtime.config import DeepSpeedConfig
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine

    ds = DeepSpeedConfig.load({
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 0},
        "mesh": {"dp": 8},
        "steps_per_print": 10**9,
    }, dp_world_size=8)
    mesh = MeshSpec(dp=8).build_mesh()
    engine = DeepSpeedEngine(
        gpt2.make_module(gpt2_tiny_cfg), ds, mesh=mesh, seed=0
    )
    batch = {
        "input_ids": np.arange(16 * 16, dtype=np.int32).reshape(16, 16)
        % gpt2_tiny_cfg.vocab_size
    }
    engine.train_batch(batch)
    return engine


class TestProgramGate:
    def test_gpt2_train_step_is_lint_clean(self, train_engine):
        """Donation + replication + budget verified on the real compiled
        gpt2-tiny train step (ISSUE 6 acceptance)."""
        assert train_engine.verify_program() == []
        # and the check is not vacuous: the program has an alias table and
        # large donated params the fraction rule actually measured
        txt = train_engine._compiled_step().as_text()
        assert len(H._aliased_params(txt)) > 0
        acfg = train_engine.config.analysis
        big = [
            num for num, (dt, dd, _) in H._entry_params(txt).items()
            if H.shape_bytes(dt, dd) >= acfg.min_donatable_param_bytes
        ]
        assert big, "fraction check had nothing to measure"

    def test_verify_program_shares_the_introspection_compile(self, train_engine):
        c1 = train_engine._compiled_step()
        train_engine.verify_program()
        assert train_engine._compiled_step() is c1  # one compile, cached

    def test_both_serving_programs_are_lint_clean(self, serving_engine):
        """Both serving executables: pools donated AND aliased, exactly two
        programs (ISSUE 6 acceptance)."""
        assert serving_engine.verify() == []
        assert len(serving_engine.executables) == 2
        # non-vacuous: each program really has two aliased pool params
        pool_dims = ",".join(str(d) for d in serving_engine.k_pool.shape)
        for exe in serving_engine.executables:
            txt = exe.as_text()
            aliased = H._aliased_params(txt)
            pools = [
                num for num, (dt, dd, _) in H._entry_params(txt).items()
                if dd == pool_dims
            ]
            assert len(pools) == 2
            assert all(p in aliased for p in pools)

    def test_gpt2_train_step_collectives_consistent(self, train_engine):
        """Engine D over the real dp8 train step (ISSUE 8 acceptance):
        channel ids unique, starts/dones matched — and the check is not
        vacuous: the program really contains collectives."""
        from deepspeed_tpu.analysis import collective_rules as D

        txt = train_engine._compiled_step().as_text()
        assert D.verify_program_set({"train_step": txt}) == []
        assert len(D.extract_collectives(txt)) > 0

    def test_serving_programs_collectives_consistent(self, serving_engine):
        """Engine D over both serving executables (ISSUE 8 acceptance):
        the full program-set pass — per-program rules + the cross-program
        order-divergence check — reports []."""
        from deepspeed_tpu.analysis import collective_rules as D

        assert D.verify_compiled_set({
            "serving_prefill": serving_engine._prefill_exec,
            "serving_decode": serving_engine._decode_exec,
        }) == []

    def test_serving_budget_violation_fires(self, serving_engine):
        from deepspeed_tpu.analysis import check_program_budget

        ctx = H.RuleContext(program="serving")
        fs = check_program_budget(
            len(serving_engine.executables) + 1, 2, ctx, exact=True
        )
        assert rules_of(fs) == ["static-shapes"]

    def test_analysis_disabled_skips(self, serving_engine):
        assert serving_engine.verify({"enabled": False}) == []

    def test_serving_budget_parameterized(self, serving_engine):
        """ISSUE 10 satellite: the Engine A serving budget is
        ``analysis.max_serving_programs`` (0 = auto-track the engine's
        feature set) instead of the old hard-coded EXACTLY 2."""
        # auto (default 0) tracks expected_executables — clean
        assert serving_engine.expected_executables == 2
        assert serving_engine.verify() == []
        # an explicit budget that disagrees with reality trips the gate
        fs = serving_engine.verify({"max_serving_programs": 5})
        assert "static-shapes" in rules_of(fs)
        # an explicit budget that matches passes
        assert serving_engine.verify({"max_serving_programs": 2}) == []

    def test_feature_enabled_serving_programs_verify_clean(self, gpt2_tiny_cfg):
        """Speculative verify + chunk-prefill executables pass the full
        A/D/E gate under the AUTO budget — the new programs must not trip
        the static-shapes, donation, or memory-budget rules (ISSUE 10
        acceptance)."""
        from deepspeed_tpu.inference.engine import InferenceEngine
        from deepspeed_tpu.models import gpt2

        tiny_cfg = gpt2_tiny_cfg
        params = gpt2.init_params(tiny_cfg, jax.random.PRNGKey(0))
        eng = InferenceEngine(
            gpt2.make_module(tiny_cfg), params=params, dtype=jnp.float32
        )
        srv = eng.serve({
            "max_slots": 4, "page_size": 4, "num_pages": 64,
            "max_prompt_len": 12, "max_new_tokens": 8,
            "kv_cache_dtype": "float32",
            "speculative": {"enabled": True, "k": 4},
            "prefix_cache": {"enabled": True},
            "prefill_chunk_tokens": 4,
        })
        # the verify step and the chunk program: an engine that chunks its
        # cold prompts builds no whole-prompt program (ISSUE 63)
        assert srv.expected_executables == 2
        assert srv.verify() == []
        names = [n for n, _ in srv.executable_names()]
        assert names == ["serving_verify", "serving_chunk_prefill"]
        # the verify program's pools are donated-and-aliased like decode's
        pool_dims = ",".join(str(d) for d in srv.k_pool.shape)
        for _, exe in srv.executable_names():
            txt = exe.as_text()
            aliased = H._aliased_params(txt)
            pools = [
                num for num, (dt, dd, _) in H._entry_params(txt).items()
                if dd == pool_dims
            ]
            assert len(pools) == 2 and all(p in aliased for p in pools)
        # Engine E labels the draft/block-table control plane "metadata"
        ana = srv._memory_analyses["serving_verify"]
        assert ana.by_category.get("metadata", 0) > 0

    def test_max_serving_programs_config_validation(self):
        from deepspeed_tpu.runtime.config import (
            AnalysisConfig,
            DeepSpeedConfigError,
        )

        assert AnalysisConfig(max_serving_programs=3).max_serving_programs == 3
        with pytest.raises(DeepSpeedConfigError, match="max_serving_programs"):
            AnalysisConfig(max_serving_programs=-1)


# ---------------------------------------------------------------------------
# config section + env_report satellite
# ---------------------------------------------------------------------------

class TestAnalysisConfig:
    def test_section_parses_and_validates(self):
        from deepspeed_tpu.runtime.config import (
            AnalysisConfig,
            DeepSpeedConfig,
            DeepSpeedConfigError,
        )

        ds = DeepSpeedConfig.load({
            "train_micro_batch_size_per_gpu": 1,
            "analysis": {"max_train_programs": 8,
                         "hot_function_patterns": ["Foo.step"]},
        })
        assert ds.analysis.max_train_programs == 8
        assert ds.analysis.hot_function_patterns == ["Foo.step"]
        assert ds.analysis.enabled
        with pytest.raises(DeepSpeedConfigError):
            AnalysisConfig(min_alias_fraction=1.5)
        with pytest.raises(DeepSpeedConfigError):
            AnalysisConfig(max_train_programs=0)

    def test_env_report_mentions_analysis(self):
        res = subprocess.run(
            [sys.executable, "-m", "deepspeed_tpu.env_report"],
            capture_output=True, text=True, timeout=300,
            env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=REPO_ROOT,
        )
        assert res.returncode == 0
        assert "Static analysis (dslint)" in res.stdout
        assert "baseline" in res.stdout


# ---------------------------------------------------------------------------
# trace_diff hardening satellite: clear exit-2 on schema/truncation damage
# ---------------------------------------------------------------------------

class TestTraceDiffRobustness:
    def _good_trace(self, path, steps=6):
        with open(path, "w") as fh:
            for s in range(steps):
                fh.write(json.dumps({
                    "kind": "train_step", "step": s, "dur_ms": 10.0,
                    "spans": {"children": {"sync": 5.0}},
                }) + "\n")

    def test_schema_mismatch_exits_2_with_message(self, tmp_path, capsys):
        from deepspeed_tpu.tools import trace_diff

        a = str(tmp_path / "a.jsonl")
        self._good_trace(a)
        alien = str(tmp_path / "alien.jsonl")
        with open(alien, "w") as fh:
            fh.write("[1, 2, 3]\n")  # valid JSON, wrong shape
        assert trace_diff.main([a, alien]) == 2
        err = capsys.readouterr().err
        assert "not a StepTracer trace" in err and "Traceback" not in err

    def test_wrong_field_types_exit_2(self, tmp_path, capsys):
        from deepspeed_tpu.tools import trace_diff

        a = str(tmp_path / "a.jsonl")
        self._good_trace(a)
        b = str(tmp_path / "b.jsonl")
        with open(b, "w") as fh:
            fh.write(json.dumps({
                "kind": "train_step", "step": 0, "dur_ms": 1.0,
                "spans": ["not", "a", "dict"],
            }) + "\n")
        assert trace_diff.main([a, b]) == 2
        assert "schema mismatch" in capsys.readouterr().err

    def test_torn_tail_is_tolerated_but_mid_file_damage_is_not(
        self, tmp_path, capsys
    ):
        from deepspeed_tpu.tools import trace_diff

        a = str(tmp_path / "a.jsonl")
        self._good_trace(a)
        # torn tail (killed run / rotation point): still diffs, exit 0
        tail = str(tmp_path / "tail.jsonl")
        self._good_trace(tail)
        with open(tail, "a") as fh:
            fh.write('{"kind": "train_st')  # cut mid-record
        assert trace_diff.main([a, tail]) == 0
        capsys.readouterr()
        # damage in the middle = truncated/corrupt capture: exit 2
        recs = open(a).read().splitlines()
        broken = str(tmp_path / "broken.jsonl")
        with open(broken, "w") as fh:
            fh.write(recs[0][: len(recs[0]) // 2] + "\n")
            fh.write("\n".join(recs[1:]) + "\n")
        assert trace_diff.main([a, broken]) == 2
        assert "truncated or corrupt" in capsys.readouterr().err

    def test_binary_garbage_exits_2(self, tmp_path, capsys):
        from deepspeed_tpu.tools import trace_diff

        a = str(tmp_path / "a.jsonl")
        self._good_trace(a)
        bin_path = str(tmp_path / "bin.jsonl")
        with open(bin_path, "wb") as fh:
            fh.write(b"\x80\x81\xfe\xff" * 64)
        assert trace_diff.main([a, bin_path]) == 2
        assert "not a text JSONL trace" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# the hot-path fix itself: the host-built serving PRNG key is bit-identical
# to jax.random.PRNGKey across the whole seed range (incl. the canonicalized
# negative / >= 2**31 cases that fall back to the exact jax path)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "seed", [0, 1, 7, 1234567, 2**31 - 1, 2**31, 2**32, 2**35 + 123, -1]
)
def test_host_prng_key_matches_jax(seed):
    from deepspeed_tpu.serving.scheduler import _host_prng_key

    want = np.asarray(jax.random.PRNGKey(seed))
    assert np.array_equal(_host_prng_key(seed), want), seed
