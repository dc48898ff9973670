"""ISSUE 15: Engine G (dsproto) — serving-protocol model checker +
page-ownership lint.

The acceptance pins:

- every lint rule fires on a minimal synthetic defect and stays silent on
  the matching correct idiom (guard-empty frees, rollback-by-concat,
  suppressions);
- the real serving sources carry ZERO Engine G findings (the disaggregated
  ``_admit`` exception paths were fixed in this PR);
- mutation self-test: deleting the drain path's free and skipping the COW
  fork each turn the gate red statically (lint) AND in the model checker,
  whose counterexample replays red on the real engine;
- the bounded model checker explores the shared and disaggregated
  protocols completely with zero violations, and each seeded mutation
  yields a minimal counterexample trace;
- lockstep fuzz: random op sequences against ``PageAllocator`` +
  ``PrefixCache`` and a mirror accounting model agree at every step and
  pass ``check_no_leaks`` at quiescence;
- the dslint CLI honors ``--engines g`` with the 0/1/2 exit contract,
  refuses ``--update-baseline`` on engine subsets, and ``--sarif`` writes
  one SARIF 2.1.0 run per engine;
- ``ServingEngine.verify()`` runs Engine G clean with speculative + prefix
  sharing + chunked prefill + int8 + TP=2 + disaggregation all on.
"""

import json
import os
import warnings

import jax
import numpy as np
import pytest

warnings.filterwarnings("ignore")

pytestmark = pytest.mark.lint

needs_8_devices = pytest.mark.skipif(
    jax.device_count() < 4, reason="needs the forced 8-device CPU mesh"
)

REPO = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
SCHEDULER = os.path.join(REPO, "deepspeed_tpu", "serving", "scheduler.py")
SERVING_DIR = os.path.join(REPO, "deepspeed_tpu", "serving")


def _lint(src):
    from deepspeed_tpu.analysis.protocol_rules import check_source

    findings, suppressed = check_source(src, "t.py")
    return findings, suppressed


def _rules(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------------------
# pass 1: the ownership-dataflow lint, rule by rule
# ---------------------------------------------------------------------------

class TestOwnershipLint:
    def test_leak_on_early_return(self):
        src = (
            "class S:\n"
            "    def f(self, n):\n"
            "        pages = self.allocator.alloc(n)\n"
            "        if n > 4:\n"
            "            return None\n"
            "        self.allocator.free(pages)\n"
        )
        findings, _ = _lint(src)
        assert _rules(findings) == ["page-leak-on-path"]
        assert findings[0].symbol == "S.f"

    def test_leak_on_exception_edge(self):
        src = (
            "class S:\n"
            "    def f(self, n):\n"
            "        held = self.allocator.alloc(n)\n"
            "        more = self.allocator.alloc(n)\n"   # raising edge drops held
            "        self.allocator.free(held)\n"
            "        self.allocator.free(more)\n"
        )
        findings, _ = _lint(src)
        assert "page-leak-on-path" in _rules(findings)

    def test_handler_cover_accepts_rollback(self):
        src = (
            "class S:\n"
            "    def f(self, i, n):\n"
            "        held = self.allocator.alloc(n)\n"
            "        try:\n"
            "            self.table.assign(i, held)\n"
            "        except Exception:\n"
            "            self.allocator.free(held)\n"
            "            raise\n"
            "        self.allocator.free(held)\n"
        )
        findings, _ = _lint(src)
        assert findings == []

    def test_handler_cover_sees_through_concat(self):
        # the _admit rollback idiom: shared pages retained up front, the
        # dual reservation inside a try whose handler frees ONE
        # concatenation covering everything acquired so far
        src = (
            "class S:\n"
            "    def f(self, n):\n"
            "        shared = self.index_pages(n)\n"
            "        if shared:\n"
            "            self.allocator.retain(shared)\n"
            "        p_priv = []\n"
            "        try:\n"
            "            p_priv = self.allocator.alloc(n)\n"
            "            pages = self.allocator.alloc(n)\n"
            "        except Exception:\n"
            "            rollback = p_priv + shared\n"
            "            if rollback:\n"
            "                self.allocator.free(rollback)\n"
            "            return None\n"
            "        self.slot.prefill_pages = shared + p_priv\n"
            "        self.slot.pages = pages\n"
            "        return pages\n"
        )
        findings, _ = _lint(src)
        assert findings == [], [f.render() for f in findings]

    def test_guard_empty_idiom(self):
        src = (
            "class S:\n"
            "    def f(self, n):\n"
            "        pages = self.allocator.alloc(n)\n"
            "        if pages:\n"
            "            self.allocator.free(pages)\n"
            "        return None\n"
        )
        findings, _ = _lint(src)
        assert findings == []

    def test_double_free(self):
        src = (
            "class S:\n"
            "    def f(self, n):\n"
            "        pages = self.allocator.alloc(n)\n"
            "        self.allocator.free(pages)\n"
            "        self.allocator.free(pages)\n"
        )
        findings, _ = _lint(src)
        assert "double-free" in _rules(findings)

    def test_use_after_free(self):
        src = (
            "class S:\n"
            "    def f(self, i, n):\n"
            "        pages = self.allocator.alloc(n)\n"
            "        self.allocator.free(pages)\n"
            "        self.table.assign(i, pages)\n"
        )
        findings, _ = _lint(src)
        assert "use-after-free" in _rules(findings)

    def test_refcount_escape_cow_taint(self):
        src = (
            "class S:\n"
            "    def release(self, pages):\n"
            "        self.allocator.free(pages)\n"
            "\n"
            "    def f(self, slot, prompt):\n"
            "        shared, tokens, cow = self.prefix_cache.lookup(prompt)\n"
            "        if cow is not None:\n"
            "            slot.pages = shared + [cow]\n"
            "        return tokens\n"
        )
        findings, _ = _lint(src)
        assert _rules(findings) == ["refcount-escape"]

    def test_cow_fork_is_clean(self):
        # the correct idiom: the cow page is only counted, never mapped
        src = (
            "class S:\n"
            "    def f(self, slot, prompt, n):\n"
            "        shared, tokens, cow = self.prefix_cache.lookup(prompt)\n"
            "        if cow is not None:\n"
            "            self.allocator.cow_forks_total += 1\n"
            "        slot.pages = shared + self.allocator.alloc(n)\n"
            "        return tokens\n"
        )
        findings, _ = _lint(src)
        assert findings == []

    def test_dual_reserve_unbalanced(self):
        src = (
            "class S:\n"
            "    def f(self, i):\n"
            "        slot = self.slots[i]\n"
            "        self.allocator.free(slot.pages)\n"
            "        if slot.prefill_pages:\n"
            "            pass\n"   # forgot the prefill-side free
            "        self.slots[i] = object()\n"
        )
        findings, _ = _lint(src)
        assert "dual-reserve-unbalanced" in _rules(findings)

    def test_balanced_teardown_clean(self):
        src = (
            "class S:\n"
            "    def f(self, i):\n"
            "        slot = self.slots[i]\n"
            "        self.allocator.free(slot.pages)\n"
            "        if slot.prefill_pages:\n"
            "            self.prefill_set.allocator.free(slot.prefill_pages)\n"
            "        self.slots[i] = object()\n"
        )
        findings, _ = _lint(src)
        assert findings == []

    def test_suppression_waives(self):
        src = (
            "class S:\n"
            "    def f(self, n):\n"
            "        pages = self.allocator.alloc(n)  "
            "# dslint: disable=page-leak-on-path\n"
            "        return None\n"
        )
        findings, suppressed = _lint(src)
        assert findings == []
        assert suppressed == 1

    def test_non_allocator_files_skip_fast(self):
        findings, suppressed = _lint("x = 1\n")
        assert findings == [] and suppressed == 0


class TestServingSourcesClean:
    def test_zero_findings_under_serving(self):
        from deepspeed_tpu.analysis.protocol_rules import check_file

        total = []
        for fname in sorted(os.listdir(SERVING_DIR)):
            if fname.endswith(".py"):
                got, _ = check_file(os.path.join(SERVING_DIR, fname))
                total.extend(got)
        assert total == [], [f.render() for f in total]


# ---------------------------------------------------------------------------
# mutation self-test, static half: the lint goes red
# ---------------------------------------------------------------------------

MUT_DRAIN_FREE = ("        self.allocator.free(slot.pages)\n", "")
MUT_SKIP_COW = (
    "            if cow_page is not None:\n"
    "                self.prefill_set.allocator.cow_forks_total += 1",
    "            if cow_page is not None:\n"
    "                self.prefill_set.allocator.retain([cow_page])\n"
    "                shared = shared + [cow_page]\n"
    "                self.prefill_set.allocator.cow_forks_total += 1",
)


class TestLintMutationSelfTest:
    def _mutate(self, old, new):
        with open(SCHEDULER, encoding="utf-8") as fh:
            src = fh.read()
        assert old in src, "mutation anchor drifted — update the self-test"
        return src.replace(old, new, 1)

    def test_dropped_drain_free_goes_red(self):
        from deepspeed_tpu.analysis.protocol_rules import check_source

        src = self._mutate(*MUT_DRAIN_FREE)
        findings, _ = check_source(src, SCHEDULER)
        assert "dual-reserve-unbalanced" in _rules(findings)
        # the one place a residency's pages go back since ISSUE 54 (_finish_slot, _fail_slot, release_slot call it)
        assert any(f.symbol.endswith("_vacate") for f in findings)

    def test_skipped_cow_fork_goes_red(self):
        from deepspeed_tpu.analysis.protocol_rules import check_source

        src = self._mutate(*MUT_SKIP_COW)
        findings, _ = check_source(src, SCHEDULER)
        assert "refcount-escape" in _rules(findings)
        assert any(f.symbol.endswith("_admit") for f in findings)


# ---------------------------------------------------------------------------
# pass 2: the bounded model checker
# ---------------------------------------------------------------------------

class TestModelChecker:
    def test_clean_protocol_shared_and_disagg(self):
        from deepspeed_tpu.analysis.protocol_model import (
            default_model_configs,
            explore,
        )

        for name, cfg in default_model_configs().items():
            rep = explore(cfg)
            assert rep.complete, name
            assert rep.violations == [], (name, rep.violations)
            assert rep.states > 500, name   # genuinely explored, not pruned

    @pytest.mark.parametrize(
        "mutation,disagg,rule",
        [
            ("drop-drain-free", False, "proto-page-leak"),
            ("skip-cow-fork", False, "proto-write-shared-page"),
            ("skip-cow-fork", True, "proto-write-shared-page"),
            ("drop-handoff-free", True, "proto-dual-reserve"),
            ("double-free-finish", False, "proto-refcount-conservation"),
            ("decode-after-free", False, "proto-use-after-free"),
            ("skip-queue-drain", False, "proto-request-wedged"),
        ],
    )
    def test_mutation_counterexamples(self, mutation, disagg, rule):
        from deepspeed_tpu.analysis.protocol_model import (
            ProtoModelConfig,
            explore,
        )

        rep = explore(ProtoModelConfig(
            disaggregated=disagg, mutations=frozenset({mutation})
        ))
        hit = [v for v in rep.violations if v.rule == rule]
        assert hit, (mutation, [v.rule for v in rep.violations])
        trace = hit[0].trace
        assert trace and trace[0].startswith("submit"), trace
        # BFS minimality: the leak counterexample is the 4-event preempt path
        if mutation == "drop-drain-free":
            assert len(trace) == 4, trace

    def test_model_findings_shape(self):
        from deepspeed_tpu.analysis.protocol_model import (
            ProtoModelConfig,
            explore,
            model_findings,
        )

        rep = explore(ProtoModelConfig(
            mutations=frozenset({"drop-drain-free"})
        ))
        fs = model_findings(rep)
        assert fs and all(f.engine == "protocol" for f in fs)
        assert all(f.path.startswith("model://serving") for f in fs)
        assert any("counterexample: submit" in f.message for f in fs)

    def test_unknown_mutation_rejected(self):
        from deepspeed_tpu.analysis.protocol_model import ProtoModelConfig

        with pytest.raises(ValueError):
            ProtoModelConfig(mutations=frozenset({"not-a-mutation"}))

    def test_state_bound_truncates_not_fires(self):
        from deepspeed_tpu.analysis.protocol_model import (
            ProtoModelConfig,
            explore,
        )

        rep = explore(ProtoModelConfig(max_states=50))
        assert not rep.complete
        assert rep.violations == []


# ---------------------------------------------------------------------------
# counterexample replay on the real engine (mutation self-test, dynamic half)
# ---------------------------------------------------------------------------

SCFG_SMALL = {
    "max_slots": 2, "page_size": 4, "num_pages": 32,
    "max_prompt_len": 8, "max_new_tokens": 4,
    "prefix_cache": {"enabled": True}, "prefill_chunk_tokens": 4,
}


@pytest.fixture(scope="module")
def tiny_cfg():
    from deepspeed_tpu.models import gpt2

    return gpt2.get_config("gpt2-tiny", attn_impl="jnp")


@pytest.fixture(scope="module")
def inference_engine(tiny_cfg):
    import jax.numpy as jnp

    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.models import gpt2

    params = gpt2.init_params(tiny_cfg, jax.random.PRNGKey(0))
    return InferenceEngine(
        gpt2.make_module(tiny_cfg), params=params, dtype=jnp.float32
    )


@pytest.fixture()
def prompt(tiny_cfg):
    rs = np.random.RandomState(0)
    return rs.randint(0, tiny_cfg.vocab_size, (8,)).astype(np.int32)


def _drive_two(srv, mon, prompt):
    h1 = srv.submit(prompt, max_new_tokens=2, seed=1)
    for _ in range(20):
        srv.step()
        mon.check_step()
        if h1.status not in ("queued", "running"):
            break
    h2 = srv.submit(prompt.copy(), max_new_tokens=2, seed=2)
    for _ in range(20):
        srv.step()
        mon.check_step()
        if h2.status not in ("queued", "running"):
            break


class TestReplayOnRealEngine:
    def test_drain_free_counterexample_replays_red(
        self, inference_engine, prompt
    ):
        from deepspeed_tpu.analysis.protocol_model import (
            ProtoModelConfig,
            apply_engine_mutation,
            explore,
            replay_trace,
        )

        rep = explore(ProtoModelConfig(
            mutations=frozenset({"drop-drain-free"})
        ))
        trace = [
            v for v in rep.violations if v.rule == "proto-page-leak"
        ][0].trace
        prompts = [prompt, prompt.copy()]

        srv = inference_engine.serve(SCFG_SMALL)
        clean = replay_trace(srv, trace, prompts, max_new_tokens=2)
        assert clean["ok"], clean["violations"]

        srv2 = inference_engine.serve(SCFG_SMALL)
        undo = apply_engine_mutation(srv2, "drop-drain-free")
        try:
            red = replay_trace(srv2, trace, prompts, max_new_tokens=2)
        finally:
            undo()
        assert not red["ok"]
        assert any(
            "proto-page-leak" in v for v in red["violations"]
        ), red["violations"]

    def test_cow_fork_mutation_monitor_red(self, inference_engine, prompt):
        from deepspeed_tpu.analysis.protocol_model import (
            ProtocolMonitor,
            apply_engine_mutation,
        )

        srv = inference_engine.serve(SCFG_SMALL)
        undo = apply_engine_mutation(srv, "skip-cow-fork")
        mon = ProtocolMonitor(srv)
        try:
            _drive_two(srv, mon, prompt)
        finally:
            undo()
            mon.uninstall()
        assert any(
            "proto-write-shared-page" in v for v in mon.violations
        ), mon.violations

    def test_clean_engine_monitor_green(self, inference_engine, prompt):
        from deepspeed_tpu.analysis.protocol_model import ProtocolMonitor

        srv = inference_engine.serve(SCFG_SMALL)
        mon = ProtocolMonitor(srv)
        _drive_two(srv, mon, prompt)
        srv.drain(deadline_s=5.0)
        mon.check_quiescent()
        mon.uninstall()
        assert mon.violations == []


# ---------------------------------------------------------------------------
# lockstep fuzz: real allocator/prefix-cache vs mirror accounting
# ---------------------------------------------------------------------------

class _MirrorAllocator:
    """Reference accounting model: refcounts as a plain dict."""

    def __init__(self, num_pages):
        self.capacity = num_pages - 1
        self.refs = {}
        self.free_count = self.capacity

    def alloc(self, n):
        assert n <= self.free_count
        self.free_count -= n

    def retain(self, pages):
        for p in pages:
            self.refs[p] = self.refs.get(p, 1) + 1

    def free(self, pages):
        for p in pages:
            c = self.refs.get(p, 1) - 1
            if c == 0:
                self.refs.pop(p, None)
                self.free_count += 1
            else:
                self.refs[p] = c

    def bind(self, pages):
        for p in pages:
            self.refs[p] = 1


class TestLockstepFuzz:
    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    def test_allocator_lockstep(self, seed):
        from deepspeed_tpu.serving.kv_cache import (
            PageAllocator,
            PageAllocatorError,
        )

        from deepspeed_tpu.telemetry.kv_heat import KVHeatLedger

        rs = np.random.RandomState(seed)
        alloc = PageAllocator(num_pages=17)
        mirror = _MirrorAllocator(17)
        # ISSUE 16 lockstep acceptance: a sink-less heat ledger rides the
        # allocator hooks and must reconcile bit-exact at EVERY op
        led = KVHeatLedger("fuzz", alloc.capacity)
        alloc.heat = led
        held = []   # flat list of held page ids (one entry per reference)
        for _ in range(300):
            op = rs.randint(4)
            if op == 0:  # alloc
                n = int(rs.randint(1, 4))
                if n <= alloc.free_pages:
                    got = alloc.alloc(n)
                    mirror.alloc(n)
                    mirror.bind(got)
                    held.extend(got)
                else:
                    with pytest.raises(PageAllocatorError):
                        alloc.alloc(n)
            elif op == 1 and held:  # retain a random held page
                p = held[int(rs.randint(len(held)))]
                alloc.retain([p])
                mirror.retain([p])
                held.append(p)
            elif op == 2 and held:  # free a random reference
                i = int(rs.randint(len(held)))
                p = held.pop(i)
                alloc.free([p])
                mirror.free([p])
            elif op == 3:  # illegal op must not corrupt state
                with pytest.raises(PageAllocatorError):
                    alloc.free([alloc.num_pages + 5])
            assert alloc.check_consistent() is None
            assert alloc.free_pages == mirror.free_count
            assert dict(alloc._refs) == mirror.refs
            assert led.reconcile(alloc) is None
        alloc.free(held)
        alloc.check_no_leaks()
        assert led.reconcile(alloc) is None and led.pages_in_use == 0

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_prefix_cache_lockstep(self, seed):
        from deepspeed_tpu.serving.kv_cache import PageAllocator, PrefixCache

        from deepspeed_tpu.telemetry.kv_heat import KVHeatLedger

        rs = np.random.RandomState(seed)
        page = 2
        alloc = PageAllocator(num_pages=33)
        cache = PrefixCache(alloc, page_size=page, max_pages=12)
        led = KVHeatLedger("fuzz", alloc.capacity)
        alloc.heat = led
        cache.heat = led
        live = []   # (pages, n_shared) per simulated in-flight request
        for _ in range(150):
            op = rs.randint(3)
            if op == 0 and alloc.free_pages >= 8:  # admit + insert
                plen = int(rs.randint(1, 5)) * page   # aligned prompts
                prompt = rs.randint(0, 3, (plen,)).astype(np.int32)
                shared, s_tokens, cow = cache.lookup(prompt)
                if shared:
                    alloc.retain(shared)
                total = plen // page + 1
                priv = alloc.alloc(total - len(shared))
                pages = shared + priv
                cache.insert(prompt, pages[: plen // page])
                live.append(pages)
            elif op == 1 and live:  # finish a request
                pages = live.pop(int(rs.randint(len(live))))
                alloc.free(pages)
            elif op == 2:  # pool-pressure eviction
                cache.evict(need_free=int(rs.randint(0, 4)))
            assert alloc.check_consistent() is None, alloc.check_consistent()
            # conservation: free + in-use partitions the pool exactly
            assert alloc.free_pages + alloc.pages_in_use == alloc.capacity
            # every index-held page is alive with at least its index ref
            for p in cache.held_pages:
                assert alloc.refcount(p) >= 1
            # ISSUE 16: the heat ledger's mirror (refcounts + prefix-held
            # set) reconciles bit-exact after every op
            assert led.reconcile(alloc, cache) is None
        for pages in live:
            alloc.free(pages)
        held = cache.held_pages
        alloc.check_no_leaks(allowed=held)
        cache.clear()
        alloc.check_no_leaks()

    @pytest.mark.parametrize("seed", [0, 1])
    def test_abstract_model_lockstep(self, seed):
        """Random event walks through the abstract transition relation keep
        the conservation invariant (the same one the live allocator's
        ``check_consistent`` enforces) at every step."""
        from deepspeed_tpu.analysis.protocol_model import (
            ProtoModelConfig,
            _apply,
            _check_state,
            _enabled,
            _initial,
        )

        rs = np.random.RandomState(seed)
        for disagg in (False, True):
            cfg = ProtoModelConfig(disaggregated=disagg, requests=3,
                                   slots=2)
            st = _initial(cfg)
            for _ in range(200):
                evs = _enabled(cfg, st)
                if not evs:
                    break
                st, vio = _apply(cfg, st, evs[int(rs.randint(len(evs)))])
                assert vio is None
                assert _check_state(cfg, st) is None


# ---------------------------------------------------------------------------
# CLI: --engines g exit contract, --sarif, --update-baseline refusal
# ---------------------------------------------------------------------------

class TestDslintCLI:
    def test_engines_g_clean_exit_0(self, capsys):
        from deepspeed_tpu.tools.dslint import main

        rc = main([SERVING_DIR, "--engines", "g", "--no-baseline"])
        assert rc == 0, capsys.readouterr().out

    def test_engines_g_findings_exit_1(self, tmp_path, capsys):
        from deepspeed_tpu.tools.dslint import main

        bad = tmp_path / "leaky.py"
        bad.write_text(
            "class S:\n"
            "    def f(self, n):\n"
            "        pages = self.allocator.alloc(n)\n"
            "        return None\n"
        )
        rc = main([str(bad), "--engines", "g", "--no-baseline"])
        assert rc == 1
        assert "page-leak-on-path" in capsys.readouterr().out

    def test_unknown_engine_exit_2(self, capsys):
        from deepspeed_tpu.tools.dslint import main

        rc = main([SERVING_DIR, "--engines", "z"])
        assert rc == 2

    def test_update_baseline_refuses_subset(self, capsys):
        from deepspeed_tpu.tools.dslint import main

        rc = main([SERVING_DIR, "--engines", "g", "--update-baseline"])
        assert rc == 2
        assert "full engine set" in capsys.readouterr().err

    def test_list_rules_includes_g(self, capsys):
        from deepspeed_tpu.tools.dslint import main

        rc = main(["--engines", "g", "--list-rules"])
        assert rc == 0
        out = capsys.readouterr().out
        for rule in ("page-leak-on-path", "refcount-escape",
                     "proto-page-leak", "proto-request-wedged"):
            assert rule in out

    def test_sarif_output(self, tmp_path, capsys):
        from deepspeed_tpu.tools.dslint import main

        bad = tmp_path / "leaky.py"
        bad.write_text(
            "class S:\n"
            "    def f(self, n):\n"
            "        pages = self.allocator.alloc(n)\n"
            "        return None\n"
        )
        out = tmp_path / "report.sarif"
        rc = main([str(bad), "--engines", "b,c,g", "--no-baseline",
                   "--sarif", str(out)])
        assert rc == 1
        doc = json.loads(out.read_text())
        assert doc["version"] == "2.1.0"
        assert "sarif-schema-2.1.0" in doc["$schema"]
        # one run per selected engine, even the clean ones
        names = [r["tool"]["driver"]["name"] for r in doc["runs"]]
        assert names == ["dslint-b", "dslint-c", "dslint-g"]
        g_run = doc["runs"][2]
        assert any(
            r["id"] == "page-leak-on-path"
            for r in g_run["tool"]["driver"]["rules"]
        )
        results = g_run["results"]
        assert len(results) == 1
        res = results[0]
        assert res["ruleId"] == "page-leak-on-path"
        assert res["level"] == "error"
        assert res["baselineState"] == "new"
        assert res["partialFingerprints"]["dslintFingerprint"]
        loc = res["locations"][0]["physicalLocation"]
        assert loc["artifactLocation"]["uri"].endswith("leaky.py")
        assert loc["region"]["startLine"] == 3

    def test_sarif_baselined_marked_unchanged(self, tmp_path):
        from deepspeed_tpu.tools.dslint import main

        bad = tmp_path / "leaky.py"
        bad.write_text(
            "class S:\n"
            "    def f(self, n):\n"
            "        pages = self.allocator.alloc(n)\n"
            "        return None\n"
        )
        # record the finding, then re-run against the fresh baseline
        bl = tmp_path / ".dslint-baseline.json"
        rc = main([str(bad), "--baseline", str(bl), "--update-baseline"])
        assert rc == 0
        out = tmp_path / "report.sarif"
        rc = main([str(bad), "--baseline", str(bl), "--sarif", str(out)])
        assert rc == 0
        doc = json.loads(out.read_text())
        states = [
            r["baselineState"] for run in doc["runs"]
            for r in run["results"]
        ]
        assert states and set(states) == {"unchanged"}


# ---------------------------------------------------------------------------
# config plumbing + the everything-on verify() gate
# ---------------------------------------------------------------------------

class TestProtocolConfig:
    def test_defaults_and_from_dict(self):
        from deepspeed_tpu.runtime.config import AnalysisConfig

        acfg = AnalysisConfig.from_dict({
            "protocol": {"max_states": 5000, "requests": 3, "model": False}
        })
        assert acfg.protocol.enabled
        assert acfg.protocol.max_states == 5000
        assert acfg.protocol.requests == 3
        assert not acfg.protocol.model

    def test_validation(self):
        from deepspeed_tpu.runtime.config import (
            DeepSpeedConfigError,
            ProtocolAnalysisConfig,
        )

        with pytest.raises(DeepSpeedConfigError):
            ProtocolAnalysisConfig(requests=0)
        with pytest.raises(DeepSpeedConfigError):
            ProtocolAnalysisConfig(retry_max=-1)

    def test_allocator_consistency_in_check_no_leaks(self):
        from deepspeed_tpu.serving.kv_cache import (
            PageAllocator,
            PageAllocatorError,
        )

        alloc = PageAllocator(num_pages=8)
        pages = alloc.alloc(3)
        assert alloc.check_consistent() is None
        # corrupt the free list behind the allocator's back
        alloc._free.append(pages[0])
        assert "both free and in use" in alloc.check_consistent()
        with pytest.raises(PageAllocatorError):
            alloc.check_no_leaks()


@pytest.mark.serving
class TestVerifyEngineG:
    @needs_8_devices
    def test_verify_clean_everything_on(self, inference_engine):
        srv = inference_engine.serve({
            "max_slots": 4, "page_size": 4, "num_pages": 64,
            "max_prompt_len": 12, "max_new_tokens": 8,
            "speculative": {"enabled": True, "k": 3},
            "prefix_cache": {"enabled": True},
            "prefill_chunk_tokens": 8,
            "kv_cache_dtype": "int8",
            "placement": {"tp": 2, "disaggregate": True},
        })
        findings = srv.verify()
        assert findings == [], [f.render() for f in findings]

    def test_verify_engine_g_catches_model_mutation(
        self, inference_engine, monkeypatch
    ):
        # force a mutation into the model bounds the verify() pass uses:
        # the gate must surface the counterexample as a Finding
        from deepspeed_tpu.analysis import protocol_model as dsproto

        orig = dsproto.explore

        def mutated_explore(cfg):
            return orig(dsproto.ProtoModelConfig(
                requests=cfg.requests, slots=cfg.slots,
                prompt_pages=cfg.prompt_pages, new_tokens=cfg.new_tokens,
                disaggregated=cfg.disaggregated,
                prefix_cache=cfg.prefix_cache, retry_max=cfg.retry_max,
                mutations=frozenset({"drop-drain-free"}),
                max_states=cfg.max_states,
            ))

        monkeypatch.setattr(dsproto, "explore", mutated_explore)
        srv = inference_engine.serve(SCFG_SMALL)
        findings = srv.verify()
        assert any(f.rule == "proto-page-leak" for f in findings)
