"""dslint — the static-analysis plane (ISSUE 6 tentpole, ISSUE 8 dsan).

Four engines over one findings/severity/suppression model:

- **Engine A** (``hlo_rules``): program verifiers over post-optimization HLO
  text — replication, buffer donation, precision, collective overlap, and
  executable-count budgets, checked on the already-compiled train/serving
  programs (``DeepSpeedEngine.verify_program()``, ``ServingEngine.verify()``).
- **Engine B** (``ast_rules``): a Python AST lint for JAX footguns — host
  syncs and device-op dispatch in per-step code, tracer branching, missing
  donation, unstable compile-cache keys.
- **Engine C** (``concurrency_rules``): the AST concurrency sanitizer —
  per-module thread/lock/shared-attribute model reporting unlocked shared
  state, lock-order cycles, signal-unsafe handlers, thread leaks and
  blocking calls under locks. Its dynamic half, ``runtime_sanitizer``,
  records REAL lock orders and cross-thread accesses in ``dsan``-marked
  tests and reports through the same Finding stream.
- **Engine D** (``collective_rules``): the HLO collective-consistency
  verifier — channel-id uniqueness, async start/done pairing and FIFO
  order, and cross-program collective-order agreement on shared mesh
  groups (the SPMD desync/deadlock shape).
- **Engine E** (``memory_rules``, ISSUE 9): the static HBM liveness
  verifier — a def-use live-range walk over the scheduled post-opt HLO
  computes peak resident bytes and a categorized live-at-peak ledger,
  gated against committed per-program byte budgets
  (``.dsmem-budgets.json``): over-budget peaks, missed donations,
  oversized collective scratch, layout padding waste.
- **Engine F** (``sharding_rules``, ISSUE 9): the pre-compile sharding-spec
  verifier — ``match_partition_rules``-style regex tables checked against
  real ``jax.eval_shape`` param trees and the mesh: dead rules, rank/axis
  mismatches, silently replicated large leaves.
- **Engine G** (``protocol_rules`` + ``protocol_model``, ISSUE 15): the
  serving-protocol plane. An AST ownership-dataflow lint tracks every
  ``PageAllocator.alloc/retain/free`` through branches, early returns and
  exception paths (page-leak-on-path, double-free, use-after-free,
  refcount-escape, dual-reserve-unbalanced), and a bounded explicit-state
  model checker explores the scheduler's event interleavings against
  refcount-conservation / leak / use-after-free / wedge / dual-reserve
  invariants, emitting minimal counterexample traces that
  ``protocol_model.replay_trace`` confirms on the real ``ServingEngine``.

Front ends: the ``python -m deepspeed_tpu.tools.dslint`` CLI (with the
committed-baseline CI gate, ``--engines a..g`` selection, and ``--sarif``
export) and the ``lint``/``dsan``/``dsmem``-marked tier-1 tests. Engine F
has no file form — it runs where
live param trees exist (``engine.verify_program()``, the dsmem tests). See
``docs/ANALYSIS.md`` for the rule catalog and the suppression / baseline
workflow.
"""

from .ast_rules import (  # noqa: F401
    DEFAULT_DONATE_PATTERNS,
    DEFAULT_HOT_PATTERNS,
    lint_file,
    lint_source,
)
from .ast_rules import RULES as AST_RULES  # noqa: F401
from .baseline import DEFAULT_BASELINE_NAME, Baseline  # noqa: F401
from .collective_rules import (  # noqa: F401
    CollectiveOp,
    extract_collectives,
    verify_collective_text,
    verify_compiled_set,
    verify_program_set,
)
from .collective_rules import RULES as COLLECTIVE_RULES  # noqa: F401
from .concurrency_rules import (  # noqa: F401
    build_model,
    check_file,
    check_source,
)
from .concurrency_rules import RULES as CONCURRENCY_RULES  # noqa: F401
from .findings import (  # noqa: F401
    SEVERITY_ERROR,
    SEVERITY_WARNING,
    Finding,
    SuppressionIndex,
)
from .hlo_rules import (  # noqa: F401
    RuleContext,
    check_program_budget,
    hlo_dtype,
    verify_compiled,
    verify_hlo_text,
)
from .hlo_rules import RULES as HLO_RULES  # noqa: F401
from .memory_rules import (  # noqa: F401
    DEFAULT_BUDGET_NAME,
    MemoryAnalysis,
    MemoryRuleContext,
    analyze_memory_text,
    find_budget_file,
    load_budgets,
    resolve_budget,
    verify_memory_compiled,
    verify_memory_text,
    xla_peak_bytes,
)
from .memory_rules import RULES as MEMORY_RULES  # noqa: F401
from .sharding_rules import (  # noqa: F401
    ShardingRuleContext,
    match_partition_rules,
    verify_spec_table,
    verify_tree_shardings,
)
from .sharding_rules import RULES as SHARDING_RULES  # noqa: F401
from .protocol_model import (  # noqa: F401
    ProtoModelConfig,
    ProtocolMonitor,
    apply_engine_mutation,
    default_model_configs,
    explore,
    model_findings,
    replay_fleet_trace,
    replay_trace,
)
from .protocol_model import MODEL_RULES as PROTOCOL_MODEL_RULES  # noqa: F401
from .protocol_rules import (  # noqa: F401
    check_file as check_protocol_file,
    check_source as check_protocol_source,
)
from .protocol_rules import RULES as PROTOCOL_RULES  # noqa: F401

# engine letter → rule catalog (the CLI's --engines selector)
ENGINE_RULES = {
    "a": HLO_RULES,
    "b": AST_RULES,
    "c": CONCURRENCY_RULES,
    "d": COLLECTIVE_RULES,
    "e": MEMORY_RULES,
    "f": SHARDING_RULES,
    "g": {**PROTOCOL_RULES, **PROTOCOL_MODEL_RULES},
}
ALL_ENGINES = frozenset(ENGINE_RULES)

# HLO text dumps the CLI can verify with Engines A/D without a live engine
HLO_SUFFIXES = (".hlo",)


def all_rules(engines=None):
    """rule id → one-line description for the selected engines (default
    all four)."""
    out = {}
    for letter in sorted(engines or ALL_ENGINES):
        out.update(ENGINE_RULES[letter])
    return out


def lint_paths(paths, hot_patterns=None, donate_patterns=None, engines=None):
    """Lint files under ``paths`` (files or directories) →
    (findings, suppressed_count, files_scanned).

    ``*.py`` files go through the source engines (B and/or C per
    ``engines``); ``*.hlo`` text dumps go through the program engines (A
    with a default declaration context, D — including the cross-program
    order-divergence check over every dump in the run — and E, whose
    budget gate resolves the dump's program name against the nearest
    committed ``.dsmem-budgets.json``). Engine F needs a live param tree
    and has no file form.

    Unparseable files surface as SyntaxError, bogus path arguments as
    ValueError — callers decide whether that is fatal (the CLI reports
    both as usage-class errors; a typo'd path must NOT make the CI gate
    pass vacuously by scanning nothing)."""
    import os

    engines = frozenset(engines or ALL_ENGINES)
    py_files, hlo_files = [], []

    def _route(f):
        if f.endswith(".py"):
            py_files.append(f)
        elif f.endswith(HLO_SUFFIXES):
            hlo_files.append(f)

    for p in paths:
        if os.path.isdir(p):
            for root, dirs, names in os.walk(p):
                dirs[:] = sorted(
                    d for d in dirs
                    if d not in ("__pycache__", ".git", ".pytest_cache")
                )
                for n in sorted(names):
                    _route(os.path.join(root, n))
        elif os.path.exists(p) and (
            p.endswith(".py") or p.endswith(HLO_SUFFIXES)
        ):
            _route(p)
        else:
            raise ValueError(
                f"dslint path {p!r} is not a directory or an existing "
                ".py/.hlo file"
            )
    findings, suppressed = [], 0
    for f in py_files:
        if "b" in engines:
            got, waived = lint_file(
                f, hot_patterns=hot_patterns, donate_patterns=donate_patterns
            )
            findings.extend(got)
            suppressed += waived
        if "c" in engines:
            got, waived = check_file(f)
            findings.extend(got)
            suppressed += waived
        if "g" in engines:
            got, waived = check_protocol_file(f)
            findings.extend(got)
            suppressed += waived
    if "g" in engines and any(
        os.path.basename(os.path.dirname(os.path.abspath(f))) == "serving"
        for f in py_files
    ):
        # the model checker has no per-file form: it verifies the serving
        # protocol itself, so it joins any scan that covers serving/
        for cfg in default_model_configs().values():
            findings.extend(model_findings(explore(cfg)))
    hlo_texts = {}
    for f in hlo_files:
        with open(f, encoding="utf-8") as fh:
            hlo_texts[f] = fh.read()

    if "e" in engines and hlo_texts:
        # Engine E gates each dump's program name against the nearest
        # committed ledger (resolved upward from the dump itself, so a
        # dump in another checkout meets THAT repo's budgets); everything
        # else in the context stays at defaults
        class _DumpBudgetCfg:
            budgets = {}
            budget_file = ""
            default_budget_bytes = 0

    for f, txt in hlo_texts.items():
        program = os.path.splitext(os.path.basename(f))[0]
        if "a" in engines:
            got = verify_hlo_text(txt, RuleContext(program=program))
            for x in got:
                x.path = f  # real file provenance beats hlo://<program>
            findings.extend(got)
        if "d" in engines:
            got = verify_collective_text(txt, program)
            for x in got:
                x.path = f
            findings.extend(got)
        if "e" in engines:
            ectx = MemoryRuleContext(
                program=program,
                budget_bytes=resolve_budget(
                    _DumpBudgetCfg, program, search_from=f
                ),
            )
            got, _ = verify_memory_text(txt, ectx)
            for x in got:
                x.path = f
            findings.extend(got)
    if "d" in engines and len(hlo_texts) > 1:
        # program name = basename when unique; colliding basenames (e.g.
        # runA/step.hlo vs runB/step.hlo — the natural two-run compare)
        # keep their full paths so neither dump silently shadows the other
        short = {}
        for f in hlo_texts:
            short.setdefault(
                os.path.splitext(os.path.basename(f))[0], []
            ).append(f)
        by_program = {
            (name if len(files) == 1 else f): hlo_texts[f]
            for name, files in short.items() for f in files
        }
        from .collective_rules import (
            extract_collectives as _ext,
            rule_order_divergence as _div,
        )

        findings.extend(_div({p: _ext(t) for p, t in by_program.items()}))
    return findings, suppressed, py_files + hlo_files
