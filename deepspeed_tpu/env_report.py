"""``ds_report`` — environment and op-compatibility report.

Analog of reference ``deepspeed/env_report.py`` (140 LoC): versions, device
inventory, native-op build/compat table, and which planes and config sections
this build has. Everything printed is observed in the running process (and,
for the two gate ledgers, found from the working directory); no record of an
earlier run is read. Measured numbers live in ``PERF.md``.

    python -m deepspeed_tpu.env_report
"""

from __future__ import annotations

import sys


GREEN_OK = "\033[92m[OKAY]\033[0m"
RED_NO = "\033[93m[NO]\033[0m"


def _ops() -> None:
    from deepspeed_tpu.ops.op_builder import op_report

    print("-" * 60)
    print("DeepSpeed-TPU C++/native op report")
    print("-" * 60)
    print(f"{'op name':<20} {'compatible':<12} {'built':<8}")
    for name, compat, built in op_report():
        print(f"{name:<20} {GREEN_OK if compat else RED_NO:<21} {GREEN_OK if built else RED_NO}")
    print("-" * 60)


def _environment() -> None:
    import jax

    import deepspeed_tpu

    print("General environment:")
    print(f"deepspeed_tpu ....... {deepspeed_tpu.__version__}")
    print(f"python .............. {sys.version.split()[0]}")
    print(f"jax ................. {jax.__version__}")
    try:
        import jaxlib

        print(f"jaxlib .............. {jaxlib.__version__}")
    except Exception:
        pass
    try:
        import flax

        print(f"flax ................ {flax.__version__}")
    except Exception:
        pass
    try:
        import optax

        print(f"optax ............... {optax.__version__}")
    except Exception:
        pass
    try:
        import orbax.checkpoint as ocp

        print(f"orbax-checkpoint .... {getattr(ocp, '__version__', 'present')}")
    except Exception:
        pass
    print(f"backend ............. {jax.default_backend()}")
    devs = jax.devices()
    print(f"devices ............. {len(devs)} x {devs[0].device_kind if devs else '-'}")
    print(f"process count ....... {jax.process_count()}")
    print("-" * 60)


def _telemetry() -> None:
    import jax

    print("Telemetry:")
    devs = jax.devices()
    try:
        import jax.profiler  # noqa: F401

        print(f"jax.profiler ........ {GREEN_OK} (watchdog auto-capture available)")
    except Exception:
        print(f"jax.profiler ........ {RED_NO} (watchdog captures disabled)")
    try:
        from deepspeed_tpu.telemetry.introspect import chip_peak

        peak = chip_peak(devs[0].device_kind if devs else None)
        note = "" if peak.source == "table" else f" ({peak.source} — nominal numbers)"
        print(
            f"peak table .......... {peak.device_kind}: "
            f"{peak.peak_flops / 1e12:.1f} TFLOP/s, "
            f"{peak.hbm_bytes_per_s / 1e9:.0f} GB/s HBM{note}"
        )
    except Exception as e:
        print(f"peak table .......... {RED_NO} ({type(e).__name__})")
    try:
        from deepspeed_tpu.telemetry.watchdog import AnomalyWatchdog  # noqa: F401

        print(
            f"anomaly watchdog .... {GREEN_OK} "
            "(telemetry.watchdog — disabled by default; policy continue|kill)"
        )
    except Exception:
        print(f"anomaly watchdog .... {RED_NO}")
    print(
        "run diff ............ python -m deepspeed_tpu.tools.trace_diff "
        "A.jsonl B.jsonl"
    )
    print("-" * 60)


def _analysis() -> None:
    print("Static analysis (dslint):")
    try:
        from deepspeed_tpu.analysis import (
            AST_RULES,
            COLLECTIVE_RULES,
            CONCURRENCY_RULES,
            HLO_RULES,
            Baseline,
        )
        from deepspeed_tpu.analysis import runtime_sanitizer as _dsan
        from deepspeed_tpu.tools.dslint import _find_baseline

        from deepspeed_tpu.analysis import (
            MEMORY_RULES,
            PROTOCOL_MODEL_RULES,
            PROTOCOL_RULES,
            SHARDING_RULES,
        )

        print(
            f"engines ............. {GREEN_OK} "
            f"A:HLO ({len(HLO_RULES)}) + B:AST ({len(AST_RULES)}) + "
            f"C:concurrency ({len(CONCURRENCY_RULES)}) + "
            f"D:collective ({len(COLLECTIVE_RULES)}) + "
            f"E:memory ({len(MEMORY_RULES)}) + "
            f"F:sharding ({len(SHARDING_RULES)}) + "
            f"G:protocol ({len(PROTOCOL_RULES) + len(PROTOCOL_MODEL_RULES)}) "
            "rules"
        )
        san = _dsan.active()
        print(
            "runtime sanitizer ... "
            + (
                f"{GREEN_OK} ACTIVE ({san.events} events recorded)"
                if san is not None
                else f"{GREEN_OK} available (off — enable via "
                "analysis.sanitizer or dsan-marked tests)"
            )
        )
        bl_path = _find_baseline(["deepspeed_tpu"])
        if bl_path:
            print(
                f"baseline ............ {bl_path}: "
                f"{len(Baseline.load(bl_path))} accepted finding(s)"
            )
        else:
            print("baseline ............ none (every finding fails)")
        print(
            "run lint ............ python -m deepspeed_tpu.tools.dslint "
            "deepspeed_tpu/ (program rules: engine.verify_program / "
            "ServingEngine.verify)"
        )
    except Exception as e:
        print(f"analysis ............ {RED_NO} ({type(e).__name__}: {e})")
    print("-" * 60)


def _memory() -> None:
    print("Memory (dsmem):")
    try:
        from deepspeed_tpu.analysis import (
            MEMORY_RULES,
            SHARDING_RULES,
            find_budget_file,
            load_budgets,
        )

        print(
            f"engine E/F rules .... {GREEN_OK} "
            f"{len(MEMORY_RULES)} memory (hbm-over-budget, "
            f"donation-missed-bytes, ...) + {len(SHARDING_RULES)} sharding"
        )
        budget_path = find_budget_file()
        if budget_path:
            budgets = load_budgets(budget_path)
            print(f"budget ledger ....... {budget_path}: "
                  f"{len(budgets)} program(s)")
            # the peaks these budgets gate come from a compile: ask the
            # engine (memory_report()), not this report
            for prog in sorted(budgets):
                print(f"  {prog:<18} budget {budgets[prog] / 1e6:.2f} MB")
        else:
            print("budget ledger ....... none (hbm-over-budget gate off)")
        print(
            "verify .............. engine.memory_report() / "
            "ServingEngine.memory_report(); CLI: dslint dumps/ --engines e"
        )
    except Exception as e:
        print(f"dsmem ............... {RED_NO} ({type(e).__name__}: {e})")
    print("-" * 60)


def _request_tracing() -> None:
    print("Request tracing (ISSUE 11):")
    try:
        from deepspeed_tpu.runtime.config import ServingConfig, TelemetryConfig
        from deepspeed_tpu.telemetry.request_trace import (
            SCHEMA,
            RequestTracer,  # noqa: F401
        )

        tcfg = TelemetryConfig()
        print(
            f"request tracer ...... {GREEN_OK} schema {SCHEMA} "
            f"(telemetry.request_trace — "
            f"{'on' if tcfg.request_trace.enabled else 'off'} by default; "
            "host-side events, StepTracer rotation)"
        )
        slo = ServingConfig().slo
        print(
            "slo classes ......... "
            + (
                f"{len(slo.classes)} configured "
                f"({', '.join(sorted(slo.classes))})"
                if slo.classes
                else "none by default (serving.slo.classes — goodput/"
                "attainment gauges activate with the first class)"
            )
        )
        from deepspeed_tpu.serving import generate_workload  # noqa: F401

        print(
            f"replay harness ...... {GREEN_OK} serving/replay.py "
            "(seeded bursty arrivals + heavy-tailed prompts + hot-tenant "
            "prefix skew)"
        )
        print(
            "report CLI .......... python -m deepspeed_tpu.tools.request_trace "
            "requests.jsonl [--waterfall N] [--diff B.jsonl]"
        )
    except Exception as e:
        print(f"request tracing ..... {RED_NO} ({type(e).__name__}: {e})")
    print("-" * 60)


def _placement() -> None:
    print("Serving placement (ISSUE 14):")
    try:
        from deepspeed_tpu.runtime.config import ServingConfig
        from deepspeed_tpu.serving.placement import (
            GPT2_SERVING_RULES,
            TP_AXIS,
        )

        pcfg = ServingConfig().placement
        print(
            f"tp mesh axis ........ '{TP_AXIS}' (serving.placement.tp — "
            f"default {pcfg.tp}; {len(GPT2_SERVING_RULES)} committed "
            "sharding rules for the gpt2 serving tree)"
        )
        print(
            f"disaggregation ...... "
            f"{'on' if pcfg.disaggregate else 'off'} by default "
            "(serving.placement.disaggregate — prefill/chunk-prefill on "
            "one placement, decode/verify on another, KV handoff over "
            "the page machinery)"
        )
        print(
            "program map ......... shared: all programs on one placement; "
            "disaggregated: serving_chunk_prefill (and serving_prefill, "
            "which only an engine with prefill_chunk_tokens 0 builds: one "
            "that chunks prefills every prompt with the chunk program) → "
            "'prefill', serving_decode/_verify → 'decode', "
            "serving_kv_gather/_scatter bridge the two"
        )
        print(
            "verify .............. ServingEngine.verify() runs Engine F "
            "(analysis.sharding.rules) PRE-compile, then Engines A/D/E "
            "on every placement's executables"
        )
    except Exception as e:
        print(f"serving placement ... {RED_NO} ({type(e).__name__}: {e})")
    print("-" * 60)


def _protocol() -> None:
    print("Protocol (dsproto, ISSUE 15):")
    try:
        from deepspeed_tpu.analysis import (
            PROTOCOL_MODEL_RULES,
            PROTOCOL_RULES,
        )
        from deepspeed_tpu.runtime.config import AnalysisConfig

        pcfg = AnalysisConfig().protocol
        print(
            f"engine G rules ...... {GREEN_OK} "
            f"{len(PROTOCOL_RULES)} ownership-lint (page-leak-on-path, "
            f"refcount-escape, ...) + {len(PROTOCOL_MODEL_RULES)} model "
            "invariants (proto-page-leak, proto-request-wedged, ...)"
        )
        print(
            f"model bounds ........ requests={pcfg.requests} "
            f"prompt_pages={pcfg.prompt_pages} new_tokens={pcfg.new_tokens} "
            f"retry_max={pcfg.retry_max} max_states={pcfg.max_states} "
            "(analysis.protocol)"
        )
        print(
            "run checker ......... python -m deepspeed_tpu.tools.dslint "
            "deepspeed_tpu/serving/ --engines g (model counterexamples "
            "replay via analysis.protocol_model.replay_trace)"
        )
    except Exception as e:
        print(f"protocol ............ {RED_NO} ({type(e).__name__}: {e})")
    print("-" * 60)


def _kv_heat() -> None:
    print("KV heat (ISSUE 16):")
    try:
        from deepspeed_tpu.runtime.config import KVHeatConfig
        from deepspeed_tpu.telemetry.kv_heat import SCHEMA as HEAT_SCHEMA

        hcfg = KVHeatConfig()
        print(
            f"page-heat tracing ... {GREEN_OK} schema {HEAT_SCHEMA} "
            "(telemetry.kv_heat — per-page lifecycle events + per-step "
            "touch columns, host-side mirror reconciles bit-exact against "
            "PageAllocator)"
        )
        print(
            f"idle thresholds ..... {list(hcfg.idle_thresholds_s)} s "
            f"(cold-fraction gauges; segment_events={hcfg.segment_events}, "
            f"flush_interval={hcfg.flush_interval})"
        )
        print(
            "report CLI .......... python -m deepspeed_tpu.tools.kv_heat "
            "kv_heat.jsonl [--heatmap] [--page N] [--what-if] "
            "[--min-cold-fraction PCT]"
        )
    except Exception as e:
        print(f"kv heat ............. {RED_NO} ({type(e).__name__}: {e})")
    print("-" * 60)


def _kv_tiering() -> None:
    print("KV tiering (ISSUE 17):")
    try:
        from deepspeed_tpu.runtime.config import TieringConfig
        from deepspeed_tpu.serving.tiering import TIERING_POLICIES

        tcfg = TieringConfig()
        print(
            f"host-DRAM tier ...... {GREEN_OK} serving.tiering — "
            f"{'on' if tcfg.enabled else 'off'} by default; policies: "
            f"{', '.join(TIERING_POLICIES)} (default {tcfg.policy})"
        )
        print(
            f"knobs ............... host_budget_pages="
            f"{tcfg.host_budget_pages} (0 = device pool capacity), "
            f"prefetch_depth={tcfg.prefetch_depth}, "
            f"crc={'on' if tcfg.crc else 'off'}"
        )
        print(
            "cross-check ......... python -m deepspeed_tpu.tools.kv_heat "
            "kv_heat.jsonl --policy idle_lru (what-if simulator vs live "
            "tier, field-by-field)"
        )
    except Exception as e:
        print(f"kv tiering .......... {RED_NO} ({type(e).__name__}: {e})")
    print("-" * 60)


def _fleet() -> None:
    print("Serving fleet (ISSUE 18):")
    try:
        from deepspeed_tpu.runtime.config import FleetConfig

        fcfg = FleetConfig()
        print(
            f"fleet router ........ {GREEN_OK} serving.fleet — "
            f"{'on' if fcfg.enabled else 'off'} by default; policies: "
            f"affinity, round_robin, least_loaded (default {fcfg.policy})"
        )
        print(
            f"knobs ............... replicas={fcfg.replicas}, "
            f"migrate_sessions={'on' if fcfg.migrate_sessions else 'off'}, "
            f"preempt_policy={fcfg.preempt_policy}, "
            f"admit_attainment_floor={fcfg.admit_attainment_floor}"
        )
        print(
            "trace grouping ...... python -m deepspeed_tpu.tools."
            "request_trace requests.jsonl --by replica"
        )
    except Exception as e:
        print(f"serving fleet ....... {RED_NO} ({type(e).__name__}: {e})")
    print("-" * 60)


def _timeseries() -> None:
    print("Time series / SLO budget (ISSUE 20):")
    try:
        from deepspeed_tpu.runtime.config import (
            SLOAlertsConfig,
            TimeseriesConfig,
        )

        tcfg = TimeseriesConfig()
        acfg = SLOAlertsConfig()
        print(
            f"metrics journal ..... {GREEN_OK} telemetry.timeseries — "
            f"{'on' if tcfg.enabled else 'off'} by default; "
            f"interval={tcfg.interval_s}s, max_mb={tcfg.max_mb}, "
            f"retention={tcfg.retention_s or 3600.0}s"
        )
        print(
            f"burn-rate alerts .... serving.fleet.slo_alerts — "
            f"{'on' if acfg.enabled else 'off'} by default; objective="
            f"{acfg.objective}, fast {acfg.fast_short_s:.0f}s/"
            f"{acfg.fast_long_s:.0f}s@{acfg.fast_burn_threshold}x, slow "
            f"{acfg.slow_short_s:.0f}s/{acfg.slow_long_s:.0f}s@"
            f"{acfg.slow_burn_threshold}x, backpressure="
            f"{'on' if acfg.backpressure else 'off'}"
        )
        print(
            "dashboard ........... python -m deepspeed_tpu.tools."
            "fleet_dash metrics_tsdb.jsonl [--watch 5] [--diff OLD.jsonl]"
        )
    except Exception as e:
        print(f"time series ......... {RED_NO} ({type(e).__name__}: {e})")
    print("-" * 60)


def main() -> int:
    _ops()
    _environment()
    _telemetry()
    _analysis()
    _memory()
    _request_tracing()
    _placement()
    _protocol()
    _kv_heat()
    _kv_tiering()
    _fleet()
    _timeseries()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
