"""``ds_report`` — environment and op-compatibility report.

Analog of reference ``deepspeed/env_report.py`` (140 LoC): versions, device
inventory, native-op build/compat table.

    python -m deepspeed_tpu.env_report
"""

from __future__ import annotations

import sys


GREEN_OK = "\033[92m[OKAY]\033[0m"
RED_NO = "\033[93m[NO]\033[0m"


def main() -> int:
    import jax

    import deepspeed_tpu
    from deepspeed_tpu.ops.op_builder import op_report

    print("-" * 60)
    print("DeepSpeed-TPU C++/native op report")
    print("-" * 60)
    print(f"{'op name':<20} {'compatible':<12} {'built':<8}")
    for name, compat, built in op_report():
        print(f"{name:<20} {GREEN_OK if compat else RED_NO:<21} {GREEN_OK if built else RED_NO}")
    print("-" * 60)
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
    print("Telemetry / introspection:")
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
    print("Memory (dsmem):")
    try:
        import json
        import os

        from deepspeed_tpu.analysis import (
            MEMORY_RULES,
            SHARDING_RULES,
            find_budget_file,
            load_budgets,
        )
        from deepspeed_tpu.analysis.memory_rules import headroom_pct

        print(
            f"engine E/F rules .... {GREEN_OK} "
            f"{len(MEMORY_RULES)} memory (hbm-over-budget, "
            f"donation-missed-bytes, ...) + {len(SHARDING_RULES)} sharding"
        )
        budget_path = find_budget_file()
        if budget_path:
            budgets = load_budgets(budget_path)
            # the bench artifact next to the ledger carries the measured
            # per-program peaks (env_report stays cheap: no compiles here)
            peaks, kv_bytes = {}, {}
            bench_path = os.path.join(
                os.path.dirname(os.path.abspath(budget_path)),
                "BENCH_pr9.json",
            )
            if os.path.exists(bench_path):
                try:
                    with open(bench_path, encoding="utf-8") as fh:
                        doc = json.load(fh)
                    for prog, rec in (doc.get("programs") or {}).items():
                        peaks[prog] = rec.get("peak_bytes_est")
                        kv_bytes[prog] = rec.get("kv_pool_bytes", 0)
                except Exception:
                    pass
            print(f"budget ledger ....... {budget_path}: "
                  f"{len(budgets)} program(s)")
            for prog in sorted(budgets):
                b = budgets[prog]
                peak = peaks.get(prog)
                head = headroom_pct(b, peak) if peak else None
                if peak and head is not None:
                    extra = (f"peak {peak / 1e6:.2f} MB, "
                             f"headroom {head:+.1f}%")
                    if kv_bytes.get(prog):
                        extra += f", kv pool {kv_bytes[prog] / 1e6:.2f} MB"
                else:
                    extra = "peak unmeasured — run bench.py"
                print(f"  {prog:<18} budget {b / 1e6:.2f} MB ({extra})")
        else:
            print("budget ledger ....... none (hbm-over-budget gate off)")
        print(
            "verify .............. engine.memory_report() / "
            "ServingEngine.memory_report(); CLI: dslint dumps/ --engines e"
        )
    except Exception as e:
        print(f"dsmem ............... {RED_NO} ({type(e).__name__}: {e})")
    print("-" * 60)
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
    print("Serving placement (ISSUE 14):")
    try:
        import json
        import os

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
        # per-device pool bytes come from the committed bench artifact —
        # env_report stays cheap (no compiles, no pool allocation here)
        bench_path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "BENCH_pr14.json",
        )
        if os.path.exists(bench_path):
            with open(bench_path, encoding="utf-8") as fh:
                doc = json.load(fh)
            for tp, rec in sorted((doc.get("tp_sweep") or {}).items()):
                pools = ", ".join(
                    f"{name}: {b / 1e6:.2f} MB/device"
                    for name, b in (rec.get(
                        "per_device_pool_bytes") or {}).items()
                )
                print(f"  {tp:<18} kv pool {pools}")
            res = doc.get("resident_sessions_at_fixed_device_hbm") or {}
            if res:
                print(
                    f"  resident sessions  "
                    f"{res.get('sessions')} at fixed per-device HBM "
                    f"(x{res.get('ratio')})"
                )
        else:
            print("  pool bytes ......... unmeasured — run bench.py "
                  "(BENCH_TP_SERVING_ONLY=1)")
        print(
            "program map ......... shared: all programs on one placement; "
            "disaggregated: serving_prefill/_chunk_prefill → 'prefill', "
            "serving_decode/_verify → 'decode', serving_kv_gather/"
            "_scatter bridge the two"
        )
        print(
            "verify .............. ServingEngine.verify() runs Engine F "
            "(analysis.sharding.rules) PRE-compile, then Engines A/D/E "
            "on every placement's executables"
        )
    except Exception as e:
        print(f"serving placement ... {RED_NO} ({type(e).__name__}: {e})")
    print("-" * 60)
    print("Protocol (dsproto, ISSUE 15):")
    try:
        import json
        import os

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
        # exploration stats come from the committed bench artifact —
        # env_report stays cheap (no state-space walk here)
        bench_path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "BENCH_pr15.json",
        )
        if os.path.exists(bench_path):
            with open(bench_path, encoding="utf-8") as fh:
                doc = json.load(fh)
            for mode, rec in sorted((doc.get("model") or {}).items()):
                print(
                    f"  {mode:<18} {rec.get('states')} states / "
                    f"{rec.get('transitions')} transitions in "
                    f"{rec.get('wall_s')}s, "
                    f"{rec.get('violations', 0)} violation(s)"
                )
            replay = doc.get("replay_self_check")
            if replay is not None:
                print(
                    f"  replay self-check  "
                    f"{GREEN_OK if replay.get('ok') else RED_NO} "
                    f"(mutations red: "
                    f"{', '.join(replay.get('mutations_red', []))})"
                )
        else:
            print("  exploration ........ unmeasured — run bench.py "
                  "(BENCH_DSPROTO_ONLY=1)")
        print(
            "run checker ......... python -m deepspeed_tpu.tools.dslint "
            "deepspeed_tpu/serving/ --engines g (model counterexamples "
            "replay via analysis.protocol_model.replay_trace)"
        )
    except Exception as e:
        print(f"protocol ............ {RED_NO} ({type(e).__name__}: {e})")
    print("-" * 60)
    print("KV heat (ISSUE 16):")
    try:
        import json
        import os

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
        # headline curves come from the committed bench artifact —
        # env_report stays cheap (no serving replay here)
        bench_path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "BENCH_pr16.json",
        )
        if os.path.exists(bench_path):
            with open(bench_path, encoding="utf-8") as fh:
                doc = json.load(fh)
            ov = (doc.get("overhead") or {}).get("heat_overhead_pct")
            if ov is not None:
                print(f"  hook overhead ...... {ov}% of traced span "
                      "(pin: <= 2%)")
            for name, rec in sorted((doc.get("cold_fraction") or {}).items()):
                end = rec.get("end") or {}
                cf = ", ".join(
                    f">{th}s: {100.0 * f:.0f}%" if f is not None else f">{th}s: -"
                    for th, f in sorted(end.items(), key=lambda kv: float(kv[0]))
                )
                print(f"  {name:<18} {cf}")
            pol = (doc.get("spill_policies") or {}).get("policies") or {}
            if pol:
                best = min(
                    pol.items(),
                    key=lambda kv: (kv[1].get("restore_stalls", 0),
                                    kv[1].get("spills", 0), kv[0]),
                )[0]
                print(f"  spill what-if ...... fewest restore stalls: {best}")
        else:
            print("  curves ............. unmeasured — run bench.py "
                  "(BENCH_KVHEAT_ONLY=1)")
        print(
            "report CLI .......... python -m deepspeed_tpu.tools.kv_heat "
            "kv_heat.jsonl [--heatmap] [--page N] [--what-if] "
            "[--min-cold-fraction PCT]"
        )
    except Exception as e:
        print(f"kv heat ............. {RED_NO} ({type(e).__name__}: {e})")
    print("-" * 60)
    print("KV tiering (ISSUE 17):")
    try:
        import json
        import os

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
        # tier sizes + spill/restore counters come from the committed bench
        # artifact — env_report stays cheap (no serving replay here)
        bench_path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "BENCH_pr17.json",
        )
        if os.path.exists(bench_path):
            with open(bench_path, encoding="utf-8") as fh:
                doc = json.load(fh)
            tiers = doc.get("tiers") or {}
            if tiers:
                print(
                    f"  tier sizes ........ device {tiers.get('device_pages')}"
                    f" pages / host {tiers.get('host_budget_pages')} pages "
                    f"x {tiers.get('page_bytes')} B "
                    f"(host buffer {(tiers.get('host_bytes') or 0) / 1e6:.2f}"
                    " MB pinned)"
                )
            run = doc.get("tiering") or {}
            cnt = doc.get("counters") or {}
            if cnt:
                print(
                    f"  spill/restore ..... policy {run.get('policy')}: "
                    f"{cnt.get('spills')} spills "
                    f"({(cnt.get('spilled_bytes') or 0) / 1e6:.2f} MB) / "
                    f"{cnt.get('restores')} restores, "
                    f"{cnt.get('restore_misses', 0)} cold miss(es), "
                    f"{cnt.get('host_evictions', 0)} host eviction(s)"
                )
            p99 = doc.get("restore_stall_p99_ms")
            if p99 is not None:
                print(f"  restore stall ..... p99 {p99} ms "
                      "(queue-wait cause: kv_restore)")
            res = doc.get("resident_sessions_at_fixed_hbm") or {}
            if res:
                print(
                    f"  resident sessions  {res.get('tiered_sessions')} vs "
                    f"{res.get('baseline_sessions')} untiered at fixed HBM "
                    f"(x{res.get('ratio')}; PR-14 baseline "
                    f"x{res.get('pr14_ratio')})"
                )
        else:
            print("  tier metrics ...... unmeasured — run bench.py "
                  "(BENCH_KVTIER_ONLY=1)")
        print(
            "cross-check ......... python -m deepspeed_tpu.tools.kv_heat "
            "kv_heat.jsonl --policy idle_lru (what-if simulator vs live "
            "tier, field-by-field)"
        )
    except Exception as e:
        print(f"kv tiering .......... {RED_NO} ({type(e).__name__}: {e})")
    print("-" * 60)
    print("Serving fleet (ISSUE 18):")
    try:
        import json
        import os

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
        # router/migration numbers come from the committed bench artifact —
        # env_report stays cheap (no fleet replay here)
        bench_path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "BENCH_pr18.json",
        )
        if os.path.exists(bench_path):
            with open(bench_path, encoding="utf-8") as fh:
                doc = json.load(fh)
            fl = doc.get("fleet") or {}
            sg = doc.get("single") or {}
            ratio = doc.get("fleet_goodput_over_single")
            print(
                f"  goodput ........... {doc.get('replicas')} replicas "
                f"({doc.get('router_policy')}): "
                f"{fl.get('goodput_tokens_per_sec')} tok/s vs single "
                f"{sg.get('goodput_tokens_per_sec')} tok/s (x{ratio}) at "
                f"{doc.get('offered_load_of_single_capacity')}x single "
                "capacity"
            )
            att = fl.get("slo_attainment")
            satt = sg.get("slo_attainment")
            if att is not None and satt is not None:
                print(
                    f"  slo attainment .... fleet {100 * att:.1f}% vs "
                    f"single {100 * satt:.1f}% (one scripted preemption "
                    f"mid-run; {fl.get('replicas_alive_at_end')} replicas "
                    "alive at end)"
                )
            mig = doc.get("migration") or {}
            if mig:
                p99 = mig.get("blackout_p99_s")
                print(
                    f"  migration ......... {mig.get('ok')} ok / "
                    f"{mig.get('crc_failed')} crc-failed / "
                    f"{mig.get('no_capacity')} no-capacity, "
                    f"{(mig.get('bytes') or 0) / 1e3:.1f} kB moved, "
                    f"blackout p99 "
                    f"{'-' if p99 is None else f'{p99 * 1e3:.0f} ms'}"
                )
        else:
            print("  fleet metrics ..... unmeasured — run bench.py "
                  "(BENCH_FLEET_ONLY=1)")
        print(
            "trace grouping ...... python -m deepspeed_tpu.tools."
            "request_trace requests.jsonl --by replica"
        )
    except Exception as e:
        print(f"serving fleet ....... {RED_NO} ({type(e).__name__}: {e})")
    print("-" * 60)
    print("Time series / SLO budget (ISSUE 20):")
    try:
        import json
        import os

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
        bench_path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "BENCH_pr20.json",
        )
        if os.path.exists(bench_path):
            with open(bench_path, encoding="utf-8") as fh:
                doc = json.load(fh)
            jd = doc.get("journal") or {}
            ar = doc.get("alert_replay") or {}
            print(
                f"  snapshot hook ..... "
                f"{doc.get('snapshot_hook_overhead_pct')}% step overhead "
                f"(pin <= {doc.get('snapshot_hook_overhead_pct_pin')}%), "
                f"{jd.get('bytes_per_record')} B/record, "
                f"{(jd.get('bytes_per_hour_at_1hz') or 0) / 1e6:.2f} "
                "MB/hour at 1 Hz"
            )
            print(
                f"  alert replay ...... injected violation at 60s: fired "
                f"t={ar.get('t_fired_s')}s (delay "
                f"{ar.get('detection_delay_s')}s), resolved "
                f"t={ar.get('t_resolved_s')}s after 120s recovery"
            )
        else:
            print("  tsdb metrics ...... unmeasured — run bench.py "
                  "(BENCH_TSDB_ONLY=1)")
        print(
            "dashboard ........... python -m deepspeed_tpu.tools."
            "fleet_dash metrics_tsdb.jsonl [--watch 5] [--diff OLD.jsonl]"
        )
        print(
            "bench trend ......... python -m deepspeed_tpu.tools."
            "bench_trend --gate BENCH_pr20.json (pinned BENCH_index.json)"
        )
    except Exception as e:
        print(f"time series ......... {RED_NO} ({type(e).__name__}: {e})")
    print("-" * 60)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
