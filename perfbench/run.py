"""The benchmark's one command.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process per run. It refuses anything but a TPU that is in
``perfbench/peaks.py`` (exit code 2, no result line), builds the cell's
configuration from its file, makes the weights on the device from ``--seed``,
warms the shapes this cell's traffic uses, opens the window for ``--seconds``
and prints one JSON object as the last line of stdout. With ``--trace 0`` the
metrics are the cell's end-to-end metrics, with ``--trace 1`` its per-layer
metrics, the last ``trace_s`` seconds of the window under the profiler.
``setup_s`` runs from the moment the chip is reached to the window's opening:
importing the program, its parameters, its programs (compiled, or loaded from
the cache), the warm-up and the ramp; what the process took to reach the chip
is ``notes.reach_chip_s``.

Everything that belongs to one cell, configuration, traffic mix or metric is
data found by name (perfbench/manifest.py). ``BENCH_RUN`` is not read.
"""

from __future__ import annotations

import time

_T_PROCESS = time.perf_counter()  # process start, as near as Python lets us stamp it

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


def log(msg: str) -> None:
    print(f"[perfbench +{time.perf_counter() - _T_PROCESS:7.2f}s] {msg}", file=sys.stderr, flush=True)


class Refused(RuntimeError):
    """The harness will not measure here (no TPU, too few chips, unknown chip)."""


class TraceCtl:
    """Profiles the last ``trace_s`` seconds of the window (``--trace 1``).
    The runner calls :meth:`tick` between two calls into the system."""

    def __init__(self, enabled: bool, trace_s: float, out_dir: str):
        self.enabled, self.trace_s, self.out_dir = enabled, float(trace_s), out_dir
        self.state = "idle"
        self.span = None
        self.t = [None, None]   # benchmark clock at start and stop

    def tick(self, rel: float, seconds: float) -> None:
        if not self.enabled:
            return
        if self.state == "idle" and rel >= max(0.0, seconds - self.trace_s):
            import jax

            shutil.rmtree(self.out_dir, ignore_errors=True)
            jax.profiler.start_trace(self.out_dir)
            self.span = jax.profiler.TraceAnnotation("perfbench.window")
            self.span.__enter__()
            self.t[0] = time.perf_counter()
            self.state = "on"
        elif self.state == "on" and rel >= seconds:
            self.stop()

    def stop(self) -> None:
        if self.state != "on":
            return
        import jax

        self.t[1] = time.perf_counter()
        self.span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.state = "done"
        log(f"trace stopped after {self.t[1] - self.t[0]:.2f}s, written in {time.perf_counter() - self.t[1]:.2f}s")


def _span_factory(enabled: bool):
    if not enabled:
        return lambda name: contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation


def setup_jax_cache() -> str:
    """The persistent compilation cache: where JAX_COMPILATION_CACHE_DIR says,
    else ``<checkout>/.jax_cache`` (a fixed path: the path is part of the key)."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = os.path.join(_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


def check_device(chips: int, require_tpu: bool):
    import jax

    from perfbench.peaks import UnknownDevice, peak_for

    devs = jax.devices()
    platform = devs[0].platform
    if require_tpu and platform != "tpu":
        raise Refused(f"the benchmark measures only on a TPU; JAX found {platform!r} ({devs})")
    if len(devs) < chips:
        raise Refused(f"the cell asks for {chips} chip(s); JAX found {len(devs)}")
    try:
        peak = peak_for(devs[0].device_kind)
    except UnknownDevice:
        if require_tpu:
            raise
        peak = peak_for("TPU v5 lite")  # tests on the CPU: a denominator for arithmetic only
    return devs, peak


def run_cell(manifest, cell_name: str, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True, trace_dir: str | None = None):
    """One run of one cell. Returns (the result line as a dict, the run's
    Context); ``require_tpu=False`` is for the CPU tests only."""
    from perfbench import xplane
    from perfbench.context import Context

    cell = manifest.cell(cell_name)
    cfg = manifest.config(cell["config"])
    traffic = manifest.traffic(cell["traffic"])
    devs, peak = check_device(int(cell["chips"]), require_tpu)
    used = devs[: int(cell["chips"])]
    # Set-up is counted from here: the interpreter's start, ``import jax`` and
    # the runtime's attach to the chip lie before it. They are the machine's
    # (9.6 to 14.4 s, up to 3.7 s apart on one machine, where everything after
    # them repeats to a second: PERF.md, PR 47), no PR can move work into them,
    # and with them in it two sets of runs of one tree read ``setup_s`` 10.6%
    # apart. The result line's ``notes.reach_chip_s`` keeps them.
    t_chip = time.perf_counter()
    log(f"{len(devs)} device(s) reached: set-up counts from here")

    from deepspeed_tpu.telemetry import compile_stats
    from deepspeed_tpu.telemetry.registry import MetricsRegistry

    reg = MetricsRegistry()
    compile_stats.install(reg)
    log("program imported")

    def compiles():
        return reg.counter("jit_compiles_total").value()

    ctx = Context(cell=cell, config=cfg, traffic=traffic, chips=len(used), peak=peak)
    runner = manifest.runner(cfg["runner"]).Runner(ctx, seed, used, _span_factory(trace), log)
    runner.setup()
    c_warm = compiles()
    trace_dir = trace_dir or os.path.join(_ROOT, ".perfbench_trace", cell_name)
    tracer = TraceCtl(trace, float(cfg.get("trace_s", 5.0)), trace_dir)
    log("warmed up; the ramp, then the window")
    runner.measure(float(seconds), tracer)
    setup_s = ctx.window[0] - t_chip
    compiled_in_window = compiles() - c_warm
    log(f"window closed; {compiled_in_window} compilation(s) inside")
    if tracer.state == "done":
        ctx.traced = (tracer.t[0], tracer.t[1])
        t0 = time.perf_counter()
        ctx.trace = xplane.reduce(xplane.load(trace_dir))
        log(f"trace reduced in {time.perf_counter() - t0:.1f}s: "
            f"{None if ctx.trace is None else (ctx.trace.n_devices, ctx.trace.line_names)}")
    correct, attempted, failed, notes = runner.finish()
    if compiled_in_window:
        correct = False
    notes["compilations_in_window"] = compiled_in_window
    notes["reach_chip_s"] = t_chip - _T_PROCESS

    group = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in manifest.metrics_for(cell_name, group):
        if m["name"] == "setup_s":
            value = setup_s
        else:
            spec = manifest.metric_spec(m["name"])
            value = manifest.reader(spec["reader"]).read(ctx, **spec.get("args", {}))
        if value is not None:   # a reader that finds nothing to read returns nothing
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    peaks = [d.memory_stats() for d in used]
    device = {
        "platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs),
        "memory_peak_bytes": max((int(s["peak_bytes_in_use"]) for s in peaks if s), default=0),
    }
    out = {"correct": bool(correct), "attempted": int(attempted), "failed": int(failed),
           "metrics": metrics, "device": device, "notes": notes,
           "cell": cell_name, "seed": int(seed), "seconds": float(seconds)}
    if trace and ctx.trace is not None:
        device["busy_s"] = ctx.trace.busy_s
        device["window_s"] = ctx.trace.window_s
        out["breakdown"] = xplane.breakdown(ctx.trace)
    return out, ctx


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump", default="", help="write the run's stamps and reduced trace here (a directory)")
    args = ap.parse_args(argv)

    from perfbench.manifest import Manifest

    manifest = Manifest(_ROOT)
    manifest.cell(args.workload)  # an unknown cell fails before JAX is touched
    setup_jax_cache()
    log("jax imported")
    try:
        out, ctx = run_cell(manifest, args.workload, args.seed, args.seconds, bool(args.trace))
    except Refused as e:
        print(f"perfbench: refused: {e}", file=sys.stderr)
        return 2
    if args.dump:
        from perfbench import dump

        dump.write(args.dump, out, ctx)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
