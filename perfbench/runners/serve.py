"""Drives a served configuration: ``deepspeed_tpu.init_inference(...).serve``
under an open-loop or a backlog traffic mix. Only the public entry points, the
scheduler's ``step``/``submit``/``drain``/``check_no_leaks`` and the stamps on
``Request`` are taken from the program; the server is given the benchmark's
clock. One thread: requests that are due are submitted between two steps, as a
front end's queue would hand them to this scheduler, and latencies count from
the due time the generator kept.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from perfbench import arith, reference, traffic as tg
from perfbench.context import Step

clock = time.perf_counter


def model_config(cfg: dict):
    from deepspeed_tpu.models import gpt2

    keys = ("vocab_size", "n_positions", "n_embd", "n_layer", "n_head", "layer_norm_epsilon")
    extra = cfg.get("model_overrides", {})
    return gpt2.GPT2Config(**{k: cfg[k] for k in keys}, **extra)


class Runner:
    def __init__(self, ctx, seed: int, devices, span, log):
        self.ctx, self.seed, self.devices, self.span, self.log = ctx, int(seed), devices, span, log
        self.cfg = ctx.config
        self.sv = dict(self.cfg["serving"])
        self.live = []          # (Request, Arrival, due) not yet terminal
        self.done = []          # same, terminal
        self.warm = []
        self.counted = {}       # id(Request) -> due (or, for a backlog, finished) in the window

    # -- set-up -----------------------------------------------------------
    def setup(self):
        import jax.numpy as jnp

        import deepspeed_tpu
        from deepspeed_tpu.models import gpt2

        self.mcfg = model_config(self.cfg)
        dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[self.cfg["dtype"]]
        t0 = clock()
        self.engine = deepspeed_tpu.init_inference(
            model=gpt2.make_module(self.mcfg), dtype=dtype, seed=self.seed % (2**31 - 1)
        )
        self.srv = self.engine.serve(dict(self.sv), clock=clock)
        self.srv.executable_names()   # compiles (or loads from the cache) the program set
        self.log(f"engine+programs {clock() - t0:.1f}s")
        # warm-up: one request through each prefill program, a few tokens each;
        # they are also the two requests the float32 reference checks
        lens = sorted({min(self.cfg["warmup_short_prompt"], self.sv["max_prompt_len"]),
                       min(self.cfg["warmup_long_prompt"], self.sv["max_prompt_len"])})
        rng = np.random.default_rng([self.seed % 2**63, 9])
        t0 = clock()
        self.warm = [
            self.srv.submit(rng.integers(0, self.mcfg.vocab_size, n).astype(np.int32),
                            max_new_tokens=int(self.cfg["warmup_new_tokens"]), seed=i)
            for i, n in enumerate(lens)
        ]
        self.srv.run()
        self.log(f"warm-up requests {clock() - t0:.1f}s (prompts {lens})")

    # -- one window -------------------------------------------------------
    def _submit(self, a, due):
        ids = tg.prompt_tokens(self.ctx.traffic, self.seed, a, self.mcfg.vocab_size)
        r = self.srv.submit(ids, max_new_tokens=a.new_tokens, seed=a.index)
        self.live.append((r, a, due))

    def _step(self):
        before = [(len(r.tokens), r.t_first_token is None) for r, _, _ in self.live]
        t0 = clock()
        with self.span("perfbench.srv.step"):
            self.srv.step()
        t1 = clock()
        dec = att = first = 0
        for (n0, unstarted), (r, _, _) in zip(before, self.live):
            d = len(r.tokens) - n0
            if unstarted and r.t_first_token is not None:
                first += 1
                d -= 1
            if d > 0:
                dec += d
                att += r.prompt_len + len(r.tokens) - 1
        self.ctx.steps.append(Step("srv.step", t0, t1, {
            "decode_tokens": dec, "attended_tokens": att, "first_tokens": first,
            "queue": len(self.srv.queue),
        }))
        still = []
        for item in self.live:
            (self.done if item[0].done else still).append(item)
        self.live = still

    def _busy(self):
        return bool(self.srv.queue) or any(s.request is not None for s in self.srv.slots)

    def measure(self, seconds: float, tracer):
        tr = self.ctx.traffic
        loop = tr["loop"]
        if loop == "open":
            self._open(seconds, tracer)
        elif loop == "backlog":
            self._backlog(seconds, tracer)
        else:
            raise ValueError(f"the serve runner drives open and backlog loops, not {loop!r}")
        tracer.stop()
        self.srv.drain(0.0)       # evicts what is uncounted; pages must all come back
        self.leak = None
        try:
            self.srv.check_no_leaks()
        except AssertionError as e:
            self.leak = str(e) or "check_no_leaks failed"
        for r, a, due in self.done + self.live:
            self.ctx.recs.append(arith.Rec(
                due=due, prompt_len=a.prompt_len, new_tokens=a.new_tokens, t_submit=r.t_submit,
                t_admit=r.t_admit, t_first_token=r.t_first_token, t_emissions=list(r.t_emissions),
                status=r.status, n_tokens=len(r.tokens),
            ))

    def _open(self, seconds, tracer):
        tr = self.ctx.traffic
        ramp, tail = float(tr.get("ramp_s", 0.0)), float(tr.get("tail_s", 30.0))
        arr = tg.open_arrivals(tr, -ramp, seconds + tail)
        t_open = clock() + ramp
        self.ctx.window = (t_open, t_open + seconds)
        i = 0
        while True:
            rel = clock() - t_open
            tracer.tick(rel, seconds)
            counted_open = any(0 <= a.due_s < seconds for _, a, _ in self.live)
            pending_counted = i < len(arr) and arr[i].due_s < seconds
            if rel >= seconds and not counted_open and not pending_counted:
                break
            if rel >= seconds + tail:
                break
            while i < len(arr) and arr[i].due_s <= rel:
                self._submit(arr[i], t_open + arr[i].due_s)
                i += 1
            if self._busy():
                self._step()
            elif i < len(arr):
                time.sleep(max(0.0, min(0.02, t_open + arr[i].due_s - clock())))
            else:
                break
        for r, a, _ in self.done + self.live:
            self.counted[id(r)] = 0 <= a.due_s < seconds
        self.ctx.extra["offered_in_window"] = sum(1 for a in arr if 0 <= a.due_s < seconds)

    def _backlog(self, seconds, tracer):
        """A backlog that never empties: ``slots + queue_depth`` requests are
        always in the system. The ramp fills the slots at once; with
        ``ramp.aged`` the first fill asks slot k of n for (k+1)/n of its new
        tokens, so that the slots come free one after another, evenly spread in
        steps, as they are in a server that has run for hours, and not all in
        the same step. Building the first contexts is set-up the traffic needs.

        The ramp is a stretch of the SCHEDULE, not of the clock: with
        ``ramp.requests`` the window opens at the step boundary after that
        many requests of the cycle have been submitted, so every run's window
        starts at the same request of the same order and holds the same work
        as far as its speed takes it. Under ``ramp.seconds`` (kept for a mix
        that states no count) a host that stood still for three seconds of the
        ramp opened its window 180 steps earlier in the cycle, and where the
        window lies in the cycle moves ``serve_tok_s`` by up to 1.5% at one
        speed, because a prompt token is cheaper than a generated one
        (PERF.md, PR 47)."""
        tr = self.ctx.traffic
        ramp = tr.get("ramp", {})
        ramp_s, aged = float(ramp.get("seconds", 0.0)), bool(ramp.get("aged", False))
        ramp_requests = int(ramp.get("requests", 0))
        depth = int(tr.get("queue_depth", 2))
        slots = int(self.sv["max_slots"])
        cycle = tg.backlog_cycle(tr)
        t_ramp, submitted, n_steps = clock(), 0, len(self.ctx.steps)
        t_open = None if ramp_requests else t_ramp + ramp_s
        first_fill = slots if aged else 0
        opened = False
        while True:
            now = clock()
            if t_open is None and submitted >= ramp_requests:
                t_open = now
            if not opened and t_open is not None and now >= t_open:
                opened = True
                self.ctx.window = (t_open, t_open + seconds)
                self.log(f"ramp {now - t_ramp:.1f}s: {submitted} requests submitted, "
                         f"{len(self.ctx.steps) - n_steps} steps before the window")
            if opened:
                rel = now - t_open
                tracer.tick(rel, seconds)
                if rel >= seconds:
                    break
            while len(self.live) < slots + depth:
                a = next(cycle)
                if first_fill > 0:
                    k = slots - first_fill
                    a = dataclasses.replace(a, new_tokens=max(1, -(-a.new_tokens * (k + 1) // slots)))
                    first_fill -= 1
                self._submit(a, now)
                submitted += 1
            self._step()
        t1 = t_open + seconds
        # A prompt whose prefill straddles an edge counts in proportion
        # (arith.tokens_in_window), which takes its first-token stamp: the
        # prefills the close caught in flight are stepped to their end, outside
        # the window, with nothing new submitted (a chunk a step, so the
        # longest prompt's chunks bound it). Without this the close credits
        # such a prompt nothing, and runs that close just before and just
        # after a first token read a whole prompt apart (0.65% of the window
        # at 512 tokens; PERF.md, PR 28).
        longest = int(self.sv["max_prompt_len"])
        for _ in range(2 * -(-longest // int(self.sv.get("prefill_chunk_tokens") or longest)) + 2):
            if not any(r.t_admit is not None and r.t_admit < t1 and r.t_first_token is None and not r.done
                       for r, _, _ in self.live):
                break
            self._step()
        for r, _, _ in self.done + self.live:
            self.counted[id(r)] = r.done and r.t_finish is not None and t_open <= r.t_finish < t1

    # -- after the window -------------------------------------------------
    def finish(self):
        """(correct, attempted, failed, notes)"""
        ok, attempted, failed, notes = self.finish_counts()
        ref_ok, ref_notes = self.reference_check()
        notes["reference"] = ref_notes
        return ok and ref_ok, attempted, failed, notes

    def finish_counts(self):
        """The window's own part of ``correct``: every counted request
        finished with the tokens asked for, none was left unsubmitted, no page
        leaked."""
        from deepspeed_tpu.serving.request import RequestStatus

        notes = {}
        attempted = failed = 0
        for rec, (r, a, _) in zip(self.ctx.recs, self.done + self.live):
            rec.counted = bool(self.counted.get(id(r), False))
            if rec.counted:
                attempted += 1
                if r.status != RequestStatus.FINISHED or len(r.tokens) != a.new_tokens:
                    failed += 1
        ok = failed == 0 and attempted > 0
        if self.leak:
            ok = False
            notes["leak"] = self.leak
        notes["offered_in_window"] = self.ctx.extra.get("offered_in_window")
        if notes["offered_in_window"] is not None and attempted != notes["offered_in_window"]:
            ok = False   # a request due in the window was never submitted
        return ok, attempted, failed, notes

    def reference_check(self, skip_layer: int = -1):
        """Teacher-forced float32 reference on the two warm-up requests: every
        served token's reference logit must lie within ``logit_margin`` of the
        reference's largest at that position."""
        import jax.numpy as jnp

        margin = float(self.cfg["reference"]["logit_margin"])
        worst, stds, n_pos = 0.0, [], 0
        for r in self.warm:
            ids = np.concatenate([np.asarray(r.prompt, np.int32), np.asarray(r.tokens, np.int32)])
            n_valid, n_prompt = len(ids), len(r.prompt)
            T = -(-n_valid // 128) * 128
            padded = np.zeros((min(T, self.mcfg.n_positions),), np.int32)
            padded[:n_valid] = ids
            gap, std = reference.served_gaps(
                self.engine.params, jnp.asarray(padded), n_prompt, n_valid,
                n_head=self.mcfg.n_head, eps=float(self.mcfg.layer_norm_epsilon),
                vocab=self.mcfg.vocab_size, skip_layer=skip_layer,
            )
            gap = np.asarray(gap)
            worst = max(worst, float(gap.max()))
            stds.append(float(np.asarray(std)[n_prompt - 1: n_valid - 1].mean()))
            n_pos += len(r.tokens)
        ok = len(self.warm) > 0 and all(len(r.tokens) > 0 for r in self.warm) and worst <= margin
        return ok, {"max_logit_gap": worst, "margin": margin, "positions": n_pos, "logit_std": stds}
