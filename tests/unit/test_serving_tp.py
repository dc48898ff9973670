"""ISSUE 14: tensor-parallel + disaggregated serving.

The acceptance pins, on the 8-virtual-device CPU mesh (conftest.py):

- TP=2 engines emit TOKEN-IDENTICAL streams to the single-device engine on
  the 16-request mixed suite with speculative decode + prefix sharing +
  chunked prefill + int8 KV pages all ON (the per-device math differs —
  psum reduction order — so bitwise logits are not promised; the sampled
  token streams are).
- Disaggregated placements (prefill and decode on separate core-sets, KV
  handoff riding the page machinery) preserve the same streams and leak
  zero pages under mid-load drain.
- Engine D agrees the sharded prefill/decode pair order their per-group
  collectives identically; Engine F fires all three rule families on a
  deliberately broken spec table BEFORE anything compiles; Engine E
  categorizes the per-device sharded pools and keeps the doubled-pool
  budget pin red at TP=2.
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import gpt2

warnings.filterwarnings("ignore")

pytestmark = pytest.mark.serving

needs_8_devices = pytest.mark.skipif(
    jax.device_count() < 4, reason="needs the forced 8-device CPU mesh"
)

BASE = {
    "max_slots": 4,
    "page_size": 4,
    "num_pages": 64,
    "max_prompt_len": 12,
    "max_new_tokens": 8,
}
ALL_FEATURES = {
    "speculative": {"enabled": True, "k": 3},
    "prefix_cache": {"enabled": True},
    "prefill_chunk_tokens": 8,
}


@pytest.fixture(scope="module")
def tiny_cfg():
    return gpt2.get_config("gpt2-tiny", attn_impl="jnp")


@pytest.fixture(scope="module")
def inference_engine(tiny_cfg):
    from deepspeed_tpu.inference.engine import InferenceEngine

    params = gpt2.init_params(tiny_cfg, jax.random.PRNGKey(0))
    return InferenceEngine(
        gpt2.make_module(tiny_cfg), params=params, dtype=jnp.float32
    )


def _mixed_requests(vocab, n=16, seed=7):
    rs = np.random.RandomState(seed)
    plens = [2, 5, 8, 12, 7, 3, 11, 4] * 2
    return [
        (rs.randint(0, vocab, (plens[i],)).astype(np.int32),
         6 if i % 7 else (1, 3, 8)[i // 7])
        for i in range(n)
    ]


def _streams(srv, reqs):
    subs = [
        srv.submit(p, max_new_tokens=n, seed=i)
        for i, (p, n) in enumerate(reqs)
    ]
    srv.run()
    return [list(r.tokens) for r in subs]


@needs_8_devices
class TestTensorParallelParity:
    def test_tp2_token_identical_mixed_suite_all_features_int8(
        self, tiny_cfg, inference_engine
    ):
        """The headline acceptance: TP=2 with EVERYTHING on (speculation,
        prefix sharing, chunked prefill, int8 KV pages) re-emits the
        single-device engine's exact token streams on the mixed suite, the
        full analysis plane (A/D/E/F) verifies clean, and the drained
        engine leaks nothing."""
        cfg = dict(BASE, kv_cache_dtype="int8", **ALL_FEATURES)
        reqs = _mixed_requests(tiny_cfg.vocab_size)
        base = _streams(inference_engine.serve(cfg), reqs)
        srv2 = inference_engine.serve(dict(cfg, placement={"tp": 2}))
        assert _streams(srv2, reqs) == base
        assert srv2.verify() == []
        srv2.drain()
        srv2.release_prefix_cache()
        srv2.check_no_leaks()

    def test_tp2_pools_sharded_and_params_placed(self, inference_engine):
        """The mechanics behind the 1/tp memory claim: the KV pools carry a
        NamedSharding splitting the KV-head axis (per-device bytes halve),
        column/row-parallel weights shard while biases of row-parallel
        layers replicate, and the compiled programs all-reduce."""
        srv = inference_engine.serve(dict(BASE, placement={"tp": 2}))
        srv._ensure_compiled()
        shard_shape = srv.k_pool.sharding.shard_shape(srv.k_pool.shape)
        assert shard_shape[2] * 2 == srv.k_pool.shape[2]
        ps = srv.decode_set
        w = ps.params["blocks"]["attn"]["c_attn_w"]
        assert w.sharding.shard_shape(w.shape)[-1] * 2 == w.shape[-1]
        b = ps.params["blocks"]["attn"]["c_proj_b"]
        assert b.sharding.shard_shape(b.shape) == b.shape  # replicated
        for name, exe in srv.executable_names():
            assert name.endswith("_tp2")
            assert "all-reduce" in exe.as_text()

    def test_tp_collective_bytes_gauge_set(self, inference_engine):
        srv = inference_engine.serve(dict(BASE, placement={"tp": 2}))
        srv._ensure_compiled()
        mc = srv.model_config
        # 2 psums/layer x B x S x n_embd x itemsize(f32)
        expect = 2 * mc.n_layer * 1 * srv.prefill_width * mc.n_embd * 4
        assert srv._g_tp_coll.value(program="serving_prefill_tp2") == expect

    def test_quantized_weights_rejected_at_tp2(self, tiny_cfg):
        from deepspeed_tpu.inference.engine import InferenceEngine

        params = gpt2.init_params(tiny_cfg, jax.random.PRNGKey(0))
        eng = InferenceEngine(
            gpt2.make_module(tiny_cfg), params=params, dtype=jnp.float32,
            quantize_bits=8,
        )
        with pytest.raises(ValueError, match="unquantized"):
            eng.serve(dict(BASE, placement={"tp": 2}))

    def test_too_many_devices_rejected(self, inference_engine):
        with pytest.raises(ValueError, match="devices"):
            inference_engine.serve(dict(BASE, placement={"tp": 16}))


@needs_8_devices
class TestDisaggregatedPlacements:
    def test_disaggregated_token_parity_all_features(
        self, tiny_cfg, inference_engine
    ):
        """Prefill and decode on separate core-sets (KV handoff through the
        gather→device_put→scatter pair) re-emit the shared-placement
        streams, at TP=1 and TP=2, and count one handoff per admission."""
        cfg = dict(BASE, **ALL_FEATURES)
        reqs = _mixed_requests(tiny_cfg.vocab_size)
        base = _streams(inference_engine.serve(cfg), reqs)
        for tp in (1, 2):
            srv = inference_engine.serve(
                dict(cfg, placement={"tp": tp, "disaggregate": True})
            )
            assert _streams(srv, reqs) == base, f"tp={tp} diverged"
            st = srv.stats()
            assert st["kv_handoffs"] > 0
            assert st["kv_handoff_bytes"] > 0
            assert st["placement"]["disaggregated"] is True
            assert set(st["placement"]["placements"]) == {"prefill", "decode"}

    def test_disaggregated_drain_zero_leaks_mid_load(
        self, tiny_cfg, inference_engine
    ):
        """The SIGTERM-shaped invariant: drain with requests mid-prefill,
        mid-handoff and mid-decode — BOTH allocators end clean (prefix
        index holdings on the prefill side only; the decode pool drains to
        empty — a page left there is a leaked handoff reservation)."""
        srv = inference_engine.serve(dict(
            BASE, **ALL_FEATURES,
            placement={"disaggregate": True},
        ))
        rs = np.random.RandomState(11)
        for i in range(12):
            srv.submit(
                rs.randint(0, tiny_cfg.vocab_size, (6 + (i % 5),)).astype(np.int32),
                max_new_tokens=8, seed=i,
            )
        srv.step()
        srv.step()
        srv.drain(deadline_s=0.0)
        srv.release_prefix_cache()
        srv.check_no_leaks()

    def test_disaggregated_verify_clean_and_handoff_programs(
        self, inference_engine
    ):
        """TP=2 disaggregated compiles the full program set of an engine
        that chunks its cold prompts (verify-or-decode + chunk + gather +
        scatter; no whole-prompt program: the chunk program runs on the
        prefill placement and leaves its token for the handoff), verifies
        clean through Engines A/D/E/F, and names programs per placement."""
        srv = inference_engine.serve(dict(
            BASE, **ALL_FEATURES,
            placement={"tp": 2, "disaggregate": True},
        ))
        assert srv.verify() == []
        names = [n for n, _ in srv.executable_names()]
        assert names == [
            "serving_verify_tp2",
            "serving_chunk_prefill_tp2", "serving_kv_gather_tp2",
            "serving_kv_scatter_tp2",
        ]
        assert len(srv.executables) == srv.expected_executables == 4

    def test_handoff_trace_span(self, tiny_cfg, inference_engine, tmp_path):
        """The kv_handoff span lands in the PR-11 request trace with pages,
        bytes and latency."""
        import json

        from deepspeed_tpu.telemetry.request_trace import RequestTracer

        path = str(tmp_path / "trace.jsonl")
        tracer = RequestTracer(path)
        srv = inference_engine.serve(
            dict(BASE, placement={"disaggregate": True}), tracer=tracer,
        )
        srv.submit(np.arange(1, 7, dtype=np.int32), max_new_tokens=4, seed=0)
        srv.run()
        tracer.flush()
        recs = [json.loads(x) for x in open(path)]
        spans = [
            e for r in recs for e in r.get("events", [])
            if e.get("e") == "kv_handoff"
        ]
        assert spans and spans[0]["pages"] >= 1
        assert spans[0]["bytes"] > 0 and spans[0]["latency_s"] >= 0


@needs_8_devices
class TestShardingAnalysisPlane:
    def test_engine_d_sharded_pair_collective_order(self, inference_engine):
        """Engine D over the TP=2 program set: every program all-reduces in
        the same per-layer order (2 psums/layer, by construction), so the
        cross-program collective-order check returns no findings."""
        from deepspeed_tpu import analysis as dsa

        srv = inference_engine.serve(
            dict(BASE, **ALL_FEATURES, placement={"tp": 2})
        )
        srv._ensure_compiled()
        texts = {n: e.as_text() for n, e in srv.executable_names()}
        assert all("all-reduce" in t for t in texts.values())
        assert dsa.verify_program_set(texts) == []

    def test_engine_f_precompile_fires_on_broken_table(self, inference_engine):
        """Satellite 1: a deliberately broken analysis.sharding.rules table
        must fire all three rule families — dead regex
        (unmatched-param-rule), wrong-rank spec (spec-rank-mismatch), and a
        large leaf left replicated (replicated-large-leaf) — and must fire
        BEFORE compile (the engine still has no executables after)."""
        srv = inference_engine.serve(dict(BASE, placement={"tp": 2}))
        broken = {
            "sharding": {
                "rules": [
                    ["no/such/param$", [None, "tp"]],  # dead regex
                    ["attn/c_attn_w$", [None, None, None, "tp"]],  # rank 4 vs 3
                    ["", []],                          # everything replicated
                ],
                "replicated_min_bytes": 1024,
            },
        }
        findings = srv.verify(broken)
        kinds = {f.rule for f in findings}
        assert "unmatched-param-rule" in kinds
        assert "spec-rank-mismatch" in kinds
        assert "replicated-large-leaf" in kinds
        assert not srv.executables and not srv._program_info  # pre-compile: nothing traced

    def test_committed_table_verifies_clean_pre_compile(self, inference_engine):
        """The committed GPT2_SERVING_RULES pass Engine F for the real tree
        on a tp=2 mesh (the same table the placement shards with — one
        resolution path, so verifier and placement cannot disagree)."""
        from deepspeed_tpu.serving.placement import (
            GPT2_SERVING_RULES,
            Placement,
        )

        plc = Placement("t", jax.devices()[:2], 2)
        assert plc.rules == GPT2_SERVING_RULES
        assert plc.verify_rules(inference_engine.params) == []

    def test_engine_e_tp2_pools_categorized_and_doubled_pin_red(
        self, tiny_cfg, inference_engine
    ):
        """Engine E at TP=2: the ledger's kv-pool category holds the
        per-DEVICE pool bytes (half the global pool), and doubling
        num_pages busts the committed serving_*_tp2 pins exactly as the
        single-device pins catch the unsharded engine."""
        from deepspeed_tpu.serving.kv_cache import pool_bytes

        srv = inference_engine.serve(dict(BASE, placement={"tp": 2}))
        assert srv.verify() == []
        rep = srv.memory_report()
        global_pool = pool_bytes(
            tiny_cfg.n_layer, BASE["num_pages"], tiny_cfg.n_head,
            BASE["page_size"], tiny_cfg.head_dim, itemsize=4,
        )
        for name in ("serving_prefill_tp2", "serving_decode_tp2"):
            assert rep[name]["kv_pool_bytes"] == global_pool // 2
        srv_big = inference_engine.serve(
            dict(BASE, num_pages=128, placement={"tp": 2})
        )
        findings = srv_big.verify()
        assert any(f.rule == "hbm-over-budget" for f in findings)
