"""Parity tests: every injection policy vs the HF transformers reference.

Reference analog: tests/unit/inference/test_inference.py (parametrized over
HF models, injected vs vanilla outputs).
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

warnings.filterwarnings("ignore")

torch = pytest.importorskip("torch")


def _hf(cls_name, cfg_name, kw):
    import transformers

    cfg = getattr(transformers, cfg_name)(**kw)
    model = getattr(transformers, cls_name)(cfg)
    model.eval()
    return model


def _assert_logits_parity(hf_model, atol=5e-3):
    from deepspeed_tpu.models import decoder
    from deepspeed_tpu.module_inject import replace_transformer_layer

    torch.manual_seed(0)
    kind, cfg, params = replace_transformer_layer(hf_model, dtype=jnp.float32)
    assert kind == "decoder"
    rs = np.random.RandomState(0)
    ids = rs.randint(0, cfg.vocab_size, (2, 10))
    with torch.no_grad():
        ref = hf_model(torch.tensor(ids)).logits.numpy()
    ours = np.asarray(decoder.forward(cfg, params, jnp.asarray(ids, jnp.int32)))
    diff = np.abs(ours - ref).max()
    assert diff < atol, f"max logits diff {diff}"
    return cfg, params, ids, ref


class TestOPT:
    def test_parity(self):
        m = _hf("OPTForCausalLM", "OPTConfig", dict(
            hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
            vocab_size=512, ffn_dim=256, max_position_embeddings=128,
            word_embed_proj_dim=64, dropout=0.0, activation_function="relu",
        ))
        _assert_logits_parity(m)

    def test_generate_parity(self):
        from deepspeed_tpu.inference.engine import InferenceEngine

        m = _hf("OPTForCausalLM", "OPTConfig", dict(
            hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
            vocab_size=512, ffn_dim=256, max_position_embeddings=128,
            word_embed_proj_dim=64, dropout=0.0,
        ))
        eng = InferenceEngine(model=m, replace_with_kernel_inject=True, dtype=jnp.float32)
        ids = np.random.RandomState(1).randint(4, 500, (1, 8))
        with torch.no_grad():
            ref = m.generate(torch.tensor(ids), max_new_tokens=5, do_sample=False, pad_token_id=1).numpy()
        ours = eng.generate(ids, max_new_tokens=5)
        assert np.array_equal(ours, ref), (ours, ref)


class TestBloom:
    def test_parity(self):
        m = _hf("BloomForCausalLM", "BloomConfig", dict(
            hidden_size=64, n_layer=2, n_head=4, vocab_size=512,
            hidden_dropout=0.0, attention_dropout=0.0,
        ))
        _assert_logits_parity(m)


class TestGPTJ:
    def test_parity(self):
        m = _hf("GPTJForCausalLM", "GPTJConfig", dict(
            n_embd=64, n_layer=2, n_head=4, vocab_size=512,
            rotary_dim=16, n_positions=128,
            resid_pdrop=0.0, embd_pdrop=0.0, attn_pdrop=0.0,
        ))
        _assert_logits_parity(m)


class TestGPTNeo:
    def test_parity_with_local_attention(self):
        m = _hf("GPTNeoForCausalLM", "GPTNeoConfig", dict(
            hidden_size=64, num_layers=2, num_heads=4, vocab_size=512,
            attention_types=[[["global", "local"], 1]],
            max_position_embeddings=128, window_size=4,
            resid_dropout=0.0, embed_dropout=0.0, attention_dropout=0.0,
        ))
        # seq 10 > window 4 so the local mask matters
        _assert_logits_parity(m)


class TestGPTNeoX:
    def test_parity(self):
        m = _hf("GPTNeoXForCausalLM", "GPTNeoXConfig", dict(
            hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
            vocab_size=512, intermediate_size=256, rotary_pct=0.25,
            max_position_embeddings=128,
            hidden_dropout=0.0, attention_dropout=0.0,
        ))
        _assert_logits_parity(m)


class TestMegatron:
    def test_state_dict_convert(self):
        """Synthetic Megatron-LM GPT-2 layout → decoder (no megatron dep)."""
        from deepspeed_tpu.models import decoder
        from deepspeed_tpu.module_inject.replace_policy import MegatronLayerPolicy

        rs = np.random.RandomState(0)
        E, H, L, V, F, P = 32, 4, 2, 128, 128, 64
        sd = {
            "language_model.embedding.word_embeddings.weight": rs.randn(V, E) * 0.02,
            "language_model.embedding.position_embeddings.weight": rs.randn(P, E) * 0.02,
            "language_model.transformer.final_layernorm.weight": np.ones(E),
            "language_model.transformer.final_layernorm.bias": np.zeros(E),
        }
        for i in range(L):
            p = f"language_model.transformer.layers.{i}."
            sd.update({
                p + "input_layernorm.weight": np.ones(E), p + "input_layernorm.bias": np.zeros(E),
                p + "post_attention_layernorm.weight": np.ones(E), p + "post_attention_layernorm.bias": np.zeros(E),
                p + "attention.query_key_value.weight": rs.randn(3 * E, E) * 0.02,
                p + "attention.query_key_value.bias": np.zeros(3 * E),
                p + "attention.dense.weight": rs.randn(E, E) * 0.02,
                p + "attention.dense.bias": np.zeros(E),
                p + "mlp.dense_h_to_4h.weight": rs.randn(F, E) * 0.02,
                p + "mlp.dense_h_to_4h.bias": np.zeros(F),
                p + "mlp.dense_4h_to_h.weight": rs.randn(E, F) * 0.02,
                p + "mlp.dense_4h_to_h.bias": np.zeros(E),
            })
        kind, cfg, params = MegatronLayerPolicy.convert_state_dict(sd, n_head=H)
        assert kind == "decoder" and cfg.n_layer == L and cfg.ffn_dim == F
        ids = rs.randint(0, V, (2, 8))
        logits = decoder.forward(cfg, params, jnp.asarray(ids, jnp.int32))
        assert logits.shape == (2, 8, V)
        assert np.isfinite(np.asarray(logits)).all()


class TestBert:
    def test_parity(self):
        from deepspeed_tpu.models import bert as ds_bert
        from deepspeed_tpu.module_inject import replace_transformer_layer

        m = _hf("BertModel", "BertConfig", dict(
            hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
            vocab_size=512, intermediate_size=256, max_position_embeddings=128,
            hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
        ))
        kind, cfg, params = replace_transformer_layer(m, dtype=jnp.float32)
        assert kind == "bert"
        rs = np.random.RandomState(0)
        ids = rs.randint(0, 512, (2, 10))
        mask = np.ones((2, 10), np.int32)
        mask[1, 7:] = 0
        with torch.no_grad():
            out = m(torch.tensor(ids), attention_mask=torch.tensor(mask))
            ref_h = out.last_hidden_state.numpy()
            ref_p = out.pooler_output.numpy()
        h, pooled = ds_bert.forward(
            cfg, params, jnp.asarray(ids, jnp.int32), jnp.asarray(mask), None
        )
        # compare only unmasked positions (HF computes masked ones too but
        # they're meaningless downstream)
        assert np.abs(np.asarray(h)[mask == 1] - ref_h[mask == 1]).max() < 5e-3
        assert np.abs(np.asarray(pooled) - ref_p).max() < 5e-3


    def test_unmasked_kernel_branch_matches_jnp(self, monkeypatch):
        """BERT's bidirectional flash branch (TPU-only) forced on CPU with
        the interpret kernel: must match the jnp encoder path exactly."""
        import functools

        import deepspeed_tpu.ops.attention as attn
        import deepspeed_tpu.ops.pallas.flash_attention as fa
        from deepspeed_tpu.models import bert as ds_bert
        from deepspeed_tpu.module_inject import replace_transformer_layer

        m = _hf("BertModel", "BertConfig", dict(
            hidden_size=256, num_hidden_layers=2, num_attention_heads=4,
            vocab_size=512, intermediate_size=256, max_position_embeddings=128,
            hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
        ))
        _, cfg, params = replace_transformer_layer(m, dtype=jnp.float32)
        ids = jnp.asarray(
            np.random.RandomState(4).randint(0, 512, (2, 128)), jnp.int32
        )
        base, _ = ds_bert.forward(cfg, params, ids, None, None)
        monkeypatch.setattr(attn, "_pallas_ok", lambda q: True)
        monkeypatch.setattr(
            fa, "flash_attention", functools.partial(fa.flash_attention, interpret=True)
        )
        forced, _ = ds_bert.forward(cfg, params, ids, None, None)
        np.testing.assert_allclose(
            np.asarray(forced), np.asarray(base), atol=2e-4, rtol=2e-4
        )


class TestBertPretraining:
    """BERT MLM+NSP pretraining through the engine (the reference's headline
    workload; docs/_pages/training.md:42)."""

    def _batch(self, cfg, B=8, seed=0):
        rs = np.random.RandomState(seed)
        S = 32
        ids = rs.randint(0, cfg.vocab_size, (B, S)).astype(np.int32)
        labels = np.full((B, S), -100, np.int32)
        mask_pos = rs.rand(B, S) < 0.15
        labels[mask_pos] = ids[mask_pos]
        ids[mask_pos] = 3  # [MASK]-style token
        return {
            "input_ids": ids,
            "labels": labels,
            "attention_mask": np.ones((B, S), np.int32),
            "next_sentence_label": rs.randint(0, 2, (B,)).astype(np.int32),
        }

    def test_loss_decreases_under_engine(self, mesh_dp8):
        from deepspeed_tpu.models import bert
        from deepspeed_tpu.runtime.config import DeepSpeedConfig
        from deepspeed_tpu.runtime.engine import DeepSpeedEngine

        cfg = bert.get_config("bert-tiny", pretraining=True)
        module = bert.make_module(cfg)
        ds = DeepSpeedConfig.load(
            {
                "train_micro_batch_size_per_gpu": 1,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 1},
            },
            dp_world_size=8,
        )
        eng = DeepSpeedEngine(module, ds, mesh=mesh_dp8, seed=0)
        b = self._batch(cfg, B=eng.train_batch_size)
        losses = [float(jax.device_get(eng.train_batch(b)["loss"])) for _ in range(8)]
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0], losses

    def test_mlm_only_without_nsp_label(self):
        from deepspeed_tpu.models import bert

        cfg = bert.get_config("bert-tiny", pretraining=True)
        params = bert.init_params(cfg, jax.random.PRNGKey(0))
        b = self._batch(cfg, B=2)
        b.pop("next_sentence_label")
        loss, metrics = bert.pretraining_loss(cfg, params, b)
        assert np.isfinite(float(loss))
        assert "nsp_loss" not in metrics

    def test_inference_path_unchanged_without_flag(self):
        from deepspeed_tpu.models import bert

        cfg = bert.get_config("bert-tiny")
        params = bert.init_params(cfg, jax.random.PRNGKey(0))
        assert "mlm" not in params
        module = bert.make_module(cfg)
        assert module.loss_fn is None


class TestDecoderChunkedCE:
    def test_decoder_ce_chunk_matches_full(self):
        from dataclasses import replace

        from deepspeed_tpu.models import decoder

        cfg = decoder.DecoderConfig(
            vocab_size=128, n_positions=64, n_embd=32, n_layer=2, n_head=4,
            ffn_dim=64, pos_emb="rope",
        )
        rs = np.random.RandomState(2)
        L, E, F = cfg.n_layer, cfg.n_embd, cfg.ffn_dim
        nrm = lambda *sh: jnp.asarray(rs.randn(*sh) * 0.05, jnp.float32)
        ln = lambda: {"scale": jnp.ones((L, E)), "bias": jnp.zeros((L, E))}
        params = {
            "wte": nrm(cfg.vocab_size, E),
            "blocks": {
                "ln_1": ln(), "ln_2": ln(),
                "attn": {"wq": nrm(L, E, E), "wk": nrm(L, E, E),
                         "wv": nrm(L, E, E), "wo": nrm(L, E, E)},
                "mlp": {"fc_in_w": nrm(L, E, F), "fc_out_w": nrm(L, F, E)},
            },
            "ln_f": {"scale": jnp.ones((E,)), "bias": jnp.zeros((E,))},
        }
        ids = rs.randint(0, cfg.vocab_size, (2, 50)).astype(np.int32)
        batch = {"input_ids": ids}

        def loss(cfg_):
            return lambda p: decoder.lm_loss(cfg_, p, batch, None, True)[0]

        # (one program a config: eager, the scan's pieces dispatch one op at a time)
        l_full, g_full = jax.jit(jax.value_and_grad(loss(cfg)))(params)
        cfg_c = replace(cfg, ce_chunk=16)  # 49 positions → pad path
        l_chunk, g_chunk = jax.jit(jax.value_and_grad(loss(cfg_c)))(params)
        np.testing.assert_allclose(float(l_full), float(l_chunk), rtol=1e-6)
        for gf, gc in zip(jax.tree.leaves(g_full), jax.tree.leaves(g_chunk)):
            np.testing.assert_allclose(np.asarray(gf), np.asarray(gc), atol=1e-5, rtol=1e-4)


class TestDecoderEngineTraining:
    """Fine-tuning a converted decoder-zoo model through the engine (the
    reference's 'bring your HF model to deepspeed.initialize' use case)."""

    def test_decoder_trains_and_loss_drops(self, mesh_dp8):
        from deepspeed_tpu.models import decoder
        from deepspeed_tpu.runtime.config import DeepSpeedConfig
        from deepspeed_tpu.runtime.engine import DeepSpeedEngine

        cfg = decoder.DecoderConfig(
            vocab_size=256, n_positions=64, n_embd=32, n_layer=2, n_head=4,
            ffn_dim=64, pos_emb="rope", ce_chunk=16,
        )
        rs = np.random.RandomState(0)
        L, E, F = cfg.n_layer, cfg.n_embd, cfg.ffn_dim
        nrm = lambda *sh: jnp.asarray(rs.randn(*sh) * 0.05, jnp.float32)
        ln = lambda: {"scale": jnp.ones((L, E)), "bias": jnp.zeros((L, E))}
        params = {
            "wte": nrm(cfg.vocab_size, E),
            "blocks": {
                "ln_1": ln(), "ln_2": ln(),
                "attn": {"wq": nrm(L, E, E), "wk": nrm(L, E, E),
                         "wv": nrm(L, E, E), "wo": nrm(L, E, E)},
                "mlp": {"fc_in_w": nrm(L, E, F), "fc_out_w": nrm(L, F, E)},
            },
            "ln_f": {"scale": jnp.ones((E,)), "bias": jnp.zeros((E,))},
        }
        ds = DeepSpeedConfig.load(
            {"train_micro_batch_size_per_gpu": 1,
             "optimizer": {"type": "AdamW", "params": {"lr": 2e-3}},
             "zero_optimization": {"stage": 2}},
            dp_world_size=8,
        )
        eng = DeepSpeedEngine(
            decoder.make_module(cfg), ds, mesh=mesh_dp8, params=params, seed=0
        )
        b = {"input_ids": rs.randint(0, cfg.vocab_size, (8, 32)).astype(np.int32)}
        losses = [float(jax.device_get(eng.train_batch(b)["loss"])) for _ in range(8)]
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0], losses


class TestLlama:
    """LLaMA-family conversion: RMSNorm + SwiGLU + GQA + neox RoPE with
    rope_theta — numerical parity vs transformers (beyond the reference
    snapshot's newest arch)."""

    def _tiny(self, **kw):
        base = dict(
            hidden_size=32, num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, intermediate_size=64, vocab_size=128,
            max_position_embeddings=64, rope_theta=10000.0,
            tie_word_embeddings=False,
        )
        base.update(kw)
        return _hf("LlamaForCausalLM", "LlamaConfig", base)

    def test_logits_parity_gqa(self):
        cfg, params, ids, ref = _assert_logits_parity(self._tiny(), atol=5e-3)
        assert cfg.norm == "rmsnorm" and cfg.mlp_type == "swiglu"
        assert cfg.n_kv_head == 2 and cfg.kv_heads == 2

    def test_logits_parity_mha_and_theta(self):
        _assert_logits_parity(
            self._tiny(num_key_value_heads=4, rope_theta=50000.0), atol=5e-3
        )

    def test_generate_matches_hf_greedy(self):
        from deepspeed_tpu.models import decoder
        from deepspeed_tpu.module_inject import replace_transformer_layer

        hf_model = self._tiny()
        kind, cfg, params = replace_transformer_layer(hf_model, dtype=jnp.float32)
        rs = np.random.RandomState(1)
        ids = rs.randint(0, cfg.vocab_size, (1, 6))
        with torch.no_grad():
            ref = hf_model.generate(
                torch.tensor(ids), max_new_tokens=6, do_sample=False,
                pad_token_id=0,
            ).numpy()
        ours = np.asarray(
            decoder.generate(cfg, params, jnp.asarray(ids, jnp.int32), 6,
                             cache_dtype=jnp.float32)
        )
        np.testing.assert_array_equal(ours, ref[:, ids.shape[1]:])

    def test_gqa_prefill_kernel_branch_matches_einsum(self, monkeypatch):
        """The decoder's GQA full-seq kernel branch (normally TPU-only)
        forced on CPU via an interpret-mode kernel: must reproduce the
        grouped-einsum path exactly — covers the decoder→dispatcher→GQA
        flash chain that otherwise only runs on a chip."""
        import functools

        import deepspeed_tpu.ops.attention as attn
        import deepspeed_tpu.ops.pallas.flash_attention as fa
        from deepspeed_tpu.models import decoder
        from deepspeed_tpu.module_inject import replace_transformer_layer

        hf_model = self._tiny(
            hidden_size=256, intermediate_size=256, max_position_embeddings=128
        )
        _, cfg, params = replace_transformer_layer(hf_model, dtype=jnp.float32)
        assert cfg.kv_heads < cfg.n_head and cfg.head_dim == 64
        ids = jnp.asarray(
            np.random.RandomState(3).randint(0, cfg.vocab_size, (1, 128)), jnp.int32
        )
        base = decoder.forward(cfg, params, ids)  # grouped-einsum path on CPU
        flash_interp = functools.partial(fa.flash_attention, interpret=True)
        monkeypatch.setattr(attn, "_pallas_ok", lambda q: True)
        monkeypatch.setattr(attn, "pallas_attention_ok", lambda q: True)
        monkeypatch.setattr(fa, "flash_attention", flash_interp)
        forced = decoder.forward(cfg, params, ids)
        np.testing.assert_allclose(
            np.asarray(forced), np.asarray(base), atol=2e-4, rtol=2e-4
        )

    def test_inert_sliding_window_rides_kernel_branch(self, monkeypatch):
        """Mistral declares sliding_window=4096; at train lengths inside the
        window the mask is a no-op, so the decoder must take the flash
        kernel branch (forced on CPU via interpret) and match the windowed
        einsum path exactly."""
        import functools

        import deepspeed_tpu.ops.attention as attn
        import deepspeed_tpu.ops.pallas.flash_attention as fa
        from deepspeed_tpu.models import decoder
        from deepspeed_tpu.module_inject import replace_transformer_layer

        S = 128
        hf_model = _hf("MistralForCausalLM", "MistralConfig", dict(
            hidden_size=256, num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, intermediate_size=256, vocab_size=128,
            max_position_embeddings=S, sliding_window=S,  # window == seq: inert
        ))
        _, cfg, params = replace_transformer_layer(hf_model, dtype=jnp.float32)
        assert cfg.local_windows and all(w == S for w in cfg.local_windows)
        assert decoder._windows_inert(cfg, S) and not decoder._windows_inert(cfg, S + 1)
        ids = jnp.asarray(
            np.random.RandomState(5).randint(0, cfg.vocab_size, (1, S)), jnp.int32
        )
        base = decoder.forward(cfg, params, ids)  # windowed einsum path on CPU
        flash_interp = functools.partial(fa.flash_attention, interpret=True)
        monkeypatch.setattr(attn, "_pallas_ok", lambda q: True)
        monkeypatch.setattr(attn, "pallas_attention_ok", lambda q: True)
        monkeypatch.setattr(fa, "flash_attention", flash_interp)
        forced = decoder.forward(cfg, params, ids)
        np.testing.assert_allclose(
            np.asarray(forced), np.asarray(base), atol=2e-4, rtol=2e-4
        )

    def test_local_windows_ride_windowed_kernel_branch(self, monkeypatch):
        """GPT-Neo-style alternating local/global layers (window < seq, NOT
        inert): the per-layer traced window flows into the windowed flash
        kernel (forced on CPU via interpret) and must reproduce the masked
        einsum path exactly — one compiled kernel serves both layer kinds."""
        import functools

        import deepspeed_tpu.ops.attention as attn
        import deepspeed_tpu.ops.pallas.flash_attention as fa
        from deepspeed_tpu.models import decoder

        S = 128
        cfg = decoder.DecoderConfig(
            vocab_size=128, n_positions=S, n_embd=128, n_layer=2, n_head=2,
            ffn_dim=128, pos_emb="rope", local_windows=(8, 0),
        )
        rs = np.random.RandomState(7)
        L, E, F = cfg.n_layer, cfg.n_embd, cfg.ffn_dim
        nrm = lambda *sh: jnp.asarray(rs.randn(*sh) * 0.05, jnp.float32)
        ln = lambda: {"scale": jnp.ones((L, E)), "bias": jnp.zeros((L, E))}
        params = {
            "wte": nrm(cfg.vocab_size, E),
            "blocks": {
                "ln_1": ln(), "ln_2": ln(),
                "attn": {"wq": nrm(L, E, E), "wk": nrm(L, E, E),
                         "wv": nrm(L, E, E), "wo": nrm(L, E, E)},
                "mlp": {"fc_in_w": nrm(L, E, F), "fc_out_w": nrm(L, F, E)},
            },
            "ln_f": {"scale": jnp.ones((E,)), "bias": jnp.zeros((E,))},
        }
        assert not decoder._windows_inert(cfg, S)
        ids = jnp.asarray(rs.randint(0, cfg.vocab_size, (1, S)), jnp.int32)
        base = decoder.forward(cfg, params, ids)  # masked einsum path on CPU
        flash_interp = functools.partial(fa.flash_attention, interpret=True)
        monkeypatch.setattr(attn, "windowed_attention_ok", lambda q: True)
        monkeypatch.setattr(fa, "flash_attention", flash_interp)
        forced = decoder.forward(cfg, params, ids)
        np.testing.assert_allclose(
            np.asarray(forced), np.asarray(base), atol=2e-4, rtol=2e-4
        )

    def test_gqa_cache_is_kv_headed(self):
        from deepspeed_tpu.models import decoder
        from deepspeed_tpu.module_inject import replace_transformer_layer

        _, cfg, _ = replace_transformer_layer(self._tiny(), dtype=jnp.float32)
        cache = decoder.init_cache(cfg, 1, 16, dtype=jnp.float32)
        assert cache.k.shape == (2, 1, 16, 2, 8)  # kv_heads=2, not 4

    def test_mistral_sliding_window_maps(self):
        from deepspeed_tpu.module_inject import replace_transformer_layer

        hf_model = _hf("MistralForCausalLM", "MistralConfig", dict(
            hidden_size=32, num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, intermediate_size=64, vocab_size=128,
            max_position_embeddings=64, sliding_window=4,
        ))
        kind, cfg, params = replace_transformer_layer(hf_model, dtype=jnp.float32)
        assert kind == "decoder"
        assert cfg.local_windows == (4, 4)  # window < seq so masking is exercised
        _assert_logits_parity(hf_model, atol=5e-3)


class TestMixtral:
    """Mixtral: SwiGLU MoE decoder with GQA — logits parity vs transformers
    (routing must match exactly: top-2 argmax, no drop, renormalized)."""

    def _tiny(self):
        return _hf("MixtralForCausalLM", "MixtralConfig", dict(
            hidden_size=32, num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, intermediate_size=64, vocab_size=128,
            max_position_embeddings=64, num_local_experts=4,
            num_experts_per_tok=2, sliding_window=None,
        ))

    def test_logits_parity(self):
        cfg, params, ids, ref = _assert_logits_parity(self._tiny(), atol=5e-3)
        assert cfg.mlp_type == "moe_swiglu" and cfg.moe_experts == 4

    def test_generate_matches_hf_greedy(self):
        from deepspeed_tpu.models import decoder
        from deepspeed_tpu.module_inject import replace_transformer_layer

        hf_model = self._tiny()
        kind, cfg, params = replace_transformer_layer(hf_model, dtype=jnp.float32)
        rs = np.random.RandomState(4)
        ids = rs.randint(0, cfg.vocab_size, (1, 5))
        with torch.no_grad():
            ref = hf_model.generate(
                torch.tensor(ids), max_new_tokens=5, do_sample=False,
                pad_token_id=0,
            ).numpy()
        ours = np.asarray(
            decoder.generate(cfg, params, jnp.asarray(ids, jnp.int32), 5,
                             cache_dtype=jnp.float32)
        )
        np.testing.assert_array_equal(ours, ref[:, ids.shape[1]:])

    def test_expert_sharded_serving_matches(self):
        """init_inference(ep_size=2): expert-sharded Mixtral equals the
        unsharded forward (GSPMD inserts the expert all-to-alls)."""
        import deepspeed_tpu

        hf_model = self._tiny()
        rs = np.random.RandomState(7)
        ids = rs.randint(0, 128, (1, 6)).astype(np.int32)
        eng = deepspeed_tpu.init_inference(hf_model, ep_size=2,
                                           config={"dtype": "fp32"})
        lg = np.asarray(eng({"input_ids": ids}))
        with torch.no_grad():
            ref = hf_model(torch.tensor(ids.astype(np.int64))).logits.numpy()
        assert np.abs(lg - ref).max() < 5e-3
