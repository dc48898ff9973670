"""GPT-2 model family tests: forward shapes, loss, TP/ZeRO sharded parity."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import gpt2
from deepspeed_tpu.runtime.config import DeepSpeedConfig
from deepspeed_tpu.runtime.engine import DeepSpeedEngine

from .simple_model import base_config


def _batch(bs, seq, vocab, seed=0):
    rs = np.random.RandomState(seed)
    return {"input_ids": rs.randint(0, vocab, size=(bs, seq)).astype(np.int32)}


def test_forward_shapes():
    cfg = gpt2.get_config("gpt2-tiny")
    module = gpt2.make_module(cfg)
    params = module.init(jax.random.PRNGKey(0))
    b = _batch(2, 16, cfg.vocab_size)
    logits = module.apply_fn(params, b)
    assert logits.shape == (2, 16, cfg.vocab_size)


def test_loss_near_uniform_at_init():
    cfg = gpt2.get_config("gpt2-tiny")
    module = gpt2.make_module(cfg)
    params = module.init(jax.random.PRNGKey(0))
    b = _batch(4, 32, cfg.vocab_size)
    loss, _ = module.loss_fn(params, b, jax.random.PRNGKey(1), False)
    assert abs(float(loss) - np.log(cfg.vocab_size)) < 1.0


def test_causality():
    """Changing a future token must not change earlier logits."""
    cfg = gpt2.get_config("gpt2-tiny")
    module = gpt2.make_module(cfg)
    params = module.init(jax.random.PRNGKey(0))
    b1 = _batch(1, 16, cfg.vocab_size, seed=1)
    b2 = {"input_ids": b1["input_ids"].copy()}
    b2["input_ids"][0, -1] = (b2["input_ids"][0, -1] + 1) % cfg.vocab_size
    l1 = module.apply_fn(params, b1)
    l2 = module.apply_fn(params, b2)
    np.testing.assert_allclose(l1[0, :-1], l2[0, :-1], atol=1e-5)


def test_labels_ignore_index():
    cfg = gpt2.get_config("gpt2-tiny")
    module = gpt2.make_module(cfg)
    params = module.init(jax.random.PRNGKey(0))
    b = _batch(2, 16, cfg.vocab_size)
    b["labels"] = np.full_like(b["input_ids"], -100)
    b["labels"][:, :4] = b["input_ids"][:, :4]
    loss, aux = module.loss_fn(params, b, jax.random.PRNGKey(1), False)
    assert float(aux["ntokens"]) == 2 * 3  # positions 1..3 predicted (shift)


@pytest.mark.parametrize("stage", [0, 3])
def test_gpt2_train_parity_tp_zero(stage, mesh_dp4_tp2, mesh_single):
    """GPT-2 tiny: dp4×tp2 mesh training == single-device training."""
    cfg = gpt2.get_config("gpt2-tiny")
    losses = {}
    for name, (mesh, dp) in {"sharded": (mesh_dp4_tp2, 4), "single": (mesh_single, 1)}.items():
        module = gpt2.make_module(cfg)
        ds = DeepSpeedConfig.load(
            {
                "train_micro_batch_size_per_gpu": 8 // dp,  # same global batch (16)
                "gradient_accumulation_steps": 2,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3, "weight_decay": 0.01}},
                "zero_optimization": {"stage": stage},
                "steps_per_print": 1000,
            },
            dp_world_size=dp,
        )
        engine = DeepSpeedEngine(module, ds, mesh=mesh, seed=3)
        b = _batch(engine.train_batch_size, 32, cfg.vocab_size, seed=5)
        losses[name] = [float(engine.train_batch(b)["loss"]) for _ in range(3)]
    np.testing.assert_allclose(losses["sharded"], losses["single"], rtol=2e-4)


def test_remat_matches_no_remat():
    cfg_a = gpt2.get_config("gpt2-tiny", remat=False)
    cfg_b = gpt2.get_config("gpt2-tiny", remat=True)
    ma, mb = gpt2.make_module(cfg_a), gpt2.make_module(cfg_b)
    params = ma.init(jax.random.PRNGKey(0))
    b = _batch(2, 16, cfg_a.vocab_size)

    def loss_a(p):
        return ma.loss_fn(p, b, jax.random.PRNGKey(1), True)[0]

    def loss_b(p):
        return mb.loss_fn(p, b, jax.random.PRNGKey(1), True)[0]

    ga = jax.grad(loss_a)(params)
    gb = jax.grad(loss_b)(params)
    jax.tree.map(lambda x, y: np.testing.assert_allclose(x, y, rtol=1e-5, atol=1e-6), ga, gb)


class TestChunkedCE:
    """ce_chunk computes the same loss/grads as the full-logits path while
    never materializing [B,S,V] logits."""

    def test_loss_and_grads_match_full(self):
        from deepspeed_tpu.models import gpt2

        cfg_full = gpt2.get_config("gpt2-tiny")
        cfg_chunk = gpt2.get_config("gpt2-tiny", ce_chunk=48)  # non-divisor: pad path
        params = gpt2.init_params(cfg_full, jax.random.PRNGKey(0))
        rs = np.random.RandomState(0)
        ids = rs.randint(0, cfg_full.vocab_size, (2, 100)).astype(np.int32)
        labels = ids.copy()
        labels[:, :10] = -100
        batch = {"input_ids": ids, "labels": labels}

        def loss(cfg):
            def f(p):
                return gpt2.lm_loss(cfg, p, batch, None, True)[0]
            return f

        # (one program a config: eager, the scan's pieces dispatch one op at a time)
        l_full, g_full = jax.jit(jax.value_and_grad(loss(cfg_full)))(params)
        l_chunk, g_chunk = jax.jit(jax.value_and_grad(loss(cfg_chunk)))(params)
        np.testing.assert_allclose(float(l_full), float(l_chunk), rtol=1e-6)
        for gf, gc in zip(jax.tree.leaves(g_full), jax.tree.leaves(g_chunk)):
            np.testing.assert_allclose(np.asarray(gf), np.asarray(gc), atol=1e-5, rtol=1e-4)

    def test_padded_vocab_matches_unpadded(self):
        """pad_vocab_multiple (Megatron make-vocab-size-divisible-by analog):
        same loss/grads as the unpadded model, zero grad on pad rows, and
        identical greedy generation — full-logits AND chunked CE."""
        from deepspeed_tpu.models import gpt2

        cfg_u = gpt2.get_config("gpt2-tiny", vocab_size=509)
        params = gpt2.init_params(cfg_u, jax.random.PRNGKey(0))
        rs = np.random.RandomState(3)
        ids = rs.randint(0, 509, (2, 64)).astype(np.int32)
        batch = {"input_ids": ids}

        for chunk in (0, 48):
            cfg_p = gpt2.get_config(
                "gpt2-tiny", vocab_size=509, pad_vocab_multiple=128, ce_chunk=chunk
            )
            cfg_uc = gpt2.get_config("gpt2-tiny", vocab_size=509, ce_chunk=chunk)
            assert cfg_p.padded_vocab_size == 512
            params_p = dict(params)
            params_p["wte"] = jnp.pad(params["wte"], ((0, 3), (0, 0)))

            def loss(cfg, p):
                return gpt2.lm_loss(cfg, p, batch, None, True)[0]

            grad = jax.jit(jax.value_and_grad(loss, argnums=1), static_argnums=0)
            l_u, g_u = grad(cfg_uc, params)
            l_p, g_p = grad(cfg_p, params_p)
            np.testing.assert_allclose(float(l_u), float(l_p), rtol=1e-6)
            np.testing.assert_allclose(
                np.asarray(g_p["wte"])[:509], np.asarray(g_u["wte"]), atol=1e-6
            )
            assert np.all(np.asarray(g_p["wte"])[509:] == 0.0)

        out_u = gpt2.generate(cfg_u, params, jnp.asarray(ids[:, :8]), 6)
        out_p = gpt2.generate(
            gpt2.get_config("gpt2-tiny", vocab_size=509, pad_vocab_multiple=128),
            {**params, "wte": jnp.pad(params["wte"], ((0, 3), (0, 0)))},
            jnp.asarray(ids[:, :8]), 6,
        )
        np.testing.assert_array_equal(np.asarray(out_u), np.asarray(out_p))

    def test_long_sequence_scan_path_matches(self):
        """> 32 chunks takes the dynamic-slice lax.scan branch (bounded
        program size for long sequences); loss + grads stay exact."""
        from deepspeed_tpu.models import lm_loss

        rs = np.random.RandomState(1)
        B, S, E, V = 2, 71, 8, 33  # 36 chunks, pad=1: scan branch + its pad path
        h = jnp.asarray(rs.randn(B, S, E), jnp.float32)
        W = jnp.asarray(rs.randn(V, E), jnp.float32) * 0.1
        batch = {"input_ids": jnp.asarray(rs.randint(0, V, (B, S)), jnp.int32)}
        proj = lambda x: x @ W.T
        l_full, nt = lm_loss.token_loss(proj(h), batch)
        l_scan, nt2 = lm_loss.chunked_token_loss(proj, h, batch, 2)  # 35 chunks
        np.testing.assert_allclose(float(l_full), float(l_scan), rtol=1e-6)
        assert float(nt) == float(nt2)
        g1 = jax.grad(lambda h: lm_loss.token_loss(proj(h), batch)[0])(h)
        g2 = jax.grad(lambda h: lm_loss.chunked_token_loss(proj, h, batch, 2)[0])(h)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-5, rtol=1e-4)

    def test_trains_under_engine(self, mesh_dp8):
        from deepspeed_tpu.models import gpt2
        from deepspeed_tpu.runtime.config import DeepSpeedConfig
        from deepspeed_tpu.runtime.engine import DeepSpeedEngine

        cfg = gpt2.get_config("gpt2-tiny", ce_chunk=64)
        ds = DeepSpeedConfig.load(
            {"train_micro_batch_size_per_gpu": 1,
             "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
             "zero_optimization": {"stage": 2}},
            dp_world_size=8,
        )
        eng = DeepSpeedEngine(gpt2.make_module(cfg), ds, mesh=mesh_dp8, seed=0)
        rs = np.random.RandomState(0)
        b = {"input_ids": rs.randint(0, cfg.vocab_size, (8, 128)).astype(np.int32)}
        l0 = float(jax.device_get(eng.train_batch(b)["loss"]))
        for _ in range(4):
            m = eng.train_batch(b)
        assert float(jax.device_get(m["loss"])) < l0
