"""Inference engine — ``deepspeed_tpu.init_inference`` backend.

Analog of reference ``deepspeed/inference/engine.py`` (InferenceEngine:28,
549 LoC): wraps a model for serving — dtype conversion, tensor-parallel
sharding over a mesh, kernel injection, compiled forward with KV cache.
Reference mechanism → TPU mechanism:

- ``_apply_injection_policy`` (engine.py:330) + fused CUDA modules
  (transformer_inference.py) → ``module_inject.replace_transformer_layer``
  converts the HF torch model ONCE into a stacked JAX pytree; the fused
  kernel is the jitted decode function.
- ``_create_model_parallel_group`` (engine.py:179) + ReplaceWithTensorSlicing
  → a tp mesh axis and NamedSharding device_put of the converted params.
- CUDA-graph capture/replay (engine.py:486) → the compiled XLA executable of
  prefill + lax.scan decode (models/gpt2.generate).
- ``_convert_to_dtype`` / GroupQuantizer int8 (engine.py:464) → bf16 cast or
  ``ops.quantizer.quantize_tree`` (weight-only int8, 4x HBM savings).

Accepts either a :class:`ModuleSpec` (JAX model) or an HF torch model (with
``replace_with_kernel_inject=True``, matching the reference call style).

MoE serving (reference ``DeepSpeedMoEInference``,
``ops/transformer/inference/moe_inference.py:205``): pass the trained MoE
``ModuleSpec`` + checkpoint params with ``ep_size>1`` — expert-stacked weights
shard over the ep mesh axis, decode flows through ``moe_mlp`` with
eval-capacity routing and the KV cache, and the dispatch/combine einsums
lower to the same ICI all-to-all the reference issues by hand. (There is no
HF torch MoE-GPT source architecture, so the injection path for MoE starts
from our own checkpoints, like the reference serving DeepSpeed-MoE ckpts.)
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..parallel.topology import MeshSpec
from ..runtime.module import ModuleSpec
from ..runtime.zero.partitioning import ZeroShardingPolicy
from ..telemetry import compile_stats, spans
from ..utils.logging import log_dist, warning_once

_UNSET = object()  # distinguishes an explicit kwarg from its default

PyTree = Any


_DTYPE_NAMES = {
    "fp16": jnp.float16, "half": jnp.float16, "float16": jnp.float16,
    "bf16": jnp.bfloat16, "bfloat16": jnp.bfloat16,
    "fp32": jnp.float32, "float": jnp.float32, "float32": jnp.float32,
    "int8": jnp.int8,
}


def _parse_dtype(d):
    """Accept jnp dtypes, numpy dtypes, torch dtypes, or DS-config strings
    ("fp16"/"bf16"/"int8"/torch.half names) — reference inference config
    dtype coercion."""
    if isinstance(d, str):
        key = d.lower().replace("torch.", "")
        if key not in _DTYPE_NAMES:
            raise ValueError(f"unknown inference dtype {d!r}")
        return _DTYPE_NAMES[key]
    name = getattr(d, "__name__", None) or str(d).replace("torch.", "")
    return _DTYPE_NAMES.get(name, d)


def _is_torch_module(model) -> bool:
    mod = type(model).__module__
    return mod.startswith("transformers") or hasattr(model, "state_dict")


class InferenceEngine:
    def __init__(
        self,
        model: Any = None,
        params: Optional[PyTree] = None,
        mp_size=_UNSET,
        ep_size=_UNSET,
        dtype=_UNSET,
        mesh: Optional[Mesh] = None,
        replace_with_kernel_inject=_UNSET,
        injection_policy: Optional[type] = None,
        quantize_bits=_UNSET,
        quantize_groups=_UNSET,
        max_tokens=_UNSET,
        seed: int = 0,
        checkpoint=_UNSET,
        config: Optional[Dict] = None,
        **kwargs,
    ):
        # reference init_inference(config={...}) dict surface
        # (deepspeed/inference/config.py keys). Precedence: an explicitly
        # passed kwarg wins over the config dict; the dict wins over the
        # built-in default.
        c = dict(config or {})
        # pop every recognized key unconditionally so the leftover-key
        # warning below never flags a key that was merely out-prioritized
        cfg_mp = c.pop("mp_size", None)
        tp_dict = c.pop("tensor_parallel", None)
        if cfg_mp is None and isinstance(tp_dict, dict):
            cfg_mp = tp_dict.get("tp_size")
        cfg_ep = c.pop("ep_size", None)
        cfg_dtype = c.pop("dtype", None)
        cfg_inject = c.pop("replace_with_kernel_inject", None)
        cfg_max = c.pop("max_out_tokens", c.pop("max_tokens", None))
        cfg_ckpt = c.pop("checkpoint", None)
        q = c.pop("quantization_setting", None)
        cfg_tel = c.pop("telemetry", None)
        cfg_cache = c.pop("generate_cache_size", None)
        cfg_serving = c.pop("serving", None)
        cfg_buckets = c.pop("prompt_bucket_sizes", None)

        mp_size = int(mp_size if mp_size is not _UNSET else (cfg_mp or 1))
        ep_size = int(ep_size if ep_size is not _UNSET else (cfg_ep or 1))
        dtype = _parse_dtype(
            dtype if dtype is not _UNSET
            else (cfg_dtype if cfg_dtype is not None else jnp.bfloat16)
        )
        replace_with_kernel_inject = bool(
            replace_with_kernel_inject if replace_with_kernel_inject is not _UNSET
            else bool(cfg_inject)
        )
        max_tokens = int(
            max_tokens if max_tokens is not _UNSET
            else (cfg_max if cfg_max is not None else 1024)
        )
        checkpoint = checkpoint if checkpoint is not _UNSET else cfg_ckpt
        # quantization_setting: groups, or (mlp_extra_grouping, groups)
        cfg_groups = None if q is None else int(q if not isinstance(q, (tuple, list)) else q[-1])
        # no quantization_setting -> 1 group, matching the reference's
        # _init_quantization_setting default (engine.py quantize_groups=1)
        quantize_groups = int(
            quantize_groups if quantize_groups is not _UNSET
            else (cfg_groups if cfg_groups is not None else 1)
        )
        quantize_bits = int(
            quantize_bits if quantize_bits is not _UNSET else (8 if q is not None else 0)
        )
        if np.dtype(dtype) == np.int8:
            # reference semantics: dtype=int8 means weight quantization, not
            # casting float weights to integers; compute stays bf16
            quantize_bits = 8
            dtype = jnp.bfloat16
        if c:
            warning_once(f"init_inference: ignoring config keys {sorted(c)}")
        if kwargs:
            warning_once(f"init_inference: ignoring kwargs {sorted(kwargs)}")
        self.dtype = dtype
        self.max_tokens = max_tokens
        if mesh is None:
            # ep axis serves MoE models: expert-stacked weights shard over ep
            # and the dispatch/combine einsums ride the ICI all-to-all
            # (reference DeepSpeedMoEInference, moe_inference.py:205, creates
            # expert-parallel groups the same way)
            n = max(1, mp_size) * max(1, ep_size)
            mesh = MeshSpec(
                dp=1, tp=mp_size, ep=ep_size, devices=jax.devices()[:n]
            ).build_mesh()
        elif ep_size > 1 and mesh.shape.get("ep", 1) != ep_size:
            raise ValueError(
                f"ep_size={ep_size} conflicts with the provided mesh "
                f"(ep axis size {mesh.shape.get('ep', 1)}); pass a mesh with a "
                "matching ep axis or omit ep_size"
            )
        self.mesh = mesh
        self.policy = ZeroShardingPolicy(mesh, stage=0)  # TP-only weight sharding
        self.model_config = None
        # compiled-generate cache, LRU-bounded: every distinct
        # (batch, prompt_len, max_new_tokens, sampling) shape holds a full
        # compiled XLA executable — unbounded growth across shapes leaks
        # device memory on long-lived servers. Cap via config
        # {"generate_cache_size": N}; evictions surface in telemetry.
        from collections import OrderedDict

        self._generate_cache: "OrderedDict" = OrderedDict()
        self._generate_cache_cap = max(1, int(cfg_cache if cfg_cache is not None else 16))
        self.generate_cache_evictions = 0
        # prompt-length bucketing for generate(): pad prompts up to the next
        # bucket before the compile-cache lookup so the LRU stops holding one
        # executable per unique prompt length. None = power-of-two buckets
        # (default); a list pins explicit sizes; []/False disables.
        self._prompt_buckets = cfg_buckets
        # serving section ({"serving": {...}}): defaults for .serve()
        self._serving_config = cfg_serving
        # unified telemetry plane (same TelemetryConfig schema as training;
        # config={"telemetry": {...}} — per-request JSONL records + registry)
        self.telemetry = None
        self._infer_steps = 0
        if cfg_tel is not None:
            from ..runtime.config import TelemetryConfig
            from ..telemetry import from_config as _tel_from_config

            tcfg = (
                TelemetryConfig.from_dict(cfg_tel)
                if isinstance(cfg_tel, dict) else cfg_tel
            )
            self.telemetry = _tel_from_config(tcfg)

        kind = None
        if checkpoint is not None and (model is not None or params is not None):
            raise ValueError(
                "pass either checkpoint= or model=/params= to init_inference, "
                "not both (one source would silently shadow the other's weights)"
            )
        if model is None and checkpoint is not None:
            # layer-streaming load straight from checkpoint files — the big-
            # model path that never instantiates a torch module (reference
            # module_inject/load_checkpoint.py:241)
            from ..module_inject.load_checkpoint import load_checkpoint_streamed

            kind, mcfg, params = load_checkpoint_streamed(checkpoint, dtype=dtype)
            if quantize_bits == 8:
                from ..ops.quantizer import quantize_tree

                params = quantize_tree(
                    jax.tree.map(jnp.asarray, params),
                    groups=quantize_groups,
                    dtype=dtype,
                )
            self.quantized = quantize_bits == 8
        elif model is not None and not isinstance(model, ModuleSpec) and _is_torch_module(model):
            # reference path: init_inference(hf_model, replace_with_kernel_inject=True)
            from ..module_inject import replace_transformer_layer

            kind, mcfg, params = replace_transformer_layer(
                model,
                policy=injection_policy,
                dtype=dtype,
                quantize_bits=quantize_bits,
                quantize_groups=quantize_groups,
            )
            self.quantized = quantize_bits == 8
        if kind is not None:
            if kind == "decoder" and getattr(mcfg, "mlp_type", "") == "moe_swiglu":
                # thread the serving mesh into the MoE layer so tp token
                # de-dup (moe/mappings.py) engages under mp_size > 1
                import dataclasses

                mcfg = dataclasses.replace(mcfg, mesh=mesh)
            self.model_config = mcfg
            if kind == "gpt2":
                from ..models import gpt2 as m_mod
            elif kind == "decoder":
                from ..models import decoder as m_mod
            elif kind == "bert":
                from ..models import bert as m_mod
            else:
                raise ValueError(f"unsupported injected model kind {kind}")
            model = m_mod.make_module(mcfg)
        else:
            assert model is not None and model.apply_fn is not None, (
                "init_inference requires a ModuleSpec with apply_fn or an HF torch model"
            )
            self.quantized = False
            self.model_config = (model.extra or {}).get("config")
            if quantize_bits == 8 and params is not None:
                # ModuleSpec path honors int8 too (reference engine.py:464
                # _convert_to_dtype → GroupQuantizer over client weights)
                from ..ops.quantizer import quantize_tree

                params = quantize_tree(
                    jax.tree.map(jnp.asarray, params),
                    groups=quantize_groups, dtype=dtype,
                )
                self.quantized = True

        self.module = model

        compile_stats.listen()
        with spans.phase("ds.init.params", what="inference"):
            # --- params: shard over tp, convert dtype (reference engine.py:464)
            init_rng = jax.random.PRNGKey(seed)
            in_dtype = (model.extra or {}).get("init_in_dtype")
            if params is None and in_dtype is not None and not self.quantized:
                # the model makes its tree on the device in the serving dtype,
                # a leaf at a time: a tree in its own dtype beside the cast
                # one would not fit for a model that fills the chip
                params = in_dtype(init_rng, dtype)
            if params is None:
                if model.init is None:
                    raise ValueError(
                        "model has no initializer (ModuleSpec.init=None — the "
                        "decoder zoo builds params from converted checkpoints); "
                        "pass them via init_inference(..., params=...) or "
                        "checkpoint=<dir>"
                    )
                abstract = jax.eval_shape(model.init, init_rng)
                shardings = self.policy.param_shardings(abstract, model.logical_axes)
                params = jax.jit(model.init, out_shardings=shardings)(init_rng)
                self.param_shardings = shardings
            else:
                abstract = jax.eval_shape(lambda: params)
                try:
                    self.param_shardings = self.policy.param_shardings(abstract, model.logical_axes)
                    params = jax.tree.map(jax.device_put, params, self.param_shardings)
                except Exception:
                    # quantized trees / trees whose structure diverges from
                    # logical_axes fall back to replicated placement
                    rep = NamedSharding(mesh, PartitionSpec())
                    self.param_shardings = jax.tree.map(lambda _: rep, params)
                    params = jax.tree.map(lambda x: jax.device_put(x, rep), params)
            if not self.quantized:
                params = jax.tree.map(
                    lambda p: p.astype(dtype)
                    if hasattr(p, "dtype") and jnp.issubdtype(p.dtype, jnp.floating)
                    else p,
                    params,
                )
        self.params = params
        self._forward = jax.jit(model.apply_fn) if model.apply_fn is not None else None
        log_dist(
            f"InferenceEngine: mesh={dict(mesh.shape)} "
            f"dtype={getattr(dtype, '__name__', dtype)} quantized={self.quantized}"
        )

    def forward(self, batch: PyTree):
        """Compiled forward (reference engine.forward:515)."""
        if self.telemetry is not None:
            # count only — no sync, so the serving hot path stays async
            self.telemetry.registry.counter(
                "inference_forward_total", "compiled forward calls"
            ).inc()
        return self._forward(self.params, batch)

    __call__ = forward

    def _prompt_bucket(self, S: int, max_new_tokens: int) -> Optional[int]:
        """Bucketed prompt length for the compile cache, or None when
        bucketing is disabled (``prompt_bucket_sizes: []``/``false``).
        Default (None/true): next power of two. A list pins explicit sizes
        (next pow2 past the largest). Capped so bucket + max_new_tokens still
        fits n_positions; never below the true length."""
        b = self._prompt_buckets
        if b is False or (isinstance(b, (list, tuple)) and len(b) == 0):
            return None
        cap = int(self.model_config.n_positions) - int(max_new_tokens)
        if isinstance(b, (list, tuple)):
            fits = sorted(int(x) for x in b if int(x) >= S)
            bucket = fits[0] if fits else 1 << max(0, S - 1).bit_length()
        else:
            bucket = 1 << max(0, S - 1).bit_length()
        return max(S, min(bucket, cap))

    def serve(self, serving_config=None, clock=None, tracer=None,
              heat_tracer=None, journal=None):
        """Continuous-batching server over this engine (serving/scheduler.py):
        a paged KV pool + slot-based decode loop over a fixed set of AOT
        executables (prefill + decode, speculative verify in the decode
        step's place and chunked prefill in the whole-prompt prefill's when
        the config enables them; prefix-cache KV reuse rides
        the same programs).
        ``serving_config`` (dict or :class:`~deepspeed_tpu.runtime.config.ServingConfig`)
        overrides the ``serving`` section passed to ``init_inference``."""
        import time as _time

        from ..serving import ServingEngine

        cfg = serving_config if serving_config is not None else self._serving_config
        return ServingEngine(
            self, cfg, clock=clock if clock is not None else _time.monotonic,
            tracer=tracer, heat_tracer=heat_tracer, journal=journal,
        )

    def _telemetry_generate(self, duration_s: float, batch: int, prompt_len: int, new_tokens: int, cached: Optional[bool]) -> None:
        """One JSONL record + registry fold per generate() call (generate
        already blocks on its output, so sampling adds no extra sync).
        ``cached`` is None on the full-prefix-recompute fallback, which has
        no compiled-generate cache to hit."""
        self._infer_steps += 1
        tel = self.telemetry
        if not tel.should_sample(self._infer_steps):
            return
        tok_s = batch * new_tokens / duration_s if duration_s > 0 else 0.0
        from ..telemetry import device_hbm_stats

        tel.record_step(
            "inference",
            step=self._infer_steps,
            duration_s=duration_s,
            scalars={
                "batch": batch,
                "prompt_tokens": prompt_len,
                "new_tokens": new_tokens,
                "tokens_per_sec": round(tok_s, 3),
            },
            spans=[("generate", duration_s * 1e3)],
            hbm=device_hbm_stats(),
            extra={} if cached is None else {"compiled_cache_hit": bool(cached)},
        )

    def generate(
        self,
        input_ids: np.ndarray,
        max_new_tokens: int = 20,
        temperature: float = 0.0,
        seed: int = 0,
        top_k: int = 0,
        top_p: float = 1.0,
    ) -> np.ndarray:
        """Autoregressive generation.

        KV-cache incremental decode when the model is a gpt2-family config
        (prefill + compiled lax.scan single-token steps); full-prefix
        recompute fallback otherwise. Returns prompt + new tokens."""
        ids = jnp.asarray(input_ids)
        t_gen0 = time.perf_counter() if self.telemetry is not None else 0.0
        rng = jax.random.PRNGKey(seed)
        from ..models.decoder import DecoderConfig
        from ..models.gpt2 import GPT2Config

        gen_mod = None
        if isinstance(self.model_config, GPT2Config):
            from ..models import gpt2 as gen_mod
        elif isinstance(self.model_config, DecoderConfig):
            from ..models import decoder as gen_mod

        if gen_mod is not None:
            S = int(ids.shape[1])
            # prompt-length bucketing (gpt2 family): pad to the bucket and
            # trace the true length, so every length in a bucket shares ONE
            # compiled executable instead of one per unique prompt length
            bucket = (
                self._prompt_bucket(S, max_new_tokens)
                if isinstance(self.model_config, GPT2Config) else None
            )
            shape_key = (
                (int(ids.shape[0]), bucket) if bucket is not None
                else tuple(ids.shape)
            )
            key = (shape_key, max_new_tokens, float(temperature), int(top_k), float(top_p))
            gen = self._generate_cache.get(key)
            was_cached = gen is not None
            if was_cached:
                self._generate_cache.move_to_end(key)  # LRU freshness
            if gen is None:
                cfg = self.model_config
                cache_dtype = self.dtype
                mod = gen_mod

                if bucket is not None:
                    from ..serving.model import generate_padded

                    def gen_fn(params, ids_padded, plen, rng):
                        return generate_padded(
                            cfg, params, ids_padded, plen, max_new_tokens,
                            temperature=temperature, rng=rng,
                            cache_dtype=cache_dtype, top_k=top_k, top_p=top_p,
                        )
                else:

                    def gen_fn(params, ids, rng):
                        return mod.generate(
                            cfg, params, ids, max_new_tokens,
                            temperature=temperature, rng=rng, cache_dtype=cache_dtype,
                            top_k=top_k, top_p=top_p,
                        )

                gen = jax.jit(gen_fn)
                self._generate_cache[key] = gen
                while len(self._generate_cache) > self._generate_cache_cap:
                    self._generate_cache.popitem(last=False)  # evict LRU entry
                    self.generate_cache_evictions += 1
                    if self.telemetry is not None:
                        self.telemetry.registry.counter(
                            "generate_cache_evictions_total",
                            "compiled-generate executables evicted by the LRU cap",
                        ).inc()
                if self.telemetry is not None:
                    self.telemetry.registry.gauge(
                        "generate_cache_size", "live compiled-generate executables"
                    ).set(len(self._generate_cache))
            if bucket is not None:
                padded = (
                    jnp.zeros((ids.shape[0], bucket), ids.dtype).at[:, :S].set(ids)
                    if bucket > S else ids
                )
                new = gen(self.params, padded, jnp.int32(S), rng)
            else:
                new = gen(self.params, ids, rng)
            out = jnp.concatenate([ids, new.astype(ids.dtype)], axis=1)
            result = np.asarray(jax.device_get(out))
            if self.telemetry is not None:
                self._telemetry_generate(
                    time.perf_counter() - t_gen0, int(ids.shape[0]),
                    int(ids.shape[1]), int(max_new_tokens), was_cached,
                )
            return result

        # fallback: full-prefix recompute each token
        from ..ops.sampling import sample_logits

        prompt_len = int(ids.shape[1])
        for _ in range(max_new_tokens):
            logits = self._forward(self.params, {"input_ids": ids})
            last = logits[:, -1, :].astype(jnp.float32)
            rng, k = jax.random.split(rng)
            nxt = sample_logits(last, k, temperature, top_k, top_p)
            ids = jnp.concatenate([ids, nxt[:, None].astype(ids.dtype)], axis=1)
        result = np.asarray(jax.device_get(ids))
        if self.telemetry is not None:
            self._telemetry_generate(
                time.perf_counter() - t_gen0, int(ids.shape[0]),
                prompt_len, int(max_new_tokens), None,
            )
        return result
