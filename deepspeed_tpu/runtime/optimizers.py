"""Optimizer registry: config ``optimizer.type`` → optax transform.

Analog of reference ``engine._configure_basic_optimizer`` (engine.py:1173) and
the ``deepspeed/ops/{adam,lamb,adagrad}`` wrappers. The reference ships three
flavors of Adam (torch, FusedAdam CUDA kernel, DeepSpeedCPUAdam SIMD); under
XLA the optimizer update is fused into the train step by the compiler, so one
optax definition covers the "fused" case. ``deepspeed_tpu/ops/fused_adam.py``
is the Pallas multi-tensor kernel alternative (SURVEY §2.7 asks for the two
to be measured: on a v5e XLA's fused update runs at 659 GB/s and the kernel,
as it is wrapped, at 170; ``PERF.md`` section 6, PR 45) — optax stays. The
CPU (host-offload) variants live in ``deepspeed_tpu/runtime/offload/``.

Accepted ``type`` strings keep DeepSpeed's names: Adam, AdamW, FusedAdam,
DeepSpeedCPUAdam, Lamb, FusedLamb, Adagrad, DeepSpeedCPUAdagrad, SGD.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Union

import optax

ADAM_OPTIMIZER = "adam"
ADAMW_OPTIMIZER = "adamw"
LAMB_OPTIMIZER = "lamb"
# DeepSpeed's compressed-communication optimizers: refused by name, so that a
# config that asks for one never trains uncompressed under plain Adam or LAMB
_REMOVED_OPTIMIZERS = ("onebitadam", "onebitlamb", "zerooneadam")

Schedule = Union[float, Callable]


def _default_wd_mask(params):
    import jax

    return jax.tree.map(lambda p: p.ndim >= 2, params)


def build_optimizer(
    opt_type: Optional[str],
    params_cfg: Optional[Dict[str, Any]] = None,
    learning_rate: Optional[Schedule] = None,
) -> optax.GradientTransformation:
    """Build the optax transform for a DeepSpeed ``optimizer`` config section."""
    p = dict(params_cfg or {})
    name = (opt_type or "Adam").lower()
    if name in _REMOVED_OPTIMIZERS:
        raise ValueError(
            f"optimizer type {opt_type!r}: the port of the 1-bit optimizers was "
            "removed in PR 46; compress the gradient reduce under any optimizer "
            "with the 'comm_compression' section (docs/COMM_COMPRESSION.md)"
        )
    lr = learning_rate if learning_rate is not None else p.get("lr", 1e-3)
    betas = tuple(p.get("betas", (0.9, 0.999)))
    eps = float(p.get("eps", 1e-8))
    weight_decay = float(p.get("weight_decay", 0.0))
    adam_w_mode = bool(p.get("adam_w_mode", True))

    if name in ("adam", "adamw", "fusedadam", "deepspeedcpuadam"):
        if weight_decay and (adam_w_mode or name == "adamw"):
            return optax.adamw(
                lr, b1=betas[0], b2=betas[1], eps=eps, weight_decay=weight_decay,
                mask=_default_wd_mask,
            )
        if weight_decay:
            # L2-style decay (adam_w_mode=False): decay folded into the gradient
            return optax.chain(
                optax.add_decayed_weights(weight_decay, mask=_default_wd_mask),
                optax.adam(lr, b1=betas[0], b2=betas[1], eps=eps),
            )
        return optax.adam(lr, b1=betas[0], b2=betas[1], eps=eps)

    if name in ("lamb", "fusedlamb"):
        return optax.lamb(lr, b1=betas[0], b2=betas[1], eps=eps, weight_decay=weight_decay)

    if name in ("adagrad", "deepspeedcpuadagrad"):
        return optax.adagrad(lr, eps=float(p.get("eps", 1e-10)))

    if name == "sgd":
        return optax.sgd(lr, momentum=float(p.get("momentum", 0.0)), nesterov=bool(p.get("nesterov", False)))

    raise ValueError(f"unknown optimizer type: {opt_type}")
