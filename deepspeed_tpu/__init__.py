"""deepspeed_tpu — a TPU-native large-model training & inference framework.

Brand-new implementation of the capabilities of DeepSpeed (reference:
OpenGPTX/DeepSpeed v0.7.3) designed for TPU from the ground up: JAX/XLA with
``pjit``-sharded state over a named device mesh, Pallas kernels for hot ops,
XLA collectives over ICI/DCN for communication, and host-side C++ for async
NVMe I/O. See SURVEY.md for the reference structural map.

Public API parity (reference ``deepspeed/__init__.py``):
- ``initialize``       (:51)  → engine construction
- ``init_inference``   (:225) → inference engine
- ``init_distributed`` (:28 re-export)
- ``add_config_arguments`` (:209)
- ``zero`` namespace (Init/GatheredParameters analogs)
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

__version__ = "0.5.0"
__git_branch__ = "main"

from . import comm  # noqa: F401
from . import serving  # noqa: F401
from . import telemetry  # noqa: F401
from .comm.comm import init_distributed  # noqa: F401
from .module_inject import (  # noqa: F401
    replace_transformer_layer,
    revert_transformer_layer,
)
from .ops.transformer import (  # noqa: F401
    DeepSpeedTransformerConfig,
    DeepSpeedTransformerLayer,
)
from .runtime.config import DeepSpeedConfig, DeepSpeedConfigError  # noqa: F401
from .runtime.lr_schedules import add_tuning_arguments  # noqa: F401
from .utils.init_on_device import OnDevice  # noqa: F401
from .runtime.engine import DeepSpeedEngine  # noqa: F401
from .runtime.module import ModuleSpec  # noqa: F401
from .parallel.topology import (  # noqa: F401
    MeshSpec,
    PipeDataParallelTopology,
    PipeModelDataParallelTopology,
    ProcessTopology,
)
from .runtime.zero import partitioning as zero  # noqa: F401
from .utils.logging import log_dist, logger  # noqa: F401


def initialize(
    args: Any = None,
    model: Optional[ModuleSpec] = None,
    optimizer: Any = None,
    model_parameters: Any = None,
    training_data: Any = None,
    lr_scheduler: Any = None,
    mesh: Any = None,
    mpu: Any = None,
    dist_init_required: Optional[bool] = None,
    collate_fn: Any = None,
    config: Any = None,
    config_params: Any = None,
    seed: int = 0,
) -> Tuple[DeepSpeedEngine, Any, Any, Any]:
    """Create a :class:`DeepSpeedEngine` (reference ``deepspeed.initialize``).

    Args mirror the reference where the concept transfers:
      model: a :class:`ModuleSpec` (functional model bundle) — the analog of
        the reference's ``nn.Module``.
      model_parameters: optional pre-built param pytree (else ``model.init``
        runs sharded — the ``zero.Init`` analog).
      training_data: indexable dataset → a deterministic loader is built.
      lr_scheduler: a ``step -> lr`` callable overriding config ``scheduler``.
      mesh: a ``jax.sharding.Mesh`` (else built from config ``mesh`` section).
      config: path / dict / JSON string (ds_config.json schema).

    Returns ``(engine, optimizer, training_dataloader, lr_scheduler)``.
    """
    assert model is not None, "deepspeed_tpu.initialize: model is required"
    if config is None:
        config = config_params
    if config is None and args is not None and hasattr(args, "deepspeed_config") and args.deepspeed_config:
        config = args.deepspeed_config
    assert config is not None, "deepspeed_tpu.initialize: config is required"

    if dist_init_required is None or dist_init_required:
        if not comm.comm.is_initialized():
            init_distributed()

    # pass the raw document through — the engine finalizes the batch triple
    # against the actual dp mesh size
    engine = DeepSpeedEngine(
        model=model,
        config=config,
        mesh=mesh,
        params=model_parameters,
        lr_schedule=lr_scheduler if callable(lr_scheduler) else None,
        seed=seed,
        training_data=training_data,
        collate_fn=collate_fn,
    )

    # monitor wiring (reference engine.py:278 MonitorMaster); a missing
    # optional writer (tensorboard / wandb) disables itself inside
    from .monitor.monitor import MonitorMaster

    monitor = MonitorMaster(engine.config)
    engine.monitor = monitor if monitor.enabled else None
    if engine.monitor is not None and engine.telemetry is not None:
        # registry gauges fan out to every Monitor backend at steps_per_print
        engine.telemetry.attach_monitor(engine.monitor)

    return engine, engine.optimizer, engine.training_dataloader, engine.lr_schedule


def init_inference(model=None, **kwargs):
    """Create an inference engine (reference ``deepspeed.init_inference``)."""
    from .inference.engine import InferenceEngine

    return InferenceEngine(model=model, **kwargs)


def add_config_arguments(parser):
    """Add --deepspeed / --deepspeed_config CLI args (reference __init__.py:209)."""
    group = parser.add_argument_group("DeepSpeed-TPU", "DeepSpeed-TPU configurations")
    group.add_argument("--deepspeed", default=False, action="store_true")
    group.add_argument("--deepspeed_config", default=None, type=str)
    group.add_argument("--deepscale", default=False, action="store_true", help=argparse_suppress())
    group.add_argument("--local_rank", type=int, default=-1)
    return parser


def argparse_suppress():
    import argparse

    return argparse.SUPPRESS
