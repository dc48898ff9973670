"""Rank-filtered logging for multi-host TPU jobs.

TPU-native analog of the reference's ``deepspeed/utils/logging.py`` (``log_dist``,
``logger``): on a TPU pod each host runs one Python process, so "rank" here is
``jax.process_index()`` rather than a torch.distributed rank.
"""

from __future__ import annotations

import functools
import logging
import os
import sys

LOG_LEVEL = os.environ.get("DSTPU_LOG_LEVEL", "INFO").upper()

log_levels = {
    "debug": logging.DEBUG,
    "info": logging.INFO,
    "warning": logging.WARNING,
    "error": logging.ERROR,
    "critical": logging.CRITICAL,
}


@functools.lru_cache(None)
def _create_logger(name: str = "deepspeed_tpu", level: str = LOG_LEVEL) -> logging.Logger:
    logger_ = logging.getLogger(name)
    logger_.setLevel(getattr(logging, level, logging.INFO))
    logger_.propagate = False
    if not logger_.handlers:
        handler = logging.StreamHandler(stream=sys.stdout)
        handler.setFormatter(
            logging.Formatter(
                "[%(asctime)s] [%(levelname)s] [%(name)s] %(message)s",
                datefmt="%Y-%m-%d %H:%M:%S",
            )
        )
        logger_.addHandler(handler)
    return logger_


logger = _create_logger()


def _process_index() -> int:
    """Process index for rank-filtered logging, WITHOUT initializing the jax
    backend: ``jax.process_index()`` before ``jax.distributed.initialize``
    both returns the wrong answer (always 0) and permanently breaks
    multi-host init (the backend can no longer join a rendezvous). Until
    backends exist, fall back to the launcher-provided env rank."""
    try:
        import jax
        from jax._src import xla_bridge

        # If the private probe ever disappears, assume backends are NOT
        # initialized: the env-rank fallback is always safe, while calling
        # jax.process_index() here would initialize the backend and break
        # any later jax.distributed.initialize.
        if not getattr(xla_bridge, "backends_are_initialized", lambda: False)():
            raise LookupError  # env fallback below
        return jax.process_index()
    except Exception:  # pragma: no cover - before jax init / API drift
        import os

        return int(os.environ.get("RANK", os.environ.get("OMPI_COMM_WORLD_RANK", "0")))


def log_dist(message: str, ranks=None, level: int = logging.INFO) -> None:
    """Log ``message`` only on the given process indices (default: process 0).

    ``ranks=[-1]`` logs on every process, mirroring the reference semantics.
    """
    my_rank = _process_index()
    ranks = ranks if ranks else [0]
    if my_rank in ranks or -1 in ranks:
        logger.log(level, f"[Rank {my_rank}] {message}")


def print_rank_0(message: str) -> None:
    if _process_index() == 0:
        print(message, flush=True)


def warning_once(message: str) -> None:
    _warn_once(message)


@functools.lru_cache(None)
def _warn_once(message: str) -> None:
    logger.warning(message)
