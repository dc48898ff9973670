"""Operator-facing CLI tools (run as ``python -m deepspeed_tpu.tools.<name>``).

- ``trace_diff`` — align two step-trace JSONL runs and report per-span /
  per-category deltas with a regression threshold and a non-zero exit code,
  making a regression between two runs machine-checkable.
"""
