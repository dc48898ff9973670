"""What a run hands to the metric readers."""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple


@dataclass
class Step:
    """One call into the system made by a runner, on the benchmark's clock."""
    kind: str            # "srv.step" | "train_batch"
    t0: float
    t1: float
    info: Dict[str, Any] = field(default_factory=dict)


@dataclass
class Context:
    cell: dict
    config: dict
    traffic: dict
    chips: int
    peak: Any                                  # peaks.Peak
    window: Tuple[float, float] = (0.0, 0.0)   # benchmark clock
    traced: Optional[Tuple[float, float]] = None  # the traced part of the window, same clock
    recs: List[Any] = field(default_factory=list)   # arith.Rec, served cells
    steps: List[Step] = field(default_factory=list)
    step_ends: List[float] = field(default_factory=list)  # train: loss-ready times
    tokens_per_step: int = 0
    trace: Any = None                          # xplane.Reduced or None
    extra: Dict[str, Any] = field(default_factory=dict)

    def steps_in(self, span: Tuple[float, float], kind: Optional[str] = None):
        return [s for s in self.steps if s.t0 >= span[0] and s.t1 <= span[1] and (kind is None or s.kind == kind)]
