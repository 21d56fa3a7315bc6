"""What one run hands the metric readers."""
from __future__ import annotations

import dataclasses
from typing import Any


@dataclasses.dataclass
class Record:
    window_s: float = 0.0          # host clock, first round's start to last end
    setup_s: float = 0.0
    attempted: int = 0             # segments
    failed: int = 0                # segments never served (counted as misses)
    rounds: int = 0                # rounds completed in the window
    round_s: list = dataclasses.field(default_factory=list)
    # serving cells: one dict per segment due in the window
    segments: list = dataclasses.field(default_factory=list)
    route_s: list = dataclasses.field(default_factory=list)
    tokens_in_window: int = 0
    device_kind: str = ""
    trace: Any = None              # xplane.Trace of a --trace 1 run
    extra: dict = dataclasses.field(default_factory=dict)


def quantile(values, q: float) -> float | None:
    """The q-quantile (linear interpolation between order statistics)."""
    import numpy as np

    if len(values) == 0:
        return None
    return float(np.quantile(np.asarray(values, np.float64), q))
