"""What one run hands to the end-to-end metrics and to the per-layer readers."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple


@dataclasses.dataclass
class RunData:
    cell: Dict[str, Any]
    config: Dict[str, Any]
    peaks: Dict[str, float]
    t_start: float
    window: Any                                   # window.Window
    reports: List[Tuple[float, int]]              # (host time, steps since that trial's last report)
    terminals: List[Tuple[float, str, str]]       # (host time, trial, condition)
    compiles: List[Tuple[float, float]]           # (host time at the end, seconds)
    spans: Dict[str, List[Dict[str, Any]]]        # trial -> the program's Tracer spans
    trace: Optional[Dict[str, Any]] = None        # trace_reduce.reduce(...), traced runs only
