"""The last line: exactly the object the driver reads."""
from __future__ import annotations

import json
import math
from typing import Any, Dict, List, Optional


def metrics_block(specs: List[Dict[str, Any]],
                  values: Dict[str, Optional[float]]) -> Dict[str, Any]:
    """{name: {value, unit}} for every listed metric that has a value; a
    reader that found nothing to read leaves its metric out."""
    out = {}
    for m in specs:
        v = values.get(m["name"])
        if v is None:
            continue
        v = float(v)
        if not math.isfinite(v):
            raise ValueError(f"metric {m['name']} is {v}")
        out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def last_line(correct: bool, attempted: int, failed: int,
              metrics: Dict[str, Any], device: Dict[str, Any],
              breakdown: Optional[Dict[str, Any]] = None) -> str:
    obj = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        obj["breakdown"] = breakdown
    return json.dumps(obj)
