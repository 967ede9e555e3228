"""Helpers the reducers share (not a reducer: no ``reduce``)."""

from __future__ import annotations

from typing import Dict, List, Optional


def window_bounds(data):
    """[t0, t1] of the measured window on the spans' clock."""
    for sp in data.spans:
        if sp["name"] == "bench.window":
            return sp["t0"], sp["t1"]
    return None


def spans_named(data, name: str, where: Optional[Dict[str, object]] = None) -> List[dict]:
    """Spans called ``name`` that lie inside the measured window and whose
    args include ``where``."""
    bounds = window_bounds(data)
    out = []
    for sp in data.spans:
        if sp["name"] != name:
            continue
        if bounds and not (sp["t0"] >= bounds[0] and sp["t1"] <= bounds[1]):
            continue
        if where and any(sp["args"].get(k) != v for k, v in where.items()):
            continue
        out.append(sp)
    return out
