"""The work a step needs, from the configuration alone: the same operations
and bytes whatever implements the step."""

from __future__ import annotations

_LEAF_BYTES = {"sum": 4, "min": 4, "max": 4, "count": 4}


def _leaves(config: dict):
    agg = config["aggregate"]
    return ["sum"] if agg["kind"] == "sum" else list(agg["fields"].values())


def fold_per_event(config: dict) -> dict:
    """Folding one event into keyed window state in HBM: read its cell index
    (int32) and its value (f32), then read and write one accumulator cell per
    leaf and the cell's int32 presence count.  One operation per leaf and one
    for the count.  Bytes bound it by four orders of magnitude."""
    leaves = _leaves(config)
    cells = sum(_LEAF_BYTES[k] for k in leaves) + 4
    return {"bytes": 4 + 4 + 2 * cells, "flops": len(leaves) + 1}
