"""Peaks, operations and bytes of the timed path's programs, from shapes.

Model FLOPs are counted by the cell's architecture module
(`chipbench/arch/<arch>.py`): the multiply-adds the architecture needs
(two operations each) in a forward pass. Training is three forward
passes' worth (the program does not recompute). Nothing here is read
from a compiled program's cost analysis.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The chip's peak rates; an unknown device is an error."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def train_flops(arch, model: dict, rows: int, seq: int) -> int:
    """A training step over `rows` sequences of `seq` tokens."""
    return 3 * arch.forward_flops(model, rows, seq)


def fleet_drift_cost(rows: int, tokens: int, buckets: int) -> dict:
    """What the drift kernel must move and compute: int32 tokens and a
    float32 reference row in, a score and a histogram row out; one
    count per token, and about a dozen operations per bucket for the
    Jensen-Shannon divergence."""
    return {"bytes": 4 * rows * (tokens + 2 * buckets + 1),
            "flops": rows * (tokens + 12 * buckets)}


def roofline_seconds(cost: dict, peak: dict) -> float:
    """The least time the chip could take: the larger of the compute
    bound and the memory bound."""
    return max(cost["flops"] / peak["flops_per_s"],
               cost["bytes"] / peak["hbm_bytes_per_s"])
