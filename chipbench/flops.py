"""Operations and bytes of the timed path's programs, from shapes.

Model FLOPs count the multiply-adds the architecture needs (two
operations each): the projections, the feed-forward, the output head
over the real vocabulary, and the attention scores and mixing over the
keys each position may see. Training is three forward passes' worth
(the program does not recompute). Nothing here is read from a compiled
program's cost analysis.
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


def matmul_params(model: dict) -> int:
    """Weights that every token multiplies once in a forward pass."""
    d, f = model["d_model"], model["d_ff"]
    H, K = model["num_heads"], model["num_kv_heads"]
    hd = model.get("head_dim") or d // H
    per_layer = d * H * hd + 2 * d * K * hd + H * hd * d + 3 * d * f
    return model["num_layers"] * per_layer + d * model["vocab_size"]


def attention_flops(model: dict, keys_seen: int) -> int:
    """Scores and mixing, summed over the keys all queries see."""
    H = model["num_heads"]
    hd = model.get("head_dim") or model["d_model"] // H
    return model["num_layers"] * 4 * H * hd * keys_seen


def forward_flops(model: dict, rows: int, seq: int) -> int:
    """A causal forward pass over `rows` sequences of `seq` tokens."""
    causal_keys = seq * (seq + 1) // 2
    return rows * (2 * matmul_params(model) * seq
                   + attention_flops(model, causal_keys))


def train_flops(model: dict, rows: int, seq: int) -> int:
    return 3 * forward_flops(model, rows, seq)


def decode_flops(model: dict, positions) -> int:
    """One token per lane, lane i at absolute position positions[i]."""
    return sum(2 * matmul_params(model) + attention_flops(model, int(p) + 1)
               for p in positions)


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
