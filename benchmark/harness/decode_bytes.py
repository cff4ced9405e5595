"""Bytes one decode step of a dense GQA decoder MUST read from HBM: every
weight except the embedding table (a step gathers one row per sequence
from it) plus the live rows of the KV cache.  Re-derived here from the
shapes; the program's own counts (scripts/kernel_bench.py) are not read.
"""

from __future__ import annotations


def weight_bytes(config: dict, bytes_per_weight: int = 2) -> int:
    """Bytes of all matrices and norms a decode step multiplies by."""
    d, f = config["hidden_size"], config["intermediate_size"]
    hq, hkv = config["num_attention_heads"], config["num_key_value_heads"]
    hd = config.get("head_dim") or d // hq
    layer = (d * hq * hd          # wq
             + 2 * d * hkv * hd   # wk, wv
             + hq * hd * d        # wo
             + 3 * d * f          # gate, up, down
             + 2 * d)             # two norms
    total = (config["num_hidden_layers"] * layer
             + d                        # final norm
             + d * config["vocab_size"])  # output head
    return total * bytes_per_weight


def cache_bytes_per_token(config: dict, bytes_per_value: int = 2) -> int:
    """K and V of one position, all layers."""
    d, hq = config["hidden_size"], config["num_attention_heads"]
    hd = config.get("head_dim") or d // hq
    return (2 * config["num_key_value_heads"] * hd * bytes_per_value
            * config["num_hidden_layers"])


def decode_step_bytes(config: dict, live_rows: float) -> float:
    """``live_rows``: cache positions attended in the step, summed over the
    sequences in the batch."""
    return weight_bytes(config) + live_rows * cache_bytes_per_token(config)
