"""Bytes and operations of a decode step of a decoder whose window layers
keep rings beside its full layers' rows and whose FFN is routed experts
(SmallThinker), and of its kernels, re-derived from the configuration
file's shapes: the sibling of ``decode_bytes.py`` and ``mla_moe_counts.py``.
A step MUST read every weight it multiplies by (of the experts only those
that got a token) and the cached positions its live slots attend: a full
layer's ``pos + 1`` a slot, a window layer's ``min(pos + 1, window)``.  A
matmul of ``[m, k]`` by ``[k, n]`` is ``2 m k n`` operations.  The counts
of positions, pairs and touched experts come from the program's
``step_log()`` (``kv_rows_full`` / ``kv_rows_window`` / ``moe_*``); a
program without them gives None and the readers return nothing.
"""

from __future__ import annotations

from benchmark.harness.serve_logs import window_steps

BF16 = 2
CHUNK_PROGRAM = "jit_serve_decode_chunk"
FULL_KERNEL, RING_KERNEL = "sw_decode_attn_stream", "sw_decode_attn_ring"


def layer_counts(config: dict) -> tuple:
    """(full layers, window layers) of the layers that are run."""
    layout = config["sliding_window_layout"][:config["num_hidden_layers"]]
    return layout.count(0), len(layout) - layout.count(0)


def attention_params(config: dict) -> int:
    """Weights of one layer's attention, the block's two norms included."""
    d, hd = config["hidden_size"], config["head_dim"]
    q, kv = config["num_attention_heads"] * hd, config["num_key_value_heads"] * hd
    return d * q + 2 * d * kv + q * d + 2 * d


def expert_params(config: dict) -> int:
    """One routed expert: gate, up, down."""
    return 3 * config["hidden_size"] * config["moe_ffn_hidden_size"]


def kv_row_bytes(config: dict) -> int:
    """One cached position of one layer: k and v of every kv head."""
    return 2 * config["num_key_value_heads"] * config["head_dim"] * BF16


def weight_bytes(config: dict, touched: float) -> float:
    """Bytes of the weights one decode step multiplies by, ``touched``
    experts a layer having got a token.  The embedding table is left out
    (a step gathers one row a sequence)."""
    d, layers = config["hidden_size"], config["num_hidden_layers"]
    per_layer = (attention_params(config) + d * config["moe_num_primary_experts"]
                 + touched * expert_params(config))
    return (layers * per_layer + d + d * config["vocab_size"]) * BF16


def kv_bytes(config: dict, rows_full: float, rows_window: float) -> float:
    """k/v a decode step must read: ``rows_full`` positions in each full
    layer, ``rows_window`` in each window layer (all slots summed)."""
    full, window = layer_counts(config)
    return (full * rows_full + window * rows_window) * kv_row_bytes(config)


def attn_flops(config: dict, rows: float) -> float:
    """One decode attention call (one layer, one step): every query head
    against ``rows`` cached positions, scores and the weighted sum."""
    return 4.0 * rows * config["num_attention_heads"] * config["head_dim"]


def attn_bytes(config: dict, batch: int, rows: float) -> float:
    """The attended positions once, the queries in and the result out."""
    q = config["num_attention_heads"] * config["head_dim"]
    return rows * kv_row_bytes(config) + 2 * batch * q * BF16


def step_flops(config: dict, batch: int, rows_full: float,
               rows_window: float, pairs: float) -> float:
    """Operations of one decode step: ``batch`` rows through every dense
    matmul, ``pairs`` (token, choice) pairs a layer through an expert."""
    d, layers = config["hidden_size"], config["num_hidden_layers"]
    full, window = layer_counts(config)
    per_token = (layers * (attention_params(config) - 2 * d
                           + d * config["moe_num_primary_experts"])
                 + d * config["vocab_size"])
    return (2.0 * batch * per_token + layers * moe_layer_flops(config, pairs)
            + full * attn_flops(config, rows_full)
            + window * attn_flops(config, rows_window))


def step_floor_s(config: dict, peaks: dict, batch: int, rows_full: float,
                 rows_window: float, touched: float, pairs: float) -> float:
    """The least seconds the chip could take for one decode step: the
    larger of its bytes over the HBM's peak and its operations over the
    bf16 peak."""
    byts = weight_bytes(config, touched) + kv_bytes(config, rows_full, rows_window)
    return max(byts / peaks["hbm_bytes_per_s"],
               step_flops(config, batch, rows_full, rows_window, pairs)
               / peaks["bf16_flops"])


def moe_layer_flops(config: dict, pairs: float) -> float:
    """The grouped matmuls of one layer (gate, up, down)."""
    return 2.0 * pairs * expert_params(config)


def moe_layer_bytes(config: dict, touched: float, pairs: float) -> float:
    """The touched experts' weights once, each pair's row in (twice: the
    two calls) and out."""
    d, f = config["hidden_size"], config["moe_ffn_hidden_size"]
    return touched * expert_params(config) * BF16 + pairs * (2 * d + 2 * f) * BF16


def roofline_s(flops: float, byts: float, peaks: dict) -> float:
    return max(flops / peaks["bf16_flops"], byts / peaks["hbm_bytes_per_s"])


# ------------------------------------------- the program's counters, averaged


def step_means(obs) -> "dict | None":
    """Means over the window's chunks, from the program's ``step_log()``:
    ``rows_full`` / ``rows_window`` (cached positions the chunk's first
    decode step attends in one full / one window layer, all slots summed:
    a lower bound of every later step's), ``touched`` experts and ``pairs``
    a layer a step, ``max`` pairs on one expert."""
    rows = [r for r in window_steps(obs)
            if "kv_rows_full" in r and "moe_assign" in r]
    if not rows:
        return None
    config = obs["config"]
    per_chunk = config["serve"]["chunk"] * config["num_hidden_layers"]
    mean = lambda key: sum(r[key] for r in rows) / len(rows)
    return {"rows_full": mean("kv_rows_full"),
            "rows_window": mean("kv_rows_window"),
            "touched": mean("moe_touched"),
            "pairs": mean("moe_assign") / per_chunk,
            "max": mean("moe_max"), "chunks": len(rows)}
