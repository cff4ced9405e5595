"""Bytes and operations of a DRAFT-AND-VERIFY step of the K-EXAONE block
(window layers' masked rings beside full rows, a leading dense layer, then
sigmoid-routed experts beside a shared one, and one MTP block), and of its
kernels, re-derived from the configuration file's shapes: the sibling of
``window_moe_counts.py``, whatever implements the step.

A step verifies TWO query rows a slot (the pending token and the draft)
through the model, runs the MTP block at both positions and takes the head
three times a slot (two verified rows, one draft).  It MUST read every
weight it multiplies by ONCE (of the experts only those that got a row;
the head once, though it is applied twice), and the cached positions its
live slots attend: in each of the THREE full rows (two main layers and the
MTP block's) ``pos + 2`` a slot, in each of the SIX rings ``min(pos + 2,
ring)``, which a masked ring is read whole for once it is warm.  A matmul
of ``[m, k]`` by ``[k, n]`` is ``2 m k n`` operations.  The counts of
positions, pairs and touched experts come from the program's ``step_log()``
(``kv_rows_full`` / ``kv_rows_window`` / ``moe_*`` / ``spec_*``); a program
without them gives None and the readers return nothing.
"""

from __future__ import annotations

from benchmark.harness.serve_logs import window_steps
from benchmark.harness.window_moe_counts import (BF16, CHUNK_PROGRAM,  # noqa: F401
                                                 FULL_KERNEL, RING_KERNEL,
                                                 roofline_s)

SCOPES = ("sw_mtp_verify", "sw_mtp_accept", "sw_mtp_draft")
VERIFY_ROWS = 2     # query rows a slot a step: the pending token, the draft


def layer_counts(config: dict) -> tuple:
    """(full layers, window layers, routed layers) a step runs: the main
    model's and the MTP block's one full, sparse layer."""
    kinds = config["layer_types"][:config["num_hidden_layers"]]
    window = kinds.count("sliding_attention")
    mtp = config["num_nextn_predict_layers"]
    routed = config["num_hidden_layers"] - config["first_k_dense_replace"]
    return len(kinds) - window + mtp, window, routed + mtp


def attention_params(config: dict) -> int:
    """Weights of one layer's attention: the projections, the block's two
    norms and the two head norms."""
    d, hd = config["hidden_size"], config["head_dim"]
    q, kv = config["num_attention_heads"] * hd, config["num_key_value_heads"] * hd
    return d * q + 2 * d * kv + q * d + 2 * d + 2 * hd


def expert_params(config: dict) -> int:
    """One routed expert (or the shared one): gate, up, down."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def kv_row_bytes(config: dict) -> int:
    """One cached position of one layer: k and v of every kv head."""
    return 2 * config["num_key_value_heads"] * config["head_dim"] * BF16


def dense_params(config: dict) -> int:
    """Per-token weights outside the routed experts: every layer's
    attention, the dense layers' MLPs, each sparse layer's router and
    shared expert, the MTP block's ``w_eh`` and three norms, and the head
    with the final norm (once)."""
    d = config["hidden_size"]
    full, window, routed = layer_counts(config)
    dense = config["first_k_dense_replace"]
    return ((full + window) * attention_params(config)
            + dense * 3 * d * config["intermediate_size"]
            + routed * (d * config["num_experts_published"]
                        + config["num_shared_experts"] * expert_params(config))
            + config["num_nextn_predict_layers"] * (2 * d * d + 3 * d)
            + d + d * config["vocab_size"])


def weight_bytes(config: dict, touched: float) -> float:
    """Bytes of the weights one step multiplies by, ``touched`` experts a
    routed layer having got a row.  The embedding table is left out (a
    step gathers three rows a slot)."""
    _full, _window, routed = layer_counts(config)
    return (dense_params(config)
            + routed * touched * expert_params(config)) * BF16


def kv_bytes(config: dict, rows_full: float, rows_window: float) -> float:
    """k/v a step must read: ``rows_full`` positions in each full row,
    ``rows_window`` in each ring (all slots summed)."""
    full, window, _routed = layer_counts(config)
    return (full * rows_full + window * rows_window) * kv_row_bytes(config)


def attn_flops(config: dict, rows: float) -> float:
    """One decode attention call (one layer, one step): every query head
    of BOTH query rows against ``rows`` cached positions, scores and the
    weighted sum."""
    return (4.0 * VERIFY_ROWS * rows * config["num_attention_heads"]
            * config["head_dim"])


def attn_bytes(config: dict, batch: int, rows: float) -> float:
    """The attended positions once, the queries in and the result out."""
    q = config["num_attention_heads"] * config["head_dim"]
    return rows * kv_row_bytes(config) + 2 * VERIFY_ROWS * batch * q * BF16


def moe_layer_flops(config: dict, pairs: float) -> float:
    """The grouped matmuls of one layer (gate, up, down)."""
    return 2.0 * pairs * expert_params(config)


def moe_layer_bytes(config: dict, touched: float, pairs: float) -> float:
    """The touched experts' weights once, each pair's row in (twice: the
    two calls) and out."""
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    return touched * expert_params(config) * BF16 + pairs * (2 * d + 2 * f) * BF16


def step_flops(config: dict, batch: int, rows_full: float,
               rows_window: float, pairs: float) -> float:
    """Operations of one step: ``2 x batch`` rows through every dense
    matmul of the model and of the MTP block, the head on three rows a
    slot, ``pairs`` (row, choice) pairs a routed layer through an expert."""
    d = config["hidden_size"]
    full, window, routed = layer_counts(config)
    head = d * config["vocab_size"]
    per_row = dense_params(config) - head
    return (2.0 * VERIFY_ROWS * batch * per_row + 2.0 * 3 * batch * head
            + routed * moe_layer_flops(config, pairs)
            + full * attn_flops(config, rows_full)
            + window * attn_flops(config, rows_window))


def step_floor_s(config: dict, peaks: dict, batch: int, rows_full: float,
                 rows_window: float, touched: float, pairs: float) -> float:
    """The least seconds the chip could take for one draft-and-verify
    step: the larger of its bytes over the HBM's peak and its operations
    over the bf16 peak."""
    return roofline_s(
        step_flops(config, batch, rows_full, rows_window, pairs),
        weight_bytes(config, touched) + kv_bytes(config, rows_full, rows_window),
        peaks)


# ------------------------------------------- the program's counters, averaged


def step_means(obs) -> "dict | None":
    """Means over the window's chunks, from the program's ``step_log()``:
    ``rows_full`` / ``rows_window`` (cached positions the chunk's first
    step reads in one full row / one ring, all slots summed: a lower bound
    of every later step's), ``touched`` experts and ``pairs`` a routed
    layer of the MODEL a step (the program counts its 7, not the block's
    own; the block's layer sees the same rows and is taken as one more of
    them), ``max`` pairs on one expert."""
    rows = [r for r in window_steps(obs)
            if "kv_rows_full" in r and "moe_assign" in r and "spec_drafted" in r]
    if not rows:
        return None
    config = obs["config"]
    counted = config["num_hidden_layers"] - config["first_k_dense_replace"]
    per_chunk = config["serve"]["chunk"] * counted
    mean = lambda key: sum(r[key] for r in rows) / len(rows)
    return {"rows_full": mean("kv_rows_full"),
            "rows_window": mean("kv_rows_window"),
            "touched": mean("moe_touched"),
            "pairs": mean("moe_assign") / per_chunk,
            "max": mean("moe_max"), "chunks": len(rows)}


def spec_sums(obs) -> "dict | None":
    """Sums over the window's chunks of the program's speculation
    counters: drafts verified (one a live slot a step), drafts accepted,
    tokens emitted."""
    rows = [r for r in window_steps(obs) if "spec_drafted" in r]
    if not rows or not sum(r["spec_drafted"] for r in rows):
        return None
    return {k: sum(r["spec_" + k] for r in rows)
            for k in ("drafted", "accepted", "emitted")}
