"""Bytes and operations of a decode step of a decoder-hybrid-decoder (Mamba
state-space layers beside window rings in the self-decoder, ONE full
layer's rows that the cross-decoder's attention layers read, gated memory
units between those; Phi-4-mini-flash-reasoning), and of its kernels,
re-derived from the configuration file's shapes: the sibling of
``gdn_gqa_moe_counts.py`` (a state that is replaced, not appended to) and
``window_moe_counts.py`` (rings beside rows).  A step MUST read every
weight it multiplies by (the tied table once, for the head), the cached
positions its live slots attend (the full layer's ``pos + 1`` a slot ONCE
FOR EACH LAYER THAT READS THEM, ``kv_full_readers``: the layer itself and
every cross layer; a window layer's ``min(pos + 1, window)``), and must read
AND write every decoding slot's state in each Mamba layer.  A matmul of
``[m, k]`` by ``[k, n]`` is ``2 m k n`` operations.  The counts of slots
and rows come from the program's ``step_log()`` (``state_slots`` /
``kv_rows_full`` / ``kv_rows_window`` / ``kv_full_readers``); a program
without them gives None and the readers return nothing.  What the cell's
per-layer readers (``layer_metrics/*.cot.py``) share.

The recurrence kernels run on the vector unit, for which the peaks' table
has no published figure: their operations are set against the bf16 peak
like every other kernel's here, which the bytes exceed by far, so their
shares are shares of the HBM bound.
"""

from __future__ import annotations

from benchmark.harness.kda_mla_moe_counts import chunk_step_s, roofline_s  # noqa: F401
from benchmark.harness.serve_logs import window_steps

BF16, F32 = 2, 4
CHUNK_PROGRAM = "jit_serve_decode_chunk"
ADMIT_PROGRAM = "jit_serve_admit_"
STEP_KERNEL, SCAN_KERNEL = "sw_ssm_step", "sw_ssm_scan"
FULL_KERNEL, RING_KERNEL = "sw_decode_attn_stream", "sw_decode_attn_ring"
SCOPES = ("sw_cross_decoder",)


def layer_counts(config: dict) -> dict:
    """Layers of each kind, from the file's ``layout``."""
    kinds = [k for period, reps in config["layout"] for _ in range(reps)
             for k in period]
    return {k: kinds.count(k) for k in ("ssm", "window", "full", "gmu", "cross")}


def _sizes(config: dict) -> tuple:
    """(D, E, N, R, taps, q width, kv width)."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    hd = d // h
    return (d, config["mamba_expand"] * d, config["mamba_d_state"],
            config["mamba_dt_rank"], config["mamba_d_conv"], h * hd,
            config["num_key_value_heads"] * hd)


def block_params(config: dict) -> int:
    """What every layer has: the gated MLP and two LayerNorms."""
    d = config["hidden_size"]
    return 3 * d * config["intermediate_size"] + 4 * d


def mixer_params(config: dict, kind: str) -> int:
    """Weights of one layer's mixer."""
    d, e, n, r, taps, q, kv = _sizes(config)
    lam = 4 * (d // config["num_attention_heads"]) + 2 * (
        d // config["num_attention_heads"])
    return {
        "ssm": (d * 2 * e + taps * e + e + e * (r + 2 * n) + r * e + e
                + n * e + e + e * d),
        "gmu": 2 * d * e,
        "cross": d * q + q + q * d + d + lam,
        "window": d * (q + 2 * kv) + q + 2 * kv + q * d + d + lam,
    }["window" if kind == "full" else kind]


def weight_bytes(config: dict) -> float:
    """Bytes of the weights one decode step multiplies by: every layer,
    the final norm and the tied table (the head reads all of it; the
    embedding's gather of one row a sequence is left out)."""
    d = config["hidden_size"]
    layers = sum(n * (mixer_params(config, kind) + block_params(config))
                 for kind, n in layer_counts(config).items())
    return (layers + 2 * d + d * config["vocab_size"]) * BF16


def state_bytes(config: dict) -> int:
    """One slot's state in one Mamba layer: float32, N states a channel."""
    _d, e, n, *_ = _sizes(config)
    return n * e * F32


def conv_tail_bytes(config: dict) -> int:
    _d, e, _n, _r, taps, *_ = _sizes(config)
    return (taps - 1) * e * BF16


def state_rw_bytes(config: dict, slots: float) -> float:
    """State a decode step reads AND writes, every Mamba layer: the
    matrices and the convolution's tails of the slots that decode."""
    return (layer_counts(config)["ssm"] * slots
            * 2 * (state_bytes(config) + conv_tail_bytes(config)))


def kv_row_bytes(config: dict) -> int:
    """One cached position of one layer: k and v of every kv head (a PAIR
    of 64-wide heads is one 128-wide head of the cache: the same bytes)."""
    return 2 * _sizes(config)[6] * BF16


def full_read_bytes(config: dict, rows_full: float, readers: float) -> float:
    """The ONE full layer's rows as a step reads them: once a reader."""
    return layer_counts(config)["full"] * readers * rows_full * kv_row_bytes(config)


def ring_read_bytes(config: dict, rows_window: float) -> float:
    return layer_counts(config)["window"] * rows_window * kv_row_bytes(config)


def kv_bytes(config: dict, rows_full: float, readers: float,
             rows_window: float) -> float:
    """k/v a decode step must read (``rows_*``: cached positions attended
    in one layer of the kind, all slots summed)."""
    return (full_read_bytes(config, rows_full, readers)
            + ring_read_bytes(config, rows_window))


def attn_flops(config: dict, rows: float) -> float:
    """One decode attention call (one layer, one step): each of the pairs'
    two 64-wide query heads against ``rows`` keys, and ``(P1 - lam P2)``
    times the 128-wide value."""
    _d, _e, _n, _r, _t, q, _kv = _sizes(config)
    return 4.0 * rows * q


def attn_bytes(config: dict, batch: int, rows: float) -> float:
    """The attended positions once, the pairs' query rows in and their
    outputs out (two 128-wide rows a pair)."""
    q = 2 * _sizes(config)[5]
    return rows * kv_row_bytes(config) + 2 * batch * q * BF16


def ssm_step_flops(config: dict, slots: float) -> float:
    """One call of the decode kernel (one layer, one step): the
    transition's exponential, decay, input and read-out over a slot's
    whole state."""
    _d, e, n, *_ = _sizes(config)
    return 7.0 * slots * e * n


def ssm_step_bytes(config: dict, slots: float) -> float:
    """The slots' states in and out, the step's dt and x in and the
    read-out out (float32 rows), B and C."""
    _d, e, n, *_ = _sizes(config)
    return slots * (2 * state_bytes(config) + (3 * e + 2 * n) * F32)


def ssm_scan_flops(config: dict, positions: int) -> float:
    """One call of the prefill kernel over ``positions`` (a bucket)."""
    _d, e, n, *_ = _sizes(config)
    return 7.0 * positions * e * n


def ssm_scan_bytes(config: dict, positions: int) -> float:
    """dt and x in and the read-out out at every position (float32), B and
    C, and the state out once."""
    _d, e, n, *_ = _sizes(config)
    return positions * (3 * e + 2 * n) * F32 + state_bytes(config)


def step_flops(config: dict, batch: int, slots: float, rows_full: float,
               readers: float, rows_window: float) -> float:
    """Operations of one decode step: ``batch`` rows through every matmul,
    the state of ``slots`` and the attended rows."""
    c = layer_counts(config)
    d = config["hidden_size"]
    per_token = (sum(n * (mixer_params(config, kind) + block_params(config))
                     for kind, n in c.items()) + d * config["vocab_size"])
    return (2.0 * batch * per_token + c["ssm"] * ssm_step_flops(config, slots)
            + c["full"] * readers * attn_flops(config, rows_full)
            + c["window"] * attn_flops(config, rows_window))


def step_floor_s(config: dict, peaks: dict, batch: int, slots: float,
                 rows_full: float, readers: float, rows_window: float) -> float:
    """The least seconds the chip could take for one decode step: the
    larger of its bytes over the HBM's peak and its operations over the
    bf16 peak."""
    byts = (weight_bytes(config) + state_rw_bytes(config, slots)
            + kv_bytes(config, rows_full, readers, rows_window))
    return max(byts / peaks["hbm_bytes_per_s"],
               step_flops(config, batch, slots, rows_full, readers,
                          rows_window) / peaks["bf16_flops"])


# ------------------------------------------- the program's counters, averaged


def step_means(obs) -> "dict | None":
    """Means over the window's chunks, from the program's ``step_log()``:
    ``slots`` that decode (each one's state is read and written a Mamba
    layer a step), ``rows_full`` (positions one read of the full layer's
    rows attends, all slots summed: the chunk's first step's plus half a
    chunk a slot), ``readers`` of those rows, ``rows_window`` (positions a
    window layer's step attends: the first step's, a ring being read
    whole once warm), and of the steps that admitted ``admit_rows_self`` /
    ``admit_rows_cross`` summed."""
    rows = [r for r in window_steps(obs)
            if "state_slots" in r and "kv_rows_full" in r
            and "kv_rows_window" in r and "kv_full_readers" in r]
    if not rows:
        return None
    chunk = obs["config"]["serve"]["chunk"]
    mean = lambda key: sum(r[key] for r in rows) / len(rows)
    slots = mean("state_slots")
    return {"slots": slots,
            "rows_full": mean("kv_rows_full") + slots * (chunk - 1) / 2,
            "readers": mean("kv_full_readers"),
            "rows_window": mean("kv_rows_window"),
            "admit_rows_self": sum(r.get("admit_rows_self", 0) for r in rows),
            "admit_rows_cross": sum(r.get("admit_rows_cross", 0) for r in rows),
            "chunks": len(rows)}
