"""Bytes and operations of a decode step of a decoder whose layers are
Gated DeltaNet linear attention or gated grouped-query attention, every
FFN routed experts beside a gated shared one (Qwen3-Next), and of its
kernels, re-derived from the configuration file's shapes: the sibling of
``kda_mla_moe_counts.py`` (same recurrence kernels: a decay a head reaches
them as a decay a channel, so a call moves the same bytes) and
``window_moe_counts.py`` (same decode attention over grouped-query rows, at
this model's 256-wide heads).  A step MUST read every weight it multiplies
by (of the held experts only those that got a token), the cached k / v
positions its live slots attend in each attention layer, and must read AND
write every decoding slot's state in each DeltaNet layer: a state is not
appended to, it is replaced.  A matmul of ``[m, k]`` by ``[k, n]`` is ``2 m
k n`` operations.  The counts of slots, rows, pairs and touched experts
come from the program's ``step_log()`` (``state_slots`` / ``kv_rows_full``
/ ``moe_*``); a program without them gives None and the readers return
nothing.  What the cell's per-layer readers (``layer_metrics/*.answer.py``)
share.
"""

from __future__ import annotations

from benchmark.harness.kda_mla_moe_counts import chunk_step_s, roofline_s  # noqa: F401
from benchmark.harness.serve_logs import window_steps

BF16, F32 = 2, 4
CHUNK_PROGRAM = "jit_serve_decode_chunk"
ADMIT_PROGRAM = "jit_serve_admit_"
STEP_KERNEL, CHUNK_KERNEL = "sw_kda_step", "sw_kda_chunk"
ATTN_KERNEL, GMM_KERNEL = "sw_decode_attn_stream", "sw_moe_gmm"
GDN_CHUNK = 64     # positions a chunk of the prefill form holds


def layer_counts(config: dict) -> tuple:
    """(DeltaNet layers, attention layers) of the layers that are run."""
    n, every = config["num_hidden_layers"], config["full_attention_interval"]
    attn = sum((i + 1) % every == 0 for i in range(n))
    return n - attn, attn


def _gdn_widths(config: dict) -> tuple:
    """(channels of q or k, channels of v, value heads, head width)."""
    dl = config["linear_key_head_dim"]
    return (config["linear_num_key_heads"] * dl,
            config["linear_num_value_heads"] * dl,
            config["linear_num_value_heads"], dl)


def gdn_params(config: dict) -> int:
    """Weights of one DeltaNet layer's mixer, the block's two norms
    included: q/k/v/z and o, b/a, the convolution's taps, dt_bias, A_log,
    the head-wise norm."""
    d = config["hidden_size"]
    kw, vw, hv, dl = _gdn_widths(config)
    return (d * (2 * kw + 2 * vw) + vw * d + d * 2 * hv
            + config["linear_conv_kernel_dim"] * (2 * kw + vw) + 2 * hv + dl
            + 2 * d)


def attention_params(config: dict) -> int:
    """Weights of one attention layer's mixer (q with its gate, k, v, o,
    the two head norms), the block's two norms included."""
    d, hd = config["hidden_size"], config["head_dim"]
    q, kv = config["num_attention_heads"] * hd, config["num_key_value_heads"] * hd
    return d * 2 * q + 2 * d * kv + q * d + 2 * hd + 2 * d


def expert_params(config: dict) -> int:
    """One routed expert: gate, up, down."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def state_bytes(config: dict) -> int:
    """One slot's state in one DeltaNet layer: a float32 matrix a value
    head."""
    _kw, _vw, hv, dl = _gdn_widths(config)
    return hv * dl * dl * F32


def conv_tail_bytes(config: dict) -> int:
    kw, vw, _hv, _dl = _gdn_widths(config)
    return (config["linear_conv_kernel_dim"] - 1) * (2 * kw + vw) * BF16


def state_rw_bytes(config: dict, slots: float) -> float:
    """State a decode step reads AND writes, every DeltaNet layer: the
    matrices and the convolution's tails of the slots that decode."""
    return (layer_counts(config)[0] * slots
            * 2 * (state_bytes(config) + conv_tail_bytes(config)))


def kv_row_bytes(config: dict) -> int:
    """One cached position of one attention layer: k and v of every kv
    head."""
    return 2 * config["num_key_value_heads"] * config["head_dim"] * BF16


def kv_bytes(config: dict, rows: float) -> float:
    """k/v a decode step must read, every attention layer (``rows``: cached
    positions attended in one layer, all slots summed)."""
    return layer_counts(config)[1] * rows * kv_row_bytes(config)


def weight_bytes(config: dict, touched: float) -> float:
    """Bytes of the weights one decode step multiplies by, ``touched`` held
    experts a layer having got a token.  The embedding table is left out
    (a step gathers one row a sequence)."""
    d = config["hidden_size"]
    gdn, attn = layer_counts(config)
    width = config.get("num_experts_published", config["num_experts"])
    per_ffn = (d * width + 3 * d * config["shared_expert_intermediate_size"]
               + d + touched * expert_params(config))
    return (gdn * gdn_params(config) + attn * attention_params(config)
            + (gdn + attn) * per_ffn + d + d * config["vocab_size"]) * BF16


def gdn_step_flops(config: dict, slots: float) -> float:
    """One call of the decode kernel (one layer, one step): decay, the
    state's product with k, the rank-one update and the read-out, each over
    a head's whole matrix."""
    _kw, _vw, hv, dl = _gdn_widths(config)
    return 7.0 * slots * hv * dl * dl


def gdn_step_bytes(config: dict, slots: float) -> float:
    """The slots' states in and out, and the step's q, k, decay, v and beta
    (float32, each a row a value head as the kernel takes them) in and the
    read-out out."""
    _kw, _vw, hv, dl = _gdn_widths(config)
    return slots * (2 * state_bytes(config) + 6 * hv * dl * F32)


def gdn_chunk_flops(config: dict, positions: int) -> float:
    """One call of the prefill kernel over ``positions`` (a bucket): a
    chunk a head, three [C, d] x [d, d] products and one [C, C] x [C, d]."""
    _kw, _vw, hv, d = _gdn_widths(config)
    c = GDN_CHUNK
    return -(-positions // c) * hv * 2.0 * c * d * (3 * d + c)


def gdn_chunk_bytes(config: dict, positions: int) -> float:
    """Its float32 operands in (four [C, d], one [C, C], one [d]) and its
    outputs out ([C, d] a chunk, the state once a head)."""
    _kw, _vw, hv, d = _gdn_widths(config)
    c = GDN_CHUNK
    chunks = -(-positions // c) * hv
    return (chunks * (5 * c * d + c * c + d) + hv * d * d) * F32


def attn_flops(config: dict, rows: float) -> float:
    """One decode attention call (one layer, one step): every query head
    against ``rows`` cached positions, scores and the weighted sum."""
    return 4.0 * rows * config["num_attention_heads"] * config["head_dim"]


def attn_bytes(config: dict, batch: int, rows: float) -> float:
    """The attended positions once, the queries in and the result out."""
    q = config["num_attention_heads"] * config["head_dim"]
    return rows * kv_row_bytes(config) + 2 * batch * q * BF16


def moe_layer_flops(config: dict, pairs: float) -> float:
    """The grouped matmuls of one layer (gate, up, down)."""
    return 2.0 * pairs * expert_params(config)


def moe_layer_bytes(config: dict, touched: float, pairs: float) -> float:
    """The touched experts' weights once, each pair's row in (twice: the
    two calls) and out."""
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    return touched * expert_params(config) * BF16 + pairs * (2 * d + 2 * f) * BF16


def step_flops(config: dict, batch: int, slots: float, rows: float,
               pairs: float) -> float:
    """Operations of one decode step: ``batch`` rows through every dense
    matmul, ``pairs`` (token, choice) pairs a layer on held experts, the
    state of ``slots`` and the k / v rows ``rows``."""
    d = config["hidden_size"]
    gdn, attn = layer_counts(config)
    width = config.get("num_experts_published", config["num_experts"])
    per_token = (gdn * gdn_params(config) + attn * attention_params(config)
                 + (gdn + attn) * (d * width + d + 3 * d
                                   * config["shared_expert_intermediate_size"])
                 + d * config["vocab_size"])
    return (2.0 * batch * per_token
            + (gdn + attn) * moe_layer_flops(config, pairs)
            + gdn * gdn_step_flops(config, slots)
            + attn * attn_flops(config, rows))


def step_floor_s(config: dict, peaks: dict, batch: int, slots: float,
                 rows: float, touched: float, pairs: float) -> float:
    """The least seconds the chip could take for one decode step: the
    larger of its bytes over the HBM's peak and its operations over the
    bf16 peak."""
    byts = (weight_bytes(config, touched) + state_rw_bytes(config, slots)
            + kv_bytes(config, rows))
    return max(byts / peaks["hbm_bytes_per_s"],
               step_flops(config, batch, slots, rows, pairs) / peaks["bf16_flops"])


# ------------------------------------------- the program's counters, averaged


def step_means(obs) -> "dict | None":
    """Means over the window's chunks, from the program's ``step_log()``:
    ``slots`` that decode (each one's state is read and written a DeltaNet
    layer a step), ``rows`` (k / v positions one attention layer's step
    attends, all slots summed: the chunk's first step's plus half a chunk a
    slot), ``touched`` experts and ``pairs`` a layer a step, ``max`` pairs
    on one expert."""
    rows = [r for r in window_steps(obs)
            if "state_slots" in r and "kv_rows_full" in r and "moe_assign" in r]
    if not rows:
        return None
    config = obs["config"]
    chunk = config["serve"]["chunk"]
    mean = lambda key: sum(r[key] for r in rows) / len(rows)
    slots = mean("state_slots")
    return {"slots": slots,
            "rows": mean("kv_rows_full") + slots * (chunk - 1) / 2,
            "touched": mean("moe_touched"),
            "pairs": mean("moe_assign") / (chunk * config["num_hidden_layers"]),
            "max": mean("moe_max"), "chunks": len(rows)}
