"""Bytes and operations of a decode step of a decoder whose layers are
gated delta-rule linear attention (KDA) or NoPE latent attention, with
routed experts (Kimi-Linear), and of its kernels, re-derived from the
configuration file's shapes: the sibling of ``mla_moe_counts.py`` (whose
counts of the latent decode attention and of the grouped matmul hold here
unchanged: no rotation changes no byte) and ``window_moe_counts.py``.  A
step MUST read every weight it multiplies by (of the held experts only
those that got a token), the live latent rows of each latent layer, and
must read AND write every decoding slot's state in each KDA layer: a state
is not appended to, it is replaced.  A matmul of ``[m, k]`` by ``[k, n]``
is ``2 m k n`` operations.  The counts of slots, rows, pairs and touched
experts come from the program's ``step_log()`` (``state_slots`` /
``kv_rows_latent`` / ``moe_*``); a program without them gives None and the
readers return nothing.
"""

from __future__ import annotations

from benchmark.harness import mla_moe_counts as M
from benchmark.harness.serve_logs import window_steps

BF16, F32 = 2, 4
CHUNK_PROGRAM = "jit_serve_decode_chunk"
ADMIT_PROGRAM = "jit_serve_admit_"
STEP_KERNEL, CHUNK_KERNEL = "sw_kda_step", "sw_kda_chunk"
KDA_CHUNK = 64     # positions a chunk of the prefill form holds

expert_params = M.expert_params
mla_decode_flops, mla_decode_bytes = M.mla_decode_flops, M.mla_decode_bytes
moe_layer_flops, moe_layer_bytes = M.moe_layer_flops, M.moe_layer_bytes
roofline_s = M.roofline_s


def layer_counts(config: dict) -> tuple:
    """(KDA layers, latent layers) of the layers that are run, read from
    the published 1-based lists."""
    run = range(1, config["num_hidden_layers"] + 1)
    kda = set(config["linear_attn_config"]["kda_layers"])
    return sum(i in kda for i in run), sum(i not in kda for i in run)


def kda_width(config: dict) -> int:
    lin = config["linear_attn_config"]
    return lin["num_heads"] * lin["head_dim"]


def kda_params(config: dict) -> int:
    """Weights of one KDA layer's attention, the block's two norms
    included: q/k/v and o, the two low-rank maps, beta, the convolutions'
    taps, dt_bias, A_log, the head-wise norm."""
    lin = config["linear_attn_config"]
    d, w, dl = config["hidden_size"], kda_width(config), lin["head_dim"]
    return (3 * d * w + w * d + 2 * (d * dl + dl * w) + d * lin["num_heads"]
            + lin["short_conv_kernel_size"] * 3 * w + w + lin["num_heads"] + dl
            + 2 * d)


def latent_params(config: dict) -> int:
    """Weights of one latent layer's attention (a direct q projection),
    norms included."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    r, nope, rope, v = (config["kv_lora_rank"], config["qk_nope_head_dim"],
                        config["qk_rope_head_dim"], config["v_head_dim"])
    return (d * h * (nope + rope) + d * (r + rope) + r + r * h * (nope + v)
            + h * v * d + 2 * d)


def state_bytes(config: dict) -> int:
    """One slot's state in one KDA layer: a float32 matrix a head."""
    lin = config["linear_attn_config"]
    return lin["num_heads"] * lin["head_dim"] ** 2 * F32


def conv_tail_bytes(config: dict) -> int:
    lin = config["linear_attn_config"]
    return (lin["short_conv_kernel_size"] - 1) * 3 * kda_width(config) * BF16


def state_rw_bytes(config: dict, slots: float) -> float:
    """State a decode step reads AND writes, every KDA layer: the matrices
    and the convolutions' tails of the slots that decode."""
    return (layer_counts(config)[0] * slots
            * 2 * (state_bytes(config) + conv_tail_bytes(config)))


def latent_bytes(config: dict, rows: float) -> float:
    """Latent rows a decode step reads, every latent layer (``rows``:
    cached positions attended in one layer, all slots summed)."""
    return layer_counts(config)[1] * rows * M.latent_row_bytes(config)


def weight_bytes(config: dict, touched: float) -> float:
    """Bytes of the weights one decode step multiplies by, ``touched`` held
    experts a routed layer having got a token.  The embedding table is left
    out (a step gathers one row a sequence)."""
    d = config["hidden_size"]
    kda, latent = layer_counts(config)
    dense = config["first_k_dense_replace"]
    routed = kda + latent - dense
    width = config.get("num_experts_published", config["num_experts"])
    per_routed = (d * width * BF16 + width * F32
                  + (config["num_shared_experts"] + touched)
                  * expert_params(config) * BF16)
    return ((kda * kda_params(config) + latent * latent_params(config)) * BF16
            + dense * 3 * d * config["intermediate_size"] * BF16
            + routed * per_routed + (d + d * config["vocab_size"]) * BF16)


def kda_step_flops(config: dict, slots: float) -> float:
    """One call of the decode kernel (one layer, one step): decay, the
    state's product with k, the rank-one update and the read-out, each over
    a head's whole matrix."""
    lin = config["linear_attn_config"]
    return 7.0 * slots * lin["num_heads"] * lin["head_dim"] ** 2


def kda_step_bytes(config: dict, slots: float) -> float:
    """The slots' states in and out, and the step's q, k, decay, v and beta
    (float32, beta as a row a head) in and the read-out out."""
    lin = config["linear_attn_config"]
    return slots * (2 * state_bytes(config)
                    + 6 * lin["num_heads"] * lin["head_dim"] * F32)


def kda_chunk_flops(config: dict, positions: int) -> float:
    """One call of the prefill kernel over ``positions`` (a bucket): a
    chunk a head, three [C, d] x [d, d] products and one [C, C] x [C, d]."""
    lin = config["linear_attn_config"]
    d, c = lin["head_dim"], KDA_CHUNK
    chunks = -(-positions // c) * lin["num_heads"]
    return chunks * 2.0 * c * d * (3 * d + c)


def kda_chunk_bytes(config: dict, positions: int) -> float:
    """Its float32 operands in (four [C, d], one [C, C], one [d]) and its
    outputs out ([C, d] a chunk, the state once a head)."""
    lin = config["linear_attn_config"]
    d, c = lin["head_dim"], KDA_CHUNK
    chunks = -(-positions // c) * lin["num_heads"]
    return (chunks * (5 * c * d + c * c + d) + lin["num_heads"] * d * d) * F32


def step_flops(config: dict, batch: int, slots: float, rows: float,
               pairs: float) -> float:
    """Operations of one decode step: ``batch`` rows through every dense
    matmul, ``pairs`` (token, choice) pairs a routed layer on held experts,
    the state of ``slots`` and the latent rows ``rows``."""
    d = config["hidden_size"]
    kda, latent = layer_counts(config)
    dense = config["first_k_dense_replace"]
    routed = kda + latent - dense
    width = config.get("num_experts_published", config["num_experts"])
    per_token = (kda * kda_params(config) + latent * latent_params(config)
                 + dense * 3 * d * config["intermediate_size"]
                 + routed * (d * width + config["num_shared_experts"]
                             * expert_params(config))
                 + d * config["vocab_size"])
    return (2.0 * batch * per_token + routed * moe_layer_flops(config, pairs)
            + kda * kda_step_flops(config, slots)
            + latent * mla_decode_flops(config, rows))


def step_floor_s(config: dict, peaks: dict, batch: int, slots: float,
                 rows: float, touched: float, pairs: float) -> float:
    """The least seconds the chip could take for one decode step: the
    larger of its bytes over the HBM's peak and its operations over the
    bf16 peak."""
    byts = (weight_bytes(config, touched) + state_rw_bytes(config, slots)
            + latent_bytes(config, rows))
    return max(byts / peaks["hbm_bytes_per_s"],
               step_flops(config, batch, slots, rows, pairs) / peaks["bf16_flops"])


# ------------------------------------------- the program's counters, averaged


def step_means(obs) -> "dict | None":
    """Means over the window's chunks, from the program's ``step_log()``:
    ``slots`` that decode (each one's state is read and written a KDA layer
    a step), ``rows`` (latent positions one layer's step attends, all slots
    summed: the chunk's first step's plus half a chunk a slot), ``touched``
    experts and ``pairs`` a routed layer a step, ``max`` pairs on one
    expert."""
    rows = [r for r in window_steps(obs)
            if "state_slots" in r and "moe_assign" in r]
    if not rows:
        return None
    config = obs["config"]
    chunk = config["serve"]["chunk"]
    routed = config["num_hidden_layers"] - config["first_k_dense_replace"]
    mean = lambda key: sum(r[key] for r in rows) / len(rows)
    slots = mean("state_slots")
    return {"slots": slots,
            "rows": mean("kv_rows_latent") + slots * (chunk - 1) / 2,
            "touched": mean("moe_touched"),
            "pairs": mean("moe_assign") / (chunk * routed),
            "max": mean("moe_max"), "chunks": len(rows)}


def chunk_step_s(obs) -> "float | None":
    """Device seconds of one decode step: the decode-chunk program's own
    seconds over its executions in the traced window (``XLA Modules``),
    over the chunk's steps."""
    modules = (obs.get("trace") or {}).get("modules") or {}
    runs = [v for name, v in modules.items() if name == CHUNK_PROGRAM]
    count = sum(n for n, _s in runs)
    if not count:
        return None
    return sum(s for _n, s in runs) / count / obs["config"]["serve"]["chunk"]
