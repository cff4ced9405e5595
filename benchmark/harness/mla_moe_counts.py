"""Bytes and operations of a latent-attention, routed-expert decoder's
decode step and of its two kernels, re-derived from the configuration
file's shapes: the sibling of ``decode_bytes.py`` (a dense GQA step).  A
step MUST read every weight it multiplies by (of the held experts only
those that got a token) and the live latent rows; a matmul of ``[m, k]`` by
``[k, n]`` is ``2 m k n`` operations.  The program's own counts are not
read.
"""

from __future__ import annotations

BF16 = 2


def attention_params(config: dict) -> int:
    """Weights of one layer's attention, norms included."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    rq, rkv = config["q_lora_rank"], config["kv_lora_rank"]
    nope, rope, v = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                     config["v_head_dim"])
    return (d * rq + rq                   # wq_a, q_norm
            + rq * h * (nope + rope)      # wq_b
            + d * (rkv + rope) + rkv      # wkv_a, kv_norm
            + rkv * h * (nope + v)        # wkv_b
            + h * v * d                   # wo
            + 2 * d)                      # the block's two norms


def expert_params(config: dict) -> int:
    """One routed expert: gate, up, down."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def layer_counts(config: dict) -> tuple:
    """(dense layers, routed layers)."""
    dense = config["first_k_dense_replace"]
    return dense, config["num_hidden_layers"] - dense


def router_width(config: dict) -> int:
    return config.get("n_routed_experts_published", config["n_routed_experts"])


def weight_bytes(config: dict, touched: float) -> float:
    """Bytes of the weights one decode step multiplies by, ``touched`` held
    experts a routed layer having got a token.  The embedding table is left
    out (a step gathers one row a sequence)."""
    d = config["hidden_size"]
    dense, routed = layer_counts(config)
    per_routed = (d * router_width(config) * BF16 + router_width(config) * 4
                  + config["n_shared_experts"] * expert_params(config) * BF16
                  + touched * expert_params(config) * BF16)
    return ((dense + routed) * attention_params(config) * BF16
            + dense * 3 * d * config["intermediate_size"] * BF16
            + routed * per_routed
            + (d + d * config["vocab_size"]) * BF16)


def latent_row_bytes(config: dict) -> int:
    """One cached position of one layer, as the algorithm needs it (the
    512 + 64 values; the chip allocates 640)."""
    return (config["kv_lora_rank"] + config["qk_rope_head_dim"]) * BF16


def step_flops(config: dict, batch: int, live_rows: float, pairs: float) -> float:
    """Operations of one decode step: ``batch`` rows through every matmul,
    attention over ``live_rows`` cached positions (all sequences summed),
    ``pairs`` (token, choice) pairs a routed layer on held experts."""
    d = config["hidden_size"]
    dense, routed = layer_counts(config)
    layers = dense + routed
    per_token = (layers * (attention_params(config) - 2 * d - config["q_lora_rank"]
                           - config["kv_lora_rank"])
                 + dense * 3 * d * config["intermediate_size"]
                 + routed * (d * router_width(config)
                             + config["n_shared_experts"] * expert_params(config))
                 + d * config["vocab_size"])
    return (2.0 * batch * per_token
            + routed * 2.0 * pairs * expert_params(config)
            + layers * mla_decode_flops(config, live_rows))


def step_floor_s(config: dict, peaks: dict, batch: int, live_rows: float,
                 touched: float, pairs: float) -> float:
    """The least seconds the chip could take for one decode step: the
    larger of its bytes over the HBM's peak and its operations over the
    bf16 peak."""
    layers = sum(layer_counts(config))
    byts = weight_bytes(config, touched) + layers * live_rows * latent_row_bytes(config)
    return max(byts / peaks["hbm_bytes_per_s"],
               step_flops(config, batch, live_rows, pairs) / peaks["bf16_flops"])


# ------------------------------------------------------------------ kernels


def mla_decode_flops(config: dict, live_rows: float) -> float:
    """One call of the absorbed decode attention (one layer, one step):
    every head's query against every live row, whole (scores) and over its
    first ``kv_lora_rank`` values (the weighted sum)."""
    h, r = config["num_attention_heads"], config["kv_lora_rank"]
    return 2.0 * live_rows * h * (r + config["qk_rope_head_dim"] + r)


def mla_decode_bytes(config: dict, batch: int, live_rows: float) -> float:
    """The live rows once, the absorbed queries in and ``P c_kv`` out."""
    h, r = config["num_attention_heads"], config["kv_lora_rank"]
    return (live_rows * latent_row_bytes(config)
            + batch * h * (r + config["qk_rope_head_dim"] + r) * BF16)


def moe_layer_flops(config: dict, pairs: float) -> float:
    """The grouped matmuls of one routed layer (gate, up, down)."""
    return 2.0 * pairs * expert_params(config)


def moe_layer_bytes(config: dict, touched: float, pairs: float) -> float:
    """The touched experts' weights once, each pair's row in (twice: the
    two calls) and out."""
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    return touched * expert_params(config) * BF16 + pairs * (2 * d + 2 * f) * BF16


def roofline_s(flops: float, byts: float, peaks: dict) -> float:
    return max(flops / peaks["bf16_flops"], byts / peaks["hbm_bytes_per_s"])
