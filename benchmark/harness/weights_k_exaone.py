"""Seeded random weights of the K-EXAONE block (``exaone_moe``): grouped-query
attention with an RMSNorm over each head's q and k, window RoPE layers
beside full NoPE layers on the published period, a leading dense layer and
then sigmoid-routed SiLU experts beside a shared one, and ONE
multi-token-prediction block behind the last layer; made ON THE DEVICE in
the type they are served in.  After ``weights_window_moe.py``, with the
same rules and the same scales: the benchmark makes the weights, the served
tree and the plain reference are both built from these functions, one
layer's weights depend on (seed, layer) alone and an expert's on (seed,
layer, EXPERT ID) alone, so any share of a layer's experts holds exactly
the numbers the whole layer would.  The MTP block is layer ``L`` of the
same functions (full attention, sparse) plus its own three norms and
``w_eh``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.harness.weights import _norm_weight, _normal, base_key  # noqa: F401
from benchmark.harness.weights_mla_moe import _mlp
from benchmark.harness.weights_window_moe import outer_weights  # noqa: F401


def dims(config: dict) -> dict:
    """Sizes from the configuration file's own (Hugging Face) keys.  The
    layer lists are the published ones, whole; the model is their first
    ``num_hidden_layers`` entries.  ``num_experts`` experts of the router's
    ``num_experts_published`` live here (``expert_share`` says which)."""
    L = config["num_hidden_layers"]
    held = config["num_experts"]
    if config["scoring_func"] != "sigmoid" or not config["norm_topk_prob"]:
        raise ValueError("k_exaone: the router is sigmoid scores, the "
                         "chosen ones normalised")
    if config["n_group"] != 1 or config["topk_group"] != 1:
        raise ValueError("k_exaone: group-limited routing is not modelled")
    if config["rope_parameters"]["rope_type"] != "default":
        raise ValueError("k_exaone: rope scaling is not modelled")
    if config["mlp_layer_types"][:L] != (["dense"] * config["first_k_dense_replace"]
                                        + ["sparse"] * (L - config["first_k_dense_replace"])):
        raise ValueError("k_exaone: dense layers lead, sparse ones follow")
    if (config["num_nextn_predict_layers"] != 1
            or config["mtp_layer_types"] != ["full_attention"]):
        raise ValueError("k_exaone: one MTP block of full attention")
    window = config["sliding_window"]
    sliding = [t == "sliding_attention" for t in config["layer_types"][:L]]
    return {
        "D": config["hidden_size"], "Hq": config["num_attention_heads"],
        "Hkv": config["num_key_value_heads"], "hd": config["head_dim"],
        "F": config["intermediate_size"], "Fe": config["moe_intermediate_size"],
        "E": config.get("num_experts_published", held), "held": held,
        "first_held": config.get("expert_share", 0) * held,
        "top_k": config["num_experts_per_tok"],
        "n_shared": config["num_shared_experts"],
        "scale": float(config["routed_scaling_factor"]),
        "dense": config["first_k_dense_replace"],
        "V": config["vocab_size"], "L": L,
        "windows": tuple(window if s else None for s in sliding),
        "rope": tuple(sliding),   # RoPE on the window layers only (assumed)
        "eps": config["rms_norm_eps"],
        "theta": float(config["rope_parameters"]["rope_theta"]),
        "dtype": config.get("torch_dtype", "bfloat16"),
    }


def runs(d: dict) -> list:
    """``[(first layer, layers)]`` of the segments the served tree is
    stacked in: runs of layers of one attention kind, cut behind the
    leading dense layers (the program's ``LlamaConfig.segment_plan``)."""
    out = []
    for i, kind in enumerate(zip(d["windows"], d["rope"])):
        first = out[-1][0] if out else None
        if (out and kind == (d["windows"][first], d["rope"][first])
                and (i < d["dense"]) == (first < d["dense"])):
            out[-1][1] += 1
        else:
            out.append([i, 1])
    return [tuple(r) for r in out]


def expert_weights(key, i, e, d: dict) -> dict:
    """Routed expert ``e`` of layer ``i``: gate, up, down."""
    ks = jax.random.split(jax.random.fold_in(
        jax.random.fold_in(key, i), (1 << 16) + e), 3)
    return _mlp(ks, (), d["D"], d["Fe"], jnp.dtype(d["dtype"]))


def layer_weights(key, i, d: dict, sparse: bool) -> dict:
    """Layer ``i`` (``d["L"]``: the MTP block's): grouped-query attention
    with the head norms, and a dense gated MLP or the router over all ``E``
    experts, its selection bias, the held experts stacked and the shared
    expert."""
    dtype = jnp.dtype(d["dtype"])
    ks = jax.random.split(jax.random.fold_in(key, i), 16)
    D, q, kv = d["D"], d["Hq"] * d["hd"], d["Hkv"] * d["hd"]
    w = {
        "attn_norm": _norm_weight(ks[0], D, dtype),
        "mlp_norm": _norm_weight(ks[1], D, dtype),
        "wq": _normal(ks[2], (D, q), D ** -0.5, dtype),
        "wk": _normal(ks[3], (D, kv), D ** -0.5, dtype),
        "wv": _normal(ks[4], (D, kv), D ** -0.5, dtype),
        "wo": _normal(ks[5], (q, D), q ** -0.5, dtype),
        "q_head_norm": _norm_weight(ks[6], d["hd"], dtype),
        "k_head_norm": _norm_weight(ks[7], d["hd"], dtype),
    }
    if not sparse:
        return {**w, **_mlp(ks[8:11], (), D, d["F"], dtype)}
    held = d["first_held"] + jnp.arange(d["held"])
    w["routed"] = {
        "router": _normal(ks[8], (D, d["E"]), D ** -0.5, dtype),
        # Small and not zero: the selection (scores + bias) is worked, and
        # the experts' popularity stays the scores' (kimi-k2's convention).
        "bias": 0.005 * jax.random.normal(ks[9], (d["E"],), jnp.float32),
        "shared": _mlp(ks[11:14], (), D, d["n_shared"] * d["Fe"], dtype),
        **lax.map(lambda e: expert_weights(key, i, e, d), held)}
    return w


def mtp_weights(key, d: dict) -> dict:
    """The MTP block: its three norms, ``w_eh [2D, D]`` and its one layer
    (full attention, sparse), which is layer ``L`` of :func:`layer_weights`."""
    dtype, D = jnp.dtype(d["dtype"]), d["D"]
    ks = jax.random.split(jax.random.fold_in(key, (1 << 20) + 1), 4)
    return {"embed_norm": _norm_weight(ks[0], D, dtype),
            "hidden_norm": _norm_weight(ks[1], D, dtype),
            "final_norm": _norm_weight(ks[2], D, dtype),
            "w_eh": _normal(ks[3], (2 * D, D), (2 * D) ** -0.5, dtype),
            "layer": layer_weights(key, d["L"], d, True)}


def make_model(seed: int, d: dict) -> dict:
    """The whole model: ``layers`` is a tuple of stacked segments
    (:func:`runs`), ``mtp`` the block with its layer stacked as a segment
    of one, plus ``embed`` / ``final_norm`` / ``lm_head``.  One jitted
    call a segment, so that the float32 intermediates of one do not sit
    beside the other's."""
    key = base_key(seed)
    segs = [jax.jit(lambda k, lo=lo, n=n: lax.map(
        lambda i: layer_weights(k, i, d, lo >= d["dense"]),
        lo + jnp.arange(n)))(key) for lo, n in runs(d)]
    out = jax.jit(lambda k: outer_weights(k, d))(key)
    out["layers"] = tuple(segs)
    mtp = jax.jit(lambda k: mtp_weights(k, d))(key)
    mtp["layers"] = jax.tree_util.tree_map(lambda a: a[None], mtp.pop("layer"))
    out["mtp"] = mtp
    return out
