"""Compact pure-JAX Llama family (RMSNorm + RoPE + GQA + SwiGLU).

TPU-first construction:

* layer parameters are *stacked* on a leading ``[n_layers, ...]`` axis and
  the forward pass is a single ``lax.scan`` over them -- one compiled layer
  body regardless of depth, optionally rematerialised (``cfg.remat``) to
  trade FLOPs for HBM;
* attention is pluggable: :func:`~starway_tpu.ops.attention.blockwise_attention`
  single-device, or sequence-parallel ring attention over an ICI mesh axis
  (:func:`make_sharded_attn`), keeping long context first-class;
* matmuls run in ``cfg.dtype`` (bfloat16 on TPU -> MXU) with f32 accumulators
  in the softmax/norm chains;
* sharding is declarative: :func:`param_specs` gives the GSPMD PartitionSpec
  tree (tp on head/ff dims, replicated norms) and XLA inserts the
  collectives.

Presets include ``llama3-8b`` (the BASELINE config 5 workload shape) and
scaled-down variants for tests and the graft entry.
"""

from __future__ import annotations

import dataclasses
import functools
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ..ops import quantized_matmul, self_attention


@dataclasses.dataclass(frozen=True)
class LatentAttn:
    """Attention kind: multi-head latent attention (models/mla.py).  Low-rank
    q and kv projections, heads split into a no-rope and a rope part, one
    rope key head shared by all heads; the cache holds ``kv_rank +
    rope_dim`` values a token a layer.  ``q_rank`` None: the queries come
    from ONE projection ``wq`` (``q_lora_rank: null``), no low-rank pair
    and no norm between.  Whether the rope part is rotated at all is the
    layer's kind (``LayerKinds.rope``: a NoPE latent layer caches ``k_pe``
    as projected)."""
    q_rank: Optional[int]
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    # The scores' whole multiplier: (nope_dim + rope_dim) ** -0.5, times
    # the square of YaRN's mscale where the checkpoint has one.
    sm_scale: float

    @property
    def cache_width(self) -> int:
        """Values a cached row is ALLOCATED: ``kv_rank + rope_dim`` rounded
        up to whole 128-lane tiles, zeros above.  The chip lays the array
        out so whatever is asked for (576 -> 640), and its compiler takes
        no DMA of part of a tile: the padding is made explicit."""
        return -(-(self.kv_rank + self.rope_dim) // 128) * 128


@dataclasses.dataclass(frozen=True)
class LinearAttn:
    """Attention kind: gated delta-rule linear attention (models/kda.py).
    ``n_heads`` value heads of ``head_dim`` (keys are as wide); q, k and v
    pass a depthwise causal convolution of ``conv`` taps.  A layer of this
    kind keeps no keys and no values: its state a request is one
    ``[head_dim, head_dim]`` float32 matrix a value head and the last
    ``conv - 1`` inputs of the convolution, whatever the length.

    ``decay`` selects the parameterisation, which is also the layer's tree:
    ``"channel"`` (Kimi Delta Attention): a log-decay a CHANNEL of a head's
    keys and a sigmoid output gate, both through low-rank maps of rank
    ``head_dim``, three projections side by side (``wqkv``);
    ``"head"`` (Gated DeltaNet, Qwen3-Next): ONE log-decay a value head,
    made with ``beta`` by one ``[D, 2 * n_heads]`` projection (``w_ba``),
    and the output gate ``SiLU(z)`` of a full projection ``z`` that rides
    the q/k/v projection (``w_qkvz``).
    ``n_k_heads`` (default ``n_heads``): key heads, where they are fewer
    than the value heads: value head ``j`` reads the q and k of key head
    ``j // (n_heads // n_k_heads)``; the state's shape does not change."""
    n_heads: int
    head_dim: int
    conv: int = 4
    n_k_heads: Optional[int] = None
    decay: str = "channel"

    def __post_init__(self):
        if self.decay not in ("channel", "head"):
            raise ValueError(f"LinearAttn.decay must be 'channel' or 'head', "
                             f"got {self.decay!r}")
        if self.n_heads % self.key_heads:
            raise ValueError(f"LinearAttn: {self.n_heads} value heads do not "
                             f"share {self.key_heads} key heads evenly")

    @property
    def width(self) -> int:
        """Channels of v (and of the layer's output before ``wo``)."""
        return self.n_heads * self.head_dim

    @property
    def key_heads(self) -> int:
        return self.n_k_heads or self.n_heads

    @property
    def key_width(self) -> int:
        """Channels of each of q and k."""
        return self.key_heads * self.head_dim

    @property
    def conv_width(self) -> int:
        """Channels the convolution runs over: q, k and v side by side."""
        return 2 * self.key_width + self.width


@dataclasses.dataclass(frozen=True)
class StateSpace:
    """Mixer kind: a selective state-space layer (Mamba-1, models/ssm.py).
    ``d_inner`` channels behind a depthwise causal convolution of ``conv``
    taps, each with ``d_state`` states under a diagonal transition that the
    token chooses through a step ``dt`` of rank ``dt_rank``.  A layer of
    this kind keeps no keys and no values: its state a request is one
    ``[d_state, d_inner]`` float32 matrix and the convolution's last
    ``conv - 1`` inputs, whatever the length."""
    d_inner: int
    d_state: int = 16
    dt_rank: int = 160
    conv: int = 4


# The kinds a layer of a ``LayerKinds.runs`` model may be, and the leaves
# of the cache each keeps (``LlamaConfig.cache_kind``).
MIXERS = {"full": "full", "window": "ring", "linear": "linear",
          "ssm": "ssm", "gmu": None, "cross": None}


@dataclasses.dataclass(frozen=True)
class RoutedFFN:
    """FFN kind: routed, dropless experts beside ``n_shared`` shared ones
    (models/moe.py::routed_ffn).  The router is ``n_experts`` wide and
    every token takes ``top_k``; THIS holder computes experts
    ``first_held .. first_held + n_held - 1`` (one chip's share of an
    expert-parallel deployment; all of them when ``n_held ==
    n_experts``).  The first ``first_dense`` layers are dense SwiGLU of
    width ``d_ff`` and form a segment of their own.

    ``score``: ``"sigmoid"`` (DeepSeek-V3: sigmoid scores, the selection
    corrected by the ``bias`` leaf, gates the chosen scores over their
    sum) or ``"softmax"`` (the ``top_k`` largest logits, gates their
    softmax; no ``bias`` leaf); either times ``scale``.  ``act``: the
    experts' gate activation (``"silu"``: SwiGLU; ``"relu"``: ReGLU).
    ``router_in``: which normed state the router scores, the FFN's own
    input (``"mlp_norm"``) or the block's input as attention sees it
    (``"attn_norm"``: experts are chosen BEFORE attention and applied
    after it).  ``shared_gate``: the shared expert's output is scaled by a
    gate of its own, ``sigmoid(x . shared_gate)``, one number a token (the
    ``shared_gate [D, 1]`` leaf; Qwen3-Next)."""
    n_experts: int
    top_k: int
    d_expert: int
    n_held: int
    first_held: int = 0
    n_shared: int = 1
    scale: float = 1.0
    first_dense: int = 0
    score: str = "sigmoid"
    act: str = "silu"
    router_in: str = "mlp_norm"
    shared_gate: bool = False

    def __post_init__(self):
        if self.shared_gate and not self.n_shared:
            raise ValueError("RoutedFFN.shared_gate gates the shared expert: "
                             "it needs n_shared >= 1")
        for field, allowed in (("score", ("sigmoid", "softmax")),
                               ("act", ("silu", "relu")),
                               ("router_in", ("mlp_norm", "attn_norm"))):
            if getattr(self, field) not in allowed:
                raise ValueError(f"RoutedFFN.{field} must be one of "
                                 f"{allowed}, got {getattr(self, field)!r}")


@dataclasses.dataclass(frozen=True)
class LayerKinds:
    """Attention kinds that differ by layer, on a period: layer ``i`` has
    the window ``windows[i % period]`` (None: full causal attention),
    rotates q and k where ``rope[i % period]`` (False: no positional
    encoding, NoPE) and is a LINEAR layer (``cfg.linear``: models/kda.py)
    where ``linear[i % period]``; the others attend, latent where
    ``cfg.latent`` is set and grouped-query else.  Layers of one kind in a
    row are one SEGMENT of the stacked tree (``layer_segments``).  The
    windows of a model are of one length: its cache then holds the full
    layers' rows of ``max_len`` beside the window layers' RINGS of that
    length and the linear layers' STATE, which has no position axis at all
    (models/cache.py::init_cache).  ``slack``: positions a ring holds
    BEYOND its window.  0: the ring's warm slots ARE the window and a step
    writes one position.  A step that writes ``C`` positions before it
    knows which of them stay (a draft beside the pending token) needs
    ``slack >= C - 1``: the ring is then read under position masks, and
    neither the draft's write nor its rejection touches an entry a live
    query attends (DESIGN.md 9b).  A model whose every layer has the
    same window is ``LlamaConfig.sliding_window``'s, not this group's.

    ``runs`` (:meth:`in_runs` builds it): the kinds are NOT one period over
    the depth but RUNS OF PERIODS, ``((kinds of one period, repeats),
    ...)`` with the kinds named (``MIXERS``): ``"full"`` / ``"window"`` /
    ``"linear"`` as above, ``"ssm"`` (a state-space layer, ``cfg.ssm``:
    models/ssm.py), ``"gmu"`` (a gated memory unit: it gates the LAST ssm
    layer's scan output of the same token and keeps nothing) and
    ``"cross"`` (attention that projects q only and reads the rows of the
    last ``"full"`` layer before it; it keeps nothing either).  The three
    tuples then hold one entry a LAYER, a run is one segment of the stacked
    tree, scanned a whole period a body (``layer_segments``), and state,
    rings and full rows may all lie in one cache."""
    windows: tuple
    rope: tuple
    linear: tuple = ()
    slack: int = 0
    runs: tuple = ()

    @classmethod
    def in_runs(cls, runs, *, window: Optional[int] = None,
                slack: int = 0) -> "LayerKinds":
        """Kinds laid out as runs of periods: ``runs = ((kinds of one
        period, repeats), ...)``; ``window``: the ``"window"`` layers'
        one length.  No layer rotates (the one model laid out so has no
        positional encoding; ``cfg.diff_attn`` refuses a rotation)."""
        runs = tuple((tuple(period), int(reps)) for period, reps in runs)
        kinds = [k for period, reps in runs for _ in range(reps)
                 for k in period]
        return cls(windows=tuple(window if k == "window" else None
                                 for k in kinds),
                   rope=(False,) * len(kinds),
                   linear=tuple(k == "linear" for k in kinds), slack=slack,
                   runs=runs)

    def __post_init__(self):
        object.__setattr__(self, "windows", tuple(self.windows))
        object.__setattr__(self, "rope", tuple(bool(r) for r in self.rope))
        object.__setattr__(self, "linear", tuple(
            bool(x) for x in (self.linear or (False,) * len(self.windows))))
        if not self.windows or not (len(self.windows) == len(self.rope)
                                    == len(self.linear)):
            raise ValueError(
                f"LayerKinds needs one window (or None), one rope flag and "
                f"one linear flag a layer of the period, got {self.windows} "
                f"/ {self.rope} / {self.linear}")
        sizes = {w for w in self.windows if w is not None}
        if self.runs:
            kinds = self.mixers
            first = {k: kinds.index(k) for k in set(kinds) & set(MIXERS)}
            if (len(kinds) != len(self.windows) or set(kinds) - set(MIXERS)
                    or any((k == "window") != (w is not None)
                           or (k == "linear") != lin for k, w, lin in
                           zip(kinds, self.windows, self.linear))
                    or len(sizes) > 1 or "full" not in kinds
                    or first.get("cross", len(kinds)) < first["full"]
                    or first.get("gmu", len(kinds)) < first.get("ssm",
                                                                 len(kinds))
                    or ("gmu" in first and "ssm" not in first)):
                raise ValueError(
                    f"LayerKinds.runs names one kind a layer out of "
                    f"{sorted(MIXERS)}, windows of ONE length, a full layer "
                    f"before any cross layer and an ssm layer before any "
                    f"gmu (LayerKinds.in_runs builds it), got {self.runs} "
                    f"/ {self.windows} / {self.linear}")
        elif any(self.linear):
            if sizes or all(self.linear):
                raise ValueError(
                    f"LayerKinds with linear layers needs attention layers "
                    f"beside them and no window, got {self.windows} / "
                    f"{self.linear}")
        elif len(sizes) != 1 or min(sizes) < 1 or None not in self.windows:
            raise ValueError(
                f"LayerKinds needs full layers (None) beside window layers "
                f"of ONE length >= 1, got {self.windows}")
        if self.slack < 0 or (self.slack and not sizes):
            raise ValueError(f"LayerKinds.slack lengthens rings: it needs "
                             f"window layers and >= 0, got {self.slack}")

    @functools.cached_property
    def mixers(self) -> tuple:
        """The kind of every layer by name, of a model laid out in
        ``runs`` (``()`` for a model on one period)."""
        return tuple(k for period, reps in self.runs for _ in range(reps)
                     for k in period)

    def segments(self) -> list:
        """``[(first layer, layers, kinds of one period)]`` of the runs."""
        out, at = [], 0
        for period, reps in self.runs:
            out.append((at, reps * len(period), period))
            at += reps * len(period)
        return out

    @property
    def window(self) -> Optional[int]:
        """The window layers' one length: the ring's (None: no window
        layer)."""
        return next((w for w in self.windows if w is not None), None)

    @property
    def ring(self) -> Optional[int]:
        """Positions a window layer's ring holds: the window and the
        slack (None: no window layer)."""
        return None if self.window is None else self.window + self.slack


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    d_ff: int = 14336
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    remat: bool = False
    # Sliding-window (Mistral-style) attention: each position attends to
    # the last `sliding_window` tokens only.  None = full causal.
    sliding_window: Optional[int] = None
    # Mixture-of-experts FFN (0 = dense SwiGLU).  Experts shard over the
    # mesh "ep" axis (models/moe.py).
    n_experts: int = 0
    moe_capacity_factor: float = 1.25
    moe_aux_coef: float = 0.01
    moe_top_k: int = 1
    # SwiGLU experts (Mixtral family): adds a w_gate [L, E, D, F] leaf and
    # switches _expert_ffn to silu(x@w_gate) * (x@w_in) @ w_out.
    moe_swiglu: bool = False
    # Gated-MLP activation: "silu" (Llama SwiGLU) or "gelu_tanh" (Gemma
    # GeGLU, = HF's gelu_pytorch_tanh).
    mlp_act: str = "silu"
    # Gemma-style sqrt(d_model) scaling of the token embedding OUTPUT
    # (the tied lm_head reads the UNSCALED table, so this cannot fold
    # into the weights).
    scaled_embed: bool = False
    # KV-cache storage: "none" keeps compute_dtype; "int8" stores the cache
    # int8 with per-token scales (ops/quantize.py) — half the HBM bytes on
    # the bandwidth-bound decode stream, double the servable context.
    kv_quant: str = "none"
    # Per-head dim override.  None derives d_model // n_heads (the classic
    # tie, recomputed on every access so dataclasses.replace(n_heads=...)
    # can never carry a stale value); modern HF checkpoints may pin it
    # independently (q/k/v project to n_heads * head_dim != d_model) —
    # every projection/reshape in this module keys off cfg.head_dim.
    head_dim_override: Optional[int] = None
    # Per-head q/k/v projection biases (Qwen2-family checkpoints; Llama/
    # Mistral have none).  Adds bq/bk/bv [L, H*hd] leaves to the layer
    # tree; consumers key off the LEAVES' presence (qkv_proj), so a
    # converted tree works even where the config doesn't travel.
    attn_bias: bool = False
    # Rematerialisation policy when ``remat`` is on.  None = full-layer
    # recompute (lowest memory, ~1 extra forward of flops in the backward
    # — an MFU ceiling of ~0.75x hardware efficiency against the 6ND
    # count).  "dots" = save every no-batch-dim matmul output AND the
    # attention kernel's output (tagged "attn_out" in decoder_layer), so
    # the backward re-runs only the cheap elementwise chain (norms, rope,
    # silu) — the remat knob for MFU-bound training (the train_step_mfu
    # >= 0.40 target, ROADMAP.md S7) at O(S * D) extra saved bytes per
    # layer.
    remat_policy: Optional[str] = None
    # Layer iteration: True scans one compiled body over the stacked layer
    # tree (constant compile time at any depth); False unrolls a Python
    # loop over per-layer slices, which lets XLA schedule across layer
    # boundaries at the cost of depth-proportional compile time.  The
    # chunked "dots" remat is recompute-free under BOTH (pinned in
    # tests/test_remat_policy.py); scanned is the default and the
    # MFU-bench setting.
    scan_layers: bool = True
    # RoPE frequency scaling, as a hashable tuple (configs key jit caches):
    #   ("linear", factor)  — all frequencies divided by factor;
    #   ("llama3", factor, low_freq_factor, high_freq_factor,
    #    original_max_position_embeddings) — Llama-3.1's banded scheme
    #    (long wavelengths scaled, short kept, smooth band between).
    # None = unscaled.  Applied inside rope_tables via cfg_rope_tables.
    rope_scaling: Optional[tuple] = None
    # The kinds of a block, where they are not the defaults (grouped-query
    # attention; one dense or capacity-MoE FFN for every layer).  A model
    # is a sequence of homogeneous SEGMENTS of layers, each a stacked tree
    # that one scan walks (layer_segments); what a layer is follows from
    # its leaves, what it needs beyond them from these.
    latent: Optional[LatentAttn] = None
    routed: Optional[RoutedFFN] = None
    kinds: Optional[LayerKinds] = None
    linear: Optional[LinearAttn] = None
    # RMSNorm over each head's q and k before the rotation, one gain vector
    # of head_dim a layer each (``q_head_norm`` / ``k_head_norm`` leaves;
    # qkv_proj keys off their presence).
    qk_norm: bool = False
    # Values of a head that are rotated, where that is not all of them
    # (``partial_rotary_factor``): the first ``rotary_dim`` of each q and k
    # head turn, split-half among themselves, the rest pass as projected.
    # The tables are that wide (``rope_dim``) and ``apply_rope`` reads the
    # width off them.  Grouped-query attention only.
    rotary_dim: Optional[int] = None
    # An output gate on attention: ``wq`` is twice as wide, each head's
    # ``head_dim`` query values beside as many gate values, and the heads'
    # outputs are multiplied by ``sigmoid(gate)`` before ``wo``
    # (Qwen3-Next).  Grouped-query attention only.
    attn_gate: bool = False
    # Every RMSNorm of the model multiplies by ``1 + w`` in float32, not
    # by ``w`` (a zero-centred gain: Gemma's and Qwen3-Next's form; a
    # linear layer's head-wise output norm is not one of them).
    norm_zero_centred: bool = False
    # Multi-token-prediction blocks behind the last layer (models/mtp.py):
    # each one decoder layer of the model's own kinds (full attention, the
    # FFN of the later layers) that reads the main model's last hidden
    # state and the NEXT token and predicts the token after it, sharing the
    # embedding and the head; its weights are the subtree ``params["mtp"]``.
    # A SlotServer built on such a configuration drafts with it and
    # verifies inside every decode step.  0 or 1.
    mtp: int = 0
    # The state-space layers' sizes (models/ssm.py): goes with
    # ``kinds.runs`` naming ``"ssm"`` layers, and the other way.
    ssm: Optional[StateSpace] = None
    # The norm at every site ``cfg_rmsnorm`` serves: ``"rms"``, or
    # ``"layer"``: LayerNorm with a gain and a bias, its weight leaf ``[2,
    # D]`` (gain, then bias) wherever an RMSNorm's is ``[D]``.
    norm: str = "rms"
    # Differential attention (arXiv 2410.05258) over ADJACENT pairs of
    # heads: two softmaxes a pair, subtracted under a scalar a layer, an
    # RMSNorm over the pair's output (:func:`diff_q`, :func:`diff_kv`,
    # :func:`diff_combine`).  The cache holds a kv PAIR as one head twice
    # as wide (``kv_cache_heads`` / ``kv_cache_dim``).
    diff_attn: bool = False
    # The head IS the embedding table: no ``lm_head`` leaf, the logits
    # contract over the table's ``D`` axis where it lies
    # (:func:`lm_head_matmul`).
    tied: bool = False

    def __post_init__(self):
        has_ssm = self.kinds is not None and "ssm" in self.kinds.mixers
        if has_ssm != (self.ssm is not None):
            raise ValueError(
                "ssm (the state-space layers' sizes) goes with kinds.runs "
                "naming ssm layers (which they are), and the other way")
        if self.norm not in ("rms", "layer"):
            raise ValueError(f"norm must be 'rms' or 'layer', got "
                             f"{self.norm!r}")
        if self.diff_attn and (
                self.latent is not None or self.n_heads % 2
                or self.n_kv_heads % 2 or self.kv_quant != "none"
                or (self.n_heads // 2) % (self.n_kv_heads // 2)
                or self.attn_gate or self.qk_norm or self.mtp
                or self.kinds is None or not self.kinds.runs
                or any(self.kinds.rope)):
            raise ValueError(
                "diff_attn pairs adjacent grouped-query heads of a model "
                "laid out in kinds.runs: it needs even head counts, a "
                "bf16/f32 cache and no rotation, latent attention, "
                "attention gate, head norms or MTP block")
        if self.kinds is not None and self.kinds.runs and (
                len(self.kinds.mixers) != self.n_layers or self.routed
                or self.n_experts or self.mtp or self.latent is not None
                or not self.diff_attn or "linear" in self.kinds.mixers):
            raise ValueError(
                f"kinds.runs lays out {len(self.kinds.mixers)} layers of a "
                f"dense model whose attention layers attend differentially "
                f"(diff_attn), with no MTP block and no delta-rule layer "
                f"(both are one more branch of the period's body, not "
                f"written yet), got n_layers={self.n_layers}")
        if self.mtp not in (0, 1):
            raise ValueError(
                f"mtp must be 0 or 1 (one draft a step; a second needs a "
                f"verify of three positions: ROADMAP M5), got {self.mtp}")
        if self.mtp and (self.latent is not None or self.linear is not None
                         or self.kv_quant != "none" or self.n_experts
                         or self.sliding_window is not None):
            raise ValueError(
                "an MTP block drafts over grouped-query rows and rings in "
                "bf16/f32: no latent rows (the draft's entry would have to "
                "be taken out of the latent cache), no linear layers (a "
                "state moved on by a rejected draft cannot be taken back), "
                "no int8 cache, no whole-model rolling window, no capacity "
                "MoE (ROADMAP M5)")
        if self.latent is not None and (self.rotary_dim is not None
                                        or self.attn_gate):
            raise ValueError("rotary_dim and attn_gate are grouped-query "
                             "attention's: latent attention has a rope part "
                             "of its own and no gate")
        has_linear = self.kinds is not None and any(self.kinds.linear)
        if has_linear != (self.linear is not None):
            raise ValueError(
                "linear (the linear-attention layers' sizes) goes with "
                "kinds.linear (which layers they are), and the other way")
        if self.kinds is not None and (
                self.sliding_window is not None or self.kv_quant != "none"
                or (self.latent is not None and not has_linear)):
            raise ValueError(
                "kinds (window, RoPE and linear by layer) goes with a "
                "bf16/f32 cache and no whole-model sliding_window; latent "
                "attention beside it only as the attention layers of a "
                "model with linear layers")
        if self.sliding_window is not None and self.sliding_window < 1:
            raise ValueError(
                f"sliding_window must be >= 1, got {self.sliding_window}")
        if self.kv_quant not in ("none", "int8"):
            raise ValueError(
                f"kv_quant must be 'none' or 'int8', got {self.kv_quant!r}")
        if self.head_dim_override is None:
            if self.d_model % self.n_heads:
                raise ValueError(
                    f"d_model={self.d_model} not divisible by "
                    f"n_heads={self.n_heads}; pass head_dim_override")
        elif self.head_dim_override < 2 or self.head_dim_override % 2:
            raise ValueError(f"head_dim_override must be an even int >= 2, "
                             f"got {self.head_dim_override}")
        if self.rotary_dim is not None and (
                self.rotary_dim < 2 or self.rotary_dim % 2
                or self.rotary_dim > self.head_dim):
            raise ValueError(f"rotary_dim must be an even int in [2, "
                             f"head_dim={self.head_dim}], got "
                             f"{self.rotary_dim}")
        if self.mlp_act not in ("silu", "gelu_tanh"):
            raise ValueError(
                f"mlp_act must be 'silu' or 'gelu_tanh', got "
                f"{self.mlp_act!r}")
        if self.remat_policy not in (None, "dots"):
            raise ValueError(
                f"remat_policy must be None or 'dots', got "
                f"{self.remat_policy!r}")
        if self.remat_policy is not None and not self.remat:
            raise ValueError(
                "remat_policy is set but remat is False — the policy "
                "would be silently ignored; set remat=True")
        if self.rope_scaling is not None:
            s = tuple(self.rope_scaling)
            if not s or s[0] not in ("linear", "llama3", "yarn",
                                     "longrope", "longrope_fixed") or (
                    s[0] == "linear" and len(s) != 2) or (
                    s[0] == "llama3" and len(s) != 5) or (
                    s[0] == "yarn" and len(s) != 7) or (
                    s[0] == "longrope" and len(s) != 5) or (
                    s[0] == "longrope_fixed" and len(s) != 3):
                raise ValueError(
                    f"rope_scaling must be ('linear', factor), ('llama3', "
                    f"factor, low_freq_factor, high_freq_factor, "
                    f"original_max_position_embeddings), ('yarn', "
                    f"factor, original_max_position_embeddings, beta_fast, "
                    f"beta_slow, attention_factor, truncate), or "
                    f"('longrope', original_max_position_embeddings, "
                    f"attention_factor, short_factors, long_factors), got "
                    f"{self.rope_scaling!r}")
            if s[0] == "longrope":
                short, long = tuple(s[3]), tuple(s[4])
                half = self.head_dim // 2
                if len(short) != half or len(long) != half:
                    raise ValueError(
                        f"longrope factor lists must have head_dim//2="
                        f"{half} entries, got {len(short)}/{len(long)}")
                s = (s[0], s[1], s[2], short, long)
            elif s[0] == "longrope_fixed":
                ext = tuple(s[2])
                if len(ext) != self.head_dim // 2:
                    raise ValueError(
                        f"longrope_fixed factors must have head_dim//2="
                        f"{self.head_dim // 2} entries, got {len(ext)}")
                s = (s[0], s[1], ext)
            object.__setattr__(self, "rope_scaling", s)

    @property
    def head_dim(self) -> int:
        if self.head_dim_override is not None:
            return self.head_dim_override
        return self.d_model // self.n_heads

    @property
    def rope_dim(self) -> int:
        """Width of the rotated part of a head: the tables' width."""
        if self.latent:
            return self.latent.rope_dim
        return self.rotary_dim or self.head_dim

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def kv_cache_heads(self) -> int:
        """Heads a cached position of a grouped-query layer holds: under
        ``diff_attn`` a PAIR of kv heads is one."""
        return self.n_kv_heads // 2 if self.diff_attn else self.n_kv_heads

    @property
    def kv_cache_dim(self) -> int:
        """Their width: a pair's two heads side by side."""
        return self.head_dim * (2 if self.diff_attn else 1)

    @property
    def attn_scale(self) -> Optional[float]:
        """The scores' multiplier where the kernels must be told: a pair's
        128-wide row scores at its own heads' ``head_dim ** -0.5``."""
        return self.head_dim ** -0.5 if self.diff_attn else None

    def mixer(self, i: int) -> Optional[str]:
        """The kind of layer ``i`` by name (``MIXERS``) in a model laid out
        in ``kinds.runs``; None in every other model."""
        if self.kinds is None or not self.kinds.runs:
            return None
        return self.kinds.mixers[i]

    def rows_layer(self, i: int) -> int:
        """Where in the full rows' leaves layer ``i`` reads: its own index
        among the full layers, or for a ``"cross"`` layer (which keeps
        nothing) that of the last full layer before it."""
        return self.kind_layers("full", i) - (self.mixer(i) == "cross")

    def layer_kind(self, i: int) -> tuple:
        """``(window or None, rotates q/k, linear)`` of layer ``i``."""
        if self.kinds is None:
            return self.sliding_window, True, False
        at = i % len(self.kinds.windows)
        return (self.kinds.windows[at], self.kinds.rope[at],
                self.kinds.linear[at])

    def cache_kind(self, i: int) -> Optional[str]:
        """Which leaves of the cache layer ``i`` keeps its state in:
        ``"linear"`` (a matrix a head, no position axis), ``"ssm"`` (a
        state-space layer's, likewise), ``"ring"`` (one window's
        positions) or ``"full"`` (a row a position); None: the layer
        keeps nothing (a gated memory unit, a cross layer)."""
        if self.mixer(i) is not None:
            return MIXERS[self.mixer(i)]
        window, _rope, linear = self.layer_kind(i)
        return ("linear" if linear else
                "ring" if window is not None and self.kinds is not None
                else "full")

    def kind_layers(self, kind: str, upto: Optional[int] = None) -> int:
        """Layers of that cache kind among the first ``upto`` (default:
        all): a layer's index among those of its kind, which is where it
        lies in that kind's stacked leaves (cache.init_cache)."""
        upto = self.n_layers if upto is None else upto
        return sum(self.cache_kind(i) == kind for i in range(upto))

    def kind_runs(self) -> list:
        """``[(first layer, layers)]`` of the runs of layers of one kind,
        in order: the model's attention segments."""
        runs = []
        for i in range(self.n_layers):
            if runs and self.layer_kind(i) == self.layer_kind(runs[-1][0]):
                runs[-1][1] += 1
            else:
                runs.append([i, 1])
        return [tuple(r) for r in runs]

    def segment_plan(self) -> list:
        """``[(first layer, layers, routed FFN?)]``: the homogeneous
        segments ``params["layers"]`` is stacked in: cut where the
        attention kind changes and behind the leading dense layers."""
        if self.kinds is not None and self.kinds.runs:
            # A run of whole periods is one segment, whatever its kinds.
            return [(first, n, False)
                    for first, n, _period in self.kinds.segments()]
        dense = (self.routed.first_dense if self.routed else self.n_layers)
        plan = []
        for first, n in self.kind_runs():
            for lo, hi in ((first, min(first + n, dense)),
                           (max(first, dense), first + n)):
                if hi > lo:
                    plan.append((lo, hi - lo, lo >= dense))
        return plan

    PRESETS = {
        # BASELINE config 5 workload shape (Llama-3 8B).
        "llama3-8b": dict(vocab_size=128256, d_model=4096, n_layers=32,
                          n_heads=32, n_kv_heads=8, d_ff=14336,
                          rope_theta=500000.0),
        "llama2-7b": dict(vocab_size=32000, d_model=4096, n_layers=32,
                          n_heads=32, n_kv_heads=32, d_ff=11008,
                          rope_theta=10000.0),
        "debug": dict(vocab_size=512, d_model=128, n_layers=2, n_heads=8,
                      n_kv_heads=4, d_ff=256, dtype="float32"),
    }

    @classmethod
    def preset(cls, name: str, **overrides) -> "LlamaConfig":
        kw = dict(cls.PRESETS[name])
        kw.update(overrides)
        return cls(**kw)


# ------------------------------------------------------------------ params


def segment_layers(seg) -> int:
    """Layers one segment of the stacked tree holds: a dict's leading
    axis, or for a run of whole periods (a TUPLE of stacked trees, one a
    layer of the period: ``LayerKinds.runs``) that times the period."""
    if isinstance(seg, (tuple, list)):
        return len(seg) * segment_layers(seg[0])
    return jax.tree_util.tree_leaves(seg)[0].shape[0]


def layer_segments(layers) -> list:
    """``params["layers"]`` as ``[(stacked tree, index of its first
    layer)]``: one dict is a model of one segment, a tuple of dicts one
    segment each (a leading dense layer, then the expert layers; the runs
    of full and of window layers of a ``cfg.kinds`` model).  A model laid
    out in ``LayerKinds.runs`` has a tuple of TUPLES: a run's segment is
    one stacked tree a layer of its period, each ``[repeats, ...]``, and
    one scan body walks a whole period."""
    out, at = [], 0
    for seg in (layers if isinstance(layers, (tuple, list)) else (layers,)):
        out.append((seg, at))
        at += segment_layers(seg)
    return out


def segment_kind(cfg: "LlamaConfig", seg, first: int) -> tuple:
    """``(window or None, rotates q/k, linear)`` of the segment whose first
    layer is ``first``: every layer of a segment is of one kind, so that
    one scan body serves it (``LlamaConfig.segment_plan`` cuts them so)."""
    n = jax.tree_util.tree_leaves(seg)[0].shape[0]
    kinds = {cfg.layer_kind(i) for i in range(first, first + n)}
    if len(kinds) != 1:
        raise ValueError(
            f"layers {first}..{first + n - 1} are one segment of the tree "
            f"and of {len(kinds)} attention kinds: cut params['layers'] "
            f"where cfg.kinds changes (init_params does)")
    return kinds.pop()


# Leaves a kernel indexes by layer itself: a scan leaves them whole.
_WHOLE_LEAVES = ("w_gate", "w_up", "w_down")


def scan_segment(body, carry, seg, *xs):
    """``lax.scan`` of ``body(carry, lp, *x) -> (carry, y)`` over the
    layers of one stacked segment (and ``xs`` beside them).  A routed
    segment's expert weights are NOT scanned over: as ``xs`` every layer's
    ``[G, K, N]`` would be sliced out and copied for the grouped matmul's
    custom call, every step (a third of a Kimi-K2 decode step's device
    time: PERF.md, PR 26).  They stay whole in the body's closure and the
    layer's ``routed`` tree gets them with ``layer``, its index, which
    reaches the kernel as a prefetched scalar."""
    if "routed" not in seg:
        return lax.scan(lambda c, x: body(c, *x), carry, (seg, *xs))
    whole = {n: seg["routed"][n] for n in _WHOLE_LEAVES}
    rest = {**seg, "routed": {k: v for k, v in seg["routed"].items()
                              if k not in whole}}
    n = jax.tree_util.tree_leaves(rest)[0].shape[0]

    def step(carry, x):
        lp, i, *more = x
        lp = {**lp, "routed": {**lp["routed"], **whole, "layer": i}}
        return body(carry, lp, *more)

    return lax.scan(step, carry, (rest, jnp.arange(n, dtype=jnp.int32), *xs))


def _unit_gain(cfg: LlamaConfig, shape):
    """A fresh norm's weight: the one that multiplies by one (LayerNorm:
    ``[..., 2, D]``, that gain over a zero bias)."""
    gain = (jnp.zeros if cfg.norm_zero_centred else jnp.ones)(
        shape, cfg.compute_dtype)
    if cfg.norm == "layer":
        return jnp.stack([gain, jnp.zeros_like(gain)], axis=-2)
    return gain


def _init_block_params(key, cfg: LlamaConfig, plan=None) -> tuple:
    """The segments of a model whose blocks are not the default kinds
    (``plan``: other segments than ``cfg.segment_plan()``'s, of attention
    layers: the MTP block's one layer)."""
    dt = cfg.compute_dtype
    D, H = cfg.d_model, cfg.n_heads

    def norm(k, shape, scale):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dt)

    unit_gain = partial(_unit_gain, cfg)

    def mlp(k, L, lead, F):
        ks = jax.random.split(k, 3)
        return {"w_gate": norm(ks[0], (L, *lead, D, F), D**-0.5),
                "w_up": norm(ks[1], (L, *lead, D, F), D**-0.5),
                "w_down": norm(ks[2], (L, *lead, F, D), F**-0.5)}

    def segment(k, L, routed: bool, linear: bool):
        ks = jax.random.split(k, 8)
        seg = {"attn_norm": unit_gain((L, D)), "mlp_norm": unit_gain((L, D))}
        la = cfg.latent
        if linear:
            from .kda import init_kda_params

            seg["kda"] = init_kda_params(ks[0], L, cfg)
            seg["wo"] = norm(ks[4], (L, cfg.linear.width, D),
                             cfg.linear.width**-0.5)
        elif la is not None:
            hq = H * (la.nope_dim + la.rope_dim)
            if la.q_rank is None:
                seg.update(wq=norm(ks[0], (L, D, hq), D**-0.5))
            else:
                seg.update(
                    wq_a=norm(ks[0], (L, D, la.q_rank), D**-0.5),
                    q_norm=jnp.ones((L, la.q_rank), dt),
                    wq_b=norm(ks[1], (L, la.q_rank, hq), la.q_rank**-0.5))
            seg.update(
                wkv_a=norm(ks[2], (L, D, la.kv_rank + la.rope_dim), D**-0.5),
                kv_norm=jnp.ones((L, la.kv_rank), dt),
                wkv_b=norm(ks[3], (L, la.kv_rank, H * (la.nope_dim + la.v_dim)),
                           la.kv_rank**-0.5),
                wo=norm(ks[4], (L, H * la.v_dim, D), (H * la.v_dim)**-0.5))
        else:
            hd, Hkv = cfg.head_dim, cfg.n_kv_heads
            # With an output gate each head's columns are [q | gate].
            wide = 2 if cfg.attn_gate else 1
            seg.update(wq=norm(ks[0], (L, D, wide * H * hd), D**-0.5),
                       wk=norm(ks[1], (L, D, Hkv * hd), D**-0.5),
                       wv=norm(ks[2], (L, D, Hkv * hd), D**-0.5),
                       wo=norm(ks[4], (L, H * hd, D), (H * hd)**-0.5))
            if cfg.qk_norm:
                seg.update(q_head_norm=unit_gain((L, hd)),
                           k_head_norm=unit_gain((L, hd)))
        if routed:
            r = cfg.routed
            seg["routed"] = {
                "router": norm(ks[5], (L, D, r.n_experts), D**-0.5),
                **mlp(ks[7], L, (r.n_held,), r.d_expert)}
            if r.score == "sigmoid":
                # Small and not zero: the selection path is worked.  Small
                # against the scores' spacing at the top-k threshold, or
                # the experts' popularity is the seed's (PERF.md, PR 26).
                seg["routed"]["bias"] = 0.005 * jax.random.normal(
                    ks[6], (L, r.n_experts), jnp.float32)
            if r.n_shared:
                seg["routed"]["shared"] = mlp(
                    jax.random.fold_in(ks[7], 1), L, (),
                    r.n_shared * r.d_expert)
            if r.shared_gate:
                seg["routed"]["shared_gate"] = norm(
                    jax.random.fold_in(ks[7], 2), (L, D, 1), D**-0.5)
        else:
            seg.update(mlp(ks[7], L, (), cfg.d_ff))
        return seg

    def mixer_segment(k, L, kind: str, ids):
        """``L`` stacked layers of one kind of a ``LayerKinds.runs`` model,
        ``ids`` their indices in the model."""
        ks = jax.random.split(k, 16)
        seg = {"attn_norm": unit_gain((L, D)), "mlp_norm": unit_gain((L, D)),
               **mlp(ks[7], L, (), cfg.d_ff)}
        if kind == "ssm":
            from .ssm import init_ssm_params

            E = cfg.ssm.d_inner
            seg["ssm"] = init_ssm_params(ks[0], L, cfg)
            seg["wo"] = norm(ks[4], (L, E, D), E**-0.5)
        elif kind == "gmu":
            E = cfg.ssm.d_inner
            seg["gmu_in"] = norm(ks[0], (L, D, E), D**-0.5)
            seg["wo"] = norm(ks[4], (L, E, D), E**-0.5)
        else:   # "full" / "window" / "cross": (differential) attention
            hd, Hkv = cfg.head_dim, cfg.n_kv_heads
            seg.update(wq=norm(ks[0], (L, D, H * hd), D**-0.5),
                       bq=norm(ks[8], (L, H * hd), 0.02),
                       wo=norm(ks[4], (L, H * hd, D), (H * hd)**-0.5),
                       bo=norm(ks[9], (L, D), 0.02))
            if kind != "cross":
                seg.update(wk=norm(ks[1], (L, D, Hkv * hd), D**-0.5),
                           wv=norm(ks[2], (L, D, Hkv * hd), D**-0.5),
                           bk=norm(ks[10], (L, Hkv * hd), 0.02),
                           bv=norm(ks[11], (L, Hkv * hd), 0.02))
            if cfg.diff_attn:
                seg.update(
                    {n: norm(ks[12 + i], (L, hd), 0.1) for i, n in enumerate(
                        ("lam_q1", "lam_k1", "lam_q2", "lam_k2"))},
                    sub_norm=jnp.ones((L, 2 * hd), dt),
                    lam0=diff_lambda_init(ids))
        return seg

    if cfg.kinds is not None and cfg.kinds.runs:
        return tuple(
            tuple(mixer_segment(
                jax.random.fold_in(key, 131 * (first + j)), n // len(period),
                kind, first + j + len(period) * jnp.arange(n // len(period)))
                for j, kind in enumerate(period))
            for first, n, period in cfg.kinds.segments())

    def key_of(first, routed):
        k = jax.random.fold_in(key, 31 + routed)   # as before cfg.kinds
        return k if cfg.kinds is None else jax.random.fold_in(k, first)

    if plan is not None:
        return tuple(segment(jax.random.fold_in(key, 97 + first), n, routed,
                             False) for first, n, routed in plan)
    return tuple(segment(key_of(first, routed), n, routed,
                         cfg.layer_kind(first)[2])
                 for first, n, routed in cfg.segment_plan())


def init_params(key, cfg: LlamaConfig) -> dict:
    """Stacked-layer parameter pytree.  Weights init: scaled normal."""
    dt = cfg.compute_dtype
    hd = cfg.head_dim
    keys = jax.random.split(key, 9)

    def norm(k, shape, scale):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dt)

    L, D, F = cfg.n_layers, cfg.d_model, cfg.d_ff
    Hq, Hkv = cfg.n_heads, cfg.n_kv_heads
    if (cfg.latent is not None or cfg.routed is not None
            or cfg.kinds is not None or cfg.qk_norm or cfg.mtp
            or cfg.attn_gate or cfg.norm_zero_centred or cfg.tied
            or cfg.norm != "rms"):
        segs = _init_block_params(jax.random.fold_in(key, 23), cfg)
        out = {"embed": norm(keys[0], (cfg.vocab_size, D), 0.02),
               "layers": (segs[0] if len(segs) == 1
                          and not isinstance(segs[0], tuple) else segs),
               "final_norm": _unit_gain(cfg, (D,))}
        if not cfg.tied:   # tied: the table is the head (lm_head_matmul)
            out["lm_head"] = norm(keys[8], (D, cfg.vocab_size), D**-0.5)
        if cfg.mtp:
            from .mtp import init_mtp_params

            out["mtp"] = init_mtp_params(jax.random.fold_in(key, 29), cfg)
        return out
    layers = {
        "wq": norm(keys[1], (L, D, Hq * hd), D**-0.5),
        "wk": norm(keys[2], (L, D, Hkv * hd), D**-0.5),
        "wv": norm(keys[3], (L, D, Hkv * hd), D**-0.5),
        "wo": norm(keys[4], (L, Hq * hd, D), (Hq * hd) ** -0.5),
        "attn_norm": jnp.ones((L, D), dt),
        "mlp_norm": jnp.ones((L, D), dt),
    }
    if cfg.attn_bias:
        layers.update(bq=jnp.zeros((L, Hq * hd), dt),
                      bk=jnp.zeros((L, Hkv * hd), dt),
                      bv=jnp.zeros((L, Hkv * hd), dt))
    if cfg.n_experts > 0:
        from .moe import init_moe_params

        layers["moe"] = init_moe_params(jax.random.fold_in(key, 17), L,
                                        cfg.n_experts, D, F, dt,
                                        swiglu=cfg.moe_swiglu)
    else:
        layers.update(
            w_gate=norm(keys[5], (L, D, F), D**-0.5),
            w_up=norm(keys[6], (L, D, F), D**-0.5),
            w_down=norm(keys[7], (L, F, D), F**-0.5),
        )
    return {
        "embed": norm(keys[0], (cfg.vocab_size, D), 0.02),
        "layers": layers,
        "final_norm": jnp.ones((D,), dt),
        "lm_head": norm(keys[8], (D, cfg.vocab_size), D**-0.5),
    }


def param_specs(cfg: LlamaConfig) -> dict:
    """GSPMD PartitionSpec tree: tensor-parallel over axis "tp".

    Projection out-dims (heads / ff) shard over tp; their consumers contract
    over the tp-sharded dim, so XLA inserts the reduce-scatter/all-reduce
    pattern over ICI automatically.  Embedding/lm_head shard the vocab dim.
    """
    if (cfg.latent is not None or cfg.routed is not None
            or cfg.kinds is not None or cfg.qk_norm or cfg.mtp
            or cfg.attn_gate or cfg.norm_zero_centred or cfg.tied
            or cfg.norm != "rms"):
        raise NotImplementedError(
            "latent attention, the routed FFN, attention kinds by layer "
            "(linear layers among them), head norms, the attention gate, "
            "zero-centred norms and the MTP block have "
            "no sharding rules yet: they "
            "serve on one chip (the routed FFN as one holder of an "
            "expert-parallel deployment; ROADMAP M1, M2, M4)")
    layers = {
        "wq": P(None, None, "tp"),
        "wk": P(None, None, "tp"),
        "wv": P(None, None, "tp"),
        "wo": P(None, "tp", None),
        "attn_norm": P(None, None),
        "mlp_norm": P(None, None),
    }
    if cfg.attn_bias:
        # Biases live on the projection OUT dim: shard with their weight.
        layers.update(bq=P(None, "tp"), bk=P(None, "tp"), bv=P(None, "tp"))
    if cfg.n_experts > 0:
        from .moe import moe_specs

        layers["moe"] = moe_specs(swiglu=cfg.moe_swiglu)
    else:
        layers.update(
            w_gate=P(None, None, "tp"),
            w_up=P(None, None, "tp"),
            w_down=P(None, "tp", None),
        )
    return {
        "embed": P("tp", None),
        "layers": layers,
        "final_norm": P(None),
        "lm_head": P(None, "tp"),
    }


def quantized_param_specs(cfg: LlamaConfig) -> dict:
    """GSPMD PartitionSpec tree for a W8A16 tree
    (ops/quantize.py:quantize_params): each matmul leaf's raw spec
    applies to its ``q``, and its ``s`` (which drops the contracted
    axis, -2) keeps only the leading/output dims of that spec — so tp
    still shards the output channels and the scales follow them."""
    specs = param_specs(cfg)

    def split(spec):
        return {"q": spec, "s": P(*spec[:-2], spec[-1])}

    from ..ops.quantize import _MATMUL_LEAVES

    layers = dict(specs["layers"])
    for name in _MATMUL_LEAVES:
        if name in layers:
            layers[name] = split(layers[name])
    out = dict(specs)
    out["layers"] = layers
    out["lm_head"] = split(specs["lm_head"])
    return out


# ----------------------------------------------------------------- kernels


def matmul_w(x, w):
    """``x @ w`` where ``w`` is a raw array or a weight-quantized
    ``{"q": int8, "s": f32}`` pair (ops/quantize.py:quantize_params —
    the W8A16 serving tree), which goes through
    ``ops.quantized_matmul``.  Every matmul consumer of the parameter tree
    (decoder_layer, head_logits, the cached decode layer scan) routes
    through here, so ONE quantized tree serves
    forward/prefill/decode/serving/speculative alike."""
    if not (isinstance(w, dict) and "q" in w):
        return x @ w
    out = quantized_matmul(x.reshape(-1, x.shape[-1]), w["q"], w["s"])
    return out.reshape(*x.shape[:-1], w["q"].shape[-1])


def rmsnorm(x, w, eps: float, zero_centred: bool = False):
    """``x / rms(x) * w``; ``zero_centred``: ``* (1 + w)``, the gain taken
    in float32 (``LlamaConfig.norm_zero_centred``)."""
    xf = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    if zero_centred:
        return (xf * scale * (1.0 + w.astype(jnp.float32))).astype(x.dtype)
    return (xf * scale).astype(x.dtype) * w


def layernorm(x, w, eps: float):
    """LayerNorm in float32: ``(x - mean) / std * w[0] + w[1]``, ``w [2,
    D]`` the gain over the bias (``LlamaConfig.norm``)."""
    xf = x.astype(jnp.float32)
    xc = xf - jnp.mean(xf, axis=-1, keepdims=True)
    scale = jax.lax.rsqrt(jnp.mean(xc * xc, axis=-1, keepdims=True) + eps)
    wf = w.astype(jnp.float32)
    return (xc * scale * wf[..., 0, :] + wf[..., 1, :]).astype(x.dtype)


def cfg_rmsnorm(x, w, cfg: "LlamaConfig"):
    """The norm of every site, keyed off a config: THE way model code
    norms (:func:`rmsnorm` with its eps and whether its gains are
    zero-centred, or ``cfg.norm == "layer"``: :func:`layernorm`)."""
    if cfg.norm == "layer":
        return layernorm(x, w, cfg.norm_eps)
    return rmsnorm(x, w, cfg.norm_eps, cfg.norm_zero_centred)


def rope_tables(seq_len: int, head_dim: int, theta: float, scaling=None):
    """[S, Dh/2] cos/sin tables in f32.

    ``scaling``: LlamaConfig.rope_scaling tuple — ``("linear", factor)``
    divides every frequency by ``factor`` (position interpolation);
    ``("llama3", factor, low_freq_factor, high_freq_factor,
    original_max_position_embeddings)`` is Llama-3.1's banded scheme
    (public formula, as shipped in the checkpoints' reference code): long
    wavelengths (beyond ``orig/low``) scale by ``1/factor``, short ones
    (inside ``orig/high``) stay, and the band between interpolates
    smoothly in ``orig/wavelength``.  ``("yarn", factor,
    original_max_position_embeddings, beta_fast, beta_slow,
    attention_factor, truncate)`` is YaRN (NTK-by-parts, the public
    paper 2309.00071 formula as HF ships it; Qwen2.5-long /
    DeepSeek-family checkpoints): per-dimension blend of interpolated
    (``1/factor``) and unscaled frequencies along a linear ramp between
    the beta_fast/beta_slow correction dims, with ``attention_factor``
    (resolved at conversion, incl. the mscale variants) multiplying the
    cos/sin tables.
    """
    half = head_dim // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    att = 1.0
    if scaling is not None:
        kind = scaling[0]
        if kind == "linear":
            inv_freq = inv_freq / scaling[1]
        elif kind == "llama3":
            factor, low, high, orig = scaling[1:]
            wavelen = 2.0 * jnp.pi / inv_freq
            smooth = (orig / wavelen - low) / (high - low)
            mid = ((1.0 - smooth) / factor + smooth) * inv_freq
            inv_freq = jnp.where(
                wavelen > orig / low, inv_freq / factor,
                jnp.where(wavelen < orig / high, inv_freq, mid))
        elif kind == "yarn":
            import math

            factor, orig, beta_fast, beta_slow, att, truncate = scaling[1:]

            def corr_dim(rot):  # dimension rotating `rot` times over orig
                return (head_dim * math.log(orig / (rot * 2.0 * math.pi))
                        ) / (2.0 * math.log(theta))

            low, high = corr_dim(beta_fast), corr_dim(beta_slow)
            if truncate:
                low, high = math.floor(low), math.ceil(high)
            low, high = max(low, 0), min(high, head_dim - 1)
            if low == high:
                high += 0.001  # ramp singularity guard (HF-identical)
            ramp = jnp.clip(
                (jnp.arange(half, dtype=jnp.float32) - low) / (high - low),
                0.0, 1.0)
            extrap = 1.0 - ramp  # 1 where the dim extrapolates (short wl)
            inv_freq = (inv_freq / factor) * (1.0 - extrap) + inv_freq * extrap
        elif kind == "longrope":
            # LongRoPE (Phi-3.5/128k line; HF's longrope type): per-dim
            # rescale factors, the SHORT set within the original training
            # horizon and the LONG set beyond it — chosen by THIS table's
            # seq_len, matching HF's per-call `seq_len > orig` switch.
            # Multi-program runs (generate/serving build prefill AND
            # decode tables at different lengths) must NOT use this form
            # directly — mixed regimes within one run would silently
            # break the cached keys' rotation geometry; they resolve the
            # regime ONCE per run via resolve_longrope() below.
            orig, att, short, long = scaling[1:]
            ext = jnp.asarray(long if seq_len > orig else short,
                              jnp.float32)
            inv_freq = inv_freq / ext
        elif kind == "longrope_fixed":
            # Run-resolved longrope: one regime whatever this table's
            # length (produced by resolve_longrope).
            att, ext = scaling[1], jnp.asarray(scaling[2], jnp.float32)
            inv_freq = inv_freq / ext
        else:  # LlamaConfig.__post_init__ already validated
            raise ValueError(f"unknown rope scaling kind {kind!r}")
    pos = jnp.arange(seq_len, dtype=jnp.float32)
    ang = pos[:, None] * inv_freq[None, :]
    return jnp.cos(ang) * att, jnp.sin(ang) * att


def cfg_rope_tables(cfg: "LlamaConfig", seq_len: int):
    """:func:`rope_tables` keyed entirely off a config — THE way model
    code builds tables (forgetting ``cfg.rope_scaling`` at one of the
    many call sites would silently mis-rotate positions)."""
    return rope_tables(seq_len, cfg.rope_dim, cfg.rope_theta,
                       cfg.rope_scaling)


def resolve_longrope(cfg: "LlamaConfig", horizon: int) -> "LlamaConfig":
    """Pin a longrope config's factor regime to ``horizon`` (the run's
    max total length) for the WHOLE run.

    generate/serving/beam/speculative build prefill and decode tables at
    DIFFERENT seq_lens; the raw ("longrope", ...) form keys the
    short-vs-long choice off each table's own length, so a run with
    prompt <= orig < horizon would rotate cached keys and decode queries
    with different frequency sets — silently broken geometry.  This
    returns a config whose rope_scaling is ("longrope_fixed",
    attention_factor, ext_factors) chosen once by ``horizon``; every
    table in the run then agrees.  (HF switches regimes per step on
    horizon-crossing runs — a geometry-inconsistent quirk this design
    deliberately does not reproduce.)  Non-longrope configs pass
    through unchanged."""
    import dataclasses

    s = cfg.rope_scaling
    if s is None or s[0] != "longrope":
        return cfg
    orig, att, short, long = s[1:]
    ext = long if horizon > orig else short
    return dataclasses.replace(
        cfg, rope_scaling=("longrope_fixed", att, tuple(ext)))


def apply_rope(x, cos, sin):
    """x: [B, H, S, Dh]; split-half (NeoX) rotation convention: the two
    rotated components are x[..., :Dh/2] and x[..., Dh/2:].  ``cos``/``sin``
    are [S, Dh/2] tables, or already-broadcastable 4-D (e.g. per-row
    [B, 1, 1, Dh/2] angles for ragged decode).  NOTE: Meta's released Llama
    checkpoints use the interleaved-pair convention; loading them requires
    permuting wq/wk columns accordingly.  Tables narrower than half a head
    (``LlamaConfig.rotary_dim``) rotate the head's first values, twice the
    tables' width, among themselves and leave the rest as they are."""
    rot = 2 * cos.shape[-1]
    if rot < x.shape[-1]:
        return jnp.concatenate(
            [apply_rope(x[..., :rot], cos, sin), x[..., rot:]], axis=-1)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    c = cos[None, None, :, :] if cos.ndim == 2 else cos
    s = sin[None, None, :, :] if sin.ndim == 2 else sin
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1).astype(x.dtype)


def head_logits(h, final_norm_w, lm_head_w, eps: float,
                zero_centred: bool = False):
    """Model tail: final RMSNorm + lm_head, f32 logits.  Shared by the scan
    forward and the pipeline last stage (models/pp_llama.py)."""
    return matmul_w(rmsnorm(h, final_norm_w, eps, zero_centred),
                    lm_head_w).astype(jnp.float32)


def lm_head_matmul(x, params: dict):
    """``x [..., D]`` times the output head: ``params["lm_head"] [D, V]``,
    or where the tree has none (``cfg.tied``) the embedding table ``[V,
    D]`` contracted over its ``D`` axis where it lies: ONE table in HBM,
    no transposed copy."""
    if "lm_head" in params:
        return matmul_w(x, params["lm_head"])
    return lax.dot_general(x, params["embed"],
                           (((x.ndim - 1,), (1,)), ((), ())))


def model_logits(params: dict, h, cfg: "LlamaConfig"):
    """Model tail off a parameter tree: the final norm of ``cfg``'s kind
    and the head (tied or not), f32 logits."""
    return lm_head_matmul(cfg_rmsnorm(h, params["final_norm"], cfg),
                          params).astype(jnp.float32)


def token_ce(logits, targets):
    """Mean next-token cross-entropy of ``logits [..., V]`` against int ids
    ``targets [...]`` (same leading shape).

    Written as ``logsumexp - target_logit`` rather than gathering from a
    materialised ``log_softmax`` tensor: the ``[B, S, V]`` f32 logits are
    the biggest activation in a train step (1 GB at S=8192 V=32000), and
    the logp variant writes + re-reads a second one; here the reductions
    fuse into the logits' producer and only ``[B, S]`` scalars survive.
    Same math, same gradient (softmax - one_hot)."""
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    tl = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - tl)


def _remat_wrap(layer, cfg: "LlamaConfig"):
    """Full-layer remat only.  The "dots" policy is NOT applied here: a
    jax.checkpoint policy that marks the q/k/v projection dots saveable
    around a pallas custom_vjp makes jax's partial-eval replay the flash
    forward kernel in the backward anyway (observed on jax 0.9; pinned in
    tests/test_remat_policy.py), so "dots" is implemented structurally
    inside :func:`decoder_layer` — two checkpointed chunks around an
    un-checkpointed attention call — rather than as a policy over the
    whole layer body."""
    if not cfg.remat:
        return layer
    if cfg.remat_policy == "dots":
        return layer  # chunked checkpointing lives inside decoder_layer
    return jax.checkpoint(layer)


def resolve_attn_fn(cfg: LlamaConfig, attn_fn: Optional[Callable]) -> Callable:
    """The one place attn_fn defaults and the sliding-window guard live
    (shared by the scan forward and models/pp_llama.py).

    None -> ``ops.self_attention`` (causal), window-bound when the config
    has one.  A supplied attn_fn on a windowed config must declare
    ``attn_fn.handles_window = True`` — silently training/serving
    full-causal on a windowed config is a different model.
    :func:`make_sharded_attn` (plain ring layout; band-skipped steps)
    and :func:`~starway_tpu.parallel.ulysses.make_ulysses_attention`
    declare it when built with ``window=``; zigzag doesn't implement
    windows.
    """
    if cfg.kinds is not None:
        if attn_fn is not None:
            raise ValueError(
                "cfg.kinds gives each layer its own attention (a window or "
                "none): attn_fn must be None")
        # forward binds each segment's window
        if cfg.diff_attn:
            return partial(self_attention, sm_scale=cfg.attn_scale)
        return (self_attention if cfg.latent is None else
                partial(self_attention, sm_scale=cfg.latent.sm_scale))
    if attn_fn is None:
        if cfg.latent is not None:
            return partial(self_attention, sm_scale=cfg.latent.sm_scale)
        if cfg.sliding_window is not None:
            return partial(self_attention, window=cfg.sliding_window)
        return self_attention
    if cfg.sliding_window is not None:
        if not getattr(attn_fn, "handles_window", False):
            raise ValueError(
                "cfg.sliding_window is set but the supplied attn_fn does "
                "not declare window support (attn_fn.handles_window)")
        declared = getattr(attn_fn, "window", None)
        if declared is not None and declared != cfg.sliding_window:
            # A mismatched band is silently a different model — the exact
            # failure this guard exists to prevent.
            raise ValueError(
                f"attn_fn was built with window={declared} but "
                f"cfg.sliding_window={cfg.sliding_window}")
    return attn_fn


# ----------------------------------------------------------------- forward


def embed_tokens(params: dict, tokens, cfg: "LlamaConfig"):
    """Token embedding gather, with Gemma's sqrt(d_model) output scaling
    when ``cfg.scaled_embed`` — the ONE embed site every entry point
    (forward/prefill, decode_step, chunk_decode_step, the pipeline step)
    shares, so no path can forget the normalizer."""
    h = params["embed"][tokens]
    if cfg.scaled_embed:
        # HF Gemma multiplies by a normalizer tensor cast to model dtype.
        h = h * jnp.asarray(cfg.d_model ** 0.5, h.dtype)
    return h


def mlp_gate_act(x, cfg: "LlamaConfig"):
    """The gated-MLP nonlinearity in f32 (MXU outputs accumulate f32):
    SiLU (Llama) or tanh-approximated GeLU (Gemma's GeGLU)."""
    xf = x.astype(jnp.float32)
    if cfg.mlp_act == "gelu_tanh":
        return jax.nn.gelu(xf, approximate=True)
    return jax.nn.silu(xf)


def qkv_proj(x, lp, cfg: "LlamaConfig"):
    """q/k/v projections on ``x [B, S, D]`` -> ``[B, H, S, hd]`` heads,
    pre-RoPE, and the output gate.  Optional per-head biases (Qwen2
    family) apply when the layer tree carries ``bq``/``bk``/``bv`` — leaf
    presence is the marker, so converted trees work wherever the config
    doesn't travel; likewise ``q_head_norm`` / ``k_head_norm``
    (``cfg.qk_norm``).  Returns ``(q, k, v, gate)``: ``gate`` is None, or
    with ``cfg.attn_gate`` (``wq`` twice as wide, a head's columns ``[q |
    gate]``) the sigmoid ``[B, S, H * hd]`` that :func:`gate_heads`
    multiplies the heads' outputs by.
    The ONE projection site shared by the scan forward (decoder_layer)
    and the cached decode layer scan (generate.py)."""
    B, S = x.shape[0], x.shape[1]
    hd = cfg.head_dim
    q = matmul_w(x, lp["wq"])
    k = matmul_w(x, lp["wk"])
    v = matmul_w(x, lp["wv"])
    if "bq" in lp:
        q = q + lp["bq"]
        k = k + lp["bk"]
        v = v + lp["bv"]
    gate = None
    if cfg.attn_gate:
        q, gate = jnp.split(q.reshape(B, S, cfg.n_heads, 2 * hd), 2, axis=-1)
        gate = jax.nn.sigmoid(gate.astype(jnp.float32)).astype(x.dtype)
        gate = gate.reshape(B, S, cfg.n_heads * hd)
    q = q.reshape(B, S, cfg.n_heads, hd).transpose(0, 2, 1, 3)
    k = k.reshape(B, S, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
    if "q_head_norm" in lp:  # RMSNorm over each head, before the rotation
        q = cfg_rmsnorm(q, lp["q_head_norm"], cfg)
        k = cfg_rmsnorm(k, lp["k_head_norm"], cfg)
    return (q, k, v.reshape(B, S, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3),
            gate)


def gate_heads(o, gate):
    """The heads' outputs ``o [B, S, H * hd]`` (before ``wo``) times the
    attention gate of :func:`qkv_proj`; ``gate`` None: as they are."""
    if gate is None:
        return o
    with jax.named_scope("sw_attn_gate"):
        return o * gate


# ------------------------------------------------- differential attention
#
# Head pair ``j`` has the queries ``q_2j, q_2j+1``; its kv pair ``g = j //
# (pairs a kv pair)`` has the keys ``k_2g, k_2g+1`` and ONE value ``V_g =
# [v_2g | v_2g+1]``, twice a head wide.  ``o_j = (softmax(q_2j k_2g^T s) -
# lam softmax(q_2j+1 k_2g+1^T s)) V_g``, an RMSNorm over its ``2 hd`` values,
# times ``1 - lam0``.  A lane tile holds 128 values and a head 64, so the
# cache keeps a kv PAIR as one head ``K_g = [k_2g | k_2g+1]`` beside
# ``V_g`` (no value stored twice) and the pair's queries go to the
# attention kernels as two rows of the pair's width, ``[q_2j | 0]`` and ``[0
# | q_2j+1]``: the zeros add exactly nothing to a score, so each row's
# softmax is its own head's, over the whole ``V_g``.


def diff_lambda_init(layer_ids):
    """``lam0`` of the layers ``layer_ids`` (0-based): ``0.8 - 0.6 exp(-0.3
    l)``, a leaf of the layer's tree so that it rides the layer scan."""
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(layer_ids, jnp.float32))


def diff_q(x, lp, cfg: "LlamaConfig"):
    """x [B, S, D] -> the pairs' query rows ``[B, H, S, 2 hd]``: row ``2j``
    is ``[q_2j | 0]``, row ``2j + 1`` is ``[0 | q_2j+1]``."""
    B, S = x.shape[:2]
    H, hd = cfg.n_heads, cfg.head_dim
    q = matmul_w(x, lp["wq"]) + lp["bq"]
    side = jnp.eye(2, dtype=q.dtype)[:, :, None]             # [2, 2, 1]
    q = q.reshape(B, S, H // 2, 2, 1, hd) * side
    return q.reshape(B, S, H, 2 * hd).transpose(0, 2, 1, 3)


def diff_kv(x, lp, cfg: "LlamaConfig"):
    """x [B, S, D] -> ``(K, V) [B, Hkv / 2, S, 2 hd]``: what the cache holds
    of these positions, adjacent kv heads side by side."""
    B, S = x.shape[:2]
    pairs, wide = cfg.kv_cache_heads, cfg.kv_cache_dim
    return tuple(
        (matmul_w(x, lp[w]) + lp[b]).reshape(B, S, pairs, wide)
        .transpose(0, 2, 1, 3) for w, b in (("wk", "bk"), ("wv", "bv")))


def diff_combine(o, lp, cfg: "LlamaConfig"):
    """The two rows' attention outputs of every pair ``o [B, H, S, 2 hd]``
    -> ``[B, S, H / 2 * 2 hd]``: subtracted under the layer's ``lam``,
    normed over the pair's width, times ``1 - lam0``; float32."""
    with jax.named_scope("sw_diff_attn"):
        f32 = jnp.float32
        B, H, S, wide = o.shape
        o = o.astype(f32).reshape(B, H // 2, 2, S, wide)
        dot = lambda a, b: jnp.sum(lp[a].astype(f32) * lp[b].astype(f32))
        lam0 = lp["lam0"].astype(f32)
        lam = (jnp.exp(dot("lam_q1", "lam_k1"))
               - jnp.exp(dot("lam_q2", "lam_k2")) + lam0)
        d = o[:, :, 0] - lam * o[:, :, 1]
        d = d * jax.lax.rsqrt(jnp.mean(d * d, -1, keepdims=True) + cfg.norm_eps)
        d = d * lp["sub_norm"].astype(f32) * (1.0 - lam0)
        return d.transpose(0, 2, 1, 3).reshape(B, S, -1).astype(
            cfg.compute_dtype)


def gated_memory(x, lp, mem):
    """A gated memory unit's mixer on the normed ``x [B, S, D]``: ``SiLU(x
    W_g) * mem`` before ``wo``, ``mem [B, S, E]`` the last state-space
    layer's scan output of the SAME tokens.  It keeps nothing."""
    with jax.named_scope("sw_gmu"):
        g = jax.nn.silu(matmul_w(x, lp["gmu_in"]).astype(jnp.float32))
        return (g * mem.astype(jnp.float32)).astype(x.dtype)


def mixer_out(h, o, lp, cfg: "LlamaConfig"):
    """The rest of a dense block behind its mixer's output ``o [B, S, wo's
    rows]``: ``wo`` (and ``bo`` where the tree has one) into the residual,
    then the gated MLP.  What the cached paths of a ``LayerKinds.runs``
    model share (``generate._mixer_scan``, an admission's one-row pass)."""
    h = h + matmul_w(o, lp["wo"])
    if "bo" in lp:
        h = h + lp["bo"]
    return h + ffn_block(cfg_rmsnorm(h, lp["mlp_norm"], cfg), lp, cfg)[0]


def ffn_block(x, lp, cfg: "LlamaConfig", moe_fn: Optional[Callable] = None,
              attn_in=None):
    """The FFN of one block on the normed ``x [B, S, D]``, by the kind its
    leaves name: ``routed`` (routed dropless experts beside shared ones;
    ``attn_in``, the block's normed input as attention saw it, is what a
    ``router_in="attn_norm"`` router scores), ``moe`` (capacity-buffer
    Switch/Mixtral) or dense gated MLP.
    Returns ``(y, aux, stats)``: the MoE balance term (0 elsewhere) and,
    routed, the pairs each held expert got ``[n_held]``; capacity MoE,
    the router-health dict of a ``with_stats`` ``moe_fn``; else None.  The
    ONE FFN site of the scan forward and the cached decode layer scan."""
    aux, stats = jnp.zeros((), jnp.float32), None
    if "routed" in lp:
        from .moe import routed_ffn

        early = cfg.routed.router_in == "attn_norm"
        y, stats = routed_ffn(x, lp["routed"], cfg.routed,
                              router_x=attn_in if early else None)
    elif "moe" in lp:
        if moe_fn is not None:
            # SwiGLU expert trees carry w_gate; pass it only when
            # present so 4-arg moe_fns (Switch-style) keep working.
            kw = ({"w_gate": lp["moe"]["w_gate"]}
                  if "w_gate" in lp["moe"] else {})
            out = moe_fn(
                x, lp["moe"]["router"], lp["moe"]["w_in"],
                lp["moe"]["w_out"], **kw)
            y, aux = out[0], out[1]
            if len(out) > 2:  # with_stats moe_fn: router-health metrics
                stats = out[2]
        else:
            from .moe import switch_moe

            y, aux = switch_moe(
                x, lp["moe"]["router"], lp["moe"]["w_in"],
                lp["moe"]["w_out"],
                capacity_factor=cfg.moe_capacity_factor,
                k=cfg.moe_top_k, w_gate=lp["moe"].get("w_gate"),
            )
    else:
        g = checkpoint_name(matmul_w(x, lp["w_gate"]), "mlp_gate")
        u = checkpoint_name(matmul_w(x, lp["w_up"]), "mlp_up")
        gate = mlp_gate_act(g, cfg).astype(x.dtype)
        y = matmul_w(gate * u, lp["w_down"])
    return y, aux, stats


def decoder_layer(lp, h, cfg: LlamaConfig, cos, sin,
                  attn_fn: Callable, moe_fn: Optional[Callable] = None,
                  lengths=None, mem=None, rows=None):
    """One pre-norm decoder block on ``h [B, S, D]`` with layer params
    ``lp`` (one slice of a stacked segment); ``cos``/``sin`` None: a layer
    that does not rotate q and k (NoPE).  Returns
    ``(h, aux, kv, stats)`` — aux is the MoE balance term (0 for dense),
    kv what the cache holds of these positions, under the cache's own
    keys (``k`` / ``v``: the post-RoPE grouped heads; latent attention:
    ``ckv``, models/mla.py; a linear layer: ``kda_state`` / ``kda_conv``,
    its state after each row's first ``lengths[b]`` positions, default
    all S: models/kda.py), stats the capacity MoE's router-health dict
    when ``moe_fn`` returns one (``with_stats=True`` builders), else None.
    A state-space layer (``ssm`` in its tree: models/ssm.py) hands back
    ``ssm_state`` / ``ssm_conv`` and, under ``mem``, its scan's output at
    every position: what the gated memory units behind it multiply
    (``mem``, in); a layer with no ``wk`` (``"cross"``) attends ``rows``,
    the ``(k, v)`` of the full layer before it, and hands back nothing.
    Shared by the scan forward, and the pipeline-parallel stage body
    (models/pp_llama.py)."""
    B, S, _ = h.shape
    # "dots" remat is CHUNKED: two checkpointed regions around an
    # un-checkpointed attention call.  A whole-layer jax.checkpoint with a
    # dots-saveable policy silently replays the flash forward kernel in the
    # backward (jax 0.9 partial-eval; pinned in tests/test_remat_policy.py),
    # while this structure provably does not: the pre chunk's saved
    # boundary IS (q, k, v), the attention custom_vjp's residuals
    # (q, k, v, o, lse) ride the scan as usual, and the post chunk
    # name-saves only the gate/up dots — the backward replays nothing but
    # norms, rope, and silu.
    chunked = cfg.remat and cfg.remat_policy == "dots"
    # The router of such a model scores what attention reads.
    early = cfg.routed is not None and cfg.routed.router_in == "attn_norm"

    def pre(h, lp):
        x = cfg_rmsnorm(h, lp["attn_norm"], cfg)
        if "wkv_a" in lp:
            from .mla import project_expanded

            q, k, v, rows = project_expanded(x, lp, cfg, cos, sin)
            return q, k, v, {"ckv": rows}, None, None
        q, k, v, gate = qkv_proj(x, lp, cfg)
        if cos is not None:
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
        # kv stays in grouped (narrow) form; attention impls expand it, so
        # the ring rotates 1/n_rep of the bytes over ICI.
        return q, k, v, {"k": k, "v": v}, (x if early else None), gate

    def post(h, o, lp, attn_in, gate=None):
        o = gate_heads(o.transpose(0, 2, 1, 3).reshape(B, S, -1), gate)
        return mixed(h, o, lp, attn_in)

    def mixed(h, o, lp, attn_in=None):
        """``o [B, S, wo's rows]``: the mixer's output before ``wo``."""
        h = h + matmul_w(o, lp["wo"])
        if "bo" in lp:
            h = h + lp["bo"]
        y, aux, stats = ffn_block(cfg_rmsnorm(h, lp["mlp_norm"], cfg),
                                  lp, cfg, moe_fn, attn_in)
        if "routed" in lp:
            stats = None  # the held experts' pair counts: the decode path's
        return h + y, aux, stats

    if chunked:
        # pre: boundary outputs (q, k, v) are saved by construction; the
        # backward replays only rmsnorm + rope (the projection dot outputs
        # are not themselves backward inputs).  post: gate/up dots saved
        # by name (silu's vjp and dW_down need them); every other matmul
        # output in the chunk is not a backward input, so the replay is
        # elementwise.  No pallas call sits inside either region, so the
        # policy pathology above cannot trigger.  MoE layers keep their
        # dispatch collectives inside post — replayed in the backward,
        # matching the pre-chunking "dots" behavior — while expert dot
        # outputs are saved via dots_with_no_batch_dims.
        pre = jax.checkpoint(pre)
        post = jax.checkpoint(
            post,
            policy=jax.checkpoint_policies.save_from_both_policies(
                jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
                jax.checkpoint_policies.save_only_these_names(
                    "mlp_gate", "mlp_up")))

    if "kda" in lp:
        from .kda import kda_prefill

        # No keys, no values, no attention call: the layer's own chunked
        # recurrence (ops.kda_chunk) and the state it leaves behind.
        x = cfg_rmsnorm(h, lp["attn_norm"], cfg)
        o, kv = kda_prefill(x, lp["kda"], cfg, lengths)
        h, aux, stats = post(h, o, lp, None)
        return h, aux, kv, stats
    if "ssm" in lp or "gmu_in" in lp or cfg.diff_attn:
        x = cfg_rmsnorm(h, lp["attn_norm"], cfg)
        if "ssm" in lp:
            from .ssm import ssm_prefill

            o, kv = ssm_prefill(x, lp["ssm"], cfg, lengths)
        elif "gmu_in" in lp:
            o, kv = gated_memory(x, lp, mem), {}
        else:
            k, v = diff_kv(x, lp, cfg) if "wk" in lp else rows
            o = checkpoint_name(attn_fn(diff_q(x, lp, cfg), k, v), "attn_out")
            o, kv = diff_combine(o, lp, cfg), (
                {"k": k, "v": v} if "wk" in lp else {})
        h, aux, stats = mixed(h, o, lp)
        return h, aux, kv, stats
    q, k, v, kv, attn_in, gate = pre(h, lp)
    o = attn_fn(q, k, v)  # [B, H, S, Dh]
    # Tag kept for user-supplied whole-model remat policies; the flash
    # kernel additionally tags o and lse internally (pallas_attention).
    o = checkpoint_name(o, "attn_out")
    h, aux, stats = post(h, o, lp, attn_in, gate)
    return h, aux, kv, stats


def forward(params: dict, tokens, cfg: LlamaConfig,
            attn_fn: Optional[Callable] = None, *, return_aux: bool = False,
            moe_fn: Optional[Callable] = None, return_kv: bool = False,
            last_only: bool = False, logit_positions=None,
            return_moe_stats: bool = False, lengths=None,
            return_hidden: bool = False, stop_at: Optional[int] = None):
    """Next-token logits ``[B, S, V]`` for token ids ``[B, S]``.

    ``return_kv`` additionally returns what the cache holds of every
    layer, under the cache's keys and stacked over ALL layers (``k`` /
    ``v [n_layers, B, Hkv, S, Dh]``, the post-RoPE grouped heads; latent
    attention: ``ckv [n_layers, B, 1, S, cache_width]``; a ``cfg.kinds``
    model: the full layers under ``k`` / ``v`` and the window layers,
    every position of them, under ``k_ring`` / ``v_ring``) -- the
    KV-cache prefix for :func:`~starway_tpu.models.generate.prefill` (one
    flash-attention pass over the whole prompt instead of S cached decode
    steps).
    ``last_only`` applies the final norm + lm_head to the last position only
    (``[B, 1, V]``), skipping the ``[B, S, V]`` logit tensor a prefill never
    reads; ``logit_positions`` ([B] ints) is its ragged analog — logits for
    one caller-chosen position per row.  Return value is ``logits``,
    extended to a tuple ``(logits[, aux][, moe_stats][, (k, v)][, hidden])``
    by ``return_aux`` / ``return_moe_stats`` / ``return_kv`` /
    ``return_hidden`` (the last layer's output at EVERY position, ``[B, S,
    D]``, before the final norm: what an MTP block reads, models/mtp.py).

    ``lengths`` ([B] ints; default: every row is S long) says how many
    positions of each right-padded row are real, for the layers whose state
    is not a row a position (``cfg.linear``): a pad must not move it.
    Attention layers need no telling, a pad lies behind every real query.

    ``stop_at`` (a model laid out in ``LayerKinds.runs``): stop in front of
    the run that begins at that layer and return ``(h [B, S, D], mem [B, S,
    E], kv)`` as they stand there, no logits: the layers an admission runs
    over every row of the prompt (``generate.prefill``).

    ``attn_fn(q, k, v) -> out`` takes q ``[B, Hq, S, Dh]`` and *grouped*
    kv ``[B, Hkv, S, Dh]`` (impls expand GQA heads internally); defaults to
    single-device blockwise attention.  Pass :func:`make_sharded_attn`'s
    result for sequence-parallel ring attention.

    ``moe_fn(x, router_w, w_in, w_out) -> (y, aux)`` overrides the MoE FFN
    when ``cfg.n_experts > 0``; defaults to the global-view
    :func:`~starway_tpu.models.moe.switch_moe` (GSPMD dispatch).  Pass
    :func:`~starway_tpu.models.moe.make_sharded_moe`'s result to pin the
    expert all-to-all over the "ep" mesh axis explicitly — built with
    ``with_stats=True`` plus ``return_moe_stats=True`` here, the
    layer-stacked router-health dict (drop fraction, per-expert load; each
    leaf gains a leading ``n_layers`` dim) is appended to the outputs.
    """
    attn_fn = resolve_attn_fn(cfg, attn_fn)
    if return_moe_stats and cfg.n_experts == 0:
        raise ValueError(
            "return_moe_stats=True but cfg.n_experts == 0: a dense model "
            "has no router to report on")
    if return_moe_stats and moe_fn is None:
        raise ValueError(
            "return_moe_stats needs a stats-producing moe_fn (build one "
            "with make_sharded_moe(..., with_stats=True) or wrap "
            "switch_moe(..., with_stats=True))")
    B, S = tokens.shape
    cos, sin = cfg_rope_tables(cfg, S)

    h = embed_tokens(params, tokens, cfg)  # [B, S, D]

    def layer_of(window, rope: bool):
        """The scan body of a segment of that attention kind."""
        attend = (attn_fn if cfg.kinds is None or window is None
                  else partial(attn_fn, window=window))
        tables = (cos, sin) if rope else (None, None)

        def layer(carry, lp):
            h, aux = carry
            h, layer_aux, kv, stats = decoder_layer(
                lp, h, cfg, *tables, attend, moe_fn=moe_fn, lengths=lengths)
            if return_moe_stats and stats is None:
                raise ValueError("return_moe_stats=True but moe_fn returned "
                                 "no stats (build it with with_stats=True)")
            return (h, aux + layer_aux), (kv if return_kv else None,
                                          stats if return_moe_stats else None)

        return _remat_wrap(layer, cfg)

    def period_of(kinds: tuple):
        """The scan body of a run of whole periods (``LayerKinds.runs``):
        one layer of each of ``kinds`` in turn.  Beside ``h`` the carry
        holds what crosses layers: ``mem``, the last state-space layer's
        scan output, for the gated memory units, and ``rows``, the last
        full layer's ``(k, v)``, for the cross layers."""
        def period(carry, lps):
            h, aux, mem, rows = carry
            kvs = []
            for kind, lp in zip(kinds, lps):
                attend = (partial(attn_fn, window=cfg.kinds.window)
                          if kind == "window" else attn_fn)
                h, layer_aux, kv, _stats = decoder_layer(
                    lp, h, cfg, None, None, attend, moe_fn=moe_fn,
                    lengths=lengths, mem=mem, rows=rows)
                aux = aux + layer_aux
                mem = kv.pop("mem", mem)
                if kind == "full":
                    rows = (kv["k"], kv["v"])
                elif kind == "window":
                    kv = {name + "_ring": x for name, x in kv.items()}
                kvs.append(kv if return_kv else None)
            return (h, aux, mem, rows), (tuple(kvs), None)

        return _remat_wrap(period, cfg)

    carry = (h, jnp.zeros((), jnp.float32))
    outs = []
    runs = cfg.kinds is not None and bool(cfg.kinds.runs)
    if runs:
        if return_moe_stats or not cfg.scan_layers:
            raise ValueError("a model laid out in LayerKinds.runs is dense "
                             "and scans its layers")
        dt = cfg.compute_dtype
        wide = (cfg.kv_cache_heads, S, cfg.kv_cache_dim)
        carry += (jnp.zeros((B, S, cfg.ssm.d_inner if cfg.ssm else 0), dt),
                  (jnp.zeros((B,) + wide, dt),) * 2)
    # One scan a segment: the segments differ in their trees (a leading
    # dense layer before the expert layers) or in their attention kind
    # (cfg.kinds); the body reads a layer's kind off its leaves.
    for seg, first in layer_segments(params["layers"]):
        if runs and first == stop_at:
            break
        if runs:
            kinds = cfg.kinds.mixers[first:first + len(seg)]
            carry, (kvs, _none) = lax.scan(period_of(kinds), carry, seg)
            # Layers of one cache kind in model order: a period's in turn.
            merged: dict = {}
            for kv in kvs if return_kv else ():
                for name, x in kv.items():
                    merged.setdefault(name, []).append(x)
            outs.append(({name: xs[0] if len(xs) == 1 else jnp.stack(
                xs, 1).reshape((-1,) + xs[0].shape[1:])
                for name, xs in merged.items()}, None))
            continue
        window, rope, _linear = segment_kind(cfg, seg, first)
        body = layer_of(window, rope)
        if cfg.scan_layers:
            carry, ys = scan_segment(body, carry, seg)
        else:
            # Unrolled: same body, Python loop over layer slices; per-layer
            # outputs are stacked to match the scan's [n_layers, ...] layout.
            ys = []
            for i in range(jax.tree_util.tree_leaves(seg)[0].shape[0]):
                lp = jax.tree_util.tree_map(lambda x: x[i], seg)
                carry, y = body(carry, lp)
                ys.append(y)
            ys = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *ys)
        if return_kv and cfg.kinds is not None and window is not None:
            ys = ({name + "_ring": x for name, x in ys[0].items()}, ys[1])
        outs.append(ys)
    h, aux = carry[:2]
    hidden = h
    by_name: dict = {}
    for kv, _stats in outs if return_kv else ():
        for name, x in kv.items():
            by_name.setdefault(name, []).append(x)
    kv = {name: xs[0] if len(xs) == 1 else jnp.concatenate(xs)
          for name, xs in by_name.items()}
    if stop_at is not None:
        return h, carry[2], kv
    stats = [st for _kv, st in outs if st is not None]
    moe_stats = (None if not stats else stats[0] if len(stats) == 1 else
                 jax.tree_util.tree_map(lambda *xs: jnp.concatenate(xs),
                                        *stats))
    if last_only:
        h = h[:, -1:]
    elif logit_positions is not None:
        h = jnp.take_along_axis(h, logit_positions[:, None, None], axis=1)
    logits = model_logits(params, h, cfg)
    out = (logits,)
    if return_aux:
        out += (aux,)
    if return_moe_stats:
        out += (moe_stats,)  # scan-stacked: leaves lead with n_layers
    if return_kv:
        out += (kv,)
    if return_hidden:
        out += (hidden,)
    return out if len(out) > 1 else logits


def loss_fn(params: dict, batch, cfg: LlamaConfig,
            attn_fn: Optional[Callable] = None,
            moe_fn: Optional[Callable] = None, *,
            with_moe_stats: bool = False):
    """Causal LM loss: batch ``[B, S+1]`` token ids -> mean next-token
    cross-entropy.  ``with_moe_stats``: return ``(loss, stats)`` (for
    ``jax.value_and_grad(..., has_aux=True)``) with the layer-stacked MoE
    router-health dict — requires a ``with_stats=True`` moe_fn."""
    tokens, targets = batch[:, :-1], batch[:, 1:]
    if with_moe_stats:
        logits, aux, stats = forward(params, tokens, cfg, attn_fn,
                                     return_aux=True, moe_fn=moe_fn,
                                     return_moe_stats=True)
    else:
        logits, aux = forward(params, tokens, cfg, attn_fn, return_aux=True,
                              moe_fn=moe_fn)
    loss = token_ce(logits, targets)
    if cfg.n_experts > 0:
        loss = loss + cfg.moe_aux_coef * aux / cfg.n_layers
    return (loss, stats) if with_moe_stats else loss


def apply_updates(tx, params, opt_state, grads):
    """Optimizer transform + parameter update, shared by make_train_step and
    the Trainer's standalone apply step (keeps the two jitted paths
    identical)."""
    updates, opt_state = tx.update(grads, opt_state, params)
    params = jax.tree_util.tree_map(
        lambda p, u: (p + u.astype(p.dtype)), params, updates
    )
    return params, opt_state


def make_train_step(cfg: LlamaConfig, tx, attn_fn: Optional[Callable] = None,
                    moe_fn: Optional[Callable] = None, *,
                    accum_steps: int = 1, with_moe_stats: bool = False):
    """One optimizer step, jit-ready (donate params+opt_state for in-place
    HBM updates).

    ``accum_steps > 1`` splits the batch into that many equal microbatches
    and accumulates gradients in f32 across a ``lax.scan`` before the
    single optimizer update — activation memory scales with the microbatch
    while the math matches the full-batch step for dense models
    (equal-size chunks make the mean of means the global mean; pinned by
    tests/test_model.py).  MoE models still train correctly but are not
    bit-identical to the full-batch step: expert capacity is computed per
    microbatch, so routing overflow can differ.

    ``with_moe_stats`` (needs a ``with_stats=True`` moe_fn): the step
    returns ``(params, opt_state, loss, stats)`` where stats is the
    layer-stacked router-health dict (drop fraction + per-expert load,
    leading ``n_layers`` dim; averaged over microbatches under accum) —
    the training loop sees a collapsing router instead of silent drops.
    """
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")

    def value_and_grad(params, batch):
        if with_moe_stats:
            (loss, stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, batch, cfg, attn_fn, moe_fn, with_moe_stats=True)
        else:
            loss, grads = jax.value_and_grad(loss_fn)(
                params, batch, cfg, attn_fn, moe_fn)
            stats = None
        return loss, grads, stats

    def train_step(params, opt_state, batch):
        if accum_steps == 1:
            loss, grads, stats = value_and_grad(params, batch)
        else:
            B = batch.shape[0]
            if B % accum_steps:
                raise ValueError(
                    f"batch {B} not divisible by accum_steps={accum_steps}")
            chunks = batch.reshape(accum_steps, B // accum_steps,
                                   *batch.shape[1:])

            def acc(carry, chunk):
                loss_sum, gacc = carry
                l, g, stats = value_and_grad(params, chunk)
                gacc = jax.tree_util.tree_map(
                    lambda a, b: a + b.astype(jnp.float32), gacc, g)
                return (loss_sum + l, gacc), stats

            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (loss_sum, grads), stats = lax.scan(
                acc, (jnp.float32(0), zeros), chunks)
            if with_moe_stats:  # mean over the microbatch chunks
                stats = jax.tree_util.tree_map(
                    lambda s: jnp.mean(s, axis=0), stats)
            loss = loss_sum / accum_steps
            # Back to param dtype: the optimizer must see the same grad
            # dtype as the accum_steps=1 path, else bf16 adamw moments get
            # promoted to f32 on step 1 (donation breaks + a recompile).
            grads = jax.tree_util.tree_map(
                lambda g, p: (g / accum_steps).astype(p.dtype), grads, params)
        params, opt_state = apply_updates(tx, params, opt_state, grads)
        if with_moe_stats:
            return params, opt_state, loss, stats
        return params, opt_state, loss

    return train_step


def make_sharded_attn(mesh, *, seq_axis: str = "sp", dp_axis: str = "dp",
                      tp_axis: str = "tp", layout: str = "ring",
                      window: Optional[int] = None):
    """Sequence-parallel ring attention for use as ``attn_fn`` inside the
    GSPMD-jitted forward: q/k/v arrive [B, H, S, Dh] with batch sharded over
    dp, heads over tp, sequence over sp; the (grouped, narrow) kv shards
    ride the ICI ring.  Requires n_kv_heads % tp == 0.

    ``layout="zigzag"`` uses the load-balanced causal layout
    (parallel/ring_attention.py:zigzag_indices): ~2x causal wall-clock at
    long S because no device spends ring steps on fully-masked blocks, at
    the cost of a sequence permutation (an sp-axis shuffle) per call --
    worth it when S is large enough that attention compute dominates.

    ``window``: sliding-window band (match ``cfg.sliding_window``; the
    returned fn declares ``handles_window`` so resolve_attn_fn admits it
    on windowed configs).  Ring layout only — out-of-band ring steps
    cond-skip their compute, so wall-clock scales with the band.
    """
    from ..parallel.ring_attention import (
        ring_attention,
        zigzag_ring_attention,
        zigzag_wrap,
    )
    from ..parallel.sharding import shard_map_fn

    if layout not in ("ring", "zigzag"):
        raise ValueError(f"unknown attention layout {layout!r}; expected 'ring' or 'zigzag'")
    if window is not None and layout != "ring":
        raise ValueError(
            "window is supported on the plain ring layout only (zigzag's "
            "interleaved shards break the contiguous band-skip argument)")

    spec = P(dp_axis, tp_axis, seq_axis, None)

    if layout == "zigzag":
        def local_z(q, k, v):
            return zigzag_ring_attention(q, k, v, seq_axis)

        inner = shard_map_fn(mesh, local_z, in_specs=(spec, spec, spec), out_specs=spec)
        return zigzag_wrap(inner, mesh.shape[seq_axis])

    def local(q, k, v):
        return ring_attention(q, k, v, seq_axis, causal=True, window=window)

    fn = shard_map_fn(mesh, local, in_specs=(spec, spec, spec),
                      out_specs=spec)
    if window is not None:
        fn.handles_window = True
        fn.window = window  # resolve_attn_fn cross-checks vs the config
    return fn
