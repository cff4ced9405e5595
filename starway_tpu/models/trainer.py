"""Minimal training harness for the model family.

Wires the pieces the framework already provides into one loop: the jitted
(optionally sharded) train step, telemetry (a perf.StageScope fed by
perf.stage_span, so each phase is also an ``sw:`` span in a profiler
trace), checkpointing
(utils.checkpoint), and -- when a DP-boundary port is supplied -- averaged
gradient exchange with a peer host over the async P2P fabric
(parallel/dp_exchange.py; the examples/dp_training_2proc.py pattern as a
library).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax

from .. import perf
from .llama import LlamaConfig, apply_updates, loss_fn, make_train_step


@dataclasses.dataclass
class TrainState:
    params: Any
    opt_state: Any
    step: int = 0


class Trainer:
    def __init__(self, cfg: LlamaConfig, tx, params,
                 attn_fn: Optional[Callable] = None,
                 donate: bool = True,
                 dp_port=None, dp_base_tag: int = 0x6000,
                 mesh=None, fsdp_axis: Optional[str] = None,
                 moe_fn: Optional[Callable] = None,
                 with_moe_stats: bool = False,
                 accum_steps: int = 1):
        """``dp_port``: a ClientPort/ServerPort to a peer rank; when set,
        gradients are averaged with the peer every step before the update.

        ``moe_fn``: MoE dispatch override for expert models (e.g.
        :func:`~starway_tpu.models.moe.make_sharded_moe`'s result).
        ``with_moe_stats`` (needs a ``with_stats=True`` moe_fn): every step
        stashes the layer-stacked router-health dict (drop fraction,
        per-expert load) on ``self.last_moe_stats`` — the training loop
        watches a collapsing router without changing ``step_sync``'s
        return type.

        ``dp_base_tag``: start of the tag range the exchange occupies.  The
        rolling window spans ``[dp_base_tag, dp_base_tag + 1024*256)`` —
        1024 in-flight steps x 256 leaves — so any *other* pytree exchange
        sharing this worker must use tags outside that 0x40000-wide range.

        ``mesh`` + ``fsdp_axis``: ZeRO mode — params and optimizer state are
        sharded 1/N over that mesh axis (parallel/fsdp.py) and ``step_sync``
        runs the fused sharded train step (batch sharded over the same
        axis).  Mutually exclusive with ``dp_port``: the P2P gradient
        exchange assumes host-visible unsharded grads.

        ``accum_steps``: gradient accumulation — the batch splits into
        that many equal microbatches whose f32-accumulated grads feed ONE
        optimizer update (make_train_step's semantics: activation memory
        scales with the microbatch, the math matches the full-batch step
        for dense models).  Local/fsdp step only; the dp_port exchange
        path averages full-batch grads and stays accum_steps=1.
        """
        self.cfg = cfg
        self.tx = tx
        self.state = TrainState(params=params, opt_state=tx.init(params))
        self.timer = perf.StageScope()
        self.dp_port = dp_port
        self.dp_base_tag = dp_base_tag
        self.with_moe_stats = with_moe_stats
        self.last_moe_stats = None
        self._fsdp_step = None
        if with_moe_stats and mesh is not None:
            raise NotImplementedError(
                "with_moe_stats is not wired through the fused fsdp step; "
                "use the plain step or make_train_step(with_moe_stats=True)")
        if with_moe_stats and (moe_fn is None or cfg.n_experts == 0):
            # Fail at construction, not at the first step inside tracing.
            raise ValueError(
                "with_moe_stats needs an expert config and a stats-producing"
                " moe_fn (make_sharded_moe(..., with_stats=True))")
        if accum_steps < 1:
            raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
        if accum_steps > 1 and dp_port is not None:
            raise ValueError(
                "accum_steps composes with the local/fsdp step only; the "
                "dp_port exchange path averages full-batch grads")
        if (mesh is None) != (fsdp_axis is None):
            raise ValueError("pass mesh and fsdp_axis together")
        if mesh is not None:
            if dp_port is not None:
                raise ValueError("fsdp mode and dp_port are mutually exclusive")
            from ..parallel.fsdp import (fsdp_specs, make_fsdp_train_step,
                                         shard_tree)

            pspecs = fsdp_specs(params, mesh, axis=fsdp_axis)
            ospecs = fsdp_specs(jax.eval_shape(tx.init, params), mesh,
                                axis=fsdp_axis)
            self.state.params = shard_tree(self.state.params, mesh, pspecs)
            self.state.opt_state = shard_tree(self.state.opt_state, mesh, ospecs)
            self._fsdp_step = make_fsdp_train_step(
                make_train_step(cfg, tx, attn_fn, moe_fn,
                                accum_steps=accum_steps), mesh, pspecs,
                ospecs, axis=fsdp_axis, donate=donate)
        if dp_port is not None:
            # step_dp gives each step a 256-tag window (base advances by 256
            # per step); more leaves than that would collide across steps.
            n_leaves = len(jax.tree_util.tree_leaves(params))
            if n_leaves > 256:
                raise ValueError(
                    f"DP gradient exchange supports <= 256 pytree leaves per "
                    f"step; got {n_leaves} (stack per-layer params, or widen "
                    f"the tag window)"
                )
        self._grad_fn = jax.jit(
            lambda p, b: jax.value_and_grad(loss_fn, has_aux=with_moe_stats)(
                p, b, cfg, attn_fn, moe_fn, with_moe_stats=with_moe_stats))
        self._apply_fn = jax.jit(
            lambda p, o, g: apply_updates(tx, p, o, g),
            donate_argnums=(0, 1) if donate else (),
        )
        self._accum_step = None
        if accum_steps > 1 and self._fsdp_step is None:
            # The fused accumulate-then-update step (make_train_step's
            # lax.scan over microbatches); step_sync dispatches to it.
            self._accum_step = jax.jit(
                make_train_step(cfg, tx, attn_fn, moe_fn,
                                accum_steps=accum_steps,
                                with_moe_stats=with_moe_stats),
                donate_argnums=(0, 1) if donate else ())

    def step_sync(self, batch) -> float:
        """One local step (no DP exchange)."""
        if self._accum_step is not None:
            with perf.stage_span(self.timer, "accum_step"):
                out = self._accum_step(self.state.params,
                                       self.state.opt_state, batch)
                if self.with_moe_stats:
                    (self.state.params, self.state.opt_state, loss,
                     self.last_moe_stats) = out
                else:
                    self.state.params, self.state.opt_state, loss = out
            self.state.step += 1
            return float(loss)
        if self._fsdp_step is not None:
            with perf.stage_span(self.timer, "fsdp_step"):
                self.state.params, self.state.opt_state, loss = self._fsdp_step(
                    self.state.params, self.state.opt_state, batch)
            self.state.step += 1
            return float(loss)
        with perf.stage_span(self.timer, "grad"):
            loss, grads = self._unpack_grad(
                self._grad_fn(self.state.params, batch))
        with perf.stage_span(self.timer, "apply"):
            self.state.params, self.state.opt_state = self._apply_fn(
                self.state.params, self.state.opt_state, grads
            )
        self.state.step += 1
        return float(loss)

    def _unpack_grad(self, out):
        """(loss[, stats]), grads -> (loss, grads); stats stashed."""
        val, grads = out
        if self.with_moe_stats:
            loss, self.last_moe_stats = val
            return loss, grads
        return val, grads

    async def step_dp(self, batch) -> float:
        """One step with averaged gradient exchange across the DP port."""
        import asyncio

        from ..parallel.dp_exchange import recv_pytree, send_pytree

        with perf.stage_span(self.timer, "grad"):
            loss, grads = self._unpack_grad(
                self._grad_fn(self.state.params, batch))
        with perf.stage_span(self.timer, "dp_exchange"):
            base = self.dp_base_tag + (self.state.step % 1024) * 256
            send_task = asyncio.ensure_future(
                send_pytree(self.dp_port, grads, base_tag=base)
            )
            peer = await recv_pytree(self.dp_port, like=grads, base_tag=base)
            await send_task
            grads = jax.tree_util.tree_map(lambda a, b: (a + b) / 2, grads, peer)
        with perf.stage_span(self.timer, "apply"):
            self.state.params, self.state.opt_state = self._apply_fn(
                self.state.params, self.state.opt_state, grads
            )
        self.state.step += 1
        return float(loss)

    # ------------------------------------------------------------ ckpt
    def save(self, path: str) -> str:
        from ..utils.checkpoint import save_pytree

        return save_pytree(path, {"params": self.state.params,
                                  "opt_state": self.state.opt_state,
                                  "step": jax.numpy.asarray(self.state.step)})

    def restore(self, path: str) -> None:
        from ..utils.checkpoint import restore_pytree

        like = {"params": self.state.params, "opt_state": self.state.opt_state,
                "step": jax.numpy.asarray(self.state.step)}
        got = restore_pytree(path, like)
        self.state = TrainState(params=got["params"], opt_state=got["opt_state"],
                                step=int(got["step"]))

    def telemetry(self) -> dict:
        """``{phase: {"count", "seconds", ...}}`` of the step phases so far
        (``grad``, ``apply``, ``fsdp_step``, ``accum_step``,
        ``dp_exchange``)."""
        return self.timer.snapshot()
