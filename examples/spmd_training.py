"""SPMD training tour: ZeRO/FSDP and pipeline-parallel Llama on one host.

Runs on the virtual CPU mesh (no TPU needed) — the same code shards over
real chips when a TPU mesh is present.  Three parts:

  1. Trainer in ZeRO mode: params + Adam state sharded 1/N over "fsdp",
     XLA inserting the all-gather/reduce-scatter schedule.
  2. The same ZeRO step assembled from the low-level pieces
     (parallel/fsdp.py) for custom training loops.
  3. End-to-end pipeline-parallel Llama (models/pp_llama.py): embed +
     collective 1F1B over "pp" + head, every parameter receiving grads.

Usage:  python examples/spmd_training.py [--devices 8] [--steps 4]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--platform", choices=("cpu", "auto"), default="cpu",
                    help="cpu (default): virtual host mesh, runs anywhere; "
                         "auto: whatever backend jax picks (real chips)")
    args = ap.parse_args()

    # Virtual device mesh when demoing on CPU (must precede the first jax
    # backend use; see tests/conftest.py for the same dance).
    flags = os.environ.get("XLA_FLAGS", "")
    if args.platform == "cpu" and "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={args.devices}".strip())
    import jax

    if args.platform == "cpu":
        jax.config.update("jax_platforms", "cpu")

    if len(jax.devices()) < args.devices:
        raise SystemExit(f"need {args.devices} devices, have {len(jax.devices())}")

    import numpy as np
    import jax.numpy as jnp
    import optax

    from starway_tpu.models import (LlamaConfig, init_params,
                                    make_pp_llama_train, make_train_step,
                                    pp_split_params, shard_pp_params)
    from starway_tpu.models.trainer import Trainer
    from starway_tpu.parallel import (fsdp_specs, make_fsdp_train_step,
                                      make_mesh, shard_tree)

    cfg = LlamaConfig.preset("debug", d_model=64, n_heads=4, n_kv_heads=4,
                             d_ff=128, vocab_size=256, n_layers=4)
    rng = np.random.default_rng(0)
    batch = lambda: jnp.asarray(
        rng.integers(0, cfg.vocab_size, (args.devices, 33), dtype=np.int32))

    # -- 1. High-level: Trainer in ZeRO mode ------------------------------
    mesh = make_mesh({"fsdp": args.devices})
    trainer = Trainer(cfg, optax.adamw(3e-3),
                      init_params(jax.random.PRNGKey(0), cfg),
                      mesh=mesh, fsdp_axis="fsdp")
    for _ in range(args.steps):
        loss = trainer.step_sync(batch())
    emb = trainer.state.params["embed"]
    print(f"[fsdp/Trainer] {args.steps} steps, loss={loss:.4f}, "
          f"embed shard {emb.addressable_shards[0].data.shape} of {emb.shape}")

    # -- 2. Low-level: the same ZeRO step from parts ----------------------
    params = init_params(jax.random.PRNGKey(1), cfg)
    tx = optax.adamw(3e-3)
    pspecs = fsdp_specs(params, mesh)
    ospecs = fsdp_specs(jax.eval_shape(tx.init, params), mesh)
    p = shard_tree(params, mesh, pspecs)
    o = shard_tree(tx.init(params), mesh, ospecs)
    step = make_fsdp_train_step(make_train_step(cfg, tx), mesh, pspecs, ospecs)
    for _ in range(args.steps):
        p, o, loss = step(p, o, batch())
    print(f"[fsdp/manual]  {args.steps} steps, loss={float(loss):.4f}")

    # -- 3. Pipeline-parallel Llama (1F1B, all grads) ---------------------
    # Stage count must divide n_layers; microbatch count must divide the
    # batch — derive both from the device budget instead of assuming 4/8.
    pp_n = max(d for d in (4, 2, 1) if d <= args.devices and cfg.n_layers % d == 0)
    n_micro, bsz = 4, 8
    mesh_pp = make_mesh({"pp": pp_n})
    pp_params = shard_pp_params(
        pp_split_params(init_params(jax.random.PRNGKey(2), cfg), pp_n), mesh_pp)
    pp_step = make_pp_llama_train(mesh_pp, cfg, n_micro=n_micro)
    tx_pp = optax.adamw(3e-3)
    opt_pp = tx_pp.init(pp_params)
    fixed = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (bsz, 33), dtype=np.int32))
    for _ in range(args.steps):
        loss, grads = pp_step(pp_params, fixed)
        updates, opt_pp = tx_pp.update(grads, opt_pp, pp_params)
        pp_params = optax.apply_updates(pp_params, updates)
    print(f"[pp-llama]     {pp_n} stages x {cfg.n_layers // pp_n} layers, "
          f"{args.steps} steps on one batch, loss={float(loss):.4f}")


if __name__ == "__main__":
    main()
