"""Training launcher.

Trains the named config as published on the default device; ``--reduced``
trains the small same-family variant instead (for CPU rehearsal, with
``JAX_PLATFORMS=cpu``). The dry-run path (--dry-run) lowers against the
production mesh without executing.

  PYTHONPATH=src python -m repro.launch.train --arch olmo-1b \
      --shape train_4k --steps 100 [--dry-run] [--ckpt path.npz]
"""
import argparse
import os
import sys
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--dry-run", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--reduced", action="store_true",
                    help="train the reduced config (CPU-sized)")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--lr", type=float, default=6e-4)
    args = ap.parse_args()

    if args.dry_run:
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count=512 "
            + os.environ.get("XLA_FLAGS", ""))
        from repro.launch.dryrun import run_combo
        run_combo(args.arch, args.shape, multi_pod=args.multi_pod)
        return

    import jax
    import jax.numpy as jnp
    from repro import models
    from repro.configs import get_config, reduced
    from repro.models import CallOpts
    from repro.training import (checkpoint, data as data_mod,
                                optimizer as opt_mod, steps)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    print(f"[train] {'reduced ' if args.reduced else ''}{cfg.name} "
          f"({cfg.param_count()/1e6:.1f}M params) on "
          f"{jax.devices()[0].device_kind}")
    adamw = opt_mod.AdamWConfig(lr=args.lr, warmup_steps=args.steps // 10,
                                total_steps=args.steps)
    train_step = jax.jit(steps.make_train_step(cfg, adamw,
                                               CallOpts(remat=True)))
    params = models.init_params(jax.random.PRNGKey(0), cfg)
    opt_state = opt_mod.init_opt_state(params)
    ds = data_mod.SyntheticLMData(cfg.vocab_size, seed=1)
    t0 = time.time()
    for step in range(args.steps):
        host = ds.batch(step, args.batch, args.seq)
        batch = {"tokens": jnp.asarray(host["tokens"])}
        if cfg.is_encoder_decoder:
            import numpy as np
            batch["frame_embeds"] = jnp.asarray(np.random.default_rng(step)
                .standard_normal((args.batch, cfg.encoder_seq, cfg.d_model)),
                jnp.bfloat16)
        if cfg.num_visual_tokens:
            import numpy as np
            batch["visual_embeds"] = jnp.asarray(np.random.default_rng(step)
                .standard_normal((args.batch, cfg.num_visual_tokens,
                                  cfg.d_model)), jnp.bfloat16)
        params, opt_state, m = train_step(params, opt_state, batch)
        if step % max(args.steps // 10, 1) == 0 or step == args.steps - 1:
            print(f"step {step:5d} loss={float(m['loss']):.4f} "
                  f"lr={float(m['lr']):.2e} ({time.time()-t0:.0f}s)",
                  flush=True)
    if args.ckpt:
        checkpoint.save(args.ckpt, {"params": params})
        print(f"checkpoint -> {args.ckpt}")


if __name__ == "__main__":
    main()
