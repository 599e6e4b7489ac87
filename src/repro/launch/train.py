"""Distributed training launcher.

On real hardware (TPU pod), run under your cluster runtime:

  python -m repro.launch.train --arch granite-8b --steps 1000 \
      [--multi-pod]

On this CPU container, use --host-mesh --reduced for a runnable
single-device demonstration of the same code path (identical pjit
program, 1-device mesh)."""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--host-mesh", action="store_true",
                    help="1-device mesh for CPU demonstration")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--checkpoint", default=None)
    args = ap.parse_args()

    from repro.configs import get_config
    from repro.data import lm_dataset
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_host_mesh, make_production_mesh
    from repro.models import init_params, param_count
    from repro.optim import adam_init
    from repro.sharding import batch_shardings, params_shardings
    from repro.train.loop import TrainConfig, make_train_step

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if cfg.family in ("encdec", "vlm"):
        raise SystemExit("text-LM launcher: decoder-only archs")
    mesh = (make_host_mesh() if args.host_mesh
            else make_production_mesh(multi_pod=args.multi_pod))

    with mesh:
        params = init_params(jax.random.PRNGKey(0), cfg)
        print(f"{cfg.name}: {param_count(params):,} params, mesh={dict(mesh.shape)}")
        p_shard = params_shardings(
            jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                         params), cfg, mesh, train=True)
        params = jax.device_put(params, p_shard)
        opt = adam_init(params)
        tcfg = TrainConfig(total_steps=args.steps,
                           log_every=max(args.steps // 10, 1))
        step_fn = make_train_step(cfg, tcfg)
        ds = iter(lm_dataset(args.batch, args.seq, cfg.vocab_size))
        for step in range(args.steps):
            batch = {k: jnp.asarray(v) for k, v in next(ds).items()}
            params, opt, metrics = step_fn(params, opt, batch)
            if step % tcfg.log_every == 0 or step == args.steps - 1:
                print(f"step {step:4d} loss {float(metrics['loss']):.4f}")
    if args.checkpoint:
        from repro.train import save_checkpoint
        save_checkpoint(args.checkpoint, {"params": params})


if __name__ == "__main__":
    main()
