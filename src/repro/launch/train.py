"""Sharded training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b \
        --steps 100 [--mode prism|local] [--devices 8] [--reduced]

On this host, ``--devices N`` builds an N-device debug mesh (host platform
devices); on a real fleet the same code runs under jax.distributed with the
production mesh from mesh.py.
"""
import argparse
import os

if __name__ == "__main__":                     # set before jax init
    _ap = argparse.ArgumentParser()
    _ap.add_argument("--devices", type=int, default=8)
    _args, _rest = _ap.parse_known_args()
    os.environ.setdefault(
        "XLA_FLAGS",
        f"--xla_force_host_platform_device_count={_args.devices} "
        "--xla_disable_hlo_passes=all-reduce-promotion")

import jax
import jax.numpy as jnp
import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--mode", default="prism", choices=["prism", "voltage",
                                                        "local"])
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--L", type=int, default=4)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_launch_train")
    args = ap.parse_args()

    from repro.configs import get_config
    from repro.core.exchange import ExchangeMode
    from repro.data.pipeline import SyntheticLMDataset
    from repro.models import registry
    from repro.sharding.specs import (batch_shardings, make_plan,
                                      opt_state_shardings, param_shardings)
    from repro.checkpoint.manager import CheckpointManager
    from repro.train.optimizer import adamw_init
    from repro.train.train_step import build_train_step

    n_model = 2 if args.devices >= 4 else 1
    from repro.launch.mesh import make_auto_mesh
    mesh = make_auto_mesh((args.devices // n_model, n_model),
                          ("data", "model"))
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced(vocab_size=512)
    plan = make_plan(mesh, cfg, ExchangeMode(args.mode), L=args.L, train=True)

    with jax.sharding.set_mesh(mesh):
        params = registry.init_params(cfg, seed=0)
        pshard = param_shardings(plan, cfg, params)
        params = jax.device_put(params, pshard)
        opt = jax.device_put(adamw_init(params),
                             opt_state_shardings(plan, cfg, params))
        step_fn = jax.jit(build_train_step(cfg, plan.xcfg),
                          in_shardings=(pshard, None, None),
                          donate_argnums=(0, 1))
        ds = SyntheticLMDataset(cfg.vocab_size, args.seq, args.batch)
        ckpt = CheckpointManager(args.ckpt_dir, keep=2)
        losses = []
        for step in range(args.steps):
            b = ds.sample(np.random.RandomState(1000 + step))
            batch = {k: jnp.asarray(v) for k, v in b.items()}
            params, opt, m = step_fn(params, opt, batch)
            losses.append(float(m["loss"]))
            if step % 10 == 0:
                print(f"step {step:4d} loss {losses[-1]:.4f} "
                      f"gnorm {float(m['grad_norm']):.3f}")
            if (step + 1) % 50 == 0:
                ckpt.save_async((params, opt), step + 1)
        ckpt.wait()
        print(f"done: loss {np.mean(losses[:5]):.3f} → "
              f"{np.mean(losses[-5:]):.3f} on mesh {dict(mesh.shape)} "
              f"mode={args.mode}")


if __name__ == "__main__":
    main()
