"""Distributed training launcher.

On real hardware this runs under the production mesh; on this CPU container
it runs the same code path on a 1x1 mesh with a reduced config — the
mesh/sharding plumbing is identical (the dry-run proves the production mesh
lowers).

  PYTHONPATH=src python -m repro.launch.train --arch granite-34b --smoke \
      --steps 50 --batch 8 --seq 128
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config, get_smoke_config, list_archs
from repro.data import lm_batches
from repro.launch.compile_cache import configure_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import get_model
from repro.sharding.policy import TP_POLICY
from repro.sharding.utils import fit_specs
from repro.training import (
    AdamWConfig, adamw_init, make_train_step, save_checkpoint,
)


def main() -> None:
    configure_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_archs(), default="granite-34b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt", default=None, help="checkpoint path to save")
    ap.add_argument("--production-mesh", action="store_true",
                    help="use the 16x16 mesh (needs 256 devices)")
    args = ap.parse_args()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh = (
        make_production_mesh() if args.production_mesh else make_host_mesh()
    )
    policy = TP_POLICY
    model = get_model(cfg)

    with jax.set_mesh(mesh):
        params = model.init(jax.random.PRNGKey(0))
        pspec = fit_specs(params, model.param_specs(policy), mesh)
        params = jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
            params, pspec, is_leaf=lambda v: hasattr(v, "shape"),
        )
        opt = adamw_init(params)
        opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                              total_steps=args.steps)
        step_fn = jax.jit(make_train_step(model, opt_cfg, policy))
        it = lm_batches(cfg.vocab_size, args.batch, args.seq, seed=0)

        t0 = time.perf_counter()
        for step in range(args.steps):
            tokens = jnp.asarray(next(it))
            if cfg.family == "encdec":
                feats = jnp.asarray(np.random.default_rng(step).normal(
                    size=(args.batch, args.seq, cfg.enc_inputs)
                ).astype(np.float32))
                batch = {"features": feats, "tokens": tokens}
            else:
                batch = tokens
            params, opt, metrics = step_fn(params, opt, batch)
            if step % 10 == 0 or step == args.steps - 1:
                print(f"step {step:4d} loss {float(metrics['loss']):.4f} "
                      f"lr {float(metrics['lr']):.2e} "
                      f"gnorm {float(metrics['grad_norm']):.3f} "
                      f"({time.perf_counter()-t0:.0f}s)")
        if args.ckpt:
            save_checkpoint(args.ckpt, {"params": params}, step=args.steps)
            print(f"saved {args.ckpt}")


if __name__ == "__main__":
    main()
