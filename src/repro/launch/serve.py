"""Serving launcher: batched prefill + greedy decode for any arch.

  PYTHONPATH=src python -m repro.launch.serve --arch mixtral-8x22b --smoke \
      --batch 4 --prompt-len 16 --steps 24
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config, list_archs
from repro.launch.compile_cache import configure_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import get_model
from repro.serving import LMServer
from repro.sharding.policy import TP_POLICY


def main() -> None:
    configure_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list_archs(), default="granite-34b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--production-mesh", action="store_true")
    args = ap.parse_args()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh = make_production_mesh() if args.production_mesh else make_host_mesh()
    model = get_model(cfg)

    with jax.set_mesh(mesh):
        params = model.init(jax.random.PRNGKey(0))
        srv = LMServer(model, params, TP_POLICY)
        rng = np.random.default_rng(0)
        prompts = jnp.asarray(
            rng.integers(0, cfg.raw_vocab_size, (args.batch, args.prompt_len)),
            jnp.int32,
        )
        feats = None
        if cfg.family == "encdec":
            feats = jnp.asarray(rng.normal(
                size=(args.batch, args.prompt_len, cfg.enc_inputs)
            ).astype(np.float32))
        t0 = time.perf_counter()  # monotonic: NTP can step time.time()
        out = srv.generate(prompts, steps=args.steps, features=feats)
        dt = time.perf_counter() - t0
        print(f"arch={cfg.name} generated {out.shape[0]}x{out.shape[1]} tokens "
              f"in {dt:.1f}s ({out.size/dt:.1f} tok/s)")
        print("sample:", out[0][:16])


if __name__ == "__main__":
    main()
