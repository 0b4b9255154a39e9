"""Training launcher for the assigned architectures.

Reduced CPU run:   PYTHONPATH=src python -m repro.launch.train \
                       --arch qwen3-1.7b --reduced --steps 50
Production lower:  handled by repro.launch.dryrun (no TPU here).
"""
from __future__ import annotations

import argparse
import time

import jax.numpy as jnp

from repro.common.compile_cache import enable_compile_cache
from repro.common.registry import get_arch, list_archs
from repro.data.synthetic import SyntheticLM
from repro.launch.mesh import make_local_mesh, make_production_mesh
from repro.obs import get_logger
from repro.train.checkpoint import save_checkpoint
from repro.train.optimizer import AdamWConfig
from repro.train.train_step import init_sharded, make_train_step

log = get_logger(__name__)


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b", choices=list_archs())
    ap.add_argument("--reduced", action="store_true",
                    help="2-layer smoke-scale variant (CPU)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--production-mesh", action="store_true",
                    help="use the 16x16 mesh (requires 256 devices)")
    args = ap.parse_args()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    mesh = (make_production_mesh() if args.production_mesh
            else make_local_mesh())
    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                          total_steps=args.steps)
    step_fn, _ = make_train_step(mesh, cfg, opt_cfg)
    params, opt_state = init_sharded(mesh, cfg)
    data = iter(SyntheticLM(cfg, batch=args.batch, seq_len=args.seq))

    t0 = time.time()
    for i in range(args.steps):
        b = next(data)
        batch = {"inputs": jnp.asarray(b.inputs),
                 "targets": jnp.asarray(b.targets),
                 "mask": jnp.asarray(b.mask)}
        params, opt_state, m = step_fn(params, opt_state, batch)
        if i % 10 == 0 or i == args.steps - 1:
            log.info(f"[train:{cfg.name}] step {i:4d} "
                  f"loss={float(m['loss']):.4f} lr={float(m['lr']):.2e} "
                  f"({(time.time()-t0)/(i+1):.2f}s/step)")
    if args.ckpt:
        save_checkpoint(args.ckpt, params, opt_state, step=args.steps,
                        meta={"arch": cfg.name})
        log.info(f"saved checkpoint to {args.ckpt}")


if __name__ == "__main__":
    main()
