"""Production training driver.

On-cluster (TPU) it builds the production mesh and shards per DESIGN.md §6;
in this CPU container use --smoke for a reduced config:

  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --smoke \
      --steps 50 --quant w1a8 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp

from repro.configs import SINGLE, get_config, make_plan
from repro.core.quant import PAPER_CONFIGS
from repro.data.synthetic import lm_batch
from repro.launch.mesh import make_host_mesh, make_production_mesh, mesh_shape_dict
from repro.train.optimizer import OptConfig
from repro.train.trainer import TrainConfig, Trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--quant", default=None, choices=list(PAPER_CONFIGS))
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    if args.quant:
        cfg = dataclasses.replace(cfg, quant=PAPER_CONFIGS[args.quant])

    if len(jax.devices()) > 1:
        mesh = make_production_mesh(multi_pod=args.multi_pod)
        plan = make_plan(mesh_shape_dict(mesh))
    else:
        mesh = make_host_mesh()
        plan = SINGLE

    tr = Trainer(cfg, plan, mesh,
                 OptConfig(lr=args.lr, warmup_steps=10, total_steps=args.steps),
                 TrainConfig(steps=args.steps, log_every=10, ckpt_every=50,
                             compress_grads=args.compress_grads),
                 ckpt_dir=args.ckpt_dir)
    if args.ckpt_dir and tr.restore():
        print(f"resumed from step {tr.step}")

    vocab = cfg.vocab

    def bf(s, m):
        b = lm_batch(s, m, batch=args.batch, seq=args.seq, vocab=vocab, seed=0)
        out = {k: jnp.asarray(v) for k, v in b.items()}
        if cfg.frame_input:
            out = dict(frame_feats=jax.random.normal(
                jax.random.PRNGKey(s), (args.batch, args.seq, cfg.frame_dim)),
                labels=out["labels"])
        if cfg.n_patches:
            out["patch_embeds"] = jax.random.normal(
                jax.random.PRNGKey(s), (args.batch, cfg.n_patches, cfg.vit_dim))
        return out

    with jax.set_mesh(mesh):
        tr.run(bf)


if __name__ == "__main__":
    main()
