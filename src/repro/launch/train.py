"""Fault-tolerant training driver.

Demonstrates (at laptop scale, with the same code paths the production mesh
uses) the pieces large-scale runnability requires:
  - sharded data-parallel train_step on whatever mesh exists,
  - step-tagged atomic checkpoints + keep-last-k (training/checkpoint.py),
  - NaN/inf loss detection with automatic restore-and-skip (node-failure /
    bad-batch recovery),
  - crash-resume: rerunning the command continues from the latest step,
  - deterministic per-step data sharding (restart-safe, straggler-safe:
    a restarted host re-derives exactly its shard from the step index).

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch internlm2-1.8b \
      --steps 50 --batch 8 --seq 256 --smoke --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache
from repro.configs import registry
from repro.dist import sharding as SH
from repro.launch import mesh as M
from repro.models import transformer as T
from repro.training import checkpoint as CK
from repro.training import optimizer as O
from repro.training.train_step import make_train_step


def synthetic_batch(cfg, batch: int, seq: int, step: int, seed: int = 0):
    """Deterministic per-step batch — restart-safe data pipeline."""
    rng = np.random.default_rng(seed * 1_000_003 + step)
    s_text = seq - cfg.vlm_patches if cfg.vlm_patches else seq
    # A learnable synthetic language: repeated arithmetic token sequences.
    base = rng.integers(0, cfg.vocab_size - 1, size=(batch, 1))
    ramp = (base + np.arange(s_text + 1)[None, :] * 7) % (cfg.vocab_size - 1)
    out = {"tokens": jnp.asarray(ramp[:, :-1], jnp.int32),
           "labels": jnp.asarray(ramp[:, 1:], jnp.int32)}
    if cfg.vlm_patches:
        out["patches"] = jnp.zeros((batch, cfg.vlm_patches, cfg.d_model),
                                   jnp.float32)
    if cfg.enc_dec:
        out["frames"] = jnp.zeros((batch, cfg.enc_frames, cfg.d_model),
                                  jnp.float32)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b",
                    choices=registry.ARCH_IDS)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--inject-nan-at", type=int, default=-1,
                    help="fault-injection test: corrupt loss at this step")
    ap.add_argument("--no-shard", action="store_true",
                    help="skip explicit in/out shardings (debug only)")
    args = ap.parse_args(argv)
    compile_cache.enable()

    cfg = (registry.get_smoke_config(args.arch) if args.smoke
           else registry.get_config(args.arch))
    opt_cfg = O.AdamWConfig(lr=args.lr, warmup_steps=10)
    if args.no_shard:
        step_fn = jax.jit(make_train_step(cfg, opt_cfg, remat=True))
    else:
        # Same spec machinery as the production dry-run, on whatever mesh
        # exists locally: params per repro.dist rules, batch over "data".
        mesh = M.make_host_mesh()
        params_s = jax.eval_shape(
            functools.partial(T.init_params, cfg), jax.random.PRNGKey(0))
        # Specs only need shapes; step 0's real batch serves as the struct.
        batch0 = synthetic_batch(cfg, args.batch, args.seq, 0)
        pspecs, ospecs, bspecs = SH.train_specs(mesh, cfg, params_s,
                                                batch0)
        step_fn = jax.jit(
            make_train_step(cfg, opt_cfg, remat=True),
            in_shardings=SH.named_tree(mesh, (pspecs, ospecs, bspecs)),
            out_shardings=(SH.named_tree(mesh, pspecs),
                           SH.named_tree(mesh, ospecs), None))

    params = T.init_params(cfg, jax.random.PRNGKey(0))
    opt_state = O.init_opt_state(params)
    start = 0
    if args.ckpt_dir and (s := CK.latest_step(args.ckpt_dir)) is not None:
        print(f"[train] resuming from checkpoint step {s}")
        state = CK.restore(args.ckpt_dir,
                           {"params": params, "opt": opt_state})
        params, opt_state = state["params"], state["opt"]
        start = s

    losses = []
    for step in range(start, args.steps):
        batch = synthetic_batch(cfg, args.batch, args.seq, step)
        t0 = time.time()
        new_params, new_opt, metrics = step_fn(params, opt_state, batch)
        loss = float(metrics["loss"])
        if args.inject_nan_at == step:
            loss = float("nan")
        if not np.isfinite(loss):
            # Node-failure / bad-batch recovery: restore & skip the batch.
            print(f"[train] step {step}: NON-FINITE loss — restoring last "
                  "checkpoint and skipping batch")
            if args.ckpt_dir and CK.latest_step(args.ckpt_dir) is not None:
                state = CK.restore(args.ckpt_dir,
                                   {"params": params, "opt": opt_state})
                params, opt_state = state["params"], state["opt"]
            continue
        params, opt_state = new_params, new_opt
        losses.append(loss)
        print(f"[train] step {step:4d} loss {loss:.4f} "
              f"gnorm {float(metrics['grad_norm']):.3f} "
              f"({time.time() - t0:.2f}s)")
        if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
            d = CK.save(args.ckpt_dir, step + 1,
                        {"params": params, "opt": opt_state})
            print(f"[train] checkpointed -> {d}")
    if len(losses) >= 10:
        print(f"[train] loss first5={np.mean(losses[:5]):.4f} "
              f"last5={np.mean(losses[-5:]):.4f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
