import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
# The two lines above MUST run before any other import — jax locks the
# device count on first initialization.  (No `from __future__` here for the
# same reason: nothing may precede the env-var lines.)

DOC = """Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell we build ShapeDtypeStruct inputs (no allocation), jit the
train/prefill/decode step with explicit in/out shardings on the production
mesh, .lower().compile(), and record memory_analysis / cost_analysis /
collective-roofline terms.  A failure here (sharding mismatch, OOM at
compile, unsupported collective) is a bug in the system.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch internlm2-1.8b \
      --shape train_4k [--multi-pod] [--json out.jsonl]
  PYTHONPATH=src python -m repro.launch.dryrun --all [--json out.jsonl]
"""

import argparse
import functools
import json
import sys
import time

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import compile_cache
from repro.configs import registry
from repro.configs.shapes import SHAPES, ShapeSpec, applicable
from repro.dist import sharding as SH
from repro.launch import hlo_analysis as HA
from repro.launch import mesh as M
from repro.models import decode as D
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.training import optimizer as O
from repro.training.train_step import make_train_step


def _param_structs(cfg: ModelConfig):
    """abstract params (+opt state) without allocating."""
    params = jax.eval_shape(
        functools.partial(T.init_params, cfg), jax.random.key(0))
    return params


def model_flops(cfg: ModelConfig, shape: ShapeSpec) -> float:
    """MODEL_FLOPS = 6·N·D (train) / 2·N·D (inference), N = active params."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        d = shape.seq_len * shape.global_batch
        return 6.0 * n * d
    if shape.kind == "prefill":
        d = shape.seq_len * shape.global_batch
        return 2.0 * n * d
    return 2.0 * n * shape.global_batch  # decode: one token per sequence


def build_lowered(cfg: ModelConfig, shape: ShapeSpec, mesh, *,
                  causal_skip: bool = True, donate: bool = True,
                  scheme: str = "tp", attn_flip: bool = False,
                  remat: bool = True):
    """Construct and .lower() the jitted step for one cell on `mesh`."""
    from repro.models import settings as SET
    import contextlib
    params_s = _param_structs(cfg)
    pspecs = SH.param_specs(mesh, cfg, params_s, scheme=scheme)
    batch_s = registry.input_specs(cfg, shape)

    named = functools.partial(SH.named_tree, mesh)
    with jax.set_mesh(mesh), SET.use_scheme(scheme, attn_flip):
        if shape.kind == "train":
            opt_s = jax.eval_shape(O.init_opt_state, params_s)
            pspecs, ospecs, bspecs = SH.train_specs(mesh, cfg, params_s,
                                                    batch_s, scheme=scheme,
                                                    pspecs=pspecs)
            step = make_train_step(cfg, causal_skip=causal_skip,
                                   remat=remat)
            jitted = jax.jit(
                step,
                in_shardings=named((pspecs, ospecs, bspecs)),
                out_shardings=(named(pspecs), named(ospecs), None),
                donate_argnums=(0, 1) if donate else ())
            lowered = jitted.lower(params_s, opt_s, batch_s)
        elif shape.kind == "prefill":
            bspecs = SH.batch_specs(mesh, cfg, batch_s, scheme=scheme)
            cache_s = jax.eval_shape(
                lambda: D.init_cache(cfg, shape.global_batch, shape.seq_len))
            cspecs = SH.cache_specs(mesh, cfg, cache_s)

            def pf(params, batch):
                return D.prefill(cfg, params, batch, max_len=shape.seq_len,
                                 causal_skip=causal_skip)

            jitted = jax.jit(pf, in_shardings=named((pspecs, bspecs)),
                             out_shardings=named((cspecs, P())))
            lowered = jitted.lower(params_s, batch_s)
        else:  # decode
            cache_s = batch_s["cache"]
            cspecs = SH.cache_specs(mesh, cfg, cache_s)
            tok_spec, logit_spec = SH.decode_specs(mesh, cfg,
                                                   shape.global_batch)

            def dec(params, cache, tokens):
                return D.decode_step(cfg, params, cache, tokens)

            jitted = jax.jit(
                dec, in_shardings=named((pspecs, cspecs, tok_spec)),
                out_shardings=named((logit_spec, cspecs)),
                donate_argnums=(1,) if donate else ())
            lowered = jitted.lower(params_s, cache_s, batch_s["tokens"])
    return lowered


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
               causal_skip: bool = True, donate: bool = True,
               compile_: bool = True, roofline: bool = True,
               scheme: str = "tp", attn_flip: bool = False,
               remat: bool = True) -> dict:
    cfg = registry.get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "skipped", "reason": why}
    mesh = M.make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))
    t0 = time.time()
    lowered = build_lowered(cfg, shape, mesh, causal_skip=causal_skip,
                            donate=donate, scheme=scheme,
                            attn_flip=attn_flip, remat=remat)
    t_lower = time.time() - t0
    row = {"arch": arch, "shape": shape_name,
           "mesh": "multi" if multi_pod else "single",
           "chips": chips, "status": "lowered",
           "lower_s": round(t_lower, 1)}
    if not compile_:
        return row
    compiled = lowered.compile()
    row["compile_s"] = round(time.time() - t0 - t_lower, 1)
    mem = compiled.memory_analysis()
    row["status"] = "ok"
    row["memory_analysis"] = {
        "argument_gb": getattr(mem, "argument_size_in_bytes", 0) / 1e9,
        "output_gb": getattr(mem, "output_size_in_bytes", 0) / 1e9,
        "temp_gb": getattr(mem, "temp_size_in_bytes", 0) / 1e9,
        "peak_gb": (getattr(mem, "argument_size_in_bytes", 0)
                    + getattr(mem, "temp_size_in_bytes", 0)) / 1e9,
    }
    if roofline:
        from repro.launch import roofline as RF
        try:
            rf = RF.roofline_cell(cfg, shape, mesh, chips,
                                  causal_skip=causal_skip, scheme=scheme,
                                  attn_flip=attn_flip, remat=remat)
            row.update(**rf.row())
        except Exception as e:  # noqa: BLE001
            row["roofline_error"] = repr(e)[:300]
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=registry.ARCH_IDS)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-causal-skip", action="store_true",
                    help="baseline flash schedule (full S² masked)")
    ap.add_argument("--scheme", default="tp",
                    choices=("tp", "fsdp", "moe2d"),
                    help="parallelism scheme (§Perf hillclimbs)")
    ap.add_argument("--no-remat", action="store_true",
                    help="disable activation checkpointing (train cells)")
    ap.add_argument("--flip-attn", action="store_true",
                    help="batch-over-(data×model) attention for archs whose "
                         "heads don't divide the model axis")
    ap.add_argument("--json", default=None)
    args = ap.parse_args(argv)
    compile_cache.enable()

    cells = []
    archs = registry.ARCH_IDS if args.all or not args.arch else (args.arch,)
    shapes = tuple(SHAPES) if args.all or not args.shape else (args.shape,)
    meshes = ((False, True) if args.both_meshes or args.all
              else (args.multi_pod,))
    for arch in archs:
        for sh in shapes:
            for mp in meshes:
                cells.append((arch, sh, mp))

    out = open(args.json, "a") if args.json else None
    failures = 0
    for arch, sh, mp in cells:
        try:
            # Roofline terms are a single-pod deliverable; multi-pod rows
            # prove the "pod" axis shards (compile + memory only).
            row = lower_cell(arch, sh, multi_pod=mp,
                             causal_skip=not args.no_causal_skip,
                             roofline=not mp, scheme=args.scheme,
                             attn_flip=args.flip_attn,
                             remat=not args.no_remat)
            row["scheme"] = args.scheme
            row["remat"] = not args.no_remat
            row["attn_flip"] = args.flip_attn
            row["causal_skip"] = not args.no_causal_skip
        except Exception as e:  # noqa: BLE001
            row = {"arch": arch, "shape": sh,
                   "mesh": "multi" if mp else "single",
                   "status": "FAILED", "error": repr(e)[:500]}
            failures += 1
        print(json.dumps(row), flush=True)
        if out:
            out.write(json.dumps(row) + "\n")
            out.flush()
    if out:
        out.close()
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
