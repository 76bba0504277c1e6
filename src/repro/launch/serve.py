"""Latency-bounded serving driver: real decode_step + pSPICE scheduler.

Runs a smoke-config model with genuine prefill/decode compute while the
pSPICE scheduler (repro/serving/scheduler.py) makes admission/eviction
decisions from its online-learned Markov utility model.  The step cost fed
to the scheduler is the MEASURED wall-clock of the jitted decode_step, so
this is the paper's architecture end-to-end: operator (decode batch) +
overload detector + model builder + load shedder.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b \
      --requests 64 --rate 50 --policy pspice
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import compile_cache
from repro.configs import registry
from repro.dist import sharding as SH
from repro.launch import mesh as M
from repro.models import decode as D
from repro.models import transformer as T
from repro.serving.scheduler import (PSpiceScheduler, Request,
                                     SchedulerConfig, synth_workload)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b",
                    choices=registry.ARCH_IDS)
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--rate", type=float, default=50.0)
    ap.add_argument("--policy", default="pspice",
                    choices=("pspice", "random", "admission"))
    ap.add_argument("--slots", type=int, default=16)
    ap.add_argument("--slo", type=float, default=1.0)
    ap.add_argument("--max-len", type=int, default=96)
    args = ap.parse_args(argv)
    compile_cache.enable()

    cfg = registry.get_smoke_config(args.arch)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    B = args.slots
    cache = D.init_cache(cfg, B, args.max_len)
    # Decode runs under the same cache/batch spec machinery the production
    # dry-run lowers with, on the local host mesh (batch over "data").
    mesh = M.make_host_mesh()
    cspecs = SH.cache_specs(mesh, cfg, cache)
    tok_spec, logit_spec = SH.decode_specs(mesh, cfg, B)
    dec = jax.jit(lambda c, t: D.decode_step(cfg, params, c, t),
                  in_shardings=SH.named_tree(mesh, (cspecs, tok_spec)),
                  out_shardings=SH.named_tree(mesh, (logit_spec, cspecs)))
    # warm the jit + measure the real step cost
    toks = jnp.zeros((B,), jnp.int32)
    _, cache_w = dec(cache, toks)
    t0 = time.time()
    for _ in range(5):
        logits, cache_w = dec(cache_w, toks)
    logits.block_until_ready()
    step_cost = (time.time() - t0) / 5
    print(f"[serve] measured decode_step cost (B={B}): {step_cost*1e3:.2f}ms")

    scfg = SchedulerConfig(max_slots=B, slo=args.slo, policy=args.policy,
                           step_cost_base=step_cost * 0.5,
                           step_cost_per_seq=step_cost * 0.5 / max(B, 1))
    sched = PSpiceScheduler(scfg)
    reqs = synth_workload(args.requests, rate=args.rate, cfg=scfg)
    i = 0
    cache_live = cache
    n_steps = 0
    while len(sched.finished) < len(reqs):
        while i < len(reqs) and reqs[i].arrival <= sched.time:
            sched.submit(reqs[i])
            i += 1
        if i >= len(reqs) // 3 and sched.ut is None:
            sched.build_model()
            print("[serve] pSPICE utility model built")
        if not sched.active and not sched.queue and i < len(reqs):
            sched.time = max(sched.time, reqs[i].arrival)
            continue
        sched.run_step()
        if sched.active and n_steps < args.max_len - 1:
            logits, cache_live = dec(cache_live, toks)  # real compute
            n_steps += 1
    m = sched.metrics()
    print(f"[serve] policy={args.policy} completed={m['completed']} "
          f"evicted={m['evicted']} in_slo={m['in_slo']} "
          f"goodput={m['goodput']:.3f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
