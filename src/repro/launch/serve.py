"""Batched serving driver: continuous prefill+decode over a request queue.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b --smoke \
      --requests 8 --max-new 32

Structure mirrors a production server: a request queue feeds fixed-size
batches; prefill fills a KV cache padded to the decode budget; the decode
loop runs until every sequence hits max-new tokens.  The watchdog flags
slow steps (straggler mitigation hook).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, smoke_config
from repro.distributed.fault import StepWatchdog
from repro.distributed.sharding import MeshRules
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import (build_params, make_decode_step,
                                make_prefill_step)
from repro.models.transformer import pad_caches


def serve(arch: str, *, smoke: bool = True, requests: int = 8,
          batch: int = 4, prompt_len: int = 32, max_new: int = 16,
          mesh=None, seed: int = 0):
    cfg = smoke_config(arch) if smoke else get_config(arch)
    mesh = mesh or make_host_mesh()
    rules = MeshRules.for_mesh(mesh)
    rng = np.random.default_rng(seed)

    with mesh:
        params, _ = build_params(cfg, rules, abstract=False, seed=seed)
        prefill = jax.jit(make_prefill_step(cfg, rules))
        decode = jax.jit(make_decode_step(cfg, rules))
        wd = StepWatchdog(tolerance=4.0)

        done = 0
        # Monotonic global watchdog step id: `done + i` collided across
        # batches (batch 2's step 0 reused batch 1's ids), making straggler
        # attribution ambiguous.  serve_fft.py follows the same convention
        # (the PlanStreamExecutor's internal counter is likewise global).
        step = 0
        results = []
        while done < requests:
            n = min(batch, requests - done)
            prompts = rng.integers(0, cfg.vocab, (batch, prompt_len))
            toks = jnp.asarray(prompts, jnp.int32)
            t0 = time.perf_counter()  # repro-lint: disable=REP002 driver throughput print, not a measured path
            logits, caches = prefill(params, {"tokens": toks})
            caches = pad_caches(caches, cfg, max_seq=prompt_len + max_new)
            cur = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
            outs = [cur]
            for i in range(max_new - 1):
                wd.start(step)
                step += 1
                nxt, _, caches = decode(params, caches, cur,
                                        jnp.asarray(prompt_len + i,
                                                    jnp.int32))
                cur = nxt[:, None].astype(jnp.int32)
                outs.append(cur)
                wd.stop()
            jax.block_until_ready(cur)
            dt = time.perf_counter() - t0  # repro-lint: disable=REP002 driver throughput print, not a measured path
            gen = np.asarray(jnp.concatenate(outs, axis=1))[:n]
            results.extend(gen.tolist())
            done += n
            print(f"[serve] batch of {n}: {max_new} toks in {dt*1e3:.0f}ms "
                  f"({n * max_new / dt:.0f} tok/s)", flush=True)
        if wd.flagged:
            print(f"[serve] straggler decode steps: {wd.flagged[:5]}",
                  flush=True)
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", type=str, default="qwen3-8b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    args = ap.parse_args()
    enable_compile_cache()
    out = serve(args.arch, smoke=args.smoke, requests=args.requests,
                batch=args.batch, prompt_len=args.prompt_len,
                max_new=args.max_new)
    print(f"[serve] completed {len(out)} requests")


if __name__ == "__main__":
    main()
