"""Spectral serving driver: warmed, bucketed, loss-tolerant FFT service.

Usage:
  PYTHONPATH=src python -m repro.launch.serve_fft --smoke --check
  PYTHONPATH=src python -m repro.launch.serve_fft --smoke --requests 24 \
      --lose 3 --json artifacts/serve_fft_metrics.json

It serves on the devices the process has.  On the CPU, give it several
with ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` set before
python starts.

The driver plays one full service lifetime:

1. seed a wisdom cache (one measured tune for the dominant traffic grid —
   stands in for yesterday's serving day) and **warm-start** the
   :class:`~repro.serving.FFTService` from it (``ensure=`` covers the
   known-but-untuned secondary grid);
2. run rounds of deterministic mixed-shape traffic (bucket-exact, odd
   shapes that pad, a second family) through submit/drain;
3. mid-stream, with requests already queued, **lose ``--lose`` devices**
   (default: half of them, none on a one-device host):
   the service re-shapes the survivors via ``choose_fft_mesh_shape``,
   re-plans every family, and the pending round completes degraded;
4. verify every completed request against the NumPy reference (padded
   requests against the documented padded-transform-then-crop semantic),
   and verify the whole post-loss stream **bitwise** against a fresh
   service booted directly on an identical survivors-only mesh;
5. dump the metrics JSON; ``--check`` additionally gates on warm
   plan-cache hit rate (default >= 0.8) and exits non-zero on any miss.
"""
import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.plan import TuningCache
from repro.core.tuner import tune
from repro.distributed.fault import choose_fft_mesh_shape
from repro.launch.compile_cache import enable_compile_cache
from repro.serving import FFTService

# Smoke traffic: two C2C families + odd shapes that pad into the first.
PRIMARY_GRID = (16, 16)
SECONDARY_GRID = (16, 32)
ODD_GRIDS = ((14, 15), (13, 16), (15, 10))
SMOKE_EDGES = (8, 16, 32, 64)


def make_mesh(n_devices=None, dims=(16, 32)):
    devs = np.array(jax.devices())
    n = len(devs) if n_devices is None else min(n_devices, len(devs))
    shape = choose_fft_mesh_shape(n, grid=dims)
    return jax.sharding.Mesh(devs[:shape[0] * shape[1]].reshape(shape),
                             ("data", "model"))


def gen_traffic(rng, n):
    """Deterministic mixed-shape request stream (grid tuples)."""
    pool = [PRIMARY_GRID] * 5 + [SECONDARY_GRID] * 2 + list(ODD_GRIDS)
    return [pool[int(rng.integers(0, len(pool)))] for _ in range(n)]


def operand(rng, grid):
    x = rng.standard_normal(grid) + 1j * rng.standard_normal(grid)
    return x.astype(np.complex64)


def verify_result(x, res, *, atol=1e-4):
    """Relative error vs the NumPy reference for this request's semantic."""
    if res.padded:
        xp = np.zeros(res.bucket_grid, np.complex64)
        xp[tuple(slice(0, n) for n in x.shape)] = x
        ref = np.fft.fftn(xp)[tuple(slice(0, n) for n in x.shape)]
    else:
        ref = np.fft.fftn(x)
    scale = max(float(np.max(np.abs(ref))), 1e-30)
    return float(np.max(np.abs(np.asarray(res.y) - ref))) / scale


def serve_fft(*, requests=24, round_size=8, lose=None, seed=0,
              wisdom=None, json_path=None, check=False,
              hit_rate_min=0.8, verify="off", verbose=True):
    rng = np.random.default_rng(seed)
    mesh = make_mesh(dims=PRIMARY_GRID + SECONDARY_GRID)
    n_dev = mesh.devices.size
    if lose is None:
        lose = n_dev // 2
    if not 0 <= lose < n_dev:
        raise SystemExit(
            f"[serve_fft] --lose {lose} must leave at least one of the "
            f"{n_dev} devices serving: pass 0 <= --lose < {n_dev}")
    cache = TuningCache(path=wisdom)
    # Yesterday's serving day: the dominant grid is already tuned+persisted.
    tune(PRIMARY_GRID, mesh, mode="auto", cache=cache)

    svc = FFTService(mesh, tune_cache=cache, bucket_edges=SMOKE_EDGES,
                     max_batch=4, verify=verify)
    rep = svc.warm(ensure=[(SECONDARY_GRID, ("fft", "fft"))])
    if verbose:
        print(f"[serve_fft] mesh={tuple(mesh.devices.shape)} "
              f"warm: {rep.describe()}", flush=True)

    grids = gen_traffic(rng, requests)
    inputs = {}                    # id -> numpy operand
    post_loss_stream = []          # (id, operand) drained after the loss
    lost = False
    lose_at_round = max(1, (requests // round_size) // 2) if lose else -1
    errors = []
    t0 = time.perf_counter()  # repro-lint: disable=REP002 driver wall-clock for the metrics report, not a measured path
    for r, lo in enumerate(range(0, len(grids), round_size)):
        round_grids = grids[lo:lo + round_size]
        for g in round_grids:
            x = operand(rng, g)
            rid = svc.submit(jnp.asarray(x))
            inputs[rid] = x
            if lost or r == lose_at_round:
                post_loss_stream.append((rid, x))
        if not lost and r == lose_at_round:
            # Mid-stream loss: this round's requests are already queued.
            shape = svc.lose_devices(lose)
            lost = True
            if verbose:
                print(f"[serve_fft] lost {lose} devices with "
                      f"{svc.queue_depth} requests in flight -> "
                      f"degraded mesh {shape}", flush=True)
        results = svc.drain()
        for rid, res in results.items():
            err = verify_result(inputs[rid], res)
            errors.append(err)
            if err > 1e-4:
                raise SystemExit(
                    f"[serve_fft] FAIL req {rid}: rel_err={err:.3e}")
        if verbose:
            lat = svc.metrics.latency_percentiles()
            print(f"[serve_fft] round {r}: {len(results)} done "
                  f"(hit_rate={svc.metrics.plan_hit_rate:.2f}, "
                  f"p50={lat['p50_s'] * 1e3:.1f}ms, "
                  f"degraded={svc.degraded})", flush=True)
    wall = time.perf_counter() - t0  # repro-lint: disable=REP002 driver wall-clock for the metrics report, not a measured path

    # Fresh-mesh reference: a service booted directly on an identical
    # survivors-only mesh must reproduce the recovered service's post-loss
    # outputs bitwise (same knobs, same devices, same batching).
    bitwise_ok = True
    if lost and post_loss_stream:
        ref_mesh = jax.sharding.Mesh(svc.mesh.devices,
                                     tuple(svc.mesh.axis_names))
        ref = FFTService(ref_mesh, tune_cache=cache,
                         bucket_edges=SMOKE_EDGES, max_batch=4)
        for fam in svc.router.families.values():
            ref.router.resolve_family(fam.grid, fam.kinds, fam.dtype)
        id_map = {}
        for rid, x in post_loss_stream:
            id_map[ref.submit(jnp.asarray(x))] = rid
        ref_results = ref.drain()
        svc_again = {}
        # Replay the same stream through the recovered service once more so
        # both sides compare the same (batched, padded) executions.
        for rid, x in post_loss_stream:
            svc_again[svc.submit(jnp.asarray(x))] = rid
        again = svc.drain()
        ref_by_orig = {id_map[k]: v for k, v in ref_results.items()}
        for new_id, orig in svc_again.items():
            a = np.asarray(again[new_id].y)
            b = np.asarray(ref_by_orig[orig].y)
            if not np.array_equal(a, b):
                bitwise_ok = False
                print(f"[serve_fft] BITWISE MISMATCH req {orig}: "
                      f"max|d|={np.max(np.abs(a - b)):.3e}", flush=True)
        if verbose:
            print(f"[serve_fft] fresh-mesh bitwise parity over "
                  f"{len(post_loss_stream)} post-loss requests: "
                  f"{'OK' if bitwise_ok else 'FAIL'}", flush=True)

    snap = svc.metrics.to_json()
    snap["driver"] = {
        "wall_s": wall, "requests": requests, "lost_devices": lose,
        "max_rel_err": max(errors) if errors else 0.0,
        "fresh_mesh_bitwise_ok": bitwise_ok,
        "degraded_mesh": list(svc.mesh.devices.shape),
    }
    if json_path:
        os.makedirs(os.path.dirname(json_path) or ".", exist_ok=True)
        with open(json_path, "w") as f:
            json.dump(snap, f, indent=1, sort_keys=True)
            f.write("\n")
        if verbose:
            print(f"[serve_fft] metrics -> {json_path}", flush=True)

    hr = svc.metrics.plan_hit_rate
    if verbose:
        print(f"[serve_fft] done: {svc.metrics.requests_completed} requests "
              f"in {wall:.2f}s, hit_rate={hr:.2f}, "
              f"degraded_rps={svc.metrics.degraded_throughput_rps():.1f}",
              flush=True)
    if check:
        if hr < hit_rate_min:
            raise SystemExit(f"[serve_fft] CHECK FAIL: hit_rate {hr:.2f} "
                             f"< {hit_rate_min}")
        if not bitwise_ok:
            raise SystemExit("[serve_fft] CHECK FAIL: fresh-mesh parity")
        print(f"[serve_fft] CHECK OK (hit_rate={hr:.2f} >= {hit_rate_min}, "
              "bitwise parity holds)", flush=True)
    return snap


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--round-size", type=int, default=8)
    ap.add_argument("--lose", type=int, default=None,
                    help="devices to drop mid-stream (0 disables; default "
                         "half of them)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--wisdom", type=str, default=None,
                    help="wisdom-file path (default: in-memory)")
    ap.add_argument("--json", type=str, default=None,
                    help="write the metrics snapshot here")
    ap.add_argument("--check", action="store_true",
                    help="gate on hit rate + bitwise parity; exit non-zero")
    ap.add_argument("--hit-rate-min", type=float, default=0.8)
    ap.add_argument("--verify", choices=("off", "warn", "strict"),
                    default="off",
                    help="statically check every drain's planned segment "
                         "order before dispatch (strict: raise on findings)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    serve_fft(requests=args.requests, round_size=args.round_size,
              lose=args.lose, seed=args.seed, wisdom=args.wisdom,
              json_path=args.json, check=args.check,
              hit_rate_min=args.hit_rate_min, verify=args.verify)


if __name__ == "__main__":
    main()
