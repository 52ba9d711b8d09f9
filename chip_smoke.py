#!/usr/bin/env python3
"""Run the distributed FFT's main path on a TPU and check every result.

    python3 chip_smoke.py              # one chip, three phases:
        c2c      3-D C2C at 512^3 complex64 on a (1, 1) mesh through
                 plan_fft: the default backend (forward + inverse), an
                 auto-tuned plan, and explicit matmul and pallas plans;
        poisson  the PPB pressure solve (fft x fft x dct2) at 512^3 float32
                 through PoissonSolver, on each local-FFT backend;
        serve    an FFTService warmed on the one-chip mesh, serving batched
                 2-D C2C requests at 1024^2 and 2048^2, one of them padded.
    python3 chip_smoke.py --chips 4    # only: a 1024^3 complex64 pencil
        transform on a (2, 2) mesh, with the bulk schedule and with the
        tuner's chunk schedule, its output sharded over four devices.
    python3 chip_smoke.py --precision-probe    # only: the error of the
        matmul and pallas backends' 3-D C2C at 512^3 at each MXU precision
        (DEFAULT, HIGH, HIGHEST); the check is on the precision the code
        uses (``kernels/precision.py``), the others are reported.
    python3 chip_smoke.py --cpu-rehearsal [--chips 4 | --precision-probe]
        the same phases at tiny sizes on the CPU (fake host devices for
        --chips 4, Pallas in interpret mode); reports the CPU platform.

Every check compares with a float64 NumPy/SciPy reference computed on the
host from ``--seed``: max|y - ref| / max|ref| <= 1e-4.  Each check prints
one JSON line (host-clock walls in seconds, errors, the device's
``peak_bytes_in_use`` so far); the last line is
``{"ok": true, "device": {"platform", "kind", "count"}}``.  A failed check
or phase exits 1, printing no such line; no TPU (without
``--cpu-rehearsal``) exits 2.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

import numpy as np

TOL = 1e-4
FULL = {"c2c": 512, "poisson": 512, "serve": (1024, 2048), "mesh4": 1024,
        "precision": 512}
TINY = {"c2c": 32, "poisson": 32, "serve": (32, 64), "mesh4": 32,
        "precision": 32}
BACKENDS = ("xla", "matmul", "pallas")


def emit(**row) -> None:
    print(json.dumps(row, sort_keys=True), flush=True)


def rel_err(y, ref) -> float:
    """max|y - ref| / max|ref| in float64, one leading slab at a time."""
    y, ref = np.asarray(y), np.asarray(ref)
    num = den = 0.0
    for s in range(0, y.shape[0], 64):
        r = ref[s:s + 64].astype(np.complex128)
        num = max(num, float(np.max(np.abs(y[s:s + 64] - r))))
        den = max(den, float(np.max(np.abs(r))))
    return num / max(den, 1e-300)


def wall(fn, timer=time.perf_counter):
    """(result, seconds) of ``fn()``, blocked until the device is done."""
    import jax
    t0 = timer()
    out = jax.block_until_ready(fn())
    return out, timer() - t0


def seconds(fn) -> float:
    """Wall seconds of ``fn()``; its result is freed at once."""
    return wall(fn)[1]


def peak_bytes(devices) -> int | None:
    stats = [d.memory_stats() for d in devices]
    if any(s is None for s in stats):
        return None
    return max(s.get("peak_bytes_in_use", 0) for s in stats)


def first_line(e: BaseException) -> str:
    """``Type: first line of the message`` of an exception."""
    lines = str(e).strip().splitlines()
    return f"{type(e).__name__}: {lines[0] if lines else ''}"


def complex_field(rng, shape) -> np.ndarray:
    x = np.empty(shape, np.complex64)
    x.real = rng.standard_normal(shape, dtype=np.float32)
    x.imag = rng.standard_normal(shape, dtype=np.float32)
    return x


class Checks:
    def __init__(self, devices):
        self.devices = devices
        self.failed = []

    def record(self, name: str, errs: dict, **row) -> None:
        ok = all(np.isfinite(e) and e <= TOL for e in errs.values())
        if not ok:
            self.failed.append(name)
        emit(check=name, ok=ok, tol=TOL, **errs, **row,
             peak_bytes_in_use=peak_bytes(self.devices))

    def phase(self, name: str, fn, *args) -> None:
        """Run one phase; an exception fails it (traceback on stderr)."""
        try:
            fn(self, *args)
        except Exception as e:
            traceback.print_exc()
            self.failed.append(name)
            emit(check=name, ok=False, error=first_line(e))


def run_plan(checks, name, plan, x, ref, **row) -> None:
    """Forward (twice), inverse (twice); forward vs ref, round trip vs x."""
    import jax
    x_dev = jax.device_put(x, plan.in_sharding)
    fwd_first = seconds(lambda: plan.forward(x_dev, sharded_in=True))
    y, fwd = wall(lambda: plan.forward(x_dev, sharded_in=True))
    inv_first = seconds(lambda: plan.inverse(y))
    x2, inv = wall(lambda: plan.inverse(y))
    errs = {"err": rel_err(y, ref), "roundtrip_err": rel_err(x2, x)}
    checks.record(name, errs, backend=plan.backend, fwd_first_s=fwd_first,
                  fwd_s=fwd, inv_first_s=inv_first, inv_s=inv,
                  schedule=plan.describe().splitlines()[2].strip(), **row)


def phase_c2c(checks, mesh, n, seed) -> None:
    import scipy.fft

    from repro.core.api import plan_fft
    from repro.core.plan import TuningCache

    rng = np.random.default_rng(seed)
    grid = (n, n, n)
    x = complex_field(rng, grid)
    ref = scipy.fft.fftn(x.astype(np.complex128), workers=-1,
                         overwrite_x=True)
    variants = [("c2c/default", {}), ("c2c/auto",
                                      {"tuning": "auto",
                                       "tune_cache": TuningCache()})]
    variants += [(f"c2c/{b}", {"backend": b}) for b in ("matmul", "pallas")]
    for name, kw in variants:
        plan, plan_s = wall(lambda: plan_fft(mesh, grid, **kw))
        run_plan(checks, name, plan, x, ref, grid=list(grid), plan_s=plan_s)
        del plan


def poisson_reference(rhs: np.ndarray, length: float) -> np.ndarray:
    """Second-order FD Poisson solve, periodic x/y and Neumann z, in float64:
    FFT x FFT x DCT-II, divide by the stencil's eigenvalues, zero mean."""
    import scipy.fft
    n0, n1, n2 = rhs.shape

    def eig(n, periodic):
        dx = length / n
        arg = (2.0 if periodic else 1.0) * np.pi * np.arange(n) / n
        return 2.0 * (np.cos(arg) - 1.0) / dx**2

    spec = scipy.fft.dct(rhs.astype(np.float64), type=2, axis=2, workers=-1)
    spec = scipy.fft.fft2(spec, axes=(0, 1), workers=-1, overwrite_x=True)
    lam = (eig(n0, True)[:, None, None] + eig(n1, True)[None, :, None]
           + eig(n2, False)[None, None, :])
    lam[0, 0, 0] = 1.0
    spec /= lam
    spec[0, 0, 0] = 0.0
    phi = scipy.fft.ifft2(spec, axes=(0, 1), workers=-1,
                          overwrite_x=True).real
    return scipy.fft.idct(phi, type=2, axis=2, workers=-1)


def phase_poisson(checks, mesh, n, seed) -> None:
    import jax

    from repro.core.api import PoissonSolver

    rng = np.random.default_rng(seed + 1)
    grid = (n, n, n)
    topology = ("periodic", "periodic", "bounded")
    rhs = rng.standard_normal(grid, dtype=np.float32)
    ref = poisson_reference(rhs, 2 * np.pi)
    for backend in BACKENDS:
        solver, plan_s = wall(lambda: PoissonSolver(
            mesh, grid, topology=topology, backend=backend))
        rhs_dev = jax.device_put(rhs, solver.plan.in_sharding)
        first = seconds(lambda: solver.solve(rhs_dev, sharded_in=True))
        phi, solve = wall(lambda: solver.solve(rhs_dev, sharded_in=True))
        checks.record(f"poisson/ppb/{backend}", {"err": rel_err(phi, ref)},
                      backend=backend, grid=list(grid), plan_s=plan_s,
                      solve_first_s=first, solve_s=solve)
        del solver, phi


def phase_serve(checks, mesh, edges, seed) -> None:
    import jax.numpy as jnp

    from repro.core.plan import TuningCache
    from repro.serving import FFTService

    small, large = edges
    svc = FFTService(mesh, tune_cache=TuningCache(), bucket_edges=edges,
                     max_batch=4)
    rep = svc.warm(ensure=[((small, small), ("fft", "fft")),
                           ((large, large), ("fft", "fft"))])
    emit(check="serve/warm", ok=rep.skipped == [], skipped=rep.skipped,
         families=rep.families + rep.ensured, batch_plans=rep.batch_plans,
         segments=rep.segments_prebuilt, warm_s=rep.seconds)
    if rep.skipped:
        checks.failed.append("serve/warm")
    rng = np.random.default_rng(seed + 2)
    shapes = [(small, small), (small, small), (small - small // 8, small),
              (large, large), (large, large)]
    inputs = {}
    for shape in shapes:
        x = complex_field(rng, shape)
        inputs[svc.submit(jnp.asarray(x))] = x
    results, drain_s = wall(svc.drain)
    for rid, x in inputs.items():
        res = results[rid]
        xp = np.zeros(res.bucket_grid, np.complex128)
        xp[tuple(slice(0, k) for k in x.shape)] = x
        ref = np.fft.fftn(xp)[tuple(slice(0, k) for k in x.shape)]
        checks.record(f"serve/req{rid}", {"err": rel_err(res.y, ref)},
                      shape=list(x.shape), bucket=list(res.bucket_grid),
                      padded=res.padded, latency_s=res.latency_s,
                      drain_s=drain_s)


def dft_points(x: np.ndarray, ks) -> np.ndarray:
    """X[k0, k1, k2] over the index sets ``ks`` by a direct separable DFT
    in float64: contract the last axis (a slab at a time), then the rest."""
    n = x.shape[0]
    e = [np.exp(-2j * np.pi * np.outer(np.arange(n), k) / n) for k in ks]
    t = np.empty((n, n, len(ks[2])), np.complex128)
    for s in range(0, n, 64):
        t[s:s + 64] = x[s:s + 64].astype(np.complex128) @ e[2]
    t = np.einsum("ijc,jb->ibc", t, e[1])
    return np.einsum("ibc,ia->abc", t, e[0])


def phase_mesh4(checks, mesh, n, seed) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core.api import plan_fft
    from repro.core.tuner import default_machine, propose_chunk_schedule

    rng = np.random.default_rng(seed + 3)
    grid = (n, n, n)
    x = complex_field(rng, grid)
    ks = [np.sort(rng.choice(n, 7, replace=False)) for _ in range(3)]
    ref = dft_points(x, ks)
    bulk, bulk_s = wall(lambda: plan_fft(mesh, grid, decomp="pencil",
                                         n_chunks=1))
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    sched = propose_chunk_schedule(bulk.pipeline_spec(), axis_sizes,
                                   default_machine())
    emit(check="mesh4/schedule", ok=True, tuner_chunk_schedule=list(sched))
    chunked, chunked_s = wall(lambda: plan_fft(mesh, grid, decomp="pencil",
                                               n_chunks=sched))
    x_dev = jax.device_put(x, bulk.in_sharding)
    for name, plan, plan_s in (("bulk", bulk, bulk_s),
                               ("chunked", chunked, chunked_s)):
        fwd_first = seconds(lambda: plan.forward(x_dev, sharded_in=True))
        y, fwd = wall(lambda: plan.forward(x_dev, sharded_in=True))
        devs = {s.device.id for s in y.addressable_shards}
        shard_elems = {s.data.size for s in y.addressable_shards}
        pts = np.asarray(y[jnp.ix_(*(jnp.asarray(k) for k in ks))])
        inv_first = seconds(lambda: plan.inverse(y))
        x2, inv = wall(lambda: plan.inverse(y))
        rt = float(jnp.max(jnp.abs(x2 - x_dev)) / jnp.max(jnp.abs(x_dev)))
        del y, x2
        sharded_ok = len(devs) == 4 and shard_elems == {x.size // 4}
        if not sharded_ok:
            checks.failed.append(f"mesh4/{name}/sharding")
        checks.record(f"mesh4/{name}", {"err": rel_err(pts, ref),
                                        "roundtrip_err": rt},
                      grid=list(grid), points=int(pts.size),
                      chunk_schedule=list(plan.pipeline_spec().chunk_schedule),
                      out_devices=sorted(devs), sharded_ok=sharded_ok,
                      plan_s=plan_s, fwd_first_s=fwd_first, fwd_s=fwd,
                      inv_first_s=inv_first, inv_s=inv)


def phase_precision(checks, device, n, seed) -> None:
    """The matmul and pallas backends' 3-D C2C (three 1-D passes on one
    device) at each MXU precision, against the float64 reference."""
    import jax
    import jax.numpy as jnp
    import scipy.fft

    from repro.core.transforms import fourstep_fft_planes
    from repro.kernels import ops
    from repro.kernels.precision import MATMUL_PRECISION

    def matmul_fft(a, axis, precision):
        a = jnp.moveaxis(a, axis, -1)
        r, i = fourstep_fft_planes(jnp.real(a), jnp.imag(a),
                                   precision=precision)
        return jnp.moveaxis(jax.lax.complex(r, i), -1, axis)

    def pallas_fft(a, axis, precision):
        return ops.fft1d(a, axis, precision=precision)

    rng = np.random.default_rng(seed)
    grid = (n, n, n)
    x = complex_field(rng, grid)
    ref = scipy.fft.fftn(x.astype(np.complex128), workers=-1,
                         overwrite_x=True)
    x_dev = jax.device_put(x, device)
    for precision in (jax.lax.Precision.DEFAULT, jax.lax.Precision.HIGH,
                      jax.lax.Precision.HIGHEST):
        for backend, fft1 in (("matmul", matmul_fft),
                              ("pallas", pallas_fft)):
            fn = jax.jit(lambda a, f=fft1, p=precision:
                         f(f(f(a, 0, p), 1, p), 2, p))
            name = f"precision/{backend}/{precision.name}"
            try:
                first = seconds(lambda: fn(x_dev))
            except NotImplementedError as e:
                if precision == MATMUL_PRECISION:
                    raise
                # Mosaic lowers only some dot precisions (not HIGH).
                emit(check=name, ok=True, used=False, backend=backend,
                     precision=precision.name, refused=first_line(e))
                continue
            y, run = wall(lambda: fn(x_dev))
            err = rel_err(y, ref)
            del y
            row = dict(backend=backend, precision=precision.name,
                       grid=list(grid), first_s=first, run_s=run,
                       within_tol=bool(err <= TOL))
            if precision == MATMUL_PRECISION:
                checks.record(name, {"err": err}, used=True, **row)
            else:
                emit(check=name, ok=True, used=False, tol=TOL, err=err, **row)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--precision-probe", action="store_true",
                    help="only the per-precision error of the matmul and "
                         "pallas backends")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny sizes on the CPU platform")
    args = ap.parse_args(argv)
    if args.precision_probe and args.chips != 1:
        ap.error("--precision-probe runs on one chip")
    if args.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.chips > 1:
            os.environ["XLA_FLAGS"] = (
                f"--xla_force_host_platform_device_count={args.chips}")
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    import jax

    from repro.compat import make_mesh
    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not args.cpu_rehearsal:
        print(f"chip_smoke: no TPU (jax found {dev.platform!r}); pass "
              f"--cpu-rehearsal for the tiny CPU run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"jax found {len(devices)}", file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    cached = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    devices = devices[:args.chips]
    sizes = TINY if args.cpu_rehearsal else FULL
    checks = Checks(devices)
    emit(check="start", ok=True, platform=dev.platform,
         kind=dev.device_kind, count=len(devices), jax=jax.__version__,
         compile_cache=cache_dir, compile_cache_entries_at_start=cached)
    if args.precision_probe:
        checks.phase("precision", phase_precision, devices[0],
                     sizes["precision"], args.seed)
    elif args.chips == 4:
        mesh = make_mesh((2, 2), ("data", "model"), devices=devices)
        checks.phase("mesh4", phase_mesh4, mesh, sizes["mesh4"], args.seed)
    else:
        mesh = make_mesh((1, 1), ("data", "model"), devices=devices)
        checks.phase("c2c", phase_c2c, mesh, sizes["c2c"], args.seed)
        checks.phase("poisson", phase_poisson, mesh, sizes["poisson"],
                     args.seed)
        checks.phase("serve", phase_serve, mesh, sizes["serve"], args.seed)
    if checks.failed:
        print(f"chip_smoke: FAILED {checks.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
