"""Autotuner benchmark: static default vs tuned plan, per shape — plus a
pencil/slab/best-hybrid face-off on a 4-D grid and a calibrated-vs-default
cost-model comparison.

For each (grid, mesh) problem the tuner enumerates the full plan space,
prunes with the LogP/roofline model and measures the top-k survivors; this
table reports the measured default (pencil/xla/n_chunks=1), the measured
winner, and which plan won — the repo's analogue of the paper's "dynamic
scheduling beats static tuning" claim, executable on whatever devices the
process sees (run under XLA_FLAGS=--xla_force_host_platform_device_count=8
for the multi-device picture).

The second block quantifies what calibration buys: for each shape it ranks
the candidates twice — once with the hard-coded model-default constants and
once with the profile ``perfmodel.calibrate()`` measured on this very
process — measures the union of both models' top-3 survivors, and reports
per-model prediction/measurement rank agreement (pairwise concordance over
the measured subset, and whether the model's argmin was the measured
argmin).

``--emit-json PATH`` instead writes a machine-keyed document of best-plan
rows per backend (xla / matmul / pallas, the latter in interpret mode
on the CPU) — the committed ``BENCH_tuner.json`` baseline.  ``--gate BASELINE``
compares the freshly measured rows against that baseline and exits nonzero
when any backend regressed by more than 20% *relative to the xla backend on
the same grid in the same run* (absolute wall times are machine-specific;
the xla-normalized ratio is the portable signal CI can gate on).
"""
from __future__ import annotations

import json
import sys
from itertools import combinations

import jax

from benchmarks.common import emit

SHAPES = ((8, 8, 16), (16, 16, 32), (32, 32, 32))
# Shapes for the JSON best-plan table: the biggest SHAPES entry is dropped
# so the pallas-interpret rows keep the CI smoke cheap.
JSON_SHAPES = ((8, 8, 16), (16, 16, 32))
KINDS3 = ("fft", "fft", "fft")
GATE_THRESHOLD = 0.20


def _rank_agreement(ranked, measured):
    """(concordant-pair fraction, argmin-hit) of a predicted ranking vs
    measured times, over the measured candidate subset."""
    pred = {c: p for p, c in ranked if c in measured}
    cands = list(pred)
    pairs = list(combinations(cands, 2))
    if not pairs:
        return 1.0, 1
    conc = 0.0
    for a, b in pairs:
        s = (pred[a] - pred[b]) * (measured[a] - measured[b])
        # A tied prediction carries no ordering information: score it 0.5
        # so a degenerate everything-ties model cannot claim 100%.
        conc += 1.0 if s > 0 else (0.5 if s == 0 else 0.0)
    best_pred = min(cands, key=lambda c: pred[c])
    best_meas = min(measured, key=measured.get)
    return conc / len(pairs), int(best_pred == best_meas)


def run() -> None:
    from repro.compat import make_mesh
    from repro.core import TuningCache, tune
    from repro.core.perfmodel import profile_from_machine
    from repro.core.tuner import (default_machine, enumerate_candidates,
                                  measure_candidate, rank_candidates,
                                  resolve_profile)

    n_dev = len(jax.devices())
    if n_dev >= 8:
        mesh = make_mesh((2, n_dev // 2), ("data", "model"))
    else:
        mesh = make_mesh((1, n_dev), ("data", "model"))
    cache = TuningCache(path=None)  # in-memory: benchmark, not wisdom

    # Block 1: static default vs tuned winner (tune() resolves the
    # calibrated profile itself and stores it in the in-memory cache).
    for grid in SHAPES:
        plan = tune(grid, mesh, cache=cache, top_k=3)
        label = "x".join(map(str, grid))
        won = (f"{plan.decomp}({','.join(plan.mesh_axes)})/{plan.backend}"
               f"/chunks={plan.n_chunks}")
        emit(f"tuner_default_{label}", plan.baseline_s * 1e6)
        emit(f"tuner_winner_{label}", plan.measured_s * 1e6, won)

    # Block 2: the decomposition families head-to-head on a 4-D grid — the
    # >3-D case the ROADMAP left open.  A pencil needs ndim-1 = 3 mesh
    # axes, so on 2-axis meshes only slab and hybrid exist; each family's
    # model-best candidate is measured, so the row shows what the hybrid
    # search space buys (or costs) over the textbook layouts.
    grid4 = (4, 4, 8, 8)
    kinds4 = ("fft",) * 4
    label4 = "x".join(map(str, grid4))
    prof4 = resolve_profile(cache, mesh=mesh, allow_calibrate=False)
    cands4 = enumerate_candidates(grid4, mesh, kinds4, machine=prof4)
    ranked_all4 = rank_candidates(cands4, grid4, mesh, prof4, kinds=kinds4)
    best_by_family = {}
    for pred, cand in ranked_all4:
        best_by_family.setdefault(cand.decomp, (pred, cand))
    for family in ("pencil", "slab", "hybrid"):
        if family not in best_by_family:
            emit(f"tuner4d_{family}_{label4}", 0.0,
                 "infeasible on this mesh")
            continue
        pred, cand = best_by_family[family]
        t = measure_candidate(cand, grid4, mesh, kinds4,
                              jax.numpy.complex64)
        emit(f"tuner4d_{family}_{label4}", t * 1e6,
             f"pred={pred * 1e6:.0f}us {cand.describe()}")

    # Best uniform n_chunks vs best per-hop schedule on the asymmetric
    # multi-hop hybrids: the chunk-schedule policy engine's pitch.  The
    # uniform row is the best hybrid whose hops all share one count; the
    # per-hop row is the best scheduler-proposed heterogeneous schedule
    # (absent when the policy argmin is uniform on this machine).
    ranked4 = [(p, c) for p, c in ranked_all4 if c.decomp == "hybrid"]
    best_uni = next(((p, c) for p, c in ranked4
                     if c.chunk_schedule is None), None)
    best_het = next(((p, c) for p, c in ranked4
                     if c.chunk_schedule is not None), None)
    for tag, pick in (("uniform", best_uni), ("perhop", best_het)):
        if pick is None:
            emit(f"tuner4d_chunks_{tag}_{label4}", 0.0,
                 "no such candidate (policy argmin is uniform)")
            continue
        pred, cand = pick
        t = measure_candidate(cand, grid4, mesh, kinds4,
                              jax.numpy.complex64)
        emit(f"tuner4d_chunks_{tag}_{label4}", t * 1e6,
             f"pred={pred * 1e6:.0f}us {cand.describe()}")

    # Block 3: does calibration improve the pruning model's ranking?
    # Blocks 1-2's tune()/resolve calls already calibrated and stored the
    # profile in `cache`; resolve it rather than re-running the
    # microbenchmarks.
    default_prof = profile_from_machine(default_machine())
    calib_prof = resolve_profile(cache, mesh=mesh)
    if not calib_prof.calibrated:
        # REPRO_CALIBRATE=off (or calibration unavailable): the
        # "calibrated" rows would silently duplicate the default ones.
        emit("tuner_rankagree_skipped", 0.0, "no calibrated profile")
        return
    for grid in SHAPES:
        label = "x".join(map(str, grid))
        cands = enumerate_candidates(grid, mesh, KINDS3)
        rk_def = rank_candidates(cands, grid, mesh, default_prof,
                                 kinds=KINDS3)
        rk_cal = rank_candidates(cands, grid, mesh, calib_prof,
                                 kinds=KINDS3)
        probe = {c for _, c in rk_def[:3]} | {c for _, c in rk_cal[:3]}
        measured = {
            c: measure_candidate(c, grid, mesh, KINDS3, jax.numpy.complex64)
            for c in probe
        }
        for name, ranked in (("default", rk_def), ("calibrated", rk_cal)):
            conc, hit = _rank_agreement(ranked, measured)
            emit(f"tuner_rankagree_{name}_{label}", conc * 100.0,
                 f"argmin_hit={hit}")


def _make_mesh():
    from repro.compat import make_mesh
    n_dev = len(jax.devices())
    if n_dev >= 8:
        return make_mesh((2, n_dev // 2), ("data", "model"))
    return make_mesh((1, n_dev), ("data", "model"))


def best_plan_rows(shapes=JSON_SHAPES) -> dict:
    """Machine-keyed best-plan-per-backend table (the BENCH_tuner.json body).

    For each grid and each tuner backend, the cost model picks that
    backend's best candidate (decomp x mesh-axis order x chunk schedule)
    and ``measure_candidate`` times its compiled executable.  On the CPU the
    pallas rows run the kernel in interpret mode (flagged per row), so the
    table is regenerable on any host — including CI.
    """
    from repro.core import TuningCache
    from repro.core.tuner import (BACKENDS, enumerate_candidates,
                                  measure_candidate, rank_candidates,
                                  resolve_profile)
    from repro.kernels.ops import interpret_mode

    mesh = _make_mesh()
    prof = resolve_profile(TuningCache(path=None), mesh=mesh)
    interpret = interpret_mode()
    rows = []
    for grid in shapes:
        kinds = ("fft",) * len(grid)
        for backend in BACKENDS:
            cands = enumerate_candidates(grid, mesh, kinds, machine=prof,
                                         backends=(backend,))
            ranked = rank_candidates(cands, grid, mesh, prof, kinds=kinds)
            pred, cand = ranked[0]
            # Best-of-10 (vs the tuner's default 3): the gate compares runs
            # across CI invocations, so per-row noise must stay well under
            # the 20% regression threshold.
            t = measure_candidate(cand, grid, mesh, kinds,
                                  jax.numpy.complex64, repeats=10)
            rows.append({
                "grid": "x".join(map(str, grid)),
                "backend": backend,
                "interpret": bool(interpret and backend == "pallas"),
                "plan": cand.describe(),
                "predicted_us": round(pred * 1e6, 1),
                "measured_us": round(t * 1e6, 1),
            })
            emit(f"tuner_best_{backend}_{rows[-1]['grid']}", t * 1e6,
                 cand.describe())
    return {
        "machine": {
            "platform": jax.default_backend(),
            "device_count": len(jax.devices()),
            "mesh": list(mesh.devices.shape),
        },
        "rows": rows,
    }


def _ratios(doc: dict) -> dict:
    """Per-(grid, backend) measured time normalized by the same grid's xla
    row — the machine-portable quantity the delta gate compares."""
    xla = {r["grid"]: r["measured_us"] for r in doc["rows"]
           if r["backend"] == "xla"}
    out = {}
    for r in doc["rows"]:
        base = xla.get(r["grid"])
        if base and base > 0:
            out[(r["grid"], r["backend"])] = r["measured_us"] / base
    return out


def gate(baseline: dict, current: dict,
         threshold: float = GATE_THRESHOLD) -> list:
    """Regression messages: any backend whose xla-normalized time grew by
    more than ``threshold`` vs the committed baseline (shared keys only —
    a smoke run gates just the grids it measured)."""
    if baseline.get("machine", {}).get("mesh") != \
            current.get("machine", {}).get("mesh"):
        return []  # different mesh: ratios aren't comparable, skip gating
    base_r, cur_r = _ratios(baseline), _ratios(current)
    msgs = []
    for key in sorted(set(base_r) & set(cur_r)):
        grid, backend = key
        if cur_r[key] > (1.0 + threshold) * base_r[key]:
            msgs.append(
                f"REGRESSION {backend}@{grid}: xla-normalized time "
                f"{cur_r[key]:.2f}x vs baseline {base_r[key]:.2f}x "
                f"(>{threshold:.0%} slower)")
    return msgs


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--emit-json", metavar="PATH",
                    help="write the best-plan-per-backend table as JSON")
    ap.add_argument("--gate", metavar="BASELINE",
                    help="compare against a committed BENCH_tuner.json; "
                         "exit 1 on >20%% xla-normalized regression")
    ap.add_argument("--smoke", action="store_true",
                    help="measure only the smallest grid (CI)")
    a = ap.parse_args(argv)
    if not (a.emit_json or a.gate):
        run()
        return 0
    doc = best_plan_rows(shapes=(JSON_SHAPES[:1] if a.smoke
                                 else JSON_SHAPES))
    if a.emit_json:
        with open(a.emit_json, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"wrote {a.emit_json} ({len(doc['rows'])} rows)")
    if a.gate:
        with open(a.gate) as f:
            baseline = json.load(f)
        msgs = gate(baseline, doc)
        for m in msgs:
            print(m)
        if msgs:
            return 1
        print(f"gate ok vs {a.gate}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
