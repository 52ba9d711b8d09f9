#!/usr/bin/env python3
"""On-chip benchmark of the distributed FFT: one run of one cell.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's configuration through the program's public entry, makes
its fields on the device from ``--seed``, warms every shape once (set-up),
then runs the traffic's closed loop for ``--seconds`` and checks what that
window produced against a float64 reference.  The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics read from a profiler trace of the window), ``device``,
with ``--trace 1`` ``breakdown``, and last ``checks``: each compared number
beside its limit, also the last lines of standard error.

JAX finding no TPU, or fewer chips than the cell asks for, exits 2 and
prints no result.  ``--cpu-rehearsal`` runs the same path on the CPU at
16 per axis (fake host devices for a four-chip cell, Pallas interpreted)
and prints ``platform: cpu`` and no metric.  ``--broken <name>`` wraps every
op of the entry in a control or a planted fault (``chipbench/broken.py``);
a benchmark run never passes it.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None, t_start: float = T_START) -> int:
    from chipbench import broken, catalog

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    ap.add_argument("--broken", choices=sorted(broken.VARIANTS))
    args = ap.parse_args(argv)
    bench = catalog.load_benchmark()
    if args.cpu_rehearsal:
        chips = catalog.cell(bench, args.workload)["chips"]
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={chips}")
    from chipbench import harness

    try:
        result, verdict = harness.run_cell(
            bench, args.workload, seed=args.seed, seconds=args.seconds,
            trace=bool(args.trace), rehearsal=args.cpu_rehearsal,
            broken=args.broken, t_start=t_start)
    except harness.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    for k, v in verdict.items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r} "
              f"{'ok' if v['ok'] else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    if args.cpu_rehearsal:
        print("platform: cpu")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    # The checkout's root (for ``chipbench``) and ``src`` (the program), in
    # place of this script's directory.
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    sys.exit(main())
