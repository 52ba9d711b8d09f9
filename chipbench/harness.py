"""One run of one cell: set-up, the measured window, the check of what the
window produced against the float64 reference, and the metrics.

A step is one call of an op of the program's public entry on the device,
ended by ``block_until_ready``.  The traffic mix (``traffic/<mix>.json``)
says which ops a step cycles through, how many seeded fields there are, and
whether each step takes the previous step's output (``chain``) or its field.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Optional

import numpy as np

from chipbench import catalog

#: JAX's persistent compilation cache: a fixed path inside the checkout, so
#: that two checkouts measured side by side share nothing.
CACHE_DIR = catalog.HERE.parent / ".jax_cache"
PEAKS_FILE = catalog.HERE / "peaks.json"
#: ``--cpu-rehearsal`` sizes: every grid axis, and sampled reference points.
REHEARSAL_EDGE = 16
REHEARSAL_POINTS = 4


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class System:
    """The program's entry for one configuration, as a run drives it."""
    ops: dict              # op name -> fn(array) -> array
    io: dict               # op name -> (input struct, output struct)
    flops: dict            # op name -> nominal operations of one call
    field_dtype: Any
    field_sharding: Any
    mesh: Any


@dataclasses.dataclass
class Run:
    """What a finished run hands to the metric readers."""
    setup_s: float
    window_s: float
    step_s: list           # wall of each step in the window
    dispatch_s: list       # call into the entry until it returned
    peak_bytes: Optional[int]
    bytes_per_step: float  # nominal least HBM bytes, per chip
    flops_per_step: float  # nominal operations, per chip
    peak: Optional[dict]   # the chip's row of peaks.json
    trace: Any = None      # a tracereduce.Reduction with --trace 1

    @property
    def steps(self) -> int:
        return len(self.step_s)


def make_mesh(spec: dict, devices):
    import jax
    from jax.sharding import AxisType

    shape = tuple(spec["shape"])
    return jax.make_mesh(shape, tuple(spec["axes"]),
                         axis_types=(AxisType.Auto,) * len(shape),
                         devices=devices)


def line_flops(grid, kinds) -> float:
    """Nominal operations of one N-D transform: 5 n log2 n per complex
    line of length n, half that for a real-input line (dct2, rfft)."""
    size = math.prod(grid)
    return sum((5.0 if k in ("fft", "ifft") else 2.5) * size * math.log2(n)
               for n, k in zip(grid, kinds))


def peak_row(kind: str, path: Path = PEAKS_FILE) -> dict:
    """The published peaks of a ``device_kind``; an unknown kind is an
    error."""
    table = catalog.load_json(path)["chips"]
    if kind not in table:
        raise KeyError(f"device_kind {kind!r} is not in {path.name} "
                       f"(known: {', '.join(sorted(table))})")
    return table[kind]


def field_key(seed: int):
    import jax
    seed %= 2**64
    return jax.random.wrap_key_data(
        np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32))


def make_fields(system: System, grid, count: int, seed: int) -> list:
    """``count`` standard-normal fields from the seed, made on the device in
    one jitted call, straight into the entry's input sharding."""
    import jax

    def gen(key):
        return [jax.random.normal(k, tuple(grid), system.field_dtype)
                for k in jax.random.split(key, count)]

    fields = jax.jit(gen, out_shardings=[system.field_sharding] * count)(
        field_key(seed))
    return jax.block_until_ready(fields)


class Loop:
    """The closed loop of one caller.  Step ``i`` calls op
    ``ops[(i // fields) % len(ops)]`` on field ``i % fields``: on the field
    itself, or with ``chain`` on that field's last output.  The last input
    and output of each field are kept for the check; nothing else outlives
    its step, so the loop holds no more device memory than the plain loop
    of a user."""

    def __init__(self, system: System, traffic: dict, fields: list,
                 annotate=None):
        self.system = system
        self.ops = list(traffic["ops"])
        self.chain = bool(traffic["chain"])
        self.fields = fields           # with chain: each field's state
        n = len(fields)
        self.last_op = [None] * n
        self.last_in = [None] * n
        self.last_out = [None] * n
        self.cycle = n * len(self.ops)
        self.i = 0
        self.annotate = annotate or (lambda name: contextlib.nullcontext())

    def run(self, seconds: float, span: str = "window",
            clock=time.perf_counter) -> tuple:
        """Steps until ``seconds`` have passed and the cycle of fields and
        ops is whole, inside the host span ``span``; (step walls, dispatch
        walls, window seconds)."""
        step_s, dispatch_s = [], []
        n = len(self.fields)
        begin = clock()
        with self.annotate(span):
            while True:
                f = self.i % n
                op = self.ops[(self.i // n) % len(self.ops)]
                self.last_in[f] = self.last_out[f] = None
                inp = self.fields[f]
                with self.annotate("step"):
                    t0 = clock()
                    with self.annotate("dispatch"):
                        out = self.system.ops[op](inp)
                    t1 = clock()
                    with self.annotate("block"):
                        out.block_until_ready()
                    t2 = clock()
                step_s.append(t2 - t0)
                dispatch_s.append(t1 - t0)
                if self.chain:
                    self.fields[f] = out
                    self.last_in[f] = inp
                self.last_op[f], self.last_out[f] = op, out
                del inp, out
                self.i += 1
                if t2 - begin >= seconds and self.i % self.cycle == 0:
                    return step_s, dispatch_s, t2 - begin


def peak_bytes(devices) -> Optional[int]:
    stats = [d.memory_stats() for d in devices]
    if any(s is None or "peak_bytes_in_use" not in s for s in stats):
        return None
    return max(s["peak_bytes_in_use"] for s in stats)


def check(loop: Loop, cfg: dict, traffic: dict, grid, seed: int) -> dict:
    """The numbers ``correct`` compares, from what the window produced:

    * ``<op>_err``: the last output of each field against the float64
      reference of the same op on the same input (max|y - ref| / max|ref|);
    * ``drift_per_step`` (traffic with ``drift``, whose cycle of ops returns
      to its input): each field's final state against the field regenerated
      from the seed, over the number of steps it went through.  Rounding
      moves the state by about the same amount each step, so the gap grows
      with the steps and only its rate is steady from run to run;
    * ``unsharded`` (a mesh of several chips): devices of the mesh that do
      not hold their share of each last output.
    """
    import jax
    import jax.numpy as jnp

    values = {}
    system = loop.system
    if traffic.get("drift"):
        x0 = make_fields(system, grid, len(loop.fields), seed)
        gap = jax.jit(lambda a, b: jnp.max(jnp.abs(a - b))
                      / jnp.max(jnp.abs(b)))
        steps = loop.i // len(loop.fields)
        values["drift_per_step"] = max(
            float(gap(s, x)) for s, x in zip(loop.fields, x0)) / steps
        del x0
    ndev = system.mesh.devices.size
    if ndev > 1:
        missing = 0
        for out in loop.last_out:
            held = {s.device for s in out.addressable_shards
                    if s.data.size * ndev == out.size}
            missing += sum(d not in held for d in system.mesh.devices.flat)
        values["unsharded"] = missing
    reference = catalog.module("references", cfg["reference"])
    rng = np.random.default_rng(seed)
    for f, op in enumerate(loop.last_op):
        inp = loop.last_in[f] if loop.chain else loop.fields[f]
        err = reference.error(op, inp, loop.last_out[f], cfg, rng)
        values[f"{op}_err"] = max(values.get(f"{op}_err", 0.0), err)
    return values


def judge(values: dict, limits: dict) -> dict:
    """Each compared number beside its limit; a number without a limit, or a
    limit without its number, is an error of the benchmark."""
    if set(values) != set(limits):
        raise KeyError(f"compared {sorted(values)}, limits are for "
                       f"{sorted(limits)}")
    return {k: {"value": v, "limit": limits[k]["limit"],
                "ok": bool(math.isfinite(v) and v <= limits[k]["limit"])}
            for k, v in values.items()}


def nominal(system: System, traffic: dict, chips: int) -> tuple:
    """(bytes, operations) per step and chip: each step's input read once
    and its output written once, whatever implements it; the mean over the
    traffic's cycle of ops."""
    ops = traffic["ops"]
    moved = [sum(math.prod(s.shape) * np.dtype(s.dtype).itemsize
                 for s in system.io[op]) for op in ops]
    flops = [system.flops[op] for op in ops]
    return (sum(moved) / len(ops) / chips, sum(flops) / len(ops) / chips)


@contextlib.contextmanager
def profiled(enabled: bool):
    """With ``enabled``, record the JAX profiler into a temporary directory.
    Yields a list: the directory, and once the block ends the ``.xplane.pb``
    path; the caller removes the directory."""
    found = []
    if not enabled:
        yield found
        return
    import jax

    path = tempfile.mkdtemp(prefix="chipbench-trace-")
    found.append(path)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(path, profiler_options=opts)
    try:
        yield found
    finally:
        jax.profiler.stop_trace()
        found.extend(glob.glob(f"{path}/**/*.xplane.pb", recursive=True))


def run_cell(bench: dict, name: str, *, seed: int, seconds: float,
             trace: bool, rehearsal: bool, broken: Optional[str],
             t_start: float) -> tuple:
    """(result, check lines) of one run.  Raises ``NoChip`` before any work
    when the platform or the chip count is wrong."""
    import jax

    cell = catalog.cell(bench, name)
    cfg = dict(catalog.config(bench, cell["config"]))
    traffic = catalog.part("traffic", cell["traffic"])
    limits = catalog.part("limits", name)
    chips = int(cell["chips"])
    if math.prod(cfg["mesh"]["shape"]) != chips:
        raise ValueError(f"{name}: mesh {cfg['mesh']['shape']} is not "
                         f"{chips} chips")
    devices = jax.devices()
    if devices[0].platform != "tpu" and not rehearsal:
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"{name} needs {chips} chips, JAX found {len(devices)}")
    devices = devices[:chips]
    kind = devices[0].device_kind
    peak = None
    if rehearsal:
        cfg["grid"] = [REHEARSAL_EDGE] * len(cfg["grid"])
        if cfg.get("reference_points"):
            cfg["reference_points"] = REHEARSAL_POINTS
    else:
        peak = peak_row(kind)
        # Every program is cached, however fast it compiled: otherwise the
        # sub-second compiles would repeat in every run's set-up.
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    grid = cfg["grid"]

    system = catalog.module("entries", cfg["entry"]).build(cfg, devices)
    if broken:
        from chipbench import broken as broken_mod
        system.ops = broken_mod.wrap(broken, system)
    fields = make_fields(system, grid, int(traffic["fields"]), seed)
    annotate = None
    if trace:
        annotate = lambda what: jax.profiler.TraceAnnotation(  # noqa: E731
            f"chipbench.{what}")
    loop = Loop(system, traffic, fields, annotate)
    del fields
    loop.run(0.0, "warm")              # one whole cycle: warm every shape
    setup_s = time.perf_counter() - t_start
    with profiled(trace) as found:
        if trace:
            loop.run(0.0, "settle")    # the profiler's own start-up, untimed
        step_s, dispatch_s, window_s = loop.run(seconds)
    slowest = sorted(range(len(step_s)), key=lambda i: -step_s[i])[:5]
    print(f"chipbench: {len(step_s)} steps, median "
          f"{sorted(step_s)[len(step_s) // 2] * 1e3:.3f} ms, slowest "
          + ", ".join(f"#{i} {step_s[i] * 1e3:.1f} ms" for i in slowest),
          file=sys.stderr)
    pk = peak_bytes(devices)
    bytes_step, flops_step = nominal(system, traffic, chips)

    values = check(loop, cfg, traffic, grid, seed)
    del loop, system
    verdict = judge(values, limits)

    run = Run(setup_s=setup_s, window_s=window_s, step_s=step_s,
              dispatch_s=dispatch_s, peak_bytes=pk,
              bytes_per_step=bytes_step, flops_per_step=flops_step,
              peak=peak)
    red = None
    try:
        if trace and len(found) > 1:
            from chipbench import tracereduce
            rules = tracereduce.load_rules()
            ops, spans = tracereduce.load(found[1], rules)
            if ops:
                red = tracereduce.reduce(ops, spans)
    finally:
        if found:
            shutil.rmtree(found[0], ignore_errors=True)
    run.trace = red

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": pk}
    metrics = {}
    if not rehearsal:
        for m in catalog.metrics_for(bench, name, trace):
            value = catalog.module("metrics", m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if trace:
            device["busy_s"] = red.busy_s if red else None
            device["window_s"] = red.window_s if red else window_s
    result = {"correct": all(v["ok"] for v in verdict.values()),
              "attempted": len(step_s),
              "failed": sum(not v["ok"] for v in verdict.values()),
              "metrics": metrics, "device": device}
    if trace and red and not rehearsal:
        from chipbench.tracereduce import breakdown
        result["breakdown"] = breakdown(red)
    result["checks"] = {k: {"value": v["value"], "limit": v["limit"]}
                        for k, v in verdict.items()}
    return result, verdict
