"""What the program records for a profiler: program names and device scopes
in the compiled text, host spans in a trace, and the XLA compile counter."""
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_subprocess

GRID = (16, 16, 16)
OP_NAME = re.compile(r'op_name="([^"]*)"')
STAGE = re.compile(r"(^|/)stage[0-9]+(/|$)")
HOP = re.compile(r"(^|/)hop[0-9]+(/|$)")
#: The plan body's parameter: its own ops carry no jit(...) prefix.
PARAM = "repro_fft_in"


def lowered_ops(exe) -> dict:
    """Module name and (opcode, op_name parts) of every instruction of a
    compiled executable's text; of the parameters, only the program's own
    (a nested computation's, such as a scatter's combiner, run no op)."""
    text = exe.as_text()
    ops, entry = [], False
    for line in text.splitlines():
        if line[:1] not in ("", " "):
            entry = line.startswith("ENTRY")
        m = OP_NAME.search(line)
        if not m or "=" not in line:
            continue
        code = re.search(r"\s([a-z][a-z0-9-]*)\(", line.split("=", 1)[1])
        code = code.group(1) if code else ""
        if code == "parameter" and not entry:
            continue
        ops.append((code, m.group(1).split(";")))
    return {"module": text.split(None, 2)[1].rstrip(","), "ops": ops}


def plan_programs(mesh, n_chunks=1, ppb=False) -> dict:
    """Compiled text of a plan's forward and inverse executables."""
    from repro.core.api import PoissonSolver, plan_fft
    if ppb:
        plan = PoissonSolver(mesh, GRID, topology=("periodic", "periodic",
                                                   "bounded")).plan
    else:
        plan = plan_fft(mesh, GRID, n_chunks=n_chunks)
    return {d: lowered_ops(plan._executable(inverse=d == "inverse",
                                            donate=False))
            for d in ("forward", "inverse")}


FOUR_DEVICE_SCRIPT = """
import json, sys
sys.path.insert(0, {tests!r})
from test_tracing import plan_programs
from repro.compat import make_mesh
mesh = make_mesh((2, 2), ("data", "model"))
print(json.dumps({{"pencil": plan_programs(mesh),
                  "pencil_chunks2": plan_programs(mesh, n_chunks=2),
                  "ppb": plan_programs(mesh, ppb=True)}}))
"""


@pytest.fixture(scope="module")
def programs(cpu_mesh):
    out = {f"1dev_{k}": v for k, v in (
        ("pencil", plan_programs(cpu_mesh)),
        ("ppb", plan_programs(cpu_mesh, ppb=True)))}
    got = run_subprocess(FOUR_DEVICE_SCRIPT.format(
        tests=os.path.dirname(__file__)), devices=4)
    out.update({f"4dev_{k}": v for k, v in
                json.loads(got.strip().splitlines()[-1]).items()})
    return out


CASES = [(case, d) for case in ("1dev_pencil", "1dev_ppb", "4dev_pencil",
                                "4dev_pencil_chunks2", "4dev_ppb")
         for d in ("forward", "inverse")]


@pytest.mark.parametrize("case,direction", CASES)
def test_every_op_lies_in_the_named_program(programs, case, direction):
    prog = programs[case][direction]
    assert prog["module"] == f"jit_repro_fft_{direction}"
    prefix = f"jit(repro_fft_{direction})/"
    for _, parts in prog["ops"]:
        for part in parts:
            assert part.startswith(prefix) or part == PARAM, part


@pytest.mark.parametrize("case,direction", CASES)
def test_stage_and_hop_scopes_partition_the_work(programs, case, direction):
    ops = programs[case][direction]["ops"]
    ffts = [parts for code, parts in ops if code == "fft"]
    a2as = [parts for code, parts in ops if code == "all-to-all"]
    assert ffts
    assert all(any(STAGE.search(p) for p in parts) for parts in ffts)
    assert all(any(HOP.search(p) for p in parts) for parts in a2as)
    assert bool(a2as) == case.startswith("4dev")
    for _, parts in ops:
        assert not (any(STAGE.search(p) for p in parts)
                    and any(HOP.search(p) for p in parts)), parts


def test_stage_and_hop_indices_follow_execution_order(programs):
    for case in ("4dev_pencil", "4dev_pencil_chunks2"):
        for d in ("forward", "inverse"):
            names = {p for _, parts in programs[case][d]["ops"]
                     for p in parts}
            stages = {int(m.group(1)) for p in names
                      for m in [re.search(r"/stage([0-9]+)/", p)] if m}
            hops = {int(m.group(1)) for p in names
                    for m in [re.search(r"/hop([0-9]+)/", p)] if m}
            assert stages == {0, 1, 2} and hops == {0, 1}, (case, d)


def test_chunked_path_keeps_the_scopes_of_the_bulk_path(programs):
    # Two chunks per hop: the same scopes on the same kinds of op; the
    # chunks' split and concatenate are part of the hop.
    for d in ("forward", "inverse"):
        def scoped(case):
            return {(code, m.group(0).strip("/"))
                    for code, parts in programs[case][d]["ops"]
                    if code in ("fft", "all-to-all") for p in parts
                    for m in [STAGE.search(p) or HOP.search(p)] if m}
        assert scoped("4dev_pencil_chunks2") == scoped("4dev_pencil")
        concats = [parts for code, parts in
                   programs["4dev_pencil_chunks2"][d]["ops"]
                   if code == "concatenate"]
        assert any(HOP.search(p) for parts in concats for p in parts)


def test_jit_path_and_segments_carry_program_names(cpu_mesh):
    from repro.core.api import plan_fft
    from repro.core.pipeline import build_segment, program_name
    plan = plan_fft(cpu_mesh, GRID, precompiled=False)
    x = jnp.ones(GRID, jnp.complex64)
    for inverse, name in ((False, "forward"), (True, "inverse")):
        fn = plan._jitted(inverse=inverse, donate=False)
        assert fn.lower(x).as_text().startswith(
            f"module @jit_repro_fft_{name}")
    spec = plan.pipeline_spec()
    for j, exe in enumerate(plan.segments()):
        assert lowered_ops(exe)["module"] == f"jit_repro_fft_forward_seg{j}"
        assert build_segment(cpu_mesh, spec, j).__name__ \
            == program_name(spec, j)


def test_plan_round_trip_unchanged_by_names_and_scopes(cpu_mesh):
    from repro.core.api import plan_fft
    plan = plan_fft(cpu_mesh, GRID)
    x = np.random.default_rng(0).standard_normal(GRID).astype(np.complex64)
    y = plan.forward(jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(y), np.fft.fftn(x), rtol=1e-4,
                               atol=1e-3)
    np.testing.assert_allclose(np.asarray(plan.inverse(y)), x, atol=1e-5)


# -- host spans ---------------------------------------------------------------

def test_entry_spans_in_a_cpu_trace(cpu_mesh, tmp_path):
    from jax.profiler import ProfileData

    from repro.core.api import PoissonSolver, plan_fft
    solver = PoissonSolver(cpu_mesh, GRID,
                           topology=("periodic", "periodic", "bounded"))
    rhs = jnp.ones(GRID, jnp.float32)
    solver.solve(rhs).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    plan = plan_fft(cpu_mesh, (8, 8, 8))
    plan.forward(jnp.ones((8, 8, 8), jnp.complex64)).block_until_ready()
    solver.solve(rhs).block_until_ready()
    jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    spans = {}
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    spans.setdefault(e.name, []).append((e.start_ns,
                                                         e.end_ns))
    assert set(spans) == {"repro.forward", "repro.inverse", "repro.solve",
                          "repro.solve.scale"}
    (s0, s1), = spans["repro.solve"]
    (c0, c1), = spans["repro.solve.scale"]
    assert s0 <= c0 <= c1 <= s1
    # The solve's forward and inverse nest in it; the plan's own forward
    # stands alone.
    inside = [(a, b) for a, b in spans["repro.forward"] + spans[
        "repro.inverse"] if s0 <= a <= b <= s1]
    assert len(inside) == 2 and len(spans["repro.forward"]) == 2


# -- the compile counter ------------------------------------------------------

def test_plan_cache_stats_has_the_xla_section():
    from repro.core import plan_cache_stats
    from repro.core.plan import PlanEntry
    xla = plan_cache_stats()["xla"]
    assert set(xla) == {"traces", "compiles", "cache_loads", "compile_s"}
    assert not hasattr(PlanEntry(executable=None, build_time_s=0.0), "hits")


def test_counter_sees_first_call_compiles_and_none_after(cpu_mesh):
    from repro.core import plan_cache_stats
    from repro.core.api import plan_fft

    def delta(before):
        after = plan_cache_stats()["xla"]
        return {k: after[k] - before[k] for k in after}

    grid = (8, 24, 8)        # a plan no other test builds
    x = jnp.ones(grid, jnp.complex64)
    before = plan_cache_stats()["xla"]
    plan = plan_fft(cpu_mesh, grid)
    plan.forward(x, sharded_in=True).block_until_ready()
    first = delta(before)
    assert first["compiles"] >= 1 and first["traces"] >= 1
    assert first["compile_s"] > 0.0
    before = plan_cache_stats()["xla"]
    plan.forward(x, sharded_in=True).block_until_ready()
    assert delta(before) == {"traces": 0, "compiles": 0, "cache_loads": 0,
                             "compile_s": 0.0}


CACHE_SCRIPT = """
import json, sys, jax
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
import jax.numpy as jnp
from repro.compat import make_mesh
from repro.core import plan_cache_stats, plan_fft
before = plan_cache_stats()["xla"]
plan = plan_fft(make_mesh((1, 1), ("data", "model")), (8, 8, 8))
after = plan_cache_stats()["xla"]
print(json.dumps({k: after[k] - before[k] for k in after}))
"""


def test_persistent_cache_load_counts_as_a_load(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    runs = []
    for _ in range(2):
        res = subprocess.run([sys.executable, "-c", CACHE_SCRIPT,
                              str(tmp_path)], capture_output=True,
                             text=True, timeout=300, env=env)
        assert res.returncode == 0, res.stderr[-3000:]
        runs.append(json.loads(res.stdout.strip().splitlines()[-1]))
    cold, warm = runs
    assert cold["compiles"] >= 1 and cold["cache_loads"] == 0
    assert warm["cache_loads"] >= 1 and warm["compiles"] == 0
