"""Device time by program scope (``scopes.py``, ``op_scopes.json``): the
buckets of op names as a v5e trace shows them, their partition of the busy
op time, the four readers, and the program's own compiled op names."""
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

from chipbench import catalog, harness, scopes
from chipbench import tracereduce as tr

RULES = tr.load_rules()
SCOPES = scopes.load_rules()
BENCH = catalog.load_benchmark()
ROOT = Path(__file__).resolve().parents[2]
MS = 1e6  # ns
READERS = {"stage_ms": "stage", "plan_glue_ms": "plan",
           "entry_ops_ms": "entry"}


def op(dev, name, tf_op, start_ms, end_ms, stats=None):
    stats = dict(stats or {}, tf_op=tf_op)
    return tr.Op(dev, f"{name} {tf_op}" if tf_op else name, start_ms * MS,
                 end_ms * MS, tr.classify(name, stats, RULES))


def span(name, start_ms, end_ms):
    return tr.Span(name, start_ms * MS, end_ms * MS)


# tf_op as the v5e traces of the three cells show it.
@pytest.mark.parametrize("tf_op,bucket", [
    ("jit(repro_fft_forward)/stage0/jit(fft):", "stage"),
    ("jit(repro_fft_inverse)/stage2/transpose:", "stage"),
    ("jit(repro_fft_forward)/stage2/complex:", "stage"),
    ("jit(repro_fft_forward)/shard_map/stage1/jit(fft):", "stage"),
    ("jit(repro_fft_forward)/shard_map/hop1/all_to_all:", "hop"),
    ("jit(repro_fft_inverse_seg1)/shard_map/hop0/concatenate:", "hop"),
    ("jit(repro_fft_forward)/reshape:", "plan"),
    ("repro_fft_in:", "plan"),
    ("x:", "entry"),
    ("jit(true_divide)/div:", "entry"),
    ("jit(scatter)/scatter:", "entry"),
    ("args[0]:", "entry"),
    ("x1:", "entry"),
    ("val:", "entry"),
    ("", "entry"),
    ("jit(run)/jit(fft):", "entry"),
])
def test_bucket(tf_op, bucket):
    assert scopes.bucket(tf_op, SCOPES) == bucket


def two_chip_step():
    # Per chip: stage 0..30, hop 30..50, plan 50..55, entry 60..70 (ms);
    # chip 1 has no entry op.
    ops = []
    for dev in (0, 1):
        ops += [op(dev, "fusion.1", "jit(repro_fft_forward)/stage0/jit(fft):",
                   0, 30),
                op(dev, "all-to-all.2", "jit(repro_fft_forward)/hop0/"
                   "all_to_all:", 30, 50, {"hlo_category": "all-to-all"}),
                op(dev, "custom-call", "repro_fft_in:", 50, 55)]
    ops.append(op(0, "custom-call.4", "jit(true_divide)/div:", 60, 70))
    return ops


def test_buckets_partition_the_busy_op_time():
    red = tr.reduce(two_chip_step(), [span("window", 0, 100)])
    got = scopes.scope_s(red, SCOPES)
    assert got == pytest.approx({"stage": 0.030, "hop": 0.020,
                                 "plan": 0.005, "entry": 0.005})
    assert sum(got.values()) == pytest.approx(sum(red.class_s.values()))


def test_a_program_without_names_has_no_buckets():
    ops = [op(0, "fusion.1", "jit(run)/jit(fft):", 0, 30),
           op(0, "custom-call", "x:", 30, 35)]
    assert scopes.scope_s(tr.reduce(ops, []), SCOPES) is None


def run_of(red, steps=2):
    return harness.Run(setup_s=1.0, window_s=0.1, step_s=[0.05] * steps,
                       dispatch_s=[0.001] * steps, peak_bytes=None,
                       bytes_per_step=0.0, flops_per_step=0.0, peak=None,
                       trace=red)


@pytest.mark.parametrize("metric,bucket", sorted(READERS.items()))
def test_readers(metric, bucket):
    read = catalog.module("metrics", metric).read
    red = tr.reduce(two_chip_step(), [span("window", 0, 100)])
    expect = {"stage": 15.0, "hop": 10.0, "plan": 2.5, "entry": 2.5}[bucket]
    assert read(run_of(red)) == pytest.approx(expect)
    assert read(run_of(None)) is None
    parent = tr.reduce([op(0, "fusion.1", "jit(run)/jit(fft):", 0, 30)], [])
    assert read(run_of(parent)) is None
    # A bucket with no op reads 0 where the program names its executables.
    named = tr.reduce([op(0, "fusion.1", "jit(repro_fft_forward)/reshape:",
                          0, 30)], [])
    assert read(run_of(named)) == (pytest.approx(15.0) if bucket == "plan"
                                   else 0.0)


def test_each_reader_is_listed_where_it_reads():
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(entries["stage_ms"]["workloads"]) == cells
    assert set(entries["plan_glue_ms"]["workloads"]) == cells
    assert entries["entry_ops_ms"]["workloads"] == ["ppb512.timestep"]
    for name in READERS:
        assert entries[name]["source"] == "device_trace"
        assert entries[name]["moves"] == "step_ms"


@pytest.mark.parametrize("extra", [
    [span("repro.forward", 1, 9)],
    [span("repro.solve", 0, 49), span("repro.forward", 1, 9),
     span("repro.solve.scale", 9, 12), span("repro.inverse", 12, 20)],
])
def test_program_spans_change_only_gap_labels(extra):
    ops = two_chip_step()
    base = [span("window", 0, 100), span("step", 0, 50),
            span("dispatch", 0, 20), span("block", 20, 50)]
    plain, spanned = tr.reduce(ops, base), tr.reduce(ops, base + extra)
    for field in ("devices", "window_s", "busy_s", "class_s",
                  "exposed_a2a_s", "op_s", "op_count"):
        assert getattr(spanned, field) == getattr(plain, field), field
    assert sorted(g[1] for g in spanned.gaps) == sorted(
        g[1] for g in plain.gaps)


def test_innermost_program_span_labels_a_gap():
    spans = [span("step", 0, 50), span("dispatch", 0, 20),
             span("repro.solve", 0, 19), span("repro.solve.scale", 9, 12)]
    assert tr.label([10 * MS, 11 * MS], spans) == "repro.solve.scale"
    assert tr.label([13 * MS, 14 * MS], spans) == "repro.solve"


# -- the program's own op names ------------------------------------------------

PROGRAM_SCRIPT = """
import json, re
from repro.compat import make_mesh
from repro.core.api import PoissonSolver, plan_fft
n = {devices}
mesh = make_mesh((2, 2) if n == 4 else (1, 1), ("data", "model"))
plans = [plan_fft(mesh, (16, 16, 16)), plan_fft(mesh, (16, 16, 16), n_chunks=2),
         PoissonSolver(mesh, (16, 16, 16),
                       topology=("periodic", "periodic", "bounded")).plan]
ops = []
for plan in plans:
    for inverse in (False, True):
        text = plan._executable(inverse=inverse, donate=False).as_text()
        entry = False
        for line in text.splitlines():
            if line[:1] not in ("", " "):
                entry = line.startswith("ENTRY")
            m = re.search(r'op_name="([^"]*)"', line)
            code = re.search(r"\\s([a-z][a-z0-9-]*)\\(", line.split("=", 1)[-1])
            if m and "=" in line and code and (
                    entry or code.group(1) != "parameter"):
                ops.append([code.group(1), m.group(1)])
print(json.dumps(ops))
"""


@pytest.mark.parametrize("devices", [1, 4])
def test_the_programs_op_names_fall_in_scope_buckets(devices):
    # As the trace's tf_op: the op name, then ':'.  A fused op may join the
    # names of its parts with ';'.
    import json
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=f"{ROOT}{os.pathsep}{ROOT / 'src'}")
    res = subprocess.run([sys.executable, "-c",
                          PROGRAM_SCRIPT.format(devices=devices)],
                         capture_output=True, text=True, timeout=600,
                         env=env, cwd=ROOT)
    assert res.returncode == 0, res.stderr[-3000:]
    ops = json.loads(res.stdout.strip().splitlines()[-1])
    got = {}
    for code, name in ops:
        got.setdefault(code, set()).add(
            scopes.bucket(f"{name.split(';')[0]}:", SCOPES))
    assert got["fft"] == {"stage"}
    assert got.get("all-to-all", {"hop"}) == {"hop"}
    assert ("all-to-all" in got) == (devices == 4)
    assert "entry" not in set().union(*got.values())
    assert all(re.match(r"^jit\(repro_fft_(forward|inverse)\)/", name)
               or name == "repro_fft_in" for _, name in ops)


@pytest.mark.parametrize("fn", ["abs", "sqrt"])
def test_eager_ops_of_other_programs_fall_in_entry(fn):
    # Their parameter is named x too: only the plan body's own name puts a
    # parameter's ops in ``plan``.
    import jax
    import jax.numpy as jnp
    text = jax.jit(getattr(jnp, fn)).lower(
        jnp.ones(8, jnp.float32)).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    assert f"jit({fn})/{fn}" in names and "x" in names
    assert {scopes.bucket(f"{n}:", SCOPES) for n in names} == {"entry"}
