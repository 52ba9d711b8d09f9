"""Plan autotuner: perfmodel pruning picks non-default plans, measured
winners never regress the static default, and tuning decisions persist
through the JSON cache across processes.

Mesh-dependent paths run in subprocesses on a fake 8-device (2x4) mesh
(see tests/README.md); the pruning model itself is pure math and runs
in-process.
"""
import json

import pytest

from conftest import run_subprocess
from repro.core.decomp import pencil_nd, slab_nd
from repro.core.perfmodel import (CPU_CORE, chunk_overlap_fraction,
                                  fft_stage_flops, matmul_stage_flops,
                                  predict_plan_time)
from repro.core.plan import TunedPlan, TuningCache, tuning_key

AXIS_SIZES = {"data": 2, "model": 4}


# ---------------------------------------------------------------------------
# Pruning model (pure, in-process)
# ---------------------------------------------------------------------------

def test_chunk_overlap_fraction():
    assert chunk_overlap_fraction(1) == 0.0
    assert chunk_overlap_fraction(2) == pytest.approx(0.5)
    assert chunk_overlap_fraction(8) == pytest.approx(7 / 8)


def test_matmul_backend_costs_more_flops():
    """Four-step matmul trades FLOPs for MXU shape: n*(n1+n2) >> 5*log2(n)."""
    grid = (64, 64, 64)
    assert matmul_stage_flops(grid, (0,)) > fft_stage_flops(grid, (0,))


def test_model_prefers_chunked_overlap_when_comm_bound():
    """The paper's overlap claim, in the model: on a comm-bound machine the
    chunked pipeline beats bulk-sync despite the extra alpha cost."""
    grid = (64, 64, 64)
    dec = pencil_nd(("data", "model"), 3)
    bulk = predict_plan_time(grid, dec, AXIS_SIZES, CPU_CORE, n_chunks=1)
    chunked = predict_plan_time(grid, dec, AXIS_SIZES, CPU_CORE, n_chunks=2)
    assert chunked["t_total_s"] < bulk["t_total_s"]


def test_model_prefers_slab_on_small_grid():
    """Fewer transposes win when the grid is small: slab (1 redistribution)
    is predicted faster than the default pencil (2) on (8, 8, 16)."""
    grid = (8, 8, 16)
    t_pencil = predict_plan_time(grid, pencil_nd(("data", "model"), 3),
                                 AXIS_SIZES, CPU_CORE)
    t_slab = predict_plan_time(grid, slab_nd("data", 3), AXIS_SIZES,
                               CPU_CORE)
    assert t_slab["t_total_s"] < t_pencil["t_total_s"]


def test_feasible_chunk_counts(cpu_mesh):
    from repro.core.decomp import make_decomposition
    from repro.core.pipeline import make_spec
    from repro.core.tuner import feasible_chunk_counts
    dec = make_decomposition("pencil", ("data", "model"), 3)
    spec = make_spec(cpu_mesh, (8, 8, 16), dec, ("fft",) * 3)
    counts = feasible_chunk_counts(spec, {"data": 1, "model": 1})
    # chunk dims are z (16) for the x<->y transpose and x (8) for y<->z:
    # powers of two dividing both.
    assert counts == [1, 2, 4, 8]
    assert feasible_chunk_counts(spec, {"data": 1, "model": 1},
                                 max_chunks=2) == [1, 2]


def test_feasible_chunk_counts_inverse_slab_bulk_only(cpu_mesh):
    """The fft-dims-aware chunk-dim choice leaves the inverse slab with no
    legal chunk dim (the hop touches dims 0 and 2, the next stage FFTs
    dims 0 and 1), so only the bulk path is feasible — the tuner must not
    propose chunk counts that would silently fall back."""
    import dataclasses
    from repro.core.decomp import make_decomposition
    from repro.core.pipeline import make_spec
    from repro.core.tuner import feasible_chunk_counts
    dec = make_decomposition("slab", ("model",), 3)
    fwd = make_spec(cpu_mesh, (8, 8, 16), dec, ("fft",) * 3)
    inv = dataclasses.replace(fwd, inverse=True)
    assert feasible_chunk_counts(fwd, {"data": 1, "model": 1}) == \
        [1, 2, 4, 8]
    assert feasible_chunk_counts(inv, {"data": 1, "model": 1}) == [1]


def test_enumerate_includes_hybrids_for_3d(cpu_mesh):
    """Acceptance: hybrid candidates ride alongside pencil/slab for 3-D."""
    from repro.core.tuner import enumerate_candidates
    cands = enumerate_candidates((8, 8, 16), cpu_mesh, ("fft",) * 3)
    by_kind = {}
    for c in cands:
        by_kind.setdefault(c.decomp, set()).add((c.mesh_axes, c.dim_groups))
    assert {"pencil", "slab", "hybrid"} <= set(by_kind)
    groups = {g for _, g in by_kind["hybrid"]}
    assert ((0, 1), (2,)) in groups     # the "2+1" hybrid
    assert ((0,), (1, 2)) in groups     # the "1+2" hybrid
    # no duplicate of the pencil structure (all singleton groups over the
    # 2-axis pool IS the pencil and is enumerated only there)
    assert ((0,), (1,), (2,)) not in groups


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_default_candidate_follows_the_default_rule(cpu_mesh, backend):
    """The tuner's never-regress baseline is the plan the static API
    builds: pencil, bulk, on the backend the default rule picks (xla on
    the CPU, the kernel on TPU devices where it takes the lines)."""
    from repro.core.tuner import _default_candidate, enumerate_candidates
    cands = enumerate_candidates((8, 8, 16), cpu_mesh, ("fft",) * 3)
    base = _default_candidate(cands, backend)
    assert (base.decomp, base.backend, base.n_chunks) == \
        ("pencil", backend, 1)


def test_enumerate_4d_on_2axis_mesh(cpu_mesh):
    """A 4-D grid on a 2-axis mesh has no pencil; slab + hybrids carry it."""
    from repro.core.tuner import enumerate_candidates
    cands = enumerate_candidates((4, 4, 8, 8), cpu_mesh, ("fft",) * 4)
    kinds = {c.decomp for c in cands}
    assert "pencil" not in kinds
    assert {"slab", "hybrid"} <= kinds
    assert any(c.dim_groups == ((0, 1), (2, 3)) for c in cands)


def test_predict_plan_time_prices_each_hop_at_own_count():
    """Acceptance: per-hop pricing — a schedule deepening only hop 0 adds
    only hop 0's extra alpha rounds/messages, and the schedule echoes back
    in the prediction."""
    grid = (8, 8, 16)
    dec = pencil_nd(("data", "model"), 3)
    base = predict_plan_time(grid, dec, AXIS_SIZES, CPU_CORE,
                             chunk_schedule=(1, 1))
    deep = predict_plan_time(grid, dec, AXIS_SIZES, CPU_CORE,
                             chunk_schedule=(8, 1))
    # hop 0 is over "data" (2 peers): 1 message per round, 8 rounds now
    assert deep["messages"] == base["messages"] + 7
    assert deep["chunk_schedule"] == (8, 1)
    assert deep["t_comm_s"] > base["t_comm_s"]   # alpha * k grew on hop 0
    with pytest.raises(ValueError, match="entries"):
        predict_plan_time(grid, dec, AXIS_SIZES, CPU_CORE,
                          chunk_schedule=(2,))


def test_feasible_hop_chunk_counts(cpu_mesh):
    from repro.core.decomp import make_decomposition
    from repro.core.pipeline import make_spec
    from repro.core.tuner import feasible_hop_chunk_counts
    dec = make_decomposition("pencil", ("data", "model"), 3)
    spec = make_spec(cpu_mesh, (8, 8, 16), dec, ("fft",) * 3)
    # hop 0 chunks z (16/4=4 on the 2x4 mesh), hop 1 chunks x (8/2=4):
    # per-hop counts, not the gcd-coupled uniform list.
    per_hop = feasible_hop_chunk_counts(spec, {"data": 2, "model": 4})
    assert per_hop == [[1, 2, 4], [1, 2, 4]]
    assert feasible_hop_chunk_counts(spec, {"data": 2, "model": 4},
                                     max_chunks=2) == [[1, 2], [1, 2]]
    # an inverse slab's single hop has no legal chunk dim: [1], not []
    import dataclasses
    slab = make_decomposition("slab", ("model",), 3)
    inv = dataclasses.replace(
        make_spec(cpu_mesh, (8, 8, 16), slab, ("fft",) * 3), inverse=True)
    assert feasible_hop_chunk_counts(inv, {"data": 1, "model": 1}) == [[1]]


def test_tuned_plan_dim_groups_json_roundtrip():
    hyb = _plan(decomp="hybrid", dim_groups=((0, 1), (2, 3)))
    assert TunedPlan.from_json(hyb.to_json()) == hyb
    assert "hybrid[2+2]" in hyb.describe()
    # pencil/slab plans (and pre-hybrid wisdom entries) stay None
    plain = _plan()
    assert "dim_groups" not in plain.to_json()
    assert TunedPlan.from_json(plain.to_json()).dim_groups is None


def test_tuned_plan_chunk_schedule_json_roundtrip():
    """Per-hop schedules persist through the wisdom cache; pre-schedule
    int-valued entries (no ``chunk_schedule`` key) read back as uniform."""
    het = _plan(chunk_schedule=(4, 2))
    assert het.to_json()["chunk_schedule"] == [4, 2]
    assert TunedPlan.from_json(het.to_json()) == het
    assert "chunks=4,2" in het.describe()
    legacy = _plan().to_json()
    assert "chunk_schedule" not in legacy          # old-format entry
    assert TunedPlan.from_json(legacy).chunk_schedule is None
    # the joint-measurement objective round-trips, defaults stay implicit
    joint = _plan(objective="fwd+scale+inv")
    assert joint.to_json()["objective"] == "fwd+scale+inv"
    assert TunedPlan.from_json(joint.to_json()) == joint
    assert "objective" not in _plan().to_json()
    assert TunedPlan.from_json(_plan().to_json()).objective == "forward"


# ---------------------------------------------------------------------------
# Persistent tuning cache (pure, in-process)
# ---------------------------------------------------------------------------

def _plan(**kw):
    base = dict(decomp="slab", mesh_axes=("data",), backend="xla",
                n_chunks=2, predicted_s=1e-4, measured_s=2e-4,
                source="measured", baseline_s=3e-4)
    base.update(kw)
    return TunedPlan(**base)


def _no_ts(plan):
    """Compare plans modulo the save timestamp put() stamps on them."""
    import dataclasses
    return dataclasses.replace(plan, ts=0.0)


def test_tuning_cache_disk_roundtrip(tmp_path):
    path = str(tmp_path / "tuning.json")
    key = tuning_key(grid=(8, 8, 16), mesh_shape=(2, 4),
                     mesh_axes=("data", "model"), kinds=("fft",) * 3,
                     dtype="complex64", inverse=False)
    cache = TuningCache(path)
    assert cache.get(key) is None
    cache.put(key, _plan())
    # A fresh instance (fresh process analogue) must see the same plan.
    cache2 = TuningCache(path)
    assert _no_ts(cache2.get(key)) == _plan()
    assert cache2.stats()["hits"] == 1


def test_tuning_cache_survives_corrupt_file(tmp_path):
    path = str(tmp_path / "tuning.json")
    with open(path, "w") as f:
        f.write("{not json")
    cache = TuningCache(path)  # must not raise
    assert len(cache) == 0
    cache.put("k", _plan())
    assert _no_ts(TuningCache(path).get("k")) == _plan()


def test_tuning_cache_rejects_stale_schema(tmp_path):
    path = str(tmp_path / "tuning.json")
    with open(path, "w") as f:
        json.dump({"version": 999, "plans": {"k": {"bogus": 1}}}, f)
    assert len(TuningCache(path)) == 0


def test_tuning_cache_cross_process_merge(tmp_path):
    """Two processes tuning different problems against one wisdom file must
    both keep their plans: every save re-reads and merges under the file
    lock instead of last-writer-wins."""
    path = str(tmp_path / "tuning.json")
    # Both "processes" open the file before either has written anything.
    c1 = TuningCache(path)
    c2 = TuningCache(path)
    c1.put("problem_a", _plan(decomp="pencil"))
    c2.put("problem_b", _plan(decomp="slab"))   # must not erase problem_a
    fresh = TuningCache(path)
    assert _no_ts(fresh.get("problem_a")) == _plan(decomp="pencil")
    assert _no_ts(fresh.get("problem_b")) == _plan(decomp="slab")


def test_tuning_cache_merge_newest_ts_wins(tmp_path):
    """Same key from two processes: the most recently measured plan wins,
    in both directions (disk newer than memory and vice versa)."""
    path = str(tmp_path / "tuning.json")
    c1 = TuningCache(path)
    c2 = TuningCache(path)
    c1.put("k", _plan(n_chunks=1, ts=100.0))
    c2.put("k", _plan(n_chunks=2, ts=200.0))      # newer: replaces
    assert TuningCache(path).get("k").n_chunks == 2
    c1.put("k", _plan(n_chunks=4, ts=50.0))       # older: disk copy kept
    assert TuningCache(path).get("k").n_chunks == 2


def test_tuning_cache_put_stamps_unstamped_plans(tmp_path):
    """A directly-constructed plan (ts=0.0) written over an existing newer
    entry must still win: put() stamps it with the save time, so the write
    is never a silent no-op."""
    path = str(tmp_path / "tuning.json")
    c = TuningCache(path)
    c.put("k", _plan(n_chunks=2, ts=100.0))
    c.put("k", _plan(n_chunks=8))                 # no ts: stamped at put
    got = TuningCache(path).get("k")
    assert got.n_chunks == 8
    assert got.ts > 100.0


def test_tuning_cache_machine_section_roundtrip(tmp_path):
    """The "machine" section persists alongside plans and survives merges."""
    path = str(tmp_path / "tuning.json")
    c1 = TuningCache(path)
    c1.put_machine("cpu", {"mem_bw": 1.0})
    c2 = TuningCache(path)
    c2.put("k", _plan())                           # plan write must keep it
    fresh = TuningCache(path)
    assert fresh.get_machine("cpu")["mem_bw"] == 1.0
    assert _no_ts(fresh.get("k")) == _plan()
    assert fresh.stats()["machines"] == 1


def test_tuning_cache_machine_merge_newest_save_wins(tmp_path):
    """A process holding a stale profile must not clobber a fresher one
    (e.g. a network-upgraded calibration) when it later saves a plan."""
    path = str(tmp_path / "tuning.json")
    c_stale = TuningCache(path)
    c_stale.put_machine("cpu", {"gen": 1, "_saved_ts": 100.0})
    c_fresh = TuningCache(path)
    c_fresh.put_machine("cpu", {"gen": 2, "_saved_ts": 200.0})
    c_stale.put("k", _plan())                      # unrelated plan save
    assert TuningCache(path).get_machine("cpu")["gen"] == 2


def test_tuning_key_separates_problems():
    k1 = tuning_key(grid=(8, 8, 16), mesh_shape=(2, 4),
                    mesh_axes=("data", "model"), kinds=("fft",) * 3,
                    dtype="complex64", inverse=False)
    k2 = tuning_key(grid=(8, 8, 16), mesh_shape=(2, 4),
                    mesh_axes=("data", "model"), kinds=("fft",) * 3,
                    dtype="complex64", inverse=True)
    k3 = tuning_key(grid=(8, 8, 16), mesh_shape=(2, 4),
                    mesh_axes=("data", "model"), kinds=("fft",) * 3,
                    dtype="complex64", inverse=False, batch_shape=(4,))
    assert len({k1, k2, k3}) == 3


def test_synth_input_realistic(cpu_mesh):
    """Measurement inputs: genuinely complex for C2C (an all-zero imaginary
    plane is XLA-constant-foldable), correctly real for rfft pipelines."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core.decomp import make_decomposition
    from repro.core.pipeline import input_struct, make_spec
    from repro.core.tuner import synth_input

    dec = make_decomposition("pencil", ("data", "model"), 3)
    spec_c = make_spec(cpu_mesh, (8, 8, 16), dec, ("fft",) * 3)
    arg_c = input_struct(cpu_mesh, spec_c, (), jnp.complex64)
    x = synth_input(arg_c)
    assert x.dtype == jnp.complex64
    assert float(np.min(np.abs(np.imag(np.asarray(x))))) > 0.0

    spec_r = make_spec(cpu_mesh, (8, 8, 16), dec, ("rfft", "fft", "fft"))
    arg_r = input_struct(cpu_mesh, spec_r, (), jnp.complex64)
    y = synth_input(arg_r)
    assert y.dtype == jnp.float32          # rfft pipeline takes real input


# ---------------------------------------------------------------------------
# End-to-end tuning on the fake 8-device mesh (subprocess)
# ---------------------------------------------------------------------------

TUNE_COMMON = """
import os, tempfile, numpy as np, jax, jax.numpy as jnp
# Isolate from any ambient user wisdom: heuristic tuning reads the global
# cache for a calibrated machine profile, so tests pin it to a tmpdir.
os.environ["REPRO_TUNING_CACHE"] = os.path.join(tempfile.mkdtemp(),
                                                "global.json")
from repro.compat import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
from repro.core import TuningCache, tune
path = os.path.join(tempfile.mkdtemp(), "tuning.json")
"""


def test_tune_measured_winner_not_worse_than_default():
    """Acceptance: the tuned plan's measured wall time is <= the static
    n_chunks=1 pencil default, measured in the same run (baseline_s)."""
    out = run_subprocess(TUNE_COMMON + """
plan = tune((8, 8, 16), mesh, cache=TuningCache(path), top_k=3)
print("source", plan.source)
print("winner_le_default", int(plan.measured_s <= plan.baseline_s))
print("measured_pos", int(plan.measured_s > 0))
print("baseline_pos", int(plan.baseline_s > 0))
""")
    vals = dict(l.split() for l in out.strip().splitlines())
    assert vals["source"] == "measured"
    assert vals["measured_pos"] == "1" and vals["baseline_pos"] == "1"
    assert vals["winner_le_default"] == "1"


def test_tune_persistent_cache_hit_on_second_call():
    out = run_subprocess(TUNE_COMMON + """
c1 = TuningCache(path)
p1 = tune((8, 8, 16), mesh, cache=c1)
# Fresh cache object = fresh-process analogue: must load p1 from disk and
# return it without re-measuring.
c2 = TuningCache(path)
p2 = tune((8, 8, 16), mesh, cache=c2)
print("same_plan", int(p1 == p2))
print("hit", c2.stats()["hits"])
print("ondisk", int(os.path.exists(path)))
""")
    vals = dict(l.split() for l in out.strip().splitlines())
    assert vals["same_plan"] == "1"
    assert int(vals["hit"]) == 1
    assert vals["ondisk"] == "1"


def test_heuristic_picks_non_default_plan_on_imbalanced_case():
    """On (8, 8, 16) over a (2, 4) mesh the model-only tuner already walks
    away from the static default (pencil/xla/1): one transpose beats two."""
    out = run_subprocess(TUNE_COMMON + """
plan = tune((8, 8, 16), mesh, mode="heuristic")
print("decomp", plan.decomp)
print("nondefault", int((plan.decomp, plan.backend, plan.n_chunks)
                        != ("pencil", "xla", 1)))
""")
    vals = dict(l.split() for l in out.strip().splitlines())
    assert vals["nondefault"] == "1"
    assert vals["decomp"] == "slab"


def test_auto_tune_passes_over_plans_that_do_not_fit_device_memory():
    """Auto tuning compiles a candidate before it runs it and passes over
    any whose arguments, output and temporaries exceed a device's memory
    limit; with no plan that fits, it says so instead of running one."""
    out = run_subprocess(TUNE_COMMON + """
import repro.core.tuner as T
from repro.core.perfmodel import CPU_CORE
cands = T.enumerate_candidates((8, 8, 16), mesh, ("fft",) * 3)
base = T._default_candidate(cands)
def footprint(cand):
    spec = T._spec_for(mesh, (8, 8, 16), cand.decomp, cand.mesh_axes,
                       ("fft",) * 3, cand.backend, cand.spec_chunks, False, 0,
                       dim_groups=cand.dim_groups)
    m = T.compile_pipeline(mesh, spec, dtype=jnp.complex64).memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)
limit = footprint(base)
T.device_memory_limit = lambda mesh: limit
plan = T.tune((8, 8, 16), mesh, cache=TuningCache(), machine=CPU_CORE,
              repeats=1)
won = T.Candidate(decomp=plan.decomp, mesh_axes=plan.mesh_axes,
                  backend=plan.backend, n_chunks=plan.n_chunks,
                  dim_groups=plan.dim_groups,
                  chunk_schedule=plan.chunk_schedule)
print("winner_fits", int(footprint(won) <= limit))
T.device_memory_limit = lambda mesh: 1
try:
    T.tune((8, 8, 16), mesh, cache=TuningCache(), machine=CPU_CORE)
    print("refused", 0)
except ValueError as e:
    print("refused", int("fits the 1 bytes" in str(e)))
""")
    vals = dict(l.split() for l in out.strip().splitlines())
    assert vals["winner_fits"] == "1"
    assert vals["refused"] == "1"


def test_model_only_chunk_choice_is_capped():
    """The cost model cannot see memory, so heuristic tuning and
    ``propose_chunk_schedule`` stop at MODEL_MAX_CHUNKS per hop even where
    the model would chunk deeper."""
    out = run_subprocess(TUNE_COMMON + """
import repro.core.tuner as T
from repro.core.decomp import make_decomposition
from repro.core.perfmodel import TPU_V5E
from repro.core.pipeline import make_spec
grid = (1024, 1024, 1024)
spec = make_spec(mesh, grid, make_decomposition("pencil", ("data", "model"), 3),
                 ("fft",) * 3, backend="xla", n_chunks=1)
sizes = {"data": 2, "model": 4}
print("uncapped", max(T.propose_chunk_schedule(spec, sizes, TPU_V5E,
                                               max_chunks=1024)))
print("proposed", max(T.propose_chunk_schedule(spec, sizes, TPU_V5E)))
plan = T.tune(grid, mesh, mode="heuristic", machine=TPU_V5E)
print("heuristic", max(plan.chunk_schedule or (plan.n_chunks,)))
""")
    vals = {k: int(v) for k, v in
            (l.split() for l in out.strip().splitlines())}
    from repro.core.tuner import MODEL_MAX_CHUNKS
    assert vals["uncapped"] > MODEL_MAX_CHUNKS
    assert vals["proposed"] == MODEL_MAX_CHUNKS
    assert vals["heuristic"] <= MODEL_MAX_CHUNKS


def test_restricted_tune_does_not_poison_cache():
    """Acceptance: a restricted search (backends subset / chunk cap) must
    never persist its winner under the unrestricted key, so a later
    unrestricted caller is never served the restricted plan."""
    out = run_subprocess(TUNE_COMMON + """
grid = (8, 8, 16)
p_r = tune(grid, mesh, cache=TuningCache(path), backends=("matmul",),
           top_k=2, repeats=1)
print("restricted_backend", p_r.backend)
print("persisted_after_restricted", len(TuningCache(path)))
c2 = TuningCache(path)
p_u = tune(grid, mesh, cache=c2, top_k=2, repeats=1)
# the unrestricted call re-tuned over the full space (cache had no plan),
# it did not inherit the restricted winner from disk
print("unrestricted_source", p_u.source)
print("unrestricted_measured_baseline", int(p_u.baseline_s > 0))
print("persisted_after_unrestricted", len(TuningCache(path)))
# chunk caps are restrictions too
p_c = tune((16, 16, 16), mesh, cache=TuningCache(path), max_chunks=1,
           top_k=1, repeats=1)
import json
plans = json.load(open(path))["plans"]
print("capped_persisted", int(any("16,16,16" in k for k in plans)))
""")
    vals = dict(l.split() for l in out.strip().splitlines())
    assert vals["persisted_after_restricted"] == "0"
    assert vals["unrestricted_source"] == "measured"
    assert vals["unrestricted_measured_baseline"] == "1"
    assert vals["persisted_after_unrestricted"] == "1"
    assert vals["capped_persisted"] == "0"


def test_auto_tune_persists_calibrated_machine_profile():
    """mode="auto" calibrates on first use and stores the profile in the
    wisdom file's "machine" section; heuristic calls can then load it."""
    out = run_subprocess(TUNE_COMMON + """
import json
from repro.core.perfmodel import MachineProfile
from repro.core.tuner import resolve_profile
tune((8, 8, 16), mesh, cache=TuningCache(path), top_k=1, repeats=1)
raw = json.load(open(path))
print("has_machine", int("cpu" in raw.get("machine", {})))
prof = resolve_profile(TuningCache(path), allow_calibrate=False)
print("loaded_calibrated", int(prof.calibrated))
print("platform", prof.platform)
""")
    vals = dict(l.split() for l in out.strip().splitlines())
    assert vals["has_machine"] == "1"
    assert vals["loaded_calibrated"] == "1"
    assert vals["platform"] == "cpu"


def test_tune_4d_hybrid_space_and_wisdom_roundtrip():
    """Auto-tuning a 4-D problem on the 2-axis mesh searches the hybrid
    space (pencil cannot exist there) and the winner — dim_groups included
    — survives the wisdom-file round trip."""
    out = run_subprocess(TUNE_COMMON + """
import warnings
warnings.simplefilter("ignore")
grid = (4, 4, 8, 8)
p1 = tune(grid, mesh, cache=TuningCache(path), top_k=2, repeats=1)
print("source", p1.source)
c2 = TuningCache(path)
p2 = tune(grid, mesh, cache=c2, top_k=2, repeats=1)
print("same_plan", int(p1 == p2))
print("hit", c2.stats()["hits"])
from repro.core.tuner import enumerate_candidates
cands = enumerate_candidates(grid, mesh, ("fft",)*4)
print("has_hybrid", int(any(c.decomp == "hybrid" for c in cands)))
print("has_pencil", int(any(c.decomp == "pencil" for c in cands)))
""")
    vals = dict(l.split() for l in out.strip().splitlines())
    assert vals["source"] == "measured"
    assert vals["same_plan"] == "1"
    assert int(vals["hit"]) == 1
    assert vals["has_hybrid"] == "1"
    assert vals["has_pencil"] == "0"


def test_tune_4d_asymmetric_persists_heterogeneous_schedule():
    """Tentpole acceptance: on a multi-hop 4-D hybrid whose hops have very
    different communication costs (a calibrated profile with a slow "data"
    link and a fast "model" link), the scheduler policy engine proposes a
    per-hop schedule with *differing* entries, the tuner ranks it best,
    tune() persists it through the wisdom cache (round-tripping the
    schedule), old int-valued wisdom entries still read, and the winning
    heterogeneous plan round-trips numerically.

    Measurement is deterministic: the "hardware" is the per-hop cost model
    itself (measure_candidate is replaced by the ranked prediction), the
    same fake-clock philosophy the calibration tests use.
    """
    out = run_subprocess(TUNE_COMMON + """
import json, warnings
warnings.simplefilter("ignore")
import repro.core.tuner as T
from repro.core.perfmodel import CPU_CORE, MachineProfile
from repro.core.plan import tuning_key

grid = (4, 4, 32, 4)
kinds = ("fft",) * 4
# Asymmetric calibrated network: "data" all_to_alls are ~100x more
# expensive per byte than "model" ones, compute is slow enough to hide
# comm under (the chunked-overlap regime).
prof = MachineProfile(base=CPU_CORE, platform="cpu", calibrated=True,
                      net_calibrated=True,
                      backend_flops=(("matmul", 1e4), ("xla", 3e5)),
                      kind_scale=(("c2c", 1.0),), mem_bw=1e12,
                      net_alpha_s=(("data", 3e-5), ("model", 1e-7)),
                      net_bw=(("data", 1e6), ("model", 1e8)))

cands = T.enumerate_candidates(grid, mesh, kinds, machine=prof)
het = [c for c in cands if c.chunk_schedule is not None]
print("hetero_enumerated", int(len(het) > 0))
print("hetero_all_differ",
      int(all(len(set(c.chunk_schedule)) > 1 for c in het)))
ranked = T.rank_candidates(cands, grid, mesh, prof, kinds=kinds)
print("argmin_hetero", int(ranked[0][1].chunk_schedule is not None))

def fake_measure(cand, grid, mesh, kinds, dtype, **kw):
    return T.rank_candidates([cand], grid, mesh, prof, 8,
                             kinds=kinds)[0][0]
T.measure_candidate = fake_measure
plan = T.tune(grid, mesh, kinds=kinds, machine=prof,
              cache=TuningCache(path), top_k=4)
print("source", plan.source)
print("winner_hetero", int(plan.chunk_schedule is not None
                           and len(set(plan.chunk_schedule)) > 1))

key = tuning_key(grid=grid, mesh_shape=(2, 4),
                 mesh_axes=("data", "model"), kinds=kinds,
                 dtype="complex64", inverse=False,
                 platform=jax.default_backend())
fresh = TuningCache(path)
got = fresh.get(key)
print("persisted", int(got is not None
                       and got.chunk_schedule == plan.chunk_schedule))
raw = json.load(open(path))
print("json_list", int(isinstance(raw["plans"][key]["chunk_schedule"],
                                  list)))
# backward-compatible read of a pre-schedule (int-only) entry
raw["plans"][key].pop("chunk_schedule")
with open(path, "w") as f:
    json.dump(raw, f)
old = TuningCache(path).get(key)
print("legacy_read", int(old is not None and old.chunk_schedule is None))

# the heterogeneous winner round-trips numerically
from repro.core import plan_fft
p = plan_fft(mesh, grid, kinds=kinds, decomp=plan.decomp,
             mesh_axes=plan.mesh_axes, dim_groups=plan.dim_groups,
             n_chunks=plan.chunk_schedule)
rng = np.random.default_rng(0)
x4 = (rng.standard_normal(grid)
      + 1j*rng.standard_normal(grid)).astype(np.complex64)
y = p(jnp.asarray(x4))
ref4 = np.fft.fftn(x4)
print("fwd", float(np.max(np.abs(np.asarray(y) - ref4))
                   / np.max(np.abs(ref4))))
xb = p.inverse(y)
print("rt", float(np.max(np.abs(np.asarray(xb) - x4))))
""")
    vals = dict(l.split() for l in out.strip().splitlines())
    assert vals["hetero_enumerated"] == "1"
    assert vals["hetero_all_differ"] == "1"
    assert vals["argmin_hetero"] == "1"
    assert vals["source"] == "measured"
    assert vals["winner_hetero"] == "1"
    assert vals["persisted"] == "1"
    assert vals["json_list"] == "1"
    assert vals["legacy_read"] == "1"
    assert float(vals["fwd"]) < 1e-5
    assert float(vals["rt"]) < 1e-5


def test_fft3d_tuning_auto_matches_numpy():
    """tuning="auto" must stay numerically identical to the default path."""
    out = run_subprocess(TUNE_COMMON + """
from repro.core import fft3d, GLOBAL_PLAN_CACHE
rng = np.random.default_rng(0)
x = (rng.standard_normal((8, 8, 16))
     + 1j*rng.standard_normal((8, 8, 16))).astype(np.complex64)
y = fft3d(jnp.asarray(x), mesh=mesh, tuning="auto",
          tune_cache=TuningCache(path))
ref = np.fft.fftn(x)
print("err", float(np.max(np.abs(np.asarray(y) - ref)) / np.max(np.abs(ref))))
print("plans", GLOBAL_PLAN_CACHE.stats()["plans"])
""")
    vals = dict(l.split() for l in out.strip().splitlines())
    assert float(vals["err"]) < 1e-5
    assert int(vals["plans"]) >= 1   # measurement warmed the plan cache
