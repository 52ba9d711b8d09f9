"""``BENCHMARK.json`` and the files it names: discovery by name, the peak
table, the nominal byte count and the metric readers."""
import json
import math
import re
import textwrap

import pytest

from chipbench import catalog, harness

BENCH = catalog.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_cell_finds_its_files():
    for w in BENCH["workloads"]:
        cfg = catalog.config(BENCH, w["config"])
        assert cfg["name"] == w["config"]
        assert math.prod(cfg["mesh"]["shape"]) == w["chips"]
        catalog.module("entries", cfg["entry"])
        catalog.module("references", cfg["reference"])
        traffic = catalog.part("traffic", w["traffic"])
        assert set(traffic["ops"]) and traffic["fields"] >= 1
        assert catalog.part("limits", w["name"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert callable(catalog.module("metrics", m["name"]).read)


def test_names_and_metric_keys_keep_to_the_contract():
    named = BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"] \
        + BENCH["per_layer"]
    for entry in named:
        assert NAME.match(entry["name"]), entry["name"]
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for c in BENCH["configs"]:
        assert c["reduced"] == catalog.load_json(
            catalog.HERE.parent / c["file"])["reduced"]


def test_metrics_for_follows_workloads_lists():
    a2a_only = {"c2c1024x4.roundtrip"}
    for w in BENCH["workloads"]:
        names = {m["name"] for m in catalog.metrics_for(BENCH, w["name"],
                                                        trace=True)}
        assert ("a2a_ms" in names) == (w["name"] in a2a_only)
        assert {"dispatch_ms", "local_fft_ms"} <= names
        e2e = {m["name"] for m in catalog.metrics_for(BENCH, w["name"],
                                                      trace=False)}
        assert e2e == {"setup_s", "step_ms", "step_p90_ms", "peak_hbm_gib"}


def test_new_config_traffic_and_metric_are_found_by_name(tmp_path):
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic").mkdir()
    (tmp_path / "metrics").mkdir()
    (tmp_path / "configs" / "tiny.json").write_text(json.dumps(
        {"name": "tiny", "grid": [8, 8, 8]}))
    (tmp_path / "traffic" / "burst.json").write_text(json.dumps(
        {"ops": ["forward"], "fields": 3, "chain": False}))
    (tmp_path / "metrics" / "twice_steps.py").write_text(textwrap.dedent("""
        def read(run):
            return 2 * run.steps
    """))
    bench = {"configs": [{"name": "tiny",
                          "file": f"{tmp_path.name}/configs/tiny.json"}],
             "workloads": [{"name": "tiny.burst", "config": "tiny",
                            "traffic": "burst", "chips": 1}]}
    w = catalog.cell(bench, "tiny.burst")
    assert catalog.config(bench, w["config"], base=tmp_path)["grid"] == [8] * 3
    assert catalog.part("traffic", w["traffic"], base=tmp_path)["fields"] == 3
    reader = catalog.module("metrics", "twice_steps", base=tmp_path)
    assert reader.read(_run(step_s=[0.1] * 4)) == 8
    with pytest.raises(KeyError):
        catalog.cell(bench, "tiny.other")
    with pytest.raises(FileNotFoundError):
        catalog.module("metrics", "absent", base=tmp_path)


def test_unknown_device_kind_is_refused():
    assert harness.peak_row("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="TPU v99"):
        harness.peak_row("TPU v99")


def test_nominal_bytes_do_not_depend_on_the_backend():
    import jax

    from repro.core.api import plan_fft

    cpu_mesh_1 = harness.make_mesh({"shape": [1, 1], "axes": ["data", "model"]},
                                   jax.devices()[:1])
    traffic = catalog.part("traffic", "roundtrip")
    counts = set()
    for backend in ("xla", "matmul", "pallas"):
        plan = plan_fft(cpu_mesh_1, (16, 16, 16), backend=backend)
        system = harness.System(
            ops={}, io={"forward": (plan.in_struct, plan.out_struct),
                        "inverse": (plan.inv_in_struct, plan.inv_out_struct)},
            flops={"forward": 1.0, "inverse": 1.0}, field_dtype=None,
            field_sharding=None, mesh=cpu_mesh_1)
        counts.add(harness.nominal(system, traffic, chips=1))
    assert counts == {(2 * 8 * 16**3, 1.0)}


def test_line_flops():
    assert harness.line_flops((8, 8, 8), ("fft",) * 3) == 5 * 512 * 9
    assert harness.line_flops((8, 8, 8), ("fft", "fft", "dct2")) == \
        5 * 512 * 6 + 2.5 * 512 * 3


def _run(**kw):
    base = dict(setup_s=1.0, window_s=1.0, step_s=[0.5, 0.5],
                dispatch_s=[0.001, 0.003], peak_bytes=2**31,
                bytes_per_step=819e6, flops_per_step=1.0,
                peak={"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12})
    base.update(kw)
    return harness.Run(**base)


def test_end_to_end_readers():
    walls = [0.010] * 9 + [0.020]
    run = _run(window_s=0.2, step_s=walls)
    read = {m["name"]: catalog.module("metrics", m["name"]).read(run)
            for m in BENCH["end_to_end"]}
    assert read["step_ms"] == pytest.approx(20.0)
    assert read["step_p90_ms"] == pytest.approx(10.0)   # 9th of 10 walls
    assert read["peak_hbm_gib"] == pytest.approx(2.0)
    assert read["setup_s"] == 1.0


def test_per_layer_readers_read_nothing_without_a_trace():
    run = _run()
    for m in BENCH["per_layer"]:
        value = catalog.module("metrics", m["name"]).read(run)
        assert (value is not None) == (m["name"] == "dispatch_ms")


def test_roofline_reads_bytes_at_peak_bandwidth():
    from chipbench.tracereduce import Reduction
    red = Reduction(devices=1, window_s=1.0, busy_s=1.0,
                    class_s={"local_transform": 0.004, "other": 0.001},
                    exposed_a2a_s=0.0, op_s={}, op_count={"local_transform": 2},
                    gaps=[])
    run = _run(trace=red)   # 819e6 B at 819e9 B/s: 1 ms of 2 ms per step
    roof = catalog.module("metrics", "local_fft_roofline").read(run)
    assert roof == pytest.approx(50.0)
    assert catalog.module("metrics", "local_fft_ms").read(run) == \
        pytest.approx(2.0)
    assert catalog.module("metrics", "a2a_ms").read(run) is None
    assert catalog.module("metrics", "device_idle_pct").read(run) == 0.0
