"""The reduction from device ops and host spans to busy time, idle gaps,
class times and exposed all-to-all time, on small synthetic traces."""
import pytest

from chipbench import tracereduce as tr

RULES = tr.load_rules()
MS = 1e6  # ns


def op(dev, name, start_ms, end_ms, stats=None):
    return tr.Op(dev, name, start_ms * MS, end_ms * MS,
                 tr.classify(name, stats or {}, RULES))


def span(name, start_ms, end_ms):
    return tr.Span(name, start_ms * MS, end_ms * MS)


def test_union_and_subtract():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)]) == [[0, 3], [5, 8]]
    assert tr.subtract([[0, 10]], [[2, 3], [5, 6]]) == [[0, 2], [3, 5], [6, 10]]
    assert tr.subtract([[0, 4], [6, 10]], [[3, 7]]) == [[0, 3], [7, 10]]
    assert tr.subtract([[0, 4]], []) == [[0, 4]]
    assert tr.subtract([[0, 4]], [[0, 4]]) == []


# Names and stats as a v5e trace shows them (xla, matmul and pallas plans,
# the PPB solve).
@pytest.mark.parametrize("name,stats,cls", [
    ("all-to-all.3", {}, "all_to_all"),
    ("all_to_all.48", {"tf_op": "jit(run)/shard_map/all_to_all:"},
     "all_to_all"),
    ("fusion.4", {"hlo_category": "all-to-all"}, "all_to_all"),
    ("all-gather.1", {"hlo_category": "all-gather"}, "collective"),
    ("fft.12", {}, "local_transform"),
    ("fusion.158", {"hlo_category": "convolution fusion",
                    "tf_op": "jit(run)/jit(fft):"}, "local_transform"),
    ("copy.27", {"hlo_category": "data formatting",
                 "tf_op": "jit(run)/jit(fft)/fft:"}, "local_transform"),
    ("fft_cols.3", {"hlo_category": "custom-call",
                    "tf_op": "jit(run)/jit(fft_cols)/pallas_call:"},
     "local_transform"),
    ("fusion.10", {"hlo_category": "convolution fusion",
                   "tf_op": "jit(run)/...kn,nm->...km/dot_general:"},
     "local_transform"),
    ("custom-call", {"hlo_category": "custom-call", "tf_op": "x:"}, "other"),
    ("copy.4", {"hlo_category": "data formatting",
                "tf_op": "jit(run)/reshape:"}, "other"),
    ("custom-call.4", {"hlo_category": "custom-call",
                       "tf_op": "jit(true_divide)/div:"}, "other"),
    ("fusion", {"hlo_category": "custom fusion",
                "tf_op": "jit(run)/gather:"}, "other"),
])
def test_classify(name, stats, cls):
    assert tr.classify(name, stats, RULES) == cls


def test_busy_idle_and_gap_labels():
    # One device, window 0..100 ms: busy 10..40 (two overlapping ops) and
    # 60..90; gaps 0..10 (dispatch), 40..60 (block), 90..100 (none).
    ops = [op(0, "fft.1", 10, 30), op(0, "copy.2", 25, 40),
           op(0, "fft.1", 60, 90), op(0, "fft.1", -5, 0)]
    spans = [span("window", 0, 100), span("step", 0, 50),
             span("dispatch", 0, 12), span("block", 12, 50)]
    red = tr.reduce(ops, spans)
    assert red.window_s == pytest.approx(0.1)
    assert red.busy_s == pytest.approx(0.060)
    assert red.class_s["local_transform"] == pytest.approx(0.050)
    assert red.class_s["other"] == pytest.approx(0.015)
    assert [g[0] for g in red.gaps] == ["block", "dispatch", "between steps"]
    assert [g[1] for g in red.gaps] == pytest.approx([0.020, 0.010, 0.010])
    bd = tr.breakdown(red, top=1)
    assert bd["device_ops"] == [["fft.1", pytest.approx(0.050)]]
    assert bd["idle_gaps"] == [["block", pytest.approx(0.020)]]


def test_exposed_all_to_all_and_mean_over_devices():
    # Device 0: a2a 0..20 overlaps an fft 10..30 -> 10 ms exposed.
    # Device 1: a2a 0..20 alone -> 20 ms exposed.  Means: 15 ms exposed,
    # 20 ms a2a, busy (30 + 20) / 2.
    ops = [op(0, "all-to-all.1", 0, 20), op(0, "fft.2", 10, 30),
           op(1, "all-to-all.1", 0, 20)]
    red = tr.reduce(ops, [], window=(0, 40 * MS))
    assert red.devices == 2
    assert red.exposed_a2a_s == pytest.approx(0.015)
    assert red.class_s["all_to_all"] == pytest.approx(0.020)
    assert red.busy_s == pytest.approx(0.025)
    assert red.op_count == {"all_to_all": 2, "local_transform": 1}


def test_window_falls_back_to_op_extent_and_refuses_empty():
    red = tr.reduce([op(0, "fft.1", 5, 15)], [])
    assert red.window_s == pytest.approx(0.010)
    assert red.busy_s == pytest.approx(0.010)
    with pytest.raises(ValueError):
        tr.reduce([], [])
    with pytest.raises(ValueError):
        tr.reduce([op(0, "fft.1", 5, 15)], [], window=(20 * MS, 30 * MS))


def test_innermost_span_wins_a_tie():
    spans = [span("step", 0, 100), span("dispatch", 0, 50)]
    assert tr.label([10 * MS, 20 * MS], spans) == "dispatch"


def test_xplane_reader_on_a_cpu_trace(tmp_path):
    # The reader sees the harness's host span and the CPU's XLA ops with
    # their stats, at the times jax's own reader gives.
    import glob

    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from chipbench import xplane

    f = jax.jit(lambda x: jnp.fft.fftn(x) * 2)
    x = jnp.ones((16, 16, 16), jnp.complex64)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("chipbench.window"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)[0]
    ours = [ev for plane in xplane.read(path).planes
            for ev in xplane.events(plane)]
    window = [ev for ev in ours if ev[1] == "chipbench.window"]
    assert len(window) == 1
    ffts = [ev for ev in ours if ev[4].get("hlo_op", "").startswith("fft")]
    assert ffts and all(window[0][2] <= ev[2] <= ev[3] <= window[0][3]
                        for ev in ffts)
    theirs = {(e.name, e.start_ns, e.end_ns)
              for plane in ProfileData.from_file(path).planes
              for line in plane.lines for e in line.events}
    assert {(ev[1], ev[2], ev[3]) for ev in window + ffts} <= theirs
