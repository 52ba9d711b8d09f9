"""From a profiler trace to per-device op intervals, busy time, idle gaps and
class times.

``load`` reads an ``.xplane.pb`` file: the device ops on each device plane's
op line, sorted into classes by the rules of ``op_classes.json``, and the
harness's own host spans (``chipbench.*`` annotations).  ``reduce`` cuts them
to the measured window and computes, per device, the union of busy
intervals, the idle gaps between them (each labelled with the host span it
fell in), the time of each class, and the part of all-to-all time during
which no other op ran on that device.  Times are seconds; device figures
are means over the devices.
"""
from __future__ import annotations

import dataclasses
import json
import re
from collections import defaultdict
from pathlib import Path

SPAN_PREFIX = "chipbench."
CLASSES_FILE = Path(__file__).resolve().parent / "op_classes.json"


@dataclasses.dataclass(frozen=True)
class Op:
    device: int
    name: str
    start: float   # ns, on the profiler's clock
    end: float
    cls: str


@dataclasses.dataclass(frozen=True)
class Span:
    name: str      # without the ``chipbench.`` prefix
    start: float
    end: float


@dataclasses.dataclass
class Reduction:
    devices: int
    window_s: float
    busy_s: float                 # mean over devices
    class_s: dict                 # class -> mean seconds over devices
    exposed_a2a_s: float          # mean over devices
    op_s: dict                    # op name -> mean seconds over devices
    op_count: dict                # class -> number of ops (all devices)
    gaps: list                    # [(host span, seconds)], every device


def load_rules(path: Path = CLASSES_FILE) -> dict:
    with open(path) as f:
        return json.load(f)


def classify(name: str, stats: dict, rules: dict) -> str:
    """The class of the first rule whose every pattern matches: ``name``
    against the op name, ``stats`` against the op's trace stats."""
    for rule in rules["classes"]:
        if "name" in rule and not re.search(rule["name"], name):
            continue
        if any(not re.search(pat, str(stats.get(key, "")))
               for key, pat in rule.get("stats", {}).items()):
            continue
        return rule["class"]
    return rules["default"]


def load(path, rules: dict) -> tuple:
    """(ops, spans) of the trace at ``path``."""
    from chipbench import xplane

    plane_re = re.compile(rules["device_plane"])
    ops, spans = [], []
    for plane in xplane.read(path).planes:
        m = plane_re.search(plane.name)
        for line, name, start, end, stats in xplane.events(plane):
            if m and line == rules["op_line"]:
                # The JAX op an HLO op came from names it for a reader.
                shown = f"{name} {stats['tf_op']}" if stats.get("tf_op") \
                    else name
                ops.append(Op(int(m.group(1)), shown, start, end,
                              classify(name, stats, rules)))
            elif not m and name.startswith(SPAN_PREFIX):
                spans.append(Span(name[len(SPAN_PREFIX):], start, end))
    return ops, spans


def union(intervals) -> list:
    """Sorted, disjoint [start, end) intervals covering ``intervals``."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list:
    """The parts of union ``a`` that union ``b`` does not cover."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def label(gap, spans) -> str:
    """The host span that overlaps the gap most (the shorter one on a tie:
    the innermost); ``between steps`` where none does."""
    best, best_key = "between steps", (0.0, 0.0)
    for sp in spans:
        over = min(gap[1], sp.end) - max(gap[0], sp.start)
        if over > 0 and (over, -(sp.end - sp.start)) > best_key:
            best, best_key = sp.name, (over, -(sp.end - sp.start))
    return best


def window_of(spans, ops) -> tuple:
    """The ``window`` host span, or the extent of the device ops."""
    for sp in spans:
        if sp.name == "window":
            return sp.start, sp.end
    return min(o.start for o in ops), max(o.end for o in ops)


def reduce(ops, spans, window=None) -> Reduction:
    """Cut ``ops`` to ``window`` (ns) and reduce them per device."""
    if not ops:
        raise ValueError("the trace holds no device op")
    w0, w1 = window if window is not None else window_of(spans, ops)
    inner = [sp for sp in spans if sp.name != "window"]
    by_dev = defaultdict(list)
    for o in ops:
        s, e = max(o.start, w0), min(o.end, w1)
        if e > s:
            by_dev[o.device].append((s, e, o))
    if not by_dev:
        raise ValueError("no device op lies in the window")
    n = len(by_dev)
    busy = 0.0
    class_s, op_s, op_count = defaultdict(float), defaultdict(float), \
        defaultdict(int)
    exposed, gaps = 0.0, []
    for dev_ops in by_dev.values():
        busy_u = union((s, e) for s, e, _ in dev_ops)
        busy += length(busy_u)
        for s, e, o in dev_ops:
            class_s[o.cls] += (e - s) / n
            op_s[o.name] += (e - s) / n
            op_count[o.cls] += 1
        a2a = union((s, e) for s, e, o in dev_ops if o.cls == "all_to_all")
        rest = union((s, e) for s, e, o in dev_ops if o.cls != "all_to_all")
        exposed += length(subtract(a2a, rest))
        for g in subtract([[w0, w1]], busy_u):
            gaps.append((label(g, inner), (g[1] - g[0]) * 1e-9))
    return Reduction(
        devices=n, window_s=(w1 - w0) * 1e-9, busy_s=busy / n * 1e-9,
        class_s={k: v * 1e-9 for k, v in class_s.items()},
        exposed_a2a_s=exposed / n * 1e-9,
        op_s={k: v * 1e-9 for k, v in op_s.items()},
        op_count=dict(op_count), gaps=sorted(gaps, key=lambda g: -g[1]))


def breakdown(red: Reduction, top: int = 10) -> dict:
    """The device ops that took most time and the longest idle gaps, each
    with the host span it fell in."""
    ops = sorted(red.op_s.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in red.gaps[:top]]}
