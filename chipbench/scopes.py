"""Device time by the program scope an op ran in, from a trace reduction.

``tracereduce`` keeps each op's time under its HLO name and ``tf_op``, the
JAX op it came from.  The program names its executables
(``jit(repro_fft_forward)``, ``..._inverse``, ``..._seg<j>``) and its plan
body's parameter (``repro_fft_in``), and runs each stage's local transform
under the scope ``stage<k>`` and each hop's exchange under ``hop<i>``, so
``tf_op`` places every op in one of four buckets (rules in
``op_scopes.json``, matched by ``tracereduce.classify``):

* ``stage`` -- under a ``stage<k>`` scope;
* ``hop``   -- under a ``hop<i>`` scope;
* ``plan``  -- in a plan executable, in neither scope;
* ``entry`` -- in no plan executable (the entry's eager ops).

A program that names nothing so (one from before the names) puts every op
in ``entry``: ``scope_s`` is then None and so is every reading of it.
"""
from __future__ import annotations

from collections import defaultdict
from pathlib import Path
from typing import Optional

from chipbench import tracereduce

RULES_FILE = Path(__file__).resolve().parent / "op_scopes.json"


def load_rules(path: Path = RULES_FILE) -> dict:
    return tracereduce.load_rules(path)


def bucket(tf_op: str, rules: dict) -> str:
    return tracereduce.classify("", {"tf_op": tf_op}, rules)


def scope_s(red, rules: Optional[dict] = None) -> Optional[dict]:
    """Bucket -> device seconds, mean over the chips, cut to the window;
    None where every op falls in the default bucket.  An ``op_s`` key is
    ``"<hlo> <tf_op>"``, or the HLO name alone for an op without one."""
    rules = rules or load_rules()
    out = defaultdict(float)
    for key, seconds in red.op_s.items():
        out[bucket(key.partition(" ")[2], rules)] += seconds
    if set(out) <= {rules["default"]}:
        return None
    return dict(out)


def per_step_ms(run, name: str) -> Optional[float]:
    """Bucket ``name``'s device time per step in ms; 0 for an empty bucket
    of a program that names its executables."""
    if run.trace is None:
        return None
    buckets = scope_s(run.trace)
    if buckets is None:
        return None
    return buckets.get(name, 0.0) / run.steps * 1e3
