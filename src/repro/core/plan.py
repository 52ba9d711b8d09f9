"""Plan caching — the JAX analogue of FFTW/cuFFT plan reuse (paper §V-B).

On TPU, "planning" is XLA compilation.  ``PlanCache`` makes the paper's
``get_or_create_plan`` behaviour explicit: plans are keyed by everything that
changes the compiled artifact (transform kind, grid, dtype, decomposition,
mesh geometry, backend, overlap chunking) and hold the *compiled* executable,
so repeated transforms of identically-shaped chunks never re-plan.

The cache also keeps hit/miss counters: benchmarks reproduce the paper's
claim that plan reuse removes per-call planning latency, and tests assert
that a second identical call is a cache hit.

Per-**segment** executables (``pipeline.compile_segment`` — the plan-stream
executor's stage-at-a-time lowering) live in the same LRU cache: the
``extra`` key component carries ``(batch_shape, donate, "segment", index)``,
so a plan's fused executable and each of its segments are distinct entries
evicted under one global bound.

Beneath both sits XLA: ``xla_compile_stats`` counts every jaxpr trace,
backend compile and persistent-cache load of the process from JAX's own
monitoring events (listeners registered once, on import), so a caller can
tell a steady loop, which adds none, from one that recompiles.

``TuningCache`` is the second, *persistent* layer: compiled executables
cannot survive the process, but the autotuner's **decisions** (which decomp
/ backend / n_chunks won for a given problem key) can, as JSON on disk — the
FFTW-wisdom analogue.  ``tune()`` consults it before measuring anything.
"""
from __future__ import annotations

import dataclasses
import json
import os
import threading
import time
from collections import OrderedDict
from typing import Any, Callable, Dict, Hashable, Optional, Sequence, Tuple

from jax import monitoring as jax_monitoring

try:  # POSIX advisory file locking for cross-process cache merging
    import fcntl
except ImportError:  # non-POSIX: single-writer semantics, merge still runs
    fcntl = None


def env_capacity(var: str, default: int) -> int:
    """LRU capacity from an env var, clamped sane (shared by the compiled
    PlanCache here and the wrapper plan memo in api.py)."""
    try:
        cap = int(os.environ.get(var, str(default)))
    except ValueError:
        cap = default
    return max(cap, 1)


@dataclasses.dataclass
class PlanEntry:
    executable: Any          # compiled jax executable (or jitted fn)
    build_time_s: float      # wall time spent planning (compile)


class PlanCache:
    """Thread-safe get-or-create LRU cache for compiled FFT plans.

    Bounded (``$REPRO_PLAN_CACHE_SIZE``, default 128): a long-running
    process sweeping many problem keys must not accumulate compiled
    executables without limit.  Eviction drops this cache's reference
    only — a ``DistributedFFT`` plan that holds its executable directly
    keeps working; an evicted key simply recompiles on its next miss.
    """

    def __init__(self, capacity: Optional[int] = None,
                 timer: Callable[[], float] = time.perf_counter):
        self._lock = threading.Lock()
        self._plans: "OrderedDict[Hashable, PlanEntry]" = OrderedDict()
        self._capacity = capacity
        self._timer = timer
        self.misses = 0
        self.hits = 0
        self.evictions = 0

    @property
    def capacity(self) -> int:
        if self._capacity is not None:
            return max(self._capacity, 1)
        return env_capacity("REPRO_PLAN_CACHE_SIZE", 128)

    def get_or_create(self, key: Hashable,
                      builder: Callable[[], Any]) -> PlanEntry:
        with self._lock:
            entry = self._plans.get(key)
            if entry is not None:
                self.hits += 1
                self._plans.move_to_end(key)
                return entry
        # Build outside the lock: compiles can take seconds and must not
        # serialize unrelated plan lookups (the paper's scheduler threads
        # share one cache).
        t0 = self._timer()
        executable = builder()
        dt = self._timer() - t0
        with self._lock:
            # Another thread may have raced us; first build wins.
            entry = self._plans.get(key)
            if entry is None:
                entry = PlanEntry(executable=executable, build_time_s=dt)
                self._plans[key] = entry
                self.misses += 1
                while len(self._plans) > self.capacity:
                    self._plans.popitem(last=False)
                    self.evictions += 1
            else:
                self.hits += 1
            self._plans.move_to_end(key)
        return entry

    def keys(self) -> list:
        """Snapshot of the cached plan keys (static key audits)."""
        with self._lock:
            return list(self._plans)

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "plans": len(self._plans),
                "capacity": self.capacity,
                "hits": self.hits,
                "misses": self.misses,
                "evictions": self.evictions,
                "total_build_time_s": sum(
                    e.build_time_s for e in self._plans.values()),
            }

    def clear(self) -> None:
        with self._lock:
            self._plans.clear()
            self.hits = 0
            self.misses = 0
            self.evictions = 0


# Process-global default cache (mirrors the paper's per-process plan store).
GLOBAL_PLAN_CACHE = PlanCache()


# ---------------------------------------------------------------------------
# XLA compile counter: every trace, compile and persistent-cache load of the
# process, from JAX's own monitoring events (their durations included, so
# this module calls no clock of its own).
# ---------------------------------------------------------------------------

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_XLA_LOCK = threading.Lock()
_XLA_COUNTS = {"traces": 0, "backend": 0, "cache_loads": 0,
               "compile_s": 0.0}


def _on_xla_duration(event: str, duration_secs: float, **_: Any) -> None:
    with _XLA_LOCK:
        if event == _TRACE_EVENT:
            _XLA_COUNTS["traces"] += 1
        elif event == _COMPILE_EVENT:
            _XLA_COUNTS["backend"] += 1
            _XLA_COUNTS["compile_s"] += duration_secs


def _on_xla_event(event: str, **_: Any) -> None:
    if event == _CACHE_HIT_EVENT:
        with _XLA_LOCK:
            _XLA_COUNTS["cache_loads"] += 1


def xla_compile_stats() -> Dict[str, Any]:
    """Process-wide counts since ``repro.core`` was imported: ``traces``
    (jaxprs traced for a jit), ``compiles`` (XLA backend compiles, cache
    loads excluded), ``cache_loads`` (executables read from the persistent
    compilation cache) and ``compile_s`` (seconds in backend compiles and
    cache loads, as JAX reports them)."""
    with _XLA_LOCK:
        counts = dict(_XLA_COUNTS)
    backend = counts.pop("backend")
    counts["compiles"] = backend - counts["cache_loads"]
    return counts


jax_monitoring.register_event_duration_secs_listener(_on_xla_duration)
jax_monitoring.register_event_listener(_on_xla_event)


@dataclasses.dataclass(frozen=True)
class TunedPlan:
    """The autotuner's decision for one problem key (JSON-serializable)."""

    decomp: str                  # "pencil" | "slab" | "hybrid"
    mesh_axes: Tuple[str, ...]   # mesh axes the decomposition runs over
    backend: str                 # "xla" | "matmul" | "pallas"
    n_chunks: int
    predicted_s: float           # perfmodel estimate
    measured_s: float            # compiled-executable timing (0.0 if none)
    source: str                  # "measured" | "heuristic" | "default"
    baseline_s: float = 0.0      # static default's time in the same run
    ts: float = 0.0              # epoch seconds when measured (merge tiebreak)
    # Hybrid schedules are distinguished by their stage grouping of the
    # spatial dims; None for pencil/slab (and for pre-hybrid wisdom files).
    dim_groups: Optional[Tuple[Tuple[int, ...], ...]] = None
    # Per-hop chunk schedule (forward hop order); None means the uniform
    # ``n_chunks`` applies to every hop — which is also how pre-schedule
    # wisdom entries (int-valued ``n_chunks``, no schedule key) read back.
    chunk_schedule: Optional[Tuple[int, ...]] = None
    # What the tuner measured: "forward" (one transform) or
    # "fwd+scale+inv" (the PoissonSolver-style joint round trip).
    objective: str = "forward"

    def to_json(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        d["mesh_axes"] = list(self.mesh_axes)
        if self.dim_groups is None:
            d.pop("dim_groups")
        else:
            d["dim_groups"] = [list(g) for g in self.dim_groups]
        if self.chunk_schedule is None:
            d.pop("chunk_schedule")
        else:
            d["chunk_schedule"] = [int(c) for c in self.chunk_schedule]
        if self.objective == "forward":
            d.pop("objective")  # keep pre-objective files byte-compatible
        return d

    @classmethod
    def from_json(cls, d: Dict[str, Any]) -> "TunedPlan":
        groups = d.get("dim_groups")
        sched = d.get("chunk_schedule")
        return cls(decomp=d["decomp"], mesh_axes=tuple(d["mesh_axes"]),
                   backend=d["backend"], n_chunks=int(d["n_chunks"]),
                   predicted_s=float(d.get("predicted_s", 0.0)),
                   measured_s=float(d.get("measured_s", 0.0)),
                   source=d.get("source", "measured"),
                   baseline_s=float(d.get("baseline_s", 0.0)),
                   ts=float(d.get("ts", 0.0)),
                   dim_groups=(tuple(tuple(int(x) for x in g) for g in groups)
                               if groups is not None else None),
                   chunk_schedule=(tuple(int(c) for c in sched)
                                   if sched is not None else None),
                   objective=str(d.get("objective", "forward")))

    def describe(self) -> str:
        """One-line human-readable account of this decision and its timings.

        Rehydrated wisdom entries report the same predicted/measured numbers
        they were persisted with, so a ``DistributedFFT.describe()`` built
        from a cache hit shows the original tuning evidence.
        """
        from .decomp import describe_decomp  # deferred: keep plan.py light
        decomp = describe_decomp(self.decomp, self.dim_groups)
        chunks = (",".join(map(str, self.chunk_schedule))
                  if self.chunk_schedule is not None else str(self.n_chunks))
        head = (f"{decomp}({','.join(self.mesh_axes)})/{self.backend}"
                f"/chunks={chunks}")
        if self.objective != "forward":
            head += f" [{self.objective}]"
        if self.source == "measured":
            return (f"{head} [measured {self.measured_s * 1e3:.3f} ms, "
                    f"predicted {self.predicted_s * 1e3:.3f} ms, "
                    f"default baseline {self.baseline_s * 1e3:.3f} ms]")
        if self.source == "heuristic":
            return f"{head} [predicted {self.predicted_s * 1e3:.3f} ms]"
        return f"{head} [static default, untuned]"


def tuning_key(*, grid: Sequence[int], mesh_shape: Sequence[int],
               mesh_axes: Sequence[str], kinds: Sequence[str], dtype: str,
               inverse: bool, batch_shape: Sequence[int] = (),
               platform: str = "", op: str = "fft") -> str:
    """Stable string key for one tuning problem (usable as a JSON key).

    ``platform`` (e.g. "cpu"/"tpu") keeps wisdom tuned on one device kind
    from being served to another via the shared on-disk cache.  ``op``
    names the measured operation; the default "fft" (a single forward
    transform) is omitted so pre-existing wisdom keys stay valid, while
    e.g. the PoissonSolver's joint "fwd+scale+inv" objective gets its own
    key space and can never shadow a forward-only plan.
    """
    parts = [
        "grid=" + ",".join(map(str, grid)),
        "mesh=" + ",".join(map(str, mesh_shape)),
        "axes=" + ",".join(mesh_axes),
        "kinds=" + ",".join(kinds),
        "dtype=" + dtype,
        "inv=" + str(int(inverse)),
        "batch=" + ",".join(map(str, batch_shape)),
        "plat=" + platform,
    ]
    if op != "fft":
        parts.append("op=" + op)
    return ";".join(parts)


def parse_tuning_key(key: str) -> Optional[Dict[str, Any]]:
    """Invert :func:`tuning_key`: one wisdom key back into its problem.

    Returns ``None`` for keys this version cannot read (unknown fields,
    missing required parts) rather than raising — the wisdom file is shared
    across versions and a warm-start pass must simply skip what it cannot
    rebuild.  The returned dict carries ``grid``/``mesh_shape``/``mesh_axes``
    /``kinds``/``dtype``/``inverse``/``batch_shape``/``platform``/``op``
    with the same types :func:`tuning_key` accepted.
    """
    fields: Dict[str, str] = {}
    for part in key.split(";"):
        name, sep, val = part.partition("=")
        if not sep:
            return None
        fields[name] = val

    def ints(raw: str) -> Tuple[int, ...]:
        return tuple(int(v) for v in raw.split(",")) if raw else ()

    def strs(raw: str) -> Tuple[str, ...]:
        return tuple(raw.split(",")) if raw else ()

    try:
        return {
            "grid": ints(fields["grid"]),
            "mesh_shape": ints(fields["mesh"]),
            "mesh_axes": strs(fields["axes"]),
            "kinds": strs(fields["kinds"]),
            "dtype": fields["dtype"],
            "inverse": bool(int(fields["inv"])),
            "batch_shape": ints(fields["batch"]),
            "platform": fields["plat"],
            "op": fields.get("op", "fft"),
        }
    except (KeyError, ValueError):
        return None


def default_tuning_path() -> str:
    env = os.environ.get("REPRO_TUNING_CACHE")
    if env:
        return env
    base = os.environ.get("XDG_CACHE_HOME",
                          os.path.join(os.path.expanduser("~"), ".cache"))
    return os.path.join(base, "repro-fft", "tuning.json")


class TuningCache:
    """Persistent key -> :class:`TunedPlan` store (FFTW-wisdom analogue).

    ``path=None`` keeps the cache in-memory only (tests, throwaway runs).
    Writes go through an atomic rename so a crashed process never leaves a
    torn JSON file behind, and every save **re-reads and merges** the file
    under an ``fcntl`` advisory lock first: two processes tuning different
    problems against the same wisdom file both keep their plans (per key,
    the entry with the newest ``ts`` wins), instead of the last writer
    erasing the other's work.

    Besides plans, the file carries a ``"machine"`` section — the
    calibrated :class:`~repro.core.perfmodel.MachineProfile` per platform
    (as raw JSON, see ``get_machine``/``put_machine``) — so calibration
    runs once per machine, not once per process.
    """

    _VERSION = 1

    def __init__(self, path: Optional[str] = None):
        self.path = path
        self._lock = threading.Lock()
        self._plans: Dict[str, TunedPlan] = {}
        self._machines: Dict[str, Dict[str, Any]] = {}
        self.hits = 0
        self.misses = 0
        if path is not None:
            self._load()

    def _read_file(self) -> Optional[Dict[str, Any]]:
        try:
            with open(self.path) as f:
                raw = json.load(f)
        except (FileNotFoundError, json.JSONDecodeError):
            return None
        if raw.get("version") != self._VERSION:
            return None  # stale schema: retune rather than misread
        return raw

    def _load(self) -> None:
        raw = self._read_file()
        if raw is None:
            return
        for k, v in raw.get("plans", {}).items():
            try:
                self._plans[k] = TunedPlan.from_json(v)
            except (KeyError, TypeError, ValueError):
                continue
        for plat, prof in raw.get("machine", {}).items():
            if isinstance(prof, dict):
                self._machines[plat] = prof

    def _merge_from_disk(self) -> None:
        """Fold the file's current contents into memory (newest ts wins)."""
        raw = self._read_file()
        if raw is None:
            return
        for k, v in raw.get("plans", {}).items():
            try:
                other = TunedPlan.from_json(v)
            except (KeyError, TypeError, ValueError):
                continue
            mine = self._plans.get(k)
            if mine is None or other.ts > mine.ts:
                self._plans[k] = other
        for plat, prof in raw.get("machine", {}).items():
            if not isinstance(prof, dict):
                continue
            mine = self._machines.get(plat)
            # Newest save wins (same rule as plans): a process holding a
            # stale profile must not clobber a fresher calibration — e.g.
            # one upgraded with network measurements — on an unrelated
            # plan save.
            if mine is None or (prof.get("_saved_ts", 0.0)
                                > mine.get("_saved_ts", 0.0)):
                self._machines[plat] = prof

    def _save(self, merge: bool = True) -> None:
        # Caller holds self._lock.  merge=False wipes the file (clear()).
        if self.path is None:
            return
        os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
        lock_file = None
        try:
            if fcntl is not None:
                try:
                    lock_file = open(self.path + ".lock", "w")
                    fcntl.flock(lock_file, fcntl.LOCK_EX)
                except OSError:
                    # Filesystem without advisory-lock support (e.g. some
                    # NFS mounts): degrade to the best-effort lockless
                    # merge + atomic rename rather than failing the save.
                    if lock_file is not None:
                        lock_file.close()
                    lock_file = None
            if merge:
                self._merge_from_disk()
            payload = {
                "version": self._VERSION,
                "plans": {k: p.to_json() for k, p in self._plans.items()},
                "machine": self._machines,
            }
            tmp = f"{self.path}.tmp.{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(payload, f, indent=1, sort_keys=True)
            os.replace(tmp, self.path)
        finally:
            if lock_file is not None:
                fcntl.flock(lock_file, fcntl.LOCK_UN)
                lock_file.close()

    def get(self, key: str) -> Optional[TunedPlan]:
        with self._lock:
            plan = self._plans.get(key)
            if plan is None:
                self.misses += 1
            else:
                self.hits += 1
            return plan

    def keys(self) -> list:
        """Snapshot of the wisdom keys (static key audits, warm scans)."""
        with self._lock:
            return list(self._plans)

    def put(self, key: str, plan: TunedPlan) -> None:
        with self._lock:
            if plan.ts == 0.0:
                # An unstamped plan would lose every recency merge against
                # an existing on-disk entry, making this put a silent no-op;
                # writing it now means it is current now.
                plan = dataclasses.replace(plan, ts=time.time())
            self._plans[key] = plan
            self._save()

    def get_machine(self, platform: str) -> Optional[Dict[str, Any]]:
        """Raw calibrated-profile JSON for ``platform`` (or None).

        Decoding to a ``MachineProfile`` is the caller's job
        (``perfmodel.MachineProfile.from_json``) — this module stays free of
        model dependencies.
        """
        with self._lock:
            prof = self._machines.get(platform)
            return dict(prof) if prof is not None else None

    def put_machine(self, platform: str, profile: Dict[str, Any]) -> None:
        """Persist one platform's calibrated profile JSON.

        The record is stamped with a ``_saved_ts`` save time so concurrent
        processes merge on recency; profile decoders ignore the extra key.
        """
        with self._lock:
            rec = dict(profile)
            rec.setdefault("_saved_ts", time.time())
            self._machines[platform] = rec
            self._save()

    def __len__(self) -> int:
        with self._lock:
            return len(self._plans)

    def items(self) -> Tuple[Tuple[str, TunedPlan], ...]:
        """Snapshot of every persisted (key, plan) pair — the warm-start
        enumeration surface (``serving.PlanWarmer``); pair with
        :func:`parse_tuning_key` to recover each key's problem."""
        with self._lock:
            return tuple(self._plans.items())

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {"plans": len(self._plans), "hits": self.hits,
                    "misses": self.misses, "path": self.path,
                    "machines": len(self._machines)}

    def clear(self) -> None:
        with self._lock:
            self._plans.clear()
            self._machines.clear()
            self.hits = 0
            self.misses = 0
            self._save(merge=False)


# Lazily-created process-global tuning cache (persisted under
# ``default_tuning_path()``; override with the REPRO_TUNING_CACHE env var).
_GLOBAL_TUNING_CACHE: Optional[TuningCache] = None
_GLOBAL_TUNING_LOCK = threading.Lock()


def global_tuning_cache() -> TuningCache:
    global _GLOBAL_TUNING_CACHE
    with _GLOBAL_TUNING_LOCK:
        if _GLOBAL_TUNING_CACHE is None:
            _GLOBAL_TUNING_CACHE = TuningCache(default_tuning_path())
        return _GLOBAL_TUNING_CACHE


def plan_key(*, kind: Tuple[str, ...], grid: Tuple[int, ...], dtype: str,
             decomp: Hashable, mesh_shape: Tuple[int, ...],
             mesh_axes: Tuple[str, ...], backend: str, n_chunks: Hashable,
             inverse: bool, extra: Optional[Hashable] = None) -> Hashable:
    """``n_chunks`` may be an int or a full per-hop chunk-schedule tuple —
    either way it is part of the compiled artifact's identity."""
    return (kind, grid, dtype, decomp, mesh_shape, mesh_axes, backend,
            n_chunks, inverse, extra)
