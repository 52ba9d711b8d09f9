"""Analytic LogP + roofline performance model for the distributed FFT.

Used by the Fig. 5/6/7/9 benchmark analogues: on this CPU-only container we
cannot time a 256-chip pod, so scaling curves are *predicted* from the same
latency-bandwidth formulation the paper uses (Eq. 1-2, 7), with machine
constants either (a) the TPU v5e targets, or (b) calibrated from measured
single-core runs.  The dry-run roofline (distributed/roofline.py) provides
the cross-check: its collective-bytes term and this model's transpose-volume
term must agree, and tests assert they do.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

from .decomp import Decomposition, local_shape
from .redistribute import hop_move_shapes, transpose_cost_bytes
from .scheduler import CostModel, TaskSpec, hop_phase_time


@dataclasses.dataclass(frozen=True)
class Machine:
    """Per-rank hardware constants."""
    name: str
    flops: float                 # sustainable FLOP/s per rank
    mem_bw: float                # HBM/DRAM bytes/s per rank
    net_alpha_s: float           # per-message latency (Eq. 1 alpha)
    net_bw: float                # per-rank injection bandwidth (1/beta)
    overlap: float = 0.0         # 0 = bulk-sync, 1 = perfect Eq. 2 overlap


TPU_V5E = Machine(name="tpu_v5e", flops=197e12, mem_bw=819e9,
                  net_alpha_s=1e-6, net_bw=3 * 50e9)
# Xeon 6240R-ish single core with FFTW (calibratable).  net_bw is the
# PER-RANK share of the node NIC: InfiniBand HDR (~25 GB/s) divided across
# 48 ranks/node with contention ~= 0.5-1 GB/s — the regime where the
# paper's overlap wins materialize.
CPU_CORE = Machine(name="cpu_core", flops=8e9, mem_bw=8e9,
                   net_alpha_s=2e-5, net_bw=0.8e9)
#: Accelerator constants keyed by ``device_kind`` as JAX reports it.
#: TPU v5e ("TPU v5 lite"): 197 TFLOP/s bf16 and 819 GB/s HBM per chip
#: (Google Cloud documentation, "TPU v5e").
ACCELERATORS = {"TPU v5 lite": TPU_V5E}


def default_machine() -> Machine:
    """Machine constants for the first device of this process.

    The CPU platform gets the calibratable ``CPU_CORE``; an accelerator
    must be listed in ``ACCELERATORS`` by its ``device_kind`` — an unknown
    kind is an error, never another chip's constants.
    """
    import jax
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return CPU_CORE
    try:
        return ACCELERATORS[dev.device_kind]
    except KeyError:
        raise ValueError(
            f"no machine constants for {dev.platform} device_kind "
            f"{dev.device_kind!r}; known kinds: {sorted(ACCELERATORS)} "
            f"(add it to perfmodel.ACCELERATORS)") from None


# Transform kinds -> cost family.  The pruning model prices the three
# families differently (R2C does half the butterflies; the DCT/DST-II pairs
# are composed from a C2C of twice the logical length) and calibration can
# further scale each family from measured runs.
KIND_FAMILY = {"fft": "c2c", "ifft": "c2c", "rfft": "r2c", "irfft": "r2c",
               "dct2": "r2r", "dct3": "r2r", "dst2": "r2r", "dst3": "r2r"}


@dataclasses.dataclass(frozen=True)
class MachineProfile:
    """Measured (or default) machine parameters for the kind-aware model.

    Wraps the base :class:`Machine` constants with everything ``calibrate()``
    can actually measure on the running hardware:

    * ``backend_flops``  — sustained local-FFT FLOP/s per backend
      ("xla" / "matmul" / "pallas"), from microbenchmarks of
      ``transforms.apply_1d``;
    * ``kind_scale``     — per kind-family ("c2c"/"r2c"/"r2r") multiplier on
      compute time.  Bare family keys are the **xla** backend's scales,
      measured relative to its analytic flop ratios (an xla whose rfft is
      no faster than its fft yields ``r2c ~= 2.0``).  ``"pallas:r2c"`` /
      ``"pallas:r2r"`` are the pallas backend's own per-kind throughput
      family (its rfft is structurally the full C2C, its R2R the
      double-length four-step, but the *measured* ratios can still drift
      from the analytic ones — e.g. the fused DCT twiddle epilogue), so
      ``predict_plan_time``/``rank_candidates`` price pallas candidates
      honestly instead of aliasing them to matmul.  Matmul carries no kind
      keys: its measured correction lives entirely in ``backend_flops``;
    * ``mem_bw``         — streaming memory bandwidth (roofline denominator);
    * ``net_alpha_s`` / ``net_bw`` — per-mesh-axis all_to_all latency and
      bandwidth.  On a single-device axis these cannot be measured, so they
      stay empty and lookups fall back to the base machine's constants.

    ``calibrated`` is False when the profile is pure model defaults (e.g.
    ``REPRO_CALIBRATE=off``); ``net_calibrated`` is False when the network
    terms specifically fell back to defaults (the 1-device case).  Profiles
    are JSON round-trippable and persist in the wisdom file's ``"machine"``
    section next to the ``TuningCache`` plans, keyed by platform.
    """

    base: Machine
    platform: str = ""
    calibrated: bool = False
    net_calibrated: bool = False
    backend_flops: Tuple[Tuple[str, float], ...] = ()
    kind_scale: Tuple[Tuple[str, float], ...] = ()
    mem_bw: float = 0.0
    net_alpha_s: Tuple[Tuple[str, float], ...] = ()
    net_bw: Tuple[Tuple[str, float], ...] = ()

    @property
    def overlap(self) -> float:
        return self.base.overlap

    def flops_for(self, backend: str) -> float:
        rates = dict(self.backend_flops)
        if backend in rates:
            return rates[backend]
        if backend == "pallas" and "matmul" in rates:
            # Pre-pallas profiles (older wisdom files) carry no measured
            # pallas rate.  The kernel runs the same four-step algorithm
            # as the matmul backend, so that measured rate is the honest
            # prior — falling through to base.flops would overprice
            # pallas against backends the profile *did* measure.
            return rates["matmul"]
        return self.base.flops

    def scale_for(self, family: str, backend: str = "xla") -> float:
        """Kind-family time multiplier for ``backend``.

        Per-backend keys (``"pallas:r2c"``) take precedence; the bare
        family keys are the xla scales (back-compat with stored profiles).
        Backends without measured kind keys (matmul) get 1.0 — their
        analytic ratios are structural.
        """
        scales = dict(self.kind_scale)
        v = scales.get(f"{backend}:{family}")
        if v is not None:
            return v
        if backend == "xla":
            return scales.get(family, 1.0)
        return 1.0

    def alpha_for(self, mesh_axis: str) -> float:
        return dict(self.net_alpha_s).get(mesh_axis, self.base.net_alpha_s)

    def bw_for(self, mesh_axis: str) -> float:
        return dict(self.net_bw).get(mesh_axis, self.base.net_bw)

    @property
    def eff_mem_bw(self) -> float:
        return self.mem_bw if self.mem_bw > 0 else self.base.mem_bw

    def to_json(self) -> Dict[str, Any]:
        return {
            "base": dataclasses.asdict(self.base),
            "platform": self.platform,
            "calibrated": self.calibrated,
            "net_calibrated": self.net_calibrated,
            "backend_flops": dict(self.backend_flops),
            "kind_scale": dict(self.kind_scale),
            "mem_bw": self.mem_bw,
            "net_alpha_s": dict(self.net_alpha_s),
            "net_bw": dict(self.net_bw),
        }

    @classmethod
    def from_json(cls, d: Mapping[str, Any]) -> "MachineProfile":
        def items(key):
            return tuple(sorted((str(k), float(v))
                                for k, v in dict(d.get(key, {})).items()))
        return cls(base=Machine(**d["base"]), platform=str(d.get("platform", "")),
                   calibrated=bool(d.get("calibrated", False)),
                   net_calibrated=bool(d.get("net_calibrated", False)),
                   backend_flops=items("backend_flops"),
                   kind_scale=items("kind_scale"),
                   mem_bw=float(d.get("mem_bw", 0.0)),
                   net_alpha_s=items("net_alpha_s"), net_bw=items("net_bw"))


def profile_from_machine(machine: Machine, platform: str = "") -> MachineProfile:
    """Uncalibrated profile: every lookup falls back to the model defaults."""
    return MachineProfile(base=machine, platform=platform, calibrated=False,
                          net_calibrated=False, mem_bw=machine.mem_bw)


def as_profile(machine) -> MachineProfile:
    """Accept either a bare :class:`Machine` or a :class:`MachineProfile`."""
    if isinstance(machine, MachineProfile):
        return machine
    return profile_from_machine(machine, platform=machine.name)


def _line_flops(n: int, backend: str) -> float:
    """FLOPs of one C2C line of length n — the single source of truth.

    "xla": 5 n log2 n butterflies.  "matmul": the four-step path's two
    complex contractions over a balanced split, ~8 real FLOPs per complex
    MAC over n*(n1+n2) MACs — more raw FLOPs but MXU-shaped, which is what
    makes the backend an autotuning decision.  "pallas": the kernel's own
    count (``kernels.ops.line_flops``: MXU matmuls plus VPU tile updates),
    priced at one blended rate (``backend_flops["pallas"]``, measured by
    ``calibrate`` at its line length).
    """
    if backend == "pallas":
        from repro.kernels.ops import line_flops  # deferred: loads Pallas
        return float(sum(line_flops(n)))
    if backend == "matmul":
        from .transforms import factorize
        n1, n2 = factorize(n)
        return 8.0 * n * (n1 + n2)
    return 5.0 * n * math.log2(max(n, 2))


def fft_stage_flops(grid: Tuple[int, int, int], dims: Sequence[int],
                    c2c: bool = True) -> float:
    """FLOPs of one local stage over the whole grid: 5 n log2 n per line."""
    total = 0.0
    n_all = grid[0] * grid[1] * grid[2]
    for d in dims:
        n = grid[d]
        lines = n_all / n
        total += lines * _line_flops(n, "xla")
    return total * (1.0 if c2c else 0.5)


def fft_total_flops(grid: Tuple[int, int, int], c2c: bool = True) -> float:
    return fft_stage_flops(grid, (0, 1, 2), c2c)


def predict_fft_time(grid: Tuple[int, int, int], decomp: Decomposition,
                     axis_sizes: Dict[str, int], machine: Machine,
                     *, dtype_bytes: int = 8, n_chunks: int = 1,
                     sched_overhead_s: float = 0.0) -> Dict[str, float]:
    """Per-stage LogP prediction of one forward 3D FFT (Eq. 1-2).

    Returns component times; ``total`` honours the machine's overlap factor:
    overlap=0 sums compute+comm (bulk-sync), overlap=1 takes max (Eq. 2).
    """
    ranks = 1
    for a in decomp.mesh_axes:
        ranks *= axis_sizes[a]

    t_comp = 0.0
    for stage in decomp.stages:
        flops = fft_stage_flops(grid, stage.fft_dims) / ranks
        shape = local_shape(stage, grid, axis_sizes)
        touched = 2 * shape[0] * shape[1] * shape[2] * dtype_bytes
        t_comp += max(flops / machine.flops, touched / machine.mem_bw)

    t_comm = 0.0
    n_msgs = 0.0
    for stage, hop in zip(decomp.stages, decomp.redists):
        start = local_shape(stage, grid, axis_sizes)
        for mv, shape in hop_move_shapes(hop, start, axis_sizes):
            peers = axis_sizes[mv.mesh_axis]
            vol = transpose_cost_bytes(shape, dtype_bytes, peers)
            # Eq. 1: alpha * |S| + beta * m, per chunk round
            t_comm += (machine.net_alpha_s * (peers - 1) * n_chunks
                       + vol / machine.net_bw)
            n_msgs += (peers - 1) * n_chunks

    bulk = t_comp + t_comm
    overlapped = max(t_comp, t_comm)
    total = (1 - machine.overlap) * bulk + machine.overlap * overlapped
    return {
        "t_comp_s": t_comp,
        "t_comm_s": t_comm,
        "t_total_s": total + sched_overhead_s,
        "t_sched_s": sched_overhead_s,
        "messages": n_msgs,
        "ranks": ranks,
    }


def matmul_stage_flops(grid: Tuple[int, ...], dims: Sequence[int]) -> float:
    """FLOPs of one local stage on the four-step matmul backend
    (:func:`_line_flops` with backend="matmul" per line)."""
    total = 0.0
    n_all = 1
    for g in grid:
        n_all *= g
    for d in dims:
        n = grid[d]
        lines = n_all / n
        total += lines * _line_flops(n, "matmul")
    return total


def kind_dim_flops(eff_grid: Tuple[int, ...], grid: Tuple[int, ...], d: int,
                   kind: str, backend: str = "xla") -> float:
    """FLOPs of transforming dim ``d`` of the whole (effective) grid once.

    Kind-aware: ``rfft`` runs at the *logical* length ``grid[d]`` and does
    half the C2C butterflies on xla only — the matmul and pallas backends'
    ``_rfft`` computes the full C2C and trims the Hermitian half;
    ``dct2``/``dst2`` (and their inverses) are priced as the double-length
    C2C they are composed from.  Line counts always come from ``eff_grid``
    — the R2C frequency pad changes the array the later stages actually
    traverse.
    """
    n_all = 1.0
    for g in eff_grid:
        n_all *= g
    lines = n_all / eff_grid[d]
    family = KIND_FAMILY.get(kind, "c2c")
    if family == "r2c":
        f = _line_flops(grid[d], backend)
        if backend == "xla":
            f *= 0.5
    elif family == "r2r":
        f = _line_flops(2 * grid[d], backend)
    else:
        f = _line_flops(eff_grid[d], backend)
    return lines * f


def chunk_overlap_fraction(n_chunks: int) -> float:
    """Fraction of comm/compute overlap the chunked pipeline exposes.

    With n chunks, chunk k's collective runs under chunk k-1's FFT work, so
    all but one chunk round of the shorter phase hides: (n-1)/n.  n<=1 is
    the bulk-synchronous baseline (no overlap beyond what the machine model
    already grants).
    """
    if n_chunks <= 1:
        return 0.0
    return (n_chunks - 1) / n_chunks


def stage_comp_times(grid: Tuple[int, ...], decomp: Decomposition,
                     axis_sizes: Dict[str, int], machine, *,
                     backend: str = "xla", dtype_bytes: int = 8,
                     kinds: Optional[Sequence[str]] = None,
                     eff_grid: Optional[Tuple[int, ...]] = None
                     ) -> List[float]:
    """Per-stage local compute time (kind-aware roofline), one per stage."""
    prof = as_profile(machine)
    kinds = tuple(kinds) if kinds is not None else ("fft",) * len(grid)
    eff = tuple(eff_grid) if eff_grid is not None else tuple(grid)
    ranks = 1
    for a in decomp.mesh_axes:
        ranks *= axis_sizes[a]
    rate = prof.flops_for(backend)
    times = []
    for stage in decomp.stages:
        flops = 0.0
        for d in stage.fft_dims:
            family = KIND_FAMILY.get(kinds[d], "c2c")
            # Per-backend kind scales: xla uses the bare family keys,
            # pallas its own "pallas:<family>" throughput family, matmul
            # none (its analytic ratios are structural; the measured
            # correction lives entirely in backend_flops).
            scale = prof.scale_for(family, backend)
            flops += kind_dim_flops(eff, grid, d, kinds[d], backend) * scale
        shape = local_shape(stage, eff, axis_sizes)
        touched = 2 * dtype_bytes
        for s in shape:
            touched *= s
        times.append(max(flops / ranks / rate, touched / prof.eff_mem_bw))
    return times


def hop_cost_terms(grid: Tuple[int, ...], decomp: Decomposition,
                   axis_sizes: Dict[str, int], machine, *,
                   backend: str = "xla", dtype_bytes: int = 8,
                   kinds: Optional[Sequence[str]] = None,
                   eff_grid: Optional[Tuple[int, ...]] = None,
                   stage_times: Optional[Sequence[float]] = None
                   ) -> List[Tuple[float, float, float, float]]:
    """Per forward hop: ``(t_comp_next, t_comm_beta, alpha_round, msgs)``.

    The inputs of the scheduler's chunk-schedule policy engine
    (``scheduler.choose_chunk_schedule``) and of the per-hop pricing path
    of :func:`predict_plan_time`: ``t_comp_next`` is the downstream
    stage's local FFT time (the work a chunked hop can hide),
    ``t_comm_beta`` the hop's bandwidth term over its moves' calibrated
    per-mesh-axis ``beta``, ``alpha_round`` the latency cost of one chunk
    round (``alpha * (peers - 1)`` summed over moves, so
    ``T_comm(k) = beta + alpha_round * k``), and ``msgs`` the messages per
    chunk round.  Hybrid multi-move hops are priced on the block each
    ``all_to_all`` actually ships (``hop_move_shapes``).  Callers that
    already hold :func:`stage_comp_times`' result pass it as
    ``stage_times`` to avoid recomputing the per-stage roofline (the
    tuner's ranking pass runs this once per candidate).
    """
    prof = as_profile(machine)
    eff = tuple(eff_grid) if eff_grid is not None else tuple(grid)
    stage_t = (list(stage_times) if stage_times is not None
               else stage_comp_times(grid, decomp, axis_sizes, prof,
                                     backend=backend,
                                     dtype_bytes=dtype_bytes,
                                     kinds=kinds, eff_grid=eff_grid))
    terms = []
    for i, hop in enumerate(decomp.redists):
        start = local_shape(decomp.stages[i], eff, axis_sizes)
        beta = alpha = msgs = 0.0
        for mv, shape in hop_move_shapes(hop, start, axis_sizes):
            peers = axis_sizes[mv.mesh_axis]
            vol = transpose_cost_bytes(shape, dtype_bytes, peers)
            beta += vol / prof.bw_for(mv.mesh_axis)
            alpha += prof.alpha_for(mv.mesh_axis) * (peers - 1)
            msgs += peers - 1
        terms.append((stage_t[i + 1], beta, alpha, msgs))
    return terms


def predict_plan_time(grid: Tuple[int, ...], decomp: Decomposition,
                      axis_sizes: Dict[str, int], machine, *,
                      backend: str = "xla", n_chunks: int = 1,
                      dtype_bytes: int = 8,
                      sched_overhead_s: float = 0.0,
                      kinds: Optional[Sequence[str]] = None,
                      eff_grid: Optional[Tuple[int, ...]] = None,
                      chunk_schedule: Optional[Sequence[int]] = None,
                      cost_model: Optional[CostModel] = None
                      ) -> Dict[str, float]:
    """LogP/roofline prediction for one *candidate plan* (tuner pruning).

    Extends :func:`predict_fft_time` with the knobs the autotuner searches
    over: the local-FFT ``backend`` (flop count differs) and ``n_chunks``
    (more overlap, but ``n_chunks``x the per-message alpha cost).  The
    machine's own ``overlap`` floor still applies.

    The model is **kind-aware**: pass ``kinds`` (one transform kind per
    spatial dim) and ``eff_grid`` (the grid after R2C frequency padding,
    see ``pipeline.effective_grid``) and each stage is priced per
    :func:`kind_dim_flops` — R2C stages do half the work, R2R stages the
    double-length composition, and *transpose volumes use the padded grid*
    the pipeline actually moves.  Omitting them reproduces the legacy
    C2C-on-the-logical-grid model.  ``machine`` may be a bare
    :class:`Machine` or a calibrated :class:`MachineProfile` (per-backend
    flops, per-kind-family scales, per-mesh-axis alpha/beta).

    With a per-hop ``chunk_schedule`` (forward hop order, one entry per
    ``RedistHop``) the prediction switches to **hop-by-hop pricing**: each
    phase (hop + downstream stage) is ``scheduler.hop_phase_time`` at its
    *own* chunk count — the exact objective the scheduler's policy engine
    argmins per hop — so asymmetric schedules are priced on what each hop
    actually does instead of one global overlap fraction.  ``n_chunks`` is
    ignored when a schedule is given.
    """
    prof = as_profile(machine)
    kinds = tuple(kinds) if kinds is not None else ("fft",) * len(grid)
    eff = tuple(eff_grid) if eff_grid is not None else tuple(grid)

    ranks = 1
    for a in decomp.mesh_axes:
        ranks *= axis_sizes[a]

    stage_t = stage_comp_times(grid, decomp, axis_sizes, prof,
                               backend=backend, dtype_bytes=dtype_bytes,
                               kinds=kinds, eff_grid=eff)
    t_comp = sum(stage_t)
    hop_terms = hop_cost_terms(grid, decomp, axis_sizes, prof,
                               backend=backend, dtype_bytes=dtype_bytes,
                               kinds=kinds, eff_grid=eff,
                               stage_times=stage_t)

    if chunk_schedule is not None:
        sched = tuple(max(int(k), 1) for k in chunk_schedule)
        if len(sched) != len(hop_terms):
            raise ValueError(
                f"chunk_schedule {sched} has {len(sched)} entries for "
                f"{len(hop_terms)} hops of {decomp.name}")
        cm = cost_model if cost_model is not None else CostModel()
        # tau_s: Eq. 5 at zero transfer volume — the chunk's bytes are
        # already in the beta term; same rule as choose_chunk_schedule.
        tau_s = cm.steal_cost(TaskSpec(data_bytes=0))
        t_comm = 0.0
        n_msgs = 0.0
        total = stage_t[0]
        for (t_next, beta, alpha, msgs), k in zip(hop_terms, sched):
            t_comm += beta + alpha * k
            n_msgs += msgs * k
            total += hop_phase_time(t_next, beta, alpha, k, tau_s=tau_s,
                                    overlap_floor=prof.overlap)
        overlap = max([prof.overlap]
                      + [chunk_overlap_fraction(k) for k in sched])
        return {
            "t_comp_s": t_comp,
            "t_comm_s": t_comm,
            "t_total_s": total + sched_overhead_s,
            "t_sched_s": sched_overhead_s,
            "messages": n_msgs,
            "ranks": ranks,
            "overlap": overlap,
            "chunk_schedule": sched,
        }

    t_comm = 0.0
    n_msgs = 0.0
    for _, beta, alpha, msgs in hop_terms:
        t_comm += beta + alpha * n_chunks
        n_msgs += msgs * n_chunks

    overlap = max(prof.overlap, chunk_overlap_fraction(n_chunks))
    bulk = t_comp + t_comm
    overlapped = max(t_comp, t_comm)
    total = (1 - overlap) * bulk + overlap * overlapped
    return {
        "t_comp_s": t_comp,
        "t_comm_s": t_comm,
        "t_total_s": total + sched_overhead_s,
        "t_sched_s": sched_overhead_s,
        "messages": n_msgs,
        "ranks": ranks,
        "overlap": overlap,
    }


# ---------------------------------------------------------------------------
# Calibration harness: measure a MachineProfile from microbenchmarks.
# ---------------------------------------------------------------------------

def _time_best(fn, timer, repeats: int) -> float:
    """Best-of-``repeats`` wall time of ``fn()`` (first call warms/compiles)."""
    import jax
    jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(max(repeats, 1)):
        t0 = timer()
        jax.block_until_ready(fn())
        best = min(best, timer() - t0)
    return max(best, 1e-12)


def _calibrate_network(mesh, timer, repeats: int):
    """Per-mesh-axis all_to_all (alpha, bytes/s) from two message sizes.

    Axes of size 1 cannot be measured and are skipped (callers fall back to
    the base machine's constants for them).
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..compat import shard_map

    alpha: Dict[str, float] = {}
    bw: Dict[str, float] = {}
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    for axis, p in axis_sizes.items():
        if p <= 1:
            continue
        samples = []
        for rows_per_rank in (8, 512):
            rows = p * rows_per_rank
            x = jax.device_put(jnp.zeros((rows, 8 * p), jnp.float32),
                               NamedSharding(mesh, P(axis)))
            fn = jax.jit(shard_map(
                lambda b, _ax=axis: lax.all_to_all(
                    b, _ax, split_axis=1, concat_axis=0, tiled=True),
                mesh=mesh, in_specs=P(axis), out_specs=P(None, axis),
                check_vma=False))
            dt = _time_best(lambda: fn(x), timer, repeats)
            vol = transpose_cost_bytes((rows_per_rank, 8 * p), 4, p)
            samples.append((float(vol), dt))
        (v1, t1), (v2, t2) = samples
        if t2 <= t1 or v2 <= v1:
            continue  # timings too noisy to separate alpha from beta
        b = (v2 - v1) / (t2 - t1)
        a = max((t1 - v1 / b) / (p - 1), 0.0)
        alpha[axis] = a
        bw[axis] = b
    return alpha, bw


def calibrate(mesh=None, *, n: int = 256, batch: int = 1024,
              repeats: int = 3, timer=None, platform: Optional[str] = None,
              base: Optional[Machine] = None) -> MachineProfile:
    """Measure a :class:`MachineProfile` on the running hardware.

    Microbenchmarks (all through ``transforms.apply_1d``, i.e. the code the
    pipeline actually runs):

    * ``fft`` per backend ("xla"/"matmul"/"pallas") -> sustained FLOP/s
      per backend;
    * ``rfft`` and ``dct2`` vs ``fft``       -> per-kind-family time scales
      (for xla *and* pallas, each against its own analytic flop ratios),
      normalized so a scale of 1.0 means "the model's ratio is right on
      this machine";
    * an elementwise stream over 32 MiB     -> memory bandwidth;
    * ``all_to_all`` at two sizes per mesh axis with >1 device -> per-axis
      alpha/beta.  With no such axis (the 1-device case) the network terms
      stay at the base machine's model defaults and ``net_calibrated`` is
      False.

    The default ``(batch, n)`` workload is sized so each timed call does
    tens of MFLOPs (and the stream tens of MiB): per-dispatch overhead must
    not dominate, or the "measured rates" would encode launch latency and
    every backend would tie.  ``timer`` is injectable (tests pass a fake
    counter so no wall-clock enters the assertion).  The result persists in
    the wisdom file's ``"machine"`` section via ``TuningCache.put_machine``;
    ``tune()`` does this automatically unless ``REPRO_CALIBRATE=off``.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from . import transforms

    timer = timer if timer is not None else time.perf_counter
    platform = platform if platform is not None else jax.default_backend()
    if base is None:
        base = default_machine()

    rng = np.random.default_rng(0)
    xc = jnp.asarray((rng.standard_normal((batch, n))
                      + 1j * rng.standard_normal((batch, n))
                      ).astype(np.complex64))
    xr = jnp.asarray(rng.standard_normal((batch, n)).astype(np.float32))

    def bench(kind: str, backend: str, arr) -> float:
        fn = jax.jit(lambda a: transforms.apply_1d(a, -1, kind,
                                                   backend=backend))
        return _time_best(lambda: fn(arr), timer, repeats)

    backend_flops: Dict[str, float] = {}
    bench_s: Dict[str, float] = {}
    for backend in ("xla", "matmul", "pallas"):
        dt = bench("fft", backend, xc)
        bench_s[backend] = dt
        backend_flops[backend] = batch * _line_flops(n, backend) / dt

    # Reuse the xla fft timing as the kind-scale baseline: re-benchmarking
    # the identical op would both waste a compile+measure cycle and, under
    # timing noise, decouple kind_scale from backend_flops["xla"].
    t_c2c = bench_s["xla"]
    kind_scale = {"c2c": 1.0}
    # Measured time ratio / analytic flop ratio: honest even on backends
    # whose rfft is no faster than fft (scale comes out ~2x).
    t_r2c = bench("rfft", "xla", xr)
    kind_scale["r2c"] = max((t_r2c / t_c2c) / 0.5, 1e-6)
    t_r2r = bench("dct2", "xla", xr)
    r2r_ratio = _line_flops(2 * n, "xla") / _line_flops(n, "xla")
    kind_scale["r2r"] = max((t_r2r / t_c2c) / r2r_ratio, 1e-6)

    # The pallas backend's own per-kind throughput family, against *its*
    # analytic ratios: rfft is structurally the full C2C (ratio 1.0), R2R
    # the double-length four-step with the phase fused into the kernel
    # epilogue.  Measured drift from those ratios (epilogue savings,
    # interpret-mode overheads) lands here instead of distorting
    # backend_flops["pallas"].
    t_pc2c = bench_s["pallas"]
    kind_scale["pallas:r2c"] = max(bench("rfft", "pallas", xr) / t_pc2c,
                                   1e-6)
    p_r2r_ratio = (_line_flops(2 * n, "pallas") / _line_flops(n, "pallas"))
    kind_scale["pallas:r2r"] = max(
        (bench("dct2", "pallas", xr) / t_pc2c) / p_r2r_ratio, 1e-6)

    big = jnp.zeros((1 << 23,), jnp.float32)  # 32 MiB
    stream = jax.jit(lambda a: a * np.float32(1.0000001))
    mem_bw = 2.0 * big.size * 4 / _time_best(lambda: stream(big), timer,
                                             repeats)

    net_alpha: Dict[str, float] = {}
    net_bw_d: Dict[str, float] = {}
    if mesh is not None:
        net_alpha, net_bw_d = _calibrate_network(mesh, timer, repeats)

    return MachineProfile(
        base=base, platform=platform, calibrated=True,
        net_calibrated=bool(net_alpha),
        backend_flops=tuple(sorted(backend_flops.items())),
        kind_scale=tuple(sorted(kind_scale.items())),
        mem_bw=mem_bw,
        net_alpha_s=tuple(sorted(net_alpha.items())),
        net_bw=tuple(sorted(net_bw_d.items())))


def strong_scaling_curve(grid, decomp_factory, rank_list, machine,
                         **kw) -> Dict[int, Dict[str, float]]:
    """predict_fft_time across rank counts; decomp_factory(ranks)->(decomp, axis_sizes)."""
    out = {}
    for r in rank_list:
        decomp, sizes = decomp_factory(r)
        out[r] = predict_fft_time(grid, decomp, sizes, machine, **kw)
    return out
