"""Plan autotuner: the runtime picks the schedule (the paper's thesis).

The static API defaults (``decomp="pencil"``, ``n_chunks=1``, and the
local backend of ``transforms.default_backend``: the Pallas kernel on TPU
devices wherever it takes the lines and no kind is R2C, else ``"xla"``)
are fixed choices of the kind the paper argues a dynamic runtime should
make.  ``tune()`` closes that loop for one problem key
(global grid, mesh geometry, transform kinds, dtype, batch shape):

1. **enumerate** candidate plans — decomposition in {pencil, slab, hybrid}
   (hybrid: every contiguous stage grouping of the dims, the
   pencil-over-k-axes family) over every mesh-axis ordering that divides
   the grid, backend in {xla, matmul, pallas}, ``n_chunks`` in powers of
   two up to the free-dim size, plus — for multi-hop plans — the **per-hop
   chunk schedule** the scheduler policy engine proposes from the
   calibrated cost model (``scheduler.choose_chunk_schedule``: Eq. 7
   argmin per hop);
2. **prune** them with the LogP/roofline model (`perfmodel.predict_plan_time`)
   down to the ``top_k`` most promising survivors;
3. **measure** each survivor's compiled executable (the measurement also
   warms the in-process `PlanCache`, so the winning plan is free to call
   afterwards), always including the static default as the baseline so the
   winner can never regress it.  A candidate whose compiled footprint does
   not fit a device's memory (``fits_device``) is passed over before it
   runs, and the next-ranked one takes its place;
4. **record** the winner in a persistent JSON `TuningCache` keyed by the
   problem, the FFTW-wisdom analogue — later processes skip straight to 4.

``fft3d``/``fftnd`` consult this transparently via ``tuning="auto"``
(enumerate+measure, persistent) or ``tuning="heuristic"`` (model-only
argmin, no timing, no disk writes — it may *read* a previously stored
calibration profile).

**Calibration.**  The pruning model's machine constants are not hard-coded:
with ``machine=None`` (the default), ``tune()`` resolves a
:class:`~repro.core.perfmodel.MachineProfile` for the current platform —
loaded from the wisdom file's ``"machine"`` section when one was saved
before, and otherwise (in ``mode="auto"`` only) measured on the spot by
``perfmodel.calibrate()`` and persisted for every later process.
``mode="heuristic"`` keeps its zero-overhead contract: it uses a stored
profile when one is available but never runs the calibration
microbenchmarks itself.  Set ``REPRO_CALIBRATE=off`` to skip calibration
entirely and prune with the model-default constants.

The model itself is kind-aware: candidates are priced with the pipeline's
per-dim transform kinds and the R2C-padded effective grid
(``pipeline.effective_grid``), so R2C/R2R plans rank on their real costs
rather than as if they were C2C on the logical grid.
"""
from __future__ import annotations

import dataclasses
import itertools
import os
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from .decomp import describe_decomp, make_decomposition, validate_grid
from .perfmodel import (MachineProfile, _calibrate_network,
                        _time_best, calibrate, hop_cost_terms,
                        default_machine, predict_plan_time,
                        profile_from_machine)
from .pipeline import (PipelineSpec, chunk_sites, compile_pipeline,
                       effective_grid, input_struct, make_spec,
                       output_struct)
from .plan import (TunedPlan, TuningCache, global_tuning_cache,
                   parse_tuning_key, tuning_key)
from .scheduler import choose_chunk_schedule
from .transforms import default_backend

# The tuner's full backend space — mirrors ``transforms.LOCAL_BACKENDS``.
# "pallas" is the explicit MXU kernel (kernels/fft_matmul.py) with fused
# DCT twiddle; on the CPU platform it runs in interpret mode.
BACKENDS = ("xla", "matmul", "pallas")
OBJECTIVES = ("forward", "fwd+scale+inv")
# Deepest chunk count per hop a model-only choice makes (heuristic tuning,
# ``propose_chunk_schedule``).  The cost model has no memory term, and the
# chunk chains are independent, so XLA keeps more of them live the deeper
# the schedule: compiled for a v5e 2x2 mesh, a 1024^3 complex64 pencil
# needs 4 GiB of temporaries per chip at 8 chunks per hop, 7.5 GiB at 16,
# and does not fit the 16 GiB chip at 64.  Auto tuning enumerates deeper
# counts too and drops those that do not fit once compiled.
MODEL_MAX_CHUNKS = 16


@dataclasses.dataclass(frozen=True)
class Candidate:
    """One point of the tuner's search space."""

    decomp: str
    mesh_axes: Tuple[str, ...]
    backend: str
    n_chunks: int
    # Stage grouping for decomp="hybrid" (None for pencil/slab).
    dim_groups: Optional[Tuple[Tuple[int, ...], ...]] = None
    # Per-hop chunk schedule (forward hop order); None = uniform n_chunks.
    chunk_schedule: Optional[Tuple[int, ...]] = None

    @property
    def spec_chunks(self):
        """What ``make_spec(n_chunks=...)`` should receive."""
        return (self.chunk_schedule if self.chunk_schedule is not None
                else self.n_chunks)

    def describe(self) -> str:
        decomp = describe_decomp(self.decomp, self.dim_groups)
        chunks = (",".join(map(str, self.chunk_schedule))
                  if self.chunk_schedule is not None else str(self.n_chunks))
        return (f"{decomp}({','.join(self.mesh_axes)})/"
                f"{self.backend}/chunks={chunks}")


# (platform, mesh-axis name) pairs whose all_to_all terms this process
# already tried to measure — bounds recalibration to once per process per
# axis when the network terms remain unmeasurable (e.g. timings too noisy
# to split alpha from beta), without blocking later meshes whose axis
# names were never attempted.
_NET_UPGRADE_ATTEMPTED: set = set()


def resolve_profile(cache: Optional[TuningCache] = None, *, mesh=None,
                    allow_calibrate: bool = True,
                    **calibrate_kw) -> MachineProfile:
    """The calibrated :class:`MachineProfile` for this platform.

    Resolution order: ``REPRO_CALIBRATE=off`` -> model defaults
    (``calibrated=False``); a profile stored in ``cache``'s ``"machine"``
    section -> load it; otherwise calibrate (when ``allow_calibrate``),
    persisting the result back into ``cache`` so later processes skip the
    microbenchmarks.  A stored profile whose network terms were never
    measured (``net_calibrated=False`` — it was calibrated in a 1-device
    process) is *upgraded* when this process can do better: with a
    multi-device ``mesh`` and ``allow_calibrate``, calibration re-runs with
    the all_to_all benchmarks and the richer profile replaces the stored
    one.  ``calibrate_kw`` is forwarded to ``perfmodel.calibrate`` (tests
    inject a fake ``timer``).
    """
    platform = jax.default_backend()
    env = os.environ.get("REPRO_CALIBRATE", "auto").strip().lower()
    if env == "off":
        return profile_from_machine(default_machine(), platform=platform)
    multidev_axes = (
        {name for name, size in zip(mesh.axis_names, mesh.devices.shape)
         if size > 1} if mesh is not None else set())
    stored = None
    if cache is not None:
        raw = cache.get_machine(platform)
        if raw is not None:
            try:
                stored = MachineProfile.from_json(raw)
            except (KeyError, TypeError, ValueError):
                stored = None  # unreadable profile: recalibrate below
            if stored is not None:
                # Network terms are per mesh-axis *name*: a stored profile
                # (even a net_calibrated one) may not cover this mesh's
                # axes, so upgrade whenever a measurable axis is uncovered
                # and not already attempted by this process.
                uncovered = multidev_axes - set(dict(stored.net_alpha_s))
                pending = {ax for ax in uncovered
                           if (platform, ax) not in _NET_UPGRADE_ATTEMPTED}
                if not (allow_calibrate and pending):
                    return stored
    if not allow_calibrate:
        return profile_from_machine(default_machine(), platform=platform)
    _NET_UPGRADE_ATTEMPTED.update((platform, ax) for ax in multidev_axes)
    if stored is not None:
        # Upgrade path: only the per-axis network terms are missing —
        # re-running the full compute/kind/mem microbenchmarks would waste
        # seconds and overwrite the stored measurements with noisier ones.
        timer = calibrate_kw.get("timer") or time.perf_counter
        repeats = calibrate_kw.get("repeats", 3)
        alpha_new, bw_new = _calibrate_network(mesh, timer, repeats)
        alpha = dict(stored.net_alpha_s)
        alpha.update(alpha_new)
        bw = dict(stored.net_bw)
        bw.update(bw_new)
        prof = dataclasses.replace(
            stored, net_alpha_s=tuple(sorted(alpha.items())),
            net_bw=tuple(sorted(bw.items())), net_calibrated=bool(alpha))
    else:
        prof = calibrate(mesh=mesh, platform=platform, **calibrate_kw)
    if cache is not None:
        cache.put_machine(platform, prof.to_json())
    return prof


def _spec_for(mesh: Mesh, grid: Tuple[int, ...], cand_decomp: str,
              mesh_axes: Tuple[str, ...], kinds: Tuple[str, ...],
              backend: str, n_chunks, inverse: bool, n_batch: int,
              dim_groups=None) -> PipelineSpec:
    """``n_chunks`` is an int or a per-hop schedule (forward hop order)."""
    dec = make_decomposition(cand_decomp, mesh_axes, len(grid),
                             dim_groups=dim_groups)
    return make_spec(mesh, grid, dec, kinds, backend=backend,
                     n_chunks=n_chunks, inverse=inverse,
                     batch_spec=(None,) * n_batch)


def feasible_chunk_counts(spec: PipelineSpec, axis_sizes: Dict[str, int],
                          batch_shape: Tuple[int, ...] = (),
                          max_chunks: Optional[int] = None) -> List[int]:
    """Powers of two that evenly chunk every redistribution of ``spec``.

    For each redistribution the chunk dim is the one ``redistribute`` will
    pick (``pipeline.chunk_sites`` — which dodges the hop's exchange dims
    *and* the downstream stage's fft_dims); ``n_chunks`` must divide its
    local size at that stage (and be at most ``max_chunks``, if given).
    Returns at least ``[1]`` (the bulk path is always feasible).
    """
    sizes = []
    for d, size in chunk_sites(spec, axis_sizes):
        if d is None:
            return [1]  # some hop has no legal chunk dim: bulk only
        if size is None:
            if d >= len(batch_shape):
                return [1]  # batch extent unknown: don't guess
            sizes.append(batch_shape[d])
        else:
            sizes.append(size)
    counts = [1]
    n = 2
    cap = min(sizes) if sizes else 1
    if max_chunks is not None:
        cap = min(cap, max_chunks)
    while n <= cap and all(s % n == 0 for s in sizes):
        counts.append(n)
        n *= 2
    return counts


def feasible_hop_chunk_counts(spec: PipelineSpec,
                              axis_sizes: Dict[str, int],
                              batch_shape: Tuple[int, ...] = (),
                              max_chunks: Optional[int] = None
                              ) -> List[List[int]]:
    """Per executed hop: the powers of two that evenly chunk *that* hop.

    The per-hop generalization of :func:`feasible_chunk_counts`: a hop
    with no legal chunk dim contributes ``[1]`` without forcing the whole
    pipeline bulk — other hops keep their own feasible counts, which is
    what lets the policy engine assign heterogeneous depths.
    """
    out: List[List[int]] = []
    for d, size in chunk_sites(spec, axis_sizes):
        if d is None:
            out.append([1])
            continue
        if size is None:
            if d >= len(batch_shape):
                out.append([1])  # batch extent unknown: don't guess
                continue
            size = batch_shape[d]
        counts = [1]
        n = 2
        cap = size if max_chunks is None else min(size, max_chunks)
        while n <= cap and size % n == 0:
            counts.append(n)
            n *= 2
        out.append(counts)
    return out


def propose_chunk_schedule(spec: PipelineSpec, axis_sizes: Dict[str, int],
                           machine, *, backend: Optional[str] = None,
                           dtype_bytes: int = 8,
                           batch_shape: Tuple[int, ...] = (),
                           max_chunks: Optional[int] = None
                           ) -> Tuple[int, ...]:
    """The scheduler policy engine's per-hop chunk schedule for ``spec``.

    Feeds the calibrated per-mesh-axis all_to_all alpha/beta and the
    kind-aware per-stage FFT costs (``perfmodel.hop_cost_terms``) into
    ``scheduler.choose_chunk_schedule`` (Eq. 7 argmin per hop), restricted
    to each hop's feasible counts (``feasible_hop_chunk_counts``, i.e. the
    ``chunk_sites`` clamp) up to ``max_chunks`` (default
    ``MODEL_MAX_CHUNKS``: the model cannot see memory).  Returns the
    schedule in **forward hop order**
    (what ``make_spec`` and ``Candidate.chunk_schedule`` expect), whatever
    the spec's direction.
    """
    from .perfmodel import as_profile, stage_comp_times
    prof = as_profile(machine)
    cands = feasible_hop_chunk_counts(
        spec, axis_sizes, batch_shape,
        MODEL_MAX_CHUNKS if max_chunks is None else max_chunks)
    stage_t = stage_comp_times(spec.grid, spec.decomp, axis_sizes, prof,
                               backend=backend or spec.backend,
                               dtype_bytes=dtype_bytes, kinds=spec.kinds,
                               eff_grid=spec.eff_grid)
    terms = hop_cost_terms(spec.grid, spec.decomp, axis_sizes, prof,
                           backend=backend or spec.backend,
                           dtype_bytes=dtype_bytes, kinds=spec.kinds,
                           eff_grid=spec.eff_grid, stage_times=stage_t)
    if spec.inverse:
        # Executed hop j inverts forward hop H-1-j (same moves, same
        # volumes) and feeds forward stage H-1-j — whose compute time is
        # the *previous* forward stage's, not the next's.  Rebuild the
        # terms in execution order before choosing.
        fwd = terms[::-1]
        terms = [(stage_t[len(terms) - 1 - j],) + tuple(fwd[j][1:])
                 for j in range(len(fwd))]
    sched = choose_chunk_schedule(terms, cands,
                                  overlap_floor=prof.overlap)
    return sched if not spec.inverse else sched[::-1]


def _hybrid_groupings(ndim: int, n_axes: int
                      ) -> List[Tuple[Tuple[int, ...], ...]]:
    """Contiguous stage groupings a hybrid over ``n_axes`` axes can run.

    Every composition of the dims into ``g`` ordered groups for
    ``2 <= g <= min(ndim, n_axes + 1)`` (each of the ``g - 1`` hops needs
    at least one axis to move).
    """
    out: List[Tuple[Tuple[int, ...], ...]] = []
    for g in range(2, min(ndim, n_axes + 1) + 1):
        for cuts in itertools.combinations(range(1, ndim), g - 1):
            bounds = (0,) + cuts + (ndim,)
            out.append(tuple(tuple(range(bounds[i], bounds[i + 1]))
                             for i in range(g)))
    return out


def enumerate_candidates(grid: Tuple[int, ...], mesh: Mesh,
                         kinds: Tuple[str, ...], *, inverse: bool = False,
                         n_batch: int = 0,
                         batch_shape: Tuple[int, ...] = (),
                         backends: Sequence[str] = BACKENDS,
                         max_chunks: Optional[int] = None,
                         machine=None, dtype_bytes: int = 8
                         ) -> List[Candidate]:
    """All valid plans for this (grid, mesh, kinds) problem.

    Mesh-axis *orderings* are part of the space: on a (2, 4) mesh, pencil
    over ("data", "model") and ("model", "data") shard different dims with
    different fan-outs, and on imbalanced grids only some orderings divide
    the grid at every stage (``validate_grid`` filters those out).

    Hybrid schedules widen the space further: every contiguous stage
    grouping of the dims (``_hybrid_groupings``) over every ordering of the
    *full* axis pool — fewer transposes than pencil, more parallelism than
    slab, and the only family that works at all when the mesh has fewer
    than ``ndim - 1`` axes (e.g. 4-D grids on 2-axis meshes).  Groupings
    that are structurally the pencil (all singleton groups, one axis each)
    or the slab (one leading group over one axis) are skipped as
    duplicates.  Enumeration stays cheap — the prune-then-measure flow
    bounds what actually gets compiled and timed to ``top_k``.

    With ``machine`` (a :class:`Machine`/:class:`MachineProfile`), the
    scheduler's policy engine additionally proposes a **per-hop chunk
    schedule** for every multi-hop structural point and backend
    (:func:`propose_chunk_schedule`): when the Eq. 7 argmin differs across
    hops — an asymmetric pipeline — the heterogeneous schedule rides
    alongside the uniform counts as its own candidate.  (Uniform argmins
    add nothing: the uniform sweep already covers them.)
    """
    ndim = len(grid)
    names = tuple(mesh.axis_names)
    axis_sizes = dict(zip(names, mesh.devices.shape))
    # 2-D pencil and 2-D slab are the same two-stage structure; keep one.
    decomp_arity = [("pencil", ndim - 1)]
    if ndim > 2:
        decomp_arity.append(("slab", 1))
    points: List[Tuple[str, Tuple[str, ...], Optional[Tuple]]] = []
    for decomp_kind, arity in decomp_arity:
        for axes in itertools.permutations(names, arity):
            points.append((decomp_kind, axes, None))
    for groups in _hybrid_groupings(ndim, len(names)):
        g = len(groups)
        if g == ndim and len(names) == ndim - 1:
            continue  # structurally the pencil: one axis per boundary
        if g == 2 and len(names) == 1 and len(groups[-1]) == 1:
            continue  # structurally the slab over the single axis
        for axes in itertools.permutations(names, len(names)):
            points.append(("hybrid", axes, groups))
    out: List[Candidate] = []
    for decomp_kind, axes, groups in points:
        try:
            spec = _spec_for(mesh, grid, decomp_kind, axes, kinds,
                             "xla", 1, inverse, n_batch, dim_groups=groups)
            validate_grid(spec.decomp, spec.eff_grid, axis_sizes)
        except (ValueError, KeyError):
            continue
        chunk_counts = feasible_chunk_counts(
            spec, axis_sizes, batch_shape, max_chunks)
        for n_chunks in chunk_counts:
            for backend in backends:
                out.append(Candidate(decomp=decomp_kind, mesh_axes=axes,
                                     backend=backend, n_chunks=n_chunks,
                                     dim_groups=groups))
        if machine is not None and len(spec.decomp.redists) > 1:
            for backend in backends:
                sched = propose_chunk_schedule(
                    spec, axis_sizes, machine, backend=backend,
                    dtype_bytes=dtype_bytes, batch_shape=batch_shape,
                    max_chunks=max_chunks)
                if len(set(sched)) > 1:
                    out.append(Candidate(decomp=decomp_kind, mesh_axes=axes,
                                         backend=backend,
                                         n_chunks=max(sched),
                                         dim_groups=groups,
                                         chunk_schedule=sched))
    return out


def rank_candidates(cands: Sequence[Candidate], grid: Tuple[int, ...],
                    mesh: Mesh, machine,
                    dtype_bytes: int = 8,
                    kinds: Optional[Sequence[str]] = None
                    ) -> List[Tuple[float, Candidate]]:
    """(predicted seconds, candidate), cheapest first — the pruning pass.

    With ``kinds`` the model is kind-aware: each candidate is priced on its
    own R2C-padded effective grid (padding depends on the decomposition) and
    with per-kind stage costs.  ``kinds=None`` reproduces the legacy
    C2C-on-the-logical-grid pricing.  Every candidate is priced **hop by
    hop** (``predict_plan_time(chunk_schedule=...)``, a uniform count being
    the constant schedule) so heterogeneous and uniform schedules rank on
    the same Eq. 7 objective the policy engine optimizes — mixing the
    legacy whole-plan overlap formula with per-hop pricing would
    systematically favor whichever happened to be cheaper-formed.
    """
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    kinds = tuple(kinds) if kinds is not None else None
    ranked = []
    for cand in cands:
        dec = make_decomposition(cand.decomp, cand.mesh_axes, len(grid),
                                 dim_groups=cand.dim_groups)
        eff = (effective_grid(grid, dec, axis_sizes, kinds)
               if kinds is not None else None)
        sched = (cand.chunk_schedule if cand.chunk_schedule is not None
                 else (cand.n_chunks,) * len(dec.redists))
        pred = predict_plan_time(grid, dec, axis_sizes, machine,
                                 backend=cand.backend,
                                 n_chunks=cand.n_chunks,
                                 dtype_bytes=dtype_bytes,
                                 kinds=kinds, eff_grid=eff,
                                 chunk_schedule=sched)
        ranked.append((pred["t_total_s"], cand))
    ranked.sort(key=lambda t: t[0])
    return ranked


def synth_input(arg: jax.ShapeDtypeStruct, seed: int = 0) -> jax.Array:
    """A realistic, correctly-sharded input for one measurement run.

    C2C candidates get *genuinely complex* data — a float draw cast to
    complex would hand XLA an all-zero imaginary plane it can constant-fold
    or otherwise favor unrealistically — while rfft/dct pipelines get real
    data in the pipeline's real input dtype.
    """
    rng = np.random.default_rng(seed)
    # Draw at single precision unless the target is double: drawing at
    # numpy's float64 default would materialize 4x the host bytes of the
    # array being synthesized (2 GiB of temporaries for a 512^3 c64 grid).
    real_dt = (np.float64 if np.dtype(arg.dtype) in (np.complex128,
                                                     np.float64)
               else np.float32)
    if jnp.issubdtype(arg.dtype, jnp.complexfloating):
        host = (rng.standard_normal(arg.shape, dtype=real_dt)
                + 1j * rng.standard_normal(arg.shape, dtype=real_dt))
    else:
        host = rng.standard_normal(arg.shape, dtype=real_dt)
    return jax.device_put(jnp.asarray(host).astype(arg.dtype), arg.sharding)


def measure_candidate(cand: Candidate, grid: Tuple[int, ...], mesh: Mesh,
                      kinds: Tuple[str, ...], dtype, *,
                      inverse: bool = False,
                      batch_shape: Tuple[int, ...] = (),
                      repeats: int = 3,
                      objective: str = "forward") -> float:
    """Wall time of the candidate's compiled executable (best of repeats).

    Compilation goes through ``compile_pipeline``'s plan cache, so measuring
    doubles as warming: the winner's executable is already resident when the
    user calls ``fftnd`` afterwards.

    ``objective="fwd+scale+inv"`` times the full paired round trip instead
    — forward, an elementwise spectral scale (the eigenvalue-divide stand-
    in), inverse — which is what a :class:`~repro.core.api.PoissonSolver`
    actually runs per solve.  Both directions compile from the *same*
    candidate, so the forward winner's stage-0 layout is reused by the
    inverse and no relayout can appear between them.
    """
    spec = _spec_for(mesh, grid, cand.decomp, cand.mesh_axes, kinds,
                     cand.backend, cand.spec_chunks, inverse,
                     len(batch_shape), dim_groups=cand.dim_groups)
    exe = compile_pipeline(mesh, spec, batch_shape=batch_shape, dtype=dtype)
    arg = input_struct(mesh, spec, batch_shape, dtype)
    x = synth_input(arg)
    if objective == "fwd+scale+inv":
        out = output_struct(mesh, spec, batch_shape, dtype)
        inv_spec = _spec_for(mesh, grid, cand.decomp, cand.mesh_axes, kinds,
                             cand.backend, cand.spec_chunks, not inverse,
                             len(batch_shape), dim_groups=cand.dim_groups)
        inv_exe = compile_pipeline(mesh, inv_spec, batch_shape=batch_shape,
                                   dtype=out.dtype)
        scale = jax.jit(lambda a: a * 0.5)
        return _time_best(lambda: inv_exe(scale(exe(x))),
                          time.perf_counter, repeats)
    # _time_best's first call doubles as the warm-up (plus any lazy init).
    return _time_best(lambda: exe(x), time.perf_counter, repeats)


def device_memory_limit(mesh: Mesh) -> Optional[int]:
    """The smallest memory limit among the mesh's devices, as the runtime
    reports it (``memory_stats()["bytes_limit"]``), or ``None`` where a
    device reports none (the CPU platform)."""
    limits = []
    for dev in mesh.devices.flat:
        stats = dev.memory_stats()
        if not stats or "bytes_limit" not in stats:
            return None
        limits.append(int(stats["bytes_limit"]))
    return min(limits)


def fits_device(cand: Candidate, grid: Tuple[int, ...], mesh: Mesh,
                kinds: Tuple[str, ...], dtype, limit: int, *,
                inverse: bool = False, batch_shape: Tuple[int, ...] = (),
                objective: str = "forward") -> bool:
    """Whether each executable the candidate runs fits ``limit`` bytes per
    device: arguments, output and temporaries of the compiled program
    (``memory_analysis``).  Compiles through the plan cache, so a
    following :func:`measure_candidate` reuses the executables."""
    directions = [inverse] if objective == "forward" else [inverse,
                                                           not inverse]
    dt = dtype
    for direction in directions:
        spec = _spec_for(mesh, grid, cand.decomp, cand.mesh_axes, kinds,
                         cand.backend, cand.spec_chunks, direction,
                         len(batch_shape), dim_groups=cand.dim_groups)
        mem = compile_pipeline(mesh, spec, batch_shape=batch_shape,
                               dtype=dt).memory_analysis()
        used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes)
        if used > limit:
            return False
        dt = output_struct(mesh, spec, batch_shape, dt).dtype
    return True


def _default_candidate(cands: Sequence[Candidate],
                       backend: str = "xla") -> Optional[Candidate]:
    """The plan the static API would have used (baseline to never regress):
    pencil, bulk, and ``backend``, the default rule's choice for the
    problem (``transforms.default_backend``; xla off TPU devices)."""
    for cand in cands:
        if cand.backend == backend and cand.n_chunks == 1 \
                and cand.decomp == "pencil":
            return cand
    return cands[0] if cands else None


def resolve_tuned_plan(grid: Sequence[int], mesh: Mesh, *,
                       kinds: Optional[Sequence[str]] = None,
                       dtype=jnp.complex64, inverse: bool = False,
                       batch_shape: Sequence[int] = (), mode: str = "off",
                       cache: Optional[TuningCache] = None,
                       default: Optional[Candidate] = None,
                       objective: str = "forward") -> TunedPlan:
    """One :class:`TunedPlan` per tuning policy — the plan API's entry point.

    ``mode="off"`` wraps the caller's explicit ``default`` candidate in a
    ``source="default"`` plan (no search, no disk); ``"heuristic"``/``"auto"``
    delegate to :func:`tune`.  Returning a ``TunedPlan`` in every mode lets
    ``DistributedFFT`` carry a uniform record of *why* its schedule was
    chosen (``TunedPlan.describe()``), whether it came from the wisdom
    cache, a measurement run, or the static defaults.
    """
    if mode == "off":
        if default is None:
            raise ValueError("resolve_tuned_plan(mode='off') needs a "
                             "default Candidate")
        return TunedPlan(decomp=default.decomp,
                         mesh_axes=tuple(default.mesh_axes),
                         backend=default.backend, n_chunks=default.n_chunks,
                         predicted_s=0.0, measured_s=0.0, source="default",
                         dim_groups=default.dim_groups,
                         chunk_schedule=default.chunk_schedule)
    return tune(grid, mesh, kinds=kinds, dtype=dtype, inverse=inverse,
                batch_shape=batch_shape, mode=mode, cache=cache,
                objective=objective)


def tune(grid: Sequence[int], mesh: Mesh, *,
         kinds: Optional[Sequence[str]] = None, dtype=jnp.complex64,
         inverse: bool = False, batch_shape: Sequence[int] = (),
         mode: str = "auto", cache: Optional[TuningCache] = None,
         machine=None, top_k: int = 3,
         backends: Sequence[str] = BACKENDS,
         max_chunks: Optional[int] = None, repeats: int = 3,
         objective: str = "forward") -> TunedPlan:
    """Pick the best plan for one problem key; see the module docstring.

    ``mode="auto"``       enumerate -> prune -> measure top_k -> persist.
    ``mode="heuristic"``  model-only argmin; no timing, no disk writes.

    ``machine=None`` resolves the calibrated :class:`MachineProfile` via
    :func:`resolve_profile` (load from the wisdom file, or — in auto mode —
    calibrate and persist; ``REPRO_CALIBRATE=off`` forces model defaults).
    Pruning is kind-aware: candidates are priced with ``kinds`` and their
    decomposition's R2C-padded effective grid.  The search space includes
    the scheduler policy engine's per-hop chunk schedules (see
    :func:`enumerate_candidates`), each priced hop-by-hop.

    ``objective="fwd+scale+inv"`` measures the joint paired round trip
    (the PoissonSolver workload) instead of the forward transform alone,
    under its own wisdom key (``op=fwd+scale+inv``) so the joint winner
    never shadows a forward-only plan.

    The returned :class:`TunedPlan` carries the winning (decomp, mesh_axes,
    backend, n_chunks, chunk_schedule) plus its predicted and (for auto)
    measured times.  Only searches over the **unrestricted** space (all
    ``backends``, no ``max_chunks`` cap) are persisted: a restricted
    search's winner must never shadow — or poison — the plan an
    unrestricted caller would get.
    """
    grid = tuple(grid)
    batch_shape = tuple(batch_shape)
    kinds = tuple(kinds) if kinds is not None else ("fft",) * len(grid)
    if mode not in ("auto", "heuristic"):
        raise ValueError(f"tune mode must be auto|heuristic, got {mode!r}")
    if objective not in OBJECTIVES:
        raise ValueError(f"tune objective must be one of {OBJECTIVES}, "
                         f"got {objective!r}")
    unrestricted = set(BACKENDS).issubset(set(backends)) and max_chunks is None
    from repro.kernels.ops import unsupported  # deferred: loads Pallas
    platform = mesh.devices.flat[0].platform
    if unsupported(grid, dtype, platform):
        # The kernel cannot run these lines here (f64 on a chip, or lines
        # too long for its VMEM): not a candidate.
        backends = tuple(b for b in backends if b != "pallas")

    key = tuning_key(grid=grid, mesh_shape=tuple(mesh.devices.shape),
                     mesh_axes=tuple(mesh.axis_names), kinds=kinds,
                     dtype=str(jnp.dtype(dtype)), inverse=inverse,
                     batch_shape=batch_shape,
                     platform=jax.default_backend(),
                     op="fft" if objective == "forward" else objective)
    if mode == "auto":
        if cache is None:
            cache = global_tuning_cache()
        hit = cache.get(key)
        # A cached plan must also satisfy THIS call's search restrictions
        # (an earlier unrestricted run may have persisted e.g. a matmul
        # winner that a backends=("xla",) caller cannot use) — retune if not.
        if hit is not None and hit.backend in backends and (
                max_chunks is None or hit.n_chunks <= max_chunks):
            return hit

    if machine is None:
        # Heuristic mode stays measurement-free but still *reads* wisdom:
        # a profile calibrated by an earlier auto run (any process) is
        # loaded from the global cache when no explicit cache was passed.
        # (NB: `cache or ...` would be wrong — an empty TuningCache is
        # falsy through __len__.)
        profile_cache = cache if cache is not None else global_tuning_cache()
        machine = resolve_profile(profile_cache, mesh=mesh,
                                  allow_calibrate=(mode == "auto"))
    dtype_bytes = jnp.dtype(dtype).itemsize
    if max_chunks is None and mode == "heuristic":
        max_chunks = MODEL_MAX_CHUNKS  # the model alone cannot see memory
    cands = enumerate_candidates(grid, mesh, kinds, inverse=inverse,
                                 n_batch=len(batch_shape),
                                 batch_shape=batch_shape, backends=backends,
                                 max_chunks=max_chunks, machine=machine,
                                 dtype_bytes=dtype_bytes)
    if not cands:
        raise ValueError(
            f"no valid plan for grid {grid} on mesh "
            f"{dict(zip(mesh.axis_names, mesh.devices.shape))}")
    ranked = rank_candidates(cands, grid, mesh, machine, dtype_bytes,
                             kinds=kinds)

    if mode == "heuristic":
        pred, best = ranked[0]
        return TunedPlan(decomp=best.decomp, mesh_axes=best.mesh_axes,
                         backend=best.backend, n_chunks=best.n_chunks,
                         predicted_s=pred, measured_s=0.0,
                         source="heuristic", dim_groups=best.dim_groups,
                         chunk_schedule=best.chunk_schedule,
                         objective=objective)

    limit = device_memory_limit(mesh)

    def fits(cand: Candidate) -> bool:
        return limit is None or fits_device(
            cand, grid, mesh, kinds, dtype, limit, inverse=inverse,
            batch_shape=batch_shape, objective=objective)

    # The top_k best-ranked candidates that fit, in rank order.
    survivors: List[Candidate] = []
    for _, cand in ranked:
        if len(survivors) == max(top_k, 1):
            break
        if fits(cand):
            survivors.append(cand)
    baseline = _default_candidate(
        cands, default_backend(platform, grid, dtype, kinds)[0])
    if baseline is not None and baseline not in survivors and fits(baseline):
        survivors.append(baseline)
    if not survivors:
        raise ValueError(
            f"no plan for grid {grid} fits the {limit} bytes of device "
            f"memory on mesh {dict(zip(mesh.axis_names, mesh.devices.shape))}")
    predicted = {c: p for p, c in ranked}
    best_cand, best_time, baseline_time = None, float("inf"), 0.0
    for cand in survivors:
        t = measure_candidate(cand, grid, mesh, kinds, dtype,
                              inverse=inverse, batch_shape=batch_shape,
                              repeats=repeats, objective=objective)
        if cand == baseline:
            baseline_time = t
        if t < best_time:
            best_cand, best_time = cand, t
    plan = TunedPlan(decomp=best_cand.decomp, mesh_axes=best_cand.mesh_axes,
                     backend=best_cand.backend, n_chunks=best_cand.n_chunks,
                     predicted_s=predicted.get(best_cand, 0.0),
                     measured_s=best_time, source="measured",
                     baseline_s=baseline_time, ts=time.time(),
                     dim_groups=best_cand.dim_groups,
                     chunk_schedule=best_cand.chunk_schedule,
                     objective=objective)
    if unrestricted:
        # A restricted winner (e.g. backends=("xla",) or max_chunks=2) was
        # picked from a smaller space under the same key; persisting it
        # would permanently replace a better unrestricted plan.
        cache.put(key, plan)
    return plan


def warm_candidates(cache: TuningCache, mesh: Mesh, *,
                    platform: Optional[str] = None,
                    ops: Sequence[str] = ("fft",)
                    ) -> List[Dict[str, object]]:
    """Persisted tuning decisions this process could serve warm.

    Enumerates the wisdom file's keys (``TuningCache.items`` +
    ``parse_tuning_key``) and keeps those matching this ``platform`` and
    ``mesh`` geometry (shape *and* axis names — a plan tuned on a (2, 4)
    mesh is not the plan for a (4, 2) one) whose measured ``op`` is in
    ``ops``.  Each returned dict is the parsed problem plus its
    ``"tuned"`` :class:`TunedPlan` — everything ``plan_fft`` needs to
    rebuild (and recompile) the winning plan without a single measurement.
    Unreadable keys (other schema versions) are skipped, not raised on:
    warm-start must never be blocked by foreign wisdom.
    """
    platform = platform if platform is not None else jax.default_backend()
    mesh_shape = tuple(mesh.devices.shape)
    mesh_axes = tuple(mesh.axis_names)
    out: List[Dict[str, object]] = []
    for key, tuned in cache.items():
        prob = parse_tuning_key(key)
        if prob is None or prob["platform"] != platform:
            continue
        if prob["mesh_shape"] != mesh_shape or prob["mesh_axes"] != mesh_axes:
            continue
        if prob["op"] not in ops or prob["inverse"]:
            continue
        prob["tuned"] = tuned
        prob["key"] = key
        out.append(prob)
    return out
