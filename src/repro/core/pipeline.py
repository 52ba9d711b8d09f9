"""The distributed FFT pipeline (paper Alg. 1), stage-per-array, shard_map'd.

``build_pipeline`` assembles the full forward or inverse transform for a
(grid, decomposition, transform-kinds) triple on a named mesh:

    stage-1 local FFTs  ->  redistribution  ->  stage-2  ->  ...  -> stage-k

Every stage owns its own layout (``decomp.stages[i]``) — the stage-specific
DArray idea — and every redistribution is an ``all_to_all`` that may be
chunk-pipelined for compute/communication overlap.  The overlap depth is a
*per-hop* ``chunk_schedule`` (one entry per ``RedistHop``): a uniform int
``n_chunks`` is the legacy special case, while heterogeneous schedules give
an asymmetric pipeline (e.g. a hybrid 4-D plan whose first hop moves more
volume than its second) a different chunk count on each hop.  The
scheduler's policy engine (``scheduler.choose_chunk_schedule``) picks the
schedule from the calibrated cost model; ``make_spec`` clamps infeasible
entries per hop and records the ask.

R2C transforms pad the frequency dim up to the LCM of the mesh-axis sizes
that shard it downstream, so every stage keeps integral local shapes; the
inverse pipeline trims the pad before the final irfft.

Besides the fused monolithic pipeline, the same stages lower as separately
compiled **stage segments** (``build_segment``/``compile_segment``):
segment 0 is the stage-0 local transform, segment ``j >= 1`` is hop
``j-1``'s redistribution (at its own ``chunk_schedule`` entry) fused with
stage ``j``'s transform — exactly the ops the monolithic pipeline runs, in
the same order, so chaining the segments is bitwise identical to one fused
call.  Each segment carries a sharding-in/sharding-out contract
(``segment_in_spec``/``segment_out_spec``); boundary shapes/dtypes come
from abstract evaluation (``segment_structs``).  ``core.executor``
interleaves segments of *different* plans on this contract.

For the device trace, programs are named ``repro_fft_forward`` /
``repro_fft_inverse`` (``_seg<j>`` for segments) and, inside the shard_map
body, each stage's local transform runs under the ``jax.named_scope``
``stage<k>`` and each hop's exchange under ``hop<i>``, both indexed in the
direction's execution order.  Scopes are trace-time metadata: they cost
nothing at run time.  The body's parameter is ``repro_fft_in``: the ops
that read it carry its name and no program prefix, and no other program
names a parameter so.
"""
from __future__ import annotations

import dataclasses
import math
import warnings
from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..compat import shard_map
from . import transforms
from .decomp import Decomposition, StageLayout, axis_product, local_shape
from .plan import GLOBAL_PLAN_CACHE, plan_key
from .redistribute import (free_chunk_dim, largest_divisor_at_most,
                           redistribute)

INVERSE_KIND = {"fft": "ifft", "rfft": "irfft", "dct2": "dct3", "dst2": "dst3"}
# Unnormalized R2R pairs satisfy inv(fwd(x)) = 2N x; complex pairs are
# self-normalizing through jnp conventions.
R2R_INV_SCALE = {"dct3", "dst3"}


@dataclasses.dataclass(frozen=True)
class PipelineSpec:
    grid: Tuple[int, ...]               # logical (pre-padding) grid
    eff_grid: Tuple[int, ...]           # grid after R2C frequency padding
    decomp: Decomposition
    kinds: Tuple[str, ...]              # one transform kind per spatial dim
    backend: str
    # One chunk count per RedistHop, in *execution* order (i.e. aligned with
    # ``stage_order()``'s redists — reversed relative to ``decomp.redists``
    # for inverse specs).  A uniform legacy ``n_chunks=k`` is the schedule
    # ``(k,) * n_hops``; heterogeneous schedules give each hop its own
    # overlap depth.
    chunk_schedule: Tuple[int, ...]
    inverse: bool
    batch_spec: Tuple[Optional[str], ...]  # shardings of leading batch dims
    # Pre-clamp ask per hop, execution order (() = nothing was requested).
    chunk_schedule_requested: Tuple[int, ...] = ()

    @property
    def spatial_offset(self) -> int:
        return len(self.batch_spec)

    @property
    def n_chunks(self) -> int:
        """Back-compat scalar view of the schedule: the deepest hop."""
        return max(self.chunk_schedule, default=1)

    @property
    def n_chunks_requested(self) -> int:
        """Back-compat scalar view of the pre-clamp ask (0 = none)."""
        return max(self.chunk_schedule_requested, default=0)

    @property
    def uniform_chunks(self) -> bool:
        """True when both the ask and the schedule are hop-uniform."""
        return (len(set(self.chunk_schedule)) <= 1
                and len(set(self.chunk_schedule_requested)) <= 1)

    @property
    def chunk_clamped(self) -> bool:
        """True when some requested chunk count was clamped at spec time."""
        return (self.chunk_schedule_requested != ()
                and self.chunk_schedule_requested != self.chunk_schedule)

    @property
    def hop_clamps(self) -> Tuple[Tuple[int, int, int], ...]:
        """Per clamped hop: (hop index, requested, effective), exec order."""
        if not self.chunk_schedule_requested:
            return ()
        return tuple((i, ask, got) for i, (ask, got)
                     in enumerate(zip(self.chunk_schedule_requested,
                                      self.chunk_schedule))
                     if ask != got)

    def stage_order(self):
        stages = list(self.decomp.stages)
        redists = list(self.decomp.redists)
        if not self.inverse:
            return stages, redists
        # Reversing a hop reverses its moves LIFO with split/concat swapped,
        # so every intermediate layout is undone in the opposite order.
        return stages[::-1], [hop.inverse() for hop in redists[::-1]]

    def in_spec(self) -> P:
        stages, _ = self.stage_order()
        return P(*(self.batch_spec + stages[0].spec))

    def out_spec(self) -> P:
        stages, _ = self.stage_order()
        return P(*(self.batch_spec + stages[-1].spec))


def _freq_pad_target(decomp: Decomposition, axis_sizes: dict, nfreq: int) -> int:
    """Pad the R2C frequency dim (dim 0) so all later shardings divide it.

    Hybrid stages may shard dim 0 over *several* mesh axes at once (a small
    group absorbing a large axis pool), so the per-stage divisor is the
    product of the sharding axes' sizes; mid-hop layouts only ever hold a
    prefix of that tuple, whose product divides the full one.
    """
    divisor = 1
    for stage in decomp.stages[1:]:
        size = axis_product(stage.spec[0], axis_sizes)
        if size > 1:
            divisor = math.lcm(divisor, size)
    return ((nfreq + divisor - 1) // divisor) * divisor


def effective_grid(grid: Tuple[int, ...], decomp: Decomposition,
                   axis_sizes: dict,
                   kinds: Tuple[str, ...]) -> Tuple[int, ...]:
    """The grid the pipeline actually moves: R2C pads the frequency dim.

    For an ``rfft`` first kind, dim 0 becomes ``n//2 + 1`` rounded up to the
    LCM of every mesh-axis size that shards it downstream — a function of
    the *decomposition*, so two candidate plans for the same logical grid
    can transpose different volumes.  The tuner's kind-aware cost model
    (``perfmodel.predict_plan_time(kinds=..., eff_grid=...)``) prices
    candidates on this grid, not the logical one.
    """
    eff = list(grid)
    if kinds[0] == "rfft":
        eff[0] = _freq_pad_target(decomp, axis_sizes, grid[0] // 2 + 1)
    return tuple(eff)


def chunk_sites(spec: "PipelineSpec", axis_sizes: dict
                ) -> List[Tuple[Optional[int], Optional[int]]]:
    """Per hop: the (absolute chunk dim, its local size) chunking would use.

    ``(None, None)`` means the hop has no legal chunk dim (bulk only);
    ``(d, None)`` means the chunk dim is a leading batch dim whose extent
    the spec does not know.  Shared by the spec-time chunk clamp and the
    tuner's feasibility filter so both agree with what ``redistribute``
    will actually do.
    """
    offset = spec.spatial_offset
    ndim_total = offset + len(spec.eff_grid)
    stages, redists = spec.stage_order()
    sites: List[Tuple[Optional[int], Optional[int]]] = []
    for i, hop in enumerate(redists):
        avoid = tuple(d + offset for d in stages[i + 1].fft_dims)
        d = free_chunk_dim(hop, ndim_total, offset, avoid_dims=avoid)
        if d is None:
            sites.append((None, None))
        elif d < offset:
            sites.append((d, None))
        else:
            block = local_shape(stages[i], spec.eff_grid, axis_sizes)
            sites.append((d, block[d - offset]))
    return sites


def make_spec(mesh: Mesh, grid: Tuple[int, ...], decomp: Decomposition,
              kinds: Tuple[str, ...], *, backend: str = "xla",
              n_chunks=1, inverse: bool = False,
              batch_spec: Tuple[Optional[str], ...] = ()) -> PipelineSpec:
    """Build a :class:`PipelineSpec`, clamping infeasible chunk counts.

    ``n_chunks`` is either an int — a *uniform* schedule, clamped (legacy
    behaviour) to the largest count dividing every hop's chunk-dim size —
    or a per-hop sequence in **forward hop order** (``decomp.redists``
    order, regardless of ``inverse``), clamped hop-by-hop via the same
    ``chunk_sites``/``largest_divisor_at_most`` machinery ``redistribute``
    uses at trace time.  Every clamp is recorded
    (``spec.chunk_schedule_requested`` keeps the ask; ``describe()``
    reports it), so a tuner- or user-selected chunk count never aborts the
    plan on an odd grid.
    """
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    eff = effective_grid(tuple(grid), decomp, axis_sizes, tuple(kinds))
    n_hops = len(decomp.redists)
    if isinstance(n_chunks, int):
        uniform = True
        requested = (max(int(n_chunks), 1),) * n_hops
    else:
        uniform = False
        sched = tuple(int(c) for c in n_chunks)
        if len(sched) != n_hops:
            raise ValueError(
                f"chunk schedule {sched} has {len(sched)} entries but "
                f"{decomp.name} over grid {tuple(grid)} has {n_hops} "
                f"redistribution hops")
        if any(c < 1 for c in sched):
            raise ValueError(f"chunk schedule entries must be >= 1: {sched}")
        # The schedule is given in forward hop order; inverse pipelines
        # execute the hops LIFO, so entry i pairs with executed hop
        # n_hops-1-i (the hop-aware inversion of the schedule).
        requested = sched if not inverse else sched[::-1]
    spec = PipelineSpec(grid=tuple(grid), eff_grid=tuple(eff), decomp=decomp,
                        kinds=tuple(kinds), backend=backend,
                        chunk_schedule=requested, inverse=inverse,
                        batch_spec=tuple(batch_spec),
                        chunk_schedule_requested=requested)
    if all(c <= 1 for c in requested):
        return spec
    sites = chunk_sites(spec, axis_sizes)
    if uniform:
        ask = requested[0]
        sizes = [s for _, s in sites if s is not None]
        if sites and all(d is None for d, _ in sites):
            # No hop can legally chunk (e.g. an inverse slab: the hop plus
            # the next stage's fft_dims cover every dim) — the whole
            # pipeline is bulk, and the spec should say so up front rather
            # than warning per-hop at trace time.
            warnings.warn(
                f"no redistribution of grid {tuple(grid)} has a legal "
                f"chunk dim ({'inverse' if inverse else 'forward'} "
                f"{decomp.name}); running bulk instead of "
                f"n_chunks={ask}", RuntimeWarning, stacklevel=2)
            clamped = (1,) * n_hops
        else:
            # A uniform ask stays uniform: the largest count <= n_chunks
            # dividing every hop's chunk-dim size == the largest divisor
            # of their gcd (same helper redistribute uses for its per-hop
            # trace-time clamp, so the two sites agree).
            eff_chunks = (largest_divisor_at_most(math.gcd(*sizes), ask)
                          if sizes else ask)
            if eff_chunks != ask:
                warnings.warn(
                    f"n_chunks={ask} does not evenly chunk every "
                    f"redistribution of grid {tuple(grid)} on this mesh; "
                    f"clamped to {eff_chunks}", RuntimeWarning, stacklevel=2)
            clamped = (eff_chunks,) * n_hops
    else:
        # Per-hop schedule: clamp each entry independently against its own
        # hop's chunk site.  A hop with no legal chunk dim runs bulk; an
        # unknown batch-dim extent is left to redistribute's trace-time
        # clamp (the spec cannot know the size).
        per_hop = []
        for (d, size), ask in zip(sites, requested):
            if ask <= 1:
                per_hop.append(ask)
            elif d is None:
                per_hop.append(1)
            elif size is None:
                per_hop.append(ask)
            else:
                per_hop.append(largest_divisor_at_most(size, ask))
        clamped = tuple(per_hop)
        if clamped != requested:
            show = (lambda s: tuple(s) if not inverse else tuple(s[::-1]))
            warnings.warn(
                f"chunk schedule {show(requested)} is not feasible on every "
                f"redistribution of grid {tuple(grid)} on this mesh; "
                f"clamped per hop to {show(clamped)}",
                RuntimeWarning, stacklevel=2)
    return dataclasses.replace(spec, chunk_schedule=clamped)


def _named(fn: Callable, name: str) -> Callable:
    """``fn`` under ``name``: ``jax.jit`` names its program after the
    function, so the device trace tells each plan executable apart."""
    fn.__name__ = fn.__qualname__ = name
    return fn


def program_name(spec: PipelineSpec, segment: Optional[int] = None) -> str:
    """``repro_fft_forward`` / ``repro_fft_inverse``, with ``_seg<j>`` for
    stage segment ``j``."""
    name = "repro_fft_inverse" if spec.inverse else "repro_fft_forward"
    return name if segment is None else f"{name}_seg{segment}"


def _stage_transform(spec: PipelineSpec, stage: StageLayout,
                     index: int) -> Callable:
    """Local transform for one stage (may cover 2 dims for slabs), under
    the device scope ``stage<index>`` (execution order)."""
    off = spec.spatial_offset

    @jax.named_scope(f"stage{index}")
    def run(repro_fft_in: jax.Array) -> jax.Array:
        x = repro_fft_in
        dims = stage.fft_dims if not spec.inverse else stage.fft_dims[::-1]
        for d in dims:
            kind = spec.kinds[d]
            if spec.inverse:
                kind = INVERSE_KIND[kind]
            if kind == "irfft":
                # trim the frequency pad, then invert to the real length
                nfreq = spec.grid[0] // 2 + 1
                x = jax.lax.slice_in_dim(x, 0, nfreq, axis=d + off)
                x = transforms.apply_1d(x, d + off, "irfft",
                                        backend=spec.backend,
                                        irfft_n=spec.grid[0])
                continue
            x = transforms.apply_1d(x, d + off, kind, backend=spec.backend)
            if kind == "rfft":
                pad = spec.eff_grid[0] - (spec.grid[0] // 2 + 1)
                if pad:
                    cfg = [(0, 0)] * x.ndim
                    cfg[d + off] = (0, pad)
                    x = jnp.pad(x, cfg)
            if kind in R2R_INV_SCALE:
                x = x / (2.0 * spec.grid[d])
        return x

    return run


def _local_pipeline(spec: PipelineSpec) -> Callable:
    """The per-device function to be shard_map'd."""
    stages, redists = spec.stage_order()

    def run(repro_fft_in: jax.Array) -> jax.Array:
        off = spec.spatial_offset
        x = _stage_transform(spec, stages[0], 0)(repro_fft_in)
        for i, hop in enumerate(redists):
            nxt_stage = stages[i + 1]
            nxt = _stage_transform(spec, nxt_stage, i + 1)
            # The chunk dim must dodge the fused transform's dims, or the
            # per-chunk FFT would run over a split dim (the inverse-slab
            # bug); redistribute falls back to bulk when none is legal.
            # Each hop runs at its own schedule entry (chunk_schedule is
            # stored in execution order, so it indexes like ``redists``).
            avoid = tuple(d + off for d in nxt_stage.fft_dims)
            x = redistribute(x, hop, n_chunks=spec.chunk_schedule[i],
                             then=nxt, spatial_offset=off, avoid_dims=avoid,
                             hop_index=i)
        return x

    return run


def _for_devices(mesh: Mesh, spec: PipelineSpec, fn: Callable) -> Callable:
    """``fn`` traced for ``mesh``'s platform: the Pallas kernel runs
    interpreted only for CPU devices, so a plan for TPU devices compiles it
    with Mosaic whatever the process's default backend."""
    if spec.backend != "pallas":
        return fn
    from repro.kernels import ops  # deferred: loads Pallas
    platform = mesh.devices.flat[0].platform

    def run(repro_fft_in: jax.Array) -> jax.Array:
        with ops.for_platform(platform):
            return fn(repro_fft_in)

    return run


def build_pipeline(mesh: Mesh, spec: PipelineSpec) -> Callable:
    """shard_map the local pipeline over the mesh.  jit-compatible; named
    :func:`program_name`."""
    fn = shard_map(_for_devices(mesh, spec, _local_pipeline(spec)),
                   mesh=mesh, in_specs=spec.in_spec(),
                   out_specs=spec.out_spec(), check_vma=False)
    return _named(fn, program_name(spec))


def input_struct(mesh: Mesh, spec: PipelineSpec,
                 batch_shape: Tuple[int, ...] = (),
                 dtype=jnp.complex64) -> jax.ShapeDtypeStruct:
    """Shape/dtype/sharding of the pipeline's input array.

    Shared by compilation and by the autotuner's measurement harness (which
    must synthesize a correctly-sharded input for each candidate plan).
    """
    in_grid = spec.eff_grid if spec.inverse else spec.grid
    if not spec.inverse and spec.kinds[0] == "rfft" \
            and jnp.issubdtype(jnp.dtype(dtype), jnp.complexfloating):
        # R2C pipelines take real input; match the precision of the complex
        # dtype the caller asked for (complex128 -> float64 under x64).
        dtype = (jnp.float64 if jnp.dtype(dtype) == jnp.dtype(jnp.complex128)
                 else jnp.float32)
    shape = tuple(batch_shape) + tuple(in_grid)
    return jax.ShapeDtypeStruct(
        shape, dtype, sharding=NamedSharding(mesh, spec.in_spec()))


def output_struct(mesh: Mesh, spec: PipelineSpec,
                  batch_shape: Tuple[int, ...] = (),
                  dtype=jnp.complex64) -> jax.ShapeDtypeStruct:
    """Shape/dtype/sharding of the pipeline's output array.

    Derived by abstract evaluation so R2C padding / irfft trimming and every
    kind's dtype behaviour (e.g. real-in/real-out DCT pipelines) are priced
    by the pipeline itself rather than re-derived here.  Powers the plan
    API's ``out_struct``/``out_sharding`` introspection.
    """
    arg = input_struct(mesh, spec, batch_shape, dtype)
    out = jax.eval_shape(build_pipeline(mesh, spec), arg)
    return jax.ShapeDtypeStruct(
        out.shape, out.dtype, sharding=NamedSharding(mesh, spec.out_spec()))


def compile_pipeline(mesh: Mesh, spec: PipelineSpec,
                     batch_shape: Tuple[int, ...] = (),
                     dtype=jnp.complex64, *, use_cache: bool = True,
                     donate: bool = False):
    """Lower+compile the pipeline once and cache it (paper's plan cache).

    ``donate=True`` compiles a variant that donates the input buffer to the
    computation (zero-copy execute-many pipelines); it is part of the plan
    key, so donating and non-donating callers never share an executable.
    """
    arg = input_struct(mesh, spec, batch_shape, dtype)
    dtype = arg.dtype

    # The decomposition's own axis ordering is part of the key: pencil over
    # ("data", "model") and ("model", "data") compile to different shardings.
    # So is the hybrid stage grouping — two hybrids over the same axes with
    # different dim_groups compile to different pipelines.
    key = plan_key(kind=spec.kinds, grid=spec.grid, dtype=str(dtype),
                   decomp=(spec.decomp.name,) + tuple(spec.decomp.mesh_axes)
                   + (spec.decomp.dim_groups,),
                   mesh_shape=tuple(mesh.devices.shape),
                   mesh_axes=tuple(mesh.axis_names), backend=spec.backend,
                   # The full per-hop schedule, not a scalar summary: two
                   # plans whose schedules differ compile differently.
                   n_chunks=spec.chunk_schedule, inverse=spec.inverse,
                   extra=(tuple(batch_shape), bool(donate)))

    def builder():
        donate_argnums = (0,) if donate else ()
        return jax.jit(build_pipeline(mesh, spec),
                       donate_argnums=donate_argnums).lower(arg).compile()

    if not use_cache:
        return builder()
    return GLOBAL_PLAN_CACHE.get_or_create(key, builder).executable


# ---------------------------------------------------------------------------
# Stage segments: the pipeline split at its redistribution hops.
# ---------------------------------------------------------------------------
#
# Segment 0 applies the stage-0 local transform; segment j (1-based) applies
# hop j-1's redistribution — at that hop's own chunk_schedule entry — with
# stage j's transform fused per chunk, exactly like the monolithic
# _local_pipeline's loop body, so chained segments match the monolithic
# pipeline bit for bit.


def n_segments(spec: PipelineSpec) -> int:
    """Number of stage segments (== number of stages)."""
    return len(spec.decomp.stages)


def segment_in_spec(spec: PipelineSpec, index: int) -> P:
    """PartitionSpec of segment ``index``'s input (stage ``index-1`` layout;
    segment 0 takes the pipeline input layout).

    Both boundary specs read the *declared* stage layouts, so
    ``segment_out_spec(j) == segment_in_spec(j+1)`` holds by construction
    and cannot detect a corrupted layout chain; the static contract
    checker (:func:`repro.analysis.contracts.check_boundaries`) verifies
    the same boundary independently by replaying hop ``j``'s moves.
    """
    stages, _ = spec.stage_order()
    return P(*(spec.batch_spec + stages[max(index - 1, 0)].spec))


def segment_out_spec(spec: PipelineSpec, index: int) -> P:
    """PartitionSpec of segment ``index``'s output (stage ``index`` layout;
    see :func:`segment_in_spec` on how boundaries are verified)."""
    stages, _ = spec.stage_order()
    return P(*(spec.batch_spec + stages[index].spec))


def _local_segment(spec: PipelineSpec, index: int) -> Callable:
    """The per-device function of one stage segment (to be shard_map'd)."""
    stages, redists = spec.stage_order()
    if not 0 <= index < len(stages):
        raise ValueError(f"segment index {index} out of range for "
                         f"{len(stages)} stages")
    off = spec.spatial_offset
    stage_fn = _stage_transform(spec, stages[index], index)
    if index == 0:
        return stage_fn
    hop = redists[index - 1]
    avoid = tuple(d + off for d in stages[index].fft_dims)

    def run(repro_fft_in: jax.Array) -> jax.Array:
        return redistribute(repro_fft_in, hop,
                            n_chunks=spec.chunk_schedule[index - 1],
                            then=stage_fn, spatial_offset=off,
                            avoid_dims=avoid, hop_index=index - 1)

    return run


def build_segment(mesh: Mesh, spec: PipelineSpec, index: int) -> Callable:
    """shard_map one stage segment over the mesh.  jit-compatible; named
    :func:`program_name` with the segment's index."""
    fn = shard_map(_for_devices(mesh, spec, _local_segment(spec, index)),
                   mesh=mesh, in_specs=segment_in_spec(spec, index),
                   out_specs=segment_out_spec(spec, index),
                   check_vma=False)
    return _named(fn, program_name(spec, index))


def segment_structs(mesh: Mesh, spec: PipelineSpec,
                    batch_shape: Tuple[int, ...] = (),
                    dtype=jnp.complex64) -> List[jax.ShapeDtypeStruct]:
    """Shape/dtype/sharding at every segment boundary.

    ``n_segments + 1`` entries: entry ``j`` is segment ``j``'s input and
    entry ``j+1`` its output (entry 0 == ``input_struct``, the last entry
    matches ``output_struct``).  Derived by abstract evaluation so R2C
    padding, irfft trimming and per-kind dtype changes at interior
    boundaries are the pipeline's own, not re-derived.
    """
    structs = [input_struct(mesh, spec, batch_shape, dtype)]
    for j in range(n_segments(spec)):
        out = jax.eval_shape(build_segment(mesh, spec, j), structs[-1])
        structs.append(jax.ShapeDtypeStruct(
            out.shape, out.dtype,
            sharding=NamedSharding(mesh, segment_out_spec(spec, j))))
    return structs


def compile_segment(mesh: Mesh, spec: PipelineSpec, index: int,
                    batch_shape: Tuple[int, ...] = (),
                    dtype=jnp.complex64, *, use_cache: bool = True,
                    donate: bool = False,
                    in_struct: Optional[jax.ShapeDtypeStruct] = None):
    """Lower+compile one stage segment; cached in the LRU plan cache.

    ``dtype`` is the **plan input** dtype (segment boundary dtypes follow
    from it deterministically, so it suffices for the key).  ``donate=True``
    donates the segment's input buffer — the executor compiles interior
    segments donating so consecutive segments reuse hop workspaces
    (double-buffering), while segment 0 only donates when the caller
    donated the entry operand.  Callers that already hold
    :func:`segment_structs` pass the segment's input entry as
    ``in_struct`` to skip the abstract-eval chain.
    """
    if in_struct is None:
        in_struct = segment_structs(mesh, spec, batch_shape, dtype)[index]

    key = plan_key(kind=spec.kinds, grid=spec.grid, dtype=str(jnp.dtype(dtype)),
                   decomp=(spec.decomp.name,) + tuple(spec.decomp.mesh_axes)
                   + (spec.decomp.dim_groups,),
                   mesh_shape=tuple(mesh.devices.shape),
                   mesh_axes=tuple(mesh.axis_names), backend=spec.backend,
                   n_chunks=spec.chunk_schedule, inverse=spec.inverse,
                   # The segment marker keeps per-segment executables from
                   # ever colliding with the fused pipeline's entries.
                   extra=(tuple(batch_shape), bool(donate),
                          "segment", int(index)))

    def builder():
        donate_argnums = (0,) if donate else ()
        return jax.jit(build_segment(mesh, spec, index),
                       donate_argnums=donate_argnums).lower(
                           in_struct).compile()

    if not use_cache:
        return builder()
    return GLOBAL_PLAN_CACHE.get_or_create(key, builder).executable
