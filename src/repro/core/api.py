"""User-facing DaggerFFT-style API: first-class distributed FFT plans.

Mirrors the paper's §V-A surface — "define the transform once, let the
runtime own the schedule" — as an FFTW/AccFFT-style **plan handle**:

    plan = plan_fft(mesh, (64, 64, 64), kinds=("rfft", "fft", "fft"),
                    tuning="auto")
    yk = plan(x)                # forward (== plan.forward(x))
    x2 = plan.inverse(yk)       # paired inverse, same schedule
    print(plan.describe())      # decomp/backend/chunks + tuner evidence

Everything expensive happens **once, at plan time**: tuning (search +
measurement), calibration, spec construction and executable compilation.
A reused plan's ``.forward()``/``.inverse()`` does no tuning, no spec work
and no plan-cache lookups per call — it holds its compiled executables
directly.  Introspection comes along for free:

* ``plan.in_sharding`` / ``plan.out_sharding`` — the stage-0 / final-stage
  ``NamedSharding``; lay your producer out in ``in_sharding`` and pass
  ``sharded_in=True`` to skip the entry ``device_put`` round trip entirely
  (zero-copy sharded pipelines).  ``plan.forward(x, donate=True)`` further
  donates the input buffer to the computation.
* ``plan.in_struct`` / ``plan.out_struct`` — shape/dtype/sharding of the
  forward input/output (R2C frequency padding included).
* ``plan.describe()`` — chosen decomposition, backend and why, n_chunks,
  and the tuner's predicted vs. measured times.

**Autotuning** (the paper's thesis): pass ``tuning=`` instead of
hand-picking the knobs:

* ``tuning="off"``        (default) use explicit ``decomp``/``backend``/
  ``n_chunks`` as given.  With no ``backend=``, the local backend follows
  one rule (``transforms.default_backend``): the Pallas kernel when the
  mesh's devices are TPUs, the kernel takes every line length in the
  plan's dtype, and no kind is R2C; ``"xla"`` otherwise (on the CPU, for
  lines over the kernel's VMEM, for float64, for R2C).  ``describe()``
  states the reason and ``plan_cache_stats()["backend"]`` counts it;
* ``tuning="heuristic"``  rank every valid plan with the calibrated
  LogP/roofline perf model and take the argmin — no timing runs, no disk;
* ``tuning="auto"``       additionally *measure* the model's top-k
  surviving plans and persist the winner in the JSON wisdom cache
  (``~/.cache/repro-fft/tuning.json`` or ``$REPRO_TUNING_CACHE``), so later
  processes rehydrate the full plan description without searching.

Passing explicit ``decomp``/``backend``/``n_chunks`` together with
``tuning != "off"`` is deprecated (the tuner overrides them).

**Legacy wrappers** ``fftnd``/``fft3d``/``fft2d``/``ifftnd``/... keep their
historical call signatures; they are now thin shims that build (and
memoize, per problem key) a ``DistributedFFT`` and delegate to it — one
example:

    mesh = make_mesh((2, 2), ("data", "model"))
    xk = fft3d(x, mesh=mesh)                    # forward
    x2 = ifft3d(xk, mesh=mesh)                  # round-trip

``PoissonSolver`` (and its ``poisson_solve`` wrapper) is the
Oceananigans-style spectral Poisson solver built on one paired plan:
forward and inverse share a single tuning resolution and a cached
eigenvalue array (benchmarked in fig8_poisson).
"""
from __future__ import annotations

import threading
import warnings
from collections import OrderedDict
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding

from . import transforms
from .decomp import describe_decomp, make_decomposition, validate_grid
from .pipeline import (PipelineSpec, build_pipeline, compile_pipeline,
                       compile_segment, input_struct, make_spec,
                       output_struct, segment_structs)
from .plan import TunedPlan, TuningCache, env_capacity

_DEF_KINDS = ("fft", "fft", "fft")
_R2R_KINDS = ("dct2", "dst2")
TUNING_MODES = ("off", "heuristic", "auto")


def _default_fft_axes(mesh: Mesh, decomp: str, ndim: int) -> Tuple[str, ...]:
    """Pick mesh axes for the pencil/slab/hybrid process grid."""
    names = tuple(mesh.axis_names)
    if decomp == "pencil":
        need = ndim - 1
        # Prefer the canonical production axes if present.
        if need == 2 and {"data", "model"}.issubset(names):
            return ("data", "model")
        if len(names) < need:
            raise ValueError(
                f"pencil decomposition of {ndim} dims needs a >={need}D "
                f"mesh (consider decomp='hybrid')")
        return names[-need:]
    if decomp == "hybrid":
        # Hybrids put the whole axis pool in play — that is their point on
        # meshes too small for a pencil (ndim >= 4 on 2-axis meshes).
        if {"data", "model"}.issubset(names):
            extra = tuple(n for n in names if n not in ("data", "model"))
            return ("data", "model") + extra
        return names
    if "model" in names:
        return ("model",)
    return (names[-1],)


def _complex_for(dtype) -> jnp.dtype:
    """The complex dtype matching ``dtype``'s precision (c128 under x64)."""
    return jnp.dtype(jnp.result_type(jnp.dtype(dtype), jnp.complex64))


def _real_for(dtype) -> jnp.dtype:
    """The real dtype matching ``dtype``'s precision."""
    dt = jnp.dtype(dtype)
    if jnp.issubdtype(dt, jnp.complexfloating):
        return jnp.dtype(jnp.finfo(dt).dtype)
    return dt


def _forward_plan_dtype(x_dtype, kinds: Tuple[str, ...]) -> jnp.dtype:
    """The plan input dtype implied by a forward operand's dtype.

    R2C and R2R pipelines keep real input real; pure-C2C input is promoted
    to the *matching* complex dtype — float64 becomes complex128 under x64,
    never a silent complex64 downcast.
    """
    dt = jnp.dtype(x_dtype)
    if kinds[0] == "rfft" or any(k in _R2R_KINDS for k in kinds):
        return dt
    if jnp.issubdtype(dt, jnp.complexfloating):
        return dt
    return _complex_for(dt)


def _inverse_plan_dtype(y_dtype, kinds: Tuple[str, ...]) -> jnp.dtype:
    """The *forward* plan dtype implied by a spectral operand's dtype.

    ``ifftnd`` receives the forward output; the paired plan is keyed on the
    forward input dtype, which real-input pipelines (rfft / any R2R kind)
    take at the matching real precision.
    """
    dt = jnp.dtype(y_dtype)
    if kinds[0] == "rfft" or any(k in _R2R_KINDS for k in kinds):
        return _real_for(dt)
    if jnp.issubdtype(dt, jnp.complexfloating):
        return dt
    return _complex_for(dt)


class DistributedFFT:
    """A reusable distributed FFT plan: plan once, execute many.

    Owns the resolved schedule (decomposition, mesh axes, backend, chunk
    count), the forward *and* inverse pipeline specs, the input/output
    structs, and the compiled executables.  Construct via :func:`plan_fft`.

    Execution never re-plans: ``forward``/``inverse`` cast the operand if
    needed, lay it out in the stage-0 sharding (skipped with
    ``sharded_in=True`` for operands already so laid out), and invoke the
    held executable.
    """

    def __init__(self, mesh: Mesh, fwd_spec: PipelineSpec,
                 inv_spec: PipelineSpec, *,
                 batch_shape: Tuple[int, ...] = (), dtype=jnp.complex64,
                 tuned: Optional[TunedPlan] = None, tuning: str = "off",
                 precompiled: bool = True, shared: bool = False,
                 backend_why: str = ""):
        self.mesh = mesh
        # Why the plan runs its local backend (``describe()``).
        self.backend_why = backend_why or fwd_spec.backend
        self._fwd_spec = fwd_spec
        self._inv_spec = inv_spec
        self.batch_shape = tuple(batch_shape)
        self.tuned = tuned
        self.tuning = tuning
        self.precompiled = precompiled
        # Shared plans (wrapper-memoized: many callers hold the same object)
        # refuse input donation — the caller still owns the buffer.
        self.shared = shared
        # None until verify() runs; then True (clean) or False (findings).
        self.verified: Optional[bool] = None
        self._in_struct = input_struct(mesh, fwd_spec, self.batch_shape,
                                       dtype)
        self._out_struct = output_struct(mesh, fwd_spec, self.batch_shape,
                                         dtype)
        self._inv_in_struct = input_struct(mesh, inv_spec, self.batch_shape,
                                           self._out_struct.dtype)
        self._inv_out_struct = output_struct(mesh, inv_spec,
                                             self.batch_shape,
                                             self._out_struct.dtype)
        self._exe: Dict[Tuple[bool, bool], Any] = {}
        self._jit: Dict[Tuple[bool, bool], Callable] = {}
        self._segs: Dict[Tuple[bool, bool, bool], list] = {}
        self._seg_structs: Dict[bool, list] = {}
        self._build_lock = threading.Lock()
        if precompiled:
            # Planning pays the forward compile; the inverse compiles on
            # first .inverse() so forward-only users don't pay it twice.
            self._executable(inverse=False, donate=False)

    # -- introspection ------------------------------------------------------

    @property
    def grid(self) -> Tuple[int, ...]:
        """Logical (pre-padding) spatial grid."""
        return self._fwd_spec.grid

    @property
    def eff_grid(self) -> Tuple[int, ...]:
        """The grid the pipeline actually moves (R2C frequency-padded)."""
        return self._fwd_spec.eff_grid

    @property
    def kinds(self) -> Tuple[str, ...]:
        return self._fwd_spec.kinds

    @property
    def decomp(self) -> str:
        return self._fwd_spec.decomp.name

    @property
    def mesh_axes(self) -> Tuple[str, ...]:
        return tuple(self._fwd_spec.decomp.mesh_axes)

    @property
    def backend(self) -> str:
        return self._fwd_spec.backend

    @property
    def n_chunks(self) -> int:
        """Deepest hop of the chunk schedule (back-compat scalar view)."""
        return self._fwd_spec.n_chunks

    @property
    def chunk_schedule(self) -> Tuple[int, ...]:
        """Per-hop chunk counts of the forward pipeline (one per hop)."""
        return self._fwd_spec.chunk_schedule

    @property
    def dtype(self) -> jnp.dtype:
        """Forward input dtype."""
        return jnp.dtype(self._in_struct.dtype)

    @property
    def in_struct(self) -> jax.ShapeDtypeStruct:
        """Shape/dtype/sharding of the forward input."""
        return self._in_struct

    @property
    def out_struct(self) -> jax.ShapeDtypeStruct:
        """Shape/dtype/sharding of the forward output."""
        return self._out_struct

    @property
    def in_sharding(self) -> NamedSharding:
        """Stage-0 sharding — lay inputs out like this for ``sharded_in``."""
        return self._in_struct.sharding

    @property
    def out_sharding(self) -> NamedSharding:
        """Final-stage sharding of the forward output."""
        return self._out_struct.sharding

    @property
    def inv_in_struct(self) -> jax.ShapeDtypeStruct:
        """Shape/dtype/sharding of the inverse input (== forward output)."""
        return self._inv_in_struct

    @property
    def inv_out_struct(self) -> jax.ShapeDtypeStruct:
        """Shape/dtype/sharding of the inverse output."""
        return self._inv_out_struct

    def describe(self) -> str:
        """Multi-line report: schedule, layouts, and tuning evidence."""
        mesh_geom = dict(zip(self.mesh.axis_names,
                             self.mesh.devices.shape))
        tuned_line = (self.tuned.describe() if self.tuned is not None
                      else "untuned")
        with self._build_lock:  # _executable may be inserting concurrently
            exe_keys = list(self._exe)
        compiled = sorted(
            ("inverse" if inv else "forward") + (" (donating)" if don else "")
            for inv, don in exe_keys)
        decomp = describe_decomp(self.decomp,
                                 self._fwd_spec.decomp.dim_groups)
        fwd = self._fwd_spec
        if fwd.uniform_chunks:
            chunks = str(self.n_chunks)
            if fwd.chunk_clamped:
                chunks += f" (clamped from {fwd.n_chunks_requested})"
        else:
            # Heterogeneous per-hop schedule: show every hop's depth and
            # any per-hop clamps against the original ask.
            chunks = f"per-hop {fwd.chunk_schedule}"
            if fwd.chunk_clamped:
                chunks += (f" (clamped from {fwd.chunk_schedule_requested}"
                           f" at hop"
                           f"{'s' if len(fwd.hop_clamps) > 1 else ''} "
                           + ",".join(str(i) for i, _, _ in fwd.hop_clamps)
                           + ")")
        inv = self._inv_spec
        if inv.chunk_schedule[::-1] != fwd.chunk_schedule:
            # e.g. a chunked slab whose inverse has no legal chunk dim
            if inv.uniform_chunks:
                chunks += f", inverse={inv.n_chunks}"
            else:
                chunks += f", inverse per-hop {inv.chunk_schedule}"
        lines = [
            f"DistributedFFT(grid={self.grid}, kinds={self.kinds}, "
            f"batch={self.batch_shape}, dtype={self.dtype.name})",
            f"  mesh: {mesh_geom}",
            f"  schedule: {decomp} over {self.mesh_axes}, "
            f"backend={self.backend}, n_chunks={chunks} "
            f"(tuning={self.tuning!r})",
            f"  backend: {self.backend_why}",
            f"  tuner: {tuned_line}",
            f"  in:  {self._in_struct.shape} {self._in_struct.dtype} "
            f"{self._fwd_spec.in_spec()}",
            f"  out: {self._out_struct.shape} {self._out_struct.dtype} "
            f"{self._fwd_spec.out_spec()}",
            f"  compiled: [{', '.join(compiled) or 'none'}] "
            f"(precompiled={self.precompiled}"
            + (", shared" if self.shared else "") + ")",
            "  verified: " + ("not verified (run plan.verify())"
                              if self.verified is None else
                              "contracts clean" if self.verified else
                              "FINDINGS (see plan.verify() report)"),
        ]
        return "\n".join(lines)

    def __repr__(self) -> str:
        return (f"DistributedFFT(grid={self.grid}, kinds={self.kinds}, "
                f"decomp={self.decomp!r}, mesh_axes={self.mesh_axes}, "
                f"backend={self.backend!r}, n_chunks={self.n_chunks})")

    # -- execution ----------------------------------------------------------

    def _executable(self, *, inverse: bool, donate: bool):
        key = (inverse, donate)
        exe = self._exe.get(key)
        if exe is None:
            with self._build_lock:
                exe = self._exe.get(key)
                if exe is None:
                    spec = self._inv_spec if inverse else self._fwd_spec
                    struct = (self._inv_in_struct if inverse
                              else self._in_struct)
                    exe = compile_pipeline(self.mesh, spec,
                                           batch_shape=self.batch_shape,
                                           dtype=struct.dtype, donate=donate)
                    self._exe[key] = exe
        return exe

    def _jitted(self, *, inverse: bool, donate: bool) -> Callable:
        key = (inverse, donate)
        fn = self._jit.get(key)
        if fn is None:
            with self._build_lock:
                fn = self._jit.get(key)
                if fn is None:
                    spec = self._inv_spec if inverse else self._fwd_spec
                    fn = jax.jit(build_pipeline(self.mesh, spec),
                                 donate_argnums=(0,) if donate else ())
                    self._jit[key] = fn
        return fn

    # -- stage segments (the plan-stream executor's unit of work) -----------

    def pipeline_spec(self, *, inverse: bool = False) -> PipelineSpec:
        """The lowered :class:`PipelineSpec` of one direction."""
        return self._inv_spec if inverse else self._fwd_spec

    def verify(self, *, tune_cache: Optional[TuningCache] = None,
               strict: bool = False):
        """Statically check this plan's sharding contracts (executes
        nothing): every segment-boundary layout re-derived by hop replay,
        chunk-schedule and grid/mesh divisibility, the plan-key
        collision audit (plus wisdom keys when ``tune_cache`` is given),
        and the buffer-provenance audit (a ``shared`` plan holding
        donating compiled variants — possible when the flag was set
        after compilation — is flagged DON002).
        Returns the :class:`~repro.analysis.DiagnosticReport`;
        ``strict=True`` raises
        :class:`~repro.analysis.PlanVerificationError` on any error.
        ``describe()`` reports the outcome."""
        from ..analysis import (PlanVerificationError, check_plan,
                                check_plan_buffers)
        report = check_plan(self, tune_cache=tune_cache)
        report.extend(check_plan_buffers(self))
        self.verified = not report.errors
        if strict and report.errors:
            raise PlanVerificationError(report, context=repr(self))
        return report

    def _direction_dtype(self, inverse: bool):
        return (self._inv_in_struct if inverse else self._in_struct).dtype

    def segment_boundary_structs(self, *, inverse: bool = False) -> list:
        """Shape/dtype/sharding at every stage-segment boundary
        (``n_segments + 1`` entries; cached per direction)."""
        structs = self._seg_structs.get(inverse)
        if structs is None:
            with self._build_lock:
                structs = self._seg_structs.get(inverse)
                if structs is None:
                    spec = self.pipeline_spec(inverse=inverse)
                    structs = segment_structs(self.mesh, spec,
                                              self.batch_shape,
                                              self._direction_dtype(inverse))
                    self._seg_structs[inverse] = structs
        return structs

    def segments(self, *, inverse: bool = False, donate_input: bool = False,
                 donate_intermediates: bool = True) -> list:
        """Per-segment compiled executables (LRU plan-cache backed).

        Chaining them over an input is bitwise identical to the fused
        ``__call__`` path.  Interior segments compile with input donation
        by default (their inputs are the caller's own intermediates — the
        executor's double-buffered hop workspaces); ``donate_input=True``
        additionally donates segment 0's operand buffer — refused for
        shared plans, whose callers still own their buffers.
        """
        if donate_input and self.shared:
            raise ValueError(
                "refusing donate_input=True for a shared (wrapper-memoized) "
                "plan: other callers may still own the input buffer")
        key = (inverse, donate_input, donate_intermediates)
        segs = self._segs.get(key)
        if segs is None:
            structs = self.segment_boundary_structs(inverse=inverse)
            with self._build_lock:
                segs = self._segs.get(key)
                if segs is None:
                    spec = self.pipeline_spec(inverse=inverse)
                    dtype = self._direction_dtype(inverse)
                    segs = [
                        compile_segment(
                            self.mesh, spec, j, self.batch_shape, dtype,
                            donate=(donate_input if j == 0
                                    else donate_intermediates),
                            in_struct=structs[j])
                        for j in range(len(structs) - 1)]
                    self._segs[key] = segs
        return segs

    def submit(self, x: jax.Array, *, executor, inverse: bool = False,
               sharded_in: bool = False, donate: bool = False,
               tag: Optional[str] = None) -> int:
        """Enqueue this plan on a ``PlanStreamExecutor``; returns the queue
        index (outputs come from ``executor.run()`` in submit order)."""
        return executor.submit(self, x, inverse=inverse,
                               sharded_in=sharded_in, donate=donate, tag=tag)

    def execute_many(self, xs: Sequence[jax.Array], *, inverse: bool = False,
                     sharded_in: bool = False, donate: bool = False,
                     executor=None, **executor_kw) -> list:
        """Run many operands through this plan as one interleaved segment
        stream (see ``core.executor``); returns outputs in operand order,
        bitwise identical to calling the plan on each solo.  Pass an
        existing ``executor`` to mix with other plans' entries, else one is
        built from ``executor_kw``."""
        from .executor import PlanStreamExecutor  # deferred: avoid cycle
        ex = executor if executor is not None \
            else PlanStreamExecutor(**executor_kw)
        for x in xs:
            ex.submit(self, x, inverse=inverse, sharded_in=sharded_in,
                      donate=donate)
        return ex.run()

    # -- fused execution ----------------------------------------------------

    def _execute(self, x: jax.Array, *, inverse: bool, sharded_in: bool,
                 donate: bool) -> jax.Array:
        if donate and self.shared:
            raise ValueError(
                "refusing donate=True on a shared (wrapper-memoized) plan: "
                "other callers may still own the input buffer; build a "
                "private plan via plan_fft for donation")
        struct = self._inv_in_struct if inverse else self._in_struct
        if tuple(x.shape) != tuple(struct.shape):
            raise ValueError(
                f"{'inverse' if inverse else 'forward'} operand has shape "
                f"{tuple(x.shape)}, plan expects {tuple(struct.shape)} "
                f"(batch={self.batch_shape}, grid={self.grid})")
        if x.dtype != struct.dtype:
            x = x.astype(struct.dtype)
        if not self.precompiled:
            return self._jitted(inverse=inverse, donate=donate)(x)
        exe = self._executable(inverse=inverse, donate=donate)
        if not sharded_in:
            x = jax.device_put(x, struct.sharding)
        return exe(x)

    def forward(self, x: jax.Array, *, sharded_in: bool = False,
                donate: bool = False) -> jax.Array:
        """Forward transform.  ``sharded_in=True`` trusts ``x`` to already
        carry ``self.in_sharding`` and skips the entry ``device_put``;
        ``donate=True`` donates the input buffer to the computation.
        Runs under the host span ``repro.forward``."""
        with jax.profiler.TraceAnnotation("repro.forward"):
            return self._execute(x, inverse=False, sharded_in=sharded_in,
                                 donate=donate)

    def inverse(self, y: jax.Array, *, sharded_in: bool = False,
                donate: bool = False) -> jax.Array:
        """Inverse transform.  A forward output is already laid out in the
        inverse input sharding, so ``plan.inverse(plan.forward(x),
        sharded_in=True)`` round-trips with zero redundant copies.  Runs
        under the host span ``repro.inverse``."""
        with jax.profiler.TraceAnnotation("repro.inverse"):
            return self._execute(y, inverse=True, sharded_in=sharded_in,
                                 donate=donate)

    def __call__(self, x: jax.Array, **kw) -> jax.Array:
        return self.forward(x, **kw)


def _validate_dim_groups(groups: Tuple[Tuple[int, ...], ...],
                         ndim: int) -> None:
    """Early, specific validation of a hybrid stage grouping.

    ``hybrid_nd`` re-checks the same invariants, but only after tuning
    policy resolution — by which point the error loses the caller's
    context.  Failing here names exactly what is wrong with the argument.
    """
    if not groups or any(not g for g in groups):
        raise ValueError(
            f"plan_fft: dim_groups must be non-empty groups of dims, "
            f"got {groups!r}")
    flat = [d for g in groups for d in g]
    if len(set(flat)) != len(flat):
        dupes = sorted({d for d in flat if flat.count(d) > 1})
        raise ValueError(
            f"plan_fft: dim_groups {groups!r} repeat dim(s) {dupes} — "
            f"each dim belongs to exactly one stage group")
    missing = sorted(set(range(ndim)) - set(flat))
    extra = sorted(set(flat) - set(range(ndim)))
    if missing or extra:
        raise ValueError(
            f"plan_fft: dim_groups {groups!r} must cover dims "
            f"0..{ndim - 1} exactly"
            + (f"; missing {missing}" if missing else "")
            + (f"; out of range {extra}" if extra else ""))
    if flat != list(range(ndim)):
        raise ValueError(
            f"plan_fft: dim_groups {groups!r} must be contiguous groups "
            f"in ascending dim order, i.e. flatten to "
            f"{tuple(range(ndim))}")


VALIDATE_MODES = ("off", "warn", "strict")


def plan_fft(mesh: Mesh, grid: Sequence[int], *,
             kinds: Optional[Sequence[str]] = None,
             batch_shape: Sequence[int] = (), dtype=None,
             decomp: Optional[str] = None, backend: Optional[str] = None,
             n_chunks=None,
             mesh_axes: Optional[Sequence[str]] = None,
             dim_groups: Optional[Sequence[Sequence[int]]] = None,
             tuning: str = "off",
             tune_cache: Optional[TuningCache] = None,
             tune_objective: str = "forward",
             precompiled: bool = True,
             validate: str = "off") -> DistributedFFT:
    """Build a :class:`DistributedFFT` plan for the trailing ``len(grid)``
    dims of ``batch_shape + grid``-shaped operands.

    All planning work — tuning policy resolution, spec construction,
    validation and (with ``precompiled=True``) forward compilation — happens
    here, once.  ``dtype`` is the forward *input* dtype and defaults to
    complex64 for pure-C2C kinds and float32 for R2C/R2R pipelines.

    ``decomp`` may be "pencil", "slab" or "hybrid" (the pencil-over-k-axes
    family: contiguous stage groups of dims, optionally given explicitly as
    ``dim_groups``, over any number of mesh axes).  When unset, it defaults
    to "pencil" on meshes with enough axes and to "hybrid" otherwise — a
    4-D grid on a 2-axis mesh plans out of the box as two 2-dim slab
    stages with one transpose, where a pencil would demand 3 axes.

    ``n_chunks`` is an int (uniform overlap depth on every redistribution
    hop) or a per-hop sequence — one entry per hop, forward hop order —
    giving each hop its own chunk count (e.g. ``n_chunks=(4, 2)`` for a
    3-stage pencil whose first transpose overlaps deeper than its second).
    Infeasible entries clamp per hop, recorded on the spec and reported by
    ``describe()``.  The tuner searches per-hop schedules on its own (the
    scheduler policy engine proposes them); ``tune_objective`` selects what
    auto-tuning measures ("forward", or the joint "fwd+scale+inv" round
    trip the :class:`PoissonSolver` runs).

    ``validate`` runs the static contract checker
    (:func:`repro.analysis.check_plan`) on the finished plan: ``"warn"``
    reports findings as a warning, ``"strict"`` raises
    :class:`~repro.analysis.PlanVerificationError`; default ``"off"``.
    """
    grid = tuple(int(n) for n in grid)
    ndim = len(grid)
    if ndim < 2:
        raise ValueError("plan_fft needs >= 2 transform dims "
                         "(use jnp.fft.fft)")
    kinds = tuple(kinds) if kinds is not None else ("fft",) * ndim
    if len(kinds) != ndim:
        raise ValueError(f"plan_fft: {len(kinds)} kinds for ndim={ndim}")
    if tuning not in TUNING_MODES:
        raise ValueError(f"tuning must be one of {TUNING_MODES}, "
                         f"got {tuning!r}")
    if validate not in VALIDATE_MODES:
        raise ValueError(f"validate must be one of {VALIDATE_MODES}, "
                         f"got {validate!r}")
    batch_shape = tuple(int(n) for n in batch_shape)
    if dtype is None:
        dtype = (jnp.float32 if kinds[0] == "rfft"
                 or any(k in _R2R_KINDS for k in kinds) else jnp.complex64)

    explicit = [name for name, val in (("decomp", decomp),
                                       ("backend", backend),
                                       ("n_chunks", n_chunks),
                                       ("dim_groups", dim_groups))
                if val is not None]
    if tuning != "off" and explicit:
        warnings.warn(
            f"explicit {'/'.join(explicit)} are overridden by "
            f"tuning={tuning!r} (the tuner owns the schedule); pass "
            "tuning='off' to force them", DeprecationWarning, stacklevel=3)
    if decomp is None:
        # dim_groups unambiguously means hybrid; otherwise pencil when the
        # mesh has its ndim-1 axes, hybrid on smaller meshes.
        if dim_groups is not None:
            decomp = "hybrid"
        else:
            decomp = ("pencil" if len(mesh.axis_names) >= ndim - 1
                      else "hybrid")
    if backend is not None and backend not in transforms.LOCAL_BACKENDS:
        # Validate up front with the supported set: an unknown backend used
        # to fall through to an unhelpful failure deep in the pipeline.
        raise ValueError(
            f"plan_fft: unknown backend {backend!r}; supported backends: "
            f"{', '.join(transforms.LOCAL_BACKENDS)}")
    platform = mesh.devices.flat[0].platform
    if tuning != "off":
        cause = "tuner"
    elif backend is not None:
        cause, backend_why = "caller", f"{backend}: named by the caller"
    else:
        cause = "default"
        backend, backend_why = transforms.default_backend(platform, grid,
                                                          dtype, kinds)
    chunk_schedule = None
    if n_chunks is None:
        n_chunks = 1
    elif not isinstance(n_chunks, int):
        # A per-hop schedule (forward hop order); validated against the
        # decomposition's hop count by make_spec below.
        chunk_schedule = tuple(int(c) for c in n_chunks)
        n_chunks = max(chunk_schedule) if chunk_schedule else 1
    if dim_groups is not None:
        dim_groups = tuple(tuple(int(d) for d in g) for g in dim_groups)
        if decomp != "hybrid":
            raise ValueError("dim_groups only applies to decomp='hybrid'")
        _validate_dim_groups(dim_groups, ndim)

    from .tuner import Candidate, resolve_tuned_plan  # deferred: heavy deps
    default = None
    if tuning == "off":
        axes = (tuple(mesh_axes) if mesh_axes
                else _default_fft_axes(mesh, decomp, ndim))
        default = Candidate(decomp=decomp, mesh_axes=axes, backend=backend,
                            n_chunks=n_chunks, dim_groups=dim_groups,
                            chunk_schedule=chunk_schedule)
    tuned = resolve_tuned_plan(grid, mesh, kinds=kinds, dtype=dtype,
                               inverse=False, batch_shape=batch_shape,
                               mode=tuning, cache=tune_cache,
                               default=default, objective=tune_objective)

    if cause == "tuner":
        backend_why = f"{tuned.backend}: chosen by tuning={tuning!r}"
    if tuned.backend == "pallas":
        from repro.kernels.ops import unsupported  # deferred: loads Pallas
        why = unsupported(grid, dtype, platform)
        if why:
            raise ValueError(f"plan_fft: backend='pallas' {why}; use "
                             f"backend='xla' or 'matmul'")
    dec = make_decomposition(tuned.decomp, tuned.mesh_axes, ndim,
                             dim_groups=tuned.dim_groups)
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    batch_spec = (None,) * len(batch_shape)
    spec_chunks = (tuned.chunk_schedule if tuned.chunk_schedule is not None
                   else tuned.n_chunks)
    fwd_spec = make_spec(mesh, grid, dec, kinds, backend=tuned.backend,
                         n_chunks=spec_chunks, inverse=False,
                         batch_spec=batch_spec)
    validate_grid(dec, fwd_spec.eff_grid, axis_sizes)
    # The same forward-order schedule drives the inverse spec; make_spec
    # reverses it to the inverse's execution order and re-clamps per hop
    # (an inverse hop can have different legal chunk dims).
    inv_spec = make_spec(mesh, grid, dec, kinds, backend=tuned.backend,
                         n_chunks=spec_chunks, inverse=True,
                         batch_spec=batch_spec)
    plan = DistributedFFT(mesh, fwd_spec, inv_spec, batch_shape=batch_shape,
                          dtype=dtype, tuned=tuned, tuning=tuning,
                          precompiled=precompiled, backend_why=backend_why)
    with _BACKEND_COUNTS_LOCK:
        _BACKEND_COUNTS[tuned.backend][cause] += 1
    if validate != "off":
        report = plan.verify(tune_cache=tune_cache,
                             strict=validate == "strict")
        if report.errors:   # validate == "warn": report and hand back
            warnings.warn(f"plan_fft(validate='warn'): static contract "
                          f"findings\n{report.render()}", RuntimeWarning,
                          stacklevel=2)
    return plan


# ---------------------------------------------------------------------------
# Legacy wrappers: thin, plan-memoizing shims over the plan API.
# ---------------------------------------------------------------------------

# LRU-bounded: a long-running serving process sweeping many (grid, mesh,
# dtype) keys must not grow plan handles — and the compiled executables
# they hold — without bound.  Eviction drops our reference only; plans a
# caller still holds stay alive.  The compiled-executable layer underneath
# (``plan.PlanCache``) carries its own LRU bound, so eviction here really
# does release memory once no caller references the plan.  Sized by
# $REPRO_PLAN_MEMO_SIZE (default 64).


def _plan_memo_capacity() -> int:
    return env_capacity("REPRO_PLAN_MEMO_SIZE", 64)


_PLAN_MEMO: "OrderedDict[Any, Any]" = OrderedDict()
_PLAN_MEMO_LOCK = threading.Lock()
_MEMO_COUNTERS = {"hits": 0, "misses": 0, "evictions": 0}  # repro-lint: disable=REP004 fixed-key stats counters, not a growing cache


def _memoized(key: Any, factory: Callable[[], Any]) -> Any:
    with _PLAN_MEMO_LOCK:
        obj = _PLAN_MEMO.get(key)
        if obj is not None:
            _PLAN_MEMO.move_to_end(key)
            _MEMO_COUNTERS["hits"] += 1
            return obj
    obj = factory()
    with _PLAN_MEMO_LOCK:
        # Another thread may have raced us; keep the first instance so every
        # caller shares one set of compiled executables.
        won = _PLAN_MEMO.setdefault(key, obj)
        if won is obj:
            _MEMO_COUNTERS["misses"] += 1
        else:
            _MEMO_COUNTERS["hits"] += 1
        _PLAN_MEMO.move_to_end(key)
        cap = _plan_memo_capacity()
        while len(_PLAN_MEMO) > cap:
            _PLAN_MEMO.popitem(last=False)
            _MEMO_COUNTERS["evictions"] += 1
        return won


def _plan_memo_keys() -> list:
    """Snapshot of the wrapper-memo keys (static key audits)."""
    with _PLAN_MEMO_LOCK:
        return list(_PLAN_MEMO)


def clear_plan_memo() -> None:
    """Drop the wrappers' memoized plan/solver objects (tests)."""
    with _PLAN_MEMO_LOCK:
        _PLAN_MEMO.clear()
        for k in _MEMO_COUNTERS:
            _MEMO_COUNTERS[k] = 0


def plan_memo_stats() -> Dict[str, int]:
    with _PLAN_MEMO_LOCK:
        return {"plans": len(_PLAN_MEMO),
                "capacity": _plan_memo_capacity(),
                **_MEMO_COUNTERS}


# Plans built per local backend and per cause: the default rule
# (``transforms.default_backend``), the caller's ``backend=``, or tuning.
_BACKEND_COUNTS = {b: {"default": 0, "caller": 0, "tuner": 0}
                   for b in transforms.LOCAL_BACKENDS}
_BACKEND_COUNTS_LOCK = threading.Lock()


def plan_cache_stats() -> Dict[str, Dict[str, Any]]:
    """Public counters of both in-process plan-caching layers, and of XLA.

    ``compiled`` — the LRU :data:`~repro.core.plan.GLOBAL_PLAN_CACHE` of
    compiled executables (fused pipelines and stage segments);
    ``memo`` — the wrappers' plan-handle memo (``fftnd``/``poisson_solve``).
    Each carries ``hits``/``misses``/``evictions`` plus occupancy, so a
    serving metrics layer can report plan-cache health without reaching
    into private counters.  ``xla`` — every jit of the process, the
    program's and others': ``traces``, ``compiles`` (cache loads
    excluded), ``cache_loads`` from the persistent compilation cache and
    ``compile_s`` (:func:`~repro.core.plan.xla_compile_stats`); a steady
    loop adds nothing to them.  ``backend`` — plans built per local
    backend, split by what chose it: ``default`` (the rule of
    :func:`~repro.core.transforms.default_backend`), ``caller``
    (``backend=``) or ``tuner``.
    """
    from .plan import GLOBAL_PLAN_CACHE, xla_compile_stats
    with _BACKEND_COUNTS_LOCK:
        backends = {b: dict(c) for b, c in _BACKEND_COUNTS.items()}
    return {"compiled": GLOBAL_PLAN_CACHE.stats(),
            "memo": plan_memo_stats(),
            "xla": xla_compile_stats(),
            "backend": backends}


def _wrapper_plan(mesh: Mesh, grid, kinds, batch_shape, dtype, decomp,
                  backend, n_chunks, mesh_axes, tuning, tune_cache,
                  precompiled) -> DistributedFFT:
    # The cache object itself is part of the key: TuningCache hashes by
    # identity, and holding the reference keeps its id from being recycled
    # onto a different cache while the memoized plan exists.
    if n_chunks is not None and not isinstance(n_chunks, int):
        n_chunks = tuple(int(c) for c in n_chunks)  # hashable schedule
    key = ("fft", mesh, tuple(grid), tuple(kinds), tuple(batch_shape),
           str(jnp.dtype(dtype)), decomp, backend, n_chunks,
           tuple(mesh_axes) if mesh_axes is not None else None, tuning,
           tune_cache, precompiled)

    def build() -> DistributedFFT:
        plan = plan_fft(
            mesh, grid, kinds=kinds, batch_shape=batch_shape, dtype=dtype,
            decomp=decomp, backend=backend, n_chunks=n_chunks,
            mesh_axes=mesh_axes, tuning=tuning, tune_cache=tune_cache,
            precompiled=precompiled)
        # Memoized plans are shared across every wrapper caller: they must
        # never donate a caller's input buffer (donate=True raises).
        plan.shared = True
        return plan

    return _memoized(key, build)


def fftnd(x: jax.Array, *, mesh: Mesh, ndim: Optional[int] = None,
          decomp: Optional[str] = None,
          kinds: Optional[Sequence[str]] = None,
          backend: Optional[str] = None, n_chunks=None,
          mesh_axes: Optional[Sequence[str]] = None, tuning: str = "off",
          tune_cache: Optional[TuningCache] = None,
          precompiled: bool = True) -> jax.Array:
    """Distributed forward N-D transform of the trailing ``ndim`` dims of x.

    Leading ``x.ndim - ndim`` dims are batch dims (replicated across the
    mesh).  ``ndim`` defaults to ``x.ndim`` (transform everything).  Thin
    wrapper: builds (and memoizes) a :func:`plan_fft` plan and delegates —
    hold a plan yourself for execute-many workloads.
    """
    ndim = x.ndim if ndim is None else ndim
    if ndim < 2:
        raise ValueError("fftnd needs >= 2 transform dims (use jnp.fft.fft)")
    if x.ndim < ndim:
        raise ValueError(f"fftnd: ndim={ndim} but input has {x.ndim} dims")
    kinds = tuple(kinds) if kinds is not None else ("fft",) * ndim
    if len(kinds) != ndim:
        raise ValueError(f"fftnd: {len(kinds)} kinds for ndim={ndim}")
    n_batch = x.ndim - ndim
    plan = _wrapper_plan(mesh, x.shape[n_batch:], kinds, x.shape[:n_batch],
                         _forward_plan_dtype(x.dtype, kinds), decomp,
                         backend, n_chunks, mesh_axes, tuning, tune_cache,
                         precompiled)
    return plan.forward(x)


def ifftnd(x: jax.Array, *, mesh: Mesh, ndim: Optional[int] = None,
           grid: Optional[Tuple[int, ...]] = None,
           decomp: Optional[str] = None,
           kinds: Optional[Sequence[str]] = None,
           backend: Optional[str] = None, n_chunks=None,
           mesh_axes: Optional[Sequence[str]] = None, tuning: str = "off",
           tune_cache: Optional[TuningCache] = None,
           precompiled: bool = True) -> jax.Array:
    """Inverse of ``fftnd``.  ``kinds`` are the FORWARD kinds.

    For R2C pipelines pass ``grid`` = the original real-space grid (the
    frequency dim of ``x`` is padded, so it cannot be inferred).  Delegates
    to the same memoized plan the forward wrapper uses.
    """
    ndim = (x.ndim if grid is None else len(grid)) if ndim is None else ndim
    if ndim < 2:
        raise ValueError("ifftnd needs >= 2 transform dims "
                         "(use jnp.fft.ifft)")
    if x.ndim < ndim:
        raise ValueError(f"ifftnd: ndim={ndim} but input has {x.ndim} dims")
    kinds = tuple(kinds) if kinds is not None else ("fft",) * ndim
    if len(kinds) != ndim:
        raise ValueError(f"ifftnd: {len(kinds)} kinds for ndim={ndim}")
    n_batch = x.ndim - ndim
    logical = tuple(grid) if grid is not None else tuple(x.shape[n_batch:])
    plan = _wrapper_plan(mesh, logical, kinds, x.shape[:n_batch],
                         _inverse_plan_dtype(x.dtype, kinds), decomp,
                         backend, n_chunks, mesh_axes, tuning, tune_cache,
                         precompiled)
    return plan.inverse(x)


def fft2d(x: jax.Array, *, mesh: Mesh, **kw) -> jax.Array:
    """Distributed forward 2D transform of the trailing two dims of x."""
    return fftnd(x, mesh=mesh, ndim=2, **kw)


def ifft2d(x: jax.Array, *, mesh: Mesh, **kw) -> jax.Array:
    """Inverse of ``fft2d``."""
    return ifftnd(x, mesh=mesh, ndim=2, **kw)


def fft3d(x: jax.Array, *, mesh: Mesh, kinds: Sequence[str] = _DEF_KINDS,
          **kw) -> jax.Array:
    """Distributed forward 3D transform of the trailing three dims of x."""
    return fftnd(x, mesh=mesh, ndim=3, kinds=kinds, **kw)


def ifft3d(x: jax.Array, *, mesh: Mesh,
           grid: Optional[Tuple[int, int, int]] = None,
           kinds: Sequence[str] = _DEF_KINDS, **kw) -> jax.Array:
    """Inverse of ``fft3d``.  ``kinds`` are the FORWARD kinds.

    For R2C pipelines pass ``grid`` = the original real-space grid (the
    frequency dim of ``x`` is padded, so it cannot be inferred).
    """
    return ifftnd(x, mesh=mesh, ndim=3, grid=grid, kinds=kinds, **kw)


# ---------------------------------------------------------------------------
# Spectral Poisson solver (Oceananigans-style), on one paired plan.
# ---------------------------------------------------------------------------

def poisson_eigenvalues(n: int, length: float = 2 * np.pi,
                        topology: str = "periodic") -> np.ndarray:
    """Second-order finite-difference spectral eigenvalues (Oceananigans-style)."""
    dx = length / n
    i = np.arange(n)
    if topology == "periodic":
        return (2.0 * (np.cos(2.0 * np.pi * i / n) - 1.0)) / dx**2
    # bounded (staggered-grid DCT eigenvalues)
    return (2.0 * (np.cos(np.pi * i / n) - 1.0)) / dx**2


class PoissonSolver:
    """Spectral solver for lap(phi) = rhs on a (Periodic|Bounded)^3 box.

    Periodic dims use C2C FFTs; Bounded dims use DCT-II (homogeneous
    Neumann), matching the Oceananigans pressure-solver topologies in paper
    Fig. 8.  One :class:`DistributedFFT` plan serves both directions — a
    single tuning resolution per topology, not two tuner hits per call —
    and the eigenvalue array is computed once and cached per spectral
    dtype.  Tuning is **joint**: the solver tunes under the
    ``fwd+scale+inv`` objective, so auto mode measures each candidate on
    the full round trip it will actually run (its own wisdom key), and the
    forward winner's stage-0 layout is reused by the paired inverse — no
    relayout can appear between the forward output and the inverse input.
    ``solve`` accepts ``sharded_in=``/``donate=`` like the plan it wraps;
    the spectral scale-and-inverse runs on the forward output's native
    sharding.
    """

    def __init__(self, mesh: Mesh, grid: Sequence[int], *,
                 topology: Tuple[str, str, str] = ("periodic",) * 3,
                 lengths: Tuple[float, ...] = (2 * np.pi,) * 3,
                 batch_shape: Sequence[int] = (), dtype=jnp.float32,
                 decomp: Optional[str] = None,
                 backend: Optional[str] = None,
                 n_chunks: Optional[int] = None,
                 mesh_axes: Optional[Sequence[str]] = None,
                 tuning: str = "off",
                 tune_cache: Optional[TuningCache] = None,
                 precompiled: bool = True):
        grid = tuple(int(n) for n in grid)
        if len(grid) != 3:
            raise ValueError(f"PoissonSolver needs a 3-D grid, got {grid}")
        self.topology = tuple(topology)
        self.lengths = tuple(lengths)
        kinds = tuple("fft" if t == "periodic" else "dct2"
                      for t in self.topology)
        self.plan = plan_fft(mesh, grid, kinds=kinds,
                             batch_shape=batch_shape,
                             dtype=_forward_plan_dtype(dtype, kinds),
                             decomp=decomp, backend=backend,
                             n_chunks=n_chunks, mesh_axes=mesh_axes,
                             tuning=tuning, tune_cache=tune_cache,
                             tune_objective="fwd+scale+inv",
                             precompiled=precompiled)
        lams = [poisson_eigenvalues(n, l, t)
                for n, l, t in zip(grid, self.lengths, self.topology)]
        lam = (lams[0][:, None, None] + lams[1][None, :, None]
               + lams[2][None, None, :])
        lam_flat = lam.reshape(-1)
        lam_flat[0] = 1.0  # pin the null mode (mean) to zero
        self._lam = lam_flat.reshape(lam.shape)
        self._lam_dev: Dict[str, jax.Array] = {}

    def _lam_for(self, dtype) -> jax.Array:
        key = str(jnp.dtype(dtype))
        lam = self._lam_dev.get(key)
        if lam is None:
            lam = jnp.asarray(self._lam, dtype=dtype)
            self._lam_dev[key] = lam
        return lam

    def describe(self) -> str:
        topo = "x".join(t[0].upper() for t in self.topology)
        return (f"PoissonSolver(topology={topo}, "
                f"tuning=joint fwd+scale+inv, single resolution)\n"
                f"{self.plan.describe()}")

    def solve(self, rhs: jax.Array, *, sharded_in: bool = False,
              donate: bool = False) -> jax.Array:
        """One pressure solve; the null (mean) mode is zeroed per batch
        element and real input comes back real.  Runs under the host span
        ``repro.solve``, its divide and null-mode pin under
        ``repro.solve.scale``."""
        with jax.profiler.TraceAnnotation("repro.solve"):
            real_in = not jnp.iscomplexobj(rhs)
            xk = self.plan.forward(rhs, sharded_in=sharded_in,
                                   donate=donate)
            with jax.profiler.TraceAnnotation("repro.solve.scale"):
                scaled = xk / self._lam_for(xk.dtype)
                # Zero the null (mean) mode explicitly — indexing only the
                # trailing 3 spectral dims so every leading batch element
                # is zeroed, not just batch index 0.
                scaled = scaled.at[..., 0, 0, 0].set(
                    jnp.zeros((), scaled.dtype))
            phi = self.plan.inverse(scaled)
            if real_in and jnp.iscomplexobj(phi):
                phi = jnp.real(phi)
            return phi

    def __call__(self, rhs: jax.Array, **kw) -> jax.Array:
        return self.solve(rhs, **kw)


def poisson_solve(rhs: jax.Array, *, mesh: Mesh,
                  topology: Tuple[str, str, str] = ("periodic",) * 3,
                  lengths: Tuple[float, ...] = (2 * np.pi,) * 3,
                  decomp: Optional[str] = None,
                  backend: Optional[str] = None,
                  n_chunks: Optional[int] = None,
                  mesh_axes: Optional[Sequence[str]] = None,
                  tuning: str = "off",
                  tune_cache: Optional[TuningCache] = None,
                  precompiled: bool = True) -> jax.Array:
    """Solve lap(phi) = rhs spectrally; thin wrapper over PoissonSolver.

    Leading dims of ``rhs`` beyond the trailing 3 are batch dims.  Builds
    (and memoizes, per topology/geometry) a :class:`PoissonSolver`, so
    repeated solves share one paired plan and one eigenvalue array; hold a
    solver yourself to also use ``sharded_in=``/``donate=``.
    """
    grid = tuple(rhs.shape[-3:])
    batch_shape = tuple(rhs.shape[:-3])
    kinds = tuple("fft" if t == "periodic" else "dct2" for t in topology)
    dtype = _forward_plan_dtype(rhs.dtype, kinds)
    if n_chunks is not None and not isinstance(n_chunks, int):
        n_chunks = tuple(int(c) for c in n_chunks)  # hashable schedule
    key = ("poisson", mesh, grid, tuple(topology), tuple(lengths),
           batch_shape, str(jnp.dtype(dtype)), decomp, backend, n_chunks,
           tuple(mesh_axes) if mesh_axes is not None else None, tuning,
           tune_cache, precompiled)
    def build() -> PoissonSolver:
        solver = PoissonSolver(
            mesh, grid, topology=topology, lengths=lengths,
            batch_shape=batch_shape, dtype=dtype, decomp=decomp,
            backend=backend, n_chunks=n_chunks, mesh_axes=mesh_axes,
            tuning=tuning, tune_cache=tune_cache, precompiled=precompiled)
        # The memoized solver (and its plan) is shared across callers:
        # refuse input donation just like the fftnd wrapper plans.
        solver.plan.shared = True
        return solver

    solver = _memoized(key, build)
    return solver.solve(rhs)
