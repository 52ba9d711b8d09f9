"""Inter-stage redistribution (global transposes) for distributed FFTs.

Paper Alg. 2 overlaps pack / send / recv / unpack so that downstream FFT
work starts as soon as *any* message lands, instead of after a global
barrier.  Under SPMD there is no host-driven polling loop, so the same idea
is expressed structurally:

* ``bulk``    — the hop's ``lax.all_to_all`` moves run once over the whole
  block (the heFFTe-style baseline: the transpose completes before the next
  stage starts).  Pencil/slab hops are a single all_to_all; hybrid hops may
  chain several (one per mesh axis crossing the stage boundary).
* ``chunked`` — the local block is split into ``n_chunks`` along a dim that
  is *not* part of the exchange **and not transformed by the next stage**;
  each chunk gets its own, independent ``all_to_all(s) -> local-FFT`` chain.
  The chains have no data dependencies between them, so XLA's latency-hiding
  scheduler can run chunk k's ICI transfer concurrently with chunk k-1's MXU
  work — the static-dataflow analogue of the paper's progressive per-chunk
  unpack.

Both paths are numerically identical; tests assert it, benchmarks and the
roofline analysis quantify the difference in the compiled schedule.

Chunk-dim legality matters: fusing the next stage's transform per chunk is
only valid when the chunk dim is untouched by that transform.  An inverse
slab pipeline, for example, has *no* legal spatial chunk dim (the hop
touches dims 0 and ndim-1, the following stage FFTs everything in between),
so :func:`free_chunk_dim` returns None and :func:`redistribute` falls back
to the bulk path with a warning instead of silently corrupting the output.
Similarly, a chunk count that does not divide the chunk dim's local size is
clamped to the largest divisor that does (``pipeline.make_spec`` records
the clamp on the spec) rather than aborting the trace.
"""
from __future__ import annotations

import warnings
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from .decomp import _as_hop


def largest_divisor_at_most(n: int, cap: int) -> int:
    """The largest divisor of ``n`` that is <= ``cap`` (>= 1)."""
    for d in range(min(int(cap), int(n)), 1, -1):
        if n % d == 0:
            return d
    return 1


def free_chunk_dim(hop, ndim: int, offset: int,
                   avoid_dims: Sequence[int] = ()) -> Optional[int]:
    """Pick a dim (absolute index) legal for chunk-pipelining this hop.

    Excluded are every dim any of the hop's moves splits or concatenates
    *and* every dim in ``avoid_dims`` — callers pass the downstream stage's
    (absolute) ``fft_dims``, because the fused per-chunk transform would
    otherwise FFT over a split dim and produce garbage (the inverse-slab
    bug).  Returns None when no legal dim exists; callers fall back to the
    bulk path.
    """
    hop = _as_hop(hop)
    busy = {d + offset for d in hop.busy_dims()}
    busy.update(avoid_dims)
    # Prefer the last spatial dim (largest stride locality for packing).
    for d in range(ndim - 1, offset - 1, -1):
        if d not in busy:
            return d
    # Fall back to a leading batch dim.
    for d in range(offset):
        if d not in busy:
            return d
    return None


def redistribute(block: jax.Array, hop, *,
                 n_chunks: int = 1,
                 then: Optional[Callable[[jax.Array], jax.Array]] = None,
                 spatial_offset: int = 0,
                 avoid_dims: Sequence[int] = (),
                 hop_index: Optional[int] = None) -> jax.Array:
    """Run one redistribution hop inside a ``shard_map`` body.

    ``block`` is the local shard; ``spatial_offset`` is the number of
    leading batch dims before the spatial dims the decomposition describes.
    ``hop`` is a :class:`~repro.core.decomp.RedistHop` (a bare
    ``Redistribution`` is accepted and wrapped).  ``then`` is the next
    stage's local transform, fused per-chunk when ``n_chunks > 1`` (the
    overlap pipeline); ``avoid_dims`` are the absolute dims that transform
    touches, which the chunk dim must avoid.  ``n_chunks`` is this hop's
    entry of the pipeline's per-hop ``chunk_schedule`` — each hop chooses
    its own chunk dim and clamps its own count, so heterogeneous schedules
    need no coordination here.  ``hop_index`` names the hop's device
    scope ``hop<i>`` and labels the trace-time warnings
    (``pipeline.make_spec`` records spec-time clamps).
    """
    hop = _as_hop(hop)
    tag = f"hop {hop_index}" if hop_index is not None else "this hop"
    # The exchange, and only it, runs under the device scope hop<i>: the
    # fused ``then`` carries its own stage<k> scope, so no op is in both.
    scope = f"hop{hop_index}" if hop_index is not None else "hop"

    def a2a(x: jax.Array) -> jax.Array:
        with jax.named_scope(scope):
            for mv in hop.moves:
                x = lax.all_to_all(x, mv.mesh_axis,
                                   split_axis=mv.split_dim + spatial_offset,
                                   concat_axis=mv.concat_dim + spatial_offset,
                                   tiled=True)
        return x

    if n_chunks <= 1:
        out = a2a(block)
        return then(out) if then is not None else out

    chunk_dim = free_chunk_dim(hop, block.ndim, spatial_offset, avoid_dims)
    if chunk_dim is None:
        warnings.warn(
            f"no legal chunk dim for {tag} over {hop.mesh_axes} (every dim "
            f"is part of the exchange or of the next stage's transform); "
            f"running the bulk path instead of n_chunks={n_chunks}",
            RuntimeWarning, stacklevel=2)
        out = a2a(block)
        return then(out) if then is not None else out
    size = block.shape[chunk_dim]
    eff_chunks = largest_divisor_at_most(size, n_chunks)
    if eff_chunks != n_chunks:
        warnings.warn(
            f"chunk dim {chunk_dim} (size {size}) of {tag} not divisible "
            f"by n_chunks={n_chunks}; clamped to {eff_chunks}",
            RuntimeWarning, stacklevel=2)
        if eff_chunks <= 1:
            out = a2a(block)
            return then(out) if then is not None else out
    # Unrolled chunk loop: each (slice -> all_to_all -> then) chain is an
    # independent dataflow island, which is exactly what lets the compiler
    # overlap collective k+1 with compute k.  A fori_loop would serialize
    # them by construction.
    with jax.named_scope(scope):
        pieces = jnp.split(block, eff_chunks, axis=chunk_dim)
    outs = []
    for piece in pieces:
        t = a2a(piece)
        outs.append(then(t) if then is not None else t)
    with jax.named_scope(scope):
        return jnp.concatenate(outs, axis=chunk_dim)


def transpose_cost_bytes(local_shape, dtype_bytes: int, axis_size: int) -> int:
    """Bytes each device puts on the wire for one all_to_all.

    Of the local block, a fraction (axis_size-1)/axis_size leaves the device
    (the diagonal block stays local — the paper's Alg. 2 phase 4 "local
    copies").  Used by the LogP model and the roofline's collective term.
    """
    n_elems = 1
    for s in local_shape:
        n_elems *= s
    total = n_elems * dtype_bytes
    return total * (axis_size - 1) // max(axis_size, 1)


def hop_move_shapes(hop, start_shape, axis_sizes):
    """Local block shape seen by each move of a hop, in execution order.

    Yields ``(move, shape_before_move)``; the shape threads through the
    moves (a split divides its dim by the axis size, a concat multiplies).
    Shared by the perf model and the roofline so multi-move hybrid hops are
    priced on the volumes each all_to_all actually ships.
    """
    shape = list(start_shape)
    for mv in _as_hop(hop).moves:
        yield mv, tuple(shape)
        p = axis_sizes[mv.mesh_axis]
        shape[mv.split_dim] //= p
        shape[mv.concat_dim] *= p
