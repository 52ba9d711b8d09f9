"""The control and the planted faults: wrappers around every op of an entry.

Used by the control runs and the tests, never by a benchmark run.

* ``bf16``        -- the control: each op's input and output stored in
  bfloat16, the precision below the configurations' float32;
* ``unchanged``   -- each op returns its input (laid out as its output);
* ``half``        -- the second half of each output along axis 0 is the
  input, untransformed;
* ``altered``     -- one element of each output is moved by the output's
  largest magnitude;
* ``no_exchange`` -- forward / inverse computed shard by shard with no
  exchange between chips.
"""
import functools

import jax
import jax.numpy as jnp


def _to_bf16(a):
    if jnp.iscomplexobj(a):
        return a.real.astype(jnp.bfloat16), a.imag.astype(jnp.bfloat16)
    return (a.astype(jnp.bfloat16),)


@functools.cache
def _from_bf16(dtype):
    def up(*parts):
        parts = [p.astype(jnp.finfo(dtype).dtype) for p in parts]
        return jax.lax.complex(*parts) if len(parts) == 2 else parts[0]
    return jax.jit(up)


def _like(a, ref):
    return jax.device_put(a, ref.sharding)


def bf16(op, fn, system):
    # Stored in bfloat16 between two programs: within one program XLA may
    # drop a round trip through a narrower type (excess precision), and the
    # control would then compute in float32.
    down = jax.jit(_to_bf16)

    def rnd(a):
        return _like(_from_bf16(a.dtype)(*down(a)), a)

    def run(x):
        out = fn(rnd(x))
        return rnd(out)
    return run


def unchanged(op, fn, system):
    out_sharding = system.io[op][1].sharding
    return lambda x: jax.device_put(x, out_sharding)


def half(op, fn, system):
    def run(x):
        out = fn(x)
        n = x.shape[0] // 2
        return _like(out.at[n:].set(x[n:].astype(out.dtype)), out)
    return run


def altered(op, fn, system):
    def run(x):
        out = fn(x)
        bump = jnp.max(jnp.abs(out)).astype(out.dtype)
        return _like(out.at[(0,) * out.ndim].add(bump), out)
    return run


def no_exchange(op, fn, system):
    local = {"forward": jnp.fft.fftn, "inverse": jnp.fft.ifftn}[op]
    in_struct, out_struct = system.io[op]
    spec = in_struct.sharding.spec
    blockwise = jax.jit(jax.shard_map(local, mesh=system.mesh,
                                      in_specs=spec, out_specs=spec))
    return lambda x: jax.device_put(blockwise(x), out_struct.sharding)


VARIANTS = {"bf16": bf16, "unchanged": unchanged, "half": half,
            "altered": altered, "no_exchange": no_exchange}


def wrap(name: str, system) -> dict:
    return {op: VARIANTS[name](op, fn, system)
            for op, fn in system.ops.items()}
