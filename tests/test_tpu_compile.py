"""Compile-only checks against a described TPU v5e: no chip needed.

The TPU compiler is installed with jax, and it compiles for a v5e:2x2
topology that is described rather than attached.  That catches what
interpret mode cannot — kernels Mosaic refuses (shape casts, VMEM over the
limit) and plans that do not fit a chip's HBM — at no chip time.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and it keeps the library
until it exits.  Keep every such compile in this one file.
"""
import os

import numpy as np
import pytest

GiB = 2**30
HBM_PER_CHIP = 16 * GiB


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # libtpu logs under /tmp unless told otherwise.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep these out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


def _largest_supported_length():
    from repro.kernels.fft_matmul import supports_length
    return max(n for n in range(1, 4097) if supports_length(n))


@pytest.mark.parametrize("n,variant", [
    (n, variant) for n in (256, 480, 512, 1024, 2048)
    for variant in ("plain", "twiddle", "inverse")] + [("largest", "plain")])
def test_pallas_kernel_compiles_for_v5e(topo, n, variant):
    """The FFT kernel compiles with Mosaic at the paper's line lengths
    (480 = 2^5 * 3 * 5 split (4, 120), the powers of two (N/128, 128)) and
    at the longest line it accepts (``supports_length``: 3249 = 57 * 57),
    forward, inverse and with the twiddle folded in, and really is a TPU
    kernel."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from repro.kernels.fft_matmul import fft_cols

    if n == "largest":
        n = _largest_supported_length()
    one_chip = SingleDeviceSharding(topo.devices[0])
    planes = jax.ShapeDtypeStruct((2, n, 1024), jnp.float32,
                                  sharding=one_chip)
    phase = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)

    def f(xr, xi, er, ei):
        return fft_cols(xr, xi, interpret=False,
                        inverse=variant == "inverse",
                        twiddle=(er, ei) if variant == "twiddle" else None)

    compiled = jax.jit(f).lower(planes, planes, phase, phase).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("mesh_shape,n,chunks", [
    ((1, 1), 512, 1),         # one chip: 1 GiB per array
    ((2, 2), 1024, 1),        # four chips: 2 GiB per chip per array
    ((2, 2), 1024, 16),       # the deepest model-only chunk schedule
])
def test_c2c_plan_fits_v5e_hbm(topo, mesh_shape, n, chunks):
    """A pencil C2C plan at the paper's grids compiles for v5e chips and
    its arguments, output and temporaries fit one chip's HBM."""
    mem = _c2c_plan(topo, mesh_shape, n, chunks, "xla").memory_analysis()
    per_chip = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes)
    assert mem.argument_size_in_bytes == 8 * n**3 // int(np.prod(mesh_shape))
    assert per_chip < HBM_PER_CHIP, f"{per_chip / GiB:.2f} GiB per chip"


@pytest.mark.parametrize("backend", ["xla", "matmul", "pallas"])
def test_mixed_radix_plan_fits_v5e_hbm(topo, backend, monkeypatch):
    """The 480^3 C2C plan (480 = 2^5 * 3 * 5) compiles for one v5e chip on
    every local backend and fits its HBM; the pallas plan runs the kernel.
    Its minor axis is laid out padded to a multiple of 128 lanes, so an
    argument takes more than 8 * 480^3 bytes."""
    from repro.kernels import ops

    # The process's platform is the CPU, where the kernel would run
    # interpreted: compile it with Mosaic, as the chip does.
    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    compiled = _c2c_plan(topo, (1, 1), 480, 1, backend)
    mem = compiled.memory_analysis()
    per_chip = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes)
    assert mem.argument_size_in_bytes == 8 * 480 * 480 * 512
    assert per_chip < HBM_PER_CHIP, f"{per_chip / GiB:.2f} GiB per chip"
    assert ("tpu_custom_call" in compiled.as_text()) == (backend == "pallas")


def _c2c_plan(topo, mesh_shape, n, chunks, backend):
    """The forward pencil C2C plan of an n^3 complex64 grid, compiled for
    the described chips."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from repro.core.decomp import make_decomposition
    from repro.core.pipeline import build_pipeline, input_struct, make_spec

    count = int(np.prod(mesh_shape))
    mesh = Mesh(np.array(topo.devices[:count]).reshape(mesh_shape),
                ("data", "model"))
    dec = make_decomposition("pencil", ("data", "model"), 3)
    spec = make_spec(mesh, (n,) * 3, dec, ("fft",) * 3, backend=backend,
                     n_chunks=chunks)
    arg = input_struct(mesh, spec, (), jnp.complex64)
    return jax.jit(build_pipeline(mesh, spec)).lower(arg).compile()


@pytest.mark.parametrize("case", ["c2c512", "c2c480", "ppb512", "c2c1024x4"])
def test_default_plan_runs_the_kernel_on_v5e(topo, case):
    """With no ``backend=``, a plan for described v5e chips takes the Pallas
    kernel at the benchmark's grids: 512^3 and 480^3 C2C and the 512^3 PPB
    pressure solve on one chip, and the 1024^3 pencil inside ``shard_map``
    on the 2x2 mesh.  Forward and inverse compile with the Mosaic kernel
    as a TPU custom call and no XLA FFT, and their argument and output
    bytes (the live arrays between calls) equal the xla plan's."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from repro.core.api import PoissonSolver, plan_fft
    from repro.core.pipeline import build_pipeline

    mesh_shape, n = ((2, 2), 1024) if case == "c2c1024x4" else ((1, 1), 512)
    if case == "c2c480":
        n = 480
    mesh = Mesh(np.array(topo.devices[:int(np.prod(mesh_shape))])
                .reshape(mesh_shape), ("data", "model"))

    def plan(backend=None):
        if case == "ppb512":
            return PoissonSolver(mesh, (n,) * 3,
                                 topology=("periodic", "periodic", "bounded"),
                                 backend=backend, precompiled=False).plan
        return plan_fft(mesh, (n,) * 3, backend=backend, precompiled=False)

    def compiled(p, inverse):
        arg = p.inv_in_struct if inverse else p.in_struct
        lowered = jax.jit(build_pipeline(
            mesh, p.pipeline_spec(inverse=inverse))).lower(arg)
        return lowered.as_text(), lowered.compile()

    default, xla = plan(), plan("xla")
    assert (default.backend, xla.backend) == ("pallas", "xla")
    assert default.backend_why.startswith(f"pallas: tpu, lines {n} fit")
    for inverse in (False, True):
        text, exe = compiled(default, inverse)
        assert "stablehlo.fft" not in text
        assert "tpu_custom_call" in exe.as_text()
        _, ref = compiled(xla, inverse)
        mem, ref_mem = exe.memory_analysis(), ref.memory_analysis()
        assert (mem.argument_size_in_bytes, mem.output_size_in_bytes) == \
            (ref_mem.argument_size_in_bytes, ref_mem.output_size_in_bytes)
