"""The spectral pressure solve through ``PoissonSolver``: op ``solve``.

Only the deployment's own arguments reach the program (grid, topology,
domain lengths, dtype, mesh); the solver's plan keeps its defaults.
"""
import jax
import jax.numpy as jnp

from chipbench.harness import System, line_flops, make_mesh

KIND = {"periodic": "fft", "bounded": "dct2"}


def build(cfg: dict, devices) -> System:
    from repro.core.api import PoissonSolver

    mesh = make_mesh(cfg["mesh"], devices)
    dtype = jnp.dtype(cfg["dtype"])
    solver = PoissonSolver(mesh, cfg["grid"], topology=tuple(cfg["topology"]),
                           lengths=tuple(cfg["lengths"]), dtype=dtype)
    rhs = solver.plan.in_struct
    phi = jax.ShapeDtypeStruct(rhs.shape, dtype)
    # A solve is a forward and an inverse transform.
    flops = 2 * line_flops(cfg["grid"], [KIND[t] for t in cfg["topology"]])
    return System(
        ops={"solve": lambda r: solver.solve(r, sharded_in=True)},
        io={"solve": (rhs, phi)}, flops={"solve": flops},
        field_dtype=rhs.dtype, field_sharding=solver.plan.in_sharding,
        mesh=mesh)
