"""A 3-D (or N-D) transform through ``plan_fft``: ops ``forward``, ``inverse``.

Only the deployment's own arguments reach the program (grid, kinds, dtype,
mesh); backend, decomposition and chunk schedule stay the program's defaults.
"""
import jax.numpy as jnp

from chipbench.harness import System, line_flops, make_mesh


def build(cfg: dict, devices) -> System:
    from repro.core.api import plan_fft

    mesh = make_mesh(cfg["mesh"], devices)
    plan = plan_fft(mesh, cfg["grid"], kinds=cfg["kinds"],
                    dtype=jnp.dtype(cfg["dtype"]))
    flops = line_flops(cfg["grid"], cfg["kinds"])
    return System(
        ops={"forward": lambda x: plan.forward(x, sharded_in=True),
             "inverse": lambda y: plan.inverse(y, sharded_in=True)},
        io={"forward": (plan.in_struct, plan.out_struct),
            "inverse": (plan.inv_in_struct, plan.inv_out_struct)},
        flops={"forward": flops, "inverse": flops},
        field_dtype=plan.in_struct.dtype, field_sharding=plan.in_sharding,
        mesh=mesh)
