"""Standalone distributed-PIC equivalence check (run in a subprocess so the
XLA host-device override never leaks into other tests).

Compares 3 steps of the 2x2-shard shard_map PIC against the single-device
simulation on identical initial conditions. Prints MAX_REL_ERR on success.
"""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4 " + os.environ.get("XLA_FLAGS", "")

import dataclasses  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.pic import FieldState, GridSpec, PICConfig, Simulation, uniform_plasma  # noqa: E402
from repro.pic.distributed import DistConfig, build_local_bins, make_dist_step, partition_particles  # noqa: E402


def main() -> None:
    steps = 3
    grid = GridSpec(shape=(8, 8, 8))
    parts = uniform_plasma(jax.random.PRNGKey(0), grid, ppc_each_dim=(2, 2, 2), density=1.0, u_thermal=0.05)

    # --- single device reference
    cfg = PICConfig(grid=grid, dt=0.2, order=1, deposition="matrix", gather="matrix", capacity=16)
    sim = Simulation(FieldState.zeros(grid.shape), parts, cfg)
    sim.run(steps)
    ref = np.asarray(sim.state.fields.ex)

    # --- distributed 2x2
    mesh = jax.make_mesh((2, 2), ("data", "model"))
    local = GridSpec(shape=(4, 4, 8))
    dcfg = DistConfig(local_grid=local, dt=0.2, order=1, capacity=32, mig_cap=128)
    pos, u, w, alive = partition_particles(parts, grid, 2, 2, n_local=2048)
    slots, pslot, slab_d, slab_valid, overflow = build_local_bins(pos, alive, local, capacity=32)
    assert overflow == 0

    fields = tuple(jnp.zeros(grid.shape, jnp.float32) for _ in range(6))
    step = make_dist_step(mesh, dcfg)
    with jax.set_mesh(mesh):
        for _ in range(steps):
            fields, pos, u, w, alive, slots, pslot, slab_d, slab_valid, stats = step(
                fields, pos, u, w, alive, slots, pslot, slab_d, slab_valid
            )
    assert int(stats["mig_send_overflow"]) == 0
    assert int(stats["mig_recv_dropped"]) == 0
    assert int(stats["n_unmigrated"]) == 0
    assert int(stats["n_overflow"]) == 0
    assert int(stats["n_alive"]) == parts.n

    got = np.asarray(fields[0])
    scale = np.abs(ref).max() + 1e-12
    err = np.abs(got - ref).max() / scale
    assert err < 1e-4, f"field mismatch: rel err {err}"
    print(f"MAX_REL_ERR={err:.3e} OK")


if __name__ == "__main__":
    main()
