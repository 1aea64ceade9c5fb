"""Mesh-step scopes and sorter counters on a 2x2 mesh (subprocess: forces 4
host devices so the XLA override never leaks into other tests).

The lowered window program must carry the halo and migration scopes (each
over its collectives) and the step's phase scopes, and two windows'
counters must total their bundles.
Prints ``SCOPES OK`` on success.
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=4 " + os.environ.get("XLA_FLAGS", "")
)

import re  # noqa: E402
import warnings  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

import repro.pic.dist_simulation as dist_simulation  # noqa: E402
from repro.core import SortPolicyConfig  # noqa: E402
from repro.distributed.fault import no_fault_vec  # noqa: E402
from repro.pic import DistConfig, DistSimulation, FieldState, GridSpec, uniform_plasma  # noqa: E402

SCOPES = (
    "pic.halo", "pic.migrate", "pic.gather", "pic.push", "pic.gpma", "pic.gpma.rank",
    "pic.stage", "pic.deposit", "pic.maxwell", "pic.policy", "pic.global_sort",
)
POLICY = SortPolicyConfig(sort_interval=4, min_sort_interval=1, sort_trigger_perf_enable=False)


def main() -> None:
    grid = GridSpec(shape=(8, 8, 4))
    parts = uniform_plasma(
        jax.random.PRNGKey(0), grid, ppc_each_dim=(2, 2, 2), density=1.0, u_thermal=0.05
    )
    cfg = DistConfig(local_grid=GridSpec(shape=(4, 4, 4)), dt=0.2, order=1, capacity=16)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        sim = DistSimulation(FieldState.zeros(grid.shape), parts, cfg, mesh_shape=(2, 2), policy=POLICY)

    fn = sim._window_fn(4, False)
    state = sim._on_mesh((sim.fields, sim.pos, sim.u, sim.w, sim.alive, sim.slots, sim.pslot,
                          sim.slab_d, sim.slab_valid, sim.mid_pos, sim.mid_u))
    pstate = jax.device_put(sim.policy_state, NamedSharding(sim.mesh, P()))
    one = jnp.int32(1)
    with jax.set_mesh(sim.mesh):
        text = fn.lower(*state, pstate, jnp.int32(4), one, one, one, one,
                        no_fault_vec()).as_text(debug_info=True)
    # the lowered program's locations carry the name stack of each op
    names = re.findall(r'loc\("([^"]*)"', text)
    found = {c for n in names for c in n.split("/") if c.startswith("pic.")}
    missing = sorted(set(SCOPES) - found)
    assert not missing, f"scopes missing from the mesh window: {missing}"
    assert any("pic.migrate" in n and "ppermute" in n for n in names), "no collective under pic.migrate"
    assert any("pic.halo" in n and "ppermute" in n for n in names), "no collective under pic.halo"

    bundles = []
    real = dist_simulation._fetch_bundle

    def fetch(bundle):
        host = real(bundle)
        bundles.append(host)
        return host

    dist_simulation._fetch_bundle = fetch
    sim.run(8, window=4)
    assert len(bundles) == 2
    sums = {"n_moved": 0, "n_alive": 0, "n_ranked": 0}
    for host in bundles:
        per = host["per_step"]
        live = np.asarray(per["active"], bool)
        for k in sums:
            sums[k] += int(np.sum(np.asarray(per[k])[live]))
    got = (sim.moved, sim.particle_steps, sim.ranked)
    assert got == (sums["n_moved"], sums["n_alive"], sums["n_ranked"]), (got, sums)
    assert sim.ranked == 8 * 4 * sim.n_local, (sim.ranked, sim.n_local)
    assert sum(sim.sort_reasons.values()) == sim.sorts + sim.rebuilds > 0, sim.sort_reasons
    print("SCOPES OK")


if __name__ == "__main__":
    main()
