"""Distributed device-resident simulation loop: the whole K-step window runs
as ONE compiled program with the `lax.scan` INSIDE `shard_map`.

This connects the two halves the repo already had — the single-shot
`shard_map` step (pic/distributed.py) and the single-device windowed scan
driver (pic/simulation.py) — into the co-designed compute/layout/
communication loop of the paper's per-MPI-rank model: fields and particles
never reshard between steps, halo/migration ppermutes stay inside the one
program, and the host sees exactly one fetched bundle per window.

Per scan iteration (every shard, SPMD):

  1. `dist_pic_step_local`    — halo exchange, gather, push, bounded-buffer
                                migration, per-shard GPMA update, deposition
                                + guard reduction, Maxwell (pic/distributed)
  2. policy decision          — `core.resort_policy.policy_update` over the
                                `lax.psum`-reduced GPMAStats; the reduced
                                scalars are replicated, so every shard takes
                                the same branch
  3. conditional global sort  — per-shard `dist_global_sort_device` under
                                `lax.cond` (purely local: attribute
                                permutation + bin rebuild)
  4. diagnostics              — psum-reduced energies + migration counters
                                accumulated on device

Host escape hatches (the ONLY reasons a window ends early; same masked
pass-through trick as `pic_run_window`, never a whole-step `lax.cond`):

  HALT_BIN_OVERFLOW    a bin stayed overfull even after the sort — the step
                       is KEPT (overflowed particles simply did not deposit,
                       exactly like the single-device driver), the host
                       doubles `capacity` and re-enters.
  HALT_MIG_SEND        a migrating particle found no exchange-buffer slot.
                       The step is KEPT and lossless — the straggler stays
                       resident, masked out of binning/gather/deposition,
                       and retries after the host doubles `mig_cap`.
  HALT_MIG_RECV        a received particle found no dead slot: it would have
                       been DESTROYED. The step is DISCARDED (not counted in
                       n_done), the host doubles the per-shard particle
                       arrays (`n_local`) and the step re-runs — `DistSimulation`
                       therefore never loses charge to receive overflow.
"""

from __future__ import annotations

import contextlib
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import (
    ResortPolicy,
    SortPolicyConfig,
    cell_index,
    choose_capacity,
    policy_init,
    policy_reset,
    policy_update,
)

# The halt-code family is shared with the single-device driver and the
# health sentinel; re-exported here for backwards compatibility (this module
# defined codes 0-3 before core.health existed).
from repro.core.health import (  # noqa: F401
    HALT_BIN_OVERFLOW,
    HALT_IMBALANCE,
    HALT_INVARIANT,
    HALT_MIG_RECV,
    HALT_MIG_SEND,
    HALT_NAMES,
    HALT_NONE,
    HALT_NONFINITE,
    HealthConfig,
    classify_health,
    nonfinite_count,
)
from repro.core.resort_policy import REASON_NAMES, REASON_OVERFLOW
from repro.distributed.sharding import plan_balanced_split
from repro.distributed.fault import (
    PICFaultInjector,
    inject_fields,
    inject_momenta,
    inject_weights,
    injected_recv_drop,
    no_fault_vec,
    run_supervised_windows,
)
from repro.pic.distributed import (
    DistConfig,
    build_local_bins,
    dist_global_sort_device,
    dist_pic_step_local,
    in_domain,
    make_dist_sort,
    make_dist_step,
    partition_particles,
    psum_all,
    resolve_sharded_backend,
)
from repro.pic.grid import FieldState, GridSpec
from repro.pic.plasma import ParticleState
from repro.pic.pusher import lorentz_gamma
from repro.pic.simulation import (
    UNSET,
    _DEPRECATION_MSG,
    add_counts,
    commit_window_counts,
    consume_window_bundle,
    resolve_run_args,
)

# Module-level alias so tests can monkeypatch and count the (single) per-
# window device->host transfer, mirroring pic.simulation._fetch_bundle.
_fetch_bundle = jax.device_get

# Trace counter (see pic.simulation._window_trace_count): asserts in-test
# that mixed-length windows (post-growth / end-of-run tails) do not retrace.
_window_trace_count = 0


def make_pic_mesh(sx: int, sy: int):
    """An (sx, sy) device mesh on the default DistConfig axis names."""
    return jax.make_mesh(
        (sx, sy), ("data", "model"), axis_types=(jax.sharding.AxisType.Auto,) * 2
    )


def _mesh_axis_sizes(mesh, axes) -> int:
    n = 1
    for name in axes:
        n *= mesh.shape[name]
    return n


# ---------------------------------------------------------------------------
# The windowed shard_map program
# ---------------------------------------------------------------------------


def _local_energies(fields, u, w, alive, cfg: DistConfig):
    """Per-shard (field, kinetic) energy in float32, same math as
    simulation._energies — callers psum the pair for the global values."""
    vol = cfg.local_grid.cell_volume
    field_e = sum(0.5 * jnp.sum(f.astype(jnp.float32) ** 2) for f in fields) * jnp.float32(vol)
    gamma = lorentz_gamma(u)
    kinetic = jnp.sum(
        w.astype(jnp.float32) * alive.astype(jnp.float32) * cfg.mass * (gamma.astype(jnp.float32) - 1.0)
    )
    return field_e.astype(jnp.float32), kinetic.astype(jnp.float32)


def make_dist_window(mesh, cfg: DistConfig, policy: SortPolicyConfig, n_steps: int,
                     with_energies: bool = True, health: HealthConfig | None = None,
                     with_fault: bool = False):
    """Build the jitted distributed window: `n_steps` scan iterations INSIDE
    one shard_map, one replicated bundle out.

    Call signature of the returned function:
        (fields6, pos, u, w, alive, slots, pslot, slab_d, slab_valid,
         mid_pos, mid_u, policy_state, n_target, presort, resume, step0,
         rebalance_armed, fault_vec)
        -> (fields6, pos, u, w, alive, slots, pslot, slab_d, slab_valid,
            mid_pos, mid_u, policy_state, bundle)

    `n_steps` is static (the compiled scan length); `n_target` is TRACED —
    steps past it are masked pass-throughs, so every window of a run
    (including post-growth and end-of-run tails) reuses one compiled
    program. Input buffers are donated: fields/particles update in place and
    never reshard between steps.

    `mid_pos`/`mid_u` carry the mid-step snapshot of the LAST executed
    step's push output (post z-wrap, pre migration). After a HALT_MIG_RECV
    the host grows `n_local` and re-enters with ``resume=1``: the first step
    of the retry window substitutes the snapshot for its own push output, so
    only the migration/binning half of the discarded step replays — the
    retried step is bit-identical to what the failed step would have
    committed.

    With ``health`` set, every step additionally runs the in-graph sentinel
    (psum-reduced nonfinite counts + charge/energy invariants against
    window-entry references, see core.health.classify_health) and raises
    HALT_NONFINITE / HALT_INVARIANT through the same halt-code channel; the
    checks are pure reads, so a sentinel-on run stays bit-identical to a
    sentinel-off run. With ``with_fault`` the chaos-harness injection
    (distributed.fault) is compiled in, keyed on the traced `fault_vec`.
    """
    cfg = resolve_sharded_backend(cfg)  # concrete name baked at build time
    n_shards = _mesh_axis_sizes(mesh, cfg.x_axes + cfg.y_axes)
    n_slots_total = n_shards * cfg.local_grid.n_cells * cfg.capacity
    need_energies = with_energies or (health is not None and health.check_energy)

    def window_body(fields, pos, u, w, alive, slots, pslot, slab_d, slab_valid,
                    mid_pos, mid_u, pstate, n_target, presort, resume, step0,
                    rebalance_armed, fault_vec):
        global _window_trace_count
        _window_trace_count += 1
        sq = lambda a: a.reshape(a.shape[2:])
        pos, u, w, alive, slots, pslot, slab_d, slab_valid, mid_pos, mid_u = map(
            sq, (pos, u, w, alive, slots, pslot, slab_d, slab_valid, mid_pos, mid_u)
        )
        # capacity-growth re-entry (the windowed halt-and-grow protocol):
        # the host PADDED the slot table / slab to the doubled capacity and
        # asks for one in-graph per-shard sort BEFORE the first step, so the
        # overflowed stragglers are slotted at the new capacity without a
        # separate compiled sort program or an extra host round-trip. Purely
        # local work under lax.cond (presort is replicated — every shard
        # takes the same branch); a still-persisting overflow is caught by
        # the first step's mandatory-sort machinery and halts again.
        pos, u, w, alive, slots, pslot, slab_d, slab_valid = lax.cond(
            presort > 0,
            lambda a: dist_global_sort_device(a[0], a[1], a[2], a[3], cfg)[:8],
            lambda a: a,
            (pos, u, w, alive, slots, pslot, slab_d, slab_valid),
        )

        # window-entry invariant references (the sentinel compares every
        # step against the state it entered the window with; computed after
        # the presort so a capacity growth does not perturb the summation
        # order between reference and check)
        if health is not None:
            ref_charge = psum_all(
                jnp.sum(w.astype(jnp.float32) * alive.astype(jnp.float32)), cfg
            )
            fe0, ke0 = _local_energies(fields, u, w, alive, cfg)
            ref_energy = psum_all(fe0, cfg) + psum_all(ke0, cfg)

        def window_step(carry, i):
            (fields, pos, u, w, alive, slots, pslot, slab_d, slab_valid,
             mid_pos, mid_u, pstate, halted, halt_code, halt_step, halt_inv,
             halt_meas, halt_ref, step_abs, sorts, rebuilds) = carry

            # chaos-harness injection: corrupt the step's INPUT when the
            # absolute step counter hits the armed fault (compiled out
            # entirely when no fault is armed — with_fault is static)
            f_in, u_in, w_in = fields, u, w
            if with_fault:
                f_in = inject_fields(fields, step_abs, fault_vec)
                u_in = inject_momenta(u, step_abs, fault_vec)
                w_in = inject_weights(w, step_abs, fault_vec)

            # mid-step replay: the first live step after a recv-drop retry
            # substitutes the carried snapshot for its own push output, so
            # the discarded step's migration re-runs bit-identically
            use_mid = (resume > 0) & (i == jnp.int32(0)) & ~halted

            # the step always executes (its ppermutes must run on every shard
            # every iteration); outputs are masked once the window is halted —
            # same masked pass-through trick as the single-device window
            (nf, npos, nu, nw, nalive, nslots, npslot, nslab_d, nslab_valid,
             nmid_pos, nmid_u, stats) = dist_pic_step_local(
                f_in, pos, u_in, w_in, alive, slots, pslot, slab_d, slab_valid, cfg,
                mid_pos=mid_pos, mid_u=mid_u, use_mid=use_mid,
            )
            if with_fault:
                stats = dict(
                    stats,
                    mig_recv_dropped=stats["mig_recv_dropped"]
                    + injected_recv_drop(step_abs, fault_vec),
                )

            # in-graph re-sort policy over the psum-reduced stats: the reduced
            # scalars are replicated across shards, so the decision (and hence
            # the lax.cond branch below) is taken uniformly
            with jax.named_scope("pic.policy"):
                mandatory = stats["n_overflow"] > 0
                do_pol, reason_pol, pstate_rec = policy_update(
                    pstate, policy,
                    n_moved=stats["n_moved"], n_alive=stats["n_alive"],
                    n_empty=stats["n_empty"], n_slots=n_slots_total,
                )
                do_pol = do_pol & ~mandatory
                do_sort = mandatory | do_pol
                reason = jnp.where(mandatory, jnp.int32(REASON_OVERFLOW), reason_pol).astype(jnp.int32)

            # per-shard global sort under lax.cond — purely local work (attribute
            # permutation + bin/slab rebuild), so no collective sits inside the
            # cond; the local overflow is psum-reduced afterwards
            def sort_branch(args):
                return dist_global_sort_device(*args, cfg)

            def no_sort(args):
                pos, u, w, alive = args
                return pos, u, w, alive, nslots, npslot, nslab_d, nslab_valid, jnp.zeros((), jnp.int32)

            with jax.named_scope("pic.global_sort"):
                npos, nu, nw, nalive, nslots, npslot, nslab_d, nslab_valid, overflow_local = lax.cond(
                    do_sort, sort_branch, no_sort, (npos, nu, nw, nalive)
                )
                overflow_after = psum_all(overflow_local, cfg)
            with jax.named_scope("pic.policy"):
                pstate_new = jax.tree.map(
                    lambda r, n: jnp.where(do_sort, r, n), policy_reset(), pstate_rec
                )

            # energies of the candidate post-step state: the sentinel checks
            # them, and the per-step diagnostics report them (identical to
            # the post-keep values for every counted step, and masked to
            # zero otherwise)
            if need_energies:
                fe_l, ke_l = _local_energies(nf, nu, nw, nalive, cfg)
                field_e = psum_all(fe_l, cfg)
                kinetic = psum_all(ke_l, cfg)
            else:
                field_e = jnp.zeros((), jnp.float32)
                kinetic = jnp.zeros((), jnp.float32)

            # health sentinel: pure psum-reduced reads of the candidate
            # state — replicated, so every shard classifies identically
            h_inv = jnp.zeros((), jnp.int32)
            h_meas = jnp.zeros((), jnp.float32)
            h_ref = jnp.zeros((), jnp.float32)
            if health is not None:
                ff = jnp.zeros((), jnp.int32)
                mf = jnp.zeros((), jnp.int32)
                if health.check_nonfinite:
                    ff = psum_all(nonfinite_count(list(nf)), cfg)
                    mf = psum_all(nonfinite_count([nu, npos], mask=nalive), cfg)
                charge = psum_all(
                    jnp.sum(nw.astype(jnp.float32) * nalive.astype(jnp.float32)), cfg
                )
                h_code, h_inv, h_meas, h_ref = classify_health(
                    health,
                    fields_nonfinite=ff, momenta_nonfinite=mf,
                    charge=charge, charge_ref=ref_charge,
                    energy=field_e + kinetic, energy_ref=ref_energy,
                )
            else:
                h_code = jnp.zeros((), jnp.int32)

            # load-imbalance trigger (comm co-design): compare the peak
            # per-shard occupancy against the ideal even split. Compiled
            # out entirely when rebalancing is off; gated on the traced
            # `rebalance_armed` flag so the host can disarm it after a
            # no-improvement repartitioning attempt (termination).
            if cfg.comm.rebalance_enable and n_shards > 1:
                halt_imb = (
                    (rebalance_armed > 0)
                    & (stats["n_alive"] > 0)
                    & (
                        stats["max_shard_alive"].astype(jnp.float32) * jnp.float32(n_shards)
                        > jnp.float32(cfg.comm.imbalance_ratio) * stats["n_alive"].astype(jnp.float32)
                    )
                )
            else:
                halt_imb = jnp.zeros((), bool)

            # halt classification (recv-drop discards the whole step: those
            # particles would have been destroyed). Health outranks the
            # growth halts: a poisoned state must not be "fixed" by growing.
            # Imbalance ranks LOWEST — it is a perf optimization request,
            # not a correctness event; any correctness halt wins the step.
            recv_drop = stats["mig_recv_dropped"] > 0
            halt_bin = overflow_after > 0
            halt_send = stats["mig_send_overflow"] > 0
            step_code = jnp.where(
                h_code != jnp.int32(HALT_NONE), h_code,
                jnp.where(
                    recv_drop, jnp.int32(HALT_MIG_RECV),
                    jnp.where(
                        halt_bin, jnp.int32(HALT_BIN_OVERFLOW),
                        jnp.where(
                            halt_send, jnp.int32(HALT_MIG_SEND),
                            jnp.where(halt_imb, jnp.int32(HALT_IMBALANCE), jnp.int32(HALT_NONE)),
                        ),
                    ),
                ),
            )
            executed = ~halted
            counted = executed & ~recv_drop  # a step that survives into n_done

            discard = halted | recv_drop
            keep = lambda old, new: jax.tree.map(lambda o, n: jnp.where(discard, o, n), old, new)
            fields = keep(fields, nf)
            pos, u, w, alive = keep((pos, u, w, alive), (npos, nu, nw, nalive))
            slots, pslot = keep((slots, pslot), (nslots, npslot))
            slab_d, slab_valid = keep((slab_d, slab_valid), (nslab_d, nslab_valid))
            pstate = jax.tree.map(lambda o, n: jnp.where(counted, n, o), pstate, pstate_new)
            sorts = sorts + (counted & do_pol).astype(jnp.int32)
            rebuilds = rebuilds + (counted & mandatory).astype(jnp.int32)
            # the snapshot updates on EXECUTED (including a discarded
            # recv-drop step — capturing its push output is the whole point)
            mid_pos = jnp.where(executed, nmid_pos, mid_pos)
            mid_u = jnp.where(executed, nmid_u, mid_u)

            step_halt = executed & (step_code != HALT_NONE)
            # absolute index (1-based) of the offending step — for a
            # discarded step `counted` is 0, so latch BEFORE the increment
            halt_step = jnp.where(
                step_halt & (halt_code == 0), step_abs + jnp.int32(1), halt_step
            )
            halt_inv = jnp.where(step_halt & (halt_code == 0), h_inv, halt_inv)
            halt_meas = jnp.where(step_halt & (halt_code == 0), h_meas, halt_meas)
            halt_ref = jnp.where(step_halt & (halt_code == 0), h_ref, halt_ref)
            halt_code = jnp.where(halt_code != 0, halt_code, jnp.where(step_halt, step_code, 0))
            step_abs = step_abs + counted.astype(jnp.int32)
            halted = halted | step_halt | (i + 1 >= n_target)

            diag = {
                "active": counted,
                "sorted": do_sort & counted,
                "reason": jnp.where(counted, reason, 0).astype(jnp.int32),
                "n_moved": jnp.where(counted, stats["n_moved"], 0).astype(jnp.int32),
                "n_alive": jnp.where(counted, stats["n_alive"], 0).astype(jnp.int32),
                "n_ranked": jnp.where(counted, stats["n_ranked"], 0).astype(jnp.int32),
                "mig_send_overflow": jnp.where(counted, stats["mig_send_overflow"], 0).astype(jnp.int32),
                "mig_recv_dropped": jnp.where(executed, stats["mig_recv_dropped"], 0).astype(jnp.int32),
                "n_unmigrated": jnp.where(counted, stats["n_unmigrated"], 0).astype(jnp.int32),
                "n_migrated": jnp.where(counted, stats["n_migrated"], 0).astype(jnp.int32),
                "mig_payload_bytes": jnp.where(counted, stats["mig_payload_bytes"], 0).astype(jnp.int32),
                "max_shard_alive": jnp.where(counted, stats["max_shard_alive"], 0).astype(jnp.int32),
                "discarded": (executed & recv_drop).astype(jnp.int32),
                "field_energy": jnp.where(counted, field_e, 0.0),
                "kinetic_energy": jnp.where(counted, kinetic, 0.0),
            }
            carry = (fields, pos, u, w, alive, slots, pslot, slab_d, slab_valid,
                     mid_pos, mid_u, pstate, halted, halt_code, halt_step, halt_inv,
                     halt_meas, halt_ref, step_abs, sorts, rebuilds)
            return carry, diag

        zero = jnp.zeros((), jnp.int32)
        zf = jnp.zeros((), jnp.float32)
        carry0 = (
            fields, pos, u, w, alive, slots, pslot, slab_d, slab_valid,
            mid_pos, mid_u, pstate,
            n_target <= jnp.int32(0), zero, -jnp.ones((), jnp.int32), zero, zf, zf,
            step0.astype(jnp.int32), zero, zero,
        )
        carry, per_step = lax.scan(window_step, carry0, jnp.arange(n_steps, dtype=jnp.int32))
        (fields, pos, u, w, alive, slots, pslot, slab_d, slab_valid,
         mid_pos, mid_u, pstate, halted, halt_code, halt_step, halt_inv,
         halt_meas, halt_ref, _step_abs, sorts, rebuilds) = carry
        bundle = {
            "n_done": jnp.sum(per_step["active"]).astype(jnp.int32),
            "n_sorts": sorts,
            "n_rebuilds": rebuilds,
            "halt_code": halt_code,
            "halt_step": halt_step,
            "halt_inv": halt_inv,
            "halt_measured": halt_meas,
            "halt_reference": halt_ref,
            "n_discarded": jnp.sum(per_step["discarded"]).astype(jnp.int32),
            "per_step": per_step,
        }
        ex = lambda a: a.reshape((1, 1) + a.shape)
        pos, u, w, alive, slots, pslot, slab_d, slab_valid, mid_pos, mid_u = map(
            ex, (pos, u, w, alive, slots, pslot, slab_d, slab_valid, mid_pos, mid_u)
        )
        return (fields, pos, u, w, alive, slots, pslot, slab_d, slab_valid,
                mid_pos, mid_u, pstate, bundle)

    fspec = P(cfg.x_axes, cfg.y_axes, None)

    def spec(*extra):
        return P(cfg.x_axes, cfg.y_axes, *extra)

    in_specs = (
        (fspec,) * 6,
        spec(None, None), spec(None, None), spec(None), spec(None),
        spec(None, None), spec(None),
        spec(None, None, None),  # slab_d
        spec(None, None),        # slab_valid
        spec(None, None),        # mid_pos (mid-step replay snapshot)
        spec(None, None),        # mid_u
        P(),  # policy state (replicated scalars)
        P(),  # n_target
        P(),  # presort flag (capacity-growth re-entry)
        P(),  # resume flag (recv-drop replay re-entry)
        P(),  # step0 (absolute step counter at window entry)
        P(),  # rebalance_armed (imbalance-halt arming flag)
        P(),  # fault_vec (chaos harness; all-shard identical)
    )
    out_specs = (
        (fspec,) * 6,
        spec(None, None), spec(None, None), spec(None), spec(None),
        spec(None, None), spec(None),
        spec(None, None, None), spec(None, None),
        spec(None, None), spec(None, None),  # mid_pos, mid_u
        P(),  # policy state
        P(),  # bundle (everything psum-reduced / replicated)
    )
    # the replication checker (check_vma) is off: the scan carry mixes
    # replicated and sharded leaves, and the replicated outputs here are
    # replicated by construction (every scalar that crosses shards goes
    # through lax.psum)
    sm = jax.shard_map(
        window_body, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )
    return jax.jit(sm, donate_argnums=tuple(range(12)))


# ---------------------------------------------------------------------------
# Host driver
# ---------------------------------------------------------------------------


class DistSimulation:
    """Multi-device driver mirroring `Simulation`'s API on a 2-D shard mesh.

    ``run(n, window=K)`` executes each K-step window as ONE compiled
    shard_map program (see `make_dist_window`): zero per-step host syncs,
    one fetched bundle per window, capacity/`mig_cap`/`n_local` growth as
    the only host escape hatches. ``window=None`` keeps a per-step host loop
    over `make_dist_step` (one stats sync per step, host-side `ResortPolicy`
    with the wall-clock perf trigger) — the baseline the windowed driver is
    benchmarked against (benchmarks/dist_sweep.py).

    Construction takes GLOBAL fields/particles exactly like `Simulation`;
    they are partitioned onto the mesh once, here, and never reshard again.

    Construct via ``repro.api.make_simulation(spec)`` (``MeshSpec("SXxSY")``)
    — the direct constructor is a deprecated shim delegating to the same
    internals with ``spec=None``.
    """

    def __init__(
        self,
        fields: FieldState,
        particles: ParticleState,
        config: DistConfig,
        *,
        mesh=None,
        mesh_shape: tuple[int, int] | None = None,
        n_local: int | None = None,
        policy: SortPolicyConfig | None = None,
        _spec=None,
    ):
        if _spec is None:
            warnings.warn(
                _DEPRECATION_MSG.format(cls="DistSimulation"), DeprecationWarning, stacklevel=2
            )
        self.spec = _spec
        if mesh is None:
            if mesh_shape is None:
                raise ValueError("pass either a mesh or mesh_shape=(sx, sy)")
            mesh = make_pic_mesh(*mesh_shape)
        self.mesh = mesh
        self.config = config
        self.sx = _mesh_axis_sizes(mesh, config.x_axes)
        self.sy = _mesh_axis_sizes(mesh, config.y_axes)

        local = config.local_grid
        self.global_grid = GridSpec(
            shape=(local.shape[0] * self.sx, local.shape[1] * self.sy, local.shape[2]),
            dx=local.dx,
        )
        fshape = tuple(np.asarray(fields.ex).shape)
        if fshape != self.global_grid.shape:
            raise ValueError(
                f"field arrays have shape {fshape} but mesh {self.sx}x{self.sy} of local "
                f"blocks {local.shape} implies a global grid {self.global_grid.shape}"
            )

        if n_local is None:
            n_local = self._default_n_local(particles)
        self.n_local = n_local
        pos, u, w, alive = partition_particles(particles, self.global_grid, self.sx, self.sy, n_local)
        self.pos, self.u, self.w, self.alive = pos, u, w, alive

        # initial binning; grow capacity up front if the initial density
        # already overflows (mirrors Simulation.__init__)
        while True:
            slots, pslot, slab_d, slab_valid, overflow = build_local_bins(
                self.pos, self.alive, local, self.config.capacity
            )
            if not overflow:
                break
            self.config = dataclasses.replace(self.config, capacity=self.config.capacity * 2)
        self.slots, self.pslot = slots, pslot
        self.slab_d, self.slab_valid = slab_d, slab_valid

        # private copies (the windowed program donates its inputs)
        self.fields = tuple(jnp.asarray(f).copy() for f in (
            fields.ex, fields.ey, fields.ez, fields.bx, fields.by, fields.bz
        ))

        self.policy = ResortPolicy(policy)
        self.policy_state = policy_init()
        self.sorts = 0
        self.rebuilds = 0
        # sorter counters (docs/sim_loop.md, "Profiling a run"; add_counts)
        self.moved = 0
        self.particle_steps = 0
        self.ranked = 0
        self.sort_reasons: dict[str, int] = {}
        self._pending_presort = False  # capacity-growth re-entry flag
        self._pending_resume = False   # recv-drop replay re-entry flag
        self.growths = {"capacity": 0, "mig_cap": 0, "n_local": 0, "rebalance": 0}
        self.mig_recv_dropped = 0  # host loop only; the windowed driver never drops
        # communication observability (comm co-design): accumulated from the
        # per-step device counters, serialized into checkpoints and the
        # BENCH_comm/BENCH_dist rows
        self.comm_stats = {"n_migrated": 0, "mig_payload_bytes": 0, "max_imbalance": 0.0}
        # the imbalance halt stays armed until a repartitioning attempt finds
        # no better split (then firing again would livelock the window)
        self._rebalance_armed = True
        self._mesh_ctx: contextlib.ExitStack | None = None
        self.history: list[dict] = []
        self._host_step = 0
        self._fns: dict = {}

        # mid-step replay snapshot (push output of the last executed step;
        # consumed by the resume re-entry after a HALT_MIG_RECV)
        self.mid_pos = jnp.zeros_like(self.pos)
        self.mid_u = jnp.zeros_like(self.u)

        # fault-tolerance counters + supervisor wiring (docs/robustness.md)
        self.halts: dict[str, int] = {}
        self.retries = 0
        self.restarts = 0
        self.discarded_steps = 0
        self._remedy_level = 0
        self._health = _spec.health if (_spec is not None and _spec.health.enable) else None
        self.fault_injector = (
            PICFaultInjector(_spec.fault)
            if (_spec is not None and _spec.fault is not None) else None
        )
        self._prewarm_dispatch()

    def _default_n_local(self, particles: ParticleState) -> int:
        nx_loc, ny_loc = self.config.local_grid.shape[:2]
        pos = np.asarray(particles.pos)
        alive = np.asarray(particles.alive)
        ix = np.clip((pos[:, 0] // nx_loc).astype(int), 0, self.sx - 1)
        iy = np.clip((pos[:, 1] // ny_loc).astype(int), 0, self.sy - 1)
        counts = np.bincount((ix * self.sy + iy)[alive], minlength=self.sx * self.sy)
        peak = int(counts.max()) if counts.size else 0
        return max(8, -(-int(peak * 1.5) // 8) * 8)  # 1.5x headroom, multiple of 8

    # -- jitted program cache (static config knobs key the entries) --------

    def _window_fn(self, window: int, with_energies: bool,
                   health: HealthConfig | None = None, with_fault: bool = False):
        key = ("window", self.config, window, with_energies, health, with_fault)
        if key not in self._fns:
            self._fns[key] = make_dist_window(
                self.mesh, self.config, self.policy.config, window, with_energies,
                health=health, with_fault=with_fault,
            )
        return self._fns[key]

    def _step_fn(self):
        key = ("step", self.config)
        if key not in self._fns:
            self._fns[key] = make_dist_step(self.mesh, self.config)
        return self._fns[key]

    def _sort_fn(self):
        key = ("sort", self.config)
        if key not in self._fns:
            self._fns[key] = make_dist_sort(self.mesh, self.config)
        return self._fns[key]

    # -- drivers -----------------------------------------------------------

    def run(self, n_steps: int | None = None, *, diagnostics_every: int | None = None,
            window: int | None = UNSET, autosave_every: int | None = None,
            autosave_path: str | None = None) -> None:
        """Advance `n_steps` (default: the spec's step count). ``window=K``
        runs the device-resident windowed program; ``window=None`` the
        per-step host loop; unset defaults to the spec window.
        ``autosave_every=N`` checkpoints the run every N steps (and at
        entry/exit) so a hard crash restores and resumes automatically; the
        health sentinel and remediation ladder (spec ``health`` node) apply
        on the windowed path — see docs/robustness.md. As with `Simulation`,
        the two drivers keep independent policy counters — pick one driver
        per DistSimulation."""
        n_steps, diagnostics_every, window, autosave_every, autosave_path = resolve_run_args(
            self.spec, n_steps, diagnostics_every, window, autosave_every, autosave_path
        )
        # the ambient mesh context is held through an ExitStack so a
        # mid-run repartitioning (`_rebalance`) can swap it for the new
        # mesh without unwinding the driver loop
        self._mesh_ctx = contextlib.ExitStack()
        try:
            with self._mesh_ctx:
                self._mesh_ctx.enter_context(jax.set_mesh(self.mesh))
                if window is None:
                    self._run_host(n_steps, diagnostics_every)
                else:
                    self._run_windowed(n_steps, diagnostics_every, window,
                                       autosave_every, autosave_path)
        finally:
            self._mesh_ctx = None

    def _run_windowed(self, n_steps: int, diagnostics_every: int, window: int,
                      autosave_every: int = 0, autosave_path: str = "") -> None:
        if window <= 0:
            raise ValueError(f"window must be positive, got {window}")
        run_supervised_windows(
            self, n_steps, diagnostics_every, window,
            autosave_every=autosave_every, autosave_path=autosave_path,
        )

    # -- supervisor hooks (distributed.fault.run_supervised_windows) --------

    def _enter_window(self, k: int, window: int, diagnostics_every: int,
                      fault_vec) -> dict:
        """Launch ONE compiled window (k live steps of a `window`-length
        program) and fetch its bundle — the single device->host sync of the
        window. Consumes (and clears) the pending presort/resume re-entry
        flags."""
        tag = {"step": self._host_step, "k": k}
        fn = self._window_fn(window, bool(diagnostics_every), self._health,
                             fault_vec is not None)
        presort = jnp.int32(1 if self._pending_presort else 0)
        resume = jnp.int32(1 if self._pending_resume else 0)
        armed = jnp.int32(1 if self._rebalance_armed else 0)
        self._pending_presort = False
        self._pending_resume = False
        vec = no_fault_vec() if fault_vec is None else fault_vec
        # enter every window with the shardings the window returns, so the
        # first window (host-built arrays) and later ones (the previous
        # window's outputs) share one compiled program
        state = self._on_mesh((
            self.fields, self.pos, self.u, self.w, self.alive, self.slots, self.pslot,
            self.slab_d, self.slab_valid, self.mid_pos, self.mid_u,
        ))
        pstate = jax.device_put(self.policy_state, NamedSharding(self.mesh, P()))
        with jax.profiler.TraceAnnotation("pic.window.launch", **tag):
            (self.fields, self.pos, self.u, self.w, self.alive, self.slots, self.pslot,
             self.slab_d, self.slab_valid, self.mid_pos, self.mid_u,
             self.policy_state, bundle) = fn(
                *state, pstate,
                jnp.int32(k), presort, resume, jnp.int32(self._host_step), armed, vec,
            )
        with jax.profiler.TraceAnnotation("pic.window.fetch", **tag):
            return _fetch_bundle(bundle)

    def _on_mesh(self, arrays):
        """Place shard-major arrays (leading (sx, sy) shard axes, or a
        global field grid) on the mesh exactly as the window's shard_map
        splits them — a no-op for arrays already placed so."""
        cfg = self.config

        def put(a):
            spec = P(cfg.x_axes, cfg.y_axes, *([None] * (a.ndim - 2)))
            return jax.device_put(a, NamedSharding(self.mesh, spec))

        return jax.tree.map(put, arrays)

    def _consume_bundle(self, host: dict, diagnostics_every: int) -> int:
        """Commit a successful (or growth-halted) window's accounting."""
        counts = consume_window_bundle(host, self._host_step, diagnostics_every, self.history)
        commit_window_counts(self, counts)
        # communication accounting: the per-step arrays are zero-masked on
        # uncounted steps, so plain sums/maxima commit exactly the kept work
        per = host["per_step"]
        self.comm_stats["n_migrated"] += int(np.sum(per["n_migrated"]))
        self.comm_stats["mig_payload_bytes"] += int(np.sum(per["mig_payload_bytes"]))
        n_alive = np.asarray(per["n_alive"])
        peak = np.asarray(per["max_shard_alive"])
        mask = n_alive > 0
        if mask.any():
            ratio = float(np.max(peak[mask] * (self.sx * self.sy) / n_alive[mask]))
            self.comm_stats["max_imbalance"] = max(self.comm_stats["max_imbalance"], ratio)
        return counts.n_done

    def _take_snapshot(self):
        """Deep-copy the window carry (the windowed call donates its
        inputs), INCLUDING the re-entry flags `_enter_window` clears — a
        rolled-back window must retry with the same presort/resume intent."""
        return (
            jax.tree.map(jnp.copy, self.state),
            jax.tree.map(jnp.copy, self.policy_state),
            self._pending_presort,
            self._pending_resume,
        )

    def _restore_snapshot(self, snap) -> None:
        state, pstate, presort, resume = snap
        self.state = state
        self.policy_state = pstate
        self._pending_presort = presort
        self._pending_resume = resume

    def _handle_halt(self, code: int, host: dict) -> None:
        if code == HALT_BIN_OVERFLOW:
            self._grow_capacity()
        elif code == HALT_MIG_SEND:
            self._grow_mig_cap()
        elif code == HALT_MIG_RECV:
            self._grow_n_local()
            self._pending_resume = True  # replay the discarded step's migration
        elif code == HALT_IMBALANCE:
            self._rebalance()
        else:
            raise RuntimeError(
                f"distributed driver cannot handle halt code {code} ({HALT_NAMES[code]})"
            )

    def _remedy_sort(self) -> None:
        """Remediation-ladder rung 2: force a per-shard global sort and
        reset the device policy counters."""
        self._dist_sort()
        self.policy_state = policy_init()

    def _demote_backend(self) -> bool:
        """Remediation-ladder rung 3: demote the kernel-dispatch backend to
        the next backend down the priority ladder, generalizing the old
        hard-coded "drop Pallas" toggle. Returns False when already at the
        bottom (the ladder is exhausted). `dispatch.demote` never
        benchmarks — remediation must not re-execute the kernels suspected
        of the halt. The key carries ``sharded=True`` (the step runs
        inside shard_map, where only "xla" is available), so on the
        distributed driver this rung reports exhausted immediately — the
        run is already on the most conservative backend."""
        from repro.kernels import dispatch

        nxt = dispatch.demote(
            self.config.backend, order=self.config.order,
            grid_shape=self.config.local_grid.shape, capacity=self.config.capacity,
            dtype=str(self.pos.dtype), sharded=True,
        )
        if nxt is None:
            return False
        self.config = dataclasses.replace(self.config, backend=nxt)
        return True

    # Backward-compatible alias for the pre-dispatcher rung name.
    _drop_pallas = _demote_backend

    def _prewarm_dispatch(self) -> None:
        """Resolve the config's "auto" dispatch keys EAGERLY so the traced
        shard_map window hits the memo. Keys use the LOCAL grid — the
        shape the per-shard step resolves at — and ``sharded=True``:
        Pallas cannot run inside shard_map, so resolution is trivially
        "xla" with no benchmark (the window builders additionally bake the
        concrete name via `resolve_sharded_backend`). Re-run after
        anything that changes the key (capacity growth, restore)."""
        if self.config.backend != "auto":
            return
        from repro.kernels import dispatch

        dispatch.prewarm(
            dispatch.ops_for_modes(self.config.deposition, self.config.gather),
            order=self.config.order, grid_shape=self.config.local_grid.shape,
            capacity=self.config.capacity, dtype=str(self.pos.dtype),
            sharded=True,
        )

    def _run_host(self, n_steps: int, diagnostics_every: int) -> None:
        import time

        for _ in range(n_steps):
            # recomputed per step: _dist_sort can double capacity mid-run
            n_slots_total = self.sx * self.sy * self.config.local_grid.n_cells * self.config.capacity
            t0 = time.perf_counter()
            (self.fields, self.pos, self.u, self.w, self.alive, self.slots, self.pslot,
             self.slab_d, self.slab_valid, stats) = self._step_fn()(
                self.fields, self.pos, self.u, self.w, self.alive, self.slots, self.pslot,
                self.slab_d, self.slab_valid,
            )
            # the per-step host sync: ONE transfer for all stat scalars (a
            # per-key int() would cost a blocking round-trip each)
            stats = {k: int(v) for k, v in jax.device_get(stats).items()}
            self._host_step += 1
            add_counts(self, moved=stats["n_moved"], particle_steps=stats["n_alive"],
                       ranked=stats["n_ranked"])
            self.comm_stats["n_migrated"] += stats["n_migrated"]
            self.comm_stats["mig_payload_bytes"] += stats["mig_payload_bytes"]
            if stats["n_alive"]:
                self.comm_stats["max_imbalance"] = max(
                    self.comm_stats["max_imbalance"],
                    stats["max_shard_alive"] * self.sx * self.sy / stats["n_alive"],
                )
            if stats["mig_recv_dropped"]:
                # the step already applied: those particles are gone. Count
                # the loss honestly and grow so it stops; only the windowed
                # driver can discard-and-retry the offending step.
                self.mig_recv_dropped += stats["mig_recv_dropped"]
                self._grow_n_local()
            if stats["mig_send_overflow"]:
                self._grow_mig_cap()  # stragglers retry with the bigger buffer
            if stats["n_overflow"] > 0:
                self._dist_sort()
                self.rebuilds += 1
                add_counts(self, sort_reasons={REASON_NAMES[REASON_OVERFLOW]: 1})
                self.policy.reset()
            else:
                dtep = time.perf_counter() - t0
                perf = float(stats["n_alive"]) / max(dtep, 1e-9)
                self.policy.record_step(rebuilt=False, perf=perf)
                do, reason = self.policy.should_sort(
                    empty_ratio=stats["n_empty"] / max(n_slots_total, 1)
                )
                if do:
                    self._dist_sort()
                    self.sorts += 1
                    add_counts(self, sort_reasons={reason: 1})
                    self.policy.reset()
            if diagnostics_every and self._host_step % diagnostics_every == 0:
                self.history.append(self.diagnostics())

    # -- growth escape hatches --------------------------------------------

    def _dist_sort(self) -> None:
        """Per-shard global sort at the current capacity; grows capacity
        until the bins absorb every resident particle. Host-loop escape
        hatch only — the windowed driver grows through `_grow_capacity`
        (pad + in-graph presort, no separate sort program)."""
        while True:
            (self.pos, self.u, self.w, self.alive, self.slots, self.pslot,
             self.slab_d, self.slab_valid, overflow) = self._sort_fn()(
                self.pos, self.u, self.w, self.alive
            )
            if int(overflow) == 0:
                return
            self.config = dataclasses.replace(self.config, capacity=self.config.capacity * 2)
            self.growths["capacity"] += 1
            assert self.config.capacity <= 2 * max(self.n_local, 1), (
                "binning overflow persists with capacity > n_local"
            )
            self._prewarm_dispatch()  # capacity is part of the dispatch key

    def _needed_capacity(self) -> int:
        """Occupancy of the densest (shard, cell) pair in the CURRENT state
        — the halt tells the host a growth is needed; this tells it how
        much. One host fetch of replicated scalars; growth is rare."""
        local = self.config.local_grid
        pos = jnp.reshape(self.pos, (-1, 3))
        alive = jnp.reshape(self.alive, (-1,))
        # stragglers (send overflow) carry out-of-range coordinates and do
        # not occupy a bin — mask them exactly like the binning does
        ok = alive & in_domain(pos, local.shape)
        cells = jnp.clip(cell_index(pos, local.shape), 0, local.n_cells - 1)
        shard = jnp.repeat(
            jnp.arange(self.sx * self.sy, dtype=jnp.int32), self.n_local
        )
        flat = shard * local.n_cells + cells
        counts = jnp.zeros(self.sx * self.sy * local.n_cells, jnp.int32).at[flat].add(
            ok.astype(jnp.int32)
        )
        return int(counts.max())

    def _grow_capacity(self) -> None:
        """Windowed halt-and-grow (HALT_BIN_OVERFLOW): grow the bin capacity
        ONCE to fit the densest cell (standard headroom, at least doubling)
        by PADDING the carried slot table / slab arrays — a pure device-side
        reshape, no separate compiled sort program and no overflow fetch
        (the host round-trip `_dist_sort` used to pay) — and flag the next
        window entry to run the in-graph per-shard presort, which slots the
        overflowed stragglers at the new capacity before the first step.
        Sizing from the actual occupancy instead of blind doubling means a
        dense hotspot costs ONE halt instead of one per doubling."""
        old_cap = self.config.capacity
        new_cap = max(choose_capacity(self._needed_capacity()), old_cap * 2)
        self.config = dataclasses.replace(self.config, capacity=new_cap)
        self.growths["capacity"] += 1
        assert new_cap <= 2 * max(self.n_local, 8), (
            "binning overflow persists with capacity > n_local"
        )
        add = new_cap - old_cap
        pad = lambda a, fill: jnp.concatenate(
            [a, jnp.full(a.shape[:3] + (add,) + a.shape[4:], fill, a.dtype)], axis=3
        )
        self.slots = pad(self.slots, np.int32(-1))
        self.slab_d = pad(self.slab_d, 0.0)
        self.slab_valid = pad(self.slab_valid, False)
        # flat slot ids encode cell * capacity + rank — remap to the new row
        # stride so the padded table stays self-consistent (the presort
        # rebuilds everything anyway, but a consistent state never hurts)
        ps = self.pslot
        self.pslot = jnp.where(
            ps >= 0, (ps // old_cap) * new_cap + ps % old_cap, ps
        )
        self._pending_presort = True
        self._prewarm_dispatch()  # capacity is part of the dispatch key

    def _grow_mig_cap(self) -> None:
        self.config = dataclasses.replace(self.config, mig_cap=self.config.mig_cap * 2)
        self.growths["mig_cap"] += 1
        assert self.config.mig_cap <= 4 * max(self.n_local, 1), (
            "migration buffer growth runaway: mig_cap exceeds 4x n_local"
        )

    def _grow_n_local(self) -> None:
        """Double the per-shard particle arrays (dead padding). Bin slot ids
        reference particle indices, which padding preserves."""
        add = self.n_local
        pad = lambda a, fill: jnp.concatenate(
            [a, jnp.full(a.shape[:2] + (add,) + a.shape[3:], fill, a.dtype)], axis=2
        )
        self.pos = pad(self.pos, 0.0)
        self.u = pad(self.u, 0.0)
        self.w = pad(self.w, 0.0)
        self.alive = pad(self.alive, False)
        self.pslot = pad(self.pslot, np.int32(-1))
        # the replay snapshot is index-aligned with pos/u — pad it the same
        # way so a pending resume survives the growth
        self.mid_pos = pad(self.mid_pos, 0.0)
        self.mid_u = pad(self.mid_u, 0.0)
        self.n_local += add
        self.growths["n_local"] += 1

    def _rebalance(self) -> None:
        """Load-aware repartitioning (HALT_IMBALANCE): re-split the global
        domain decomposition so the peak per-shard particle count drops.

        The halting step was KEPT — the state is lossless — so this is a
        pure host-side re-layout: gather the global particle/field state,
        pick the (sx, sy) factorization minimizing the peak shard occupancy
        (`distributed.sharding.plan_balanced_split`), and re-partition onto
        a fresh mesh exactly like construction did. When no strictly better
        split exists the trigger DISARMS instead (otherwise the next window
        would halt on the same state forever); it re-arms only on a later
        successful rebalance. Every cached compiled program keys on the
        replaced config, and the ambient mesh context held by `run()` is
        swapped in place, so the supervisor loop re-enters the window on
        the new decomposition transparently."""
        parts = self.particles_global()
        fields = self.fields_global()
        pos = np.asarray(parts.pos)
        alive = np.asarray(parts.alive)

        # peak occupancy of the CURRENT split, for the strict-improvement test
        nx_loc, ny_loc = self.config.local_grid.shape[:2]
        ix = np.clip((pos[alive, 0] // nx_loc).astype(int), 0, self.sx - 1)
        iy = np.clip((pos[alive, 1] // ny_loc).astype(int), 0, self.sy - 1)
        cur_peak = (
            int(np.bincount(ix * self.sy + iy, minlength=self.sx * self.sy).max())
            if alive.any() else 0
        )

        sx, sy, peak = plan_balanced_split(
            self.sx * self.sy, self.global_grid.shape, self.config.order, pos, alive
        )
        if (sx, sy) == (self.sx, self.sy) or peak >= cur_peak:
            self._rebalance_armed = False
            return

        local = GridSpec(
            shape=(self.global_grid.shape[0] // sx, self.global_grid.shape[1] // sy,
                   self.global_grid.shape[2]),
            dx=self.config.local_grid.dx,
        )
        self.mesh = make_pic_mesh(sx, sy)
        self.sx, self.sy = sx, sy
        self.config = dataclasses.replace(self.config, local_grid=local)
        # size the per-shard particle arrays to the NEW peak (1.5x headroom,
        # rounded up to 8): the imbalanced split padded every shard to the
        # straggler's occupancy, and shrinking that padding is where the
        # rebalanced decomposition's throughput comes from — the n_local
        # growth hatch still covers any later overflow
        self.n_local = max(8, -(-int(peak * 1.5) // 8) * 8)
        self.pos, self.u, self.w, self.alive = partition_particles(
            parts, self.global_grid, sx, sy, self.n_local
        )
        while True:
            slots, pslot, slab_d, slab_valid, overflow = build_local_bins(
                self.pos, self.alive, local, self.config.capacity
            )
            if not overflow:
                break
            self.config = dataclasses.replace(self.config, capacity=self.config.capacity * 2)
            self.growths["capacity"] += 1
        self.slots, self.pslot = slots, pslot
        self.slab_d, self.slab_valid = slab_d, slab_valid
        # re-upload the fields from the gathered host copy: the old device
        # arrays are laid out over the retired mesh
        self.fields = tuple(jnp.asarray(np.asarray(f)) for f in (
            fields.ex, fields.ey, fields.ez, fields.bx, fields.by, fields.bz
        ))
        # the replay snapshot is index-aligned with the OLD partitioning;
        # a rebalance only follows a kept step, so no resume is pending
        self.mid_pos = jnp.zeros_like(self.pos)
        self.mid_u = jnp.zeros_like(self.u)
        self._pending_presort = False
        self._pending_resume = False
        self._rebalance_armed = True
        self.growths["rebalance"] += 1
        # keep the declarative spec in sync with the live decomposition so
        # checkpoints written after the rebalance rebuild the right mesh
        if self.spec is not None:
            self.spec = dataclasses.replace(
                self.spec, mesh=dataclasses.replace(self.spec.mesh, shape=(sx, sy))
            )
        self._fns.clear()  # every cached program was built for the old mesh
        self._prewarm_dispatch()
        if self._mesh_ctx is not None:
            self._mesh_ctx.close()
            self._mesh_ctx.enter_context(jax.set_mesh(self.mesh))

    # -- protocol state view + checkpointing -------------------------------

    @property
    def state(self) -> dict:
        """The device-resident simulation pytree (SimDriver protocol view):
        sharded field blocks + shard-local particle/bin/slab arrays. Plays
        the same role `PICState` plays for the single-device driver."""
        return {
            "fields": self.fields,
            "pos": self.pos, "u": self.u, "w": self.w, "alive": self.alive,
            "slots": self.slots, "pslot": self.pslot,
            "slab_d": self.slab_d, "slab_valid": self.slab_valid,
            "mid_pos": self.mid_pos, "mid_u": self.mid_u,
        }

    @state.setter
    def state(self, tree: dict) -> None:
        self.fields = tuple(tree["fields"])
        self.pos, self.u, self.w = tree["pos"], tree["u"], tree["w"]
        self.alive, self.slots, self.pslot = tree["alive"], tree["slots"], tree["pslot"]
        self.slab_d, self.slab_valid = tree["slab_d"], tree["slab_valid"]
        # pre-robustness checkpoints have no replay snapshot — zeros means
        # "no pending resume", which is always true at a checkpoint boundary
        self.mid_pos = tree.get("mid_pos", jnp.zeros_like(tree["pos"]))
        self.mid_u = tree.get("mid_u", jnp.zeros_like(tree["u"]))

    def save(self, path: str) -> None:
        """Checkpoint the full pytree (state + SortPolicyState) and host
        counters to `path` — see repro.api.facade.save_simulation."""
        from repro.api.facade import save_simulation

        save_simulation(self, path)

    def restore(self, path: str) -> None:
        """Restore a checkpoint written by a compatible driver into this
        one — see repro.api.facade.restore_simulation."""
        from repro.api.facade import restore_simulation

        restore_simulation(self, path)

    # -- host-side views ---------------------------------------------------

    def fields_global(self) -> FieldState:
        """The global field state (host fetch)."""
        ex, ey, ez, bx, by, bz = (np.asarray(f) for f in self.fields)
        return FieldState(ex=jnp.asarray(ex), ey=jnp.asarray(ey), ez=jnp.asarray(ez),
                          bx=jnp.asarray(bx), by=jnp.asarray(by), bz=jnp.asarray(bz))

    def particles_global(self) -> ParticleState:
        """All particle slots flattened to one array with positions shifted
        back to the global frame (dead/unused padding rows keep alive=False;
        unmigrated stragglers keep their out-of-range local coordinates
        shifted by their CURRENT shard's origin)."""
        pos = np.asarray(self.pos).copy()
        nx_loc, ny_loc = self.config.local_grid.shape[:2]
        for a in range(self.sx):
            pos[a, :, :, 0] += a * nx_loc
        for b in range(self.sy):
            pos[:, b, :, 1] += b * ny_loc
        flat = lambda x: jnp.asarray(np.asarray(x).reshape((-1,) + np.asarray(x).shape[3:]))
        return ParticleState(
            pos=jnp.asarray(pos.reshape(-1, 3)),
            u=flat(self.u), w=flat(self.w), alive=flat(self.alive),
        )

    def diagnostics(self) -> dict:
        """Host-facing diagnostics with the same float32 energy definition
        as `Simulation.diagnostics` (this is a device->host sync). The
        global sharded arrays sum to exactly the psum of per-shard sums, so
        this reuses the window's `_local_energies`."""
        fe, ke = _local_energies(self.fields, self.u, self.w, self.alive, self.config)
        field_e, kinetic = float(fe), float(ke)
        return {
            "step": self._host_step,
            "field_energy": field_e,
            "kinetic_energy": kinetic,
            "total_energy": field_e + kinetic,
            "n_alive": int(jnp.sum(self.alive)),
        }
