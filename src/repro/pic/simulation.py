"""The Matrix-PIC simulation loop (paper Algorithm 1).

Per step (jitted `pic_step`):
  1. gather E, B at particles         (matrix gather on current bins)
  2. relativistic Boris push          (VPU-class elementwise work)
  3. incremental sort preparation     (new cell ids -> gpma_update)
  4. deposition                       (scatter | rhocell | matrix)
  5. Maxwell field update             (Yee / CKC)

Two drivers wrap the step:

* Legacy host driver (`Simulation.run` with ``window=None``): one jitted
  step per Python iteration, the adaptive re-sort policy evaluated on the
  host from synced GPMAStats scalars (plus a wall-clock perf trigger). This
  costs several device→host syncs per step, which serializes dispatch.

* Device-resident windowed driver (`Simulation.run(..., window=K)` /
  `pic_run_window`): a whole window of K steps runs as ONE compiled
  `lax.scan` with donated buffers. The re-sort policy (core.resort_policy
  device path), the mandatory overflow rebuild, and the global sort itself
  (`global_sort_device` under `lax.cond`) all happen in-graph; per-step
  diagnostics accumulate on device, and the host fetches exactly one bundle
  per window. Capacity growth is the only host escape hatch: a persistent
  post-sort overflow halts the remaining steps of the window (they become
  no-ops), the host doubles the bin capacity and re-enters. See
  docs/sim_loop.md.

The host-side `Simulation` driver implements the paper's adaptive global
re-sort policy (resort_policy): overflow -> mandatory rebuild; interval /
rebuild-count / gap-ratio / perf triggers -> global counting sort INCLUDING
the SoA attribute permutation (memory coherence).

`sort_mode` gives the paper's ablation axes:
  "incremental"  FullOpt: GPMA + adaptive policy
  "rebuild"      Matrix-only: bins rebuilt from scratch every step (indices
                 only — no attribute permutation)
  "global"       Hybrid-GlobalSort: full sort (indices + attributes) each step
  "none"         for scatter deposition paths that need no bins
"""

from __future__ import annotations

import dataclasses
import os
import time
import warnings
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import (
    REASON_NAMES,
    BinSlab,
    ResortPolicy,
    SortPolicyConfig,
    SortPolicyState,
    bin_slab_staging,
    build_bin_slab,
    build_bins,
    cell_index,
    choose_capacity,
    deposit_current_matrix_fused,
    deposit_matrix,
    deposit_rhocell,
    deposit_scatter,
    fold_guards,
    gather_fields_fused,
    gather_matrix,
    gather_scatter,
    gpma_update,
    max_guard,
    policy_init,
    policy_reset,
    policy_update,
    sort_permutation,
    unfold_guards,
)
from repro.core.binning import BinnedLayout
from repro.core.gpma import GPMAStats
from repro.core.health import (
    HALT_BIN_OVERFLOW,
    HALT_NAMES,
    HALT_NONE,
    HealthConfig,
    classify_health,
    nonfinite_count,
)
from repro.core.resort_policy import REASON_OVERFLOW
from repro.distributed.fault import (
    PICFaultInjector,
    inject_fields,
    inject_momenta,
    inject_weights,
    no_fault_vec,
    run_supervised_windows,
)
from repro.grad.permutations import permute_tree
from repro.pic.grid import B_STAGGER, E_STAGGER, FieldState, GridSpec
from repro.pic.maxwell import maxwell_step
from repro.pic.plasma import ParticleState
from repro.pic.pusher import advance_positions, boris_push, lorentz_gamma, wrap_periodic


@dataclasses.dataclass(frozen=True)
class PICConfig:
    grid: GridSpec
    dt: float
    order: int = 1
    deposition: str = "matrix"   # scatter | rhocell | matrix (fused) | matrix_unfused
    gather: str = "matrix"       # scatter | matrix (fused) | matrix_unfused (six-call)
    sort_mode: str = "incremental"
    charge: float = -1.0
    mass: float = 1.0
    ckc_beta: float = 0.0
    capacity: int = 16
    backend: str = "auto"        # kernel-dispatch backend for the bin
                                 # contractions (deposition AND gather):
                                 # auto | xla | pallas | pallas_reduced
    dispatch_batch: int = 1      # leading vmap member axis the step runs
                                 # under (the ensemble engine sets this to
                                 # the bucket width so the dispatcher keys
                                 # autotune per batched shape instead of
                                 # replaying single-sim winners)

    @property
    def q_over_m(self) -> float:
        return self.charge / self.mass

    @property
    def guard(self) -> int:
        return max_guard(self.order)

    @property
    def needs_bins(self) -> bool:
        return self.deposition in ("matrix", "matrix_unfused") or self.gather in ("matrix", "matrix_unfused")

    @property
    def needs_slab(self) -> bool:
        """Whether the step stages (and the state carries) a `BinSlab` —
        exactly when a FUSED bin kernel consumes it. The unfused ablation
        modes keep their historical per-call staging."""
        return self.deposition == "matrix" or self.gather == "matrix"


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PICState:
    fields: FieldState
    particles: ParticleState
    layout: BinnedLayout
    step: jax.Array
    # The step's one bin-resident staging slab (None unless a fused bin
    # kernel consumes it — config.needs_slab). Always consistent with
    # (particles.pos, layout): rebuilt right after every bin update and
    # after every global sort, so the slab the deposition of step n
    # contracts against is the slab the gather of step n+1 reuses.
    slab: BinSlab | None = None


def _state_slab(particles: ParticleState, layout: BinnedLayout, config: PICConfig) -> BinSlab | None:
    """The ONE slot-table slab staging of a step (see binning.BinSlab)."""
    if not config.needs_slab:
        return None
    return build_bin_slab(particles.pos, layout, grid_shape=config.grid.shape)


def init_state(fields: FieldState, particles: ParticleState, config: PICConfig) -> tuple[PICState, int]:
    """Global init (paper Alg. 1 lines 1-5): global sort + GPMA build."""
    cells = cell_index(particles.pos, config.grid.shape)
    perm = sort_permutation(cells, particles.alive)
    particles = permute_tree(particles, perm)
    cells = cell_index(particles.pos, config.grid.shape)
    layout, overflow = build_bins(cells, particles.alive, n_cells=config.grid.n_cells, capacity=config.capacity)
    state = PICState(
        fields=fields, particles=particles, layout=layout, step=jnp.int32(0),
        slab=_state_slab(particles, layout, config),
    )
    return state, int(overflow)


def _gather_fields(pos, fields: FieldState, layout, slab: BinSlab | None, config: PICConfig):
    g = config.guard
    shape = config.grid.shape
    pe = [unfold_guards(f, g) for f in fields.e()]
    pb = [unfold_guards(f, g) for f in fields.b()]
    if config.gather == "matrix":
        # default hot path: fused six-component pass over the step's slab —
        # no re-staging, six shared weight sets, one slot-map scatter-back;
        # the contraction backend resolves through the kernel dispatcher
        return gather_fields_fused(
            slab, tuple(pe) + tuple(pb), layout,
            grid_shape=shape, order=config.order, backend=config.backend,
            batch=config.dispatch_batch,
        )
    comps_e, comps_b = [], []
    if config.gather == "matrix_unfused":
        # six-call ablation mode: each component re-stages the slab and
        # recomputes its three weight sets
        for k in range(3):
            comps_e.append(gather_matrix(pos, pe[k], layout, grid_shape=shape, order=config.order, stagger=E_STAGGER[k], backend=config.backend, batch=config.dispatch_batch))
            comps_b.append(gather_matrix(pos, pb[k], layout, grid_shape=shape, order=config.order, stagger=B_STAGGER[k], backend=config.backend, batch=config.dispatch_batch))
    else:
        for k in range(3):
            comps_e.append(gather_scatter(pos, pe[k], order=config.order, stagger=E_STAGGER[k]))
            comps_b.append(gather_scatter(pos, pb[k], order=config.order, stagger=B_STAGGER[k]))
    return jnp.stack(comps_e, -1), jnp.stack(comps_b, -1)


def _deposit_current(pos, v, qw, layout, slab, cells, config: PICConfig, values=None):
    shape = config.grid.shape
    inv_vol = 1.0 / config.grid.cell_volume

    if config.deposition == "matrix":
        # default hot path: fused three-component megakernel consuming the
        # step's slab — shared shape weights, packed Jx/Jy/Jz contraction;
        # the contraction backend resolves through the kernel dispatcher
        j3 = deposit_current_matrix_fused(
            pos, v, qw, layout, grid_shape=shape, order=config.order,
            backend=config.backend, slab=slab, batch=config.dispatch_batch,
            values=values,
        )
        return [fold_guards(j, config.guard) * inv_vol for j in j3]

    # comparison modes: scatter | rhocell | matrix_unfused (per component)
    out = []
    for k, stagger in enumerate(((True, False, False), (False, True, False), (False, False, True))):
        values = qw * v[:, k]
        if config.deposition == "scatter":
            j = deposit_scatter(pos, values, grid_shape=shape, order=config.order, stagger=stagger)
        elif config.deposition == "rhocell":
            j = deposit_rhocell(pos, values, cells, grid_shape=shape, order=config.order, stagger=stagger)
        elif config.deposition == "matrix_unfused":
            j = deposit_matrix(pos, values, layout, grid_shape=shape, order=config.order, stagger=stagger, backend=config.backend, batch=config.dispatch_batch)
        else:
            raise ValueError(f"unknown deposition method {config.deposition}")
        out.append(fold_guards(j, config.guard) * inv_vol)
    return out


def _pic_step(state: PICState, config: PICConfig) -> tuple[PICState, GPMAStats]:
    """One simulation step (traceable; jitted as pic_step / pic_step_donated
    and inlined into the scan window by pic_run_window)."""
    p = state.particles
    alive_f = p.alive.astype(p.pos.dtype)

    # 1. field gather (bins AND the carried slab are current w.r.t.
    #    pre-push positions: the slab the previous step staged for its
    #    deposition is exactly this step's gather staging)
    with jax.named_scope("pic.gather"):
        e_p, b_p = _gather_fields(p.pos, state.fields, state.layout, state.slab, config)

    # 2. push
    with jax.named_scope("pic.push"):
        u_new = boris_push(p.u, e_p, b_p, config.q_over_m, config.dt)
        u_new = jnp.where(p.alive[:, None], u_new, p.u)
        pos_new = wrap_periodic(advance_positions(p.pos, u_new, config.dt, config.grid.dx), config.grid.shape)
        pos_new = jnp.where(p.alive[:, None], pos_new, p.pos)

    # 3. incremental sort / rebuild
    with jax.named_scope("pic.gpma"):
        new_cells = cell_index(pos_new, config.grid.shape)
        if config.sort_mode in ("incremental",):
            layout, stats = gpma_update(state.layout, new_cells, p.alive)
        elif config.sort_mode in ("rebuild", "global"):
            layout, overflow = build_bins(new_cells, p.alive, n_cells=config.grid.n_cells, capacity=config.capacity)
            stats = GPMAStats(
                n_moved=jnp.sum(new_cells != cell_index(p.pos, config.grid.shape)),
                n_overflow=overflow,
                n_empty=layout.n_empty(),
                n_alive=jnp.sum(p.alive),
                n_ranked=jnp.int32(new_cells.shape[0]),
            )
        else:  # none
            layout = state.layout
            stats = GPMAStats(
                n_moved=jnp.int32(0), n_overflow=jnp.int32(0),
                n_empty=jnp.int32(0), n_alive=jnp.sum(p.alive),
                n_ranked=jnp.int32(0),
            )

    # 3b. the step's ONE slab staging, consistent with (pos_new, layout):
    # consumed by the deposition below and carried for the next gather.
    # Velocity and charge-weight come first so the fused matrix path can
    # stage positions AND deposition values off a single slot-table gather
    # instead of a second gather inside the deposit kernel.
    with jax.named_scope("pic.push"):
        particles = dataclasses.replace(p, pos=pos_new, u=u_new)
        gamma = lorentz_gamma(u_new)
        v = u_new / gamma[:, None]
        qw = config.charge * p.w * alive_f
    with jax.named_scope("pic.stage"):
        values = None
        if config.deposition == "matrix":
            slab, values = bin_slab_staging(pos_new, v, qw, layout, grid_shape=config.grid.shape)
        else:
            slab = _state_slab(particles, layout, config)

    # 4. deposition at x^{n+1}, v^{n+1/2}
    with jax.named_scope("pic.deposit"):
        j = _deposit_current(pos_new, v, qw, layout, slab, new_cells, config, values=values)

    # 5. fields
    with jax.named_scope("pic.maxwell"):
        fields = maxwell_step(state.fields, j, dx=config.grid.dx, dt=config.dt, ckc_beta=config.ckc_beta)

    return PICState(fields=fields, particles=particles, layout=layout, step=state.step + 1, slab=slab), stats


pic_step = partial(jax.jit, static_argnames=("config",))(_pic_step)

# Same step with the input state's buffers donated: particle and field arrays
# update in place instead of being copied every step. Used by the Simulation
# drivers, which always replace their state reference with the result. Do NOT
# use this variant when re-invoking on a saved state (benchmarks that time
# the same state repeatedly must use `pic_step`).
pic_step_donated = partial(jax.jit, static_argnames=("config",), donate_argnums=(0,))(_pic_step)


def global_sort_device(state: PICState, config: PICConfig) -> tuple[PICState, jax.Array]:
    """GlobalSortParticlesByCell, traceable: permute attributes + rebuild
    bins (and the staging slab — the sort invalidates both), returning
    overflow as a traced int32 scalar so the sort can run inside jit /
    under `lax.cond` in the scan window."""
    with jax.named_scope("pic.global_sort"):
        cells = cell_index(state.particles.pos, config.grid.shape)
        perm = sort_permutation(cells, state.particles.alive)
        # the sort is a piecewise-constant permutation: the index computation is
        # stop-gradient, the value movement differentiable (grad.permutations) —
        # bitwise identical to plain a[perm] in the forward pass
        particles = permute_tree(state.particles, perm)
        cells = cell_index(particles.pos, config.grid.shape)
        layout, overflow = build_bins(cells, particles.alive, n_cells=config.grid.n_cells, capacity=config.capacity)
        state = dataclasses.replace(
            state, particles=particles, layout=layout,
            slab=_state_slab(particles, layout, config),
        )
        return state, overflow.astype(jnp.int32)


def global_sort(state: PICState, config: PICConfig) -> tuple[PICState, int]:
    """Host-facing wrapper around `global_sort_device` (syncs the overflow)."""
    state, overflow = global_sort_device(state, config)
    return state, int(overflow)


# ---------------------------------------------------------------------------
# Device-resident windowed driver: K steps as one lax.scan, zero per-step
# host syncs. The host fetches a single diagnostics bundle per window.
# ---------------------------------------------------------------------------


def _energies(state: PICState, config: PICConfig) -> tuple[jax.Array, jax.Array]:
    """(field, kinetic) energy in float32 — the ONE definition shared by
    host-side Simulation.diagnostics() and the in-graph window diagnostics,
    so the two drivers report identical values."""
    gamma = lorentz_gamma(state.particles.u)
    alive_f = state.particles.alive.astype(jnp.float32)
    kinetic = jnp.sum(
        state.particles.w.astype(jnp.float32) * alive_f * config.mass * (gamma.astype(jnp.float32) - 1.0)
    ).astype(jnp.float32)
    field_e = state.fields.energy(config.grid.cell_volume).astype(jnp.float32)
    return field_e, kinetic


def _zeros_diag():
    f = jnp.zeros((), jnp.float32)
    i = jnp.zeros((), jnp.int32)
    return {
        "active": jnp.zeros((), bool),
        "sorted": jnp.zeros((), bool),
        "reason": i,
        "n_moved": i,
        "n_alive": i,
        "n_ranked": i,
        "field_energy": f,
        "kinetic_energy": f,
    }


def _total_charge(state: PICState) -> jax.Array:
    """Sum of alive macro-particle weights (float32) — exactly conserved by
    the step, so the sentinel's charge invariant compares against the value
    captured at window entry."""
    return jnp.sum(
        state.particles.w.astype(jnp.float32) * state.particles.alive.astype(jnp.float32)
    ).astype(jnp.float32)


def _apply_fault(state: PICState, fault_vec) -> PICState:
    """Chaos harness hook: corrupt the step INPUT when the armed fault vector
    fires at this step counter (see distributed.fault.FaultSpec). Compiled in
    only when the window is built with a fault armed (`with_fault`), so the
    production program carries zero overhead."""
    f = state.fields
    ex, ey, ez, bx, by, bz = inject_fields(
        (f.ex, f.ey, f.ez, f.bx, f.by, f.bz), state.step, fault_vec
    )
    fields = dataclasses.replace(f, ex=ex, ey=ey, ez=ez, bx=bx, by=by, bz=bz)
    p = state.particles
    particles = dataclasses.replace(
        p,
        u=inject_momenta(p.u, state.step, fault_vec),
        w=inject_weights(p.w, state.step, fault_vec),
    )
    return dataclasses.replace(state, fields=fields, particles=particles)


def _window_active_step(state, pstate, sorts, rebuilds, config: PICConfig,
                        policy: SortPolicyConfig, with_energies: bool,
                        health: HealthConfig | None, ref_charge, ref_energy):
    """One live step of the scan window: pic_step + in-graph sort decision +
    conditional global sort, mirroring the legacy host driver's control flow
    step for step (see Simulation.run). With `health` set, the sentinel's
    pure-read checks classify the post-step state; the returned `step_code`
    is one of the core.health halt codes (HALT_NONE = healthy)."""
    n_slots = config.grid.n_cells * config.capacity
    state, stats = _pic_step(state, config)

    no_sort = lambda s: (s, jnp.zeros((), jnp.int32))
    do_sort = jnp.zeros((), bool)
    reason = jnp.zeros((), jnp.int32)
    overflow_after = jnp.zeros((), jnp.int32)

    if config.sort_mode == "incremental":
        with jax.named_scope("pic.policy"):
            mandatory = (stats.n_overflow > 0) if config.needs_bins else jnp.zeros((), bool)
            do_pol, reason_pol, pstate_rec = policy_update(
                pstate, policy,
                n_moved=stats.n_moved, n_alive=stats.n_alive,
                n_empty=stats.n_empty, n_slots=n_slots,
            )
            do_pol = do_pol & ~mandatory
            do_sort = mandatory | do_pol
        with jax.named_scope("pic.global_sort"):
            state, overflow_after = lax.cond(
                do_sort, lambda s: global_sort_device(s, config), no_sort, state
            )
        with jax.named_scope("pic.policy"):
            # after a sort (mandatory or triggered) the counters reset; otherwise
            # keep the recorded (post-record_step) state — exactly the host order
            pstate = jax.tree.map(
                lambda r, n: jnp.where(do_sort, r, n), policy_reset(), pstate_rec
            )
            sorts = sorts + do_pol.astype(jnp.int32)
            rebuilds = rebuilds + mandatory.astype(jnp.int32)
            reason = jnp.where(
                mandatory, jnp.int32(REASON_OVERFLOW), reason_pol
            ).astype(jnp.int32)
    elif config.sort_mode == "global":
        # per-step full sort including attribute permutation
        state, overflow_after = global_sort_device(state, config)
        do_sort = jnp.ones((), bool)
    elif config.sort_mode == "rebuild":
        # bins were rebuilt inside _pic_step; overflow -> capacity too small
        overflow_after = stats.n_overflow.astype(jnp.int32)
    # "none": nothing to decide

    with jax.named_scope("pic.diag"):
        need_energies = with_energies or (health is not None and health.check_energy)
        if need_energies:
            field_e, kinetic = _energies(state, config)
        else:
            kinetic = jnp.zeros((), jnp.float32)
            field_e = jnp.zeros((), jnp.float32)

        diag = {
            "active": jnp.ones((), bool),
            "sorted": do_sort,
            "reason": reason,
            "n_moved": stats.n_moved.astype(jnp.int32),
            "n_alive": stats.n_alive.astype(jnp.int32),
            "n_ranked": stats.n_ranked.astype(jnp.int32),
            "field_energy": field_e if with_energies else jnp.zeros((), jnp.float32),
            "kinetic_energy": kinetic if with_energies else jnp.zeros((), jnp.float32),
        }

        # health sentinel: pure reads of the post-step state — no arithmetic of
        # the step itself changes, so a healthy sentinel-on run stays
        # bit-identical to a sentinel-off run (tests/test_health.py pins this)
        zero_i = jnp.zeros((), jnp.int32)
        zero_f = jnp.zeros((), jnp.float32)
        h_code, h_inv, h_meas, h_ref = zero_i, zero_i, zero_f, zero_f
        if health is not None:
            p = state.particles
            ff = mf = zero_i
            if health.check_nonfinite:
                f = state.fields
                ff = nonfinite_count([f.ex, f.ey, f.ez, f.bx, f.by, f.bz])
                mf = nonfinite_count([p.u, p.pos], mask=p.alive)
            h_code, h_inv, h_meas, h_ref = classify_health(
                health,
                fields_nonfinite=ff, momenta_nonfinite=mf,
                charge=_total_charge(state), charge_ref=ref_charge,
                energy=field_e + kinetic, energy_ref=ref_energy,
            )

        # persistent overflow (a bin fuller than `capacity` even after the sort)
        # halts the window exactly as before; a health violation outranks it
        # (a corrupt state must roll back before any capacity reaction)
        step_code = jnp.where(
            h_code != HALT_NONE, h_code,
            jnp.where(overflow_after > 0, jnp.int32(HALT_BIN_OVERFLOW), jnp.int32(HALT_NONE)),
        )
    return state, pstate, step_code, sorts, rebuilds, diag, (h_inv, h_meas, h_ref)


# Trace-time counter: incremented every time the window impl is (re)traced.
# Tests read the delta to assert that mixed-length runs (post-growth tails,
# end-of-run tails with k < window) do NOT recompile — the padded fixed-size
# window is compiled once per static (config, policy, n_steps, with_energies).
_window_trace_count = 0


def _pic_run_window_impl(state, pstate, n_target, fault_vec, config: PICConfig,
                         policy: SortPolicyConfig, n_steps: int, with_energies: bool,
                         health: HealthConfig | None, with_fault: bool,
                         remat: str = "none", remat_chunk: int = 0):
    global _window_trace_count
    _window_trace_count += 1

    # invariant references, captured at window entry: the sentinel compares
    # every step of the window against the state it started from
    with jax.named_scope("pic.diag"):
        if health is not None:
            ref_charge = _total_charge(state)
            ref_fe, ref_ke = _energies(state, config)
            ref_energy = ref_fe + ref_ke
        else:
            ref_charge = ref_energy = jnp.zeros((), jnp.float32)

    def body(carry, i):
        (state, pstate, halted, halt_code, halt_step, halt_inv, halt_meas,
         halt_ref, sorts, rebuilds) = carry
        # The step always executes and its outputs are MASKED once the window
        # is halted, rather than branching with lax.cond: on the CPU backend a
        # conditional whose branch contains the whole step body costs ~2x the
        # step itself, while the masking selects are nearly free. Post-halt
        # steps therefore burn (discarded) FLOPs, but a halt ends the window
        # at most once per capacity growth — a rare event. The traced target
        # length reuses the same halt flag: step i+1 onward is masked once
        # i + 1 >= n_target, so post-growth and end-of-run tails (k < window)
        # run the one compiled program instead of retracing per length; a
        # per-step ys flag ("halt") distinguishes a genuine halt from simple
        # target exhaustion in the fetched bundle.
        st_in = _apply_fault(state, fault_vec) if with_fault else state
        new_state, new_pstate, step_code, new_sorts, new_rebuilds, diag, hinfo = _window_active_step(
            st_in, pstate, sorts, rebuilds, config, policy, with_energies,
            health, ref_charge, ref_energy
        )
        with jax.named_scope("pic.mask"):
            halted_step = step_code != HALT_NONE
            diag = dict(diag, halt=halted_step)
            keep = lambda old, new: jax.tree.map(lambda o, n: jnp.where(halted, o, n), old, new)
            # first genuine halt of the window latches its full classification
            # (code, absolute step, offending invariant, measured/reference)
            first = halted_step & ~halted
            carry = (
                keep(state, new_state),
                keep(pstate, new_pstate),
                halted | halted_step | (i + 1 >= n_target),
                jnp.where(first, step_code, halt_code),
                jnp.where(first, new_state.step, halt_step),
                jnp.where(first, hinfo[0], halt_inv),
                jnp.where(first, hinfo[1], halt_meas),
                jnp.where(first, hinfo[2], halt_ref),
                jnp.where(halted, sorts, new_sorts),
                jnp.where(halted, rebuilds, new_rebuilds),
            )
            return carry, keep(dict(_zeros_diag(), halt=jnp.zeros((), bool)), diag)

    zero = jnp.zeros((), jnp.int32)
    zero_f = jnp.zeros((), jnp.float32)
    carry0 = (state, pstate, n_target <= jnp.int32(0), zero, jnp.int32(-1),
              zero, zero_f, zero_f, zero, zero)
    xs = jnp.arange(n_steps, dtype=jnp.int32)
    # Rematerialization policy for reverse-mode (run_window_diff). The primal
    # computation is untouched — jax.checkpoint is the identity on the
    # forward pass — so "none" IS the production program and the remat
    # variants stay bit-identical forward (tests/test_grad.py pins this).
    # `prevent_cse=False` is the documented setting under scan, where the
    # loop structure already prevents the CSE that checkpoint guards against.
    if remat == "step":
        # one remat point per step: backward recomputes each step from its
        # carry, so peak residency is O(window state), not O(n_steps x state)
        carry, per_step = lax.scan(
            jax.checkpoint(body, prevent_cse=False), carry0, xs
        )
    elif remat == "chunk":
        # one remat point per `remat_chunk`-step sub-window: the backward
        # keeps chunk boundaries and recomputes inside each chunk — the
        # memory/recompute trade dialed between "none" and "step"
        if remat_chunk <= 0 or n_steps % remat_chunk:
            raise ValueError(
                f"remat='chunk' needs remat_chunk > 0 dividing n_steps, "
                f"got remat_chunk={remat_chunk}, n_steps={n_steps}"
            )
        chunk = jax.checkpoint(
            lambda c, ii: lax.scan(body, c, ii), prevent_cse=False
        )
        carry, per_step = lax.scan(
            chunk, carry0, xs.reshape(n_steps // remat_chunk, remat_chunk)
        )
        per_step = jax.tree.map(
            lambda a: a.reshape((n_steps,) + a.shape[2:]), per_step
        )
    elif remat == "none":
        carry, per_step = lax.scan(body, carry0, xs)
    else:
        raise ValueError(f"unknown remat policy {remat!r} (none | step | chunk)")
    (state, pstate, halted, halt_code, halt_step, halt_inv, halt_meas,
     halt_ref, sorts, rebuilds) = carry
    per_step.pop("halt")
    bundle = {
        "n_done": jnp.sum(per_step["active"]).astype(jnp.int32),
        "n_sorts": sorts,
        "n_rebuilds": rebuilds,
        # kept for direct pic_run_window callers (pre-halt-code protocol)
        "overflow_pending": halt_code == jnp.int32(HALT_BIN_OVERFLOW),
        "halt_code": halt_code,
        "halt_step": halt_step,
        "halt_inv": halt_inv,
        "halt_measured": halt_meas,
        "halt_reference": halt_ref,
        "per_step": per_step,
    }
    return state, pstate, bundle


_WINDOW_STATICS = ("config", "policy", "n_steps", "with_energies", "health",
                   "with_fault", "remat", "remat_chunk")
_pic_run_window_jit = partial(jax.jit, static_argnames=_WINDOW_STATICS)(_pic_run_window_impl)
_pic_run_window_donated = partial(
    jax.jit, static_argnames=_WINDOW_STATICS, donate_argnums=(0, 1)
)(_pic_run_window_impl)

# Module-level alias so tests can monkeypatch and count the (single) per-
# window device->host transfer performed by the windowed driver.
_fetch_bundle = jax.device_get


@dataclasses.dataclass(frozen=True)
class WindowCounts:
    """What one fetched window bundle adds to a driver's counters: its live
    steps, global sorts and mandatory rebuilds, and over the live steps the
    sums of the per-step ``n_moved``, ``n_alive`` and ``n_ranked`` and the
    sorted steps by ``REASON_NAMES`` (every sort, whatever its trigger:
    they sum to ``sorts + rebuilds`` under the incremental sort mode)."""

    n_done: int
    sorts: int
    rebuilds: int
    moved: int
    particle_steps: int
    ranked: int
    sort_reasons: dict


def consume_window_bundle(host: dict, host_step: int, diagnostics_every: int,
                          history: list) -> WindowCounts:
    """Host-side accounting for a FETCHED window bundle, shared by the
    single-device, distributed and ensemble windowed drivers: returns the
    window's `WindowCounts` and appends every ``diagnostics_every``-th
    per-step diagnostics record to ``history``."""
    n_done = int(host["n_done"])
    per = host["per_step"]
    if diagnostics_every:
        for i in range(n_done):
            step_abs = host_step + i + 1
            if step_abs % diagnostics_every == 0:
                fe = float(per["field_energy"][i])
                ke = float(per["kinetic_energy"][i])
                history.append({
                    "step": step_abs,
                    "field_energy": fe,
                    "kinetic_energy": ke,
                    "total_energy": fe + ke,
                    "n_alive": int(per["n_alive"][i]),
                    # windowed drivers only (the host loop's diagnostics()
                    # snapshots state, which has no per-step churn counter)
                    "n_moved": int(per["n_moved"][i]),
                })
    live = np.asarray(per["active"], bool)
    total = lambda key: int(np.sum(np.asarray(per[key], np.int64)[live]))
    reasons: dict[str, int] = {}
    for code in np.asarray(per["reason"])[live & np.asarray(per["sorted"], bool)]:
        name = REASON_NAMES[int(code)]
        reasons[name] = reasons.get(name, 0) + 1
    return WindowCounts(
        n_done=n_done, sorts=int(host["n_sorts"]), rebuilds=int(host["n_rebuilds"]),
        moved=total("n_moved"), particle_steps=total("n_alive"), ranked=total("n_ranked"),
        sort_reasons=reasons,
    )


def add_counts(driver, *, moved: int = 0, particle_steps: int = 0, ranked: int = 0,
               sort_reasons: dict | None = None) -> None:
    """Add to a driver's counters beside ``sorts`` and ``rebuilds``:
    ``moved`` (particles that changed cell), ``particle_steps`` (live
    particles summed over steps), ``ranked`` (keys the GPMA rank sorted and
    scanned) and ``sort_reasons`` ({reason name: global sorts})."""
    driver.moved += moved
    driver.particle_steps += particle_steps
    driver.ranked += ranked
    for name, n in (sort_reasons or {}).items():
        driver.sort_reasons[name] = driver.sort_reasons.get(name, 0) + n


def commit_window_counts(driver, counts: WindowCounts) -> None:
    """Add a consumed window's counts to a (single-simulation) driver."""
    driver.sorts += counts.sorts
    driver.rebuilds += counts.rebuilds
    add_counts(driver, moved=counts.moved, particle_steps=counts.particle_steps,
               ranked=counts.ranked, sort_reasons=counts.sort_reasons)
    driver._host_step += counts.n_done


def pic_run_window(
    state: PICState,
    policy_state: SortPolicyState,
    config: PICConfig,
    n_steps: int,
    *,
    policy: SortPolicyConfig | None = None,
    with_energies: bool = True,
    donate: bool = True,
    n_target: int | jax.Array | None = None,
    health: HealthConfig | None = None,
    fault_vec: jax.Array | None = None,
):
    """Run a window of `n_steps` PIC steps as ONE compiled `lax.scan` with
    zero per-step host syncs: step, in-graph re-sort policy, conditional
    global sort, and per-step diagnostics all stay on device.

    ``n_steps`` is static (it sets the compiled scan length); ``n_target``
    is a TRACED step count ``<= n_steps`` — steps past it are masked
    pass-throughs (same trick as the overflow halt). Drivers always compile
    the full ``window`` length and vary only ``n_target``, so post-growth
    and end-of-run tails reuse one compiled program instead of retracing
    per distinct length. ``None`` means run all ``n_steps``.

    Returns ``(state, policy_state, bundle)`` — all device-resident. The
    bundle holds window scalars (``n_done``, ``n_sorts``, ``n_rebuilds``,
    ``overflow_pending``) plus per-step arrays (``active``, ``sorted``,
    ``reason`` — see core.resort_policy.REASON_NAMES — ``n_moved``,
    ``n_alive``, ``n_ranked``, and, when `with_energies`, ``field_energy`` /
    ``kinetic_energy``); fetch it with a single `jax.device_get`.

    If a global sort cannot absorb an overflowing bin (capacity too small),
    the remaining steps of the window become no-ops and
    ``bundle["overflow_pending"]`` is set: the host must grow the capacity
    and re-enter for the ``n_steps - n_done`` remaining steps. More
    generally ``bundle["halt_code"]`` carries the structured halt protocol
    (core.health.HALT_NAMES) with the halting step and — under the health
    sentinel (``health=HealthConfig(enable=True, ...)``) — the offending
    invariant and its measured/reference values.

    ``fault_vec`` (chaos harness, tests only) arms the in-graph fault
    injection of ``distributed.fault``; ``None`` compiles the injection out
    entirely.

    With ``donate=True`` (default) the input state and policy-state buffers
    are donated to the window — particle and field arrays update in place.
    Keep a copy (or pass ``donate=False``) if you need the pre-window state
    afterwards.
    """
    if n_target is None:
        n_target = n_steps
    with_fault = fault_vec is not None
    if fault_vec is None:
        fault_vec = no_fault_vec()
    fn = _pic_run_window_donated if donate else _pic_run_window_jit
    return fn(
        state, policy_state, jnp.asarray(n_target, jnp.int32), fault_vec,
        config, policy or SortPolicyConfig(), n_steps, with_energies,
        health, with_fault,
    )


def run_window_diff(
    state: PICState,
    policy_state: SortPolicyState,
    config: PICConfig,
    n_steps: int,
    *,
    policy: SortPolicyConfig | None = None,
    with_energies: bool = False,
    n_target: int | jax.Array | None = None,
    remat: str = "step",
    remat_chunk: int = 0,
):
    """The differentiable window: `pic_run_window` with reverse-mode
    rematerialization and none of the forward-only conveniences that block
    `jax.grad` (docs/autodiff.md).

    Identical physics program — the forward pass is bit-identical to
    ``pic_run_window(..., donate=False)`` under the same remat policy, and
    ``remat="none"`` IS the production program. The differences are purely
    AD plumbing:

    * buffers are never donated (grad re-reads the primal inputs),
    * the health sentinel and chaos-harness injection are compiled out,
    * ``remat`` picks the `jax.checkpoint` granularity: ``"step"`` (default)
      rematerializes every step so reverse-mode peak memory scales with the
      window state instead of ``n_steps`` stacked step residuals;
      ``"chunk"`` checkpoints ``remat_chunk``-step sub-windows (less
      recompute, more memory); ``"none"`` stores every residual.

    Requires ``config.backend="xla"`` — the Pallas kernel backends define no
    VJP, and "auto" could resolve to one. `grad.fit.make_objective` builds
    the config accordingly; direct callers get a loud error instead of an
    opaque Pallas differentiation failure.

    Returns ``(state, policy_state, bundle)`` exactly like `pic_run_window`;
    every float leaf is differentiable w.r.t. the float leaves of ``state``.
    """
    if config.backend != "xla":
        raise ValueError(
            f"run_window_diff needs config.backend='xla' (got "
            f"{config.backend!r}): the Pallas kernel backends have no VJP"
        )
    if n_target is None:
        n_target = n_steps
    return _pic_run_window_jit(
        state, policy_state, jnp.asarray(n_target, jnp.int32), no_fault_vec(),
        config, policy or SortPolicyConfig(), n_steps, with_energies,
        None, False, remat, remat_chunk,
    )


# ---------------------------------------------------------------------------
# Vmapped ensemble window: N independent simulations of ONE shape bucket run
# their windows as a single compiled program (leading member axis on every
# PICState/SortPolicyState leaf). See pic.ensemble for the stacked-state
# container and the host driver.
# ---------------------------------------------------------------------------

# Trace-time counter for the ensemble window, mirroring _window_trace_count:
# the one-compile-per-bucket tests read the delta.
_ensemble_trace_count = 0


def _ensemble_window_impl(state, pstate, n_target, fault_vec, config: PICConfig,
                          policy: SortPolicyConfig, n_steps: int, with_energies: bool,
                          health: HealthConfig | None, with_fault: bool):
    """`_pic_run_window_impl` lifted over a leading member axis on every
    array argument: stacked PICState + SortPolicyState, per-member traced
    targets ``n_target`` (i32[B]) and fault vectors (i32[B, 3]).

    Each member's window is the EXACT single-sim program — same masked
    post-halt steps, same in-graph sort decisions, same halt latching — so
    one member halting (overflow, health) simply masks that member's
    remaining steps while its siblings keep running. The host inspects the
    per-member ``halt_code`` vector and re-enters with per-member targets.
    """
    global _ensemble_trace_count
    _ensemble_trace_count += 1
    member = partial(
        _pic_run_window_impl, config=config, policy=policy, n_steps=n_steps,
        with_energies=with_energies, health=health, with_fault=with_fault,
    )
    return jax.vmap(member)(state, pstate, n_target, fault_vec)


# The ensemble window is forward-only (no remat statics — reverse-mode goes
# through run_window_diff on the single-sim impl).
_ENSEMBLE_STATICS = ("config", "policy", "n_steps", "with_energies", "health", "with_fault")
_ensemble_window_jit = partial(jax.jit, static_argnames=_ENSEMBLE_STATICS)(_ensemble_window_impl)
_ensemble_window_donated = partial(
    jax.jit, static_argnames=_ENSEMBLE_STATICS, donate_argnums=(0, 1)
)(_ensemble_window_impl)


def ensemble_run_window(
    state,
    policy_state,
    config: PICConfig,
    n_steps: int,
    *,
    policy: SortPolicyConfig | None = None,
    with_energies: bool = True,
    donate: bool = True,
    n_target=None,
    health: HealthConfig | None = None,
    fault_vec: jax.Array | None = None,
):
    """Run one window for every member of a stacked ensemble state as ONE
    compiled program (`jax.vmap` of the single-sim window scan).

    ``state``/``policy_state`` carry a leading member axis on every leaf
    (build them with `pic.ensemble.stack_states`). ``n_target`` is a traced
    i32[B] of per-member live-step counts ``<= n_steps`` (None runs all
    members the full window); members whose target is 0 pass through
    untouched, so a re-entry after one member's capacity growth advances
    only the members that still owe steps. ``fault_vec`` is i32[B, 3]
    (chaos harness; None compiles injection out).

    Returns ``(state, policy_state, bundle)`` with the member axis on every
    bundle leaf — ``bundle["halt_code"]`` is i32[B], ``per_step`` arrays
    are ``(B, n_steps)``. The config's ``dispatch_batch`` should equal the
    member count so the traced contractions hit the batched autotune keys
    the ensemble driver prewarms.
    """
    n_members = int(jax.tree.leaves(state)[0].shape[0])
    if n_target is None:
        n_target = jnp.full((n_members,), n_steps, jnp.int32)
    with_fault = fault_vec is not None
    if fault_vec is None:
        fault_vec = jnp.broadcast_to(no_fault_vec(), (n_members, 3))
    fn = _ensemble_window_donated if donate else _ensemble_window_jit
    return fn(
        state, policy_state, jnp.asarray(n_target, jnp.int32), fault_vec,
        config, policy or SortPolicyConfig(), n_steps, with_energies,
        health, with_fault,
    )


# Sentinel distinguishing "caller said nothing" (-> spec default) from an
# explicit window=None (-> legacy host loop) in SimDriver.run signatures.
UNSET = object()

_DEPRECATION_MSG = (
    "{cls}(fields, particles, config) is deprecated: describe the run as a "
    "repro.api.SimSpec (scenario registry: repro.api.scenario) and build the "
    "driver with repro.api.make_simulation(spec). The legacy constructor "
    "delegates to the same spec-built internals and will keep working, but "
    "spec-built drivers additionally carry run defaults, provenance, and "
    "checkpoint rebuild metadata."
)


def resolve_run_args(spec, n_steps, diagnostics_every, window,
                     autosave_every=None, autosave_path=None):
    """Resolve SimDriver.run() arguments against the driver's spec
    (``None``/``UNSET`` -> spec defaults; spec-less legacy drivers keep the
    historical defaults). Shared by Simulation and DistSimulation. An
    ``autosave_every=N`` with no path derives ``checkpoints/<spec.name>``."""
    run = None if spec is None else spec.run
    if n_steps is None:
        if run is None:
            raise TypeError("run() needs n_steps (this driver has no spec defaults)")
        n_steps = run.steps
    if diagnostics_every is None:
        diagnostics_every = 0 if run is None else run.diagnostics_every
    if window is UNSET:
        window = None if run is None else (run.window or None)
    if autosave_every is None:
        autosave_every = 0 if run is None else run.autosave_every
    if autosave_path is None:
        autosave_path = "" if run is None else run.autosave_path
    if autosave_every and not autosave_path:
        autosave_path = os.path.join("checkpoints", getattr(spec, "name", None) or "sim")
    if autosave_every and window is None:
        raise ValueError("autosave_every requires the windowed driver (window=K)")
    return n_steps, diagnostics_every, window, autosave_every, autosave_path


class Simulation:
    """Host driver: jitted step + adaptive resort policy + diagnostics.

    ``run(n, window=K)`` uses the device-resident windowed driver (one
    compiled K-step scan + one fetched bundle per window); ``window=None``
    keeps the legacy per-step host loop.

    Construct via ``repro.api.make_simulation(spec)`` — the direct
    constructor is a deprecated shim that delegates to the same internals
    with ``spec=None`` (no run defaults, no checkpoint rebuild metadata).
    """

    def __init__(self, fields: FieldState, particles: ParticleState, config: PICConfig,
                 policy: SortPolicyConfig | None = None, *, _spec=None):
        if _spec is None:
            warnings.warn(
                _DEPRECATION_MSG.format(cls="Simulation"), DeprecationWarning, stacklevel=2
            )
        self.spec = _spec
        self._setup(fields, particles, config, policy)

    def _setup(self, fields: FieldState, particles: ParticleState, config: PICConfig,
               policy: SortPolicyConfig | None) -> None:
        """The spec-built construction path (shared by `make_simulation`
        and the deprecated direct constructor)."""
        self.config = config
        # private copies: the drivers donate state buffers to the step, which
        # would otherwise invalidate the caller's field arrays
        fields = jax.tree.map(lambda a: jnp.asarray(a).copy(), fields)
        state, overflow = init_state(fields, particles, config)
        if overflow:
            self.config = dataclasses.replace(config, capacity=choose_capacity(config.capacity * 2 // 3 * 2))
            state, overflow = init_state(fields, particles, self.config)
            assert overflow == 0, "initial binning overflow after capacity growth"
        self.state = state
        self._prewarm_dispatch()
        self.policy = ResortPolicy(policy)
        self.policy_state = policy_init()
        self.sorts = 0
        self.rebuilds = 0
        # sorter counters (docs/sim_loop.md, "Profiling a run"; add_counts)
        self.moved = 0
        self.particle_steps = 0
        self.ranked = 0
        self.sort_reasons: dict[str, int] = {}
        self.history: list[dict] = []
        self._host_step = 0  # host mirror of state.step (windowed path syncs nothing)
        # fault-tolerance plumbing (docs/robustness.md): halt/retry/restart
        # counters, the sentinel config, and the chaos-harness injector
        self.halts: dict[str, int] = {}
        self.retries = 0
        self.restarts = 0
        self.discarded_steps = 0
        self.growths = {"capacity": 0}
        self._remedy_level = 0
        spec = self.spec
        self._health = spec.health if (spec is not None and spec.health.enable) else None
        self.fault_injector = (
            PICFaultInjector(spec.fault) if (spec is not None and spec.fault is not None) else None
        )

    def run(self, n_steps: int | None = None, *, diagnostics_every: int | None = None,
            window: int | None = UNSET, autosave_every: int | None = None,
            autosave_path: str | None = None) -> None:
        """Advance `n_steps` (default: the spec's step count). ``window=K``
        uses the device-resident scan driver; ``window=None`` the legacy
        host loop; unset defaults to the spec window (legacy drivers: host
        loop). ``autosave_every=N`` checkpoints the run every N steps (and
        at entry/exit) so a hard crash restores and resumes automatically;
        the health sentinel and remediation ladder (spec ``health`` node)
        apply on the windowed path — see docs/robustness.md.

        The two drivers keep INDEPENDENT policy counters (host
        ``self.policy`` vs device ``self.policy_state``) — pick one driver
        per Simulation. Switching mid-run restarts the sort cadence (both
        policies behave as if freshly reset); physics is unaffected.
        """
        n_steps, diagnostics_every, window, autosave_every, autosave_path = resolve_run_args(
            self.spec, n_steps, diagnostics_every, window, autosave_every, autosave_path
        )
        if window is None:
            self._run_host(n_steps, diagnostics_every)
        else:
            self._run_windowed(n_steps, diagnostics_every, window,
                               autosave_every, autosave_path)

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

    # ------------------------------------------------------------------
    # Legacy host-driven loop: one jitted step per Python iteration, policy
    # evaluated on host (several device->host syncs per step).
    # ------------------------------------------------------------------
    def _run_host(self, n_steps: int, diagnostics_every: int) -> None:
        needs_bins = self.config.needs_bins
        for _ in range(n_steps):
            t0 = time.perf_counter()
            self.state, stats = pic_step_donated(self.state, self.config)
            self._host_step += 1
            if self.config.sort_mode == "incremental":
                # the per-step host sync: ONE transfer for all stat scalars
                stats = jax.device_get(stats)
                n_slots = self.config.grid.n_cells * self.config.capacity
                add_counts(self, moved=int(stats.n_moved), particle_steps=int(stats.n_alive),
                           ranked=int(stats.n_ranked))
                if needs_bins and stats.n_overflow > 0:
                    # mandatory rebuild (paper: overflow with low slots)
                    self.state, of = global_sort(self.state, self.config)
                    self.rebuilds += 1
                    add_counts(self, sort_reasons={REASON_NAMES[REASON_OVERFLOW]: 1})
                    if of:
                        self._grow_capacity()
                    self.policy.reset()
                else:
                    dtep = time.perf_counter() - t0
                    perf = float(stats.n_alive) / max(dtep, 1e-9)
                    self.policy.record_step(rebuilt=False, perf=perf)
                    do, reason = self.policy.should_sort(empty_ratio=int(stats.n_empty) / max(n_slots, 1))
                    if do:
                        self.state, of = global_sort(self.state, self.config)
                        self.sorts += 1
                        add_counts(self, sort_reasons={reason: 1})
                        if of:
                            self._grow_capacity()
                        self.policy.reset()
            elif self.config.sort_mode == "global":
                # per-step full sort including attribute permutation
                self.state, of = global_sort(self.state, self.config)
                if of:
                    self._grow_capacity()
            elif self.config.sort_mode == "rebuild" and int(stats.n_overflow) > 0:
                self._grow_capacity()
            # gate on the host mirror of state.step — fetching the device
            # counter would cost a blocking sync on every step, not just the
            # recorded ones
            if diagnostics_every and self._host_step % diagnostics_every == 0:
                self.history.append(self.diagnostics())

    # ------------------------------------------------------------------
    # Device-resident windowed loop: ONE host sync (the fetched bundle) per
    # K-step window; capacity growth is the only other host intervention.
    # ------------------------------------------------------------------
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
        program) and fetch its bundle — the single device->host sync."""
        tag = {"step": self._host_step, "k": k}
        with jax.profiler.TraceAnnotation("pic.window.launch", **tag):
            state, pstate, bundle = pic_run_window(
                self.state, self.policy_state, self.config, window,
                n_target=k,
                policy=self.policy.config,
                with_energies=bool(diagnostics_every),
                health=self._health,
                fault_vec=fault_vec,
            )
        self.state, self.policy_state = state, pstate
        with jax.profiler.TraceAnnotation("pic.window.fetch", **tag):
            return _fetch_bundle(bundle)

    def _consume_bundle(self, host: dict, diagnostics_every: int) -> int:
        """Commit a successful (or capacity-halted) window's accounting."""
        counts = consume_window_bundle(host, self._host_step, diagnostics_every, self.history)
        commit_window_counts(self, counts)
        return counts.n_done

    def _take_snapshot(self):
        """Deep-copy the window carry: the windowed call donates its input
        buffers, so rollback needs owned copies taken before entry."""
        return (
            jax.tree.map(jnp.copy, self.state),
            jax.tree.map(jnp.copy, self.policy_state),
        )

    def _restore_snapshot(self, snap) -> None:
        self.state, self.policy_state = snap

    def _handle_halt(self, code: int, host: dict) -> None:
        if code == HALT_BIN_OVERFLOW:
            self._grow_capacity()
        else:
            raise RuntimeError(
                f"single-device driver cannot handle halt code {code} ({HALT_NAMES[code]})"
            )

    def _remedy_sort(self) -> None:
        """Remediation-ladder rung 2: force a global sort (fresh bins +
        attribute permutation) and reset the device policy counters."""
        self.state, overflow = global_sort(self.state, self.config)
        if overflow:
            self._grow_capacity()
        self.policy_state = policy_init()

    def _demote_backend(self) -> bool:
        """Remediation-ladder rung 3: demote the kernel-dispatch backend to
        the next backend down the priority ladder (e.g. pallas_reduced ->
        pallas -> xla), generalizing the old hard-coded "drop Pallas"
        toggle. Returns False when already at the bottom (the ladder is
        exhausted). `dispatch.demote` answers from the memo/cache only —
        remediation never re-executes the kernels suspected of the halt —
        and gets the step's actual dtype so the key matches the run."""
        from repro.kernels import dispatch

        nxt = dispatch.demote(
            self.config.backend, order=self.config.order,
            grid_shape=self.config.grid.shape, capacity=self.config.capacity,
            dtype=str(self.state.particles.pos.dtype),
            batch=self.config.dispatch_batch,
        )
        if nxt is None:
            return False
        self.config = dataclasses.replace(self.config, backend=nxt)
        return True

    # Backward-compatible alias for the pre-dispatcher rung name.
    _drop_pallas = _demote_backend

    def _prewarm_dispatch(self) -> None:
        """Resolve the config's "auto" dispatch keys EAGERLY (benchmark +
        persist on first measurement) so the traced step hits the memoized
        winner: under an ambient trace `resolve` cannot benchmark and would
        fall back to priority order. Re-run after anything that changes the
        key — capacity growth, checkpoint restore."""
        if self.config.backend != "auto":
            return
        from repro.kernels import dispatch

        dispatch.prewarm(
            dispatch.ops_for_modes(self.config.deposition, self.config.gather),
            order=self.config.order, grid_shape=self.config.grid.shape,
            capacity=self.config.capacity,
            dtype=str(self.state.particles.pos.dtype),
            batch=self.config.dispatch_batch,
        )

    def _needed_capacity(self) -> int:
        """Occupancy of the densest cell in the CURRENT state — the halt
        stats tell the host a growth is needed; this tells it how much."""
        p = self.state.particles
        cells = cell_index(p.pos, self.config.grid.shape)
        counts = jnp.zeros(self.config.grid.n_cells, jnp.int32).at[cells].add(
            p.alive.astype(jnp.int32)
        )
        return int(counts.max())

    def _grow_capacity(self) -> None:
        """Grow the bin capacity ONCE to fit the densest cell (with the
        standard headroom, and at least doubling) and re-bin the CURRENT
        state in place. Sizing from the actual occupancy instead of blind
        doubling means a single kept step is never wasted re-halting when
        one doubling would not have sufficed.

        Preserves the evolved fields, particle attributes, and step counter —
        an older implementation re-ran `init_state`, which zeroed `state.step`
        and replaced the fields mid-run (regression: tests/test_sim_loop.py).
        """
        needed = self._needed_capacity()
        new_cap = max(choose_capacity(needed), self.config.capacity * 2)
        self.config = dataclasses.replace(self.config, capacity=new_cap)
        self.growths["capacity"] = self.growths.get("capacity", 0) + 1
        self.state, overflow = global_sort(self.state, self.config)
        assert overflow == 0, "binning overflow persists after sizing capacity to the densest cell"
        self._prewarm_dispatch()  # capacity is part of the dispatch key

    def diagnostics(self) -> dict:
        s = self.state
        field_e, kinetic_e = _energies(s, self.config)
        kinetic = float(kinetic_e)
        em = float(field_e)
        return {
            "step": int(s.step),
            "field_energy": em,
            "kinetic_energy": kinetic,
            "total_energy": em + kinetic,
            "n_alive": int(jnp.sum(s.particles.alive)),
        }

    def sort_reason_name(self, code: int) -> str:
        """Map a per-step `reason` code from the window bundle to the host
        policy's reason string."""
        return REASON_NAMES[code]
