"""Batched ensemble engine: N independent single-device simulations of one
shape bucket advanced by ONE compiled program per window.

The member axis is pure data parallelism — `ensemble_run_window`
(pic.simulation) vmaps the K-step scan window over a stacked `PICState` +
`SortPolicyState`, so every member runs the exact single-sim program
(in-graph sort policy, masked post-halt steps, per-member halt codes) and
the ensemble compiles ONCE per bucket instead of once per member.

Halt-and-grow stays a host concern, now per member: when any member's bins
overflow, its window halts (masked steps) while its siblings keep running
to their own targets. The host then grows the SHARED bin capacity (the
compiled shape is per bucket, not per member) and rebuilds per member:

* halted members get the same `global_sort` the single-sim growth path
  runs (attribute permutation + re-bin) — so a grown member stays
  step-for-step equivalent to its sequential run;
* healthy siblings get a permutation-FREE re-bin (`build_bins` on current
  cells): their particle order is untouched and the valid slots stay a
  prefix of each (now longer, zero-padded) bin, which keeps their
  subsequent XLA contractions bit-identical — one member's overflow must
  not perturb its siblings.

`EnsembleSimulation` is the host driver over this: per-member step/sort
counters and diagnostics histories, one fetched bundle per window,
batched-dispatch prewarming (`DispatchKey.batch` = member count) at
setup/growth/restore, and per-member checkpointing through
`api.facade.save_ensemble_member` (each member checkpoint is a standard
single-driver checkpoint, resumable standalone). See docs/ensemble.md.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    SortPolicyConfig,
    build_bins,
    cell_index,
    choose_capacity,
    policy_init,
)
from repro.core.health import HALT_BIN_OVERFLOW, HALT_NAMES, HALT_NONE
from repro.pic.simulation import (
    _ENSEMBLE_STATICS,
    PICConfig,
    PICState,
    _energies,
    _ensemble_window_impl,
    _fetch_bundle,
    _state_slab,
    consume_window_bundle,
    global_sort,
    init_state,
)

__all__ = [
    "EnsembleSimulation",
    "make_ensemble_window_fn",
    "member_bundle",
    "stack_trees",
    "unstack_tree",
]


def stack_trees(*trees):
    """Stack identically-shaped pytrees along a new leading member axis."""
    return jax.tree.map(lambda *leaves: jnp.stack(leaves), *trees)


def unstack_tree(tree, n: int | None = None):
    """Split a stacked pytree back into its per-member trees."""
    if n is None:
        n = int(jax.tree.leaves(tree)[0].shape[0])
    return [jax.tree.map(lambda a: a[i], tree) for i in range(n)]


def member_bundle(host: dict, i: int) -> dict:
    """Member ``i``'s view of a fetched ensemble window bundle, in the
    single-sim bundle schema (scalars + (n_steps,) per-step arrays) so the
    shared `consume_window_bundle` accounting applies unchanged."""
    out = {k: v[i] for k, v in host.items() if k != "per_step"}
    out["per_step"] = {k: v[i] for k, v in host["per_step"].items()}
    return out


def make_ensemble_window_fn(*, donate: bool = True):
    """A FRESH jitted ensemble-window callable with its own executable
    cache — the unit the serving layer caches and evicts per spec
    signature (launch.sim_serve.ExecutableCache). Dropping the returned
    function releases its compiled executables; the module-level default
    (`EnsembleSimulation(window_fn=None)`) is shared and never evicted."""
    return partial(
        jax.jit,
        static_argnames=_ENSEMBLE_STATICS,
        donate_argnums=(0, 1) if donate else (),
    )(_ensemble_window_impl)


_ensemble_window_default = make_ensemble_window_fn()


class EnsembleSimulation:
    """Host driver for one shape bucket of N member simulations.

    ``members`` is a sequence of ``(fields, particles)`` initial
    conditions; every member shares ``config`` (grid, order, dt, backend,
    capacity — the compiled shape) and the sort ``policy``. Per-member
    physics differences live entirely in the initial conditions; members
    needing different compiled shapes belong in different buckets
    (`api.facade.make_ensemble` groups by spec signature).

    The run loop is windowed-only (there is no per-member host loop to
    batch): per window, every member advances ``min(window, remaining_i)``
    live steps in one compiled call, the host fetches one bundle, and
    members that halted on bin overflow trigger a shared capacity growth
    before re-entry. Non-overflow halt codes raise (the ensemble path runs
    without the fault-supervisor ladder; run health-sentinel workloads on
    the single-sim driver).
    """

    def __init__(self, members, config: PICConfig, policy: SortPolicyConfig | None = None,
                 *, specs=None, window_fn=None):
        members = list(members)
        if not members:
            raise ValueError("an ensemble needs at least one member")
        self.n_members = len(members)
        self.specs = list(specs) if specs is not None else [None] * self.n_members
        if len(self.specs) != self.n_members:
            raise ValueError(
                f"{len(self.specs)} specs for {self.n_members} members"
            )
        self.spec = next((s for s in self.specs if s is not None), None)
        self.policy_config = policy or SortPolicyConfig()
        self._window_fn = window_fn or _ensemble_window_default
        self.config = dataclasses.replace(config, dispatch_batch=self.n_members)

        # private copies (the window donates its input buffers)
        members = [
            (jax.tree.map(lambda a: jnp.asarray(a).copy(), f), p) for f, p in members
        ]
        states = self._init_members(members)
        self.state = stack_trees(*states)
        self.policy_state = stack_trees(*[policy_init() for _ in states])
        self._prewarm_dispatch()

        self.host_step = np.zeros(self.n_members, np.int64)
        self.sorts = np.zeros(self.n_members, np.int64)
        self.rebuilds = np.zeros(self.n_members, np.int64)
        self.histories: list[list[dict]] = [[] for _ in range(self.n_members)]
        self.growths = {"capacity": 0}
        self.halts: dict[str, int] = {}

    # -- construction -------------------------------------------------------

    def _init_members(self, members) -> list[PICState]:
        """Per-member `init_state` at the SHARED capacity, growing it up
        front (densest cell across all members, at least doubling) when any
        member's initial binning overflows."""
        states = []
        for fields, particles in members:
            state, overflow = init_state(fields, particles, self.config)
            if overflow:
                needed = max(
                    self._max_cell_count(p.pos, p.alive) for _, p in members
                )
                new_cap = max(choose_capacity(needed), self.config.capacity * 2)
                self.config = dataclasses.replace(self.config, capacity=new_cap)
                return self._init_members(members)
            states.append(state)
        return states

    def _max_cell_count(self, pos, alive) -> int:
        cells = cell_index(pos, self.config.grid.shape)
        counts = jnp.zeros(self.config.grid.n_cells, jnp.int32).at[cells].add(
            alive.astype(jnp.int32)
        )
        return int(counts.max())

    def _prewarm_dispatch(self) -> None:
        """Resolve the config's "auto" keys eagerly AT THE BATCHED SHAPE
        (`batch` = member count) so the vmapped window's traced resolves hit
        the measured batched winner, never a batch=1 entry — re-run after
        capacity growth and member restore, like the single-sim driver."""
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

    # -- the windowed run loop ---------------------------------------------

    def run(self, n_steps: int | None = None, *, diagnostics_every: int | None = None,
            window: int | None = None, on_window=None, _fault_vec=None) -> None:
        """Advance the members by ``n_steps`` — an int (all members), a
        per-member sequence, or None (each member's own spec default, so
        batched jobs with different step counts coexist in one bucket).
        ``on_window(self, host_bundle)`` is the serving layer's streaming
        hook, called once per fetched window bundle (after the accounting
        commits, before any growth). ``_fault_vec`` (i32[B, 3], chaos
        tests) arms per-member in-graph fault injection."""
        run = None if self.spec is None else self.spec.run
        if n_steps is None:
            if any(s is None for s in self.specs):
                raise TypeError("run() needs n_steps (not every member has a spec)")
            per_steps = np.array([s.run.steps for s in self.specs], np.int64)
        elif np.ndim(n_steps) == 0:
            per_steps = np.full(self.n_members, int(n_steps), np.int64)
        else:
            per_steps = np.asarray(n_steps, np.int64)
            if per_steps.shape != (self.n_members,):
                raise ValueError(
                    f"n_steps sequence has shape {per_steps.shape}; expected "
                    f"({self.n_members},)"
                )
        if diagnostics_every is None:
            if all(s is not None for s in self.specs):
                diagnostics_every = max(s.run.diagnostics_every for s in self.specs)
            else:
                diagnostics_every = 0 if run is None else run.diagnostics_every
        if window is None:
            window = 16 if run is None else (run.window or 16)
        if window <= 0:
            raise ValueError(f"window must be positive, got {window}")

        target = self.host_step + per_steps
        while True:
            k = np.clip(target - self.host_step, 0, window).astype(np.int64)
            if not k.any():
                break
            host = self._enter_window(k, window, diagnostics_every, _fault_vec)
            self._consume_bundle(host, diagnostics_every)
            if on_window is not None:
                on_window(self, host)
            codes = np.asarray(host["halt_code"])
            bad = [
                (i, int(c)) for i, c in enumerate(codes)
                if c not in (HALT_NONE, HALT_BIN_OVERFLOW)
            ]
            if bad:
                i, c = bad[0]
                raise RuntimeError(
                    f"ensemble member {i} halted with code {c} ({HALT_NAMES[c]}); "
                    "the ensemble driver only recovers bin-overflow halts"
                )
            overflowed = [i for i, c in enumerate(codes) if c == HALT_BIN_OVERFLOW]
            if overflowed:
                self.halts["bin_overflow"] = self.halts.get("bin_overflow", 0) + len(overflowed)
                self._grow_capacity(overflowed)

    def _enter_window(self, k, window: int, diagnostics_every: int, fault_vec) -> dict:
        """ONE compiled vmapped window + its single device->host fetch."""
        with_fault = fault_vec is not None
        if fault_vec is None:
            from repro.distributed.fault import no_fault_vec

            fault_vec = jnp.broadcast_to(no_fault_vec(), (self.n_members, 3))
        state, pstate, bundle = self._window_fn(
            self.state, self.policy_state,
            jnp.asarray(k, jnp.int32), jnp.asarray(fault_vec, jnp.int32),
            config=self.config, policy=self.policy_config, n_steps=int(window),
            with_energies=bool(diagnostics_every), health=None,
            with_fault=with_fault,
        )
        self.state, self.policy_state = state, pstate
        return _fetch_bundle(bundle)

    def _consume_bundle(self, host: dict, diagnostics_every: int) -> None:
        for i in range(self.n_members):
            counts = consume_window_bundle(
                member_bundle(host, i), int(self.host_step[i]),
                diagnostics_every, self.histories[i],
            )
            self.host_step[i] += counts.n_done
            self.sorts[i] += counts.sorts
            self.rebuilds[i] += counts.rebuilds

    # -- halt-and-grow ------------------------------------------------------

    def _grow_capacity(self, overflowed) -> None:
        """Grow the SHARED bin capacity to fit the densest cell of any
        member (with headroom, at least doubling) and rebuild every member
        at the new shape: `global_sort` for the overflowed members (the
        single-sim growth path — keeps them sequentially equivalent), a
        permutation-free re-bin for their siblings (keeps them bit-exact)."""
        overflowed = set(overflowed)
        states = unstack_tree(self.state, self.n_members)
        needed = max(
            self._max_cell_count(st.particles.pos, st.particles.alive) for st in states
        )
        new_cap = max(choose_capacity(needed), self.config.capacity * 2)
        self.config = dataclasses.replace(self.config, capacity=new_cap)
        self.growths["capacity"] += 1
        rebuilt = []
        for i, st in enumerate(states):
            if i in overflowed:
                st, overflow = global_sort(st, self.config)
            else:
                st, overflow = self._rebin(st)
            assert overflow == 0, (
                "binning overflow persists after sizing capacity to the densest cell"
            )
            rebuilt.append(st)
        self.state = stack_trees(*rebuilt)
        self._prewarm_dispatch()  # capacity (and so the batched key) changed

    def _rebin(self, state: PICState) -> tuple[PICState, int]:
        """Re-bin one member at the current (grown) capacity WITHOUT the
        attribute permutation: particle order is preserved, so each bin's
        occupied slots remain the same prefix (now with more zero padding)
        and the member's subsequent contractions stay bit-identical."""
        cells = cell_index(state.particles.pos, self.config.grid.shape)
        layout, overflow = build_bins(
            cells, state.particles.alive,
            n_cells=self.config.grid.n_cells, capacity=self.config.capacity,
        )
        state = dataclasses.replace(
            state, layout=layout,
            slab=_state_slab(state.particles, layout, self.config),
        )
        return state, int(overflow)

    # -- introspection ------------------------------------------------------

    def member_state(self, i: int) -> PICState:
        from repro.checkpoint.checkpoint import tree_member_slice

        return tree_member_slice(self.state, i)

    def diagnostics(self, i: int | None = None) -> dict | list[dict]:
        """The shared diagnostics schema, per member (or all members)."""
        if i is None:
            return [self.diagnostics(j) for j in range(self.n_members)]
        st = self.member_state(i)
        field_e, kinetic_e = _energies(st, self.config)
        em, kin = float(field_e), float(kinetic_e)
        return {
            "member": i,
            "step": int(st.step),
            "field_energy": em,
            "kinetic_energy": kin,
            "total_energy": em + kin,
            "n_alive": int(jnp.sum(st.particles.alive)),
        }

    # -- per-member checkpointing (api.facade implements the format) --------

    def save_member(self, i: int, path: str) -> None:
        from repro.api.facade import save_ensemble_member

        save_ensemble_member(self, i, path)

    def restore_member(self, i: int, path: str) -> None:
        from repro.api.facade import restore_ensemble_member

        restore_ensemble_member(self, i, path)
