"""One driver facade over single-device and distributed runs.

`make_simulation(spec)` is the single construction path of the public API:
it builds fields and particles from the declarative `SimSpec`, derives the
driver config, and returns either the windowed single-device driver
(`repro.pic.Simulation`, when ``spec.mesh.shape is None``) or the
domain-decomposed shard_map driver (`repro.pic.DistSimulation`, when a mesh
is named) — both satisfying the same `SimDriver` protocol:

    run(n_steps=None, *, diagnostics_every=None, window=...)   spec defaults
    diagnostics() -> dict                                      shared schema
    state                                                      device pytree
    save(path) / restore(path)                                 checkpointing

Checkpoints are a directory (atomic tmp+rename) holding the full device
pytree — fields, particles, bin layout, AND the in-graph `SortPolicyState`
— plus a JSON sidecar with the serialized spec, grown capacities, and host
counters, so `load_simulation(path)` rebuilds the driver and continues
bit-for-bit where the saved run stopped (tests/test_api.py,
tests/dist_sim_check.py 'checkpoint').
"""

from __future__ import annotations

import dataclasses
import json
import os
import shutil
from typing import Protocol, runtime_checkable

import jax
import numpy as np

from repro.api.spec import EnsembleSpec, SimSpec
from repro.checkpoint.checkpoint import (
    _flatten_with_names,
    array_checksums,
    clean_stale_tmp,
    tree_member_set,
    tree_member_slice,
    verify_checksums,
)
from repro.pic.grid import FieldState, GridSpec
from repro.pic.laser import inject_laser
from repro.pic.plasma import (
    ParticleState,
    apply_counter_drift,
    perturb_velocity,
    profiled_plasma,
    uniform_plasma,
)

__all__ = [
    "EnsembleRun",
    "SimCheckpointer",
    "SimDriver",
    "bucket_specs",
    "build_fields",
    "build_particles",
    "dist_config",
    "fit_simulation",
    "load_simulation",
    "make_ensemble",
    "make_objective",
    "make_simulation",
    "pic_config",
    "restore_ensemble_member",
    "restore_simulation",
    "save_ensemble_member",
    "save_simulation",
    "spec_signature",
]


@runtime_checkable
class SimDriver(Protocol):
    """What every driver returned by `make_simulation` provides. ``state``
    is the device-resident simulation pytree (structure is driver-specific:
    `PICState` for the single-device driver, a dict of shard-local arrays
    for the distributed one) — `save`/`restore` checkpoint it together with
    the policy state and host counters."""

    spec: SimSpec | None
    sorts: int
    rebuilds: int
    history: list

    def run(self, n_steps: int | None = None, *, diagnostics_every: int | None = None,
            window=...) -> None: ...
    def diagnostics(self) -> dict: ...
    @property
    def state(self): ...
    def save(self, path: str) -> None: ...
    def restore(self, path: str) -> None: ...


# ---------------------------------------------------------------------------
# Spec -> initial conditions
# ---------------------------------------------------------------------------


def build_particles(spec: SimSpec) -> ParticleState:
    """PlasmaSpec -> ParticleState: lattice base (uniform or profiled),
    then counter-streaming drift, then the velocity seed."""
    import jax.numpy as jnp

    p = spec.plasma
    key = jax.random.PRNGKey(p.seed)
    if p.profile is not None:
        z_on = p.profile.z_on
        density = p.density
        parts = profiled_plasma(
            key, spec.grid, ppc_each_dim=p.ppc_each_dim,
            density_fn=lambda z: jnp.where(z > z_on, density, 0.0),
            u_thermal=p.u_thermal, jitter=p.jitter,
        )
    else:
        parts = uniform_plasma(
            key, spec.grid, ppc_each_dim=p.ppc_each_dim, density=p.density,
            u_thermal=p.u_thermal, jitter=p.jitter,
        )
    if p.drift is not None:
        parts = apply_counter_drift(parts, u_drift=p.drift.u, axis=p.drift.axis)
    if p.perturb is not None:
        pe = p.perturb
        parts = perturb_velocity(
            parts, axis=pe.v_axis, amplitude=pe.amplitude, mode=pe.mode,
            grid=spec.grid, k_axis=None if pe.k_axis < 0 else pe.k_axis,
        )
    return parts


def build_fields(spec: SimSpec) -> FieldState:
    """Zero fields, plus the laser pulse when the spec names one."""
    fields = FieldState.zeros(spec.grid.shape)
    if spec.laser is not None:
        fields = inject_laser(fields, spec.grid, spec.laser)
    return fields


# ---------------------------------------------------------------------------
# Spec -> driver configs
# ---------------------------------------------------------------------------


def pic_config(spec: SimSpec):
    """Derive the single-device `PICConfig` from a spec."""
    from repro.pic.simulation import PICConfig

    d = spec.deposition
    return PICConfig(
        grid=spec.grid,
        dt=spec.dt,
        order=d.order,
        deposition=d.mode,
        gather=d.resolved_gather,
        sort_mode=spec.sort.mode,
        charge=spec.charge,
        mass=spec.mass,
        ckc_beta=spec.ckc_beta,
        capacity=spec.sort.resolved_capacity(spec.plasma.ppc),
        backend=d.backend,
    )


def dist_config(spec: SimSpec):
    """Derive the distributed `DistConfig` (per-shard local grid) from a
    spec with a mesh. SimSpec.__post_init__ already validated divisibility
    and the bin-based deposition/sort requirements."""
    from repro.pic.distributed import DistConfig

    if spec.mesh.shape is None:
        raise ValueError("dist_config needs a spec with mesh.shape set")
    sx, sy = spec.mesh.shape
    local = GridSpec(
        shape=(spec.grid.shape[0] // sx, spec.grid.shape[1] // sy, spec.grid.shape[2]),
        dx=spec.grid.dx,
    )
    return DistConfig(
        local_grid=local,
        dt=spec.dt,
        order=spec.deposition.order,
        deposition=spec.deposition.mode,
        gather=spec.deposition.resolved_gather,
        backend=spec.deposition.backend,
        charge=spec.charge,
        mass=spec.mass,
        capacity=spec.sort.resolved_capacity(spec.plasma.ppc),
        mig_cap=spec.mesh.mig_cap,
        comm=spec.comm,
    )


# ---------------------------------------------------------------------------
# The facade
# ---------------------------------------------------------------------------


def make_simulation(spec: SimSpec, *, fields: FieldState | None = None,
                    particles: ParticleState | None = None) -> "SimDriver":
    """Build the driver a spec describes: `Simulation` for
    ``MeshSpec(None)``, `DistSimulation` for ``MeshSpec("SXxSY")``.

    ``fields``/``particles`` override the spec-built initial conditions
    (e.g. benchmark states prepared elsewhere); the spec still provides the
    config, policy, and run defaults.
    """
    from repro.pic.dist_simulation import DistSimulation
    from repro.pic.simulation import Simulation

    fields = build_fields(spec) if fields is None else fields
    particles = build_particles(spec) if particles is None else particles
    policy = spec.sort.policy

    if spec.mesh.shape is None:
        return Simulation(fields, particles, pic_config(spec), policy=policy, _spec=spec)

    needed = spec.mesh.n_devices
    if jax.device_count() < needed:
        raise RuntimeError(
            f"spec mesh {spec.mesh.shape} needs {needed} devices but jax sees "
            f"{jax.device_count()}. Force emulated host devices BEFORE importing jax "
            "(repro.launch.devices.force_host_devices, or the --mesh/--spec peek in "
            "repro.launch.pic_run)."
        )
    return DistSimulation(
        fields, particles, dist_config(spec),
        mesh_shape=spec.mesh.shape,
        n_local=spec.mesh.n_local or None,
        policy=policy,
        _spec=spec,
    )


# ---------------------------------------------------------------------------
# The gradient subsystem (repro.grad, docs/autodiff.md): same facade, so a
# spec in hand is one call away from a differentiable objective or a fit.
# ---------------------------------------------------------------------------


def make_objective(spec: SimSpec, grad=None, **kw):
    """Differentiable problem from a spec: ``(loss_fn, params0)`` with
    ``loss_fn(params) -> (loss, aux)`` jit/grad-able through the whole
    windowed run — see repro.grad.fit.make_objective (``grad`` is a
    `GradSpec`; keywords like ``objective=``, ``learn=``, ``steps=``
    override it)."""
    from repro.grad.fit import make_objective as _make_objective

    return _make_objective(spec, grad, **kw)


def fit_simulation(spec: SimSpec, grad=None, **kw):
    """AdamW-optimize the learned SimSpec leaves against a registered
    objective — see repro.grad.fit.fit_simulation. Returns a `FitResult`
    (final params, per-iteration trajectory, compile count)."""
    from repro.grad.fit import fit_simulation as _fit_simulation

    return _fit_simulation(spec, grad, **kw)


# ---------------------------------------------------------------------------
# Ensembles: spec signatures, shape bucketing, the batched facade
# ---------------------------------------------------------------------------


def spec_signature(spec: SimSpec) -> str:
    """Canonical compile-shape signature of a single-device spec: two specs
    with the same signature run the SAME compiled window program (identical
    `PICConfig`, sort policy, window length, and particle count) and may
    share one vmapped executable — this is the ensemble bucketing key AND
    the serving layer's compiled-executable cache key.

    Physics that lives in the initial conditions (seed, density, thermal
    spread, drift/perturb/laser/profile parameters) deliberately does NOT
    enter the signature: it changes array VALUES, not the program.
    """
    import hashlib

    if spec.mesh.shape is not None:
        raise ValueError(
            f"spec {spec.name!r} names a device mesh {spec.mesh.shape}; "
            "signatures (and the ensemble engine) cover single-device specs"
        )
    cfg = pic_config(spec)
    payload = {
        "grid": list(cfg.grid.shape),
        "dx": list(cfg.grid.dx),
        "dt": cfg.dt,
        "order": cfg.order,
        "deposition": cfg.deposition,
        "gather": cfg.gather,
        "sort_mode": cfg.sort_mode,
        "charge": cfg.charge,
        "mass": cfg.mass,
        "ckc_beta": cfg.ckc_beta,
        "capacity": cfg.capacity,
        "backend": cfg.backend,
        "policy": dataclasses.asdict(spec.sort.policy),
        "window": spec.run.window,
        "n_particles": spec.grid.n_cells * spec.plasma.ppc,
    }
    blob = json.dumps(payload, sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()[:16]


def bucket_specs(specs) -> dict:
    """Group spec indices by signature (insertion-ordered):
    ``{signature: [member indices]}``. Each bucket is one compiled
    executable's worth of compatible members."""
    buckets: dict[str, list[int]] = {}
    for i, spec in enumerate(specs):
        buckets.setdefault(spec_signature(spec), []).append(i)
    return buckets


class EnsembleRun:
    """The member-indexed facade over one or more shape buckets.

    `make_ensemble` builds one `EnsembleSimulation` per signature bucket;
    this object keeps the member's-eye view: member ``i`` of the
    `EnsembleSpec` maps to ``(bucket, slot)`` and every accessor
    (`diagnostics`, `history`, `save_member`, ...) takes the GLOBAL member
    index. ``run`` advances the buckets one after another — each bucket is
    a single vmapped executable; buckets are independent programs.
    """

    def __init__(self, spec: EnsembleSpec, members: list[SimSpec],
                 sims: list, slots: list[tuple[int, int]]):
        self.spec = spec
        self.members = members
        self.sims = sims
        self._slots = slots

    @property
    def n_members(self) -> int:
        return len(self.members)

    @property
    def signatures(self) -> list[str]:
        return [spec_signature(m) for m in self.members]

    def slot(self, i: int) -> tuple[int, int]:
        """Global member index -> (bucket index, slot within the bucket)."""
        return self._slots[i]

    def run(self, n_steps: int | None = None, *, diagnostics_every: int | None = None,
            window: int | None = None, on_window=None) -> None:
        for sim in self.sims:
            sim.run(n_steps, diagnostics_every=diagnostics_every, window=window,
                    on_window=on_window)

    def diagnostics(self, i: int | None = None):
        if i is None:
            return [self.diagnostics(j) for j in range(self.n_members)]
        b, s = self._slots[i]
        d = self.sims[b].diagnostics(s)
        return dict(d, member=i)

    def history(self, i: int) -> list[dict]:
        b, s = self._slots[i]
        return self.sims[b].histories[s]

    def member_state(self, i: int):
        b, s = self._slots[i]
        return self.sims[b].member_state(s)

    def save_member(self, i: int, path: str) -> None:
        b, s = self._slots[i]
        save_ensemble_member(self.sims[b], s, path)

    def restore_member(self, i: int, path: str) -> None:
        b, s = self._slots[i]
        restore_ensemble_member(self.sims[b], s, path)


def make_ensemble(spec: EnsembleSpec, *, window_fn_for=None) -> EnsembleRun:
    """Build the batched driver(s) an `EnsembleSpec` describes: members are
    bucketed by `spec_signature` and each bucket becomes ONE
    `pic.ensemble.EnsembleSimulation` (one compiled window executable for
    all its members).

    ``window_fn_for`` (optional): ``signature -> window_fn`` supplying each
    bucket's jitted window callable — the serving layer passes its
    signature-keyed `ExecutableCache` lookup here so executables are shared
    and evicted across jobs; ``None`` uses the shared module-level jit.
    """
    from repro.pic.ensemble import EnsembleSimulation

    members = spec.members()
    buckets = bucket_specs(members)
    slots: list[tuple[int, int] | None] = [None] * len(members)
    sims = []
    for b, (sig, idxs) in enumerate(buckets.items()):
        bucket_specs_ = [members[i] for i in idxs]
        pairs = [(build_fields(m), build_particles(m)) for m in bucket_specs_]
        sims.append(EnsembleSimulation(
            pairs, pic_config(bucket_specs_[0]),
            policy=bucket_specs_[0].sort.policy,
            specs=bucket_specs_,
            window_fn=None if window_fn_for is None else window_fn_for(sig),
        ))
        for s, i in enumerate(idxs):
            slots[i] = (b, s)
    return EnsembleRun(spec, members, sims, slots)


# ---------------------------------------------------------------------------
# Checkpointing (save / restore / load)
# ---------------------------------------------------------------------------

_ARRAYS = "arrays.npz"
_META = "checkpoint.json"


def _write_dir(path: str, tree, meta: dict) -> None:
    """Atomic checkpoint directory write (tmp + rename, like
    repro.checkpoint.CheckpointManager)."""
    names, leaves, _ = _flatten_with_names(tree)
    host = [np.asarray(x) for x in leaves]
    tmp = path + f".tmp-{os.getpid()}"
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp)
    np.savez(os.path.join(tmp, _ARRAYS), **{f"a{i}": a for i, a in enumerate(host)})
    with open(os.path.join(tmp, _META), "w") as f:
        json.dump(dict(meta, names=names, checksums=array_checksums(host)), f, indent=1)
    # overwrite without a window where NO checkpoint exists: move the old
    # one aside, rename the new one in, only then delete the old — a crash
    # in between leaves either the old or the new checkpoint intact
    old = path + f".old-{os.getpid()}"
    if os.path.exists(old):
        shutil.rmtree(old)
    had_old = os.path.exists(path)
    if had_old:
        os.rename(path, old)
    os.rename(tmp, path)
    if had_old:
        shutil.rmtree(old)


def _read_meta(path: str) -> dict:
    with open(os.path.join(path, _META)) as f:
        return json.load(f)


def _read_dir(path: str) -> tuple[dict, dict]:
    """-> (name -> numpy array, meta dict). Integrity is checked here: a
    truncated npz, an unreadable sidecar, or a checksum mismatch all fail
    LOUDLY instead of installing silently corrupt state."""
    try:
        meta = _read_meta(path)
        with np.load(os.path.join(path, _ARRAYS)) as data:
            host = [np.asarray(data[f"a{i}"]) for i in range(len(meta["names"]))]
    except Exception as exc:
        raise ValueError(f"corrupt or truncated checkpoint at {path}: {exc}") from exc
    if "checksums" in meta:  # absent only in pre-robustness checkpoints
        verify_checksums(host, meta["checksums"], meta["names"], path)
    arrays = dict(zip(meta["names"], host))
    return arrays, meta


def _restore_tree(template, arrays: dict):
    """Rebuild `template`'s structure with the checkpointed leaves (matched
    by flattened name; shapes may differ from the template, e.g. after
    capacity growth — the saved shapes win)."""
    names, _, treedef = _flatten_with_names(template)
    missing = [n for n in names if n not in arrays]
    if missing:
        raise ValueError(f"checkpoint is missing leaves {missing[:4]}... ({len(missing)} total)")
    import jax.numpy as jnp

    return treedef.unflatten([jnp.asarray(arrays[n]) for n in names])


def _host_policy_scalars(sim) -> dict:
    st = sim.policy.state
    return {
        "steps_since_sort": st.steps_since_sort,
        "rebuilds_since_sort": st.rebuilds_since_sort,
        "baseline_perf": st.baseline_perf,
        "perf_ema": st.perf_ema,
    }


def _restore_host_policy(sim, scal: dict) -> None:
    st = sim.policy.state
    st.steps_since_sort = scal["steps_since_sort"]
    st.rebuilds_since_sort = scal["rebuilds_since_sort"]
    st.baseline_perf = scal["baseline_perf"]
    st.perf_ema = scal["perf_ema"]


def save_simulation(sim, path: str) -> None:
    """Checkpoint a driver (single-device or distributed) to `path`."""
    from repro.pic.dist_simulation import DistSimulation

    distributed = isinstance(sim, DistSimulation)
    scalars = {
        "sorts": sim.sorts,
        "rebuilds": sim.rebuilds,
        "moved": sim.moved,
        "particle_steps": sim.particle_steps,
        "ranked": sim.ranked,
        "sort_reasons": dict(sim.sort_reasons),
        "host_step": sim._host_step,
        "capacity": sim.config.capacity,
        "host_policy": _host_policy_scalars(sim),
        "history": sim.history,
        # fault-tolerance counters (docs/robustness.md). A crash-recovery
        # restore would clobber sim.restarts with the pre-crash value, so
        # the supervisor re-asserts its live count after restoring.
        "growths": dict(sim.growths),
        "halts": dict(sim.halts),
        "retries": sim.retries,
        "restarts": sim.restarts,
        "discarded_steps": sim.discarded_steps,
    }
    if distributed:
        scalars.update(
            mig_cap=sim.config.mig_cap,
            n_local=sim.n_local,
            # the LIVE decomposition: a load-aware rebalance may have
            # re-split the mesh mid-run (sim.spec is kept in sync)
            mesh_shape=[sim.sx, sim.sy],
            mig_recv_dropped=sim.mig_recv_dropped,
            pending_presort=bool(sim._pending_presort),
            pending_resume=bool(sim._pending_resume),
            comm_stats=dict(sim.comm_stats),
            rebalance_armed=bool(sim._rebalance_armed),
        )
    tree = {"state": sim.state, "policy_state": sim.policy_state}
    meta = {
        "driver": "dist" if distributed else "single",
        "spec": None if sim.spec is None else sim.spec.to_dict(),
        "scalars": scalars,
    }
    _write_dir(path, tree, meta)


def restore_simulation(sim, path: str) -> None:
    """Restore a checkpoint into an existing, compatible driver (same spec
    shape: particle counts and mesh must match; capacity/mig_cap/n_local are
    taken from the checkpoint)."""
    from repro.pic.dist_simulation import DistSimulation

    arrays, meta = _read_dir(path)
    scal = meta["scalars"]
    distributed = isinstance(sim, DistSimulation)
    if distributed != (meta["driver"] == "dist"):
        raise ValueError(f"checkpoint was written by the {meta['driver']!r} driver")
    # structural guards: installing arrays of the wrong global shape would
    # otherwise surface much later as an opaque jit shape/sharding error
    if distributed and list(scal["mesh_shape"]) != [sim.sx, sim.sy]:
        raise ValueError(
            f"checkpoint was written on a {scal['mesh_shape'][0]}x{scal['mesh_shape'][1]} "
            f"mesh but this driver runs {sim.sx}x{sim.sy}"
        )
    template_names, template_leaves, _ = _flatten_with_names(
        {"state": sim.state, "policy_state": sim.policy_state}
    )
    for name, leaf in zip(template_names, template_leaves):
        if name not in arrays:
            continue  # _restore_tree reports missing leaves with the full list
        saved, tmpl = arrays[name].shape, tuple(leaf.shape)
        # capacity and (distributed) n_local legitimately grow mid-run and
        # take their sizes from the checkpoint; every OTHER dimension is a
        # structural invariant of the driver (grid blocks, particle count,
        # n_cells, mesh layout) — install-then-crash-inside-jit is the
        # failure mode this guard preempts
        if "fields" in name:
            ok = saved == tmpl        # grid blocks: exact invariants
        elif distributed:
            if "slab" in name:        # (sx, sy, n_cells, capacity, ...)
                ok = saved[:3] == tmpl[:3] and saved[4:] == tmpl[4:]
            elif "slots" in name:     # (sx, sy, n_cells, capacity)
                ok = saved[:3] == tmpl[:3]
            else:                     # particle arrays: (sx, sy, n_local, ...)
                ok = saved[:2] == tmpl[:2] and saved[3:] == tmpl[3:]
        elif "slab" in name:          # (n_cells, capacity, ...)
            ok = saved[:1] == tmpl[:1] and saved[2:] == tmpl[2:]
        elif "slots" in name and "particle_slot" not in name:
            ok = saved[:1] == tmpl[:1]  # (n_cells, capacity)
        else:
            ok = saved == tmpl
        if not ok:
            raise ValueError(
                f"checkpoint leaf {name} has shape {saved} but this driver implies "
                f"{tmpl} — the checkpoint belongs to a different grid/mesh/plasma"
            )

    if distributed:
        sim.config = dataclasses.replace(
            sim.config, capacity=scal["capacity"], mig_cap=scal["mig_cap"]
        )
        sim.n_local = scal["n_local"]
        sim.mig_recv_dropped = scal["mig_recv_dropped"]
        sim._pending_presort = bool(scal.get("pending_presort", False))
        sim._pending_resume = bool(scal.get("pending_resume", False))
        sim.comm_stats = dict(scal.get("comm_stats", sim.comm_stats))
        sim._rebalance_armed = bool(scal.get("rebalance_armed", True))
        sim._fns.clear()
        # pre-robustness checkpoints carry no replay snapshot: substitute
        # zeros of the saved particle shapes (always valid — a checkpoint
        # boundary never has a pending resume)
        for name in list(arrays):
            for mid, src in (("mid_pos", "pos"), ("mid_u", "u")):
                cand = name.replace(src, mid)
                if name.endswith(f"'{src}']") and cand not in arrays:
                    arrays[cand] = np.zeros_like(arrays[name])
    else:
        sim.config = dataclasses.replace(sim.config, capacity=scal["capacity"])

    restored = _restore_tree({"state": sim.state, "policy_state": sim.policy_state}, arrays)
    sim.state = restored["state"]
    sim.policy_state = restored["policy_state"]
    sim.sorts = scal["sorts"]
    sim.rebuilds = scal["rebuilds"]
    # sorter counters (absent from checkpoints that predate them)
    sim.moved = int(scal.get("moved", 0))
    sim.particle_steps = int(scal.get("particle_steps", 0))
    sim.ranked = int(scal.get("ranked", 0))
    sim.sort_reasons = dict(scal.get("sort_reasons", {}))
    sim._host_step = scal["host_step"]
    sim.history = list(scal["history"])
    sim.growths = dict(scal.get("growths", sim.growths))
    sim.halts = dict(scal.get("halts", {}))
    sim.retries = int(scal.get("retries", 0))
    sim.restarts = int(scal.get("restarts", 0))
    sim.discarded_steps = int(scal.get("discarded_steps", 0))
    sim._remedy_level = 0
    _restore_host_policy(sim, scal["host_policy"])
    # the restored capacity may differ from the driver's — re-resolve the
    # "auto" dispatch keys eagerly before the next window traces
    sim._prewarm_dispatch()


def load_simulation(path: str) -> "SimDriver":
    """Rebuild the driver a checkpoint describes (requires the checkpoint
    to have been written by a spec-built driver) and restore its state."""
    meta = _read_meta(path)  # sidecar only — restore_simulation reads the arrays
    if meta.get("spec") is None:
        raise ValueError(
            "checkpoint has no embedded SimSpec (written by a legacy-constructed "
            "driver); rebuild the driver yourself and call restore_simulation(sim, path)"
        )
    spec = SimSpec.from_dict(meta["spec"])
    sim = make_simulation(spec)
    restore_simulation(sim, path)
    return sim


def save_ensemble_member(ens, i: int, path: str) -> None:
    """Checkpoint ONE member out of a stacked ensemble state as a standard
    single-driver checkpoint: `load_simulation(path)` rebuilds it as a
    standalone `Simulation` (when the member has a spec) and
    `restore_ensemble_member` installs it back into an ensemble slot."""
    spec = ens.specs[i]
    tree = {
        "state": tree_member_slice(ens.state, i),
        "policy_state": tree_member_slice(ens.policy_state, i),
    }
    meta = {
        "driver": "single",
        "spec": None if spec is None else spec.to_dict(),
        "scalars": {
            "sorts": int(ens.sorts[i]),
            "rebuilds": int(ens.rebuilds[i]),
            "host_step": int(ens.host_step[i]),
            "capacity": ens.config.capacity,
            # the ensemble path drives the DEVICE policy only; a standalone
            # resume starts its host-loop policy counters fresh
            "host_policy": {
                "steps_since_sort": 0,
                "rebuilds_since_sort": 0,
                "baseline_perf": None,
                "perf_ema": None,
            },
            "history": ens.histories[i],
            "growths": dict(ens.growths),
            "halts": dict(ens.halts),
            "retries": 0,
            "restarts": 0,
            "discarded_steps": 0,
        },
    }
    _write_dir(path, tree, meta)


def restore_ensemble_member(ens, i: int, path: str) -> None:
    """Install a single-driver checkpoint into slot ``i`` of a stacked
    ensemble. The checkpoint may carry a DIFFERENT bin capacity (it was
    grown independently, or the ensemble grew since the save): the member
    is re-binned — permutation-free, so its continuation stays bit-exact —
    at the ensemble's capacity. A member too dense for the ensemble's
    current capacity is refused (grow the ensemble first); grid and
    particle count must match the slot exactly."""
    arrays, meta = _read_dir(path)
    if meta["driver"] != "single":
        raise ValueError(
            f"ensemble member slots take 'single' driver checkpoints, got "
            f"{meta['driver']!r}"
        )
    scal = meta["scalars"]
    template = {
        "state": tree_member_slice(ens.state, i),
        "policy_state": tree_member_slice(ens.policy_state, i),
    }
    restored = _restore_tree(template, arrays)
    state = restored["state"]
    want = template["state"].particles.pos.shape
    got = state.particles.pos.shape
    if tuple(want) != tuple(got):
        raise ValueError(
            f"checkpoint carries {got[0]} particles but ensemble slot {i} "
            f"holds {want[0]} — the member belongs to a different bucket"
        )
    if int(scal["capacity"]) != ens.config.capacity:
        state, overflow = ens._rebin(state)
        if overflow:
            raise ValueError(
                f"checkpointed member is denser than the ensemble capacity "
                f"{ens.config.capacity} (saved capacity {scal['capacity']}); "
                "grow the ensemble before restoring this member"
            )
    ens.state = tree_member_set(ens.state, i, state)
    ens.policy_state = tree_member_set(ens.policy_state, i, restored["policy_state"])
    ens.host_step[i] = int(scal["host_step"])
    ens.sorts[i] = int(scal["sorts"])
    ens.rebuilds[i] = int(scal["rebuilds"])
    ens.histories[i] = list(scal["history"])
    ens._prewarm_dispatch()


class SimCheckpointer:
    """Rolling autosave for a driver: step-stamped `save_simulation`
    directories under one root, a keep-`keep` GC, and crash recovery via
    `latest_path()`. Wired in automatically by
    ``run(..., autosave_every=N)`` (distributed.fault.run_supervised_windows);
    stale ``*.tmp-<pid>`` debris from dead writers is swept at construction.

    `maybe_save(step)` saves once at least `every` steps have elapsed since
    the last save — window-grained progress rarely lands exactly on a
    multiple, so the cadence is "every N or the first boundary after it".
    """

    def __init__(self, sim, directory: str, *, every: int, keep: int = 2):
        if every <= 0:
            raise ValueError(f"autosave interval must be positive, got {every}")
        if keep < 1:
            raise ValueError(f"keep must be >= 1, got {keep}")
        self.sim = sim
        self.directory = directory or "checkpoints"
        self.every = every
        self.keep = keep
        os.makedirs(self.directory, exist_ok=True)
        clean_stale_tmp(self.directory)
        self._last: int | None = None

    def _path(self, step: int) -> str:
        return os.path.join(self.directory, f"step_{step:09d}")

    def _steps(self) -> list[int]:
        out = []
        for name in os.listdir(self.directory):
            if not name.startswith("step_") or ".tmp-" in name or ".old-" in name:
                continue
            try:
                out.append(int(name[len("step_"):]))
            except ValueError:
                continue
        return sorted(out)

    def latest_path(self) -> str:
        steps = self._steps()
        if not steps:
            raise FileNotFoundError(f"no checkpoints under {self.directory}")
        return self._path(steps[-1])

    def maybe_save(self, step: int, force: bool = False) -> bool:
        if not force and self._last is not None and step - self._last < self.every:
            return False
        if not force and self._last is None:
            self._last = step  # baseline: count `every` steps from here
            return False
        save_simulation(self.sim, self._path(step))
        self._last = step
        for old in self._steps()[: -self.keep]:
            shutil.rmtree(self._path(old), ignore_errors=True)
        return True
