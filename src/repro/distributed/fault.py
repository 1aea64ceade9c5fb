"""Fault-tolerance primitives: failure injection, straggler detection, and
the checkpoint/restart supervisors used by the training loop and the PIC
drivers.

Posture for 1000+ nodes (DESIGN.md §5): preemptions and hardware failures
are the common case, not the exception. The supervisors treat any exception
from the step function as a (possibly transient) node failure: they restore
the latest checkpoint, rebuild device state, and resume. The data pipeline
is stateless (batch = f(step)), so restarts replay no data and skip none.

Two layers live here:

* the generic training-loop pieces (``FailureInjector`` / ``Supervisor``)
  kept from the original stack, and
* the PIC-aware chaos harness and window supervisor: a declarative frozen
  ``FaultSpec`` (serialized on ``SimSpec``) drives deterministic in-graph
  fault injection (NaN into a named field component / momenta, charge-scale
  weight corruption, forced migration recv-drop) or a host-side simulated
  crash, and ``run_supervised_windows`` runs either driver's windowed loop
  under the health sentinel with snapshot/rollback-and-retry on health
  halts and checkpoint-restore on hard exceptions (docs/robustness.md).

This module must stay importable without ``repro.api`` or ``repro.pic``
(both import it); anything from those packages is imported lazily inside
functions.
"""

from __future__ import annotations

import dataclasses
import logging
import time
from typing import Callable

import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.core.health import (
    HALT_INVARIANT,
    HALT_NAMES,
    HALT_NONFINITE,
    INVARIANT_NAMES,
    SimulationHealthError,
)

log = logging.getLogger("repro.fault")


class SimulatedFailure(RuntimeError):
    pass


class FailureInjector:
    """Raises SimulatedFailure at the given step numbers (test/chaos tool)."""

    def __init__(self, fail_at_steps=(), fail_once: bool = True):
        self.fail_at = set(fail_at_steps)
        self.fail_once = fail_once
        self.fired: set[int] = set()

    def maybe_fail(self, step: int) -> None:
        if step in self.fail_at and (not self.fail_once or step not in self.fired):
            self.fired.add(step)
            raise SimulatedFailure(f"injected failure at step {step}")


@dataclasses.dataclass
class StragglerMonitor:
    """Step-time EMA; flags steps slower than `threshold` x the EMA.

    On a real pod the flag feeds the control plane (re-shard away from the
    slow host / re-route ICI traffic); here it is surfaced in metrics and
    asserted on in tests.
    """

    threshold: float = 3.0
    ema: float | None = None
    alpha: float = 0.1
    flagged: int = 0

    def record(self, dt: float) -> bool:
        is_straggler = self.ema is not None and dt > self.threshold * self.ema
        if is_straggler:
            self.flagged += 1
            log.warning("straggler step: %.4fs vs EMA %.4fs", dt, self.ema)
        else:
            # stragglers don't poison the EMA
            self.ema = dt if self.ema is None else (1 - self.alpha) * self.ema + self.alpha * dt
        return is_straggler


class Supervisor:
    """Checkpoint/restart wrapper around a step function.

    step_fn(state, step_idx) -> (state, metrics); state must be
    checkpointable (pytree of arrays). Restores on ANY exception, up to
    max_restarts times.
    """

    def __init__(
        self,
        step_fn: Callable,
        checkpoint_manager,
        *,
        save_every: int = 50,
        max_restarts: int = 10,
        injector: FailureInjector | None = None,
        straggler: StragglerMonitor | None = None,
        async_save: bool = True,
    ):
        self.step_fn = step_fn
        self.ckpt = checkpoint_manager
        self.save_every = save_every
        self.max_restarts = max_restarts
        self.injector = injector
        self.straggler = straggler or StragglerMonitor()
        self.async_save = async_save
        self.restarts = 0
        self.metrics_log: list[dict] = []

    def run(self, state, n_steps: int, *, start_step: int = 0):
        step = start_step
        while step < n_steps:
            try:
                if self.injector is not None:
                    self.injector.maybe_fail(step)
                t0 = time.perf_counter()
                state, metrics = self.step_fn(state, step)
                dt = time.perf_counter() - t0
                is_straggler = self.straggler.record(dt)
                self.metrics_log.append(
                    dict(metrics, step=step, step_time=dt, straggler=is_straggler)
                )
                step += 1
                if step % self.save_every == 0 or step == n_steps:
                    self.ckpt.save(step, state, blocking=not self.async_save)
            except Exception as exc:  # noqa: BLE001 — any failure = node loss
                self.restarts += 1
                if self.restarts > self.max_restarts:
                    raise
                log.warning("step %d failed (%s); restoring latest checkpoint", step, exc)
                self.ckpt.wait()
                latest = self.ckpt.latest_step()
                if latest is None:
                    # nothing saved yet: restart from the initial state
                    step = start_step
                    continue
                state, step = self.ckpt.restore(state)
        self.ckpt.wait()
        return state, step


# ---------------------------------------------------------------------------
# PIC-aware declarative chaos harness
# ---------------------------------------------------------------------------

# In-graph fault kinds, encoded into a traced i32[3] vector
# [kind, step, component] so arming a fault never recompiles the window.
FAULT_NONE = 0
FAULT_NAN_FIELD = 1
FAULT_NAN_MOMENTUM = 2
FAULT_CHARGE_SCALE = 3
FAULT_RECV_DROP = 4

FIELD_COMPONENTS = ("ex", "ey", "ez", "bx", "by", "bz")

# "crash" is host-side only (raises SimulatedFailure between windows).
FAULT_KINDS = {
    "nan_field": FAULT_NAN_FIELD,
    "nan_momentum": FAULT_NAN_MOMENTUM,
    "charge_scale": FAULT_CHARGE_SCALE,
    "recv_drop": FAULT_RECV_DROP,
    "crash": FAULT_NONE,
}

GRAPH_FAULT_KINDS = frozenset(k for k in FAULT_KINDS if k != "crash")


@dataclasses.dataclass(frozen=True)
class FaultSpec:
    """Declarative fault to inject — serialized on ``SimSpec`` so every chaos
    scenario is reproducible from a spec file.

    ``kind``: one of ``nan_field`` (poison ``component`` before the step),
    ``nan_momentum`` (poison particle momenta), ``charge_scale`` (double the
    macro-particle weights, violating charge conservation), ``recv_drop``
    (force the distributed migration recv-drop halt), ``crash`` (raise
    ``SimulatedFailure`` on the host before the window containing ``step``).

    ``step``: the absolute step counter at which the fault fires; in-graph
    faults corrupt the *input* of step ``step + 1``, so that is the step the
    sentinel reports. ``count``: how many times the fault fires; ``0`` means
    persistent (fires on every opportunity — used to test ladder exhaustion).
    """

    kind: str = "nan_field"
    step: int = 0
    component: str = "ez"
    count: int = 1

    def __post_init__(self):
        if self.kind not in FAULT_KINDS:
            raise ValueError(f"unknown fault kind {self.kind!r}; expected one of {sorted(FAULT_KINDS)}")
        if self.component not in FIELD_COMPONENTS:
            raise ValueError(f"unknown field component {self.component!r}")
        if self.step < 0 or self.count < 0:
            raise ValueError("FaultSpec step and count must be >= 0")

    @staticmethod
    def from_dict(d: dict) -> "FaultSpec":
        names = {f.name for f in dataclasses.fields(FaultSpec)}
        unknown = set(d) - names
        if unknown:
            raise ValueError(f"FaultSpec has unknown keys {sorted(unknown)}")
        return FaultSpec(**d)


def no_fault_vec():
    """Fault vector that never fires (step -1 matches no counter)."""
    return jnp.array([FAULT_NONE, -1, 0], jnp.int32)


def inject_fields(fields, step_count, fault_vec):
    """Poison one field component with NaN when the fault fires.

    ``fields``: tuple of the six field arrays in ``FIELD_COMPONENTS`` order;
    ``step_count``: traced i32 absolute step counter at window position i.
    Pure masked select — a non-firing vector returns the inputs unchanged.
    """
    fire = (fault_vec[0] == FAULT_NAN_FIELD) & (step_count == fault_vec[1])
    out = []
    for i, f in enumerate(fields):
        hit = fire & (fault_vec[2] == jnp.int32(i))
        out.append(jnp.where(hit, jnp.full_like(f, jnp.nan), f))
    return tuple(out)


def inject_momenta(u, step_count, fault_vec):
    """Poison particle momenta with NaN when a nan_momentum fault fires."""
    fire = (fault_vec[0] == FAULT_NAN_MOMENTUM) & (step_count == fault_vec[1])
    return jnp.where(fire, jnp.full_like(u, jnp.nan), u)


def inject_weights(w, step_count, fault_vec):
    """Double macro-particle weights when a charge_scale fault fires."""
    fire = (fault_vec[0] == FAULT_CHARGE_SCALE) & (step_count == fault_vec[1])
    return jnp.where(fire, w * jnp.asarray(2.0, w.dtype), w)


def injected_recv_drop(step_count, fault_vec):
    """i32 1 when a recv_drop fault fires at this step, else 0."""
    fire = (fault_vec[0] == FAULT_RECV_DROP) & (step_count == fault_vec[1])
    return fire.astype(jnp.int32)


class PICFaultInjector:
    """Host-side driver of a ``FaultSpec``: arms the in-graph fault vector
    for windows that cover ``spec.step``, raises simulated crashes, and
    retires the fault after it has fired ``spec.count`` times so retried /
    replayed windows run clean."""

    def __init__(self, spec: FaultSpec):
        self.spec = spec
        self.remaining = spec.count if spec.count > 0 else None  # None = persistent
        self.fired = 0

    def _armed(self) -> bool:
        return self.remaining is None or self.remaining > 0

    def _consume(self) -> None:
        self.fired += 1
        if self.remaining is not None:
            self.remaining -= 1

    def window_vec(self, host_step: int, k: int):
        """Fault vector for a window of k steps starting at ``host_step``,
        or None when no in-graph fault is armed for it."""
        if self.spec.kind not in GRAPH_FAULT_KINDS or not self._armed():
            return None
        if not host_step <= self.spec.step < host_step + k:
            return None
        comp = FIELD_COMPONENTS.index(self.spec.component)
        return jnp.array([FAULT_KINDS[self.spec.kind], self.spec.step, comp], jnp.int32)

    def maybe_crash(self, host_step: int, k: int) -> None:
        if self.spec.kind != "crash" or not self._armed():
            return
        if host_step <= self.spec.step < host_step + k:
            self._consume()
            raise SimulatedFailure(
                f"injected crash before window at step {host_step}"
            )

    def note_halt(self, code: int, halt_step: int) -> None:
        """Record that a window halt consumed one firing of the armed fault.
        In-graph faults corrupt the input of step ``spec.step + 1``, so only
        a halt at exactly that step is attributed to the injector."""
        if self.spec.kind in GRAPH_FAULT_KINDS and self._armed() and halt_step == self.spec.step + 1:
            self._consume()


# ---------------------------------------------------------------------------
# Shared windowed-run supervisor (both PIC drivers)
# ---------------------------------------------------------------------------


def run_supervised_windows(sim, n_steps: int, diagnostics_every: int,
                           window: int, *, autosave_every: int = 0,
                           autosave_path: str = "") -> None:
    """Run ``n_steps`` of a windowed PIC driver under fault supervision.

    ``sim`` is either driver (``pic.simulation.Simulation`` or
    ``pic.dist_simulation.DistSimulation``); both expose the same hook set:
    ``_take_snapshot``/``_restore_snapshot`` (device-resident window-start
    carry), ``_enter_window`` (launch one compiled window, return the host
    bundle), ``_consume_bundle`` (commit a successful window), ``_handle_halt``
    (grow-and-continue for the overflow/migration halt family),
    ``_remedy_sort`` and ``_demote_backend`` (remediation ladder rungs), plus
    the ``halts``/``retries``/``restarts``/``discarded_steps`` counters.

    Recovery paths:

    * health halt (``HALT_NONFINITE``/``HALT_INVARIANT``): restore the
      window-start snapshot and retry under the escalating ladder — halve
      the window, then force a global sort, then demote the kernel backend, then
      abort with ``SimulationHealthError`` naming the halt code, step, and
      offending invariant;
    * capacity halts (overflow / migration family): delegate to the driver's
      grow-and-continue handler exactly as before;
    * hard Python/XLA exception: restore the latest on-disk checkpoint
      (``autosave_every`` wires a ``SimCheckpointer`` in automatically) and
      resume, up to ``max_restarts`` times.
    """
    health = sim._health
    inj = sim.fault_injector
    max_retries = health.max_retries if health is not None else 3
    max_restarts = health.max_restarts if health is not None else 3

    ckpt = None
    if autosave_every:
        from repro.api.facade import SimCheckpointer

        ckpt = SimCheckpointer(sim, autosave_path, every=autosave_every)
        ckpt.maybe_save(sim._host_step, force=True)

    target = sim._host_step + n_steps
    retry_target = 0  # nonzero: ladder level >= 1 capped the window length
    while True:
        try:
            while sim._host_step < target:
                k = min(window, target - sim._host_step)
                if retry_target:
                    k = min(k, retry_target)
                # host spans on the profiler's clock, each tagged with the
                # window's start step and live steps (docs/sim_loop.md)
                tag = {"step": sim._host_step, "k": k}
                with TraceAnnotation("pic.window", **tag):
                    if inj is not None:
                        inj.maybe_crash(sim._host_step, k)
                    fault_vec = inj.window_vec(sim._host_step, k) if inj is not None else None
                    snap = None
                    if health is not None:
                        with TraceAnnotation("pic.window.snapshot", **tag):
                            snap = sim._take_snapshot()
                    host = sim._enter_window(k, window, diagnostics_every, fault_vec)
                    code = int(host.get("halt_code", 0))

                    if code in (HALT_NONFINITE, HALT_INVARIANT):
                        sim._restore_snapshot(snap)
                        name = HALT_NAMES[code]
                        sim.halts[name] = sim.halts.get(name, 0) + 1
                        if inj is not None:
                            inj.note_halt(code, int(host.get("halt_step", -1)))
                        sim.retries += 1
                        sim._remedy_level += 1
                        level = sim._remedy_level
                        exhausted = level > max_retries
                        if not exhausted and level >= 3:
                            # last rung: demote the kernel backend one step down
                            # the dispatcher's priority ladder; exhausted when
                            # already on the most conservative backend
                            with TraceAnnotation("pic.window.remedy", **tag):
                                exhausted = not sim._demote_backend()
                        if exhausted:
                            raise SimulationHealthError(
                                halt=name,
                                step=int(host.get("halt_step", -1)),
                                invariant=INVARIANT_NAMES[int(host.get("halt_inv", 0))],
                                measured=float(host.get("halt_measured", float("nan"))),
                                reference=float(host.get("halt_reference", float("nan"))),
                                retries=sim.retries,
                            )
                        if level == 1:
                            retry_target = max(1, k // 2)
                        elif level == 2:
                            with TraceAnnotation("pic.window.remedy", **tag):
                                sim._remedy_sort()
                        log.warning(
                            "health halt %s at step %s: rollback, remediation level %d",
                            name, host.get("halt_step"), level,
                        )
                        continue

                    with TraceAnnotation("pic.window.consume", **tag):
                        n_done = sim._consume_bundle(host, diagnostics_every)
                    sim.discarded_steps += int(host.get("n_discarded", 0))
                    sim._remedy_level = 0
                    retry_target = 0
                    if code:
                        name = HALT_NAMES[code]
                        sim.halts[name] = sim.halts.get(name, 0) + 1
                        if inj is not None:
                            inj.note_halt(code, int(host.get("halt_step", -1)))
                        with TraceAnnotation("pic.window.halt", **tag):
                            sim._handle_halt(code, host)
                    elif n_done < k:
                        raise RuntimeError("windowed driver made no progress without a halt")
                    if ckpt is not None:
                        with TraceAnnotation("pic.window.checkpoint", **tag):
                            ckpt.maybe_save(sim._host_step)
            break
        except SimulationHealthError:
            raise
        except Exception as exc:  # noqa: BLE001 — any failure = node loss
            if ckpt is None:
                raise
            sim.restarts += 1
            if sim.restarts > max_restarts:
                raise
            restarts = sim.restarts
            log.warning("window at step %d failed (%s); restoring latest checkpoint",
                        sim._host_step, exc)
            from repro.api.facade import restore_simulation

            restore_simulation(sim, ckpt.latest_path())
            # the checkpoint predates the crash: keep the live restart count
            sim.restarts = restarts
            sim._remedy_level = 0
            retry_target = 0
    if ckpt is not None:
        ckpt.maybe_save(sim._host_step, force=True)
