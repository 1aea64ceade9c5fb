#!/usr/bin/env python3
"""Run one benchmark cell once on the accelerator and print its result.

    python bench/run.py --workload uniform.o3 --seed 7 --seconds 10 --trace 0

A cell is a ``workloads`` entry of ``BENCHMARK.json``: a configuration
(``bench/configs/<config>.json``, the deployment) under a traffic mix
(``bench/traffic/<mix>.json``, shape order and window). The run builds the
cell's simulation through ``repro.api.make_simulation`` with particles made
on the device from ``--seed``, warms up until the window program is
compiled and no capacity growth is pending, drives one checked window
through the same compiled program (its start and end state are kept on
the host; the sort policy's cycle is set so that a global sort falls on
its first step), then runs whole windows until ``--seconds`` have passed.

``--trace 0`` reports the end-to-end metrics (``step_ms``, ``peak_hbm_gib``,
``setup_s``); ``--trace 1`` traces a few windows with the profiler and
reports the per-layer metrics, each read by ``bench/metrics/<name>.py``
from the reduced trace and the run's counters. After the window the
program's state is freed and the plain reference (``bench/reference.py``)
recomputes the checked window from its start state; the numbers compared
and their limits (``bench/limits/<cell>.json``) are the last lines of
standard error and the ``checks`` entry, last, of the result line. The
result line is the last line of standard output.

Without a TPU, or with fewer chips than the cell asks for, the run exits 3
and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import hashlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
FIELD_NAMES = ("ex", "ey", "ez", "bx", "by", "bz")
#: where a traced run writes its profile; removed once it is reduced
TRACE_DIR = ROOT / ".bench_trace"
#: the dispatcher's autotune record, kept inside the checkout
AUTOTUNE_FILE = ROOT / ".bench_autotune.json"
#: JAX's event for every executable it compiles or loads from its cache
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
EXIT_NO_CHIP = 3

sys.path.insert(0, str(BENCH))
# libtpu writes its logs under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")


# ---------------------------------------------------------------------------
# the files the harness finds by name
# ---------------------------------------------------------------------------


def _named_file(kind: str, name: str, suffix: str) -> Path:
    if not NAME_RE.match(name):
        raise ValueError(f"bad {kind} name {name!r}")
    path = BENCH / kind / f"{name}{suffix}"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file {path.relative_to(ROOT)}")
    return path


def load_named(kind: str, name: str) -> dict:
    """``bench/<kind>/<name>.json``: a configuration, traffic mix or limit set."""
    return json.loads(_named_file(kind, name, ".json").read_text())


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find_cell(bench: dict, workload: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == workload:
            return cell
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json")


def load_reader(metric: str):
    """The ``read(ctx)`` function of ``bench/metrics/<metric>.py``."""
    path = _named_file("metrics", metric, ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def load_layers(directory: Path = BENCH / "layers") -> list[dict]:
    """Every attribution file, in the order they claim device ops
    (``priority``, then name): the first layer whose pattern matches an
    op's name or metadata takes it."""
    layers = []
    for path in sorted(directory.glob("*.json")):
        layer = json.loads(path.read_text())
        layer["key"] = path.stem
        layers.append(layer)
    return sorted(layers, key=lambda l: (l.get("priority", 100), l["key"]))


# ---------------------------------------------------------------------------
# building the cell
# ---------------------------------------------------------------------------


def plasma_seed(seed: int) -> int:
    """The 31-bit PRNG seed the plasma is drawn from: a hash of the whole
    ``--seed``, since JAX keeps only the low 32 bits of a larger one."""
    digest = hashlib.blake2b(str(int(seed)).encode(), digest_size=8).digest()
    return int.from_bytes(digest, "little") & 0x7FFFFFFF


def _merge(node, value):
    """``value`` laid over ``node``: a dict replaces fields of a spec node,
    recursively; anything else replaces the node."""
    if isinstance(value, dict) and dataclasses.is_dataclass(node):
        return dataclasses.replace(node, **{k: _merge(getattr(node, k), v) for k, v in value.items()})
    return value


def build_spec(config: dict, traffic: dict, seed: int):
    """The cell's `SimSpec`: the configuration's scenario and overrides,
    then the mix's order and window. A flat override goes through the
    scenario registry (``grid``, ``ppc``, ...); a dict-valued one is laid
    over that node of the spec (``"laser": {"z_center": 76.8}``)."""
    from repro.api import scenario

    overrides = {**config.get("spec", {}), **traffic.get("spec", {})}
    flat = {k: v for k, v in overrides.items() if not isinstance(v, dict)}
    flat.update(order=traffic["order"], window=traffic["window"], seed=plasma_seed(seed))
    spec = scenario(config["scenario"], **flat)
    return _merge(spec, {k: v for k, v in overrides.items() if isinstance(v, dict)})


def snapshot(sim) -> dict:
    """The single-device driver's physics state, copied to the host."""
    import jax
    import numpy as np

    s = sim.state
    host = jax.device_get({
        "fields": [getattr(s.fields, n) for n in FIELD_NAMES],
        "pos": s.particles.pos, "u": s.particles.u, "w": s.particles.w, "alive": s.particles.alive,
    })
    host["fields"] = [np.asarray(f) for f in host["fields"]]
    return host


class CompileCounter:
    """Counts executables JAX compiles (or loads from its cache) between
    `start` and `stop`."""

    def __init__(self):
        import jax

        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kwargs):
        if self.active and event == COMPILE_EVENT:
            self.count += 1

    def start(self):
        self.count, self.active = 0, True

    def stop(self) -> int:
        self.active = False
        return self.count


def due_sort(sim) -> None:
    """Set the sort policy's count of steps since the last global sort so
    that its fixed interval falls due on the next step. The run then sits
    at that phase of the configuration's sort cycle, as a run resumed from
    a checkpoint would; the interval is unchanged."""
    import jax.numpy as jnp

    interval = sim.policy.config.sort_interval
    sim.policy_state = dataclasses.replace(
        sim.policy_state, steps_since_sort=jnp.int32(interval - 1))


def warm_up(sim, window: int) -> None:
    """Run windows until one compiles nothing new: the first compiles the
    window program, and a capacity growth makes the next compile again."""
    while True:
        before = dict(sim.growths)
        sim.run(window, window=window)
        if sim.growths == before:
            return


def peak_memory_bytes(devices) -> int | None:
    """Peak device memory of the fullest of ``devices``: the allocator's
    peak of live buffers plus its peak reservation for compiled programs'
    temporaries (a TPU reserves those apart from live buffers), where the
    backend reports them."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(stats["peak_bytes_in_use"] + stats.get("peak_bytes_reserved", 0))
    return max(peaks) if peaks else None


def window_hlo(sim, window: int) -> str:
    """The compiled window program's HLO text, for joining the trace's op
    names to their metadata. Lowering the same call again finds the
    executable the window ran in JAX's caches."""
    import jax.numpy as jnp
    from repro.distributed.fault import no_fault_vec
    from repro.pic import simulation

    lowered = simulation._pic_run_window_donated.lower(
        sim.state, sim.policy_state, jnp.asarray(window, jnp.int32), no_fault_vec(),
        sim.config, sim.policy.config, window, bool(sim.spec.run.diagnostics_every), sim._health,
        False,
    )
    return lowered.compile().as_text()


def _instrument(sim):
    """Host spans around the driver's window entry, bundle fetch and bundle
    consumption, written into the profiler's trace."""
    import jax
    from repro.pic import simulation

    def span(name, fn):
        def wrapped(*a, **k):
            with jax.profiler.TraceAnnotation(name):
                return fn(*a, **k)
        return wrapped

    sim._enter_window = span("bench.enter_window", sim._enter_window)
    sim._consume_bundle = span("bench.consume_bundle", sim._consume_bundle)
    simulation._fetch_bundle = span("bench.fetch_bundle", simulation._fetch_bundle)


@dataclasses.dataclass
class LayerContext:
    """What a per-layer metric reader reads: the reduced trace of the
    traced windows and the run's counters."""

    steps: int
    order: int
    n_cells: int
    capacity: int
    n_weighted: int
    compiles_in_window: int
    peak: dict
    trace: object  # devtrace.Reduction


def run_cell(workload: str, config: dict, traffic: dict, *, seed: int, seconds: float,
             trace: bool, devices, limits: dict, per_layer: list, log=print) -> dict:
    """Set up, warm up, check one window, measure, compare; returns the
    result line as a dict."""
    import jax

    import check
    import counts
    import reference
    from repro.api import make_simulation
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    os.environ.setdefault("REPRO_AUTOTUNE_CACHE", str(AUTOTUNE_FILE))
    compiles = CompileCounter()
    window = int(traffic["window"])
    check_steps = int(traffic["check_steps"])

    spec = build_spec(config, traffic, seed)
    sim = make_simulation(spec)
    warm_up(sim, window)
    # the checked window: the same compiled program, from a kept start
    # state, with a global sort due on its first step so that the sort's
    # branch of the window runs under the check
    due_sort(sim)
    start = snapshot(sim)
    growths, sorts_before = dict(sim.growths), sim.sorts + sim.rebuilds
    sim.run(check_steps, window=window)
    end = snapshot(sim)
    check_sorts = sim.sorts + sim.rebuilds - sorts_before
    if check_sorts < 1:
        raise RuntimeError("the checked window ran no global sort")
    if sim.growths != growths:
        warm_up(sim, window)
    cfg = sim.config
    log(f"{workload}: grid {cfg.grid.shape} particles {start['pos'].shape[0]} order {cfg.order} "
        f"capacity {cfg.capacity} backend {cfg.backend} growths {sim.growths} "
        f"global sorts in the checked window {check_sorts} at step {sim._host_step} "
        f"setup {time.perf_counter() - T_START:.2f}s")

    step0, halts0, sorts0 = sim._host_step, dict(sim.halts), sim.sorts + sim.rebuilds
    breakdown, traced = None, {}
    if not trace:
        compiles.start()
        laps = []
        t0 = time.perf_counter()
        setup_s = t0 - T_START
        attempted = 0
        while True:
            sim.run(window, window=window)
            attempted += window
            laps.append(time.perf_counter())
            if laps[-1] - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
        n_compiles = compiles.stop()
        log(f"{workload}: window seconds "
            f"{[round(b - a, 6) for a, b in zip([t0] + laps, laps)]}")
        done = sim._host_step - step0
        peak = peak_memory_bytes(devices)
        metrics = {"step_ms": {"value": 1e3 * elapsed / done, "unit": "ms"}}
        if peak is not None:
            metrics["peak_hbm_gib"] = {"value": peak / 2**30, "unit": "GiB"}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
        log(f"{workload}: {done} steps in {elapsed:.3f}s, {n_compiles} compiles in window; "
            f"memory {devices[0].memory_stats()}")
    else:
        import devtrace

        _instrument(sim)
        n_windows = int(traffic["trace_windows"])
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        compiles.start()
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=options)
        t0 = time.perf_counter()
        for _ in range(n_windows):
            with jax.profiler.TraceAnnotation("bench.window"):
                sim.run(window, window=window)
        elapsed = time.perf_counter() - t0
        jax.profiler.stop_trace()
        n_compiles = compiles.stop()
        attempted = n_windows * window
        done = sim._host_step - step0
        peak = peak_memory_bytes(devices)
        p = sim.state.particles
        n_weighted = int(jax.numpy.sum(p.alive & (p.w != 0)))
        del p
        red = devtrace.reduce(devtrace.find_xplane(TRACE_DIR), load_layers(), len(devices),
                              window_hlo(sim, window))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        ctx = LayerContext(
            steps=done, order=cfg.order, n_cells=cfg.grid.n_cells, capacity=cfg.capacity,
            n_weighted=n_weighted, compiles_in_window=n_compiles,
            peak=counts.peaks(devices[0].device_kind), trace=red,
        )
        metrics = {}
        for m in per_layer:
            value = load_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        log(f"{workload}: traced {done} steps in {elapsed:.3f}s; device busy {red.busy_s:.4f}s "
            f"of {red.window_s:.4f}s; unclaimed {100 * red.unclaimed_share:.2f}% of device time: "
            f"{red.unclaimed_top[:8]}")
        breakdown = red.breakdown()
        traced = {"busy_s": red.busy_s, "window_s": red.window_s}

    failed = attempted - done
    halts = {k: v - halts0.get(k, 0) for k, v in sim.halts.items() if v != halts0.get(k, 0)}
    log(f"{workload}: global sorts in window {sim.sorts + sim.rebuilds - sorts0}, halts {halts}")
    del sim
    gc.collect()

    # the reference recomputes the checked window once the program is gone
    t_ref = time.perf_counter()
    ref = reference.run(start, check_steps, order=cfg.order, dt=cfg.dt, charge=cfg.charge,
                        mass=cfg.mass, dx=cfg.grid.dx)
    numbers = check.compare(end, ref)
    log(f"{workload}: reference over {check_steps} steps took {time.perf_counter() - t_ref:.2f}s")
    # the limits file names the numbers compared; the others are printed only
    checks = {name: {"value": numbers[name], "limit": limit} for name, limit in limits.items()}
    info = {name: v for name, v in numbers.items() if name not in limits}
    for name, v in info.items():
        log(f"{workload}: {name} {v!r} (not compared)")
    correct = bool(done == attempted and check.within(checks))

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
              "memory_peak_bytes": peak, **traced}
    line = {"correct": correct, "attempted": attempted, "failed": failed, "metrics": metrics,
            "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["info"] = info
    line["checks"] = checks
    return line


def cell_metrics(bench: dict, workload: str, section: str) -> list[dict]:
    """The metrics of ``section`` that this cell reports: those that list
    it under ``workloads``, or list no cells at all."""
    return [m for m in bench[section] if workload in m.get("workloads", [workload])]


def require_chips(n_chips: int):
    """The accelerator's devices, or exit: no TPU, or fewer chips than the
    cell asks for, gives no result."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"bench: no accelerator: {e}", file=sys.stderr)
        sys.exit(EXIT_NO_CHIP)
    if devices[0].platform != "tpu":
        print(f"bench: no TPU (JAX platform is {devices[0].platform!r})", file=sys.stderr)
        sys.exit(EXIT_NO_CHIP)
    if len(devices) < n_chips:
        print(f"bench: the cell needs {n_chips} chips, JAX sees {len(devices)}", file=sys.stderr)
        sys.exit(EXIT_NO_CHIP)
    return devices[:n_chips]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_benchmark()
    cell = find_cell(bench, args.workload)
    config = load_named("configs", cell["config"])
    traffic = load_named("traffic", cell["traffic"])
    limits = load_named("limits", args.workload)
    sys.path.insert(0, str(ROOT / "src"))
    devices = require_chips(int(cell["chips"]))

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    line = run_cell(
        args.workload, config, traffic, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), devices=devices, limits=limits,
        per_layer=cell_metrics(bench, args.workload, "per_layer"), log=log,
    )
    for name, c in line["checks"].items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    log(f"correct {line['correct']}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
