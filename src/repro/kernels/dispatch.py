"""Logical-op -> backend dispatch with benchmark-to-select autotuning.

Every hot contraction in the repo is a *logical op* with several
interchangeable implementations (ROADMAP item 1, modeled on xformers'
fmha registry — one op, multiple backends, ``is_available()`` + priority +
benchmark-to-select, persisted autotune cache):

    op               backends (priority)
    ---------------  -------------------------------------------------
    deposit_fused    pallas_reduced (30) > pallas (20) > xla (10)
    gather_fused     pallas (20) > xla (10)
    deposit_unfused  pallas (20) > xla (10)
    bin_gather       pallas (20) > xla (10)

Backend names are spec-level (`DepositionSpec.backend`):

  * ``"xla"``            — the pure-XLA reference contraction (always
                           available; the old ``use_pallas=False``).
  * ``"pallas"``         — the Pallas megakernel (``use_pallas=True``).
  * ``"pallas_reduced"`` — deposition only: the epilogue-fused megakernel
                           that folds the rhocell z-reduction in-kernel so
                           the packed (C, 3, T, T*T) tile never
                           round-trips through HBM.
  * ``"auto"``           — benchmark the available candidates on the first
                           real call (synthetic inputs at the call's exact
                           shapes) and persist the winner.

On a TPU no Pallas backend is offered yet (see `_pallas_ok`): every
"auto" resolves to ``"xla"`` there. A candidate that fails to compile
raises — nothing is caught and skipped.

Resolution of a *forced* name never fails sideways: if the name is not
registered on the op (or unavailable for the key), the best available
backend of priority <= the forced one is used — forcing
``"pallas_reduced"`` on `gather_fused` runs ``"pallas"``.

``"auto"`` winners persist in a JSON cache keyed on
``(op, order, grid_shape, capacity, n_bins, dtype, platform, interpret)``
at ``$REPRO_AUTOTUNE_CACHE`` (default ``.repro_autotune_cache.json`` in
the working directory), so subsequent runs and restarts resolve with zero
re-measurement. A corrupt cache file is reported loudly (RuntimeWarning)
and rebuilt by re-benchmarking. ``counters`` tracks benchmark runs /
cache hits / memo hits for the smoke lane's no-re-benchmark assertions.

Benchmarking only happens EAGERLY — never under an ambient JAX trace.
Inside a jit/scan trace the thunks would be staged instead of executed
(timing Python tracing, not the device) and would bloat the caller's
jaxpr with dead candidate graphs, so ``resolve`` detects the trace,
falls back to priority order with a RuntimeWarning, and persists
nothing. The public core entry points resolve eagerly before entering
their jitted impls, and the sim drivers ``prewarm`` their config's keys
at setup/growth so the traced step always hits the memoized, genuinely
measured winner.
"""

from __future__ import annotations

import dataclasses
import json
import os
import time
import warnings
from typing import Any, Callable

from repro.kernels.common import resolve_interpret

DEFAULT_CACHE_FILE = ".repro_autotune_cache.json"
CACHE_ENV = "REPRO_AUTOTUNE_CACHE"
CACHE_VERSION = 1

#: The global priority ladder (higher = preferred before measurement, and
#: the order the fault supervisor demotes along).
BACKEND_PRIORITY = {"pallas_reduced": 30, "pallas": 20, "xla": 10}

BENCH_ROUNDS = 5
BENCH_WARMUP = 1

#: Observability for tests and the benchmark smoke lane. "trace_fallback"
#: counts "auto" resolutions that could not benchmark because they ran
#: under an ambient JAX trace (see _trace_clean).
counters = {"benchmark": 0, "cache_hit": 0, "memo_hit": 0, "trace_fallback": 0}


@dataclasses.dataclass(frozen=True)
class DispatchKey:
    """Everything a backend choice may legally depend on."""

    op: str
    order: int
    grid_shape: tuple[int, int, int] | None
    capacity: int
    n_bins: int
    dtype: str
    platform: str
    interpret: bool
    #: op runs inside a shard_map body — pallas_call has no replication
    #: rule there, so the Pallas backends are unavailable for sharded keys
    sharded: bool = False
    #: leading vmap batch width the op runs under (the ensemble engine's
    #: member axis). batch=1 is the plain single-sim key; batched keys
    #: benchmark/memoize separately so ensemble shapes autotune per bucket
    #: instead of replaying single-sim winners.
    batch: int = 1

    def cache_key(self) -> str:
        gs = "x".join(map(str, self.grid_shape)) if self.grid_shape else "none"
        mode = "interp" if self.interpret else "compiled"
        shard = "|sharded" if self.sharded else ""
        # batch=1 omits the suffix so pre-batch cache entries stay valid
        bat = f"|batch{self.batch}" if self.batch != 1 else ""
        return (
            f"{self.op}|order{self.order}|grid{gs}|cap{self.capacity}"
            f"|bins{self.n_bins}|{self.dtype}|{self.platform}|{mode}{shard}{bat}"
        )


@dataclasses.dataclass(frozen=True)
class Backend:
    """One implementation of a logical op.

    ``is_available(key)`` gates on platform / interpret mode / shape
    constraints; ``make_thunk(key)`` builds a nullary benchmark thunk on
    synthetic inputs of the key's exact shapes (called only for "auto").
    """

    name: str
    priority: int
    is_available: Callable[[DispatchKey], bool]
    make_thunk: Callable[[DispatchKey], Callable[[], Any]]


_REGISTRY: dict[str, dict[str, Backend]] = {}
# memoized per (key, requested-name) — "auto" and a forced name may resolve
# differently for the same DispatchKey
_MEMO: dict[tuple[DispatchKey, str], str] = {}


def register(op: str, backend: Backend, *, override: bool = False) -> None:
    """Register ``backend`` under ``op``; re-registering an existing name
    requires ``override=True`` (catches accidental double registration)."""
    table = _REGISTRY.setdefault(op, {})
    if backend.name in table and not override:
        raise ValueError(
            f"backend {backend.name!r} already registered for op {op!r} "
            "(pass override=True to replace it)"
        )
    table[backend.name] = backend
    _MEMO.clear()


def backends_for(op: str) -> dict[str, Backend]:
    _ensure_default_registry()
    if op not in _REGISTRY:
        raise KeyError(f"unknown op {op!r}; registered: {sorted(_REGISTRY)}")
    return dict(_REGISTRY[op])


def ops() -> tuple[str, ...]:
    _ensure_default_registry()
    return tuple(sorted(_REGISTRY))


def cache_path() -> str:
    return os.environ.get(CACHE_ENV) or DEFAULT_CACHE_FILE


def clear_memo() -> None:
    """Drop the in-process memo (the JSON cache is untouched) — the next
    resolve re-reads the cache file. Test/smoke hook."""
    _MEMO.clear()


def reset_counters() -> None:
    for k in counters:
        counters[k] = 0


# ---------------------------------------------------------------------------
# resolution
# ---------------------------------------------------------------------------


def _trace_clean() -> bool:
    """True when no ambient JAX trace is active, i.e. executing a thunk
    here would really run it on the device rather than stage it into some
    caller's jaxpr (where timings would measure Python tracing and
    block_until_ready would be a no-op on tracers)."""
    import jax

    return jax.core.trace_ctx.is_top_level()


def _make_key(
    op: str,
    *,
    order: int,
    grid_shape=None,
    capacity: int = 0,
    n_bins: int | None = None,
    dtype: str = "float32",
    interpret: bool | None = None,
    sharded: bool = False,
    batch: int = 1,
) -> DispatchKey:
    """The key of one call on the current platform (n_bins defaults to the
    grid's cell count)."""
    import jax

    if grid_shape is not None:
        grid_shape = tuple(int(s) for s in grid_shape)
        if n_bins is None:
            n_bins = grid_shape[0] * grid_shape[1] * grid_shape[2]
    return DispatchKey(
        op=op,
        order=int(order),
        grid_shape=grid_shape,
        capacity=int(capacity),
        n_bins=int(n_bins or 0),
        dtype=str(dtype),
        platform=jax.default_backend(),
        interpret=resolve_interpret(interpret),
        sharded=bool(sharded),
        batch=int(batch),
    )


def resolve(
    op: str,
    requested: str,
    *,
    order: int,
    grid_shape=None,
    capacity: int = 0,
    n_bins: int | None = None,
    dtype: str = "float32",
    interpret: bool | None = None,
    sharded: bool = False,
    batch: int = 1,
    allow_benchmark: bool = True,
) -> str:
    """Resolve ``requested`` ("auto" or a backend name) to a concrete
    backend name for ``op`` at this shape key.

    ``sharded=True`` marks an op that runs inside a shard_map body, where
    ``pallas_call`` has no replication rule — the Pallas backends are
    unavailable and resolution (even "auto") answers "xla" with no
    benchmark. The distributed step builders resolve with this flag at
    build time and bake the concrete name into the shard body.

    Cheap after the first call per key: in-process memo, then the JSON
    autotune cache, and only then — for "auto" with >1 candidate — a
    benchmark of the available candidates on synthetic inputs. The
    benchmark runs ONLY when called eagerly: under an ambient JAX trace
    (or with ``allow_benchmark=False`` — the fault supervisor's demotion
    path, which must not re-execute suspect kernels) an unmeasured "auto"
    falls back to priority order without memoizing or persisting anything,
    so a later eager call still gets to measure. Callers that trace with
    "auto" should ``prewarm`` their keys eagerly first.
    """
    key = _make_key(
        op, order=order, grid_shape=grid_shape, capacity=capacity, n_bins=n_bins,
        dtype=dtype, interpret=interpret, sharded=sharded, batch=batch,
    )

    memo_key = (key, requested)
    if memo_key in _MEMO:
        counters["memo_hit"] += 1
        return _MEMO[memo_key]

    table = backends_for(op)
    available = [b for b in table.values() if b.is_available(key)]
    if not available:
        raise RuntimeError(f"no available backend for op {op!r} at {key}")
    available.sort(key=lambda b: -b.priority)

    if requested != "auto":
        if requested not in BACKEND_PRIORITY:
            raise ValueError(
                f"unknown backend {requested!r}; known: "
                f"{sorted(BACKEND_PRIORITY)} or 'auto'"
            )
        # forced: the named backend if available, else the best available
        # one at or below the forced priority (never escalate past a
        # demotion), else the most conservative available
        rank = BACKEND_PRIORITY[requested]
        eligible = [b for b in available if b.priority <= rank]
        choice = (eligible or [available[-1]])[0].name
        _MEMO[memo_key] = choice
        return choice

    if len(available) == 1:
        _MEMO[memo_key] = available[0].name
        return available[0].name

    path = cache_path()
    ck = key.cache_key()
    cached = _load_cache(path).get(ck)
    if isinstance(cached, dict) and cached.get("backend") in table:
        name = cached["backend"]
        counters["cache_hit"] += 1
        _MEMO[memo_key] = name
        return name

    if not allow_benchmark:
        # demotion/introspection path: never execute kernels, answer from
        # priority order (exactly what an unmeasured traced step ran)
        return available[0].name
    if not _trace_clean():
        # Benchmarking under a trace would stage the thunks into the
        # caller's jaxpr and time Python tracing instead of the device —
        # fall back to priority order and persist NOTHING (a later eager
        # resolve or prewarm still measures this key properly).
        counters["trace_fallback"] += 1
        warnings.warn(
            f"dispatch.resolve({op!r}, 'auto') called under a JAX trace with "
            f"no autotune-cache entry for {ck}: falling back to priority "
            f"order ({available[0].name!r}) without benchmarking. Resolve "
            "eagerly first (dispatch.prewarm) to autotune this key.",
            RuntimeWarning,
            stacklevel=2,
        )
        return available[0].name

    name, timings = _benchmark(key, available)
    _merge_store(path, ck, {"backend": name, "timings_us": timings})
    _MEMO[memo_key] = name
    return name


#: Which dispatcher op a driver deposition / gather mode routes through
#: (the scatter/rhocell comparison modes never touch the dispatcher).
OP_BY_DEPOSITION = {"matrix": "deposit_fused", "matrix_unfused": "deposit_unfused"}
OP_BY_GATHER = {"matrix": "gather_fused", "matrix_unfused": "bin_gather"}


def ops_for_modes(deposition: str, gather: str) -> tuple[str, ...]:
    """The dispatcher ops a sim config with these deposition/gather modes
    resolves in its hot step (empty for pure scatter/rhocell configs)."""
    ops_ = []
    if deposition in OP_BY_DEPOSITION:
        ops_.append(OP_BY_DEPOSITION[deposition])
    if gather in OP_BY_GATHER:
        ops_.append(OP_BY_GATHER[gather])
    return tuple(ops_)


def prewarm(
    ops_: tuple[str, ...] | list[str],
    *,
    order: int,
    grid_shape=None,
    capacity: int = 0,
    n_bins: int | None = None,
    dtype: str = "float32",
    interpret: bool | None = None,
    sharded: bool = False,
    batch: int = 1,
    requested: str = "auto",
) -> dict[str, str]:
    """Eagerly resolve (benchmarking + persisting if unmeasured) each op at
    one shape key, returning {op: backend}.

    The sim drivers call this from host code at setup and after every
    capacity growth: `resolve` refuses to benchmark under an ambient JAX
    trace, so without a prewarmed memo the traced step would silently run
    the priority-order fallback instead of the measured winner."""
    return {
        op: resolve(
            op, requested, order=order, grid_shape=grid_shape, capacity=capacity,
            n_bins=n_bins, dtype=dtype, interpret=interpret, sharded=sharded,
            batch=batch,
        )
        for op in ops_
    }


def describe(ops_: tuple[str, ...] | list[str], **key) -> dict[str, dict]:
    """{op: {"offered": backend names by priority, "timings_us": the
    persisted autotune medians or None}} at one shape key (``key`` as for
    `prewarm`). Reads only: nothing is benchmarked or memoized."""
    entries = _load_cache(cache_path(), quiet=True)
    out = {}
    for op in ops_:
        k = _make_key(op, **key)
        offered = [b for b in backends_for(op).values() if b.is_available(k)]
        cached = entries.get(k.cache_key())
        out[op] = {
            "offered": [b.name for b in sorted(offered, key=lambda b: -b.priority)],
            "timings_us": cached.get("timings_us") if isinstance(cached, dict) else None,
        }
    return out


def demote(
    current: str,
    *,
    order: int,
    grid_shape=None,
    capacity: int = 0,
    n_bins: int | None = None,
    dtype: str = "float32",
    interpret: bool | None = None,
    sharded: bool = False,
    batch: int = 1,
) -> str | None:
    """The fault supervisor's remediation rung: the next backend down the
    priority ladder from what ``current`` resolves to for the fused
    deposition op (the op every config runs), or None when already at the
    bottom — generalizing the old hard-coded "drop Pallas" toggle.

    NEVER benchmarks: this runs mid-error-recovery, where re-executing the
    very kernels suspected of the non-finite/invariant halt is the last
    thing remediation should do. An unmeasured "auto" resolves from the
    memo/cache, else to priority order — which is exactly the backend an
    unmeasured traced step actually ran, so the demotion steps down from
    the true effective backend either way. Pass the step's actual ``dtype``
    (and ``interpret``, if the step forced it) so the key matches the run."""
    effective = resolve(
        "deposit_fused", current, order=order, grid_shape=grid_shape,
        capacity=capacity, n_bins=n_bins, dtype=dtype, interpret=interpret,
        sharded=sharded, batch=batch, allow_benchmark=False,
    )
    ladder = sorted(BACKEND_PRIORITY, key=BACKEND_PRIORITY.get, reverse=True)
    below = [n for n in ladder if BACKEND_PRIORITY[n] < BACKEND_PRIORITY[effective]]
    return below[0] if below else None


def record(
    op: str,
    *,
    order: int,
    grid_shape=None,
    capacity: int = 0,
    n_bins: int | None = None,
    dtype: str = "float32",
    interpret: bool | None = None,
    batch: int = 1,
    timings_us: dict[str, float],
) -> str:
    """Seed (or overwrite) the autotune-cache entry for one key from
    externally measured timings, returning the winner's name.

    The benchmark sweeps call this with their interleaved-round medians —
    higher-quality measurements than the dispatcher's quick first-call
    probe — so the persisted choice and the published BENCH_* rows agree
    by construction."""
    unknown = set(timings_us) - set(BACKEND_PRIORITY)
    if unknown:
        raise ValueError(f"unknown backends in timings: {sorted(unknown)}")
    key = _make_key(
        op, order=order, grid_shape=grid_shape, capacity=capacity, n_bins=n_bins,
        dtype=dtype, interpret=interpret, batch=batch,
    )
    winner = min(timings_us, key=timings_us.get)
    _merge_store(cache_path(), key.cache_key(), {
        "backend": winner,
        "timings_us": {n: round(float(us), 1) for n, us in timings_us.items()},
    })
    _MEMO.pop((key, "auto"), None)
    return winner


# ---------------------------------------------------------------------------
# autotune cache (JSON, env-overridable path)
# ---------------------------------------------------------------------------


def _load_cache(path: str, quiet: bool = False) -> dict:
    if not os.path.exists(path):
        return {}
    try:
        with open(path) as f:
            data = json.load(f)
        if data.get("version") != CACHE_VERSION or not isinstance(data.get("entries"), dict):
            raise ValueError(f"unexpected schema (want version {CACHE_VERSION})")
        return data["entries"]
    except (OSError, ValueError) as e:
        if not quiet:
            warnings.warn(
                f"autotune cache {path!r} is corrupt ({e}); ignoring it and "
                "re-benchmarking — the file will be rewritten",
                RuntimeWarning,
                stacklevel=3,
            )
        return {}


def _merge_store(path: str, ck: str, entry: dict) -> None:
    """Write one entry with merge-on-write: re-load the file immediately
    before replacing it so concurrent processes (multi-process distributed
    runs share the default CWD cache path) updating DIFFERENT keys don't
    drop each other's entries — os.replace only prevents torn files, not
    lost updates from a stale read-modify-write."""
    entries = _load_cache(path, quiet=True)
    entries[ck] = entry
    _store_cache(path, entries)


def _store_cache(path: str, entries: dict) -> None:
    tmp = f"{path}.tmp-{os.getpid()}"
    try:
        with open(tmp, "w") as f:
            json.dump({"version": CACHE_VERSION, "entries": entries}, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except OSError as e:  # read-only dir etc. — autotuning still works, unpersisted
        warnings.warn(f"could not persist autotune cache to {path!r}: {e}", RuntimeWarning)
        if os.path.exists(tmp):
            os.remove(tmp)


def _benchmark(key: DispatchKey, candidates: list[Backend]) -> tuple[str, dict]:
    """Interleaved-round timing of each candidate's synthetic thunk; returns
    (winner name, per-backend median microseconds). Precondition: no ambient
    JAX trace (resolve guards this) — the thunks must really execute so
    block_until_ready fences device work."""
    counters["benchmark"] += 1
    thunks = {b.name: b.make_thunk(key) for b in candidates}
    for fn in thunks.values():  # compile/warm outside the timed rounds
        for _ in range(BENCH_WARMUP):
            fn()
    samples: dict[str, list[float]] = {n: [] for n in thunks}
    for _ in range(BENCH_ROUNDS):
        for name, fn in thunks.items():
            t0 = time.perf_counter()
            fn()
            samples[name].append((time.perf_counter() - t0) * 1e6)
    medians = {n: sorted(s)[len(s) // 2] for n, s in samples.items()}
    winner = min(medians, key=medians.get)
    return winner, {n: round(us, 1) for n, us in medians.items()}


# ---------------------------------------------------------------------------
# default registry: the four logical ops
# ---------------------------------------------------------------------------


def _always(_key: DispatchKey) -> bool:
    return True


def _pallas_ok(key: DispatchKey) -> bool:
    # pallas_call has no shard_map replication rule (on any platform), so
    # ops traced inside a shard body can never route to Pallas. Everywhere
    # but TPU the kernels need the interpreter — with interpret forced off
    # on a non-TPU platform the Pallas backends are unavailable and
    # resolution falls back to XLA.
    if key.sharded:
        return False
    if key.platform == "tpu" and not key.interpret:
        # Mosaic compiles every kernel of the registry for v5e except
        # pallas_reduced (tests/test_tpu_compile.py), but none is offered
        # on the chip: at 64^3 bins their (8, 128)-tiled (C, cap, 3)
        # operands need 9-14 GiB of HBM — the uniform 64^3 order-3 window
        # with them asks for "15.80G of 15.75G hbm" — and no kernel has
        # passed the chip's oracle check. On a v5e both gather kernels
        # never returned while they requested a 64 MiB scoped-VMEM limit,
        # and a smoke with Pallas offered hung in a block_until_ready.
        # ROADMAP C3: fix or delete.
        return False
    return key.interpret


def _pallas_reduced_ok(key: DispatchKey) -> bool:
    # the column-blocked kernel additionally needs the grid geometry (and
    # Mosaic cannot lower its z-epilogue, which splits the lane axis of
    # each (CB, T, T*T) tile into (T, T): "unsupported shape cast")
    return _pallas_ok(key) and key.grid_shape is not None


def _bshape(key: DispatchKey, *shape: int) -> tuple[int, ...]:
    """Operand shape for the key — a leading member axis when batched, so a
    batched key's benchmark measures the vmapped contraction it will run."""
    return (key.batch, *shape) if key.batch > 1 else tuple(shape)


def _bvmap(key: DispatchKey, fn):
    """Lift ``fn`` over the leading member axis for batched keys (matching
    how the ensemble window actually invokes the op)."""
    if key.batch > 1:
        import jax

        return jax.vmap(fn)
    return fn


def _synthetic_slab(key: DispatchKey):
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(key.dtype)
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    d = jax.random.uniform(k1, _bshape(key, key.n_bins, key.capacity, 3), dt, maxval=0.999)
    val = jax.random.normal(k2, _bshape(key, key.n_bins, key.capacity, 3), dt)
    return d, val


def _deposit_fused_thunk(impl: str):
    def make(key: DispatchKey):
        import jax

        from repro.core.deposition import fused_deposit_grids

        d, val = _synthetic_slab(key)
        fn = jax.jit(_bvmap(key, lambda d_, val_: fused_deposit_grids(
            d_, val_, grid_shape=key.grid_shape, order=key.order, backend=impl
        )))
        return lambda: jax.block_until_ready(fn(d, val))

    return make


def _gather_fused_thunk(impl: str):
    def make(key: DispatchKey):
        import jax
        import jax.numpy as jnp

        from repro.core.gather import fused_gather_bins
        from repro.core.shape_functions import max_guard

        d, _ = _synthetic_slab(key)
        g = max_guard(key.order)
        nx, ny, nz = key.grid_shape
        keys = jax.random.split(jax.random.PRNGKey(1), 6)
        padded = tuple(
            jax.random.normal(
                k, _bshape(key, nx + 2 * g, ny + 2 * g, nz + 2 * g), jnp.dtype(key.dtype)
            )
            for k in keys
        )
        fn = jax.jit(_bvmap(key, lambda d_, padded_: fused_gather_bins(
            d_, padded_, grid_shape=key.grid_shape, order=key.order, backend=impl
        )))
        return lambda: jax.block_until_ready(fn(d, padded))

    return make


def _deposit_unfused_thunk(impl: str):
    def make(key: DispatchKey):
        import jax

        from repro.core.shape_functions import support

        m, _ = support(key.order, True)
        tu, _ = support(key.order, False)
        k1, k2 = jax.random.split(jax.random.PRNGKey(2))
        a = jax.random.normal(k1, _bshape(key, key.n_bins, key.capacity, m), key.dtype)
        b = jax.random.normal(k2, _bshape(key, key.n_bins, key.capacity, tu * tu), key.dtype)
        if impl == "pallas":
            from repro.kernels.deposition.ops import bin_outer_product as fn
        else:
            from repro.kernels.deposition.ref import bin_outer_product_ref

            fn = bin_outer_product_ref
        fn = jax.jit(_bvmap(key, fn))
        return lambda: jax.block_until_ready(fn(a, b))

    return make


def _bin_gather_thunk(impl: str):
    def make(key: DispatchKey):
        import jax
        import jax.numpy as jnp

        from repro.core.shape_functions import support

        m, _ = support(key.order, True)
        tu, _ = support(key.order, False)
        n = tu * tu
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(3), 3)
        wx = jax.random.normal(k1, _bshape(key, key.n_bins, key.capacity, m), key.dtype)
        byz = jax.random.normal(k2, _bshape(key, key.n_bins, key.capacity, n), key.dtype)
        g = jax.random.normal(k3, _bshape(key, key.n_bins, m, n), key.dtype)
        if impl == "pallas":
            from repro.kernels.gather.ops import bin_gather as fn
        else:
            from repro.core.shape_functions import CONTRACTION_PRECISION

            fn = lambda wx, byz, g: jnp.sum(
                wx * jnp.einsum("cpn,cmn->cpm", byz, g, precision=CONTRACTION_PRECISION),
                axis=-1,
            )
        fn = jax.jit(_bvmap(key, fn))
        return lambda: jax.block_until_ready(fn(wx, byz, g))

    return make


_DEFAULTS_REGISTERED = False


def _ensure_default_registry() -> None:
    global _DEFAULTS_REGISTERED
    if _DEFAULTS_REGISTERED:
        return
    _DEFAULTS_REGISTERED = True
    register("deposit_fused", Backend("xla", 10, _always, _deposit_fused_thunk("xla")))
    register("deposit_fused", Backend("pallas", 20, _pallas_ok, _deposit_fused_thunk("pallas")))
    register(
        "deposit_fused",
        Backend("pallas_reduced", 30, _pallas_reduced_ok, _deposit_fused_thunk("pallas_reduced")),
    )
    register("gather_fused", Backend("xla", 10, _always, _gather_fused_thunk("xla")))
    register("gather_fused", Backend("pallas", 20, _pallas_ok, _gather_fused_thunk("pallas")))
    register("deposit_unfused", Backend("xla", 10, _always, _deposit_unfused_thunk("xla")))
    register("deposit_unfused", Backend("pallas", 20, _pallas_ok, _deposit_unfused_thunk("pallas")))
    register("bin_gather", Backend("xla", 10, _always, _bin_gather_thunk("xla")))
    register("bin_gather", Backend("pallas", 20, _pallas_ok, _bin_gather_thunk("pallas")))
