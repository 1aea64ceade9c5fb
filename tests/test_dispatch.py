"""Kernel backend dispatcher: registry semantics, availability filtering,
autotune-cache round-trips, the deprecated use_pallas shim, and bit-parity
of the epilogue-fused pallas_reduced deposition backend against the
two-step (packed kernel + reduce_rhocell_separable) route.
"""

import dataclasses
import json
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.rhocell import reduce_rhocell_separable, reduce_rhocell_tail
from repro.core.shape_functions import max_guard, unified_support
from repro.kernels import dispatch
from repro.kernels.deposition.ops import (
    fused_bin_deposit,
    fused_bin_deposit_reduced,
    fused_bin_deposit_reduced_ref,
)

ORDERS = [1, 2, 3]
GRIDS = [(6, 5, 4), (3, 8, 5)]  # non-cubic, mutually non-divisible extents


@pytest.fixture(autouse=True)
def _isolated_cache(tmp_path, monkeypatch):
    """Every test gets its own autotune-cache file and a cold memo."""
    monkeypatch.setenv(dispatch.CACHE_ENV, str(tmp_path / "autotune.json"))
    dispatch.clear_memo()
    dispatch.reset_counters()
    yield
    dispatch.clear_memo()


def _slab(grid_shape, cap=5, seed=0):
    c = int(np.prod(grid_shape))
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    d = jax.random.uniform(k1, (c, cap, 3), maxval=0.999)
    val = jax.random.normal(k2, (c, cap, 3))
    return d, val


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------


def test_registry_lists_expected_ops_and_backends():
    assert set(dispatch.ops()) == {
        "deposit_fused", "gather_fused", "deposit_unfused", "bin_gather",
    }
    assert set(dispatch.backends_for("deposit_fused")) == {"xla", "pallas", "pallas_reduced"}
    assert set(dispatch.backends_for("gather_fused")) == {"xla", "pallas"}


def test_register_requires_override_to_replace():
    table = dispatch.backends_for("deposit_fused")
    existing = table["xla"]
    with pytest.raises(ValueError, match="already registered"):
        dispatch.register("deposit_fused", existing)
    # override=True replaces, then restore the original
    probe = dataclasses.replace(existing, priority=11)
    dispatch.register("deposit_fused", probe, override=True)
    try:
        assert dispatch.backends_for("deposit_fused")["xla"].priority == 11
    finally:
        dispatch.register("deposit_fused", existing, override=True)


def test_unknown_op_and_backend_raise():
    with pytest.raises(KeyError, match="unknown op"):
        dispatch.backends_for("nope")
    with pytest.raises(ValueError, match="unknown backend"):
        dispatch.resolve("deposit_fused", "nope", order=1, grid_shape=(4, 4, 4), capacity=4)


# ---------------------------------------------------------------------------
# is_available filtering
# ---------------------------------------------------------------------------


def test_forced_interpret_off_disables_pallas_backends():
    """With interpret forced off on a non-TPU platform the Pallas backends
    are unavailable: auto has one candidate (no benchmark), and forcing
    "pallas" falls back to the best available backend at or below its
    priority — xla."""
    if jax.default_backend() == "tpu":
        pytest.skip("on TPU the Pallas backends compile without the interpreter; "
                    "this test exercises the non-TPU forced-compiled fallback")
    kw = dict(order=2, grid_shape=(4, 4, 4), capacity=4, interpret=False)
    assert dispatch.resolve("deposit_fused", "auto", **kw) == "xla"
    assert dispatch.counters["benchmark"] == 0
    assert dispatch.resolve("deposit_fused", "pallas", **kw) == "xla"
    assert dispatch.resolve("deposit_fused", "pallas_reduced", **kw) == "xla"


def test_sharded_key_disables_pallas_backends():
    """pallas_call has no shard_map replication rule, so a sharded key has
    exactly one candidate — "xla" — and resolution (even "auto") never
    benchmarks; the fault ladder has nowhere to demote to."""
    kw = dict(order=1, grid_shape=(4, 4, 4), capacity=4, sharded=True)
    assert dispatch.resolve("deposit_fused", "auto", **kw) == "xla"
    assert dispatch.resolve("deposit_fused", "pallas_reduced", **kw) == "xla"
    assert dispatch.counters["benchmark"] == 0
    assert dispatch.demote("auto", **kw) is None


def test_dist_step_builder_bakes_sharded_backend():
    """The distributed step builders bake cfg.backend into a concrete
    shard-safe name at build time — "auto" (and a forced Pallas name)
    become "xla" before the shard body traces."""
    from repro.pic.distributed import DistConfig, resolve_sharded_backend
    from repro.pic.grid import GridSpec

    cfg = DistConfig(local_grid=GridSpec(shape=(4, 4, 4)), dt=0.1)
    assert cfg.backend == "auto"
    baked = resolve_sharded_backend(cfg)
    assert baked.backend == "xla"
    assert resolve_sharded_backend(
        dataclasses.replace(cfg, backend="pallas_reduced")
    ).backend == "xla"
    assert dispatch.counters["benchmark"] == 0


def test_forced_name_never_escalates():
    """Forcing a low-priority backend never resolves to a higher-priority
    one (the demotion ladder depends on this): "xla" stays "xla", and
    "pallas_reduced" on an op that lacks it falls to "pallas"."""
    kw = dict(order=1, grid_shape=(4, 4, 4), capacity=4)
    assert dispatch.resolve("deposit_fused", "xla", **kw) == "xla"
    assert dispatch.resolve("gather_fused", "pallas_reduced", **kw) == "pallas"


# ---------------------------------------------------------------------------
# autotune cache round-trip
# ---------------------------------------------------------------------------


def test_auto_benchmarks_once_then_hits_cache():
    kw = dict(order=1, grid_shape=(4, 4, 4), capacity=4)
    name = dispatch.resolve("deposit_fused", "auto", **kw)
    assert name in dispatch.backends_for("deposit_fused")
    assert dispatch.counters["benchmark"] == 1

    entries = json.load(open(dispatch.cache_path()))["entries"]
    [(key, entry)] = list(entries.items())
    assert entry["backend"] == name
    assert set(entry["timings_us"]) == {"xla", "pallas", "pallas_reduced"}

    # same process, cold memo: resolve from the file, no re-benchmark
    dispatch.clear_memo()
    assert dispatch.resolve("deposit_fused", "auto", **kw) == name
    assert dispatch.counters["benchmark"] == 1
    assert dispatch.counters["cache_hit"] == 1
    assert dispatch.counters["trace_fallback"] == 0
    # warm memo: no file read either
    hits = dispatch.counters["memo_hit"]
    assert dispatch.resolve("deposit_fused", "auto", **kw) == name
    assert dispatch.counters["memo_hit"] == hits + 1


def test_describe_reports_offered_and_measured_without_benchmarking():
    kw = dict(order=1, grid_shape=(4, 4, 4), capacity=4)
    before = dispatch.describe(["deposit_fused", "gather_fused"], **kw)
    assert before == {
        "deposit_fused": {"offered": ["pallas_reduced", "pallas", "xla"], "timings_us": None},
        "gather_fused": {"offered": ["pallas", "xla"], "timings_us": None},
    }
    assert dispatch.counters["benchmark"] == 0
    dispatch.prewarm(["deposit_fused"], **kw)
    after = dispatch.describe(["deposit_fused"], **kw)["deposit_fused"]
    entry = next(iter(json.load(open(dispatch.cache_path()))["entries"].values()))
    assert after["timings_us"] == entry["timings_us"]
    assert set(after["timings_us"]) == {"xla", "pallas", "pallas_reduced"}
    assert dispatch.counters["benchmark"] == 1


def test_cache_key_distinguishes_shapes():
    a = dict(order=1, grid_shape=(4, 4, 4), capacity=4)
    b = dict(order=2, grid_shape=(4, 4, 4), capacity=4)
    dispatch.resolve("deposit_fused", "auto", **a)
    dispatch.resolve("deposit_fused", "auto", **b)
    assert dispatch.counters["benchmark"] == 2
    assert len(json.load(open(dispatch.cache_path()))["entries"]) == 2


def test_corrupt_cache_falls_back_loudly():
    kw = dict(order=1, grid_shape=(4, 4, 4), capacity=4)
    dispatch.resolve("deposit_fused", "auto", **kw)
    with open(dispatch.cache_path(), "w") as f:
        f.write("{this is not json")
    dispatch.clear_memo()
    with pytest.warns(RuntimeWarning, match="corrupt"):
        name = dispatch.resolve("deposit_fused", "auto", **kw)
    assert name in dispatch.backends_for("deposit_fused")
    assert dispatch.counters["benchmark"] == 2  # re-benchmarked
    # and the file was rewritten into a loadable state
    dispatch.clear_memo()
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        dispatch.resolve("deposit_fused", "auto", **kw)
    assert dispatch.counters["cache_hit"] == 1


def test_wrong_version_cache_is_rejected():
    with open(dispatch.cache_path(), "w") as f:
        json.dump({"version": 999, "entries": {}}, f)
    dispatch.clear_memo()
    with pytest.warns(RuntimeWarning, match="corrupt"):
        dispatch.resolve("deposit_fused", "auto", order=1, grid_shape=(4, 4, 4), capacity=4)


# ---------------------------------------------------------------------------
# trace safety: never benchmark (or persist) under an ambient JAX trace
# ---------------------------------------------------------------------------


def test_auto_under_trace_never_benchmarks_or_persists():
    """Resolving "auto" inside a jitted body must NOT run the synthetic
    benchmark (the thunks would be staged, timing Python tracing instead of
    the device) and must NOT write the cache: it falls back to priority
    order with a warning, leaving the key free for a later eager resolve
    to measure for real."""
    import os

    kw = dict(order=1, grid_shape=(4, 4, 4), capacity=4)
    seen = {}

    @jax.jit
    def f(x):
        seen["name"] = dispatch.resolve("deposit_fused", "auto", **kw)
        return x + 1

    with pytest.warns(RuntimeWarning, match="under a JAX trace"):
        f(jnp.zeros(2))
    table = dispatch.backends_for("deposit_fused")
    best = max(table.values(), key=lambda b: b.priority).name
    assert seen["name"] == best  # priority-order fallback
    assert dispatch.counters["benchmark"] == 0
    assert dispatch.counters["trace_fallback"] == 1
    assert not os.path.exists(dispatch.cache_path())  # nothing persisted

    # the fallback is NOT memoized: the same key resolved eagerly now
    # benchmarks for real and persists the measured winner
    name = dispatch.resolve("deposit_fused", "auto", **kw)
    assert dispatch.counters["benchmark"] == 1
    assert name in table
    entries = json.load(open(dispatch.cache_path()))["entries"]
    assert all(us > 0 for us in next(iter(entries.values()))["timings_us"].values())


def test_eager_entry_point_resolves_before_tracing():
    """fused_deposit_grids(backend="auto") called eagerly resolves (and
    benchmarks) BEFORE its jitted impl traces — no trace fallback."""
    from repro.core.deposition import fused_deposit_grids

    d, val = _slab((4, 4, 4), cap=4)
    fused_deposit_grids(d, val, grid_shape=(4, 4, 4), order=1, backend="auto")
    assert dispatch.counters["benchmark"] == 1
    assert dispatch.counters["trace_fallback"] == 0


def test_simulation_setup_prewarms_auto_keys():
    """The sim driver resolves its "auto" keys eagerly at setup, so the
    traced step hits the memo — no trace fallback, and the winner was
    genuinely measured."""
    from repro.api import make_simulation, scenario

    spec = scenario("uniform", steps=2, grid=(4, 4, 4), ppc=1, order=1)
    sim = make_simulation(spec)
    assert sim.config.backend == "auto"
    assert dispatch.counters["benchmark"] == 2  # deposit_fused + gather_fused
    before = dispatch.counters["trace_fallback"]
    sim.run(2, window=2)
    assert dispatch.counters["trace_fallback"] == before
    assert dispatch.counters["benchmark"] == 2  # window resolved from memo


# ---------------------------------------------------------------------------
# batched keys (the ensemble engine's DispatchKey.batch axis)
# ---------------------------------------------------------------------------


def test_batched_key_never_reuses_batch1_entry():
    """A vmapped contraction has different arithmetic intensity than the
    single-sim one, so the batched winner must be measured at the batched
    shape: seeding the batch=1 cache entry must NOT satisfy a batch=4
    resolve (counter-checked), the two entries persist under distinct keys,
    and the batch=1 key keeps its pre-batch-axis spelling (old autotune
    caches stay valid)."""
    kw = dict(order=1, grid_shape=(4, 4, 4), capacity=4)
    dispatch.resolve("deposit_fused", "auto", **kw)
    assert dispatch.counters["benchmark"] == 1

    name4 = dispatch.resolve("deposit_fused", "auto", batch=4, **kw)
    assert name4 in dispatch.backends_for("deposit_fused")
    assert dispatch.counters["benchmark"] == 2, (
        "batch=4 reused the batch=1 measurement"
    )

    entries = json.load(open(dispatch.cache_path()))["entries"]
    assert len(entries) == 2
    assert sum("|batch4" in k for k in entries) == 1
    assert all("batch" not in k for k in entries if "|batch4" not in k)

    # warm: each key hits its OWN memo entry, no further benchmarking
    assert dispatch.resolve("deposit_fused", "auto", **kw) in dispatch.backends_for("deposit_fused")
    assert dispatch.resolve("deposit_fused", "auto", batch=4, **kw) == name4
    assert dispatch.counters["benchmark"] == 2


def test_prewarm_at_batched_shape():
    """prewarm(batch=N) (the ensemble driver's setup path) measures the
    batched keys eagerly so the vmapped window's traced resolves hit the
    memo — no trace fallback."""
    ops = dispatch.ops_for_modes("matrix", "matrix")
    kw = dict(order=1, grid_shape=(4, 4, 4), capacity=4, batch=3)
    dispatch.prewarm(ops, **kw)
    n_bench = dispatch.counters["benchmark"]
    assert n_bench == len(ops)
    for op in ops:
        dispatch.resolve(op, "auto", **kw)
    assert dispatch.counters["benchmark"] == n_bench  # all from memo
    assert dispatch.counters["trace_fallback"] == 0


# ---------------------------------------------------------------------------
# demotion ladder
# ---------------------------------------------------------------------------


def test_demote_never_benchmarks():
    """The fault supervisor's rung must not re-execute the suspect kernels
    mid-recovery: demoting an unmeasured "auto" answers from priority order
    without running the synthetic benchmark or writing the cache."""
    import os

    kw = dict(order=1, grid_shape=(4, 4, 4), capacity=4)
    nxt = dispatch.demote("auto", **kw)
    table = dispatch.backends_for("deposit_fused")
    best = max(table.values(), key=lambda b: b.priority).name
    if best == "xla":
        assert nxt is None
    else:
        assert dispatch.BACKEND_PRIORITY[nxt] < dispatch.BACKEND_PRIORITY[best]
    assert dispatch.counters["benchmark"] == 0
    assert not os.path.exists(dispatch.cache_path())


def test_demote_walks_priority_ladder():
    kw = dict(order=1, grid_shape=(4, 4, 4), capacity=4)
    assert dispatch.demote("pallas_reduced", **kw) == "pallas"
    assert dispatch.demote("pallas", **kw) == "xla"
    assert dispatch.demote("xla", **kw) is None
    # "auto" demotes from whatever it resolves to — always strictly down
    effective = dispatch.resolve("deposit_fused", "auto", **kw)
    nxt = dispatch.demote("auto", **kw)
    if effective == "xla":
        assert nxt is None
    else:
        assert dispatch.BACKEND_PRIORITY[nxt] < dispatch.BACKEND_PRIORITY[effective]


# ---------------------------------------------------------------------------
# deprecated use_pallas shim
# ---------------------------------------------------------------------------


def test_use_pallas_shim_maps_to_backend():
    from repro.api.spec import DepositionSpec

    with pytest.deprecated_call():
        d = DepositionSpec(use_pallas=True)
    assert d.backend == "pallas" and d.use_pallas is None
    with pytest.deprecated_call():
        d = DepositionSpec(use_pallas=False)
    assert d.backend == "xla" and d.use_pallas is None
    assert DepositionSpec().backend == "auto"


def test_spec_json_with_deprecated_use_pallas_still_loads():
    """Old spec JSON carrying "use_pallas" loads and maps onto backend;
    a normalized spec round-trips bit-exactly."""
    from repro.api import scenario
    from repro.api.spec import SimSpec

    base = scenario("uniform")
    old = json.loads(base.to_json())
    old["deposition"]["use_pallas"] = True
    old["deposition"].pop("backend")
    with pytest.deprecated_call():
        spec = SimSpec.from_dict(old)
    assert spec.deposition.backend == "pallas"
    assert spec.deposition.use_pallas is None
    s = spec.to_json()
    spec2 = SimSpec.from_json(s)
    assert spec2 == spec and spec2.to_json() == s


# ---------------------------------------------------------------------------
# pallas_reduced: parity with the two-step route
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("grid", GRIDS)
@pytest.mark.parametrize("order", ORDERS)
def test_reduced_kernel_bit_parity_with_two_step(grid, order):
    """deposit_fused_reduced (in-kernel z-reduction epilogue + shared
    reduce_rhocell_tail) must be BIT-identical to the two-step route
    (packed megakernel + reduce_rhocell_separable): same weights, same
    dots, same per-element accumulation order, and the off-support unified
    taps the two-step adds are exact zeros."""
    nx, ny, nz = grid
    g = max_guard(order)
    t, base = unified_support(order)
    d, val = _slab(grid, cap=5, seed=order)

    acc = fused_bin_deposit_reduced(d, val, order=order, grid_shape=grid, guard=g)
    one = [
        reduce_rhocell_tail(acc[:, c].reshape(nx, ny, nz + 2 * g, t, t), grid, (base, base), g)
        for c in range(3)
    ]
    packed = fused_bin_deposit(d, val, order=order)
    two = [
        reduce_rhocell_separable(packed[:, c].reshape(-1, t, t, t), grid, (base,) * 3, g)
        for c in range(3)
    ]
    for a, b in zip(one, two):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("grid", GRIDS)
@pytest.mark.parametrize("order", ORDERS)
def test_reduced_kernel_matches_oracle(grid, order):
    """Kernel vs the pure-jnp unified-window oracle (fp32 tolerance — the
    oracle evaluates weights on the unified window, which reorders a few
    fp32 roundings exactly like the packed megakernel's oracle does)."""
    g = max_guard(order)
    d, val = _slab(grid, cap=7, seed=10 + order)
    got = fused_bin_deposit_reduced(d, val, order=order, grid_shape=grid, guard=g)
    want = fused_bin_deposit_reduced_ref(d, val, order=order, grid_shape=grid, guard=g)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("order", ORDERS)
def test_backend_routes_agree_through_core(order):
    """fused_deposit_grids: the three backends agree (pallas routes bit-
    exactly, xla within fp32 tolerance) and "auto" equals whichever
    backend it resolved to."""
    from repro.core.deposition import fused_deposit_grids

    grid = (6, 5, 4)
    d, val = _slab(grid, cap=5, seed=20 + order)
    out = {
        b: fused_deposit_grids(d, val, grid_shape=grid, order=order, backend=b)
        for b in ("xla", "pallas", "pallas_reduced")
    }
    for a, b in zip(out["pallas"], out["pallas_reduced"]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(out["xla"], out["pallas"]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)

    auto = fused_deposit_grids(d, val, grid_shape=grid, order=order, backend="auto")
    winner = json.load(open(dispatch.cache_path()))["entries"]
    [(key, entry)] = [kv for kv in winner.items() if kv[0].startswith("deposit_fused")]
    for a, b in zip(auto, out[entry["backend"]]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
