"""Planner subsystem: vectorized-vs-legacy parity, array-state
incremental sync, policy registry, and controller integration.

The parity test is the load-bearing one: the vectorized Algorithm 1
(planner/vectorized.py) must reproduce the legacy loop implementation
(planner/legacy.py) EXACTLY — same assignments, same unplaced list,
bit-identical Eq. 1 objective — across seeded random clusters,
exclusions, α values, and latency SLOs."""

import math
import random

import numpy as np
import pytest

from repro.core.cluster import Cluster, RESOURCES, Server, make_cluster
from repro.core.planner import (PlanRequest, PlannerState,
                                available_planners, eq1_objective,
                                faillite_heuristic,
                                faillite_heuristic_legacy, get_planner)
from repro.core.variants import Application, synthetic_family


def _rand_cluster(rng: random.Random) -> Cluster:
    """Heterogeneous cluster: 1-3 sites, uneven per-server capacity."""
    servers = []
    n_sites = rng.randint(1, 3)
    for si in range(n_sites):
        for sj in range(rng.randint(2, 5)):
            servers.append(Server(
                id=f"s{si}-{sj}", site=f"site{si}",
                capacity={"mem": rng.uniform(6e9, 24e9),
                          "compute": rng.uniform(0.5, 2.0)}))
    return Cluster(servers)


def _rand_apps(rng: random.Random, n: int):
    out = []
    for i in range(n):
        lad = synthetic_family(f"f{i}", rng.uniform(0.3e9, 6e9),
                               n_variants=rng.randint(2, 6),
                               spread=rng.uniform(1.5, 12.0))
        out.append(Application(
            id=f"a{i}", family=f"f{i}", variants=lad,
            request_rate=rng.uniform(0.2, 3.0),
            latency_slo=(rng.uniform(0.005, 0.05)
                         if rng.random() < 0.5 else math.inf),
            critical=rng.random() < 0.5))
    return out


def _lat_fn(app, variant, server):
    """Deterministic synthetic latency: per-server distance + size term."""
    return (0.002 * (sum(map(ord, server.id)) % 7)
            + variant.mem_bytes / 1e12 + 0.001)


def _norm(res):
    return ({k: (v.name, s) for k, (v, s) in res.assignment.items()},
            list(res.unplaced))


@pytest.mark.parametrize("seed", range(20))
def test_vectorized_matches_legacy(seed):
    """Seeded property test: identical assignments AND identical Eq. 1
    objective bits across random instances (tentpole acceptance)."""
    rng = random.Random(seed * 1009 + 7)
    cluster = _rand_cluster(rng)
    apps = _rand_apps(rng, rng.randint(1, 25))
    sids = list(cluster.servers)
    exclude = {a.id: {rng.choice(sids)} for a in apps
               if rng.random() < 0.7}
    site_exclude = {a.id: {f"site{rng.randrange(3)}"} for a in apps
                    if rng.random() < 0.3}
    alpha = rng.choice([0.0, 0.1, 0.25, 0.5])
    latency_fn = _lat_fn if rng.random() < 0.5 else None
    # make some instances capacity-starved: pre-place primaries
    for a in apps[::3]:
        sid = rng.choice(sids)
        if cluster.servers[sid].fits(a.variants[-1].demand):
            cluster.place(a.id, a.variants[-1], sid, "primary")

    old = faillite_heuristic_legacy(apps, cluster, exclude=exclude,
                                    site_exclude=site_exclude,
                                    alpha=alpha, latency_fn=latency_fn)
    new = faillite_heuristic(apps, cluster, exclude=exclude,
                             site_exclude=site_exclude,
                             alpha=alpha, latency_fn=latency_fn)
    assert _norm(old) == _norm(new)
    assert old.objective == new.objective      # bit-identical


def test_parity_with_dead_servers_and_empty_edge_cases():
    rng = random.Random(42)
    cluster = _rand_cluster(rng)
    apps = _rand_apps(rng, 8)
    for sid in list(cluster.servers)[::2]:
        cluster.fail_server(sid)
    old = faillite_heuristic_legacy(apps, cluster, alpha=0.1)
    new = faillite_heuristic(apps, cluster, alpha=0.1)
    assert _norm(old) == _norm(new)
    assert old.objective == new.objective
    # no apps
    assert _norm(faillite_heuristic([], cluster)) == ({}, [])
    # no alive servers
    for sid in cluster.servers:
        cluster.fail_server(sid)
    res = faillite_heuristic(apps, cluster)
    ref = faillite_heuristic_legacy(apps, cluster)
    assert _norm(res) == _norm(ref)
    assert res.assignment == {}


def test_objective_is_eq1():
    """Satellite: heuristic reports Σ accuracy·rate (Eq. 1), not raw
    accuracy, so ILP and heuristic compare like with like."""
    rng = random.Random(0)
    cluster = make_cluster(1, 4, mem=32e9)
    apps = _rand_apps(rng, 5)
    res = faillite_heuristic(apps, cluster)
    rate = {a.id: a.request_rate for a in apps}
    want = sum(v.accuracy * rate[aid] for aid, (v, _) in
               res.assignment.items())
    assert res.objective == pytest.approx(want, abs=1e-12)
    assert res.objective == eq1_objective(res.assignment, apps)


# ---------------------------------------------------------------------------
# PlannerState incremental sync
# ---------------------------------------------------------------------------

def _fresh(cluster):
    st = PlannerState(cluster, subscribe=False)
    st.sync()
    return st


def test_state_incremental_matches_rebuild():
    """Place / fail / revive / remove feed per-server deltas; the synced
    persistent state must equal a from-scratch rebuild exactly."""
    rng = random.Random(1)
    cluster = make_cluster(2, 3, mem=16e9)
    state = PlannerState(cluster)          # subscribes to cluster
    state.sync()
    apps = _rand_apps(rng, 6)
    keys = {}
    for i, a in enumerate(apps):
        sid = list(cluster.servers)[i % 6]
        keys[a.id] = cluster.place(a.id, a.full, sid, "primary")
    assert state.n_dirty > 0               # deltas were observed
    state.sync()
    ref = _fresh(cluster)
    assert np.array_equal(state.free, ref.free)
    assert np.array_equal(state.alive, ref.alive)

    cluster.fail_server("s0-0")
    cluster.remove(keys[apps[1].id], list(cluster.servers)[1])
    cluster.revive_server("s0-0")          # returns empty
    cluster.remove_app(apps[2].id)
    state.sync()
    ref = _fresh(cluster)
    assert np.array_equal(state.free, ref.free)
    assert np.array_equal(state.alive, ref.alive)
    # dirty set is now empty: a no-op sync touches nothing
    assert state.sync() == 0


def test_state_worst_fit_matches_legacy_freeview():
    from repro.core.planner.legacy import _FreeView, worst_fit
    rng = random.Random(5)
    for _ in range(10):
        cluster = _rand_cluster(rng)
        if rng.random() < 0.5:
            cluster.fail_server(rng.choice(list(cluster.servers)))
        state = PlannerState(cluster)
        demand = {"mem": rng.uniform(1e9, 20e9),
                  "compute": rng.uniform(0.1, 1.5)}
        excl = ({rng.choice(list(cluster.servers))}
                if rng.random() < 0.5 else set())
        view = _FreeView(cluster.alive_servers())
        assert (state.worst_fit(demand, excl)
                == worst_fit(view, demand, excl))


# ---------------------------------------------------------------------------
# registry + controller integration
# ---------------------------------------------------------------------------

def test_registry_contents_and_errors():
    names = available_planners()
    for want in ("greedy", "ilp", "legacy-greedy", "load-aware"):
        assert want in names
    with pytest.raises(KeyError, match="unknown planner"):
        get_planner("no-such-policy")
    assert get_planner("ilp").realtime is False
    assert get_planner("greedy").realtime is True


def test_load_aware_is_feasible_and_placed():
    rng = random.Random(9)
    cluster = make_cluster(2, 4, mem=24e9)
    apps = _rand_apps(rng, 10)
    res = get_planner("load-aware").plan(
        PlanRequest(apps=apps, cluster=cluster, alpha=0.1))
    used = {s.id: {r: 0.0 for r in RESOURCES} for s in cluster.servers.values()}
    for aid, (v, sid) in res.assignment.items():
        for r in RESOURCES:
            used[sid][r] += v.demand[r]
    for s in cluster.alive_servers():
        for r in RESOURCES:
            assert used[s.id][r] <= s.free(r) + 1e-6
    assert set(res.assignment) | set(res.unplaced) == {a.id for a in apps}


@pytest.mark.parametrize("name", ["greedy", "load-aware", "legacy-greedy"])
def test_controller_runs_with_any_registered_planner(name):
    """Acceptance: FailLiteController selects planners by name without
    importing planner internals."""
    from repro.core.simulation import SimConfig, Simulation
    cfg = SimConfig(n_sites=2, servers_per_site=3, server_mem=24e9,
                    planner=name, traffic_rate_scale=0.0, seed=3)
    sim = Simulation(cfg).setup()
    assert sim.controller.planner.name == name
    victim = sim.controller.primaries[next(iter(sim.controller.apps))]
    res = sim.inject_failure(servers=[victim], run_for=30.0)
    assert res.n_affected > 0
    assert res.recovery_rate > 0.0
    assert sim.controller.plan_wall_s > 0.0


def test_controller_has_no_private_freeview_dependency():
    """Satellite: the underscore import is gone for good."""
    import inspect
    import repro.core.controller as ctl
    src = inspect.getsource(ctl)
    assert "_FreeView" not in src
    assert "from repro.core.heuristic import" not in src


def test_ilp_planner_via_registry_dominates_greedy():
    rng = random.Random(11)
    cluster = make_cluster(2, 3, mem=8e9)
    apps = _rand_apps(rng, 6)
    primaries = {}
    for i, a in enumerate(apps):
        sid = cluster.alive_servers()[i % 6].id
        cluster.place(a.id, a.variants[-1], sid, "primary")
        primaries[a.id] = sid
    req = PlanRequest(apps=apps, cluster=cluster, primaries=primaries,
                      alpha=0.1)
    ilp = get_planner("ilp").plan(req)
    greedy = get_planner("greedy").plan(req)
    assert ilp.objective >= greedy.objective - 1e-6
    for aid, (v, sid) in ilp.assignment.items():
        assert sid != primaries[aid]


# ---------------------------------------------------------------------------
# jax planner backend: bit-identical compiled path
# ---------------------------------------------------------------------------

try:                                       # dev extra — shim to seeded
    from hypothesis import given, settings  # sweeps when not installed
    from hypothesis import strategies as hst
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


def _fixed_cluster(rng: random.Random) -> Cluster:
    """Random capacities on a FIXED 2x3 shape: the jax kernels compile
    per (S, R, V, E, dtype) signature, so the property sweep keeps S
    pinned and varies everything else."""
    servers = []
    for si in range(2):
        for sj in range(3):
            servers.append(Server(
                id=f"s{si}-{sj}", site=f"site{si}",
                capacity={"mem": rng.uniform(6e9, 24e9),
                          "compute": rng.uniform(0.5, 2.0)}))
    return Cluster(servers)


def _jax_apps(rng: random.Random, n: int):
    """Like _rand_apps but <= 4 variants so the V bucket stays at 4."""
    out = []
    for i in range(n):
        lad = synthetic_family(f"f{i}", rng.uniform(0.3e9, 6e9),
                               n_variants=rng.randint(2, 4),
                               spread=rng.uniform(1.5, 12.0))
        out.append(Application(
            id=f"a{i}", family=f"f{i}", variants=lad,
            request_rate=rng.uniform(0.2, 3.0),
            critical=rng.random() < 0.5))
    return out


def _check_jax_parity(seed: int, dtype: str) -> None:
    from repro.core.planner.jax_backend import (JaxPlanContext,
                                                plan_greedy_jax)
    from repro.core.planner.vectorized import plan_greedy

    rng = random.Random(seed)
    cluster = _fixed_cluster(rng)
    apps = _jax_apps(rng, rng.randint(1, 20))
    sids = list(cluster.servers)
    exclude = {a.id: {rng.choice(sids)} for a in apps
               if rng.random() < 0.6}
    site_exclude = {a.id: {f"site{rng.randrange(3)}"} for a in apps
                    if rng.random() < 0.3}
    alpha = rng.choice([0.0, 0.1, 0.4])
    if rng.random() < 0.3:
        cluster.fail_server(rng.choice(sids))
    for a in apps[::4]:
        sid = rng.choice(sids)
        if cluster.servers[sid].fits(a.variants[-1].demand):
            cluster.place(a.id, a.variants[-1], sid, "primary")

    st_np = PlannerState(cluster, subscribe=False, dtype=dtype)
    st_jx = PlannerState(cluster, subscribe=False, dtype=dtype)
    r_np = plan_greedy(apps, cluster, state=st_np, exclude=exclude,
                       site_exclude=site_exclude, alpha=alpha)
    r_jx = plan_greedy_jax(apps, cluster, state=st_jx, exclude=exclude,
                           site_exclude=site_exclude, alpha=alpha,
                           ctx=JaxPlanContext())
    assert _norm(r_np) == _norm(r_jx)
    assert list(r_np.assignment) == list(r_jx.assignment)
    assert r_np.objective == r_jx.objective          # bit-identical


@pytest.mark.slow
@pytest.mark.parametrize("dtype", ["float64", "float32"])
def test_jax_backend_matches_numpy_random_instances(dtype):
    """Tentpole acceptance: the compiled planner is bit-identical to
    the numpy path across random clusters, exclusions, alphas, dead
    servers, and capacity-starved instances — property-style via
    hypothesis when installed, a seeded sweep otherwise."""
    pytest.importorskip("jax")
    if HAVE_HYPOTHESIS:
        @settings(max_examples=20, deadline=None)
        @given(hst.integers(min_value=0, max_value=2**31 - 1))
        def check(seed):
            _check_jax_parity(seed, dtype)
        check()
    else:
        for seed in range(10):
            _check_jax_parity(seed * 7919 + 13, dtype)


@pytest.mark.slow
def test_jax_dirty_row_sync_sequence_matches_numpy():
    """Incremental rounds: two identically mutated clusters, one
    planned by numpy and one by jax with a persistent DeviceMirror —
    every round must stay bit-identical, and the mirror must move
    dirty rows through the donated scatter, not full re-uploads."""
    pytest.importorskip("jax")
    from repro.core.planner.jax_backend import (JaxPlanContext,
                                                plan_greedy_jax)
    from repro.core.planner.vectorized import plan_greedy

    cl_np = _fixed_cluster(random.Random(5))
    cl_jx = _fixed_cluster(random.Random(5))
    apps = _jax_apps(random.Random(6), 12)
    st_np = PlannerState(cl_np, dtype="float32")
    st_jx = PlannerState(cl_jx, dtype="float32")
    ctx = JaxPlanContext()
    mirror = ctx.mirror(st_jx)
    mut = random.Random(7)
    downed = []
    for rnd in range(5):
        subset = [a for a in apps if mut.random() < 0.7] or apps[:1]
        r_np = plan_greedy(subset, cl_np, state=st_np, alpha=0.1)
        r_jx = plan_greedy_jax(subset, cl_jx, state=st_jx, alpha=0.1,
                               ctx=ctx)
        assert _norm(r_np) == _norm(r_jx)
        assert r_np.objective == r_jx.objective
        for aid, (v, sid) in list(r_np.assignment.items())[:3]:
            cl_np.place(f"{aid}-r{rnd}", v, sid, "backup")
            cl_jx.place(f"{aid}-r{rnd}", v, sid, "backup")
        if downed and rnd % 2:
            sid = downed.pop()
            cl_np.revive_server(sid)
            cl_jx.revive_server(sid)
        else:
            alive = [s.id for s in cl_np.alive_servers()]
            if len(alive) > 2:
                sid = mut.choice(alive)
                cl_np.fail_server(sid)
                cl_jx.fail_server(sid)
                downed.append(sid)
    assert mirror.full_uploads == 1
    assert mirror.rows_scattered > 0


def test_masked_argmax_jnp_matches_ref():
    """The jnp reduction (max + first-index min over iota) must keep
    numpy's first-maximum tie rule, including heavy ties and the
    empty mask."""
    pytest.importorskip("jax")
    import jax.numpy as jnp
    from repro.kernels.planner_argmax.ops import masked_argmax
    from repro.kernels.planner_argmax.ref import masked_argmax_ref

    rng = np.random.default_rng(3)
    for n in (1, 7, 512, 1000):
        for _ in range(5):
            vals = rng.standard_normal(n).astype(np.float32)
            for mask in (rng.random(n) < 0.5,
                         np.zeros(n, bool), np.ones(n, bool)):
                for v in (vals, np.round(vals)):     # round -> ties
                    wi, wv = masked_argmax_ref(v, mask)
                    gi, gv = masked_argmax(jnp.asarray(v),
                                           jnp.asarray(mask))
                    assert (int(gi), float(gv)) == (int(wi), float(wv))


@pytest.mark.slow
def test_masked_argmax_pallas_interpret_matches_ref():
    """The Pallas tiled kernel, run in interpret mode on CPU, is
    bit-identical to the numpy ref — ties, empty mask, non-multiple
    -of-block lengths."""
    pytest.importorskip("jax")
    import jax.numpy as jnp
    from repro.kernels.planner_argmax.ops import masked_argmax
    from repro.kernels.planner_argmax.ref import masked_argmax_ref

    rng = np.random.default_rng(9)
    for n in (128, 300, 512):
        vals = np.round(rng.standard_normal(n)).astype(np.float32)
        for mask in (rng.random(n) < 0.5, np.zeros(n, bool),
                     np.ones(n, bool)):
            wi, wv = masked_argmax_ref(vals, mask)
            gi, gv = masked_argmax(jnp.asarray(vals),
                                   jnp.asarray(mask),
                                   impl="pallas", block=128,
                                   interpret=True)
            assert (int(gi), float(gv)) == (int(wi), float(wv))


def test_ilp_branch_frac_pinned_to_float64():
    """Satellite regression: branching-variable selection must compare
    fractionalities in float64 — two LP values 1e-8 apart tie in
    float32 (argmax falls back to index 0) but have a strict winner in
    float64."""
    from repro.core.planner.ilp import _branch_frac

    x = np.array([0.50000002, 0.50000001])
    f = _branch_frac(x)
    assert f.dtype == np.float64
    assert int(np.argmax(f)) == 1
    # the float32 computation this pins away: both round to 0.5, the
    # fracs tie at 0.5, and argmax flips to index 0
    f32 = np.abs(x.astype(np.float32) - np.round(x.astype(np.float32)))
    assert int(np.argmax(f32)) == 0
    assert _branch_frac(x.astype(np.float32)).dtype == np.float64


def test_sharded_dense_fallback_warns_once_and_counts(caplog):
    """Satellite: a latency_fn request on the sharded planner falls
    back to the dense path — logged ONCE per planner instance, counted
    per round in stats["fallback_dense"]."""
    import logging

    rng = random.Random(21)
    cluster = _rand_cluster(rng)
    apps = _rand_apps(rng, 8)
    planner = get_planner("sharded")
    req = PlanRequest(apps=apps, cluster=cluster, alpha=0.1,
                      latency_fn=_lat_fn)
    with caplog.at_level(logging.WARNING, "repro.planner.sharded"):
        r1 = planner.plan(req)
        planner.plan(req)
    assert planner.stats["fallback_dense"] == 2
    warns = [r for r in caplog.records
             if "dense" in r.getMessage().lower()]
    assert len(warns) == 1                  # log-once, counted twice
    dense = get_planner("greedy").plan(req)
    assert _norm(r1) == _norm(dense)


@pytest.mark.parametrize("coordinators", [2, 3])
def test_multi_coordinator_sharded_matches_single(coordinators):
    """Tentpole: row-group coordinators planning concurrently must
    reproduce the single-coordinator sharded selection exactly (the
    deterministic ceiling-ordered merge)."""
    for seed in range(6):
        rng = random.Random(seed * 131 + 17)
        cluster = _rand_cluster(rng)
        apps = _rand_apps(rng, rng.randint(4, 18))
        req = PlanRequest(apps=apps, cluster=cluster, alpha=0.1)
        base = get_planner("sharded").plan(req)
        multi = get_planner("sharded", coordinators=coordinators)
        got = multi.plan(req)
        assert multi.stats["coordinators"] == coordinators
        assert _norm(base) == _norm(got)
        assert base.objective == got.objective


def test_planner_backend_registry_and_validation():
    from repro.core.planner import have_jax

    assert get_planner("greedy", backend="numpy").stats["backend"] \
        == "numpy"
    with pytest.raises(ValueError, match="unknown planner backend"):
        get_planner("greedy", backend="tpu")
    if have_jax():
        assert get_planner("sharded", backend="jax").stats["backend"] \
            == "jax"
    else:
        with pytest.raises(RuntimeError, match="requires jax"):
            get_planner("greedy", backend="jax")


def test_jax_backend_refuses_float64_on_tpu(monkeypatch):
    """On a TPU the jax backend runs the Pallas argmax, which has no
    64-bit types: float64 state fails at construction, float32 passes."""
    jax = pytest.importorskip("jax")
    from repro.core.planner import resolve_backend

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="no 64-bit types"):
        resolve_backend("jax", "float64")
    assert resolve_backend("jax", "float32") == "jax"
    assert resolve_backend("numpy", "float64") == "numpy"


@pytest.mark.slow
def test_simulation_jax_backend_matches_numpy():
    """End-to-end: the same failure scenario under planner_backend
    "jax" and "numpy" recovers identically, and the run surfaces the
    backend + round counters through planner_stats."""
    pytest.importorskip("jax")
    from repro.core.simulation import SimConfig, Simulation

    def run(backend):
        cfg = SimConfig(n_sites=2, servers_per_site=3, server_mem=24e9,
                        planner="greedy", planner_backend=backend,
                        traffic_rate_scale=0.0, seed=11)
        sim = Simulation(cfg).setup()
        victim = sim.controller.primaries[
            next(iter(sim.controller.apps))]
        res = sim.inject_failure(servers=[victim], run_for=30.0)
        return sim, res

    sim_np, res_np = run("numpy")
    sim_jx, res_jx = run("jax")
    assert res_np.recovery_rate == res_jx.recovery_rate
    assert res_np.n_affected == res_jx.n_affected
    stats = sim_jx.controller.planner_stats()
    assert stats["backend"] == "jax"
    assert stats["jax_rounds"] > 0
    assert sim_np.controller.planner_stats()["backend"] == "numpy"
