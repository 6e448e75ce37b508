"""JAX planner backend — compiled Algorithm 1, bit-identical to numpy.

`plan_greedy_jax` is a transliteration of `vectorized.plan_greedy`
(default rank, no latency/score/tiebreak hooks) whose inner loops run
as the jitted chunk kernels in planner/kernels.py instead of a Python
loop over apps. The host side is byte-for-byte the numpy prologue —
ordering, ordered-sum δ and α-budget, per-app exclusion rows — so the
compiled path and the numpy path consume identical inputs; the device
side replays every comparison, argmax, and state update as the same
IEEE ops in the same order (see kernels.py for the contract). The
property tests in tests/test_planner.py assert assignment AND
objective bits match across random clusters, exclusions, dtypes, and
dirty-sync sequences.

Two pieces of persistent state make repeated rounds cheap:

  * `DeviceMirror` — device-resident (S, R) free / (S,) head / alive
    copies of a `PlannerState`, registered via
    `PlannerState.attach_mirror` so `sync()` forwards its dirty rows;
    a refresh scatters O(dirty) rows through the donated-buffer kernel
    instead of re-uploading the matrices.
  * `AppMatrixCache` — padded per-app variant-demand tensors, gathered
    per round by row index (apps are immutable, so rows never go
    stale).

Chunking (kernels.CHUNK_MAIN / CHUNK_TAIL) keeps the set of compiled
scan shapes at two per cluster signature: big proactive rounds compile
both, MTTR-critical failover rounds only ever hit the jit cache.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Set

import numpy as np

from repro.core.cluster import RESOURCES
from repro.core.planner.base import HeuristicResult, eq1_objective
from repro.core.planner.kernels import (build_kernels, build_scatter,
                                        chunk_sizes, have_jax)
from repro.core.planner.state import PlannerState, _ordered_sum
from repro.core.variants import Application

_EPS = 1e-9

# padded-variant floor: every app catalog in the repo is <= 8 variants,
# so V is almost always one compiled value; exclusion-row padding gets
# a floor of 8 so proactive rounds (1 primary row) and failover rounds
# (primary + site peers) share one compiled E
_V_MIN = 4
_E_MIN = 8


def _bucket(n: int, floor: int) -> int:
    b = floor
    while b < n:
        b *= 2
    return b


def _cmp_thresholds(dm: np.ndarray, dtype) -> np.ndarray:
    """Feasibility thresholds in the state dtype, exactly equivalent to
    numpy's f64 comparison.

    numpy decides `free >= d - eps` in f64 (f32 state rows promote
    losslessly). For an f32 x and real t, `x >= t` iff `x >= c` where
    c is the smallest f32 with c >= t — so rounding t = d - eps UP to
    the state dtype lets the kernel compare in pure f32, halving the
    (S, R) memory traffic of its hottest loop with zero behavior
    change. For f64 state the threshold is t itself."""
    t = dm - _EPS
    if np.dtype(dtype) == np.float64:
        return t
    c = t.astype(np.float32)
    low = c.astype(np.float64) < t
    return np.where(low, np.nextafter(c, np.float32(np.inf)),
                    c).astype(np.float32)


class DeviceMirror:
    """Device-resident mirror of a `PlannerState` (free/head/alive/cap).

    Attach once per state; `PlannerState.sync()` forwards dirty rows to
    `mark_dirty`, structural rebuilds call `invalidate`. `arrays()`
    returns current device buffers, pushing only the pending rows
    through the donated scatter kernel (bucket-padded index vector so
    the jit cache stays small)."""

    def __init__(self, state: PlannerState):
        self.state = state
        self._pending: set = set()
        self._bufs = None                  # (free, head, alive) on device
        self._cap = None
        self.full_uploads = 0
        self.rows_scattered = 0
        state.attach_mirror(self)

    def mark_dirty(self, rows) -> None:
        if self._bufs is not None:
            self._pending.update(int(r) for r in rows)

    def invalidate(self) -> None:
        self._bufs = None
        self._cap = None
        self._pending.clear()

    def _prewarm_scatter(self) -> None:
        """Compile the donated scatter for every index-bucket size up
        front (k = 16, 32, ... until >= S) with pad-only no-op calls:
        an MTTR-critical failover round must never pay an XLA compile
        inside the measured plan wall just because its dirty-row count
        landed in a bucket no earlier round had used."""
        import jax.numpy as jnp
        S = self.state.alive.size
        k = 16
        while True:
            idx = jnp.full((k,), S, jnp.int32)      # pad rows: no-op
            frows = jnp.zeros((k, len(RESOURCES)),
                              self._bufs[0].dtype)
            hrows = jnp.zeros((k,), self._bufs[1].dtype)
            arows = jnp.zeros((k,), bool)
            self._bufs = build_scatter()(*self._bufs, idx, frows,
                                         hrows, arows)
            if k >= S:
                break
            k *= 2

    def arrays(self):
        """(free, head, alive, cap) device arrays, synced to the state.
        Caller must hold the x64 scope and have called `state.sync()`."""
        import jax.numpy as jnp
        st = self.state
        if self._bufs is None:
            self._bufs = (jnp.asarray(st.free), jnp.asarray(st.head),
                          jnp.asarray(st.alive))
            self._cap = jnp.asarray(st.capacity)
            self._pending.clear()
            self.full_uploads += 1
            self._prewarm_scatter()
        elif self._pending:
            idx = np.fromiter(sorted(self._pending), np.int32,
                              len(self._pending))
            S = st.alive.size
            k = _bucket(idx.size, 16)
            pidx = np.full(k, S, np.int32)          # pad rows drop out
            pidx[:idx.size] = idx
            frows = np.zeros((k, len(RESOURCES)), st.free.dtype)
            hrows = np.zeros(k, st.head.dtype)
            arows = np.zeros(k, bool)
            frows[:idx.size] = st.free[idx]
            hrows[:idx.size] = st.head[idx]
            arows[:idx.size] = st.alive[idx]
            free, head, alive = build_scatter()(
                *self._bufs, jnp.asarray(pidx), jnp.asarray(frows),
                jnp.asarray(hrows), jnp.asarray(arows))
            self._bufs = (free, head, alive)
            self._pending.clear()
            self.rows_scattered += int(idx.size)
        return (*self._bufs, self._cap)


class AppMatrixCache:
    """Padded (V, R) demand tensors per app, gathered per round.

    Apps and their variant ladders are immutable, so a cached row never
    goes stale; the cache grows (and re-pads) only when an app with
    more variants than the current pad width appears."""

    def __init__(self):
        self.V = _V_MIN
        self._row: Dict[str, int] = {}
        self._dm = np.zeros((0, self.V, len(RESOURCES)), np.float64)
        self._vmask = np.zeros((0, self.V), bool)
        self._full = np.zeros((0, len(RESOURCES)), np.float64)

    def _grow_v(self, V: int) -> None:
        n = self._dm.shape[0]
        dm = np.full((n, V, len(RESOURCES)), np.inf, np.float64)
        dm[:, :self.V] = self._dm
        vm = np.zeros((n, V), bool)
        vm[:, :self.V] = self._vmask
        self._dm, self._vmask, self.V = dm, vm, V

    def rows(self, apps: List[Application]) -> np.ndarray:
        """Row indices for `apps`, adding unseen apps to the cache."""
        new = [a for a in apps if a.id not in self._row]
        if new:
            maxv = max(len(a.variants) for a in new)
            if maxv > self.V:
                self._grow_v(_bucket(maxv, _V_MIN))
            n0 = self._dm.shape[0]
            dm = np.full((len(new), self.V, len(RESOURCES)), np.inf,
                         np.float64)
            vm = np.zeros((len(new), self.V), bool)
            fd = np.zeros((len(new), len(RESOURCES)), np.float64)
            for i, a in enumerate(new):
                m = a.demand_matrix()
                dm[i, :m.shape[0]] = m
                vm[i, :m.shape[0]] = True
                fd[i] = a.full.demand_vec
                self._row[a.id] = n0 + i
            self._dm = np.concatenate([self._dm, dm])
            self._vmask = np.concatenate([self._vmask, vm])
            self._full = np.concatenate([self._full, fd])
        return np.array([self._row[a.id] for a in apps], np.int64)

    def gather(self, rows: np.ndarray):
        return self._dm[rows], self._vmask[rows], self._full[rows]


class JaxPlanContext:
    """Per-planner-instance persistent caches: one `DeviceMirror` per
    `PlannerState` identity plus the shared `AppMatrixCache`."""

    def __init__(self):
        self.apps = AppMatrixCache()
        self._mirrors: Dict[int, DeviceMirror] = {}

    def mirror(self, state: PlannerState) -> DeviceMirror:
        m = self._mirrors.get(id(state))
        if m is None or m.state is not state:
            m = DeviceMirror(state)
            self._mirrors[id(state)] = m
        return m


def plan_greedy_jax(apps: List[Application], cluster=None, *,
                    state: Optional[PlannerState] = None,
                    exclude: Optional[Dict[str, Set[str]]] = None,
                    site_exclude: Optional[Dict[str, Set[str]]] = None,
                    alpha: float = 0.0,
                    ctx: Optional[JaxPlanContext] = None,
                    ) -> HeuristicResult:
    """Compiled Algorithm 1 — same contract (and same bits) as
    `vectorized.plan_greedy` with the default worst-fit rank.

    Unsupported hooks (latency_fn / score_fn / tiebreak_fn /
    site_index) are the caller's responsibility: the planner policies
    route such requests to the numpy path."""
    assert have_jax(), "jax backend requested but jax is not importable"
    import jax

    t0 = time.time()
    exclude = exclude or {}
    site_exclude = site_exclude or {}
    if state is None:
        assert cluster is not None, "need a cluster or a PlannerState"
        state = PlannerState(cluster, subscribe=False)
    if cluster is None:
        cluster = state.cluster
    if ctx is None:
        ctx = JaxPlanContext()
    state.sync()

    order = sorted(apps, key=lambda a: (not a.critical, -a.request_rate))
    rows = state.alive_rows()
    if not apps or rows.size == 0:
        assignment: Dict[str, tuple] = {}
        return HeuristicResult(assignment, [a.id for a in order],
                               time.time() - t0,
                               eq1_objective(assignment, apps))

    S = int(state.alive.size)                    # full rows; dead masked
    R = len(RESOURCES)

    # host prologue — the numpy path's exact code over the gathered
    # alive rows: ordered sums seed δ and the α-budget bit-identically
    arows = ctx.apps.rows(order)
    dm_all, vmask_all, full_order = ctx.apps.gather(arows)
    gfree = state.free[rows]
    C = [_ordered_sum(gfree[:, j]) for j in range(R)]
    # δ's demand total is accumulated in `apps` order (not placement
    # order), matching plan_greedy's full_dem construction
    full_apps = np.array([a.full.demand_vec for a in apps],
                         dtype=np.float64).reshape(len(apps), R)
    D = [_ordered_sum(full_apps[:, j]) for j in range(R)]
    delta = min((C[j] / D[j]) if D[j] > 0 else 1.0 for j in range(R))
    budget0 = np.array([(1.0 - alpha) * C[j] for j in range(R)],
                       dtype=np.float64)

    if delta >= 1.0:
        thr_all = np.full((len(order), R), np.inf, np.float64)
    else:
        thr_all = delta * full_order + _EPS

    # sparse per-app exclusion rows as GLOBAL row indices (the kernel
    # masks the full alive vector, so dead rows are harmless to list)
    excl_lists: List[List[int]] = []
    for app in order:
        er: List[int] = []
        for sid in exclude.get(app.id, ()):
            if sid:
                i = state.sidx.get(sid)
                if i is not None:
                    er.append(i)
        for site in site_exclude.get(app.id, ()):
            for sid in cluster.sites.get(site, ()):
                i = state.sidx.get(sid)
                if i is not None:
                    er.append(i)
        excl_lists.append(er)
    E = _bucket(max((len(e) for e in excl_lists), default=0), _E_MIN)
    excl_all = np.full((len(order), E), S, np.int32)     # pad drops out
    for i, er in enumerate(excl_lists):
        if er:
            u = sorted(set(er))
            excl_all[i, :len(u)] = u

    dmc_all = _cmp_thresholds(dm_all, state.dtype)

    with jax.enable_x64(True):
        import jax.numpy as jnp
        kern = build_kernels(S, R, ctx.apps.V, E, str(state.dtype))
        free, head, alive, cap = ctx.mirror(state).arrays()
        budget = jnp.asarray(budget0)

        chunks = chunk_sizes(len(order))
        dev_chunks = []                    # (dm, vmask) kept for upgrade
        j_parts, k_parts = [], []
        off = 0
        for n in chunks:
            lo, hi = off, off + n
            na = min(hi, len(order)) - lo              # active rows
            dm = np.full((n, ctx.apps.V, R), np.inf, np.float64)
            dc = np.full((n, ctx.apps.V, R), np.inf, state.dtype)
            vm = np.zeros((n, ctx.apps.V), bool)
            th = np.full((n, R), np.inf, np.float64)
            ex = np.full((n, E), S, np.int32)
            ac = np.zeros(n, bool)
            dm[:na] = dm_all[lo:lo + na]
            dc[:na] = dmc_all[lo:lo + na]
            vm[:na] = vmask_all[lo:lo + na]
            th[:na] = thr_all[lo:lo + na]
            ex[:na] = excl_all[lo:lo + na]
            ac[:na] = True
            dmj, vmj = jnp.asarray(dm), jnp.asarray(vm)
            free, head, budget, j, k = kern["place_chunk"](
                free, head, budget, alive, cap, dmj, jnp.asarray(dc),
                vmj, jnp.asarray(th), jnp.asarray(ex), jnp.asarray(ac))
            dev_chunks.append((dmj, vmj))
            j_parts.append(j)
            k_parts.append(k)
            off = hi

        # upgrade pass over the SAME order once every app is placed —
        # matching the numpy path's two sequential sweeps
        up_parts = []
        for (dmj, vmj), j, k in zip(dev_chunks, j_parts, k_parts):
            free, head, budget, j_up = kern["upgrade_chunk"](
                free, head, budget, cap, dmj, vmj, j, k)
            up_parts.append(j_up)

        A = len(order)
        jj = np.concatenate([np.asarray(p) for p in j_parts])[:A]
        kk = np.concatenate([np.asarray(p) for p in k_parts])[:A]
        ju = np.concatenate([np.asarray(p) for p in up_parts])[:A]

    assignment = {}
    unplaced: List[str] = []
    for i, app in enumerate(order):
        k = int(kk[i])
        if k < 0:
            unplaced.append(app.id)
            continue
        j = int(ju[i]) if int(ju[i]) >= 0 else int(jj[i])
        assignment[app.id] = (app.variants[j], state.server_ids[k])

    return HeuristicResult(assignment, unplaced, time.time() - t0,
                           eq1_objective(assignment, apps))


__all__ = ["AppMatrixCache", "DeviceMirror", "JaxPlanContext",
           "plan_greedy_jax"]
