"""Testbed shard plane: REAL tensor-parallel groups on worker threads.

`core/shardgroup.py` gives the control plane (group lifecycle, the
degrade/reshard/monolith ladder, recovery records); this module is the
mini-testbed's data plane for it. Nothing here is modeled:

* at deploy, the app's full param tree is built once and **partitioned
  along the `parallel/sharding.py` "model" axes** (heads / d_ff /
  vocab — the production TP rules) into `tp_degree` rank slices, each
  hosted in a different `WorkerServer`'s memory (`host_shard`; a
  `kill()` loses the slice, the cold store does not have it);
* the serving engine is assembled by gathering the slices off the
  member workers (`jnp.concatenate` per model axis — the all-gather)
  and compiled on the rank-0 lead;
* a shard-host kill breaks the group: the ladder's real costs are paid
  on the wall clock — degraded-TP continuation rebuilds an engine from
  the surviving slices with the lost partition zero-filled (KevlarFlow:
  fewer effective heads/channels, measurably degraded output), and a
  reshard re-materializes the lost slice from the deterministic
  checkpoint seed, pays the slice-byte fetch through the model-state
  plane, then re-gathers and recompiles;
* every measured wall time is folded back into the sim's reshard cost
  model through `ShardGroupManager.calibrate_repartition`, and the raw
  measurements ride out through ``extras["shard"]["measured"]``.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from repro.core.shardgroup import ShardGroup, ShardGroupManager, slice_name
from repro.core.variants import Application, Variant
from repro.parallel.sharding import param_specs
from repro.serving.engine import InferenceEngine
from repro.serving.server import checkpoint_params

# ---------------------------------------------------------------------------
# param-tree partitioning along the production TP ("model") axes
# ---------------------------------------------------------------------------


def _walk2(a, b, fn):
    """Parallel structural walk: `b` mirrors `a`'s dict/list nesting
    (PartitionSpecs are tuples but sit at `a`'s leaf positions, so
    dispatch on `a` only)."""
    if isinstance(a, dict):
        return {k: _walk2(a[k], b[k], fn) for k in a}
    if isinstance(a, (list, tuple)):
        return type(a)([_walk2(x, y, fn) for x, y in zip(a, b)])
    return fn(a, b)


def _model_axis(spec, shape, k: int) -> Optional[int]:
    """The axis this leaf is TP-split on, or None (replicated)."""
    for i, entry in enumerate(spec):
        axes = entry if isinstance(entry, tuple) else (entry,)
        if "model" in axes and i < len(shape) and shape[i] >= k:
            return i
    return None


def split_axes(params, k: int):
    """Tree of split-axis indices (None = replicated), derived from the
    same `param_specs` rules the production mesh uses."""
    specs = param_specs(params)
    return _walk2(params, specs,
                  lambda leaf, spec: _model_axis(spec, leaf.shape, k))


def rank_slice(params, axes, k: int, rank: int):
    """Rank `rank`'s slice of the full tree (host numpy — this is what
    one worker's memory holds)."""
    def cut(leaf, ax):
        a = np.asarray(leaf)
        if ax is None:
            return a
        return np.array_split(a, k, axis=ax)[rank]
    return _walk2(params, axes, cut)


class _LeafMeta:
    """Shape+dtype of one slice leaf (a non-tuple leaf type, so the
    structural walkers don't recurse into it)."""
    __slots__ = ("shape", "dtype")

    def __init__(self, shape, dtype):
        self.shape = tuple(shape)
        self.dtype = str(dtype)


def slice_meta(slice_tree, axes):
    """Shape/dtype tree of one rank slice — enough to zero-fill a lost
    partition for degraded-TP continuation."""
    return _walk2(slice_tree, axes,
                  lambda leaf, _ax: _LeafMeta(leaf.shape, leaf.dtype))


def zero_slice(meta):
    return _walk2(meta, meta,
                  lambda m, _: np.zeros(m.shape, m.dtype))


def gather(rank_trees: List, axes):
    """All-gather: concatenate the k rank slices back into one param
    tree (replicated leaves come from the first rank)."""
    t0 = rank_trees[0]

    def walk(node0, ax_node, picks):
        if isinstance(node0, dict):
            return {key: walk(node0[key], ax_node[key],
                              [p[key] for p in picks]) for key in node0}
        if isinstance(node0, (list, tuple)):
            return type(node0)(
                [walk(v, ax_node[i], [p[i] for p in picks])
                 for i, v in enumerate(node0)])
        if ax_node is None:
            return node0
        return np.concatenate(picks, axis=ax_node)
    return walk(t0, axes, rank_trees)


@dataclass
class _GroupLayout:
    """Per-group partition metadata kept OFF the workers (the slices
    themselves live on the workers and die with them)."""
    axes: object
    rank_meta: Dict[int, object] = field(default_factory=dict)


# ---------------------------------------------------------------------------
# the testbed manager: control plane + real data plane
# ---------------------------------------------------------------------------


class TestbedShardManager(ShardGroupManager):
    """`ShardGroupManager` whose repartition/degrade phases are real
    JAX work on the testbed's worker threads, wall-clock measured."""

    def __init__(self, testbed, *, tp_degree: int, policy: str = "auto"):
        super().__init__(testbed.controller, tp_degree=tp_degree,
                         policy=policy, defer=None)
        self.tb = testbed
        self._layout: Dict[str, _GroupLayout] = {}
        # routes consumed while their engine is still building:
        # app_id -> (server_id, variant_name), pushed on install
        self._deferred: Dict[str, tuple] = {}
        self._fail_ctx: Dict[str, float] = {}      # app_id -> t_fail
        self._meas_lock = threading.Lock()
        self.measured: Dict[str, List[float]] = {
            "deploy_build_s": [],       # initial gather+compile per group
            "slice_fetch_s": [],        # reshard slice re-materialization
            "repartition_s": [],        # reshard re-gather + recompile
            "reshard_mttr_s": [],       # kill -> resharded engine serving
            "degrade_rebuild_s": [],    # zero-filled degraded recompile
            "degrade_mttr_s": [],       # kill -> degraded engine serving
        }

    def _note(self, key: str, value: float):
        with self._meas_lock:
            self.measured[key].append(value)

    # -- data-plane deploy --------------------------------------------------
    def is_slice(self, name: str) -> bool:
        return "::shard" in name

    def deploy_real(self, app: Application):
        """Partition the app's full params across the group members and
        bring up the gathered engine on the lead. Call after the
        controller-side `deploy_group`."""
        g = self.groups[app.id]
        k = g.tp_degree
        t0 = time.monotonic()
        params = checkpoint_params(g.base)
        axes = split_axes(params, k)
        layout = _GroupLayout(axes=axes)
        slices = {}
        for rank, m in sorted(g.members.items()):
            sl = rank_slice(params, axes, k, rank)
            layout.rank_meta[rank] = slice_meta(sl, axes)
            self.tb.workers[m.server_id].host_shard(
                slice_name(g.base, rank, k), sl)
            slices[rank] = sl
        del params                      # the engine comes from the slices
        self._layout[app.id] = layout
        gathered = gather([slices[r] for r in sorted(slices)], axes)
        self._install(g, g.lead.server_id, g.base.name, gathered)
        self._note("deploy_build_s", time.monotonic() - t0)
        self._push_if_current(app.id)

    def _install(self, g: ShardGroup, server_id: str, name: str,
                 params) -> None:
        w = self.tb.workers[server_id]
        eng = InferenceEngine(g.base.config, params,
                              batch_slots=w.batch_slots,
                              max_len=w.max_len, device=w.device,
                              tags={"server": server_id, "rung": name})
        eng.warmup()
        w.install(name, eng)

    # -- route interception -------------------------------------------------
    def on_route(self, app_id: str, server_id: str,
                 variant_name: str) -> bool:
        """RoutingTable-observer hook: push the route to the serving
        router only once the target engine is actually resident.
        Returns True when the push is deferred to an install."""
        g = self.groups.get(app_id)
        if g is None or g.state == "fallen-back":
            return False
        w = self.tb.workers.get(server_id)
        if w is None:
            return False
        if not w.has(variant_name) and "::tp" in variant_name:
            # degraded route: the lead's gathered engine (if it
            # survived) keeps answering under the degraded name until
            # the honest zero-filled rebuild swaps in underneath
            w.alias(variant_name, g.base.name)
        if w.has(variant_name):
            return False
        self._deferred[app_id] = (server_id, variant_name)
        return True

    def _push_if_current(self, app_id: str):
        """Flush a deferred route if it still matches the controller's
        current routing decision."""
        pending = self._deferred.pop(app_id, None)
        if pending is None:
            return
        with self.tb._ctl_lock:
            current = self.controller.routing.routes.get(app_id)
        if current is None or tuple(current) != tuple(pending):
            return
        self.tb._push_route(app_id, pending[0], pending[1])

    # -- ladder overrides: real work ----------------------------------------
    def handle_lost(self, failed_set, t_fail, t_detect):
        for gid, g in self.groups.items():
            if g.state == "fallen-back":
                continue
            if any(m.server_id in failed_set
                   for m in g.members.values()) or (
                    g.pending is not None
                    and g.pending.server_id in failed_set):
                self._fail_ctx[gid] = t_fail
        return super().handle_lost(failed_set, t_fail, t_detect)

    def _teardown_engines(self, g: ShardGroup):
        """A member died and the ladder is NOT continuing seamlessly:
        the TP collective is broken, so the gathered engine must stop
        answering until it is rebuilt."""
        for m in g.members.values():
            w = self.tb.workers.get(m.server_id)
            if w is None or not w.alive:
                continue
            w.unload(g.base.name)
            for name in list(w.engines):
                if name.startswith(g.base.name + "::"):
                    w.unload(name)

    def _degrade(self, g, app, t_fail, t_detect):
        rec = super()._degrade(g, app, t_fail, t_detect)
        lead = g.lead

        def rebuild():
            t0 = time.monotonic()
            try:
                parts = self._collect_slices(g, zero_missing=True)
                if parts is None:
                    return
                gathered = gather(parts, self._layout[app.id].axes)
                self._install(g, lead.server_id, rec.variant, gathered)
            except RuntimeError:
                return                       # lead died mid-rebuild
            self._note("degrade_rebuild_s", time.monotonic() - t0)
            t_kill = self._fail_ctx.get(app.id, t_fail)
            self._note("degrade_mttr_s", time.monotonic() - t_kill)
            self._push_if_current(app.id)

        self.tb.executor._spawn(rebuild)
        return rec

    def _collect_slices(self, g: ShardGroup,
                        zero_missing: bool = False) -> Optional[list]:
        """The k rank slices off the member workers (pending member
        included); missing ranks come back zero-filled when allowed."""
        layout = self._layout.get(g.app_id)
        if layout is None:
            return None
        holders = dict(g.members)
        if g.pending is not None:
            holders[g.pending.rank] = g.pending
        parts = []
        for rank in range(g.tp_degree):
            m = holders.get(rank)
            sl = None
            if m is not None:
                w = self.tb.workers.get(m.server_id)
                if w is not None:
                    sl = w.shard(slice_name(g.base, rank, g.tp_degree))
            if sl is None:
                meta = layout.rank_meta.get(rank)
                if not zero_missing or meta is None:
                    return None
                sl = zero_slice(meta)
            parts.append(sl)
        return parts

    def materialize_slice(self, app: Application, sv: Variant,
                          server_id: str) -> float:
        """Executor hook for a reshard's slice load: re-materialize the
        lost rank from the deterministic checkpoint seed and host it on
        the replacement worker. Returns wall seconds (the 'warmup' leg
        of the load ticket; the byte transfer was already slept at
        slice-byte cost by the executor's fetch plan)."""
        g = self.groups[app.id]
        rank = int(sv.name.rsplit("::shard", 1)[1].split("of")[0])
        t0 = time.monotonic()
        params = checkpoint_params(g.base)
        axes = self._layout[app.id].axes
        sl = rank_slice(params, axes, g.tp_degree, rank)
        self._layout[app.id].rank_meta[rank] = slice_meta(sl, axes)
        self.tb.workers[server_id].host_shard(sv.name, sl)
        wall = time.monotonic() - t0
        self._note("slice_fetch_s", wall)
        return wall

    def _reshard(self, g, app, rank, failed_set, t_fail, t_detect):
        self._teardown_engines(g)
        return super()._reshard(g, app, rank, failed_set, t_fail,
                                t_detect)

    def _after_repartition(self, g, sv, repart_s, finish):
        """The real repartition: re-gather all k slices (the pending
        member now hosts the re-materialized one), recompile on the
        post-commit lead, then commit the controller-side state. The
        measured wall time calibrates the sim's modeled cost."""
        def work():
            t0 = time.monotonic()
            holders = dict(g.members)
            if g.pending is not None:
                holders[g.pending.rank] = g.pending
            lead_sid = holders[min(holders)].server_id
            try:
                parts = self._collect_slices(g)
                if parts is None:
                    return         # a holder died; next epoch falls back
                gathered = gather(parts, self._layout[g.app_id].axes)
                self._install(g, lead_sid, g.base.name, gathered)
            except RuntimeError:
                return
            measured = time.monotonic() - t0
            with self.tb._ctl_lock:
                finish()
            self.calibrate_repartition(measured, sv.mem_bytes)
            self._note("repartition_s", measured)
            t_kill = self._fail_ctx.get(g.app_id, t0)
            self._note("reshard_mttr_s", time.monotonic() - t_kill)
            self._push_if_current(g.app_id)

        del repart_s
        self.tb.executor._spawn(work)

    def _fallback(self, g, app, t_fail, t_detect):
        self._teardown_engines(g)
        self._deferred.pop(app.id, None)
        return super()._fallback(g, app, t_fail, t_detect)

    # -- reporting ----------------------------------------------------------
    def summary(self) -> dict:
        out = super().summary()
        with self._meas_lock:
            out["measured"] = {
                k: {"n": len(v),
                    "avg_s": sum(v) / len(v) if v else -1.0,
                    "max_s": max(v) if v else -1.0}
                for k, v in self.measured.items()}
        return out
