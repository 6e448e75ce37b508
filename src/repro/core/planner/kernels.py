"""Jitted JAX kernels for the planner's inner loops (jax backend).

Three compiled primitives, built per (S, R, Vmax, E, dtype) signature
and cached process-wide so a planning round never recompiles:

  * ``place_chunk`` — the fused feasibility-match + masked-argmax
    worst-fit: one `lax.scan` step per app runs Algorithm 1's
    match (Line 6, δ-threshold variant selection), degradation loop
    (Lines 7-12, lazily testing one (S,) feasibility column per tried
    variant), and worst-fit reduction (Line 9, the
    `kernels/planner_argmax` masked argmax — first-maximum tie rule)
    against carried (S, R) free / (S,) headroom / (R,) α-budget
    device arrays;
  * ``upgrade_chunk`` — the fused upgrade pass (Lines 13-14): per
    placed app, first feasible larger variant on its chosen row, with
    the legacy give-then-take two-step replayed op-for-op;
  * ``scatter_rows`` — donated-buffer dirty-row update powering the
    incremental `PlannerState` device mirror: the old free/head/alive
    buffers are donated to XLA, so a sync touches O(dirty) rows and
    never re-materializes the (S, R) arrays.

Bit-exactness contract (the property tests in tests/test_planner.py
assert it end-to-end): every arithmetic op here is an elementary IEEE
op in the same dtype and the same order as the numpy path — the (S, R)
feasibility compare runs in the state dtype against precomputed
round-up thresholds proven equal to numpy's f64 `free >= d - eps`
(jax_backend._cmp_thresholds), small f64 compares promote f32 state
losslessly, in-place f32 updates replay numpy's
compute-in-f64-then-cast semantics via an explicit astype round-trip,
and every argmax keeps numpy's first-maximum rule. All public entry points run under
`jax.enable_x64(True)` so f64 stays f64 without flipping the
global x64 flag for the rest of the process.

Chunking: callers drive whole rounds through fixed chunk shapes
(`CHUNK_MAIN` then `CHUNK_TAIL` for the remainder, padded with inactive
apps) so only two scan shapes ever compile per cluster signature — the
proactive setup round pays the compile; MTTR-critical failover rounds
hit the cache.
"""

from __future__ import annotations

from functools import lru_cache, partial

_EPS = 1e-9

CHUNK_MAIN = 4096       # bulk chunk (large proactive rounds)
CHUNK_TAIL = 256        # remainder chunk (failover-round scale)


def have_jax() -> bool:
    try:
        import jax  # noqa: F401
        return True
    except ImportError:                             # pragma: no cover
        return False


def resolve_backend(backend: str, dtype: str | None = None) -> str:
    """Validate a planner backend name (and, where given, the planner
    state dtype) at construction time, so a bad config fails loudly
    instead of at the first failover round."""
    if backend not in ("numpy", "jax"):
        raise ValueError(f"unknown planner backend {backend!r}; "
                         "expected 'numpy' or 'jax'")
    if backend == "jax" and not have_jax():
        raise RuntimeError("planner backend 'jax' requires jax, which is "
                           "not importable here; use backend='numpy'")
    if backend == "jax" and dtype == "float64":
        import jax
        if jax.default_backend() == "tpu":
            raise ValueError(
                "planner backend 'jax' on a TPU runs the Pallas masked "
                "argmax, and TPU kernels have no 64-bit types: use "
                "planner_dtype='float32' or backend='numpy'")
    return backend


def chunk_sizes(n: int):
    """Decompose a round of n apps into fixed-shape chunks: as many
    CHUNK_MAIN as fit, then CHUNK_TAIL chunks for the remainder (the
    last one padded) — exactly two compiled shapes per signature."""
    out = []
    while n >= CHUNK_MAIN:
        out.append(CHUNK_MAIN)
        n -= CHUNK_MAIN
    while n > 0:
        out.append(CHUNK_TAIL)
        n -= CHUNK_TAIL
    return out


@lru_cache(maxsize=None)
def build_kernels(S: int, R: int, V: int, E: int, dtype_str: str):
    """Compile-cached kernel set for one cluster/round signature.

    S/R: state matrix shape; V: padded variants per app; E: padded
    exclusion rows per app (pad index = S, dropped by scatter mode);
    dtype_str: the PlannerState dtype ("float64" | "float32")."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.planner_argmax.ops import masked_argmax

    with jax.enable_x64(True):
        f64 = jnp.float64
        st_dtype = jnp.dtype(dtype_str)

        def _place_step(carry, x):
            free, head, budget, alive, cap = carry
            dm, dmc, vmask, thr, excl, active = x
            # (S,) allowed mask: alive minus this app's excluded rows
            # (Eq. 4 / §3.4) — pad index S drops out
            allowed = alive.at[excl].set(False, mode="drop")

            # fused match (Line 6): first variant under the δ threshold,
            # else the smallest variant — bit-equal to the numpy
            # segment scan (thr rows are +inf when δ >= 1)
            okv = (dm <= thr[None, :]).all(axis=1) & vmask
            nvar = jnp.maximum(vmask.sum(), 1).astype(jnp.int32)
            start = jnp.where(okv.any(), jnp.argmax(okv),
                              nvar - 1).astype(jnp.int32)

            # degradation loop (Lines 7-12): lazily test one (S,)
            # feasibility column per tried variant
            def cond(s):
                j, k, done = s
                return (~done) & (j < V)

            def body(s):
                j, _, _ = s
                bok = (budget >= dm[j] - _EPS).all() & vmask[j]

                def attempt(_):
                    # pure-dtype compares against the precomputed
                    # per-variant thresholds (jax_backend._cmp_thresholds
                    # proves them equal to numpy's f64 `free >= d - eps`),
                    # unrolled over R — XLA:CPU vectorizes the unrolled
                    # compares but not an (S, R) `.all(axis=1)` reduce
                    feas = allowed
                    for r in range(R):
                        feas = feas & (free[:, r] >= dmc[j, r])
                    k, _val = masked_argmax(head, feas)
                    return k

                k = jax.lax.cond(bok, attempt,
                                 lambda _: jnp.int32(-1), None)
                return (j + 1, k, k >= 0)

            j_end, k, done = jax.lax.while_loop(
                cond, body, (start, jnp.int32(-1), ~active))
            placed = active & (k >= 0)
            j = jnp.where(placed, j_end - 1, -1).astype(jnp.int32)
            ku = jnp.where(placed, k, 0)
            d = dm[jnp.where(placed, j, 0)]
            # numpy in-place `free[k] -= d` computes in f64, casts back
            newrow = (free[ku].astype(f64) - d).astype(st_dtype)
            free2 = free.at[ku].set(jnp.where(placed, newrow, free[ku]))
            budget2 = jnp.where(placed, budget - d, budget)
            newhead = (free2[ku] / cap[ku]).min()
            head2 = head.at[ku].set(jnp.where(placed, newhead, head[ku]))
            return ((free2, head2, budget2, alive, cap),
                    (j, jnp.where(placed, k, -1).astype(jnp.int32)))

        @jax.jit
        def place_chunk(free, head, budget, alive, cap,
                        dm, dmc, vmask, thr, excl, active):
            (free, head, budget, alive, cap), (j, k) = jax.lax.scan(
                _place_step, (free, head, budget, alive, cap),
                (dm, dmc, vmask, thr, excl, active))
            return free, head, budget, j, k

        def _upgrade_step(carry, x):
            free, head, budget, cap = carry
            dm, vmask, jcur, k = x
            active = (k >= 0) & (jcur > 0)
            ku = jnp.where(active, k, 0)
            d_cur = dm[jnp.where(active, jcur, 0)]
            row = free[ku]

            # first feasible larger variant (Lines 13-14): extras =
            # d[j] - d[jcur], fits row k AND the α-budget
            def cond(s):
                j, up, done = s
                return (~done) & (j < jcur)

            def body(s):
                j, _, _ = s
                extras = dm[j] - d_cur                      # f64 exact
                ok = vmask[j] \
                    & (row >= extras - _EPS).all() \
                    & (budget >= extras - _EPS).all()
                return (j + 1, jnp.where(ok, j, -1).astype(jnp.int32),
                        ok)

            _j_end, j_up, found = jax.lax.while_loop(
                cond, body, (jnp.int32(0), jnp.int32(-1), ~active))
            take = active & (j_up >= 0)
            d_up = dm[jnp.where(take, j_up, 0)]
            # give(current) then take(upgrade), two casts, NOT one
            # fused delta — replays the legacy float rounding exactly
            row1 = (row.astype(f64) + d_cur).astype(st_dtype)
            row2 = (row1.astype(f64) - d_up).astype(st_dtype)
            free2 = free.at[ku].set(jnp.where(take, row2, row))
            budget2 = jnp.where(take, (budget + d_cur) - d_up, budget)
            newhead = (free2[ku] / cap[ku]).min()
            head2 = head.at[ku].set(jnp.where(take, newhead, head[ku]))
            return ((free2, head2, budget2, cap),
                    jnp.where(take, j_up, -1).astype(jnp.int32))

        @jax.jit
        def upgrade_chunk(free, head, budget, cap, dm, vmask, jcur, k):
            (free, head, budget, cap), j_up = jax.lax.scan(
                _upgrade_step, (free, head, budget, cap),
                (dm, vmask, jcur, k))
            return free, head, budget, j_up

        return {"place_chunk": place_chunk,
                "upgrade_chunk": upgrade_chunk}


@lru_cache(maxsize=None)
def build_scatter():
    """Donated dirty-row scatter for the `PlannerState` device mirror:
    the stale free/head/alive buffers are donated to XLA so the update
    writes in place — O(dirty) work, no (S, R) re-materialization.
    Row indices >= S (the bucket padding) drop out."""
    import jax

    with jax.enable_x64(True):
        @partial(jax.jit, donate_argnums=(0, 1, 2))
        def scatter_rows(free, head, alive, idx, frows, hrows, arows):
            free = free.at[idx].set(frows, mode="drop")
            head = head.at[idx].set(hrows, mode="drop")
            alive = alive.at[idx].set(arows, mode="drop")
            return free, head, alive

        return scatter_rows


__all__ = ["CHUNK_MAIN", "CHUNK_TAIL", "build_kernels", "build_scatter",
           "chunk_sizes", "have_jax", "resolve_backend"]
