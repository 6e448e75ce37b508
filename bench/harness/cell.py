"""One run of one cell: deploy, warm up, drive the window, check.

The window drives the program's normal path. A client FIFO per app
(here: one app) looks up the app's route in the `Router`, submits to the
`WorkerServer` (`submit` runs `InferenceEngine.try_admit`: the prefill
into a slot) and the worker's thread decodes through the slotted cache
(`InferenceEngine.step`). The crash goes through the testbed's own
failure path: `MiniTestbed._fail_servers` kills the worker, the
testbed's sweeper (`MiniTestbed._sweeper_loop`) detects it and runs
`controller.handle_failures`, and the route update reaches the router.

The FIFO sends the next request only once the one before has finished
and `settle_ms` has passed: the engine admits a request in the caller's
thread while its worker thread may still be stepping, so two requests
in flight on one engine can corrupt each other's tokens. A request
refused because its route is down waits at the head of the FIFO and is
retried as the route changes. A request in flight on a crashed worker
is lost. Times are taken from each request's due time.

Set-up compiles every program the window runs, the backups' too, and
then drops them from memory, so that a failover reads the backup's
programs from the persistent compile cache in every run (`warm_up`).

After the window: the device's peak memory is read, the testbed is shut
down and its arrays freed, and only then does the float32 reference run
over a sample of the served requests (`reference.py`).
"""

from __future__ import annotations

import gc
import math
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from bench.harness import reference, traffic
from bench.harness.weights import Shape, engine_tree, make_flat

RETRY_S = 0.001               # poll of a refused request's route
WAIT_S = 0.0005               # poll of an in-flight request's completion
LEAD_S = 0.05                 # from the end of set-up to the first due time
WARMUP_TOKENS = 2             # per warm-up request
WARMUP_PER_BUCKET = 1         # warm-up requests per prompt bucket


@dataclass
class Sent:
    """What the FIFO saw of one request."""
    index: int
    due: float
    prompt_len: int
    want_tokens: int          # prefill's token + decode steps
    t_ready: Optional[float] = None      # FIFO free and request due
    t_first_try: Optional[float] = None  # first submit attempt
    t_admit: Optional[float] = None      # start of the submit that held
    server: Optional[str] = None
    rung: Optional[str] = None
    lost: bool = False
    req: object = None

    def row(self) -> dict:
        r = self.req
        done = r is not None and r.done_at is not None and not self.lost
        return {"index": self.index, "due": self.due,
                "prompt_len": self.prompt_len,
                "want_tokens": self.want_tokens, "t_ready": self.t_ready,
                "t_first_try": self.t_first_try, "t_admit": self.t_admit,
                "server": self.server, "rung": self.rung,
                "first": None if r is None else r.first_token_at,
                "done": r.done_at if done else None,
                "n_tokens": 0 if r is None else len(r.tokens),
                "failed": not done, "lost": self.lost}


@dataclass
class Window:
    t0: float
    t_end: float
    deadline: float
    stop_sending_at_end: bool
    settle_s: float
    spans: List[Tuple[str, float, float]] = field(default_factory=list)


def model_config(config: dict):
    from repro.models.config import ModelConfig
    m = dict(config["model"])
    m["block_pattern"] = tuple(m["block_pattern"])
    return ModelConfig(**m)


class Cell:
    """Builds the testbed for a configuration and a mix, and runs it."""

    def __init__(self, config: dict, mix: dict, seed: int):
        self.config, self.mix, self.seed = config, mix, seed
        self.dep = config["deployment"]
        self.cfg = model_config(config)
        self.tb = None
        self.app = None
        self._orig_ckpt = None
        self.timings: Dict[str, float] = {}      # set-up phases, seconds

    # -- deployment -----------------------------------------------------------
    def weights_for(self, variant):
        """The benchmark's weights for a rung: seeded, made on the device
        the load runs under, in the serving engine's layout."""
        return engine_tree(make_flat(Shape.of(variant.config), self.seed,
                                     variant.name))

    def deploy(self):
        from repro.core.variants import Application, build_ladder
        from repro.serving import server as server_mod
        from repro.serving.testbed import MiniTestbed

        self.app = Application(id=f"{self.cfg.name}-app0",
                               family=self.cfg.name,
                               variants=build_ladder(self.cfg),
                               critical=bool(self.mix["critical"]))
        # the testbed's checkpoint store serves the benchmark's weights
        self._orig_ckpt = server_mod.checkpoint_params
        server_mod.checkpoint_params = self.weights_for
        self.tb = MiniTestbed(n_sites=1,
                              servers_per_site=int(self.dep["workers"]),
                              apps=[self.app], alpha=float(self.dep["alpha"]),
                              seed=self.seed)
        for w in self.tb.workers.values():
            w.batch_slots = int(self.dep["batch_slots"])
            w.max_len = int(self.dep["max_len"])
        t = time.monotonic()
        self.tb.deploy()
        self.timings["deploy_s"] = time.monotonic() - t
        self._sweeper = threading.Thread(target=self.tb._sweeper_loop,
                                         daemon=True)
        self._sweeper.start()
        return self

    def route(self):
        return self.tb.router.lookup(self.app.id)

    def rung_shape(self, name: str) -> Shape:
        return Shape.of(self.app.variant_by_name(name).config)

    # -- requests --------------------------------------------------------------
    def _request(self, rid: str, prompt: np.ndarray, new_tokens: int):
        from repro.serving.engine import Request
        return Request(id=rid, prompt=prompt, max_new_tokens=new_tokens,
                       submitted_at=time.monotonic())

    def _wait_done(self, req, worker, deadline: float) -> bool:
        """True once `req` is done; False if its worker died or the
        deadline passed first."""
        while req.done_at is None:
            self.tb.raise_errors()
            if not worker.alive or time.monotonic() > deadline:
                return False
            time.sleep(WAIT_S)
        return True

    def _warm(self, settle_s: float, target=None):
        """A request of each prompt bucket, to `target` (server, rung) or
        else through the router: the rung's prefill programs and eager
        admission ops."""
        rng = np.random.default_rng([self.seed & (2**64 - 1), 99])
        for size, _count in self.mix["prompt_buckets"]:
            for i in range(WARMUP_PER_BUCKET):
                prompt = rng.integers(0, self.cfg.vocab_size, int(size),
                                      dtype=np.int32)
                sid, vname = target or self.route()
                req = self._request(f"warm-{sid}-{size}-{i}", prompt,
                                    WARMUP_TOKENS - 1)
                w = self.tb.workers[sid]
                if not w.submit(vname, req):
                    raise RuntimeError(f"warm-up refused on {sid}/{vname}")
                if not self._wait_done(req, w, time.monotonic() + 600):
                    raise RuntimeError("warm-up request did not finish")
                time.sleep(settle_s)

    def warm_up(self, settle_s: float):
        """Compile in set-up every program the window runs, and leave the
        backups as a failover finds them.

        Each warm backup first serves a request of each prompt bucket, so
        that its programs go into the persistent compile cache here and
        never compile in the window. Then every compiled program is
        dropped from memory (`jax.clear_caches`) and each engine's own
        `warmup()` runs again, which leaves the engines as `load()` left
        them: after the failover a backup's first requests read their
        programs from the cache, the cost its users feel, and do so alike
        in a checkout's first run and every later one. Last, a request of
        each bucket goes through the router to the primary."""
        import jax
        primary = self.route()
        backups = [(sid, vname) for sid, w in self.tb.workers.items()
                   for vname in list(w.engines) if (sid, vname) != primary]
        for target in backups:
            self._warm(settle_s, target)
        if backups:
            jax.clear_caches()
            for w in self.tb.workers.values():
                for eng in list(w.engines.values()):
                    with jax.default_device(w.device):
                        eng.warmup()
        self._warm(settle_s)

    # -- the window -------------------------------------------------------------
    def _fifo(self, plan: List[traffic.Planned], sent: List[Sent],
              win: Window, trace_spans: bool):
        from jax.profiler import TraceAnnotation
        ready_after = win.t0
        for p in plan:
            s = Sent(p.index, win.t0 + p.offset_s, len(p.prompt),
                     p.max_new_tokens + 1)
            now = time.monotonic()
            if win.stop_sending_at_end and now >= win.t_end:
                return
            t_ready = max(s.due, ready_after)
            if t_ready > now:
                time.sleep(t_ready - now)
            if win.stop_sending_at_end and time.monotonic() >= win.t_end:
                return
            sent.append(s)
            s.t_ready = t_ready
            s.req = self._request(f"r{p.index}", p.prompt, p.max_new_tokens)
            worker = None
            while True:
                now = time.monotonic()
                if now > win.deadline:
                    break
                route = self.route()
                if route is not None:
                    sid, vname = route
                    w = self.tb.workers[sid]
                    if s.t_first_try is None:
                        s.t_first_try = now
                    t_a = time.monotonic()
                    if trace_spans:
                        with TraceAnnotation("bench.admit"):
                            ok = w.submit(vname, s.req)
                    else:
                        ok = w.submit(vname, s.req)
                    win.spans.append(("admit", t_a, time.monotonic()))
                    if ok:
                        s.t_admit, s.server, s.rung = t_a, sid, vname
                        worker = w
                        break
                time.sleep(RETRY_S)
            if worker is None:
                ready_after = time.monotonic()
                continue
            done = self._wait_done(s.req, worker, win.deadline)
            if not done and not worker.alive:
                s.lost = True
            ready_after = time.monotonic() + win.settle_s

    def run_window(self, seconds: float, trace_dir: Optional[str],
                   t_process: float) -> dict:
        import jax

        mix = self.mix
        settle_s = float(mix["settle_ms"]) / 1e3
        t = time.monotonic()
        self.warm_up(settle_s)
        self.timings["warm_up_s"] = time.monotonic() - t
        if mix["arrivals"] == "saturated":
            plan = traffic.schedule(mix, self.seed, seconds,
                                    self.cfg.vocab_size,
                                    traffic.saturated_count(mix, seconds))
        else:
            plan = traffic.schedule(mix, self.seed, seconds,
                                    self.cfg.vocab_size)
        primary = self.route()
        gc.collect()
        t0 = time.monotonic() + LEAD_S
        kill_at = (None if mix.get("kill_at") is None
                   else t0 + float(mix["kill_at"]) * seconds)
        win = Window(t0=t0, t_end=t0 + seconds,
                     deadline=t0 + seconds + float(mix["timeout_s"]),
                     stop_sending_at_end=mix["arrivals"] == "saturated",
                     settle_s=settle_s)
        setup_s = t0 - t_process
        sent: List[Sent] = []
        if trace_dir is not None:
            jax.profiler.start_trace(trace_dir)
        fifo = threading.Thread(target=self._fifo,
                                args=(plan, sent, win, trace_dir is not None))
        t_trace0 = time.monotonic()
        fifo.start()
        kill = None
        if kill_at is not None:
            time.sleep(max(0.0, kill_at - time.monotonic()))
            t_kill = time.monotonic()
            self.tb._fail_servers([primary[0]])
            kill = {"t": t_kill, "server": primary[0], "rung": primary[1]}
        time.sleep(max(0.0, win.t_end - time.monotonic()))
        t_end = time.monotonic()
        tokens_in_window = sum(len(s.req.tokens) for s in list(sent)
                               if s.req is not None and not s.lost)
        t_trace1 = time.monotonic()
        if trace_dir is not None:
            jax.profiler.stop_trace()
        fifo.join(timeout=max(0.0, win.deadline - time.monotonic()) + 30)
        if fifo.is_alive():
            raise RuntimeError("client FIFO did not stop by its deadline")
        self.tb.raise_errors()
        rec = self.tb.controller.records.get(self.app.id)
        recovery = None
        if rec is not None:
            recovery = {"recovered": bool(rec.recovered),
                        "mttr_s": float(rec.mttr), "mode": rec.mode,
                        "variant": rec.variant,
                        "phases": {k: float(v) for k, v in rec.phases.items()}}
        loads = {sid: dict(w.load_s) for sid, w in self.tb.workers.items()}
        rungs = {}
        for s in sent:
            if s.rung is not None and s.rung not in rungs:
                rungs[s.rung] = vars(self.rung_shape(s.rung))
        return {"setup_s": setup_s, "seconds": seconds,
                "timings": dict(self.timings),
                "window": {"t0": t0, "t_end": t_end,
                           "trace": [t_trace0, t_trace1]},
                "kill": kill, "recovery": recovery, "loads": loads,
                "slots": int(self.dep["batch_slots"]),
                "max_len": int(self.dep["max_len"]),
                "rungs": rungs, "tokens_in_window": tokens_in_window,
                "requests": [s.row() for s in sent],
                "spans": list(win.spans), "_sent": sent}

    def shutdown(self):
        from repro.serving import server as server_mod
        try:
            if self.tb is not None:
                self.tb.shutdown()
                self._sweeper.join(timeout=5)
        finally:
            if self._orig_ckpt is not None:
                server_mod.checkpoint_params = self._orig_ckpt
            self.tb = None
            gc.collect()


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------

def sample(run: dict, seed: int, min_tokens: int) -> List[Sent]:
    """Finished requests to check: the longest, the first served after a
    failover, one of every rung that served, then others in an order
    drawn from the seed until `min_tokens` served tokens are in."""
    done = [s for s in run["_sent"] if s.row()["done"] is not None]
    if not done:
        return []
    rng = np.random.default_rng([seed & (2**64 - 1), 7])
    order = [done[i] for i in rng.permutation(len(done))]
    picked: List[Sent] = [max(done, key=lambda s: (s.want_tokens,
                                                   -s.index))]
    kill = run["kill"]
    if kill is not None:
        after = [s for s in done if s.server != kill["server"]]
        if after:
            picked.append(min(after, key=lambda s: s.req.first_token_at))
    for rung in sorted({s.rung for s in done}):
        if not any(p.rung == rung for p in picked):
            picked.append(next(s for s in order if s.rung == rung))
    for s in order:
        if sum(len(p.req.tokens) for p in picked) >= min_tokens:
            break
        if s not in picked:
            picked.append(s)
    return picked


GAP_NUMBERS = {"max_gap": max, "mean_gap": lambda g: float(np.mean(g))}


def judge(gaps: List[float], short: int, correct_cfg: dict) -> dict:
    """The numbers compared, each beside its limit, and the verdict. Each
    gap number the configuration's `correct` gives a limit for (the
    widest gap, the mean gap) must lie at or under it, no checked
    request may have come back short, and at least `sample_tokens`
    tokens must have been checked."""
    need = int(correct_cfg["sample_tokens"])
    checks = {k: {"value": f(gaps) if gaps else math.inf,
                  "limit": float(correct_cfg[k])}
              for k, f in GAP_NUMBERS.items() if k in correct_cfg}
    if not checks:
        raise KeyError(f"no gap limit in {sorted(correct_cfg)}")
    correct = (bool(gaps) and short == 0 and len(gaps) >= need
               and all(c["value"] <= c["limit"] for c in checks.values()))
    checks["short_requests"] = {"value": short, "limit": 0}
    checks["checked_tokens"] = {"value": len(gaps), "limit": need}
    return {"checks": checks, "correct": correct}


def check(run: dict, seed: int, correct_cfg: dict, mix: dict,
          controls: Tuple[str, ...] = ()) -> dict:
    """Run the reference over the sample and judge the served tokens;
    with `controls`, judge as well the tokens that each lower-precision
    pass of the reference puts first at the same positions (which must
    come out not correct). Call once the testbed is shut down."""
    picked = sample(run, seed, int(correct_cfg["sample_tokens"]))
    rows_to = int(mix["output_tokens"][1])
    gaps: List[float] = []
    ctl: Dict[str, List[float]] = {c: [] for c in controls}
    short = 0
    for rung in sorted({s.rung for s in picked}):
        shape = Shape(**run["rungs"][rung])
        flat = make_flat(shape, seed, rung)
        for s in (p for p in picked if p.rung == rung):
            if len(s.req.tokens) != s.want_tokens:
                short += 1
                continue
            g = reference.gaps(flat, shape, s.req.prompt, list(s.req.tokens),
                               pad_to=reference.pad_len(
                                   len(s.req.prompt) + s.want_tokens),
                               rows_to=rows_to, controls=controls)
            gaps.extend(g["gap"].tolist())
            for c in controls:
                ctl[c].extend(g[c].tolist())
        del flat
    out = judge(gaps, short, correct_cfg)
    out["checked_requests"] = len(picked)
    out["controls"] = {c: judge(ctl[c], short, correct_cfg)
                       for c in controls}
    return out
