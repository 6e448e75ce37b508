"""Worker agent: one serving cell — hosts engines, heartbeats, fails.

Real work happens here in the mini-testbed: `load()` actually builds JAX
params and compiles the engine (that wall-clock time IS the measured
cold-load cost, the analogue of the paper's Fig. 2b Triton loads), and
`submit()` runs real batched inference on the worker's device: the
chip it is bound to, or JAX's default device (the CPU in tests).
"""

from __future__ import annotations

import queue
import threading
import time
import zlib
from typing import Callable, Dict

import jax

from repro.core.heartbeat import FailureDetector
from repro.core.variants import Application, Variant
from repro.models import model as MDL
from repro.serving.engine import InferenceEngine, Request


def checkpoint_params(variant: Variant):
    """The variant's deterministic 'checkpoint': random weights seeded
    by a CRC of its name, so every process and every rerun builds the
    same weights (Python's `hash` of a str is salted per process)."""
    cfg = variant.config
    assert cfg is not None, "testbed variants need real configs"
    return MDL.init_params(
        jax.random.PRNGKey(zlib.crc32(variant.name.encode())), cfg)


class WorkerServer:
    """Thread-backed serving cell with heartbeat + engine hosting.

    `device` binds the cell to one chip: its params and engine caches
    live there, and a crash frees that chip. None = JAX's default
    device."""

    def __init__(self, server_id: str, detector: FailureDetector, *,
                 on_error: Callable[[BaseException], None],
                 heartbeat_s: float = 0.020, batch_slots: int = 2,
                 max_len: int = 96, device=None):
        self.id = server_id
        self.detector = detector
        self.heartbeat_s = heartbeat_s
        self.batch_slots = batch_slots
        self.max_len = max_len
        self.device = device
        # a decode step that raises on a live cell (an HBM OOM, an XLA
        # error) is a fault of the program, handed here
        self.on_error = on_error
        # variant -> wall of its last load. load() returns it, but only
        # the executor's failover loads keep it (in their LoadTicket):
        # deploy's primary load and the warm-backup load drop it, and
        # this is the one place all three paths pass
        self.load_s: Dict[str, float] = {}
        self.engines: Dict[str, InferenceEngine] = {}     # variant -> engine
        self.cold_store: Dict[str, Variant] = {}          # on "disk"
        self.shard_store: Dict[str, object] = {}          # TP slices (HBM)
        self._alive = threading.Event()
        self._alive.set()
        self._threads = []
        self._lock = threading.Lock()
        self._work = queue.Queue()

    # -- lifecycle -----------------------------------------------------------
    def start(self):
        hb = threading.Thread(target=self._heartbeat_loop, daemon=True)
        wk = threading.Thread(target=self._serve_loop, daemon=True)
        hb.start()
        wk.start()
        self._threads = [hb, wk]
        return self

    def kill(self):
        """Crash-failure injection: heartbeats stop, engines vanish."""
        self._alive.clear()
        with self._lock:
            self.engines.clear()
            self.shard_store.clear()

    def revive(self):
        """Rejoin after a crash: the node returns EMPTY (engines were
        lost) but its cold store (disk) survived; heartbeats resume."""
        if self._alive.is_set():
            return self
        self._alive.set()
        return self.start()

    def join(self, timeout: float = 2.0):
        """Wait for the worker's threads to exit (after kill()); keeps
        JAX work out of interpreter teardown."""
        for t in self._threads:
            t.join(timeout=timeout)

    @property
    def alive(self) -> bool:
        return self._alive.is_set()

    def _heartbeat_loop(self):
        while self._alive.is_set():
            self.detector.beat(self.id)
            time.sleep(self.heartbeat_s)

    def _serve_loop(self):
        while True:
            try:
                fn = self._work.get(timeout=0.05)
            except queue.Empty:
                if not self._alive.is_set():
                    return
                continue
            if not self._alive.is_set():
                return
            try:
                fn()
            except Exception as e:      # noqa: BLE001
                if not self._alive.is_set():
                    return              # killed mid-step
                self.on_error(e)

    # -- model management (Triton Load/Unload analogue) -----------------------
    def stage_cold(self, app: Application, variant: Variant):
        """Cold replica: weights on disk/host only."""
        self.cold_store[variant.name] = variant

    def load(self, app: Application, variant: Variant,
             warm: bool = True) -> float:
        """Build params + compile; returns wall-clock load seconds."""
        if not self.alive:
            raise RuntimeError(f"{self.id} is down")
        t0 = time.monotonic()
        with jax.default_device(self.device):
            eng = InferenceEngine(variant.config, checkpoint_params(variant),
                                  batch_slots=self.batch_slots,
                                  max_len=self.max_len, device=self.device,
                                  tags={"server": self.id,
                                        "rung": variant.name})
            eng.warmup()
        wall = time.monotonic() - t0
        with self._lock:
            if not self.alive:
                raise RuntimeError(f"{self.id} died during load")
            self.engines[variant.name] = eng
            self.load_s[variant.name] = wall
        return wall

    def install(self, variant_name: str, engine: InferenceEngine):
        """Adopt a pre-built engine (tensor-parallel deployments gather
        their shard slices off-worker and install the result here)."""
        if not self.alive:
            raise RuntimeError(f"{self.id} is down")
        with self._lock:
            if not self.alive:
                raise RuntimeError(f"{self.id} died during install")
            self.engines[variant_name] = engine

    def alias(self, dst: str, src: str) -> bool:
        """Serve `src`'s resident engine under the name `dst` too
        (degraded-TP routes keep answering on the gathered engine until
        the honest rebuild swaps in). False if `src` is not resident."""
        with self._lock:
            eng = self.engines.get(src)
            if eng is None or not self.alive:
                return False
            self.engines[dst] = eng
            return True

    def host_shard(self, name: str, slice_tree) -> None:
        """Hold one TP weight slice in this cell's memory. Lost on
        kill() (unlike the cold store, which models disk)."""
        if not self.alive:
            raise RuntimeError(f"{self.id} is down")
        with self._lock:
            if not self.alive:
                raise RuntimeError(f"{self.id} died hosting a shard")
            self.shard_store[name] = slice_tree

    def shard(self, name: str):
        """The hosted slice, or None if this cell is dead/empty."""
        if not self.alive:
            return None
        with self._lock:
            return self.shard_store.get(name)

    def unload(self, variant_name: str):
        with self._lock:
            self.engines.pop(variant_name, None)

    def has(self, variant_name: str) -> bool:
        with self._lock:
            return variant_name in self.engines

    # -- serving ---------------------------------------------------------------
    def submit(self, variant_name: str, req: Request) -> bool:
        with self._lock:
            eng = self.engines.get(variant_name)
        if eng is None or not self.alive:
            return False
        if not eng.try_admit(req):
            return False
        self._work.put(lambda: self._drain(eng))
        return True

    def _drain(self, eng: InferenceEngine):
        while eng.active_count() and self._alive.is_set():
            eng.step()
