"""Batched inference engine: continuous batching over a slotted KV cache.

One engine = one loaded model variant on one serving cell.  Requests are
admitted into free batch slots; each step() runs one decode step for all
active slots (prefill on admission).  Greedy sampling; per-slot position
bookkeeping lives in the model cache ("pos").
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import model as MDL
from repro.models.config import ModelConfig
from repro.serving import spans


@dataclass
class Request:
    id: str
    prompt: np.ndarray              # (S,) int32
    max_new_tokens: int = 8
    submitted_at: float = 0.0
    first_token_at: Optional[float] = None
    done_at: Optional[float] = None
    tokens: List[int] = field(default_factory=list)
    error: Optional[str] = None

    @property
    def latency(self) -> Optional[float]:
        return None if self.done_at is None else \
            self.done_at - self.submitted_at


class InferenceEngine:
    """Slot-based continuous batching for one model instance.

    With a `device`, params and cache are committed to it, so every
    step runs there whichever thread calls it. `tags` (the serving cell
    and the rung) go on every admission's span (`repro.serving.spans`)."""

    def __init__(self, cfg: ModelConfig, params, *, batch_slots: int = 4,
                 max_len: int = 256, device=None,
                 tags: Optional[dict] = None):
        self.cfg = cfg
        self.tags = dict(tags or {})
        self.batch_slots = batch_slots
        self.max_len = max_len
        cache = MDL.init_cache(cfg, batch_slots, max_len)
        if device is not None:
            params, cache = jax.device_put((params, cache), device)
        self.params = params
        self.cache = cache
        self.slots: List[Optional[Request]] = [None] * batch_slots
        self.remaining: np.ndarray = np.zeros(batch_slots, np.int32)
        self._lock = threading.Lock()

        self._decode = jax.jit(
            lambda p, c, t: MDL.decode_step(p, cfg, t, c))
        self._prefill_one = jax.jit(
            lambda p, c, t: MDL.prefill(p, cfg, t, c))

    def warmup(self, prompt_bucket: int = 8):
        """Compile decode + bucketed prefill (counts toward load time,
        the paper's Fig. 2b load+warmup analogue)."""
        tok = jnp.zeros((self.batch_slots,), jnp.int32)
        logits, _ = self._decode(self.params, self.cache, tok)
        logits.block_until_ready()
        if not self.cfg.is_encoder_decoder:
            sub = MDL.cache_take_slot(self.cache, 0)
            sub["pos"] = jnp.zeros((1,), jnp.int32)
            pl_, _ = self._prefill_one(
                self.params, sub, jnp.zeros((1, prompt_bucket), jnp.int32))
            pl_.block_until_ready()

    # -- admission -----------------------------------------------------------
    def try_admit(self, req: Request) -> bool:
        with self._lock:
            try:
                slot = self.slots.index(None)
            except ValueError:
                return False
            self.slots[slot] = req
            self.remaining[slot] = req.max_new_tokens
        with spans.span("engine.admit", id=req.id,
                        prompt_len=len(req.prompt), **self.tags):
            # single-sequence prefill into the slot (pos bookkeeping per
            # slot)
            prompt = jnp.asarray(req.prompt, jnp.int32)[None, :]
            sub = MDL.cache_take_slot(self.cache, slot)
            sub["pos"] = jnp.zeros((1,), jnp.int32)
            logits, sub = self._prefill_one(self.params, sub, prompt)
            with self._lock:
                self.cache = MDL.cache_put_slot(self.cache, slot, sub)
                with spans.span("engine.first_token", id=req.id):
                    first = int(jnp.argmax(logits[0]))
                req.tokens.append(first)
                req.first_token_at = time.monotonic()
        return True

    # -- decode ---------------------------------------------------------------
    def step(self) -> List[Request]:
        """One decode step for all active slots; returns finished reqs."""
        with self._lock:
            active = [i for i, r in enumerate(self.slots) if r is not None]
            if not active:
                return []
            last = [r.tokens[-1] if r is not None and r.tokens else 0
                    for r in self.slots]
            ids = tuple(self.slots[i].id for i in active)
        with spans.span("engine.decode", ids=ids):
            tok = jnp.asarray(last, jnp.int32)
            logits, self.cache = self._decode(self.params, self.cache, tok)
        with spans.span("engine.sync", ids=ids):
            nxt = np.asarray(jnp.argmax(logits, axis=-1))
        finished = []
        with self._lock:
            for i in active:
                req = self.slots[i]
                req.tokens.append(int(nxt[i]))
                self.remaining[i] -= 1
                if self.remaining[i] <= 0:
                    req.done_at = time.monotonic()
                    finished.append(req)
                    self.slots[i] = None
        return finished

    def device_bytes(self) -> int:
        """Bytes this engine holds on its device: params plus cache."""
        return sum(x.nbytes for x in
                   jax.tree_util.tree_leaves((self.params, self.cache)))

    def active_count(self) -> int:
        with self._lock:
            return sum(r is not None for r in self.slots)
