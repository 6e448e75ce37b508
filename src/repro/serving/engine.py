"""Batched inference engine: continuous batching over a slotted KV cache.

One engine = one loaded model variant on one serving cell.  Requests are
admitted into free batch slots; each step() runs one decode step for all
active slots (prefill on admission).  Greedy sampling; per-slot position
bookkeeping lives in the model cache ("pos").

The engine's programs are a fixed set: one admission per prompt bucket
(or per prompt length where padding is not exact), one decode step, and
its sampling. `warmup()` builds the set through the helpers serving
calls, in the same JAX context, so a warm engine's first request
traces, lowers and compiles nothing.

The decode step is given the cache to consume (donated): it writes one
position per slot in place, and no one reads the cache it was given.
"""

from __future__ import annotations

import contextlib
import functools
import threading
import time
from dataclasses import dataclass, field
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import model as MDL
from repro.models.config import ModelConfig
from repro.serving import spans

# Shortest prompt bucket. The v5e's ridge point is 197 TFLOP/s / 819
# GB/s ~ 240 FLOP/byte: a bf16 prefill of fewer than ~240 tokens is
# bound by reading the weights, so padding a shorter prompt up to 256
# costs little device time.
MIN_BUCKET = 256
# prompt length an exact-length engine's warmup() admits
WARM_PROMPT = 8


def prefill_buckets(cfg: ModelConfig,
                    max_len: int) -> Optional[Tuple[int, ...]]:
    """Padded prompt lengths: the powers of two from MIN_BUCKET below
    `max_len`, then `max_len`. None where padding is not exact: pads
    would enter a local ring buffer, a recurrent state or an encoder."""
    if cfg.is_encoder_decoder or any(k != "global"
                                     for k in cfg.layer_kinds()):
        return None
    out, n = [], MIN_BUCKET
    while n < max_len:
        out.append(n)
        n *= 2
    return tuple(out) + (max_len,)


def _admit(cfg, params, cache, tokens, slot, length):
    """Prefill `tokens[:, :length]` into `slot` from position 0: the
    first greedy token and the new cache, in one program per token
    shape (`slot` and `length` are traced)."""
    sub = MDL.cache_take_slot(cache, slot)
    sub["pos"] = jnp.zeros((1,), jnp.int32)
    logits, sub = MDL.prefill(params, cfg, tokens, sub, length=length)
    return jnp.argmax(logits[0]), MDL.cache_put_slot(cache, slot, sub)


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

    With a `device`, params and cache are committed to it and every JAX
    call of the engine runs under `jax.default_device(device)`, whatever
    context its caller holds: JAX keys its jit cache on that setting.
    `tags` (the serving cell and the rung) go on every admission's span
    (`repro.serving.spans`).

    An admission runs the prefill, slot ops and first token as one
    program and, like a decode step, reads and replaces the cache under
    the engine's lock, so the two never lose each other's writes."""

    def __init__(self, cfg: ModelConfig, params, *, batch_slots: int = 4,
                 max_len: int = 256, device=None,
                 tags: Optional[dict] = None):
        self.cfg = cfg
        self.tags = dict(tags or {})
        self.batch_slots = batch_slots
        self.max_len = max_len
        self.device = device
        self.buckets = prefill_buckets(cfg, max_len)
        self._built: set = set()        # prompt shapes warmup() admitted
        with self._on_device():
            cache = MDL.init_cache(cfg, batch_slots, max_len)
            if device is not None:
                params, cache = jax.device_put((params, cache), device)
        self.params = params
        self.cache = cache
        self.slots: List[Optional[Request]] = [None] * batch_slots
        self.remaining: np.ndarray = np.zeros(batch_slots, np.int32)
        self._lock = threading.Lock()
        # the cache's largest leaf, whose deletion shows that a decode
        # step consumed the cache it was given
        sizes = [x.nbytes for x in jax.tree_util.tree_leaves(cache)]
        self._probe = sizes.index(max(sizes))

        # the benchmark finds the decode program in a device trace by its
        # module name, `jit__lambda`
        self._decode = jax.jit(
            lambda p, c, t: MDL.decode_step(p, cfg, t, c), donate_argnums=1)
        self._admit = jax.jit(functools.partial(_admit, cfg))

    def _on_device(self):
        return (jax.default_device(self.device) if self.device is not None
                else contextlib.nullcontext())

    def _bucket(self, n: int) -> Optional[int]:
        """The padded length of an n-token prompt; None for exact length."""
        return next((b for b in self.buckets or () if b >= n), None)

    def _prefill(self, cache, slot: int, prompt: np.ndarray):
        """Dispatch the admission of `prompt` into `slot` of `cache`:
        (first token on the device, new cache)."""
        n = len(prompt)
        tokens = np.zeros((1, self._bucket(n) or n), np.int32)
        tokens[0, :n] = prompt
        return self._admit(self.params, cache,
                           jax.device_put(tokens, self.device), slot, n)

    def _decode_step(self, cache, last: List[int]):
        """Dispatch one decode step from the slots' last tokens:
        (logits, new cache)."""
        tok = jax.device_put(np.asarray(last, np.int32), self.device)
        return self._decode(self.params, cache, tok)

    @staticmethod
    def _sample(logits) -> np.ndarray:
        return np.asarray(jnp.argmax(logits, axis=-1))

    def warmup(self):
        """Build every program serving runs (counts toward load time,
        the paper's Fig. 2b load+warmup analogue): an admission per
        prompt bucket, or of WARM_PROMPT tokens where prompts keep their
        exact length, then a decode step and its sampling. They run
        through serving's own helpers and leave the cache as it was: the
        decode consumes a copy."""
        lengths = self.buckets or (
            () if self.cfg.is_encoder_decoder else (WARM_PROMPT,))
        with self._on_device():
            for n in lengths:
                first, _ = self._prefill(self.cache, 0,
                                         np.zeros(n, np.int32))
                int(first)
                self._built.add(n)
            scratch = jax.tree_util.tree_map(jnp.copy, self.cache)
            logits, _ = self._decode_step(scratch, [0] * self.batch_slots)
            self._sample(logits)

    # -- admission -----------------------------------------------------------
    def try_admit(self, req: Request) -> bool:
        n = len(req.prompt)
        bucket = self._bucket(n)
        with self._lock:
            try:
                slot = self.slots.index(None)
            except ValueError:
                return False
            with self._on_device(), spans.span(
                    "engine.admit", id=req.id, prompt_len=n, bucket=bucket,
                    warm=(bucket or n) in self._built, **self.tags):
                first, self.cache = self._prefill(self.cache, slot,
                                                  req.prompt)
                with spans.span("engine.first_token", id=req.id):
                    req.tokens.append(int(first))
                req.first_token_at = time.monotonic()
            self.slots[slot] = req
            self.remaining[slot] = req.max_new_tokens
        return True

    # -- decode ---------------------------------------------------------------
    def step(self) -> List[Request]:
        """One decode step for all active slots; returns finished reqs."""
        with self._lock:
            active = [i for i, r in enumerate(self.slots) if r is not None]
            if not active:
                return []
            last = [r.tokens[-1] if r is not None else 0
                    for r in self.slots]
            ids = tuple(self.slots[i].id for i in active)
            probe = jax.tree_util.tree_leaves(self.cache)[self._probe]
            with self._on_device(), spans.span("engine.decode",
                                               ids=ids) as sp:
                logits, self.cache = self._decode_step(self.cache, last)
                sp.attrs["donated"] = probe.is_deleted()
        with self._on_device(), spans.span("engine.sync", ids=ids):
            nxt = self._sample(logits)
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
        with self._lock:
            return sum(x.nbytes for x in
                       jax.tree_util.tree_leaves((self.params, self.cache)))

    def active_count(self) -> int:
        with self._lock:
            return sum(r is not None for r in self.slots)
