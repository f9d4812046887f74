"""Continuous batching for LM decode: a fixed slot pool.

Single-stream decode leaves the multiplier on the table: every step
re-reads all params (HBM-bound), so stepping one sequence costs almost
the same as stepping eight. The pool holds ``slots`` sequences in ONE
batched cache; each decode step advances every active slot together,
finished sequences retire (EOS or length), and queued prompts prefill
into freed slots *between steps* — aggregate tokens/s scales with
occupancy instead of serializing streams.

Built directly on the per-row cache positions the decode path grew for
this (:func:`keystone_tpu.models.lm.decode.decode_step` with a ``(B,)``
``pos`` vector): slots are never position-aligned, because they join at
different times with different prompt lengths.

Everything device-side is two compiled programs — the pooled decode
step and the per-bucket prefill — plus a slot-merge; membership
bookkeeping (who is active, who retires, who joins) is host-side per
step, which is the nature of continuous batching (the schedule is
data-dependent).
"""

from __future__ import annotations

import collections
import functools
import threading
import time
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from keystone_tpu.core.logging import get_logger
from keystone_tpu.models.lm.decode import (
    KVCache,
    _filter_logits,
    decode_step,
    prefill,
)
from keystone_tpu.observe import metrics as _metrics
from keystone_tpu.observe import spans as _spans
from keystone_tpu.observe import telemetry as _telemetry
from keystone_tpu.serve.queue import ServeFuture

logger = get_logger("keystone_tpu.serve.decode_loop")


@functools.partial(jax.jit, static_argnames=("s_max", "kv_dtype"))
def _jit_prefill(model, tokens, s_max, kv_dtype, lengths):
    return prefill(model, tokens, s_max, kv_dtype=kv_dtype, lengths=lengths)


@functools.partial(
    jax.jit, static_argnames=("temperature", "top_k", "top_p")
)
def _pick(logits, key, temperature, top_k, top_p):
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = _filter_logits(logits / temperature, top_k, top_p)
    return jax.random.categorical(key, logits).astype(jnp.int32)


@functools.partial(
    jax.jit, static_argnames=("temperature", "top_k", "top_p")
)
def _pool_step(model, tok, cache, key, temperature, top_k, top_p):
    """One decode step over the whole slot pool: (slots,) last tokens →
    ((slots,) next tokens, updated pooled cache)."""
    logits, cache2 = decode_step(model, tok, cache)
    if temperature == 0.0:
        tok2 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    else:
        tok2 = jax.random.categorical(
            key, _filter_logits(logits / temperature, top_k, top_p)
        ).astype(jnp.int32)
    return tok2, cache2


@jax.jit
def _merge_slot(pool: KVCache, one: KVCache, slot):
    """Write a freshly prefilled single-sequence cache into pool slot
    ``slot`` (traced scalar — one compilation covers every slot)."""

    def put(p, o):
        return jax.lax.dynamic_update_slice(p, o, (0, slot, 0, 0, 0))

    return KVCache(
        k=put(pool.k, one.k),
        v=put(pool.v, one.v),
        pos=jax.lax.dynamic_update_slice(
            pool.pos, one.pos.astype(pool.pos.dtype), (slot,)
        ),
        k_scale=None if pool.k_scale is None else put(pool.k_scale, one.k_scale),
        v_scale=None if pool.v_scale is None else put(pool.v_scale, one.v_scale),
    )


class _Sequence:
    __slots__ = (
        "rid", "tokens", "remaining", "future", "submitted", "ctx",
        "gen_ctx",
    )

    def __init__(self, rid, remaining: int, future: ServeFuture, ctx=None):
        self.rid = rid
        self.tokens: list[int] = []
        self.remaining = remaining
        self.future = future
        self.submitted = time.perf_counter()
        # ctx: the submitter's span context (captured at submit — the
        # decode worker thread has no ambient context); gen_ctx: the
        # pre-allocated slot-span ids so the prefill recorded at admit
        # parents on the generation span recorded at retire
        self.ctx = ctx
        self.gen_ctx = None


class DecodeLoop:
    """Continuous-batching generation over a fixed pool of decode slots.

    ``submit`` queues a prompt and returns a future resolving to the
    generated ``(n,) int32`` tokens (EOS included when hit); ``step``
    admits queued prompts into free slots, advances every active slot
    one token, and retires finished sequences. ``run`` drives steps
    until a set of futures resolves (tests); a server runs
    :meth:`worker` in a thread instead.

    Sampling config is fixed per loop (it is baked into the two
    compiled programs); prompts are bucketed to ``prefill_buckets``
    widths so prefill compiles once per bucket, not once per length.
    """

    def __init__(
        self,
        model,
        *,
        slots: int = 8,
        s_max: int = 512,
        kv_dtype: str | None = None,
        temperature: float = 0.0,
        top_k: int = 0,
        top_p: float = 0.0,
        eos_id: int | None = None,
        max_new: int = 64,
        prefill_buckets: Sequence[int] | None = None,
        seed: int = 0,
    ):
        if slots < 1:
            raise ValueError(f"slots={slots}: need >= 1")
        from keystone_tpu.models.lm.decode import refuse_unservable

        # window layers, a head count per layer: refused by name here,
        # before a pool is sized for a cache that cannot hold them
        refuse_unservable(model)
        self.model = model
        self.slots = slots
        self.s_max = s_max
        self.kv_dtype = kv_dtype
        self.temperature = temperature
        self.top_k = top_k
        self.top_p = top_p
        self.eos_id = eos_id
        self.default_max_new = max_new
        if prefill_buckets is None:
            # the ladder must COVER every admissible prompt length
            # (prompt.size <= s_max at submit): a top bucket below s_max
            # would silently recompile prefill per distinct long-prompt
            # length on the request path, breaking warm()'s
            # ahead-of-traffic guarantee
            buckets, b = [], 8
            while b < s_max:
                buckets.append(b)
                b *= 4
            buckets.append(s_max)
            prefill_buckets = buckets
        self.prefill_buckets = tuple(sorted(prefill_buckets))
        self._key = jax.random.key(seed)
        self._steps = 0
        self._lock = threading.Lock()
        self._work = threading.Condition(self._lock)
        self._queue: collections.deque = collections.deque()
        self._slots: list[_Sequence | None] = [None] * slots
        self._tok = np.zeros(slots, np.int32)
        self.cache = self._empty_cache()
        # occupancy accounting for the batch-fill telemetry the
        # serving panel reports
        self.tokens_out = 0
        self.occupancy_steps = 0  # sum of active slots over steps

    # ------------------------------------------------------------- state

    def _empty_cache(self) -> KVCache:
        m = self.model
        d = m.embed.shape[-1]
        hd = d // m.num_heads
        kvh = m.kv_heads
        depth = len(m.blocks)
        shape = (depth, self.slots, kvh, self.s_max, hd)
        if self.kv_dtype == "int8":
            return KVCache(
                k=jnp.zeros(shape, jnp.int8),
                v=jnp.zeros(shape, jnp.int8),
                pos=jnp.zeros(self.slots, jnp.int32),
                k_scale=jnp.zeros((*shape[:-1], 1), jnp.float32),
                v_scale=jnp.zeros((*shape[:-1], 1), jnp.float32),
            )
        cdt = jnp.dtype(m.compute_dtype)
        return KVCache(
            k=jnp.zeros(shape, cdt),
            v=jnp.zeros(shape, cdt),
            pos=jnp.zeros(self.slots, jnp.int32),
        )

    def _next_key(self):
        self._steps += 1
        return jax.random.fold_in(self._key, self._steps)

    # ------------------------------------------------------------ submit

    def max_prompt_len(self, max_new: int | None = None) -> int:
        return self.s_max - (max_new or self.default_max_new)

    def submit(
        self, prompt, max_new: int | None = None, rid: Any = None
    ) -> ServeFuture:
        """Queue one prompt ((n,) ints). Returns the future of its
        generated tokens."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        max_new = max_new or self.default_max_new
        fut = ServeFuture()
        if max_new < 1:
            fut.set_exception(ValueError(f"max_new={max_new}: need >= 1"))
            return fut
        if prompt.size < 1 or prompt.size + max_new > self.s_max:
            fut.set_exception(
                ValueError(
                    f"prompt len {prompt.size} + max_new {max_new} "
                    f"exceeds the pool's s_max={self.s_max}"
                )
            )
            return fut
        with self._work:
            self._queue.append((prompt, max_new, rid, fut, _spans.current()))
            _metrics.get_registry().counter("serve_decode_requests").inc()
            self._work.notify()
        return fut

    # -------------------------------------------------------------- step

    def _admit(self) -> None:
        """Prefill queued prompts into free slots (host-side schedule)."""
        reg = _metrics.get_registry()
        while True:
            with self._lock:
                if not self._queue:
                    return
                free = next(
                    (b for b, s in enumerate(self._slots) if s is None), None
                )
                if free is None:
                    return
                prompt, max_new, rid, fut, ctx = self._queue.popleft()
            width = next(
                (w for w in self.prefill_buckets if w >= prompt.size),
                self.prefill_buckets[-1],
            )
            width = max(width, prompt.size)
            padded = np.zeros((1, width), np.int32)
            padded[0, : prompt.size] = prompt
            span_log = _spans.active_span_log()
            t_pre0 = time.perf_counter()
            logits, one = _jit_prefill(
                self.model,
                jnp.asarray(padded),
                self.s_max,
                self.kv_dtype,
                jnp.asarray([prompt.size], jnp.int32),
            )
            tok0 = int(
                _pick(
                    logits, self._next_key(), self.temperature, self.top_k,
                    self.top_p,
                )[0]
            )
            seq = _Sequence(rid, max_new, fut, ctx=ctx)
            if span_log is not None:
                # slot-span scaffolding: the generation span's ids are
                # allocated NOW so the prefill can parent on it, but the
                # span itself is recorded at retire (when its wall is
                # known)
                seq.gen_ctx = _spans.make_context(ctx)
                span_log.record_span(
                    "decode.prefill",
                    wall_s=time.perf_counter() - t_pre0,
                    bucket="compute",
                    parent=seq.gen_ctx,
                    rid=rid,
                    width=width,
                    slot=free,
                )
            seq.tokens.append(tok0)
            seq.remaining = max_new - 1
            self.tokens_out += 1
            with self._lock:
                self.cache = _merge_slot(self.cache, one, free)
                self._tok[free] = tok0
                self._slots[free] = seq
            reg.counter("serve_decode_prefills").inc()
            if seq.remaining == 0 or (
                self.eos_id is not None and tok0 == self.eos_id
            ):
                self._retire(free)

    def _retire(self, slot: int) -> None:
        with self._lock:
            seq, self._slots[slot] = self._slots[slot], None
        if seq is not None:
            _metrics.get_registry().counter("serve_decode_finished").inc()
            seq.future.set_result(np.asarray(seq.tokens, np.int32))
            wall = time.perf_counter() - seq.submitted
            # one source="serve" stream row per finished generation —
            # the serving panel's decode line (one global read when no
            # telemetry sink is active)
            steplog = _telemetry.active_step_log()
            if steplog is not None:
                steplog.record(
                    "serve",
                    kind="decode",
                    tokens=len(seq.tokens),
                    wall_s=round(wall, 6),
                    slots=self.slots,
                )
            # the slot span: submit→retire wall of this generation,
            # with the admit-time prefill as its child (gen_ctx was
            # pre-allocated at admit; structural — the prefill and the
            # pooled steps carry the classified time)
            if seq.gen_ctx is not None:
                span_log = _spans.active_span_log()
                if span_log is not None:
                    span_log.record_span(
                        "serve.generate",
                        wall_s=wall,
                        parent=seq.ctx,
                        ctx=seq.gen_ctx,
                        rid=seq.rid,
                        tokens=len(seq.tokens),
                        slot=slot,
                    )

    def step(self) -> int:
        """Admit + one pooled decode step. Returns the number of active
        slots that advanced (0 = pool idle)."""
        self._admit()
        with self._lock:
            active = [b for b, s in enumerate(self._slots) if s is not None]
            tok = jnp.asarray(self._tok)
            cache = self.cache
        if not active:
            return 0
        tok2, cache2 = _pool_step(
            self.model, tok, cache, self._next_key(),
            self.temperature, self.top_k, self.top_p,
        )
        t = np.asarray(tok2)
        finished: list[int] = []
        with self._lock:
            self.cache = cache2
            for b in active:
                seq = self._slots[b]
                if seq is None:
                    continue
                tb = int(t[b])
                self._tok[b] = tb
                seq.tokens.append(tb)
                seq.remaining -= 1
                self.tokens_out += 1
                if seq.remaining == 0 or (
                    self.eos_id is not None and tb == self.eos_id
                ):
                    finished.append(b)
        for b in finished:
            self._retire(b)
        reg = _metrics.get_registry()
        reg.counter("serve_decode_steps").inc()
        reg.counter("serve_decode_tokens").inc(len(active))
        reg.gauge("serve_slots_active").set(float(len(active)))
        reg.gauge("serve_slot_fill").set(len(active) / self.slots)
        self.occupancy_steps += len(active)
        return len(active)

    # ------------------------------------------------------------ drivers

    def pending(self) -> bool:
        with self._lock:
            return bool(self._queue) or any(
                s is not None for s in self._slots
            )

    def run(self, prompts: Sequence[Any], max_new: int | None = None) -> list:
        """Submit every prompt, drive steps until all resolve, return
        the generated token arrays in submit order."""
        futs = [self.submit(p, max_new=max_new) for p in prompts]
        while not all(f.done() for f in futs):
            if self.step() == 0 and not self.pending():
                break
        return [f.result(timeout=0) for f in futs]

    def worker(self, stop: threading.Event, idle_wait_s: float = 0.05) -> None:
        """Server decode thread: step while there is work, park on the
        condition when idle, exit when ``stop`` is set (draining what is
        already in flight first — the SIGTERM contract)."""
        while True:
            if self.step():
                continue
            if stop.is_set():
                if not self.pending():
                    return
                continue
            with self._work:
                if not self._queue and not any(
                    s is not None for s in self._slots
                ):
                    self._work.wait(timeout=idle_wait_s)

    def warm(self) -> float:
        """Compile every program the loop can need — the pooled step,
        each prefill bucket, the slot merge, the first-token pick —
        before traffic arrives. The executables come back from the
        persistent compilation cache (``core/runtime.py``), so a
        relaunched server warms in seconds. Returns wall seconds."""
        t0 = time.perf_counter()
        reg = _metrics.get_registry()
        for width in self.prefill_buckets:
            logits, one = _jit_prefill(
                self.model,
                jnp.zeros((1, width), jnp.int32),
                self.s_max,
                self.kv_dtype,
                jnp.asarray([1], jnp.int32),
            )
            reg.counter("serve_aot_compiled", kind="prefill").inc()
        _merge_slot(self.cache, one, 0)
        _pick(
            logits, self._key, self.temperature, self.top_k, self.top_p
        )
        tok2, _ = _pool_step(
            self.model,
            jnp.zeros(self.slots, jnp.int32),
            self.cache,
            self._key,
            self.temperature,
            self.top_k,
            self.top_p,
        )
        jax.block_until_ready(tok2)
        reg.counter("serve_aot_compiled", kind="decode_pool").inc()
        wall = time.perf_counter() - t0
        logger.info(
            "decode pool warm: %d slots, s_max %d, %d prefill bucket(s) "
            "in %.2fs", self.slots, self.s_max, len(self.prefill_buckets),
            wall,
        )
        return wall
