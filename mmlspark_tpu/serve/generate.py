"""Autoregressive token serving — slot-based continuous batching.

The serve plane's streaming-generate traffic class (Orca-style
iteration-level scheduling with the KV-cache as explicit device state,
the vLLM insight), built from three repo primitives:

* the KV-cache is a **stateful plan segment**
  (:class:`~mmlspark_tpu.core.plan.StatefulSegment`): one slot-major
  pair ``[slots, layers, heads, T_max, head_dim]`` allocated per engine,
  carried as a *donated* argument so every prefill/decode program
  updates it in place — no per-token reallocation, no H2D re-upload;
* **prefill** packs waiting prompts through a PR 15 length-bucketed
  ladder (``GenerateConfig.prefill_buckets`` — validated, warmable) at a
  fixed row width, runs the full causal forward once, and scatters each
  prompt's per-layer K/V into its assigned slot (pad rows scatter to the
  out-of-bounds slot id and are dropped by XLA);
* **decode** is ONE fixed-shape program ``[slots]`` forever: requests
  join and leave per token step via the active-slot mask, inactive
  rows' cache writes are masked off, and the per-row argmax is greedy —
  so a request's token stream is **bit-identical** whether it decodes
  alone or packed with churning neighbors (row independence through the
  SAME compiled program; the correctness anchor the tier-1 gate pins
  against :meth:`GenerateBatcher.oneshot`).

Total compiled programs ≤ ``len(prefill_buckets) + 1``, counted
honestly via :func:`mmlspark_tpu.obs.runtime.compiled_programs` over
the engine's own plan cache (the engine is its own cache host).

The decode loop never blocks on the token it just dispatched: the host
fetch lags one step (consume step *t* while step *t+1* computes), the
carry token rides forward on the device, and prompt joins inject their
prefill token through the in-program merge — the JX109 lint exists to
keep it that way.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any

import numpy as np

from mmlspark_tpu.core.logging_utils import get_logger
from mmlspark_tpu.obs import flight as _obs_flight
from mmlspark_tpu.obs import runtime as _obs_rt
from mmlspark_tpu.obs.lockwitness import named_condition
from mmlspark_tpu.obs.spans import span as _obs_span
from mmlspark_tpu.serve import faults as _faults
from mmlspark_tpu.serve.batcher import THREAD_PREFIX
from mmlspark_tpu.serve.config import GenerateConfig
from mmlspark_tpu.serve.errors import BadRequest, Overloaded, ServerClosed
from mmlspark_tpu.serve.faults import InjectedFault
from mmlspark_tpu.serve.stats import ServerStats

_log = get_logger(__name__)


# ---- the two programs (built once per engine; also what the SPMD
#      entry point `serve_decode_replica` traces) ----

def build_prefill_step(model):
    """``(bufs, params, tokens [P, L], attn_mask [P, L], lengths [P],
    slot_ids [P]) -> (bufs', first_token [P])`` — the prefill program.

    One full causal forward over the packed prompt batch; every layer's
    K/V scatters into the slot-major cache at the assigned slots (a pad
    row carries ``slot_id == slots``, out of bounds, which XLA drops
    from the scatter — the guard that keeps pad rows from clobbering a
    live slot), and the returned first token is the greedy argmax at
    each prompt's last real position."""
    import jax.numpy as jnp

    def prefill_step(bufs, params, tokens, attn_mask, lengths, slot_ids):
        L = tokens.shape[1]
        logits, (pk, pv) = model.apply(
            {"params": params}, tokens, mask=attn_mask, return_cache=True)
        # the cache is allocated in the model's own K/V dtype; the cast
        # is explicit so a mismatch can never ride an implicit scatter
        # conversion (which jax is turning into an error)
        ck = bufs["k"].at[slot_ids, :, :, :L, :].set(
            pk.astype(bufs["k"].dtype))
        cv = bufs["v"].at[slot_ids, :, :, :L, :].set(
            pv.astype(bufs["v"].dtype))
        last = jnp.take_along_axis(
            logits, (lengths - 1)[:, None, None], axis=1)[:, 0]
        first = jnp.argmax(last, axis=-1).astype(jnp.int32)
        return {"k": ck, "v": cv}, first

    return prefill_step


def build_decode_step(model, decode_attention_fn=None):
    """``(bufs, params, carry [S], injected [S], inject [S], positions
    [S], active [S]) -> (bufs', next_token [S])`` — THE decode program.

    ``carry`` is the previous step's own output (a device array that
    never visits the host on the hot path); a slot that just joined
    overrides it with its prefill token through ``inject``. The model
    writes the new token's K/V at ``positions`` (inactive rows masked
    off), attends ``q_len=1`` against the cache, and the next token is
    the greedy per-row argmax — inactive rows pass their input through
    unchanged, so the program's shape (and its ONE compilation) never
    depends on who is active."""
    import jax.numpy as jnp

    def decode_step(bufs, params, carry, injected, inject, positions,
                    active):
        tokens = jnp.where(inject, injected, carry).astype(jnp.int32)
        logits, (ck, cv) = model.apply(
            {"params": params}, tokens[:, None],
            cache=(bufs["k"], bufs["v"]), positions=positions,
            update_mask=active, decode_attention_fn=decode_attention_fn)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        nxt = jnp.where(active, nxt, tokens)
        return {"k": ck, "v": cv}, nxt

    return decode_step


# ---- per-request surfaces ----

class TokenStream:
    """Streaming handle for one generate request.

    Iterate to receive tokens as they are produced, or block on
    :meth:`result` for the full list. Terminal exactly once: finished
    (``cancelled`` True when a churn cancel truncated it — the stream
    delivered a *prefix* of the full decode, never a wrong token) or
    failed with one typed error.
    """

    __slots__ = ("model", "_cv", "_tokens", "_done", "_error", "cancelled")

    def __init__(self, model: str):
        self.model = model
        self._cv = named_condition("serve.generate.TokenStream._cv")
        self._tokens: list[int] = []
        self._done = False
        self._error: BaseException | None = None
        self.cancelled = False

    # -- engine side --

    def _push(self, tok: int) -> None:
        with self._cv:
            self._tokens.append(tok)
            self._cv.notify_all()

    def _finish(self, cancelled: bool = False) -> None:
        with self._cv:
            self._done = True
            self.cancelled = cancelled
            self._cv.notify_all()

    def _fail(self, err: BaseException) -> None:
        with self._cv:
            self._error = err
            self._done = True
            self._cv.notify_all()

    # -- client side --

    @property
    def done(self) -> bool:
        with self._cv:
            return self._done

    @property
    def tokens(self) -> list[int]:
        """Snapshot of everything streamed so far."""
        with self._cv:
            return list(self._tokens)

    def __iter__(self):
        i = 0
        while True:
            with self._cv:
                while len(self._tokens) <= i and not self._done:
                    self._cv.wait()
                if len(self._tokens) > i:
                    tok = self._tokens[i]
                else:
                    if self._error is not None:
                        raise self._error
                    return
            yield tok
            i += 1

    def result(self, timeout: float | None = None) -> list[int]:
        """Block until terminal; the full token list, or the typed
        error."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._cv:
            while not self._done:
                rem = (None if deadline is None
                       else deadline - time.monotonic())
                if rem is not None and rem <= 0:
                    raise TimeoutError(
                        f"model {self.model!r}: stream not terminal "
                        f"within {timeout}s")
                self._cv.wait(rem)
            if self._error is not None:
                raise self._error
            return list(self._tokens)


class GenerateRequest:
    """Engine-internal state of one admitted generate request."""

    __slots__ = ("prompt", "max_new", "stream", "slot", "emitted",
                 "steps_done", "steps_needed", "done", "cancelled",
                 "submitted", "last_token_t")

    def __init__(self, prompt: list[int], max_new: int,
                 stream: TokenStream):
        self.prompt = prompt
        self.max_new = max_new
        self.stream = stream
        self.slot: int | None = None
        self.emitted = 0
        self.steps_done = 0
        self.steps_needed = max_new - 1  # prefill delivers token 1
        self.done = False
        self.cancelled = False
        self.submitted = time.monotonic()
        self.last_token_t = self.submitted


class SlotTable:
    """Slot ownership ledger — the no-double-assignment invariant.

    Assignment and release are the ONLY mutation points, both called
    with the engine lock held; a slot handed out while still owned, or
    released by a non-owner, is an engine bug the chaos gate must see
    as a raise, never as silent cache corruption."""

    __slots__ = ("_owner",)

    def __init__(self, slots: int):
        self._owner: list[GenerateRequest | None] = [None] * slots

    def assign(self, req: GenerateRequest) -> int | None:
        """First free slot (None when full)."""
        for s, owner in enumerate(self._owner):
            if owner is None:
                if req.slot is not None:
                    raise RuntimeError(
                        f"request already owns slot {req.slot}")
                self._owner[s] = req
                req.slot = s
                return s
        return None

    def release(self, req: GenerateRequest) -> None:
        s = req.slot
        if s is None or self._owner[s] is not req:
            raise RuntimeError(
                f"slot release by non-owner (slot={s}) — "
                "double-assignment or double-release")
        self._owner[s] = None
        req.slot = None

    @property
    def free(self) -> int:
        return sum(1 for o in self._owner if o is None)

    def owner(self, s: int) -> GenerateRequest | None:
        return self._owner[s]


class GenerateBatcher:
    """Continuous-batching token engine for ONE causal model.

    ``model`` is a cache-capable module (``TransformerTagger`` with
    ``causal=True``); ``params`` its fitted variables. The engine owns
    the slot-major KV-cache as plan-managed device state, packs waiting
    prompts through the prefill ladder, and runs the single fixed-shape
    decode program with per-step join/leave. One engine thread does
    everything ordered (prefill ↔ decode interleave at step
    granularity), so slot assignment needs no cross-thread dance —
    the :class:`SlotTable` invariants still raise if the ordering is
    ever broken."""

    def __init__(self, name: str, model: Any, params: Any,
                 config: GenerateConfig | None = None,
                 stats: ServerStats | None = None,
                 decode_attention_fn: Any = None):
        import jax
        import jax.numpy as jnp

        from mmlspark_tpu.core import plan

        if not getattr(model, "causal", False):
            raise BadRequest(
                f"model {name!r}: token generation needs a causal "
                "model (causal=True)")
        self.name = name
        self.model = model
        self.config = config or GenerateConfig()
        self.stats = stats or ServerStats(self.config.stats_window,
                                          model=name)
        self._params = params
        cfg = self.config
        S = cfg.slots
        layers = model.num_layers
        heads = model.num_heads
        hd = model.embed_dim // model.num_heads
        shape = (S, layers, heads, cfg.t_max, hd)
        # the cache holds what the model's attention layers produce: a
        # bf16 model gets a bf16 cache (half the HBM), read off an
        # abstract trace of the prefill forward — nothing executes
        kv_dtype = jax.eval_shape(
            lambda p, t: model.apply({"params": p}, t, return_cache=True),
            params, jax.ShapeDtypeStruct((1, cfg.prefill_buckets[0]),
                                         jnp.int32))[1][0].dtype
        self._state = plan.allocate_segment_state(
            f"{name}.kv", {"k": shape, "v": shape}, dtype=kv_dtype)
        # the engine IS the cache host: obs.runtime.compiled_programs
        # walks this object's _plan_cache, so the two stateful programs
        # below are the ONLY entries and the ladder budget is auditable
        self._prefill = plan.StatefulSegment(
            "generate.prefill", build_prefill_step(model), self._state,
            cache_host=self)
        self._decode = plan.StatefulSegment(
            "generate.decode",
            build_decode_step(model, decode_attention_fn), self._state,
            cache_host=self)
        # host mirror of the device-side slot state (engine-thread only
        # once running; guarded by _cv during startup/submit)
        self._slots = SlotTable(S)
        self._positions = np.zeros(S, np.int32)
        self._inject_tok = np.zeros(S, np.int32)
        self._inject = np.zeros(S, bool)
        self._mask = np.zeros(S, bool)
        self._carry = jnp.zeros(S, jnp.int32)
        # lagged-consume state: (out device array, per-slot request refs
        # at dispatch time, active snapshot)
        self._pending: tuple | None = None
        self._cv = named_condition("serve.generate.GenerateBatcher._cv")
        self._queue: deque[GenerateRequest] = deque()
        self._closed = False
        self._abort = False
        self._hb = f"serve/{name}/generate"
        self._thread = threading.Thread(
            target=self._run, name=f"{THREAD_PREFIX}[{name}]/generate",
            daemon=True)
        self._thread.start()

    # -- admission --

    def submit(self, prompt, max_new_tokens: int | None = None
               ) -> TokenStream:
        """Admit one prompt; returns its :class:`TokenStream`."""
        prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
        if not prompt:
            raise BadRequest(f"model {self.name!r}: empty prompt")
        max_new = (self.config.max_new_tokens if max_new_tokens is None
                   else int(max_new_tokens))
        if max_new < 1:
            raise BadRequest(
                f"model {self.name!r}: max_new_tokens must be >= 1")
        self.config.prefill_bucket_for(len(prompt), self.name)
        if len(prompt) + max_new > self.config.t_max:
            raise BadRequest(
                f"model {self.name!r}: prompt ({len(prompt)}) + "
                f"max_new_tokens ({max_new}) exceeds the cache horizon "
                f"t_max={self.config.t_max}")
        stream = TokenStream(self.name)
        req = GenerateRequest(prompt, max_new, stream)
        with self._cv:
            if self._closed:
                raise ServerClosed(
                    f"model {self.name!r} is shutting down",
                    retry_after_s=self.config.retry_after_s)
            if len(self._queue) >= self.config.max_queue:
                self.stats.record_rejected()
                raise Overloaded(self.name, len(self._queue),
                                 self.config.max_queue,
                                 retry_after_s=self.config.retry_after_s)
            self._queue.append(req)
            self.stats.record_generate_admitted(len(prompt))
            self._cv.notify()
        return stream

    @property
    def queued(self) -> int:
        with self._cv:
            return len(self._queue)

    def compiled_programs(self) -> int | None:
        """Live XLA program count over the engine's two stateful
        entries — the ladder-budget observable (≤ prefill buckets + 1)."""
        return _obs_rt.compiled_programs(self)

    # -- the engine loop --

    def _run(self) -> None:
        try:
            self._loop()
        except BaseException as e:  # noqa: BLE001 — no stranded stream
            _log.exception("GenerateBatcher[%s] engine loop died",
                           self.name)
            self._fail_outstanding(e)
            if _obs_flight._rec is not None:
                _obs_flight._rec.disarm(self._hb)

    def _fail_outstanding(self, err: BaseException) -> None:
        with self._cv:
            leftovers = list(self._queue)
            self._queue.clear()
            active = [self._slots.owner(s)
                      for s in range(self.config.slots)]
        for req in leftovers + [r for r in active if r is not None]:
            if not req.done:
                req.done = True
                req.stream._fail(err)
                self.stats.record_failed()

    def _loop(self) -> None:
        while True:
            with self._cv:
                if self._abort:
                    break
            worked = False
            group = self._next_prefill_group()
            if group:
                self._do_prefill(group)
                worked = True
            if self._mask.any():
                self._churn_tick()
                self.advance_decode()
                worked = True
            elif self._pending is not None:
                # trailing lagged output after the last active slot left
                self._consume(self._pending)
                self._pending = None
                worked = True
            if worked:
                if _obs_flight._rec is not None:
                    _obs_flight._rec.beat(self._hb)
                continue
            with self._cv:
                if self._queue:
                    continue  # raced with a submit
                if self._closed or self._abort:
                    break
                if _obs_flight._rec is not None:
                    _obs_flight._rec.disarm(self._hb)
                self._cv.wait()
        self._shutdown_flush()

    def _shutdown_flush(self) -> None:
        """Terminal sweep: every admitted request must resolve."""
        if self._pending is not None:
            self._consume(self._pending)
            self._pending = None
        err = ServerClosed(f"model {self.name!r} closed")
        with self._cv:
            leftovers = list(self._queue)
            self._queue.clear()
        for req in leftovers:
            req.done = True
            req.stream._fail(err)
            self.stats.record_failed()
        for s in range(self.config.slots):
            req = self._slots.owner(s)
            if req is not None and not req.done:
                req.done = True
                self._mask[s] = False
                self._slots.release(req)
                req.stream._fail(err)
                self.stats.record_failed()
        if _obs_flight._rec is not None:
            _obs_flight._rec.disarm(self._hb)

    def _next_prefill_group(self) -> list[GenerateRequest]:
        """FIFO prompts sharing ONE prefill bucket, up to the free-slot
        and row-width caps. Same-bucket-only packing is the bit-identity
        discipline: a prompt must go through the same ℓ-program whether
        it prefills alone or packed (row independence covers the rest)."""
        cfg = self.config
        group: list[GenerateRequest] = []
        with self._cv:
            free = self._slots.free
            cap = min(free, cfg.prefill_rows)
            bucket = None
            while self._queue and len(group) < cap:
                req = self._queue[0]
                b = cfg.prefill_bucket_for(len(req.prompt), self.name)
                if bucket is None:
                    bucket = b
                elif b != bucket:
                    break
                self._queue.popleft()
                group.append(req)
        return group

    def _do_prefill(self, group: list[GenerateRequest]) -> None:
        cfg = self.config
        S = cfg.slots
        bucket = cfg.prefill_bucket_for(len(group[0].prompt), self.name)
        P = cfg.prefill_rows
        toks = np.zeros((P, bucket), np.int32)
        am = np.zeros((P, bucket), bool)
        lengths = np.ones(P, np.int32)
        slot_ids = np.full(P, S, np.int32)  # pad rows scatter off-range
        with self._cv:
            for r, req in enumerate(group):
                s = self._slots.assign(req)
                assert s is not None  # group was capped at free slots
                n = len(req.prompt)
                toks[r, :n] = req.prompt
                am[r, :n] = True
                lengths[r] = n
                slot_ids[r] = s
        labels = ({"model": self.name, "bucket": bucket,
                   "rows": len(group)} if _obs_rt._enabled else None)
        try:
            with _obs_span("serve/prefill", "serve", labels):
                first = self._prefill.dispatch(self._params, toks, am,
                                               lengths, slot_ids)
                # prefill is the TTFT seam, not the decode loop: the
                # blocking fetch here is what time-to-first-token means
                vals = np.asarray(first)
        except BaseException as e:  # noqa: BLE001 — relayed per stream
            with self._cv:
                for req in group:
                    req.done = True
                    self._slots.release(req)
            for req in group:
                req.stream._fail(e)
                self.stats.record_failed()
            return
        now = time.monotonic()
        for r, req in enumerate(group):
            tok = int(vals[r])
            self.stats.record_ttft((now - req.submitted) * 1e3)
            req.stream._push(tok)
            req.emitted = 1
            req.last_token_t = now
            self.stats.record_tokens(1)
            s = req.slot
            if req.max_new == 1 or tok == cfg.eos_token:
                self._retire(req, now)
                continue
            self._positions[s] = len(req.prompt)
            self._inject_tok[s] = tok
            self._inject[s] = True
            self._mask[s] = True

    def advance_decode(self) -> None:
        """One token step: dispatch the fixed-shape decode program over
        the current slot state, then consume the PREVIOUS step's output
        (the one-step-lagged host fetch — step *t+1* computes while
        step *t*'s tokens stream out)."""
        import jax.numpy as jnp

        S = self.config.slots
        act = self._mask.copy()
        refs = [self._slots.owner(s) for s in range(S)]
        # the dispatch is asynchronous and the host→device transfer may
        # read (on the CPU backend: alias) the numpy buffers it is
        # handed after this call returns — the mirrors are mutated on
        # the very next lines, so the program gets private copies
        out = self._decode.dispatch(
            self._params, self._carry,
            jnp.asarray(self._inject_tok.copy()),
            jnp.asarray(self._inject.copy()),
            jnp.asarray(self._positions.copy()), jnp.asarray(act))
        self._carry = out
        self._inject[:] = False
        n_active = int(act.sum())
        self.stats.record_decode_step(n_active, S)
        for s in np.nonzero(act)[0]:
            req = refs[s]
            self._positions[s] += 1
            req.steps_done += 1
            if req.steps_done >= req.steps_needed:
                # generation budget reached: this dispatch was the
                # request's last — nothing further joins the batch, and
                # the lagged consume below (next call) retires it
                self._mask[s] = False
        prev, self._pending = self._pending, (out, refs, act)
        if prev is not None:
            self._consume(prev)

    def _consume(self, pending: tuple) -> None:
        out, refs, act = pending
        vals = np.asarray(out)  # lint-jax: allow(JX109) — one-step
        # lagged: this output's step already overlapped the dispatch
        # above; the fetch drains a finished computation
        now = time.monotonic()
        cfg = self.config
        for s in np.nonzero(act)[0]:
            req = refs[s]
            if req is None or req.done:
                continue
            if req.cancelled:
                self._retire(req, now, cancelled=True)
                continue
            tok = int(vals[s])
            req.stream._push(tok)
            self.stats.record_itl((now - req.last_token_t) * 1e3)
            self.stats.record_tokens(1)
            req.last_token_t = now
            req.emitted += 1
            if req.emitted >= req.max_new or tok == cfg.eos_token:
                self._retire(req, now)

    def _retire(self, req: GenerateRequest, now: float,
                cancelled: bool = False) -> None:
        req.done = True
        with self._cv:
            if req.slot is not None:
                self._mask[req.slot] = False
                self._slots.release(req)
        req.stream._finish(cancelled=cancelled)
        if cancelled:
            self.stats.record_generate_cancelled()
        self.stats.record_done((now - req.submitted) * 1e3, 0.0)

    def _churn_tick(self) -> None:
        """The ``generate_cancel`` injection point: a seeded churn plan
        models clients abandoning streams mid-decode. The oldest active
        request is cancelled — its slot frees at the next lagged
        consume, exactly the join/leave path real traffic exercises."""
        try:
            _faults.hit("generate_cancel", model=self.name)
        except InjectedFault:
            oldest = None
            for s in np.nonzero(self._mask)[0]:
                req = self._slots.owner(int(s))
                if req is not None and not req.cancelled and (
                        oldest is None
                        or req.submitted < oldest.submitted):
                    oldest = req
            if oldest is not None:
                oldest.cancelled = True

    # -- the one-shot reference (the bit-identity anchor) --

    def oneshot(self, prompt, max_new_tokens: int | None = None
                ) -> list[int]:
        """Whole-sequence decode of one prompt through the SAME two
        compiled programs on FRESH buffers (no engine state touched, no
        stats): prefill alone, then decode alone to the budget. The
        tier-1 gate pins every continuously-batched stream bit-identical
        to this."""
        import jax.numpy as jnp

        cfg = self.config
        prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
        max_new = (cfg.max_new_tokens if max_new_tokens is None
                   else int(max_new_tokens))
        S = cfg.slots
        bucket = cfg.prefill_bucket_for(len(prompt), self.name)
        live = self._state.buffers["k"]
        bufs = {"k": jnp.zeros(live.shape, live.dtype),
                "v": jnp.zeros(live.shape, live.dtype)}
        P = cfg.prefill_rows
        toks = np.zeros((P, bucket), np.int32)
        am = np.zeros((P, bucket), bool)
        lengths = np.ones(P, np.int32)
        slot_ids = np.full(P, S, np.int32)
        n = len(prompt)
        toks[0, :n] = prompt
        am[0, :n] = True
        lengths[0] = n
        slot_ids[0] = 0
        bufs, first = self._prefill.jitted(bufs, self._params, toks, am,
                                           lengths, slot_ids)
        tokens = [int(np.asarray(first)[0])]
        if max_new == 1 or tokens[0] == cfg.eos_token:
            return tokens
        carry = jnp.zeros(S, jnp.int32)
        inject_tok = np.zeros(S, np.int32)
        inject = np.zeros(S, bool)
        positions = np.zeros(S, np.int32)
        active = np.zeros(S, bool)
        inject_tok[0] = tokens[0]
        inject[0] = True
        positions[0] = n
        active[0] = True
        for _ in range(max_new - 1):
            # copies for the two arrays mutated right after the async
            # dispatch (same aliasing hazard as advance_decode)
            bufs, carry = self._decode.jitted(
                bufs, self._params, carry, jnp.asarray(inject_tok),
                jnp.asarray(inject.copy()), jnp.asarray(positions.copy()),
                jnp.asarray(active))
            inject[0] = False
            positions[0] += 1
            # the reference path is DELIBERATELY synchronous: one
            # request, one token per round-trip — it exists to anchor
            # bit-identity, not to be fast
            tok = int(np.asarray(carry)[0])  # lint-jax: allow(JX109)
            tokens.append(tok)
            if tok == cfg.eos_token:
                break
        return tokens

    # -- lifecycle --

    def close(self, drain: bool = True) -> None:
        """Stop admission; ``drain=True`` finishes every admitted
        stream first, ``drain=False`` fails outstanding work typed.
        Idempotent; joins the engine thread (no leaked thread)."""
        with self._cv:
            self._closed = True
            if not drain:
                self._abort = True
            self._cv.notify_all()
        self._thread.join(timeout=self.config.drain_timeout_s)
        if self._thread.is_alive():  # pragma: no cover - defensive
            _log.warning("GenerateBatcher[%s] did not stop within %.1fs",
                         self.name, self.config.drain_timeout_s)
        elif _obs_flight._rec is not None:
            _obs_flight._rec.forget(self._hb)
