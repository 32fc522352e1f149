"""Static-shape serving fast path: bucketed jit dispatch with donated caches.

Every distinct (batch, prompt length, max_new) triple hitting a jitted
generate function triggers a fresh XLA compile, so online traffic through
the admission Scheduler — whose micro-batches vary in size tick to tick —
recompiles on nearly every batch.  This module removes that tax:

* **Bucketing** — micro-batches are padded up to a small fixed ladder of
  shapes (:class:`BucketLadder`, powers-of-two by default).  Batch rows
  are padded by *replicating row 0* (generation is row-independent, so
  padding rows cannot perturb real rows); token axes are right-padded
  with ``pad_id`` (position -1 → masked out, pinned by
  ``test_generate_padded_equals_unpadded``).  Outputs are sliced back to
  the caller's true shape.
* **Jit caching** — one jitted callable per bucket, compiled on first
  use (or eagerly via :meth:`warm`) and reused forever after: steady
  traffic hits zero recompiles.  ``compiles`` exposes the live XLA
  compile count for tests and benchmarks.
* **Cache donation** — the KV/decode cache is a persistent per-bucket
  buffer threaded through the jitted call with ``donate_argnums``, so
  XLA writes the step-final cache back into the same HBM allocation:
  zero cache reallocations in steady state.  Stale state is neutralized
  by ``generate.reset_cache`` inside the jit (position slots → -1, SSM
  state → 0).  Donation is skipped automatically on backends that cannot
  alias buffers (CPU).

Adding a bucket = adding one rung to the relevant :class:`BucketLadder`
tuple (see README "Performance").
"""

from __future__ import annotations

import bisect
import dataclasses
import threading
from collections import deque
from typing import Callable, Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.tokenizer import TOKENIZER
from repro.models.encdec import EncDecLM
from repro.models.transformer import DecoderLM
from repro.serve import spans
from repro.serve.generate import (
    decoder_generate_with_cache,
    encdec_decode_step,
    encdec_generate_with_cache,
    encdec_prefill_with_cache,
)


def _next_pow2(n: int) -> int:
    return 1 << max(0, int(n - 1).bit_length())


@dataclasses.dataclass(frozen=True)
class BucketLadder:
    """The fixed shape set the fast path compiles for.

    Values bucket to the smallest rung >= value; values beyond the top
    rung fall back to the next power of two (a new bucket — compiled
    once, then cached like any other).  Rungs need not be powers of two:
    the defaults pin the repo's common prompt lengths (96 = max_query_len,
    512 = max_fusion_len) so the hot shapes pad by zero."""

    batch: Tuple[int, ...] = (1, 2, 4, 8, 16, 32)
    new_tokens: Tuple[int, ...] = (8, 16, 32, 64, 128)
    prompt: Tuple[int, ...] = (32, 64, 96, 128, 256, 512)

    @staticmethod
    def _pick(value: int, rungs: Tuple[int, ...]) -> int:
        for r in rungs:
            if value <= r:
                return r
        return _next_pow2(value)

    def batch_bucket(self, b: int) -> int:
        return self._pick(b, self.batch)

    def floor_batch_rung(self, b: int) -> int:
        """Largest batch rung <= b, for batch *formation* (the Scheduler):
        dispatching exactly a rung's worth of requests means the padded
        batch equals the real batch — zero wasted rows.  Falls back to
        ``b`` itself when every rung is larger (the batch then pads up to
        ``batch_bucket(b)``, which is still a compiled-once bucket)."""
        best = 0
        for r in self.batch:
            if r <= b:
                best = r
        return best or b

    def new_bucket(self, n: int) -> int:
        return self._pick(n, self.new_tokens)

    def prompt_bucket(self, s: int) -> int:
        return self._pick(s, self.prompt)


def _donate_default() -> bool:
    # CPU cannot alias donated buffers (XLA warns and ignores); donation
    # only buys anything where HBM reuse is real.
    return jax.default_backend() in ("tpu", "gpu")


@dataclasses.dataclass
class _Entry:
    fn: object  # jitted (params, tokens, cache) -> (out_tokens, cache)
    cache: dict  # persistent per-bucket decode cache (donated each call)


class _BucketedGenerate:
    """Shared machinery: bucket lookup, padding, entry cache, stats."""

    def __init__(self, params: dict, pad_id: int, eos_id: int,
                 ladder: Optional[BucketLadder], donate: Optional[bool],
                 placement: Optional[jax.sharding.Sharding] = None):
        self.params = params
        # where the decode caches and token inputs live (None = default
        # device); params are expected to be placed there already
        self.placement = placement
        self.pad_id = pad_id
        self.eos_id = eos_id
        self.ladder = ladder or BucketLadder()
        self.donate = _donate_default() if donate is None else donate
        self._entries: Dict[Tuple[int, int, int], _Entry] = {}
        self._built = 0  # bucket compiles (fallback compile metric)
        # one generate at a time per dispatcher: entry caches are donated
        # (consumed per call), so a caller-thread warm() racing the async
        # DispatchWorker's generate on the same bucket would hand XLA an
        # already-consumed buffer
        self._call_lock = threading.Lock()
        self.stats = {"calls": 0, "padded_rows": 0, "padded_tokens": 0,
                      "direct_calls": 0}

    # -- subclass hooks -------------------------------------------------
    def _build(self, bb: int, sb: int, nb: int) -> _Entry:
        raise NotImplementedError

    def _make_cache(self, bb: int, sb: int, nb: int) -> dict:
        """Fresh decode cache for a bucket (first build + post-failure rebuild)."""
        raise NotImplementedError

    def _direct(self, tokens: np.ndarray, max_new: int) -> np.ndarray:
        """Exact-shape ad-hoc jit path (no bucket entry, no cached cache)."""
        raise NotImplementedError

    # -- compile accounting ---------------------------------------------
    @property
    def compiles(self) -> int:
        """Live XLA compile count across all buckets.  Reads the jit cache
        size when jax exposes it (it also catches intra-bucket misses,
        e.g. weak-type churn); otherwise falls back to the dispatcher's
        own bucket-build counter rather than silently flattening to a
        constant.  The entry dict is snapshotted first — monitoring reads
        race bucket creation on fan-out host executor threads, and
        iterating a dict mid-insert raises."""
        sizes = [getattr(entry.fn, "_cache_size", None)
                 for entry in list(self._entries.values())]
        if all(callable(s) for s in sizes):
            return sum(s() for s in sizes)
        return self._built

    @property
    def buckets(self) -> List[Tuple[int, int, int]]:
        return sorted(self._entries)

    def _token_bucket(self, s: int) -> int:
        """Bucketed token-axis length.  Decoder prompts right-pad safely
        (pad positions are masked out of attention); the enc-dec encoder
        has no pad masking, so its subclass keeps the length verbatim."""
        return self.ladder.prompt_bucket(s)

    def _place(self, x):
        if self.placement is None:
            return jax.tree.map(jnp.asarray, x)
        return jax.device_put(x, self.placement)

    # -- dispatch --------------------------------------------------------
    def _entry(self, bb: int, sb: int, nb: int) -> _Entry:
        key = (bb, sb, nb)
        entry = self._entries.get(key)
        if entry is None:
            entry = self._entries[key] = self._build(bb, sb, nb)
            self._built += 1
        return entry

    def __call__(self, tokens: np.ndarray, max_new: int) -> np.ndarray:
        """tokens [B, S] right-padded -> generated tokens [B, max_new]."""
        b, s = tokens.shape
        if b > self.ladder.batch[-1]:
            # one-shot offline mega-batch (e.g. a 400-row Table-1 eval):
            # padding to the next pow2 would waste up to ~2x compute and pin
            # an oversized donated cache forever — use the exact shape and
            # let its buffers die with the call
            self.stats["calls"] += 1
            self.stats["direct_calls"] += 1
            return self._direct(tokens, max_new)
        bb = self.ladder.batch_bucket(b)
        sb = self._token_bucket(s)
        nb = self.ladder.new_bucket(max_new)
        padded = np.full((bb, sb), self.pad_id, np.int32)
        padded[:b, :s] = tokens
        if bb > b:
            padded[b:] = padded[0]  # replicate a real row; rows are independent
        with self._call_lock:
            entry = self._entry(bb, sb, nb)
            try:
                out, entry.cache = entry.fn(self.params, self._place(padded),
                                            entry.cache)
            except Exception:
                # with donation active the cache buffer may already be consumed
                # even though the call failed (e.g. a transient device OOM);
                # rebuild it so the bucket isn't poisoned for all later traffic
                entry.cache = self._make_cache(bb, sb, nb)
                raise
            self.stats["calls"] += 1
            self.stats["padded_rows"] += bb - b
            self.stats["padded_tokens"] += (sb - s) * b
        return np.asarray(out)[:b, :max_new]

    def warm(self, shapes: Iterable[Tuple[int, int, int]]) -> None:
        """Pre-compile buckets: shapes are (batch, token_len, max_new),
        where token_len is the *actual* prompt/encoder length traffic will
        present (callers know it: max_query_len / max_fusion_len) — a
        guessed length would warm a bucket real traffic never hits.  Runs
        a dummy generate per shape so the jit cache (not just an AOT
        artifact) is primed."""
        for b, s, max_new in shapes:
            dummy = np.full((b, s), self.pad_id, np.int32)
            dummy[:, 0] = TOKENIZER.bos_id
            self(dummy, max_new)


class DecoderGenerateDispatcher(_BucketedGenerate):
    """Bucketed, cache-donating front-end over a decoder LM's greedy loop."""

    def __init__(self, model: DecoderLM, params: dict,
                 pad_id: int = TOKENIZER.pad_id, eos_id: int = TOKENIZER.eos_id,
                 ladder: Optional[BucketLadder] = None,
                 donate: Optional[bool] = None,
                 placement: Optional[jax.sharding.Sharding] = None):
        super().__init__(params, pad_id, eos_id, ladder, donate, placement)
        self.model = model

    def _build(self, bb: int, sb: int, nb: int) -> _Entry:
        model, pad_id, eos_id = self.model, self.pad_id, self.eos_id

        def run(params, prompt, cache):
            return decoder_generate_with_cache(
                model, params, prompt, cache, nb, pad_id, eos_id
            )

        fn = jax.jit(run, donate_argnums=(2,) if self.donate else ())
        return _Entry(fn=fn, cache=self._make_cache(bb, sb, nb))

    def _make_cache(self, bb: int, sb: int, nb: int) -> dict:
        return self._place(
            self.model.init_cache(bb, sb + nb + self.model.cfg.frontend_tokens))

    def _direct(self, tokens: np.ndarray, max_new: int) -> np.ndarray:
        from repro.serve.generate import greedy_generate

        return greedy_generate(self.model, self.params, tokens, max_new=max_new,
                               pad_id=self.pad_id, eos_id=self.eos_id)


class EncDecGenerateDispatcher(_BucketedGenerate):
    """Bucketed, cache-donating front-end over an enc-dec greedy loop
    (the GEN-FUSER hot path — every served micro-batch ends here).

    Only batch and max_new bucket; the encoder length keys the bucket
    verbatim because this encoder embeds pads like real tokens (no pad
    masking), so padding the encoder axis would perturb real rows.  The
    engine always presents a fixed ``max_fusion_len`` encoder shape, so
    the length axis is already static in practice."""

    def __init__(self, model: EncDecLM, params: dict,
                 pad_id: int = TOKENIZER.pad_id, eos_id: int = TOKENIZER.eos_id,
                 bos_id: int = TOKENIZER.bos_id,
                 ladder: Optional[BucketLadder] = None,
                 donate: Optional[bool] = None):
        super().__init__(params, pad_id, eos_id, ladder, donate)
        self.model = model
        self.bos_id = bos_id

    def _token_bucket(self, s: int) -> int:
        return s  # encoder length is part of the key — never padded

    def _build(self, bb: int, sb: int, nb: int) -> _Entry:
        model, pad_id, eos_id, bos_id = self.model, self.pad_id, self.eos_id, self.bos_id

        def run(params, enc_tokens, cache):
            return encdec_generate_with_cache(
                model, params, enc_tokens, cache, nb, pad_id, eos_id, bos_id
            )

        fn = jax.jit(run, donate_argnums=(2,) if self.donate else ())
        return _Entry(fn=fn, cache=self._make_cache(bb, sb, nb))

    def _make_cache(self, bb: int, sb: int, nb: int) -> dict:
        return self.model.init_cache(bb, nb + 2, enc_seq=sb)

    def _direct(self, tokens: np.ndarray, max_new: int) -> np.ndarray:
        from repro.serve.generate import greedy_generate_encdec

        return greedy_generate_encdec(self.model, self.params, tokens,
                                      max_new=max_new, pad_id=self.pad_id,
                                      eos_id=self.eos_id, bos_id=self.bos_id)


# ---------------------------------------------------------------------------
# Token-level continuous batching: persistent in-flight decode state
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _StreamRow:
    """Host-side bookkeeping for one in-flight decode slot."""

    cap: int  # row's max_new budget (leave trigger)
    tokens: List[int]  # emitted so far (includes eos/pad emissions verbatim)
    on_token: Optional[Callable]  # (tokens_so_far) -> None, per emission
    on_done: Callable  # (tokens) -> None, once, at eviction
    on_error: Optional[Callable]  # (exc) -> None if the decode loop dies


@dataclasses.dataclass
class _JoinGroup:
    """One prefilled admission chunk waiting for free decode slots.

    Prefill already ran (disaggregated from decode): the group carries its
    rung-shaped first tokens / done flags / fresh cache rows, so admitting
    it into the in-flight batch is a single scatter, never a prompt pass."""

    size: int  # real rows
    jb: int  # prefill/join rung (>= size; padding rows scatter nowhere)
    tok0: jax.Array  # [jb]
    done0: np.ndarray  # [jb] host copy (immediate-eviction decisions)
    done0_dev: jax.Array  # [jb]
    cache: dict  # fresh cache rows, [L, jb, ...] leaves
    rows: List[_StreamRow]


class StreamingEncDecBatcher:
    """Persistent in-flight decode state for the enc-dec fuser: requests
    join and leave the batch at ladder rungs on *every decode step*, not at
    batch boundaries.

    The replacement for per-batch :class:`EncDecGenerateDispatcher` calls
    on the streaming path: instead of one jitted whole-generation per
    (batch, max_new) bucket, the batcher keeps ``capacity`` decode slots
    live on device — carry token, per-row position, done mask, and a
    donated KV/cross cache — and compiles exactly three jit families:

    * **prefill** (one per join rung ``jb``) — encoder forward + BOS step
      over a fresh rung-shaped cache, run at :meth:`submit` time so long
      prompts never stall the decode loop (prefill disaggregation;
      ``prefill_chunk`` bounds rows per prefill call);
    * **join** (one per rung) — scatters the prefilled rows into free
      slots of the persistent state; padding rows carry an out-of-bounds
      slot index and are dropped by the scatter, so the join is
      rung-shaped without ever touching an occupied slot.  A joining row
      fully overwrites its slot's cache rows — KV slots are recycled in
      place, with no stale-state leak;
    * **step** (exactly one, capacity-shaped) — one
      :func:`~repro.serve.generate.encdec_decode_step` over all slots.
      Vacant/finished slots decode ``pad`` into themselves; live rows are
      bit-identical to the batch-boundary path (row independence, pinned
      by the padding-invariance property).

    Completed rows (eos, or their ``cap`` emitted) are evicted between
    steps and their slots backfilled from the FIFO pending queue, so a
    request arriving mid-decode joins at the next step with **zero new
    compiles** once the rungs are warm.  All host state is guarded by one
    lock; :meth:`pump` may be driven from any thread."""

    def __init__(self, model: EncDecLM, params: dict, enc_seq: int,
                 capacity: int = 8, max_new_cap: Optional[int] = None,
                 pad_id: int = TOKENIZER.pad_id, eos_id: int = TOKENIZER.eos_id,
                 bos_id: int = TOKENIZER.bos_id,
                 ladder: Optional[BucketLadder] = None,
                 donate: Optional[bool] = None,
                 prefill_chunk: Optional[int] = None):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.model = model
        self.params = params
        self.enc_seq = enc_seq
        self.ladder = ladder or BucketLadder()
        # capacity is a compiled shape; snap it to a rung so the step fn
        # matches the ladder the rest of the fast path speaks
        self.capacity = self.ladder.batch_bucket(capacity)
        self.max_new_cap = (self.ladder.new_tokens[-1] if max_new_cap is None
                            else max_new_cap)
        self.pad_id, self.eos_id, self.bos_id = pad_id, eos_id, bos_id
        self.donate = _donate_default() if donate is None else donate
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1")
        self.prefill_chunk = prefill_chunk
        self._lock = threading.RLock()
        # persistent device state: one slot per in-flight row
        self._tok = jnp.full((self.capacity,), pad_id, jnp.int32)
        self._pos = jnp.zeros((self.capacity,), jnp.int32)
        self._done = jnp.ones((self.capacity,), bool)
        self._cache = model.init_cache(self.capacity, self.max_new_cap + 2,
                                       enc_seq=enc_seq)
        self._rows: List[Optional[_StreamRow]] = [None] * self.capacity
        self._free: List[int] = list(range(self.capacity))  # kept sorted
        self._pending: "deque[_JoinGroup]" = deque()
        self._prefill_fns: Dict[int, object] = {}
        self._join_fns: Dict[int, object] = {}
        self._step_fn = None
        self._built = 0
        self.stats = {"prefills": 0, "joins": 0, "steps": 0, "rows": 0,
                      "evicted": 0, "padded_rows": 0}

    # -- compile accounting ---------------------------------------------
    @property
    def compiles(self) -> int:
        """Live XLA compile count across the prefill/join/step families
        (same contract as :attr:`_BucketedGenerate.compiles`)."""
        fns = (list(self._prefill_fns.values()) + list(self._join_fns.values())
               + ([self._step_fn] if self._step_fn is not None else []))
        sizes = [getattr(fn, "_cache_size", None) for fn in fns]
        if fns and all(callable(s) for s in sizes):
            return sum(s() for s in sizes)
        return self._built

    @property
    def in_flight(self) -> int:
        with self._lock:
            return sum(r is not None for r in self._rows)

    @property
    def idle(self) -> bool:
        with self._lock:
            return not self._pending and all(r is None for r in self._rows)

    # -- jit families ----------------------------------------------------
    def _prefill(self, jb: int):
        fn = self._prefill_fns.get(jb)
        if fn is None:
            model = self.model
            eos_id, bos_id = self.eos_id, self.bos_id
            max_seq, enc_seq = self.max_new_cap + 2, self.enc_seq

            def run(params, enc_tokens):
                cache = model.init_cache(jb, max_seq, enc_seq=enc_seq)
                return encdec_prefill_with_cache(
                    model, params, enc_tokens, cache, eos_id, bos_id)

            fn = self._prefill_fns[jb] = jax.jit(run)
            self._built += 1
        return fn

    def _join(self, jb: int):
        fn = self._join_fns.get(jb)
        if fn is None:
            def run(tok, pos, done, cache, idx, tok0, done0, cache0):
                # padding rows carry idx == capacity: out of bounds, so the
                # scatter drops them — the join stays rung-shaped without a
                # per-size compile and without touching occupied slots
                tok = tok.at[idx].set(tok0, mode="drop")
                pos = pos.at[idx].set(1, mode="drop")
                done = done.at[idx].set(done0, mode="drop")
                cache = jax.tree.map(
                    lambda big, small: big.at[:, idx].set(small, mode="drop"),
                    cache, cache0)
                return tok, pos, done, cache

            fn = self._join_fns[jb] = jax.jit(
                run, donate_argnums=(0, 1, 2, 3) if self.donate else ())
            self._built += 1
        return fn

    def _step(self):
        if self._step_fn is None:
            model, pad_id, eos_id = self.model, self.pad_id, self.eos_id

            def run(params, tok, pos, done, cache):
                return encdec_decode_step(
                    model, params, tok, pos, done, cache, pad_id, eos_id)

            self._step_fn = jax.jit(
                run, donate_argnums=(1, 2, 3, 4) if self.donate else ())
            self._built += 1
        return self._step_fn

    def warm(self, join_sizes: Iterable[int]) -> None:
        """Pre-compile the prefill/join rungs traffic will hit plus the
        step body, without disturbing in-flight state: the warm join
        scatters every row to the out-of-bounds sentinel (a no-op write),
        and the warm step runs over the untouched state — vacant slots
        already decode inert pads."""
        with self._lock:
            for size in join_sizes:
                jb = self.ladder.batch_bucket(max(1, min(size, self.capacity)))
                enc = np.full((jb, self.enc_seq), self.pad_id, np.int32)
                enc[:, 0] = self.bos_id
                tok0, done0, cache0 = self._prefill(jb)(
                    self.params, jnp.asarray(enc))
                idx = jnp.full((jb,), self.capacity, jnp.int32)
                self._tok, self._pos, self._done, self._cache = self._join(jb)(
                    self._tok, self._pos, self._done, self._cache,
                    idx, tok0, jnp.ones_like(done0), cache0)
            emit, self._tok, self._pos, self._done, self._cache = self._step()(
                self.params, self._tok, self._pos, self._done, self._cache)
            del emit

    # -- admission -------------------------------------------------------
    def submit(self, enc_tokens: np.ndarray, caps: List[int],
               on_token: Optional[Callable] = None,
               on_done: Optional[Callable] = None,
               on_error: Optional[Callable] = None) -> None:
        """Prefill ``enc_tokens [B, enc_seq]`` now and queue the rows for
        the decode loop.  Per-row callbacks fire under the batcher lock:
        ``on_token(i, tokens_so_far)`` after every emission,
        ``on_done(i, tokens)`` once at eviction, ``on_error(i, exc)`` if
        the decode loop dies with the row in flight.  Rows whose prefill
        already finished them (BOS argmax == eos, or ``cap == 0``) settle
        immediately — they never occupy a slot."""
        b, se = enc_tokens.shape
        if se != self.enc_seq:
            raise ValueError(
                f"encoder length {se} != batcher enc_seq {self.enc_seq}")
        if len(caps) != b:
            raise ValueError("caps must have one entry per row")
        if max(caps, default=0) > self.max_new_cap:
            raise ValueError(
                f"row cap {max(caps)} exceeds max_new_cap {self.max_new_cap}")
        chunk = self.capacity
        if self.prefill_chunk is not None:
            chunk = min(chunk, self.prefill_chunk)
        with self._lock:
            for lo in range(0, b, chunk):
                hi = min(lo + chunk, b)
                self._submit_chunk(enc_tokens[lo:hi], caps[lo:hi], lo,
                                   on_token, on_done, on_error)
            self._admit_pending()

    def _submit_chunk(self, enc: np.ndarray, caps: List[int], base: int,
                      on_token, on_done, on_error) -> None:
        size = enc.shape[0]
        jb = self.ladder.batch_bucket(size)
        jb = min(jb, self.capacity) if jb > self.capacity else jb
        padded = np.full((jb, self.enc_seq), self.pad_id, np.int32)
        padded[:size] = enc
        if jb > size:
            padded[size:] = padded[0]  # replicate a real row (independence)
        with spans.span("serve.fuser.prefill", rows=size):
            tok0, done0_dev, cache0 = self._prefill(jb)(self.params,
                                                        jnp.asarray(padded))
            done0 = np.asarray(done0_dev)
        self.stats["prefills"] += 1
        self.stats["rows"] += size
        self.stats["padded_rows"] += jb - size
        rows = []
        for k in range(size):
            i = base + k
            rows.append(_StreamRow(
                cap=caps[k], tokens=[],
                on_token=(lambda t, _i=i: on_token(_i, t)) if on_token else None,
                on_done=(lambda t, _i=i: on_done(_i, t)) if on_done
                else (lambda t: None),
                on_error=(lambda e, _i=i: on_error(_i, e)) if on_error
                else None,
            ))
        self._pending.append(_JoinGroup(
            size=size, jb=jb, tok0=tok0, done0=done0,
            done0_dev=done0_dev, cache=cache0, rows=rows))

    def _admit_pending(self) -> None:
        """FIFO-join pending groups while slots are free.  Strict FIFO (a
        large group at the head waits even if a smaller one behind it
        would fit) keeps join order — and therefore slot assignment and
        the completion trace — deterministic across dispatch modes."""
        while self._pending and len(self._free) >= self._pending[0].size:
            g = self._pending.popleft()
            slots = self._free[:g.size]
            del self._free[:g.size]
            idx = np.full((g.jb,), self.capacity, np.int32)  # padding -> OOB
            idx[:g.size] = slots
            with spans.span("serve.fuser.join", rows=g.size):
                self._tok, self._pos, self._done, self._cache = self._join(g.jb)(
                    self._tok, self._pos, self._done, self._cache,
                    jnp.asarray(idx), g.tok0, g.done0_dev, g.cache)
            self.stats["joins"] += 1
            for slot, row, finished in zip(slots, g.rows, g.done0[:g.size]):
                if finished or row.cap <= 0:
                    # BOS argmax hit eos (every emission would be pad) or a
                    # zero-token budget: settle now, recycle the slot
                    bisect.insort(self._free, slot)
                    self.stats["evicted"] += 1
                    row.on_done(list(row.tokens))
                else:
                    self._rows[slot] = row

    # -- the decode loop -------------------------------------------------
    def pump(self, steps: Optional[int] = None) -> int:
        """Run up to ``steps`` decode steps (``None`` = until drained),
        admitting pending joins before each step and evicting finished
        rows after it.  Returns the number of steps executed.  On a device
        error every in-flight and pending row fails through ``on_error``
        (the stream's failure semantics: the error surfaces at the
        consumer, not inside the loop)."""
        executed = 0
        with self._lock:
            try:
                while steps is None or executed < steps:
                    self._admit_pending()
                    live = sum(r is not None for r in self._rows)
                    if not live:
                        break
                    with spans.span("serve.fuser.step", rows=live):
                        self._step_once()
                    executed += 1
            except Exception as exc:
                self._fail_all(exc)
                raise
        return executed

    def _step_once(self) -> None:
        """One decode step over every slot: the jitted call (``launch``),
        the host reads of ``emit`` and ``done`` (``read``), then each live
        row's token and callbacks (``emit``)."""
        with spans.span("serve.fuser.step.launch"):
            emit, self._tok, self._pos, self._done, self._cache = (
                self._step()(self.params, self._tok, self._pos,
                             self._done, self._cache))
        with spans.span("serve.fuser.step.read"):
            emit_h = np.asarray(emit)
            done_h = np.asarray(self._done)
        self.stats["steps"] += 1
        with spans.span("serve.fuser.step.emit"):
            for slot in range(self.capacity):
                row = self._rows[slot]
                if row is None:
                    continue
                row.tokens.append(int(emit_h[slot]))
                if row.on_token is not None:
                    row.on_token(list(row.tokens))
                if done_h[slot] or len(row.tokens) >= row.cap:
                    # leave: every later emission would be pad, or the
                    # row's budget is spent — final text is already
                    # byte-complete
                    self._rows[slot] = None
                    bisect.insort(self._free, slot)
                    self.stats["evicted"] += 1
                    row.on_done(list(row.tokens))

    def _fail_all(self, exc: BaseException) -> None:
        rows = [r for r in self._rows if r is not None]
        self._rows = [None] * self.capacity
        self._free = list(range(self.capacity))
        for g in self._pending:
            rows.extend(g.rows)
        self._pending.clear()
        # neutralize device state: vacant slots must decode inert pads
        self._tok = jnp.full((self.capacity,), self.pad_id, jnp.int32)
        self._pos = jnp.zeros((self.capacity,), jnp.int32)
        self._done = jnp.ones((self.capacity,), bool)
        for row in rows:
            if row.on_error is not None:
                row.on_error(exc)
