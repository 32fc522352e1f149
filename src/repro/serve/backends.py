"""Pluggable member backends: how a selected pool member produces text.

The engine is backend-agnostic: anything satisfying the
:class:`MemberBackend` protocol can serve a pool.  Two implementations
ship with the repro:

* :class:`SimBackend` — the behavioural simulator (DESIGN.md §3).  The
  RNG is derived per ``(seed, member, query)``, so a member's response to
  a query is identical whether it arrives in a 400-row offline batch or
  as a single online request — the property the Scheduler-equivalence
  guarantee rests on.
* :class:`LiveLMBackend` — real tiny JAX decoder LMs, dispatched through
  the bucketed static-shape fast path (:mod:`repro.serve.dispatch`) so
  steady-state traffic compiles each generate bucket once and reuses its
  donated decode cache.

``max_new_tokens`` may be one int for the whole batch or a per-record
sequence: backends OWN truncation and must consume at most the row's
token cap per response (``TOKENIZER.decode_capped`` — the cut never
fabricates replacement characters, so valid-UTF-8 responses re-encode to
<= cap tokens; a live LM emitting genuinely invalid interior bytes can
still decode to U+FFFD, which is content, not cap overflow).  The engine
never re-tokenizes responses to enforce the cap.  The cap must not
depend on which other rows share the micro-batch (greedy decoding is
prefix-stable, so generating a member batch at the rows' max length and
slicing each row to its own cap equals generating each row alone at its
own cap).

This replaces the ``live_members is None`` branching that used to live
inside ``EnsembleServer._generate_member``.
"""

from __future__ import annotations

import dataclasses
import hashlib
import threading
import time
from typing import (Dict, List, Optional, Protocol, Sequence, Tuple, Union,
                    runtime_checkable)

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.data.mixinstruct import PoolMemberSpec, Record, member_response
from repro.data.tokenizer import TOKENIZER
from repro.models.transformer import DecoderLM
from repro.serve.dispatch import BucketLadder, DecoderGenerateDispatcher
from repro.serve.generate import greedy_generate
from repro.sharding.api import current_rules

MaxNewTokens = Union[int, Sequence[int]]


class MemberFailure(RuntimeError):
    """A single pool member's backend call failed mid-batch.

    The engine wraps any exception escaping ``backend.generate(j, ...)``
    in this type so the Scheduler can tell "one member is down" apart
    from "the engine itself is broken" and hedge: re-serve the batch with
    ``member_idx`` excluded instead of failing every sibling future."""

    def __init__(self, member_idx: int, cause: BaseException):
        super().__init__(f"pool member {member_idx} failed: {cause!r}")
        self.member_idx = member_idx
        self.cause = cause


class HostFailure(RuntimeError):
    """A whole placement host died mid-batch (cluster serving).

    Raised by a placement-aware backend (see
    :class:`repro.serve.cluster.ClusterRouter`) when a host-level fault
    takes down every member replica placed on ``host_id``.
    ``member_idxs`` lists the pool members left with *no* surviving
    replica — the set the Scheduler must mask out of the knapsack before
    re-serving the batch on the surviving placements.  Members that keep
    a live replica on another host are failed over inside the router and
    never appear here."""

    def __init__(self, host_id: int, member_idxs: Sequence[int] = (),
                 cause: BaseException | None = None):
        dead = ", ".join(str(j) for j in member_idxs) or "none"
        super().__init__(
            f"host {host_id} failed (members with no surviving replica: {dead})"
        )
        self.host_id = host_id
        self.member_idxs = tuple(member_idxs)
        self.cause = cause


@dataclasses.dataclass(frozen=True)
class GenerationCall:
    """One member's generation work within a batch: the rows that
    selected it plus their per-row token caps.  The engine hands the full
    batch's calls (member order) to a backend's optional
    ``generate_many(calls)`` hook — the seam fan-out routing
    (:class:`repro.serve.cluster.ClusterRouter`) plugs into — and falls
    back to one ``generate`` per call otherwise.  ``generate_many`` must
    return results in call order and raise :class:`MemberFailure` /
    :class:`HostFailure` with the same attribution the sequential loop
    would."""

    member_idx: int
    records: Tuple
    max_new_tokens: Tuple[int, ...]


def per_row_caps(max_new_tokens: MaxNewTokens, n_rows: int) -> List[int]:
    """Normalize an int-or-sequence token cap to one cap per row."""
    if isinstance(max_new_tokens, int):
        return [max_new_tokens] * n_rows
    caps = list(max_new_tokens)
    if len(caps) != n_rows:
        raise ValueError(f"{len(caps)} caps for {n_rows} records")
    return caps


@runtime_checkable
class MemberBackend(Protocol):
    """Generates pool-member responses for a micro-batch of queries."""

    def num_members(self) -> int:
        """Size of the pool this backend serves."""
        ...

    def generate(
        self,
        member_idx: int,
        records: Sequence[Record],
        max_new_tokens: MaxNewTokens,
    ) -> List[str]:
        """Member ``member_idx``'s response to each record, in order,
        each truncated to its row's token cap."""
        ...


def _query_rng(seed: int, member_idx: int, query: str) -> np.random.Generator:
    # errors="replace" mirrors the tokenizer: unpaired surrogates in an
    # online query must not crash the batch
    digest = hashlib.blake2b(
        query.encode("utf-8", errors="replace"), digest_size=8
    ).digest()
    return np.random.default_rng([seed, member_idx, int.from_bytes(digest, "little")])


@dataclasses.dataclass
class SimBackend:
    """Behavioural simulator over a pool of :class:`PoolMemberSpec`."""

    pool: Sequence[PoolMemberSpec]
    seed: int = 0

    def num_members(self) -> int:
        return len(self.pool)

    def generate(self, member_idx: int, records: Sequence[Record],
                 max_new_tokens: MaxNewTokens) -> List[str]:
        caps = per_row_caps(max_new_tokens, len(records))
        spec = self.pool[member_idx]
        out = []
        for r, cap in zip(records, caps):
            text = member_response(spec, r, _query_rng(self.seed, member_idx, r.query))
            # the simulator writes whole responses; one capped decode enforces
            # the row cap without fabricating U+FFFD at the cut point
            out.append(TOKENIZER.decode_capped(TOKENIZER.encode(text), cap))
        return out


@dataclasses.dataclass
class FailureInjector:
    """Deterministic failure wrapper around any :class:`MemberBackend`.

    ``failures`` maps a member index to the 0-based *call indices* (that
    member's n-th ``generate`` call, counted over the backend's lifetime)
    that raise instead of generating.  Because the schedule is keyed on
    call counts — not wall time — a traffic-simulator run that injects
    failures is exactly replayable: same seed, same arrivals, same calls,
    same faults.  Hedged retries consume call indices like any other
    call, so a member that fails call 2 can succeed on call 3.

    ``slow`` is the *grey-failure* schedule: call indices that complete
    normally but only after sleeping ``slow_s`` wall seconds — a member
    alive but straggling.  Slowness touches wall clock only, never the
    logical trace, so slowed runs stay byte-identical to fast ones;
    it exists to give shard deadlines and straggler hedging something
    real to race against."""

    inner: MemberBackend
    failures: Dict[int, Sequence[int]] = dataclasses.field(default_factory=dict)
    slow: Dict[int, Sequence[int]] = dataclasses.field(default_factory=dict)
    slow_s: float = 0.0
    calls: Dict[int, int] = dataclasses.field(default_factory=dict)
    slowed: int = 0  # grey-slow calls actually served (diagnostics)

    def num_members(self) -> int:
        return self.inner.num_members()

    def generate(self, member_idx: int, records: Sequence[Record],
                 max_new_tokens: MaxNewTokens) -> List[str]:
        k = self.calls.get(member_idx, 0)
        self.calls[member_idx] = k + 1
        if k in tuple(self.failures.get(member_idx, ())):
            raise RuntimeError(
                f"injected failure: member {member_idx}, call {k}"
            )
        if self.slow_s > 0 and k in tuple(self.slow.get(member_idx, ())):
            self.slowed += 1
            time.sleep(self.slow_s)
        return self.inner.generate(member_idx, records, max_new_tokens)

    # optional-protocol hooks forward to the wrapped backend
    def warm(self, shapes: Sequence,
             members: Optional[Sequence[int]] = None) -> None:
        warm = getattr(self.inner, "warm", None)
        if callable(warm):
            warm(shapes, members=members)

    def compiles(self) -> int:
        compiles = getattr(self.inner, "compiles", None)
        return compiles() if callable(compiles) else 0

    def dead_members(self) -> List[int]:
        dead = getattr(self.inner, "dead_members", None)
        return dead() if callable(dead) else []

    # NOTE: generate_many is deliberately NOT forwarded — it would route
    # the engine's batch straight to the inner backend's fan-out and
    # bypass this injector's per-member schedules.  Maintenance hooks
    # are pure placement state and forward safely.
    def maintenance_pending(self, now: int) -> bool:
        pending = getattr(self.inner, "maintenance_pending", None)
        return pending(now) if callable(pending) else False

    def maintain(self, now: int) -> List[dict]:
        maintain = getattr(self.inner, "maintain", None)
        return maintain(now) if callable(maintain) else []


@dataclasses.dataclass
class LiveMember:
    """A real (tiny) decoder LM standing in for one pool member."""

    spec: PoolMemberSpec
    model: DecoderLM
    params: dict


@dataclasses.dataclass
class LiveLMBackend:
    """Live JAX LMs: prompt = ``<bos> query <sep>``, greedy decode.

    ``fast=True`` routes generation through one
    :class:`~repro.serve.dispatch.DecoderGenerateDispatcher` per member and
    host: micro-batches pad up to the bucket ladder, each bucket compiles
    once, and the decode cache is donated back to the same buffers call
    after call.  ``fast=False`` keeps the ad-hoc jit path (one compile per
    distinct shape).

    Under a cluster host's axis rules (installed by
    :class:`~repro.serve.cluster.ClusterRouter` around each routed call)
    the member's dispatcher is the one for that host's mesh: its weights
    and decode caches live on the host's devices, and its programs were
    traced under that host's rules only, so a trace for one host is never
    reused on another."""

    members: Sequence[LiveMember]
    max_query_len: int = 96
    fast: bool = True
    ladder: BucketLadder = dataclasses.field(default_factory=BucketLadder)
    _dispatchers: Dict[Tuple[int, object], DecoderGenerateDispatcher] = (
        dataclasses.field(default_factory=dict, repr=False))
    _lock: threading.Lock = dataclasses.field(
        default_factory=threading.Lock, repr=False)

    def num_members(self) -> int:
        return len(self.members)

    def _dispatcher(self, member_idx: int) -> DecoderGenerateDispatcher:
        rules = current_rules()
        mesh = None if rules is None else rules.mesh
        with self._lock:
            d = self._dispatchers.get((member_idx, mesh))
            if d is None:
                lm = self.members[member_idx]
                placement = (None if mesh is None
                             else NamedSharding(mesh, PartitionSpec()))
                params = (lm.params if placement is None
                          else jax.device_put(lm.params, placement))
                d = self._dispatchers[(member_idx, mesh)] = (
                    DecoderGenerateDispatcher(lm.model, params,
                                              ladder=self.ladder,
                                              placement=placement))
        return d

    def compiles(self) -> int:
        """Total live XLA compiles across member dispatchers.  Snapshot
        the dict first: fan-out shards lazily create dispatchers on host
        executor threads, and iterating a dict another thread is
        inserting into raises."""
        with self._lock:
            dispatchers = list(self._dispatchers.values())
        return sum(d.compiles for d in dispatchers)

    def warm(self, shapes: Sequence,
             members: Optional[Sequence[int]] = None) -> None:
        """Pre-compile the given (batch, max_new) buckets for ``members``
        (default: every member) under the axis rules currently installed."""
        if not self.fast:
            return  # the ad-hoc jit path has no buckets to warm
        for j in range(len(self.members)) if members is None else members:
            self._dispatcher(j).warm(
                [(b, self.max_query_len, n) for b, n in shapes]
            )

    def generate(self, member_idx: int, records: Sequence[Record],
                 max_new_tokens: MaxNewTokens) -> List[str]:
        caps = per_row_caps(max_new_tokens, len(records))
        group_max = max(caps)
        prompts = [
            TOKENIZER.encode(r.query, bos=True) + [TOKENIZER.sep_id] for r in records
        ]
        batch = TOKENIZER.pad_batch(prompts, self.max_query_len)
        if self.fast:
            out = self._dispatcher(member_idx)(batch, group_max)
        else:
            lm = self.members[member_idx]
            out = greedy_generate(lm.model, lm.params, batch, max_new=group_max)
        # slice token ids to the row cap BEFORE the single decode — no
        # decode->encode->decode round trip per row; decode_capped strips a
        # cut-induced partial UTF-8 char instead of inflating it to U+FFFD
        return [TOKENIZER.decode_capped(row, cap) for row, cap in zip(out, caps)]
