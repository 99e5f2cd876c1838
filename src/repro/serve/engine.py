"""Adaptive micro-batching serving engine — the request is the unit of work.

Fixes the seed server's score/request misalignment and rebuilds serving
around three ideas from the paper's §2.2:

  * **request-aligned scoring** — the batcher's ``BatchPlan`` maps every
    request to its contiguous slot range, so the engine returns exactly one
    score array per input request, shape-aligned with ``request.item_ids``
    (empty for zero-impression requests). Requests larger than the biggest
    batch are *split* across batches and reassembled, never silently
    truncated.
  * **adaptive micro-batching** — online traffic is admitted into a pending
    queue and flushed by a size-or-deadline policy (``EnginePolicy``); every
    flush is rounded up to a rung of a fixed shape ladder
    (serve/bucketing.py) so ragged traffic never causes per-shape jit
    recompiles.
  * **user-tower memoization** — with split model entry points
    (``user_fn`` + ``score_from_user``), the RO side is computed once per
    unique request payload and reused across repeat candidates
    (serve/user_cache.py) — ROO dedup applied to inference.

The bulk path (``score_stream``) is a generator: scores leave the engine one
flush-group at a time, so offline scoring of 262k impressions never holds
the full result set host-side twice.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import threading
import time
from typing import (Callable, Dict, Hashable, Iterable, Iterator, List,
                    Optional, Sequence, Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.joiner import ROOSample
from repro.data.batcher import BatcherConfig, BatchPlan, ROOBatcher
from repro.kernels.hstu_attention import record_tile_grids
from repro.obs import export as obs_export
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.reliability import faults
from repro.serve.adapter import ServeAdapter
from repro.serve.bucketing import BucketLadder, BucketStats
from repro.serve.user_cache import (UserStateStore, UserTowerCache,
                                    request_key)


class ScoreError:
    """Returned (never raised) in place of a score array when the engine
    could not score a request: its batch's forward failed, or the circuit
    breaker shed it. Callers check ``isinstance(x, ScoreError)``; healthy
    requests in the same stream still get real scores."""
    __slots__ = ("reason", "shed")

    def __init__(self, reason: str, shed: bool = False):
        self.reason = reason
        self.shed = shed

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"ScoreError({self.reason!r}, shed={self.shed})"


@dataclasses.dataclass
class EnginePolicy:
    """Admission policy: a flush happens when the pending queue reaches
    ``max_requests`` requests or ``max_impressions`` impressions (size), or
    when the oldest pending request has waited ``max_delay_ms`` (deadline).

    Circuit breaker: after ``breaker_threshold`` CONSECUTIVE batch scoring
    failures the engine stops invoking the model and sheds incoming work
    (instant ``ScoreError(shed=True)``) for ``breaker_cooldown_s``; the
    first batch after the cooldown is a half-open trial — success closes
    the breaker, failure re-opens it. ``breaker_threshold=0`` disables
    shedding (every batch is always attempted)."""
    max_requests: int = 64
    max_impressions: int = 512
    max_delay_ms: float = 2.0
    hist_len: int = 64
    breaker_threshold: int = 5
    breaker_cooldown_s: float = 1.0


@dataclasses.dataclass
class EngineStats:
    n_requests: int = 0
    n_impressions: int = 0
    n_batches: int = 0
    n_split_requests: int = 0          # requests scored across >1 batch
    n_size_flushes: int = 0
    n_deadline_flushes: int = 0
    n_forced_flushes: int = 0
    n_full_cache_batches: int = 0      # batches whose user tower was skipped
    n_incremental_batches: int = 0     # batches scored via the state store
    n_failed_batches: int = 0          # forwards that raised (isolated)
    n_failed_requests: int = 0         # requests resolved to ScoreError
    n_shed_requests: int = 0           # requests shed by the open breaker
    n_breaker_opens: int = 0           # open transitions (incl. re-opens)
    buckets: BucketStats = dataclasses.field(default_factory=BucketStats)
    # counters are mutated from whatever thread drives scoring and read
    # from monitoring threads; bare += would lose updates
    _lock: threading.Lock = dataclasses.field(
        default_factory=threading.Lock, repr=False, compare=False)

    def inc(self, field: str, n: int = 1) -> None:
        with self._lock:
            setattr(self, field, getattr(self, field) + n)

    def record_bucket(self, spec) -> None:
        with self._lock:
            self.buckets.record(spec)

    def snapshot(self) -> dict:
        """Consistent point-in-time copy of every counter."""
        with self._lock:
            out = {f.name: getattr(self, f.name)
                   for f in dataclasses.fields(self)
                   if not f.name.startswith("_") and f.name != "buckets"}
            out["buckets"] = self.buckets.snapshot()
            return out


def split_oversize(sample: ROOSample, cap: int) -> List[ROOSample]:
    """Chunk a request with more than ``cap`` impressions into sub-requests
    sharing the RO payload. The engine scores each chunk and concatenates —
    alignment with ``item_ids`` is preserved for arbitrarily large requests."""
    if sample.num_impressions <= cap:
        return [sample]
    return [
        dataclasses.replace(
            sample,
            item_ids=sample.item_ids[lo:lo + cap],
            item_dense=sample.item_dense[lo:lo + cap],
            item_idlist=sample.item_idlist[lo:lo + cap],
            labels=sample.labels[lo:lo + cap])
        for lo in range(0, sample.num_impressions, cap)
    ]


class ScoringEngine:
    """Request-aligned, cache-aware scoring around jit'd model halves.

    The model halves come from a :class:`~repro.serve.adapter.ServeAdapter`
    (``adapter=``) or from bare callables: ``score_fn(params, batch) ->
    (B_NRO,) | (B_NRO, n_tasks)`` is the fused forward; the split entry
    points ``user_fn(params, batch) -> (B_RO, ...)`` and
    ``score_from_user(params, batch, user)`` additionally enable the
    user-tower cache; an adapter with stateful hooks plus a
    ``state_store`` routes every batch through the incremental path
    (repeat users cost O(new events); misses recompute from empty through
    the same prefix code path).

    Two front ends share one scoring core:
      * online:  ``submit`` / ``poll`` / ``flush`` / ``take``  (micro-batcher)
      * bulk:    ``score_stream`` (generator) / ``score_requests`` (list)
    """

    def __init__(self, params, score_fn: Optional[Callable] = None, *,
                 policy: Optional[EnginePolicy] = None,
                 ladder: Optional[BucketLadder] = None,
                 adapter: Optional[ServeAdapter] = None,
                 user_fn: Optional[Callable] = None,
                 score_from_user: Optional[Callable] = None,
                 cache: Optional[UserTowerCache] = None,
                 state_store: Optional[UserStateStore] = None,
                 attn_backend: Optional[str] = None,
                 clock: Callable[[], float] = time.monotonic):
        if adapter is not None:
            score_fn = score_fn or adapter.score
            user_fn = user_fn or adapter.user_repr
            score_from_user = score_from_user or adapter.score_from_user
        if score_fn is None:
            raise ValueError("ScoringEngine needs score_fn or an adapter")
        if cache is not None and (user_fn is None or score_from_user is None):
            raise ValueError("user-tower cache requires the split entry "
                             "points user_fn and score_from_user")
        if state_store is not None:
            if adapter is None or not adapter.supports_incremental:
                raise ValueError(
                    "state_store requires an adapter with the stateful "
                    "hooks (init_user_state / score_from_state)")
            if cache is not None:
                raise ValueError("state_store and the user-tower cache are "
                                 "mutually exclusive")
        self._params = params
        self.policy = policy or EnginePolicy()
        if (state_store is not None
                and adapter.state_hist_len != self.policy.hist_len):
            raise ValueError(
                f"incremental serving needs the adapter state capacity "
                f"({adapter.state_hist_len}) to equal the batcher window "
                f"(policy.hist_len={self.policy.hist_len}) so 'prefix of "
                f"the effective history' is well defined")
        self.ladder = ladder or BucketLadder.geometric(
            max_b_ro=self.policy.max_requests,
            max_b_nro=self.policy.max_impressions)
        self.adapter = adapter
        self.cache = cache
        self.state_store = state_store
        self.attn_backend = attn_backend
        self.clock = clock
        self.stats = EngineStats()
        # fused HSTU kernels' tile grids per batch shape, recorded while the
        # scoring halves trace (for the flush's attn_tiles_* args)
        self._tile_grids: Dict[Tuple[int, int], list] = {}
        self._score = jax.jit(self._recording_grids(score_fn))
        self._user = jax.jit(user_fn) if user_fn is not None else None
        self._from_user = (jax.jit(self._recording_grids(score_from_user))
                           if score_from_user is not None else None)
        # param epoch versions every store entry; bumped on weight swap
        self._param_epoch = 0
        # jitted score_from_state per static n_new rung (bounded: powers of 2)
        self._from_state_jit: Dict[int, Callable] = {}
        # online micro-batcher state
        self._pending: List[Tuple[int, ROOSample]] = []
        self._pending_imps = 0
        self._oldest_ts: Optional[float] = None
        self._next_ticket = 0
        self._results: Dict[int, np.ndarray] = {}
        self._submit_ts: Dict[int, float] = {}
        obs_metrics.register_stats("serve.engine", self)
        # trailing score dims ((,) single-task, (n_tasks,) multi-task) from
        # the last scored batch — used to shape empty results when a whole
        # flush-group has zero impressions and the model never runs
        self._score_tail: Tuple[int, ...] = ()
        # circuit breaker: consecutive batch failures + open-until deadline
        self._breaker_failures = 0
        self._breaker_open_until: Optional[float] = None

    def _recording_grids(self, fn: Callable) -> Callable:
        """``fn`` recording, while jit traces it, the tile grid of every
        fused HSTU kernel call it makes, under its batch's shape. A fresh
        wrapper per engine, so each engine traces (and records) anew."""
        @functools.wraps(fn)
        def traced(params, batch, *rest):
            with record_tile_grids() as grids:
                out = fn(params, batch, *rest)
            self._tile_grids[(batch.b_ro, batch.b_nro)] = grids
            return out
        return traced

    def _attn_tiles(self, batch, samples: List[ROOSample],
                    plan: BatchPlan) -> Tuple[int, int]:
        """(live, dense) tiles of the fused HSTU kernels over the batch's
        rows, heads and attention layers: each row's history length and
        packed impressions (the ROO mask's ``hist_lengths`` and
        ``target_counts``) through the kernels' live-tile predicate. (0, 0)
        where no fused kernel was traced for this batch shape."""
        grids = self._tile_grids.get((batch.b_ro, batch.b_nro))
        if not grids:
            return 0, 0
        hl = np.zeros(batch.b_ro, np.int32)
        tc = np.zeros(batch.b_ro, np.int32)
        for p in plan.requests:
            hl[p.row] = min(len(samples[p.request_index].history_ids),
                            self.policy.hist_len)
            tc[p.row] = p.n_packed
        live = dense = 0
        for g, n in collections.Counter(grids).items():
            live += n * g.n_heads * int(g.live_tiles(hl, tc).sum())
            dense += n * g.n_heads * batch.b_ro * g.dense_tiles
        return live, dense

    @classmethod
    def from_scenario(cls, spec, params=None, rng_seed: int = 0,
                      clock: Optional[Callable[[], float]] = None
                      ) -> "ScoringEngine":
        """Build an engine from a ScenarioSpec: the serve section sets the
        admission policy/ladder/cache, the knobs section pins the attention
        backend, and the arch's serving adapter (scenario/build.py) supplies
        the model halves. ``params=None`` initializes fresh parameters."""
        from repro.scenario.build import engine_from_scenario
        return engine_from_scenario(spec, params=params, rng_seed=rng_seed,
                                    clock=clock)

    @property
    def params(self):
        return self._params

    @params.setter
    def params(self, new_params) -> None:
        # cached rows / user states were computed with the old params — a
        # weight refresh bumps the epoch and drops every stale-epoch entry,
        # so mixed-version scores are impossible
        self._params = new_params
        self._param_epoch += 1
        if self.cache is not None:
            self.cache.invalidate_epoch(self._param_epoch)
        if self.state_store is not None:
            self.state_store.invalidate_epoch(self._param_epoch)

    @property
    def param_epoch(self) -> int:
        """Monotone version of the served parameters (0 at construction,
        +1 per assignment to ``params``); stores key entries by it."""
        return self._param_epoch

    def snapshot(self) -> dict:
        """Whole-engine view for ``obs.snapshot()``: scoring counters,
        cache effectiveness, breaker state — one consistent read."""
        out = {"stats": self.stats.snapshot(),
               "pending_requests": len(self._pending),
               "param_epoch": self._param_epoch,
               "breaker": {"consecutive_failures": self._breaker_failures,
                           "open": self._breaker_open_until is not None}}
        if self.cache is not None:
            out["cache"] = self.cache.snapshot()
        if self.state_store is not None:
            out["state_store"] = self.state_store.snapshot()
        return out

    # ---- online front end ----------------------------------------------------
    def submit(self, request: ROOSample) -> int:
        """Admit one request; returns a ticket redeemable via ``take``."""
        ticket = self._next_ticket
        self._next_ticket += 1
        now = self.clock()
        if not self._pending:
            self._oldest_ts = now
        self._pending.append((ticket, request))
        self._pending_imps += request.num_impressions
        self._submit_ts[ticket] = now
        return ticket

    def poll(self, now: Optional[float] = None) -> bool:
        """Flush if the admission policy triggers. Returns True if a batch
        was scored (results became available)."""
        if not self._pending:
            return False
        now = self.clock() if now is None else now
        if (len(self._pending) >= self.policy.max_requests
                or self._pending_imps >= self.policy.max_impressions):
            self.stats.inc("n_size_flushes")
        elif (now - self._oldest_ts) * 1e3 >= self.policy.max_delay_ms:
            self.stats.inc("n_deadline_flushes")
        else:
            return False
        self._drain()
        return True

    def flush(self) -> None:
        """Force-score everything pending regardless of policy."""
        if self._pending:
            self.stats.inc("n_forced_flushes")
            self._drain()

    def take(self, ticket: int) -> Optional[np.ndarray]:
        """Scores for a submitted request, or None if not yet flushed."""
        return self._results.pop(ticket, None)

    def _drain(self) -> None:
        pending, self._pending = self._pending, []
        self._pending_imps, self._oldest_ts = 0, None
        observe = obs_metrics.metrics_enabled()
        for ticket, scores in self._score_keyed(pending, self._submit_ts):
            self._results[ticket] = scores
            t0 = self._submit_ts.pop(ticket)
            if observe:
                obs_metrics.histogram("engine.request_ms").observe(
                    (self.clock() - t0) * 1e3)

    # ---- bulk front end ------------------------------------------------------
    def score_stream(self, requests: Iterable[ROOSample]
                     ) -> Iterator[Tuple[int, np.ndarray]]:
        """Yield ``(request_index, scores)`` as batches complete — at most one
        flush-group of scores is held host-side at any time."""
        yield from self._score_keyed(enumerate(requests))

    def score_requests(self, requests: Sequence[ROOSample]
                       ) -> List[np.ndarray]:
        """One score array per input request, exactly aligned with that
        request's ``item_ids`` (empty array for zero-impression requests)."""
        out: List[Optional[np.ndarray]] = [None] * len(requests)
        for i, scores in self.score_stream(requests):
            out[i] = scores
        return out

    # ---- scoring core --------------------------------------------------------
    def _score_keyed(self, keyed: Iterable[Tuple[Hashable, ROOSample]],
                     submit_ts: Optional[Dict[Hashable, float]] = None
                     ) -> Iterator[Tuple[Hashable, np.ndarray]]:
        """Split oversize requests, group into bucket-shaped flushes, score,
        reassemble per original key. Yields each key exactly once.
        ``submit_ts`` (online path) maps each key to its ``submit`` time,
        for the flush span's queueing args."""
        top = self.ladder.max_rung
        tracing = obs_trace.tracing_enabled()
        trace_ids: Dict[Hashable, int] = {}
        parts_needed: Dict[Hashable, int] = {}
        parts_got: Dict[Hashable, List[np.ndarray]] = {}
        group: List[Tuple[Hashable, ROOSample]] = []
        group_imps = 0
        # zero-impression requests never enter a batch; they resolve to an
        # empty array once the trailing score dims are known (i.e. after the
        # first real batch of this or an earlier call), so a multi-task
        # model yields (0, n_tasks) rather than (0,)
        deferred_empty: List[Hashable] = []

        def reassemble(scored: Iterator[Tuple[Hashable, np.ndarray]]):
            for key, piece in scored:
                got = parts_got.setdefault(key, [])
                got.append(piece)
                if len(got) == parts_needed[key]:
                    del parts_got[key], parts_needed[key]
                    if tracing:
                        obs_trace.instant("engine.reassemble",
                                          trace_id=trace_ids.pop(key, None),
                                          parts=len(got))
                    errs = [p for p in got if isinstance(p, ScoreError)]
                    if errs:
                        # one bad piece poisons the request: a partial
                        # score array misaligned with item_ids is worse
                        # than an explicit error
                        hard = [e for e in errs if not e.shed]
                        err = hard[0] if hard else errs[0]
                        if hard:
                            self.stats.inc("n_failed_requests")
                        else:
                            self.stats.inc("n_shed_requests")
                        yield key, err
                        continue
                    yield key, (np.concatenate(got, axis=0)
                                if len(got) > 1 else got[0])

        def flush_empty():
            while deferred_empty:
                yield (deferred_empty.pop(),
                       np.zeros((0,) + self._score_tail, np.float32))

        for key, sample in keyed:
            self.stats.inc("n_requests")
            self.stats.inc("n_impressions", sample.num_impressions)
            if tracing:
                trace_ids[key] = obs_trace.new_trace_id()
                obs_trace.instant("engine.admit", trace_id=trace_ids[key],
                                  impressions=sample.num_impressions)
            if sample.num_impressions == 0:
                deferred_empty.append(key)
                continue
            parts = split_oversize(sample, top.b_nro)
            parts_needed[key] = len(parts)
            if len(parts) > 1:
                self.stats.inc("n_split_requests")
            for part in parts:
                n = part.num_impressions
                if group and (len(group) + 1 > top.b_ro
                              or group_imps + n > top.b_nro):
                    yield from reassemble(
                        self._score_group(group, trace_ids, submit_ts))
                    yield from flush_empty()
                    group, group_imps = [], 0
                group.append((key, part))
                group_imps += n
        if group:
            yield from reassemble(
                self._score_group(group, trace_ids, submit_ts))
        yield from flush_empty()
        assert not parts_needed, "engine bug: unreassembled request parts"

    def _score_group(self, group: List[Tuple[Hashable, ROOSample]],
                     trace_ids: Dict[Hashable, int],
                     submit_ts: Optional[Dict[Hashable, float]] = None
                     ) -> Iterator[Tuple[Hashable, np.ndarray]]:
        """Score one flush-group at its bucket shape; yields (key, piece)
        for every request part via the batch plan's slot mapping.

        Spans: ``engine.flush`` (args below) > ``engine.bucket`` (packing)
        and, per batch, ``engine.score`` > ``engine.dispatch`` (the jitted
        call) and ``engine.fetch`` (the wait for the scores and their copy
        to the host). The flush's args: ``requests``, ``impressions``, the
        rung's ``b_ro`` and ``b_nro``, ``history_events`` (each row's
        history up to ``hist_len``), ``history_slots`` (``b_ro x hist_len``
        over its batches) and, on the online path, ``queued_ms_sum`` /
        ``queued_ms_max`` (the flush's start less each ``submit``) and,
        while the span records and the model runs the fused HSTU kernels,
        ``attn_tiles_live`` / ``attn_tiles_grid``: the tiles those kernels
        compute and the tiles of their dense grid, over the batches' rows,
        heads and layers (``_attn_tiles``)."""
        hist_len = self.policy.hist_len
        n_imps = sum(s.num_impressions for _, s in group)
        bucket = self.ladder.select(len(group), n_imps)
        args = dict(requests=len(group), impressions=n_imps,
                    b_ro=bucket.b_ro, b_nro=bucket.b_nro,
                    history_events=sum(min(len(s.history_ids), hist_len)
                                       for _, s in group))
        if submit_ts is not None:
            start = self.clock()
            queued = [(start - submit_ts[k]) * 1e3 for k, _ in group]
            args.update(queued_ms_sum=sum(queued), queued_ms_max=max(queued))
        with obs_trace.span("engine.flush", **args) as fspan:
            with obs_trace.span("engine.bucket"):
                self.stats.record_bucket(bucket)
                batcher = ROOBatcher(BatcherConfig(
                    b_ro=bucket.b_ro, b_nro=bucket.b_nro, hist_len=hist_len))
                samples = [s for _, s in group]
                plans = list(batcher.batches_with_plan(samples))
            fspan.set(history_slots=len(plans) * bucket.b_ro * hist_len)
            tiles_live = tiles_grid = 0
            for batch, plan in plans:
                if self._breaker_sheds():
                    for p in plan.requests:
                        yield (group[p.request_index][0],
                               ScoreError("shed: circuit breaker open",
                                          shed=True))
                    continue
                tids = {trace_ids.get(group[p.request_index][0])
                        for p in plan.requests} - {None}
                span = obs_trace.span("engine.score",
                                      rows=len(plan.requests),
                                      trace_ids=sorted(tids))
                try:
                    with span:
                        scores = self._score_batch(batch, samples, plan)
                except Exception as e:   # isolation boundary: batch != engine
                    self._breaker_record_failure()
                    self.stats.inc("n_failed_batches")
                    for p in plan.requests:
                        yield (group[p.request_index][0],
                               ScoreError(f"scoring failed: {e!r}"))
                    continue
                self._breaker_failures = 0
                self._breaker_open_until = None
                self.stats.inc("n_batches")
                if fspan.recording:
                    live, grid = self._attn_tiles(batch, samples, plan)
                    tiles_live += live
                    tiles_grid += grid
                for p in plan.requests:
                    if p.n_dropped:
                        raise RuntimeError(
                            "engine invariant violated: truncation inside a "
                            f"bucket-shaped batch ({p.n_dropped} dropped)")
                    yield (group[p.request_index][0],
                           scores[p.slot_start:p.slot_start + p.n_packed])
            if tiles_grid:
                fspan.set(attn_tiles_live=tiles_live,
                          attn_tiles_grid=tiles_grid)
        obs_export.maybe_emit("serve.flush")

    # ---- circuit breaker -----------------------------------------------------
    def _breaker_sheds(self) -> bool:
        """True when the open breaker should shed the next batch; an expired
        cooldown admits the batch as a half-open trial."""
        if (self.policy.breaker_threshold <= 0
                or self._breaker_open_until is None):
            return False
        if self.clock() < self._breaker_open_until:
            return True
        self._breaker_open_until = None        # half-open: one trial batch
        return False

    def _breaker_record_failure(self) -> None:
        self._breaker_failures += 1
        if (self.policy.breaker_threshold > 0
                and self._breaker_failures >= self.policy.breaker_threshold):
            if self._breaker_open_until is None:
                self.stats.n_breaker_opens += 1
            self._breaker_open_until = (self.clock()
                                        + self.policy.breaker_cooldown_s)

    def _score_batch(self, batch, samples: List[ROOSample],
                     plan: BatchPlan) -> np.ndarray:
        faults.maybe_fail("engine.score")   # injected forward failure
        from repro.kernels.dispatch import use_backend
        with use_backend(self.attn_backend), obs_trace.span("engine.dispatch"):
            scores = self._score_batch_device(batch, samples, plan)
        with obs_trace.span("engine.fetch"):
            out = np.asarray(scores)
        self._score_tail = out.shape[1:]
        return out

    def _score_batch_device(self, batch, samples: List[ROOSample],
                            plan: BatchPlan):
        if self.state_store is not None:
            return self._score_batch_incremental(batch, samples, plan)
        if self.cache is None:
            return self._score(self.params, batch)
        # cache path: try to serve the whole RO side from cache; on any
        # miss compute the user tower once for the batch and backfill.
        epoch = self._param_epoch
        keys = {p.row: request_key(samples[p.request_index])
                for p in plan.requests}
        cached = {row: self.cache.get(k, epoch) for row, k in keys.items()}
        if cached and all(v is not None for v in cached.values()):
            any_row = next(iter(cached.values()))
            u_host = np.zeros((batch.b_ro,) + any_row.shape, any_row.dtype)
            for row, v in cached.items():
                u_host[row] = v
            user = jnp.asarray(u_host)
            self.stats.inc("n_full_cache_batches")
        else:
            user = self._user(self.params, batch)
            u_host = np.asarray(user)
            for row, k in keys.items():
                self.cache.put(k, u_host[row], epoch)
        return self._from_user(self.params, batch, user)

    def _score_batch_incremental(self, batch, samples: List[ROOSample],
                                 plan: BatchPlan):
        """Incremental path: probe the state store per row, extend each
        user's K/V state with only their uncached events, score, and write
        the refreshed per-row states back.

        Misses (unknown user / eviction / epoch change / prefix mismatch)
        probe as prefix 0 with a zero state, which makes them full
        recomputes *through the same prefix kernel* — one parity-tested
        code path for hit and fallback. The per-batch new-event budget
        ``n_new`` is the max uncached count rounded up to a power of two,
        so jit sees at most log2(hist_cap) shapes per bucket.
        """
        ad = self.adapter
        epoch = self._param_epoch
        cap = ad.state_hist_len
        probes = {p.row: self.state_store.probe(
            samples[p.request_index], epoch, cap) for p in plan.requests}
        n_new_max = max([pr.eff_len - pr.prefix_len
                         for pr in probes.values()], default=1)
        n_new = 1
        while n_new < n_new_max:
            n_new *= 2
        n_new = min(n_new, cap)
        template = jax.tree.map(np.asarray, ad.init_user_state())
        rows = [probes[r].state
                if (r in probes and probes[r].state is not None) else template
                for r in range(batch.b_ro)]
        state = jax.tree.map(lambda *xs: jnp.asarray(np.stack(xs)), *rows)
        fn = self._from_state_jit.get(n_new)
        if fn is None:
            fn = jax.jit(lambda p, b, s, _n=n_new:
                         ad.score_from_state(p, b, s, n_new=_n))
            self._from_state_jit[n_new] = fn
        scores, new_state = fn(self.params, batch, state)
        new_host = jax.tree.map(np.asarray, new_state)
        for p in plan.requests:
            pr = probes[p.row]
            row_state = jax.tree.map(lambda a: np.array(a[p.row]), new_host)
            self.state_store.put(samples[p.request_index].user_id, epoch,
                                 pr.eff_len, pr.digest, row_state)
        self.stats.inc("n_incremental_batches")
        return scores
