"""Host-ingest staging for fused device jobs: line-rate H2D feed.

BENCH_r05 measured the engine's defining gap: q4 with device-side datagen
sustains ~3.7B eps while the same SQL with host ingest in the measured
path does 671k — a ~5000x gap that is ENTIRELY ingest+transfer, not
compute. StreamBox-HBM's (PAPERS.md) lesson is that a stream engine wins
by landing records in fast memory at arrival time and keeping the ingest
pipeline off the compute critical path. This module is that pipeline for
fused jobs:

* **Zero-copy columnar staging** — connector polls produce numpy int64/
  f64 surrogate columns (for nexmark, `connectors/nexmark.gen_surrogates`
  — bit-identical to the device generator by construction); the stager
  packs them into PINNED, REUSED numpy staging buffers with vectorized
  slice copies (`np.searchsorted` block cuts — no per-epoch Python row
  loops) and moves them with ONE `jax.device_put` per epoch, the same
  dlpack/direct-H2D seam `core/arrow.to_jax` rides. Two staging-buffer
  sets alternate so a buffer being refilled can never alias an in-flight
  transfer.

* **Double-buffered async H2D** — a staging thread packs and device_puts
  epoch N+1 while epoch N computes, so transfer hides under dispatch.
  The dispatch thread's residual (blocked-on-staging) wall is the
  profiler's `pack`/`h2d` phases; the staging thread's hidden walls are
  reported via `stats()` — overlap is proven when total h2d wall stays
  under total dispatch wall.

* **Fixed pow2-bucketed event capacities** — every feed buffer is sized
  to the job's epoch cadence (already a pow2 bucket) and the per-epoch
  row count rides as a masked device scalar, so the AOT compile service
  sees ONE aval signature regardless of how many rows a poll window
  actually admitted: zero fresh compiles across varying batch sizes.

* **Per-shard H2D placement** — under `mesh_shards > 1` each poll window
  is bucketed host-side into the shards' contiguous event blocks (the
  same block layout `vnode_block_bounds` keys device state by, and the
  exact host twin of the device generator's per-shard id slices) and
  transferred with the vnode-block `NamedSharding`
  (`parallel/mesh.state_sharding`), so every chip's ingest lands
  directly on its shard — closing the PR 7 residual where sharded
  sources only split device-side datagen ranges. Cross-vnode routing
  then happens where it always has: the in-program ICI exchange, which
  composes unchanged with PR 13's rebalanced `vnode_bounds`.

* **Multi-source multiplexing** — N independent connector sources share
  ONE global event clock; each epoch cuts one window across all of them
  and dispatches one fused epoch, with per-source row provenance
  (`source_rows`) and per-source PR 14 `AdmissionBucket` gating: an
  exhausted budget DEFERS the window (the rows stay at the connector —
  backpressure reaching the source), a throttle factor shrinks the
  admitted window. The shedding rung also defers here rather than
  dropping: a fused job's exact replay (recovery bit-identity) needs a
  gap-free event clock, so unadmitted windows are delayed, never lost —
  the admission lag still surfaces in rw_source_admission.

* **Replay** — every staged window's host arrays are RETAINED until the
  checkpoint that commits them (`trim`); growth replays and in-place
  crash-window re-dispatch rebuild their feeds from the retained window,
  and committed history re-derives from the sources' deterministic
  range-replay contract (`IngestSource.rows_for`) — the Kafka-offset-
  rewind analog the fused recovery design already relies on.
"""
from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np


def feed_capacity(epoch_events: int, n_shards: int = 1) -> int:
    """Static per-shard row capacity of one staged feed buffer: the
    ceil-div contiguous event block (matches the device generator's
    per-shard slicing, tail padding included)."""
    return -(-int(epoch_events) // max(1, int(n_shards)))


class IngestSource:
    """One connector feeding one IngestNode, multiplexed on the job's
    global event-id clock.

    The contract recovery leans on: `rows_for` is RANGE-REPLAYABLE —
    calling it again for the same id range yields the same rows (a pure
    generator, a seekable log, a retained-offset connector). That is the
    same determinism the fused recovery design has required of sources
    since the beginning (regenerate == re-read from offset)."""

    name: str = "?"                 # catalog source name (admission key)
    table: str = "?"

    def rows_for(self, lo: int, hi: int
                 ) -> Tuple[np.ndarray, List[np.ndarray]]:
        """(ascending event ids, surrogate columns) for this source's
        rows with event id in [lo, hi). Vectorized; no Python row loops."""
        raise NotImplementedError


class NexmarkIngestSource(IngestSource):
    """Host-side nexmark feed: numpy surrogate columns, bit-identical to
    `device/nexmark_gen.gen_table` over the same ids (verified in
    tests/test_ingest.py), polled straight off the shared event clock.
    With `live` (feed-column pruning, planner-proven), only those
    column positions are generated and shipped."""

    def __init__(self, name: str, table: str, gencfg, col_names,
                 rowid_pos: Optional[int], max_events: Optional[int],
                 live=None):
        self.name = name
        self.table = table
        self.gencfg = gencfg
        self.col_names = list(col_names)
        self.rowid_pos = rowid_pos
        self.max_events = max_events
        self.live = tuple(live) if live is not None else None

    @property
    def n_feed_cols(self) -> int:
        return len(self.live) if self.live is not None \
            else len(self.col_names)

    def rows_for(self, lo: int, hi: int):
        from ..connectors.nexmark import _event_kinds, gen_surrogates
        kind = {"person": 0, "auction": 1, "bid": 2}[self.table]
        if self.max_events is not None:
            hi = min(hi, self.max_events)
        ids = np.arange(lo, max(lo, hi), dtype=np.int64)
        ids = ids[_event_kinds(ids) == kind]
        pos = self.live if self.live is not None \
            else range(len(self.col_names))
        names = [self.col_names[i] for i in pos if i != self.rowid_pos]
        cols = gen_surrogates(self.gencfg, self.table, ids, cols=names)
        return ids, [ids if i == self.rowid_pos else cols[self.col_names[i]]
                     for i in pos]


class StagedWindow:
    """One staged epoch window: the device feeds plus the retained host
    arrays (replay) and the staging-thread cost attribution."""

    __slots__ = ("lo", "events", "feeds", "ingest_ts", "pack_s", "h2d_s",
                 "prefetched")

    def __init__(self, lo: int, events: int, feeds, ingest_ts,
                 pack_s: float, h2d_s: float, prefetched: bool):
        self.lo = lo
        self.events = events
        self.feeds = feeds              # {node idx: (count, pk, *cols)}
        self.ingest_ts = ingest_ts      # wall when the rows were polled
        self.pack_s = pack_s
        self.h2d_s = h2d_s
        self.prefetched = prefetched


class HostIngest:
    """The staging pipeline of one fused job: owns the sources, the
    reused staging buffers, the prefetch thread, the admission buckets,
    and the replay retention. `take(lo)` is the executor-dispatch seam:
    FusedJob asks for the window at its event counter and gets back
    pre-staged device buffers (idempotent per `lo` — a window taken but
    lost to a device fault before its dispatch was logged is re-served
    from retention on the recovery retry)."""

    def __init__(self, sources: Sequence[Tuple[int, IngestSource]],
                 epoch_events: int, mesh=None,
                 max_events: Optional[int] = None):
        self.sources = list(sources)          # [(node idx, source)]
        self.epoch_events = int(epoch_events)
        self.mesh = mesh
        if mesh is not None:
            from ..parallel.mesh import data_shards
            self.n_shards = data_shards(mesh)
        else:
            self.n_shards = 1
        self.cap = feed_capacity(epoch_events, self.n_shards)
        self.max_events = max_events
        # per-source PR 14 admission buckets (Database wires them after
        # CREATE); absent => ungated, exactly the old behavior
        self.buckets: Dict[str, Any] = {}
        # provenance: rows admitted into dispatch, per source
        self.source_rows: Dict[str, int] = {s.name: 0
                                            for _, s in self.sources}
        # retained host windows since the last checkpoint:
        # lo -> (events, [(ids, cols) per source], ingest_ts)
        self._retained: Dict[int, Tuple] = {}
        # every dispatched window boundary since job start (ints only):
        # the exact re-cut schedule for full-history replay (rebalance /
        # in-place recovery). A restart synthesizes uniform-cadence
        # windows instead — content-equal, see replay_range.
        self._history: List[Tuple[int, int]] = []
        self._hist_end = 0
        # bounded observability ring of recent (lo, events) windows —
        # _history trims at checkpoints (replay bookkeeping, not an
        # archive), so throttle behavior needs its own surface
        from collections import deque
        self.recent_windows: Any = deque(maxlen=64)
        # two alternating staging-buffer sets so refilling one can never
        # alias a transfer still in flight from the other. Packing is
        # additionally serialized (`_pack_lock`): a growth replay's
        # re-pack on the dispatch thread can overlap a prefetch on the
        # staging thread, and two concurrent packs must never interleave
        # on one buffer set.
        self._bufs = [self._alloc_buffers(), self._alloc_buffers()]
        self._flip = 0
        self._pack_lock = threading.Lock()
        # serializes whole _stage calls (admission verdicts, counter
        # updates, retention insert): a post-recovery sync stage on the
        # dispatch thread can overlap an in-flight prefetch of a LATER
        # window, and the peek-then-admit token check must stay atomic
        self._stage_lock = threading.Lock()
        # lazily probed: must the transfer source be copied because the
        # backend may share host buffers? (CPU: yes — see _pack_feeds)
        self._host_copy: Optional[bool] = None
        # prefetch plumbing: one staged window ahead, one worker thread
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self._staged: Optional[StagedWindow] = None
        self._inflight_lo: Optional[int] = None
        self._thread: Optional[threading.Thread] = None
        # the owning FusedJob's profiler (it sets this): the stager's
        # work as `rw:ingest.poll` / `.pack` / `.h2d` spans, the dispatch
        # thread's wait for it as `rw:ingest.wait`
        from ..utils.profile import NULL_PROFILER
        self.profiler = NULL_PROFILER
        self._stop = False
        # cost accounting (bench/tests): total staging walls wherever
        # they ran, split by whether the dispatch thread had to wait
        self.stat = {"windows": 0, "rows": 0, "events": 0,
                     "pack_s": 0.0, "h2d_s": 0.0, "prefetched": 0,
                     "sync_staged": 0, "deferred": 0, "replayed": 0}

    # ---- buffers --------------------------------------------------------
    def _feed_shape(self):
        return (self.cap,) if self.n_shards == 1 \
            else (self.n_shards, self.cap)

    def _alloc_buffers(self):
        """One reused staging set: per ingest node, a pk buffer plus one
        buffer per SHIPPED column (feed-column pruning keeps dead
        columns out of the pipeline entirely), shaped [cap] (single
        chip) or [n_shards, cap]."""
        shape = self._feed_shape()
        out = {}
        for idx, src in self.sources:
            ncols = getattr(src, "n_feed_cols", None)
            if ncols is None:
                # generic source: defer allocation until the first rows
                out[idx] = None
                continue
            out[idx] = (np.zeros(shape, np.int64),
                        [np.zeros(shape, np.int64) for _ in range(ncols)])
        return out

    def source_names(self) -> List[str]:
        return [s.name for _, s in self.sources]

    # ---- admission ------------------------------------------------------
    def epoch_refill(self, mult: int = 1) -> None:
        """Barrier-time token refill (the SourceExecutor contract): one
        token authorizes one window per source; a cadence stretch that
        dispatches k epochs per barrier needs k tokens or the tail
        windows defer."""
        for b in self.buckets.values():
            b.epoch_refill(mult)

    def _admit(self) -> Tuple[bool, float]:
        """(window admitted?, throttle factor). Any deferred source
        defers the WHOLE multiplexed window — the sources share one
        event clock, and advancing it past an unadmitted source would
        silently drop that source's rows. Shed verdicts defer too (see
        module docstring: the fused event clock must stay gap-free for
        exact replay; delay, never loss)."""
        bs = [b for _, src in self.sources
              for b in [self.buckets.get(src.name)] if b is not None]
        factor = min([1.0] + [float(getattr(b, "factor", 1.0))
                              for b in bs])
        # peek first: a window only cuts when EVERY source has budget —
        # consuming tokens from the willing sources while one defers
        # would drain their budgets (and inflate their admitted counts)
        # on attempts that move no rows
        lacking = [b for b in bs if b.tokens <= 0]
        if lacking:
            for b in lacking:
                b.admit()            # records offered + deferred/shed
            return False, factor
        for b in bs:
            b.admit()
        return True, factor

    # ---- staging --------------------------------------------------------
    def _cut(self, lo: int) -> Tuple[int, int]:
        """[lo, hi) of the next window under admission throttling."""
        ev = self.epoch_events
        ok, factor = self._admit()
        if not ok:
            return lo, 0
        if factor < 1.0:
            ev = max(1, int(ev * factor))
        if self.max_events is not None:
            ev = min(ev, max(0, self.max_events - lo))
        return lo, ev

    def _pack_feeds(self, lo: int, events: int, per_source) -> Tuple[
            Dict[int, Tuple], float, float]:
        """Pack retained host arrays into the next staging-buffer set and
        transfer: returns ({node idx: feed}, pack wall, h2d wall). The
        feed pytree is (count, pk, *cols) — count masks the pow2 buffer,
        so varying admitted sizes share one aval signature."""
        with self._pack_lock:
            return self._pack_feeds_locked(lo, events, per_source)

    def _pack_feeds_locked(self, lo: int, events: int, per_source):
        span = self.profiler.span
        with span("rw:ingest.pack", window=lo):
            t0 = time.perf_counter()
            host = self._pack_host(lo, per_source)
            t1 = time.perf_counter()
        with span("rw:ingest.h2d", window=lo):
            feeds = self._put_feeds(host)
            t2 = time.perf_counter()
        return feeds, t1 - t0, t2 - t1

    def _pack_host(self, lo: int, per_source) -> Dict[int, Tuple]:
        """The window's rows in the next staging-buffer set."""
        import jax
        if self._host_copy is None:
            self._host_copy = jax.default_backend() == "cpu"
        if self._host_copy:
            # CPU backend: jax.device_put may SHARE host numpy buffers
            # (mutation after conversion is undefined — observed:
            # deep-queue runs shipping another window's bytes), so pack
            # into FRESH arrays whose ownership passes to jax; one copy
            # cheaper than a defensive copy-on-ship of a reused set.
            # Real accelerators DMA host->HBM, so the pinned reused
            # sets are both safe and faster there.
            shape = self._feed_shape()
            bufs = {idx: (np.zeros(shape, np.int64),
                          [np.zeros(shape, c.dtype) for c in cols])
                    for (idx, _s), (_i, cols)
                    in zip(self.sources, per_source)}
        else:
            bufs = self._bufs[self._flip]
            self._flip ^= 1
        n = self.n_shards
        host: Dict[int, Tuple] = {}
        for (idx, src), (ids, cols) in zip(self.sources, per_source):
            if bufs.get(idx) is None:
                shape = (self.cap,) if n == 1 else (n, self.cap)
                bufs[idx] = (np.zeros(shape, np.int64),
                             [np.zeros(shape, c.dtype) for c in cols])
            pk_buf, col_bufs = bufs[idx]
            if n == 1:
                k = len(ids)
                pk_buf[:k] = ids
                for b, c in zip(col_bufs, cols):
                    b[:k] = c
                counts = np.int64(k)
            else:
                # host-side shard bucketing: the ceil-div contiguous
                # event blocks (device-generator twin); ids are sorted,
                # so one searchsorted cuts every block
                block = feed_capacity(self.epoch_events, n)
                bounds = lo + block * np.arange(n + 1, dtype=np.int64)
                cuts = np.searchsorted(ids, bounds)
                counts = np.diff(cuts).astype(np.int64)
                for s in range(n):
                    a, b_ = cuts[s], cuts[s + 1]
                    k = b_ - a
                    pk_buf[s, :k] = ids[a:b_]
                    for cb, c in zip(col_bufs, cols):
                        cb[s, :k] = c[a:b_]
            host[idx] = (counts, pk_buf, col_bufs)
        return host

    def _put_feeds(self, host: Dict[int, Tuple]) -> Dict[int, Tuple]:
        """The staging buffers on the device, transfer done."""
        import jax
        import jax.numpy as jnp
        feeds: Dict[int, Tuple] = {}
        if self.mesh is not None:
            from ..parallel.mesh import state_sharding
            sh = state_sharding(self.mesh)
            for idx, (counts, pk_buf, col_bufs) in host.items():
                feeds[idx] = jax.device_put(
                    (counts, pk_buf, *col_bufs), sh)
        else:
            for idx, (counts, pk_buf, col_bufs) in host.items():
                feeds[idx] = jax.device_put(
                    (jnp.int64(counts), pk_buf, *col_bufs))
        # block on the FEED arrays only (each buffer's own ready event,
        # never the queued compute): device_put is async, and the
        # transfer must be off the staging buffers before their next
        # refill. Paid on the staging thread, where it hides under
        # dispatch — this wall IS the measured h2d phase.
        for f in feeds.values():
            jax.block_until_ready(f)
        return feeds

    def _stage(self, lo: int, prefetched: bool) -> StagedWindow:
        """Poll + pack + transfer one window at `lo` (any thread).
        Deferred windows produce events == 0 and retain nothing — the
        data stays at the connectors."""
        with self._stage_lock:
            return self._stage_locked(lo, prefetched)

    def _stage_locked(self, lo: int, prefetched: bool) -> StagedWindow:
        lo, events = self._cut(lo)
        if events <= 0:
            self.stat["deferred"] += 1
            return StagedWindow(lo, 0, {}, None, 0.0, 0.0, prefetched)
        ingest_ts = time.time()
        per_source = []
        with self.profiler.span("rw:ingest.poll", window=lo):
            for idx, src in self.sources:
                ids, cols = src.rows_for(lo, lo + events)
                per_source.append((ids, cols))
                b = self.buckets.get(src.name)
                if b is not None:
                    b.note_admitted(len(ids))
                self.source_rows[src.name] += len(ids)
        feeds, pack_s, h2d_s = self._pack_feeds(lo, events, per_source)
        self._retained[lo] = (events, per_source, ingest_ts)
        self.stat["windows"] += 1
        self.stat["events"] += events
        self.stat["rows"] += sum(len(i) for i, _ in per_source)
        self.stat["pack_s"] += pack_s
        self.stat["h2d_s"] += h2d_s
        self.stat["prefetched" if prefetched else "sync_staged"] += 1
        return StagedWindow(lo, events, feeds, ingest_ts, pack_s, h2d_s,
                            prefetched)

    # ---- the dispatch seam ---------------------------------------------
    def take(self, lo: int) -> Tuple[StagedWindow, float, float]:
        """The window at event counter `lo`, plus the DISPATCH-THREAD
        walls it cost: (window, pack wall, h2d wall). With the double
        buffer warm, both walls collapse to the lock wait; the staging
        thread's hidden cost is in `stats()`. Kicks the prefetch of the
        next window before returning."""
        t0 = time.perf_counter()
        w: Optional[StagedWindow] = None
        # the dispatch thread blocked on the stager: on a busy device
        # this is where a host-fed job's `pack` phase goes
        with self.profiler.span("rw:ingest.wait", window=lo):
            with self._cv:
                while self._inflight_lo == lo:
                    self._cv.wait(0.05)
                if self._staged is not None and self._staged.lo == lo:
                    w, self._staged = self._staged, None
        wait_s = time.perf_counter() - t0
        pack_s = wait_s
        h2d_s = 0.0
        if w is None:
            retained = self._retained.get(lo)
            if retained is not None:
                # taken before, lost to a device fault before its
                # dispatch was logged: re-serve the identical window
                events, per_source, ingest_ts = retained
                feeds, p, h = self._pack_feeds(lo, events, per_source)
                self.stat["replayed"] += 1
                w = StagedWindow(lo, events, feeds, ingest_ts, p, h,
                                 False)
            else:
                w = self._stage(lo, prefetched=False)
            pack_s += w.pack_s
            h2d_s += w.h2d_s
        if w.events > 0:
            if lo >= self._hist_end:
                self._history.append((lo, w.events))
                self._hist_end = lo + w.events
                self.recent_windows.append((lo, w.events))
            nxt = lo + w.events
            if self.max_events is None or nxt < self.max_events:
                self._prefetch(nxt)
        return w, pack_s, h2d_s

    def _prefetch(self, lo: int) -> None:
        with self._cv:
            if self._stop or self._inflight_lo is not None \
                    or (self._staged is not None and self._staged.lo == lo) \
                    or lo in self._retained:
                return
            self._inflight_lo = lo
            if self._thread is None or not self._thread.is_alive():
                self._thread = threading.Thread(
                    target=self._prefetch_loop, daemon=True,
                    name="rw-ingest-stage")
                self._thread.start()
            self._cv.notify_all()

    def _prefetch_loop(self) -> None:
        while True:
            with self._cv:
                # blocking wait, no timeout: an idle stager (job drained,
                # or an abandoned test Database) costs zero wakeups —
                # `_prefetch` and `close` both notify
                while self._inflight_lo is None and not self._stop:
                    self._cv.wait()
                if self._stop:
                    return
                lo = self._inflight_lo
            try:
                w = self._stage(lo, prefetched=True)
            except Exception:
                w = None         # staging must never kill the job; the
            with self._cv:       # dispatch thread re-stages synchronously
                if w is not None and w.events > 0:
                    self._staged = w
                self._inflight_lo = None
                self._cv.notify_all()

    def close(self) -> None:
        with self._cv:
            self._stop = True
            self._cv.notify_all()
        if self._thread is not None:
            self._thread.join(5.0)

    # ---- replay ---------------------------------------------------------
    def replay_range(self, lo: int, hi: int):
        """Yield (window lo, events, feeds) covering [lo, hi) — the
        growth-replay / recovery path. Retained windows replay verbatim
        (same boundaries, same rows); committed history re-derives from
        the sources' deterministic range contract, cut at the recorded
        boundaries (or, after a restart lost the in-memory schedule, at
        uniform cadence — same rows in the same order, grouped into
        different epochs: the sorted device state is content-identical
        either way, the cadence-stretch argument)."""
        sched = [(w, e) for w, e in self._history if lo <= w < hi]
        covered = sched and sched[0][0] == lo \
            and all(sched[i][0] + sched[i][1] == sched[i + 1][0]
                    for i in range(len(sched) - 1)) \
            and sched[-1][0] + sched[-1][1] >= hi
        if not covered:
            sched = []
            c = lo
            while c < hi:
                ev = min(self.epoch_events, hi - c)
                sched.append((c, ev))
                c += ev
        for wlo, ev in sched:
            ev = min(ev, hi - wlo)
            retained = self._retained.get(wlo)
            if retained is not None and retained[0] == ev:
                _, per_source, _ts = retained
            else:
                per_source = [src.rows_for(wlo, wlo + ev)
                              for _, src in self.sources]
            feeds, p, h = self._pack_feeds(wlo, ev, per_source)
            self.stat["pack_s"] += p
            self.stat["h2d_s"] += h
            yield wlo, ev, feeds

    def host_window(self, lo: int, events: int):
        """The window's HOST rows, one (ids, cols) per source — the
        tier-promotion candidate probe (device/tiering.py) reads these
        to recompute each node's packed keys host-side. Retained
        windows answer from the staged arrays for free; otherwise the
        deterministic range contract re-derives them."""
        retained = self._retained.get(lo)
        if retained is not None and retained[0] == events:
            return retained[1]
        return [src.rows_for(lo, lo + events)
                for _, src in self.sources]

    def trim(self, committed: int) -> None:
        """Checkpoint trim: windows at or past `committed` stay (the
        next crash window replays them); everything older is durable."""
        # snapshot the keys first: the staging thread inserts retained
        # windows concurrently, and iterating the live dict would race
        for k in list(self._retained):
            if k < committed:
                del self._retained[k]
        # committed windows' boundary schedule is done too: replays of
        # committed history fall back to the uniform-cadence re-cut
        # (content-identical), so an unbounded job must not accumulate
        # one tuple per window forever
        self._history = [(w, e) for w, e in self._history
                         if w + e > committed]
        with self._cv:
            if self._staged is not None and self._staged.lo < committed:
                self._staged = None

    # ---- surfaces -------------------------------------------------------
    def stats(self) -> Dict[str, Any]:
        out = dict(self.stat)
        out["sources"] = dict(self.source_rows)
        out["retained_windows"] = len(self._retained)
        out["shards"] = self.n_shards
        out["feed_capacity"] = self.cap
        return out
