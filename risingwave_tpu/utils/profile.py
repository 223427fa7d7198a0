"""Epoch-timeline device profiler for fused jobs.

StreamBox-HBM's lesson (arxiv 1901.01328) is that an HBM-resident
streaming engine is only tunable with continuous phase/occupancy
accounting; this module is that accounting for the fused execution path.
Each epoch of a `FusedJob` is one phase-split span:

  pack         — building the epoch's host-side inputs: the event cursor
                 for device-datagen jobs; for host-ingest jobs
                 (device/ingest.py) the wall the dispatch thread spends
                 packing poll windows into staging buffers OR blocked on
                 the staging thread doing it (a well-overlapped double
                 buffer drives this toward zero)
  h2d          — host->device transfer enqueue (`jax.device_put` of the
                 staged ingest buffers) as seen by the dispatch thread;
                 split disjointly out of the old `host_pack` so the
                 ingest pipeline's two costs are separately attributable.
                 The stager's HIDDEN walls (work done on the staging
                 thread while the device computes) are reported through
                 `HostIngest.stats()`, not epoch spans — in-span phases
                 stay on-thread so they keep summing to <= epoch wall
  dispatch     — the async per-node jit dispatch loop (no device sync)
  exchange     — dispatching the in-program ICI shuffle of mesh-sharded
                 programs (device/shard_exec.py); 0 on single-chip jobs.
                 Split out of `dispatch` so the all_to_all stage's cost
                 is attributable per shard count
  device_sync  — blocking on the device (`jax.device_get` of stats_acc at
                 a checkpoint/SELECT — covers ALL device compute enqueued
                 since the last sync, growth replays included)
  commit       — MV mirror diff + job-state-table rows at a checkpoint

Every span and row carries the job's `shards` dimension (device mesh
size; 1 = single chip) so phase timings from sharded and unsharded runs
never aggregate silently.

Non-checkpoint epochs only carry pack+h2d+dispatch (their device work is
paid for by the next sync — that asymmetry is the async-dispatch design,
and exactly what the profiler exists to make visible).

A compile leaves ONE record, its span. jax reports every trace, lowering,
persistent-cache read and backend compile through `jax.monitoring`, on the
thread that compiles; one pair of listeners (registered once, when
`risingwave_tpu.device` is imported) gathers what a thread compiled and
either the compile service takes it for its open `rw:compile` span
(`take_compiled`) or, where no `rw:compile` is open on that thread, it
becomes a `rw:compile.inline` span under the innermost open span (a node
step jitted inline with the service off, the stats stack / fold, a tier,
gather or exchange program, an eager primitive). Both carry what jax did:
`persistent` (`"hit"`: the executable was read from the persistent cache,
`retrieval_s` says how long that took; `"miss"`: jax asked the cache and
built the program; `"off"`: jax never asked), `backend_compile_s` (the
build's seconds — on a hit the load's, jax times the cache read inside it),
`trace_s`, `lower_s`, `fun_name` (and a `rw:compile` the `programs` it
took: one). Two words, two meanings:
`cache_hit` is the compile MANIFEST's (some process compiled this
signature's digest once), `persistent` is what jax did this time; a
`rw:compile` with `cache_hit` and `persistent == "miss"` also says
`lost=True`: an executable this machine was thought to have and jax built
anew (a build shorter than the least jax writes an entry for is never
lost: the cache never held it). `COMPILES` counts the same events process-wide, whatever span was or
was not open. The labeled compile/retrace record of the old readers
(`compile_info`, `epoch_profile.jsonl`, `summary()["compile_events"]`,
`rw_epoch_profile`'s EXPLAIN lines, `risectl profile`) is written when such
a span closes: a `rw:compile` that ended `ok`, or a `rw:compile.inline`
directly under a `rw:step` (kind `compile` for a node's first, `retrace`
after a growth or for any other re-trace jax really made — a slow step is
no longer taken for one).

Records land in a memory ring (the `rw_epoch_profile` system table) AND —
when a data directory is attached — in `epoch_profile.jsonl`, appended at
checkpoints so `risectl profile` works offline against any data dir, the
same contract as `barrier_trace.jsonl`.

The phases are SPANS (`JobProfiler.span`, `span`): name, start and end on
`time.perf_counter_ns`, the innermost open span of the same thread as
parent, the thread, and the identifiers that tie one barrier's work
together (`job`, job instance `inst`, barrier `epoch`, event `seq`).
Every span name starts with `rw:` (the benchmark's trace reduction
charges idle gaps to host events named `tick:`, `sync…` or `window`; a
program span under one of those names would change its numbers). A span
also enters a `jax.profiler.TraceAnnotation` of its name, so under a
profiler session it lands on the host plane of the same `.xplane.pb`, on
the device trace's clock. Finished spans go to `SPANS`, one bounded
process-global ring beside `blackbox.RECORDER` and `metrics.REGISTRY`:
they outlive the job that made them. A span named `rw:<phase>` feeds the
phase totals when it closes, so `phase_s`, `rw_epoch_profile`,
`epoch_profile.jsonl` and `risectl profile` read what they always read;
what is finer sits inside the phases as child spans:

  rw:barrier > rw:store_commit | rw:epoch > rw:<phase>
  rw:pack > rw:event_lo            rw:dispatch > rw:step > rw:compile_wait
  rw:dispatch > rw:stats_fold      rw:device_sync > rw:stats_pull | rw:growth
  rw:commit > rw:commit.mirror > .pull | .diff | .table_commit
    (`rows` the MV holds; `inserted`, `updated`, `deleted`: the keys only
    the new image has, the keys of both whose row changed, the keys only
    the last image had — what the state table was handed;
    `keys_vectorised`: the keys came from one matrix, not `key_of` a row)
  rw:commit.mirror.pull > rw:commit.mirror.decode (`rows`,
    `string_cols`: the MV's VARCHAR columns turned from surrogates into
    strings; only an MV that has one; the same span under a SELECT's pull)
  rw:commit > rw:commit.job_state | rw:commit.gauges (carries
    `flow_report`, `FusedJob.flow_report()`: per node rows in and out,
    the lanes its step was handed, live entries over capacity — of the
    fullest slot, and under `slots` of every slot a keyed node has)
  rw:dispatch > rw:exchange (one an exchange stage of a mesh-sharded
    job: `node`, `xi`, `shards`, `exch` = bucket capacity, `rows_slots` =
    shards x exch, the rows the stage hands its step); `rw:commit.gauges`
    of such a job carries `shard_report` (`FusedJob.shard_report()`)
  rw:step carries `node`, `i` and what `Node.span_attrs()` adds: the
    agg step over a pre-combined delta says `recombine` (true behind an
    exchange, false where it takes the pre-combine's delta as it is); a
    device source's step says `lanes` (the lanes of the delta it made,
    all shards': its own table's rows, pow2) `of` the epoch's events;
    a hop's step says `fanout` (windows a row), an agg's that keeps
    retractable min/max multisets says `minputs` (how many)
  rw:compile (worker thread; `node`, `label`, `kind`, `bucket`,
    `cache_hit`, `ok`, what jax did, `code_bytes` where the executable
    says)                          rw:ingest.poll | .pack | .h2d (stager)
  <any open span> > rw:compile.inline (what jax did, `node` where the
    parent has one)
  rw:pack > rw:ingest.wait (the dispatch thread blocked on the stager)
  rw:sql > rw:sql.fuse_plan
  rw:boot > rw:boot.start | rw:boot.import (the OS's process start — field
    22 of /proc/self/stat on this clock — to the end of the import of
    `risingwave_tpu.device`: `.start` is the interpreter and whatever the
    caller imported before the package's first line, `.import` jax, x64,
    the cache's placement and the program's own modules)
  rw:boot.backend (a device Database's first touch of the backend; short
    where the caller initialised it) rw:compile_drain (`wait_idle()`)

Overhead when enabled is two clock reads, one annotation and one ring
append per span, a few dozen spans per barrier; `DeviceConfig.profile=
False` hands out one shared null span and records nothing.
"""
from __future__ import annotations

import itertools
import json
import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

from .blackbox import RECORDER

PROFILE_FILE = "epoch_profile.jsonl"
_MAX_FILE_BYTES = 4 << 20
# record schema version stamped on every epoch record; readers normalize
# through `decode_epoch`, so a format change is one branch on the version
PROFILE_SCHEMA = 2
PHASES = ("pack", "h2d", "promote_h2d", "dispatch", "exchange",
          "device_sync", "demote_d2h", "commit")
RING = 512

# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

SPAN_PREFIX = "rw:"
# finished spans of every job of the process, oldest first (q7 makes ~40
# a barrier): plain dicts with id, parent, name, t0, t1 (perf_counter_ns),
# thread, tname, the identifiers in ID_KEYS where known, and the span's
# own attributes
SPAN_RING = 16384
SPANS: deque = deque(maxlen=SPAN_RING)
# identifiers a child span inherits from its parent
ID_KEYS = ("job", "inst", "epoch", "seq")
_OPEN = threading.local()          # .stack: this thread's open spans
_SPAN_IDS = itertools.count(1)
_INSTANCES = itertools.count(1)    # one number per JobProfiler
_ANNOTATION = None


def _annotation(name: str):
    global _ANNOTATION
    if _ANNOTATION is None:
        from jax.profiler import TraceAnnotation
        _ANNOTATION = TraceAnnotation
    return _ANNOTATION(name)


def _open_stack() -> List["Span"]:
    try:
        return _OPEN.stack
    except AttributeError:
        _OPEN.stack = []
        return _OPEN.stack


class Span:
    """One timed region; a context manager. `record=False` only times
    (no ring record, no annotation, not a parent): what a caller that
    needs the seconds uses when its job's profiler is off."""

    __slots__ = ("name", "ids", "attrs", "owner", "record", "id", "parent",
                 "t0", "t1", "excluded", "_ann")

    def __init__(self, name: str, attrs: Optional[Dict[str, Any]] = None,
                 owner: Optional["JobProfiler"] = None, record: bool = True):
        if not name.startswith(SPAN_PREFIX):
            raise ValueError(f"span name {name!r} does not start with "
                             f"{SPAN_PREFIX!r}")
        self.name = name
        self.attrs = attrs or {}
        self.ids = {k: self.attrs.pop(k) for k in ID_KEYS if k in self.attrs}
        self.owner = owner
        self.record = record
        self.id = self.parent = None
        self.t0 = self.t1 = 0
        # seconds inside this span that were handed to ANOTHER phase
        self.excluded = 0.0

    def set(self, **attrs) -> None:
        """Attributes known only once the work is done (`rows`)."""
        self.attrs.update(attrs)

    def no_phase(self) -> None:
        """This span found nothing to do: it stays a span and feeds no
        phase."""
        self.owner = None

    @property
    def seconds(self) -> float:
        return (self.t1 - self.t0) / 1e9

    def __enter__(self) -> "Span":
        if self.record:
            stack = _open_stack()
            if stack:
                self.parent = stack[-1].id
                self.ids = {**stack[-1].ids, **self.ids}
            self.id = next(_SPAN_IDS)
            stack.append(self)
            self._ann = _annotation(self.name)
            self._ann.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1 = time.perf_counter_ns()
        stack = _open_stack()
        if not self.record or self not in stack:
            return False      # not recorded, or gone with its parent
        # spans an exception left open above this one go with it
        while True:
            top = stack.pop()
            top._ann.__exit__(*exc)
            if top is self:
                break
        self._record(stack)
        return False

    def _record(self, still_open: List["Span"]) -> None:
        t = threading.current_thread()
        SPANS.append({"id": self.id, "parent": self.parent,
                      "name": self.name, "t0": self.t0, "t1": self.t1,
                      "thread": t.ident, "tname": t.name,
                      **self.ids, **self.attrs})
        if self.owner is not None:
            self.owner._span_closed(self, still_open)

    @classmethod
    def past(cls, name: str, t0: int, t1: int,
             parent: Optional["Span"] = None, **attrs) -> "Span":
        """A span of this thread that is known only once it is over (a
        compile jax reports when it ends, the start of the process):
        recorded at once, as a child of `parent` with its identifiers
        and its owner; no annotation, since its start has passed."""
        sp = cls(name, attrs, owner=parent.owner if parent else None)
        sp.id, sp.t0, sp.t1 = next(_SPAN_IDS), t0, t1
        if parent is not None:
            sp.parent, sp.ids = parent.id, {**parent.ids, **sp.ids}
        sp._record(_open_stack())
        return sp


class _NullSpan:
    """What a disabled profiler hands out: one shared object."""
    seconds = 0.0
    excluded = 0.0

    def set(self, **attrs) -> None:
        pass

    def no_phase(self) -> None:
        pass

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False


NULL_SPAN = _NullSpan()


def span(name: str, **attrs) -> Span:
    """A span of no particular job (a barrier, a statement)."""
    return Span(name, attrs)


def null_span(name: str, **attrs) -> _NullSpan:
    return NULL_SPAN


def spans(enabled: bool):
    """`span` or `null_span`: what an owner without a JobProfiler (the
    Database, for `DeviceConfig.profile`) calls to open its spans."""
    return span if enabled else null_span


# ---------------------------------------------------------------------------
# what jax says of a compile
# ---------------------------------------------------------------------------

COMPILE_SPANS = ("rw:compile", "rw:compile.inline")
# a backend compile of at least this many seconds is a program jax BUILT
# (a node step, an exchange); below it, an eager primitive or a tiny
# program (`small`). The benchmark's `setup_compiles` draws the same line.
BUILD_MIN_S = 1.0
# jax's own totals for this process, counted from its events whatever span
# is open: programs built / loaded from the persistent cache (and the
# seconds of the builds and of the cache reads), builds under
# BUILD_MIN_S, and executables the manifest promised and jax built anew
COMPILES: Dict[str, Any] = {"built": 0, "built_s": 0.0, "small": 0,
                            "small_s": 0.0, "loaded": 0, "loaded_s": 0.0,
                            "lost": 0}
_COMPILES_LOCK = threading.Lock()
_EV_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
_EV_HIT = "/jax/compilation_cache/cache_hits"
_EV_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_EV_BACKEND = "/jax/core/compile/backend_compile_duration"
_EV_STAGES = {"/jax/core/compile/jaxpr_trace_duration": "trace",
              "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower"}
_LISTENING = False


def _pending() -> Dict[str, Any]:
    """What this thread has traced, lowered and asked the cache since its
    last backend compile."""
    try:
        return _OPEN.pending
    except AttributeError:
        _OPEN.pending = {"trace": [], "lower": []}
        return _OPEN.pending


def _on_event(event: str, **kwargs) -> None:
    if event == _EV_ASKED:
        _pending()["asked"] = True
    elif event == _EV_HIT:
        _pending()["hit"] = True


def _on_duration(event: str, duration: float, **kwargs) -> None:
    stage = _EV_STAGES.get(event)
    if stage is not None:
        # a jit traced inside another's trace reports first and lies
        # inside the outer one's interval: keep the outermost only
        t1 = time.perf_counter_ns()
        t0 = t1 - int(duration * 1e9)
        ivs = _pending()[stage]
        ivs[:] = [iv for iv in ivs if iv[0] < t0]
        ivs.append((t0, t1))
    elif event == _EV_RETRIEVAL:
        _pending()["retrieval_s"] = duration
    elif event == _EV_BACKEND:
        _backend_compiled(duration, str(kwargs.get("fun_name", "")))


def _backend_compiled(seconds: float, fun_name: str) -> None:
    """jax finished one backend compile (or cache read) on this thread:
    count it, add it to what `take_compiled` hands the compile service
    and, where no `rw:compile` is open here, make it a
    `rw:compile.inline` span under the innermost open span."""
    now = time.perf_counter_ns()
    pend = _pending()
    del _OPEN.pending
    # jax "asks" a cache that has no directory too (the key is made
    # before the directory is looked for): that is no cache, not a miss
    import jax
    persistent = ("hit" if pend.get("hit")
                  else "miss" if pend.get("asked")
                  and jax.config.jax_compilation_cache_dir else "off")
    stack = _open_stack()
    since = stack[-1].t0 if stack else 0
    stages = {k: [iv for iv in pend[k] if iv[0] >= since]
              for k in ("trace", "lower")}
    info = {"fun_name": fun_name, "persistent": persistent,
            "backend_compile_s": seconds,
            "trace_s": sum(b - a for a, b in stages["trace"]) / 1e9,
            "lower_s": sum(b - a for a, b in stages["lower"]) / 1e9}
    if persistent == "hit":
        info["retrieval_s"] = pend.get("retrieval_s", 0.0)
    with _COMPILES_LOCK:
        if persistent == "hit":
            COMPILES["loaded"] += 1
            COMPILES["loaded_s"] += info["retrieval_s"]
        else:
            kind = "built" if seconds >= BUILD_MIN_S else "small"
            COMPILES[kind] += 1
            COMPILES[kind + "_s"] += seconds
    if stack and not any(sp.name == "rw:compile" for sp in stack):
        parent = stack[-1]
        t0 = min([now - int(seconds * 1e9)]
                 + [a for ivs in stages.values() for a, _b in ivs])
        if "node" in parent.attrs:
            info["node"] = parent.attrs["node"]
        Span.past("rw:compile.inline", max(t0, parent.t0), now, parent,
                  **info)
        return
    # for `take_compiled`. Several programs before one take: the sums,
    # and the last one's words
    done = getattr(_OPEN, "compiled", None) or {"programs": 0}
    for k in ("backend_compile_s", "trace_s", "lower_s", "retrieval_s"):
        if k in done:
            info[k] = info.get(k, 0.0) + done[k]
    info["programs"] = done["programs"] + 1
    _OPEN.compiled = info


def take_compiled(cache_hit: bool = False) -> Dict[str, Any]:
    """What jax compiled on this thread since the last take (`{}` where
    nothing, or the listeners are not registered): the attributes of the
    compile service's `rw:compile` span. `cache_hit` is the manifest's
    word for the signature; where jax asked the cache and built the
    program all the same, the executable was lost — unless the build was
    shorter than the least jax writes an entry for: such a program was
    never in the cache."""
    info, _OPEN.compiled = getattr(_OPEN, "compiled", None) or {}, None
    import jax
    if cache_hit and info.get("persistent") == "miss" \
            and info["backend_compile_s"] >= \
            jax.config.jax_persistent_cache_min_compile_time_secs:
        info["lost"] = True
        with _COMPILES_LOCK:
            COMPILES["lost"] += 1
    return info


def listen() -> None:
    """Register the one pair of jax.monitoring listeners (idempotent).
    They run at compile time only: a steady-state epoch never enters
    them."""
    global _LISTENING
    if _LISTENING:
        return
    _LISTENING = True
    from jax import monitoring
    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_duration)


# ---------------------------------------------------------------------------
# the seconds before the first statement
# ---------------------------------------------------------------------------

_BOOTED = False
_BACKEND_TOUCHED = False


def _process_start_ns() -> Optional[int]:
    """The OS's start of this process on the `perf_counter_ns` clock:
    field 22 of /proc/self/stat (clock ticks after boot) against the
    boot clock now. `None` where the OS does not say."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        age_s = (time.clock_gettime(time.CLOCK_BOOTTIME)
                 - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError, AttributeError):
        return None
    return time.perf_counter_ns() - int(age_s * 1e9)


def boot_done(t_import: int) -> None:
    """The end of the import of `risingwave_tpu.device` (its last line
    calls this; `t_import` is the package's first line): record
    `rw:boot` and its two parts, once a process, and start listening to
    jax's compile events."""
    global _BOOTED
    if _BOOTED:
        return
    _BOOTED = True
    listen()
    t1 = time.perf_counter_ns()
    t_proc = _process_start_ns()
    t_proc = t_import if t_proc is None else min(t_proc, t_import)
    boot = Span.past("rw:boot", t_proc, t1)
    if t_proc < t_import:
        Span.past("rw:boot.start", t_proc, t_import, boot)
    Span.past("rw:boot.import", t_import, t1, boot)


def boot_backend(span_fn) -> None:
    """A device Database's first touch of the backend, once a process,
    under `span_fn` (the Database's `span` or `null_span`). Short where
    the caller initialised the backend already: the seconds before it are
    then the caller's, and no span of the program covers them."""
    global _BACKEND_TOUCHED
    if _BACKEND_TOUCHED:
        return
    _BACKEND_TOUCHED = True
    import jax
    with span_fn("rw:boot.backend") as sp:
        devs = jax.devices()
        sp.set(platform=devs[0].platform, devices=len(devs))


class JobProfiler:
    """Per-FusedJob epoch profiler. All methods are cheap no-ops when
    `enabled` is False; callers guard their own perf_counter reads on
    `enabled` so a disabled profiler costs one attribute load per epoch."""

    def __init__(self, job: str, enabled: bool = True, shards: int = 1):
        self.job = job
        self.enabled = enabled
        # device mesh size of the job's fused program (1 = single chip):
        # a dimension on every span so sharded/unsharded timings are
        # never conflated
        self.shards = shards
        # which of the process's profilers this is: a re-created job of
        # the same name is a new instance, and its spans say so
        self.instance = next(_INSTANCES) if enabled else 0
        self.ring: deque = deque(maxlen=RING)
        # compile records incl. bucket/aot/cache_hit labels
        self.compile_info: deque = deque(maxlen=256)
        # events may arrive from compile-service worker threads while the
        # barrier thread flushes — guard the shared buffers
        self._ev_lock = threading.Lock()
        self.path: Optional[str] = None
        self._f = None
        self._buf: List[Dict[str, Any]] = []
        self._cur: Optional[Dict[str, Any]] = None
        self.epochs = 0
        self.totals = {p: 0.0 for p in PHASES}
        # node index -> why its NEXT compile happens ("compile": cold
        # start; "retrace": capacity growth re-traced the node); filled
        # by FusedJob, read where that compile is requested
        # (FusedProgram.epoch, for the service) or recorded (an inline
        # compile's span closing under the node's `rw:step`)
        self.pending_compile: Dict[int, str] = {}

    # ---- wiring ----------------------------------------------------------
    def attach(self, data_dir: Optional[str]) -> None:
        """Mirror records into <data_dir>/epoch_profile.jsonl (the
        `risectl profile` surface)."""
        if data_dir and self.enabled:
            self.path = os.path.join(data_dir, PROFILE_FILE)

    # ---- spans -----------------------------------------------------------
    def span(self, name: str, **attrs):
        """A span of this job (see the module docstring); named
        `rw:<phase>` it feeds that phase when it closes."""
        if not self.enabled:
            return NULL_SPAN
        attrs["job"], attrs["inst"] = self.job, self.instance
        return Span(name, attrs, owner=self)

    def _span_closed(self, sp: Span, still_open: List[Span]) -> None:
        """Feed the phase a `rw:<phase>` span is named after. Phases stay
        disjoint: a phase span directly inside another takes its seconds
        out of the outer one (`rw:exchange` in `rw:dispatch`); one deeper
        inside (the steps of a growth replay under `rw:device_sync`)
        feeds nothing, its time is the outer phase's."""
        if sp.name in COMPILE_SPANS:
            self._compile_closed(sp, still_open)
            return
        phase = sp.name[len(SPAN_PREFIX):]
        if phase not in self.totals:
            return
        outer = next((o for o in reversed(still_open)
                      if o.owner is self
                      and o.name[len(SPAN_PREFIX):] in self.totals), None)
        if outer is not None:
            if outer.id != sp.parent:
                return
            outer.excluded += sp.seconds
        self.phase(phase, sp.seconds - sp.excluded)

    # ---- epoch spans -----------------------------------------------------
    def begin_epoch(self, seq: int, events: int,
                    epoch: Optional[int] = None) -> None:
        if self._cur is not None:
            # an exception ended the last barrier's work: close its span
            self._cur["span"].__exit__(None, None, None)
        attrs = {"seq": seq, "events": events}
        if epoch is not None:
            attrs["epoch"] = epoch
        sp = self.span("rw:epoch", **attrs)
        sp.__enter__()
        self._cur = {"seq": seq, "events": events, "ph": {}, "span": sp}

    def phase(self, name: str, seconds: float) -> None:
        """Accumulate a phase duration: what a closing `rw:<phase>` span
        calls, and what a duration measured on another thread and handed
        over is recorded with. Sync time from OUTSIDE an epoch span (a
        SELECT pulling the MV between barriers) still lands in the totals
        so warmup decomposition stays honest."""
        self.totals[name] = self.totals.get(name, 0.0) + seconds
        if self._cur is not None:
            ph = self._cur["ph"]
            ph[name] = ph.get(name, 0.0) + seconds

    def end_epoch(self) -> None:
        cur = self._cur
        if cur is None:
            return
        self._cur = None
        sp = cur["span"]
        sp.__exit__(None, None, None)
        wall = sp.seconds
        # "ts" = epoch END wall clock: the unified trace export
        # (utils/export.py) places the span at [ts - wall, ts] on the
        # coordinator timeline
        rec = {"ev": "epoch", "schema": PROFILE_SCHEMA, "job": self.job,
               "seq": cur["seq"], "events": cur["events"],
               "shards": self.shards, "ts": time.time(),
               "wall_ms": wall * 1e3,
               "ph_ms": {k: v * 1e3 for k, v in cur["ph"].items()}}
        self.ring.append(rec)
        with self._ev_lock:
            self._buf.append(rec)
        self.epochs += 1
        try:
            RECORDER.record("epoch", {
                "job": self.job, "seq": rec["seq"],
                "events": rec["events"], "shards": self.shards,
                "wall_ms": round(rec["wall_ms"], 3),
                "ph_ms": {k: round(v, 3)
                          for k, v in rec["ph_ms"].items()}})
        except Exception:
            pass             # the flight recorder must never fail an epoch

    # ---- compile / retrace events ---------------------------------------
    def _compile_closed(self, sp: Span, still_open: List[Span]) -> None:
        """A compile's span closed: write the labeled record the old
        readers read. A `rw:compile` of the service that ended `ok`
        carries its label and kind; a `rw:compile.inline` is a node's
        compile only directly under that node's `rw:step`, which names
        it (`label`, `i`)."""
        a = sp.attrs
        if sp.name == "rw:compile":
            if not a.get("ok"):
                return
            label, kind = a["label"], a["kind"]
        else:
            step = next((o for o in still_open if o.id == sp.parent), None)
            if step is None or step.name != "rw:step":
                return
            label = step.attrs["label"]
            kind = self.pending_compile.pop(step.attrs["i"], "retrace")
        self.compile_event(label, sp.seconds, kind=kind,
                           bucket=a.get("bucket"), aot=a.get("aot", False),
                           cache_hit=a.get("cache_hit", False),
                           persistent=a.get("persistent"))

    def compile_event(self, label: str, seconds: float,
                      kind: str = "compile", bucket: Optional[str] = None,
                      aot: bool = False, cache_hit: bool = False,
                      persistent: Optional[str] = None) -> None:
        """Record one compile/retrace; called where its span closes
        (`_compile_closed`). `bucket` names the capacity bucket the
        trace was shaped for, `aot` marks background (compile-service)
        compiles vs inline ones, `cache_hit` signatures the compile
        manifest knew, `persistent` what jax did (`hit` / `miss` /
        `off`) — together they decompose warmup into named, attributable
        compiles. Thread-safe: the compile service's spans close on its
        worker threads."""
        rec = {"ev": "compile", "job": self.job, "label": label,
               "kind": kind, "s": seconds, "ts": time.time()}
        if bucket is not None:
            rec["bucket"] = bucket
        if aot:
            rec["aot"] = True
        if cache_hit:
            rec["cache_hit"] = True
        if persistent is not None:
            rec["persistent"] = persistent
        with self._ev_lock:
            self.compile_info.append(rec)
            self._buf.append(rec)

    @property
    def compiles(self) -> List[Tuple[str, str, float]]:
        """(label, kind, seconds) of every compile record."""
        with self._ev_lock:
            return [(r["label"], r["kind"], r["s"])
                    for r in self.compile_info]

    # ---- file sink (flushed at checkpoints) ------------------------------
    def flush(self) -> None:
        """Write buffered records to epoch_profile.jsonl. The WHOLE
        write+rotate runs under the event lock: flush is reachable from
        more than one coordinator thread (the epoch loop at checkpoints,
        a supervisor respawn draining a job mid-recovery), and two
        interleaved writers could tear lines or rotate the file out from
        under each other's handle — `--follow` readers and the offline
        summarizer both assume whole lines."""
        with self._ev_lock:
            buf, self._buf = self._buf, []
            if self.path is None or not buf:
                return                   # unattached: the ring is the record
            try:
                if self._f is None:
                    self._f = open(self.path, "a")
                for rec in buf:
                    self._f.write(json.dumps(rec) + "\n")
                self._f.flush()
                if os.path.getsize(self.path) > _MAX_FILE_BYTES:
                    from .trace import rotate_tail
                    self._f.close()
                    rotate_tail(self.path)
                    self._f = open(self.path, "a")
            except OSError:
                self.path = None         # profiling must never fail the job

    # ---- surfaces --------------------------------------------------------
    def rows(self) -> List[Tuple]:
        """rw_epoch_profile rows: (job, seq, events, shards, pack_ms,
        h2d_ms, promote_h2d_ms, dispatch_ms, exchange_ms,
        device_sync_ms, demote_d2h_ms, commit_ms, wall_ms). promote_h2d
        / demote_d2h are the state tier's surgery phases
        (device/tiering.py) — zero when tiering is off."""
        out = []
        for r in self.ring:
            ph = decode_epoch(r)
            out.append((self.job, r["seq"], r["events"],
                        r.get("shards", 1))
                       + tuple(ph.get(p, 0.0) for p in PHASES)
                       + (r["wall_ms"],))
        return out

    def summary(self, top: int = 5) -> Dict[str, Any]:
        """Compact report for bench detail blocks / risectl."""
        slow = sorted(self.ring, key=lambda r: -r["wall_ms"])[:top]
        with self._ev_lock:              # background compiles may land now
            compile_info = list(self.compile_info)
        return {
            "epochs": self.epochs,
            "phase_s": {k: round(v, 4) for k, v in self.totals.items()},
            "compile_events": [
                {k: (round(v, 3) if k == "s" else v)
                 for k, v in rec.items() if k not in ("ev", "job")}
                for rec in compile_info],
            "compile_s": round(sum(r["s"] for r in compile_info), 3),
            "top_epochs": [
                {"seq": r["seq"], "wall_ms": round(r["wall_ms"], 3),
                 "ph_ms": {k: round(v, 3) for k, v in r["ph_ms"].items()}}
                for r in slow],
        }


# what code that may have no job's profiler at hand opens its spans on
NULL_PROFILER = JobProfiler("", enabled=False)


def decode_epoch(rec: Dict[str, Any]) -> Dict[str, float]:
    """Phase map of one epoch record. Every reader of epoch records
    (rw_epoch_profile, risectl profile, the unified trace export)
    normalizes through here, so a format change is one branch on the
    record's `schema` — not a field-presence heuristic copied into each
    reader. One schema has been written so far."""
    return dict(rec.get("ph_ms", {}))


# ---------------------------------------------------------------------------
# live tail (risectl profile --follow)
# ---------------------------------------------------------------------------


def tail_jsonl(path: str, poll_s: float = 0.25, stop=None,
               from_start: bool = False):
    """Yield records appended to a JSONL file as they land — rotation-
    aware: `rotate_tail` replaces the file (new inode, smaller size), so
    the tail re-opens and resumes from the replacement's start instead
    of wedging on a stale handle or a position past EOF. The replacement
    IS the old file's second half, which this tail already yielded — so
    after a rotation, already-seen lines (tracked by a bounded hash ring
    of recent yields) are skipped until the first unseen line, and only
    genuinely new records flow. Partial lines (a writer mid-append) stay
    buffered until their newline arrives. `stop` is an optional
    threading.Event; the generator also exits if the file never appears
    within one poll after `stop` is set."""
    import io
    from collections import deque
    f = None
    ino = None
    buf = b""
    # hashes of the most recent yielded lines: rotate_tail keeps the
    # newest ~512 KiB (a few thousand records) — the ring must cover it
    recent: deque = deque(maxlen=16384)
    recent_set: set = set()
    skipping = False       # replaying a rotation's already-seen prefix
    try:
        while True:
            if f is None:
                try:
                    f = open(path, "rb")
                    st = os.fstat(f.fileno())
                    ino = st.st_ino
                    if not from_start:
                        f.seek(0, io.SEEK_END)
                    elif recent:
                        skipping = True     # rotation replay: dedupe
                    from_start = True       # after a rotation: read all
                    buf = b""
                except OSError:
                    if stop is not None and stop.wait(poll_s):
                        return
                    elif stop is None:
                        time.sleep(poll_s)
                    continue
            chunk = f.read()
            if chunk:
                buf += chunk
                while b"\n" in buf:
                    line, buf = buf.split(b"\n", 1)
                    h = hash(line)
                    if skipping:
                        if h in recent_set:
                            continue        # already yielded pre-rotation
                        skipping = False    # first unseen: all new now
                    if len(recent) == recent.maxlen:
                        recent_set.discard(recent[0])
                    recent.append(h)
                    recent_set.add(h)
                    try:
                        yield json.loads(line)
                    except ValueError:
                        pass                # torn line from a crash: skip
                continue
            # no new bytes: rotated (inode changed / file shrank)?
            try:
                st = os.stat(path)
                if st.st_ino != ino or st.st_size < f.tell():
                    f.close()
                    f = None
                    continue
            except OSError:
                f.close()
                f = None
                continue
            if stop is not None:
                if stop.wait(poll_s):
                    return
            else:
                time.sleep(poll_s)
    finally:
        if f is not None:
            f.close()


def format_record(rec: Dict[str, Any]) -> Optional[str]:
    """One-line human rendering of a profile record (`--follow`)."""
    if rec.get("ev") == "epoch":
        ph = rec.get("ph_ms", {})
        phs = " ".join(f"{k}={v:.1f}" for k, v in ph.items() if v)
        return (f"[{rec.get('job')}] epoch seq={rec.get('seq')} "
                f"events={rec.get('events')} "
                f"wall={rec.get('wall_ms', 0):.1f}ms " + phs)
    if rec.get("ev") == "compile":
        tags = "".join(
            f" {t}" for t in ("aot", "cache_hit") if rec.get(t))
        b = f" bucket={rec['bucket']}" if "bucket" in rec else ""
        if "persistent" in rec:
            tags += f" persistent={rec['persistent']}"
        return (f"[{rec.get('job')}] {rec.get('kind', 'compile')} "
                f"{rec.get('label')} {rec.get('s', 0):.2f}s{b}{tags}")
    return None


# ---------------------------------------------------------------------------
# offline reader (risectl profile)
# ---------------------------------------------------------------------------


def summarize_file(path: str, job: Optional[str] = None,
                   top: int = 10) -> Dict[str, Any]:
    """Per-job profile summary from an epoch_profile.jsonl: phase totals,
    compile/retrace events, and the top-N slowest epochs with their phase
    splits — the offline `risectl profile` answer."""
    jobs: Dict[str, Dict[str, Any]] = {}
    with open(path) as f:
        for line in f:
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            j = rec.get("job", "?")
            if job is not None and j != job:
                continue
            agg = jobs.setdefault(j, {"epochs": 0, "events": 0,
                                      "phase_ms": {p: 0.0 for p in PHASES},
                                      "compiles": [], "_all": []})
            if rec.get("ev") == "epoch":
                agg["epochs"] += 1
                agg["events"] += rec.get("events", 0)
                for k, v in decode_epoch(rec).items():
                    agg["phase_ms"][k] = agg["phase_ms"].get(k, 0.0) + v
                agg["_all"].append(rec)
            elif rec.get("ev") == "compile":
                agg["compiles"].append(
                    {k: rec[k] for k in ("label", "kind", "s", "bucket",
                                         "aot", "cache_hit", "persistent")
                     if k in rec})
    out = {}
    for j, agg in jobs.items():
        slow = sorted(agg.pop("_all"), key=lambda r: -r["wall_ms"])[:top]
        agg["phase_ms"] = {k: round(v, 3) for k, v in agg["phase_ms"].items()}
        agg["compile_s"] = round(sum(c["s"] or 0 for c in agg["compiles"]), 3)
        agg["slowest_epochs"] = [
            {"seq": r["seq"], "events": r.get("events"),
             "wall_ms": round(r["wall_ms"], 3),
             "ph_ms": {k: round(v, 3)
                       for k, v in decode_epoch(r).items()}}
            for r in slow]
        out[j] = agg
    return out
