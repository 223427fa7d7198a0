"""Ahead-of-time compile service: the owner of every fused-path compile.

Before this module, jit trace/compile happened INLINE on the epoch hot
loop: the first barrier after CREATE (and after every capacity growth)
blocked on tens-of-seconds XLA compiles — the r05 q5/q7/q8 bench spent
421.7s of warmup that way, and PR 5's profiler could only name it, not
remove it. This service inverts the lifecycle: compiles become a managed,
observable, pre-fetchable resource instead of a side effect of dispatch.

Three pillars:

* **Shape bucketing** — node capacities are pow2-bucketed (capacity.py),
  so every trace-shaping value is a ladder rung; the service keys its
  executable cache on (node structural signature, mutable-capacity salt,
  epoch cadence, input avals) — exactly the jit signature — and a growth
  resize that lands on an already-compiled rung dispatches with ZERO
  retrace.

* **Background AOT** — `jax.jit(step).lower(avals).compile()` runs on a
  small daemon worker pool, a program's nodes in parallel. A step whose
  executable is still pending WAITS for it (`_await`): there is one way
  to run a node step, the compiled one, on every backend. Input avals
  for shapes that have never been dispatched (CREATE-time pre-warm,
  predicted growth buckets) come from an abstract `jax.eval_shape` walk
  over a cloned node graph.

* **Plan-shape-hash pre-warm** — a compile manifest next to the
  persistent XLA cache records which key digests (and which plan-shape
  hashes) were compiled by ANY process; a re-created or restarted job
  whose signatures appear there is served from the disk cache and its
  compile events are labeled `cache_hit`. Within one process the
  executable cache itself is shared, so DROP + re-CREATE (or a second
  identically-shaped job) performs zero fresh compiles.

Observability: a compile leaves ONE record, its `rw:compile` span
(`utils/profile.py`; on the requesting job's profiler, or a span of no job
where the requester has none; nothing where profiling is off). The span
says whose compile it was (`node`, `label`, `kind`, `bucket`, `aot`) and
carries two words that mean different things: `cache_hit` — the compile
MANIFEST knew the signature's digest (some process compiled it once) — and
`persistent` — what jax did this time (`hit`: read from the persistent
cache, `retrieval_s`; `miss`: asked and built, `backend_compile_s`; `off`:
no cache), taken from jax's own events on the compiling thread
(`profile.take_compiled`). `cache_hit` with `persistent == "miss"` is
`lost`: an executable the machine was thought to have. The job profiler's
labeled compile record (`compile_info`, `epoch_profile.jsonl`, `risectl
profile`) is written where that span closes; `summary()` counts `built` /
`loaded` / `lost` beside the manifest's `cache_hits`, and `risectl
compile-status <job>` reports pending/ready/cached per signature with
both words. `DeviceConfig.aot_compile=False` restores inline compiles
(each a `rw:compile.inline` span under its `rw:step`).
"""
from __future__ import annotations

import copy
import ctypes
import hashlib
import json
import logging
import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

__all__ = ["CompileService", "get_service", "shutdown", "read_manifest",
           "offline_report"]

_WORKERS = max(1, min(4, (os.cpu_count() or 2) - 1))
# longest a dispatcher waits on ONE pending compile before it raises
# with the node label (the slowest TPU node step compiles in ~5 minutes;
# a full queue ahead of it is a few dozen of those over the workers)
AWAIT_LIMIT_S = 3600.0
MANIFEST_FILE = "compile_manifest.json"
_log = logging.getLogger(__name__)

# A TPU compile of one node step allocates 4-5 GB of host scratch, and
# glibc keeps what the compiler frees in its per-thread arenas: offline
# compiles of q5's 15 programs, 4 at a time, left the process at 13.1 GB
# of which `malloc_trim` gave 8.2 GB back to the OS while every executable
# was still held (PR 22, CHANGES.md). Untrimmed, q5 + q7 + q8 outgrew the
# one-chip host's 40 GiB. The trim walks every arena (seconds at 10 GB),
# so only a compile that itself ran for seconds is followed by one.
TRIM_AFTER_S = 5.0
try:
    _MALLOC_TRIM = ctypes.CDLL(None).malloc_trim
except (OSError, AttributeError):        # not glibc: nothing to trim
    _MALLOC_TRIM = None


def _trim_heap(compile_s: float) -> None:
    if _MALLOC_TRIM is not None and compile_s >= TRIM_AFTER_S:
        _MALLOC_TRIM(0)


def _code_bytes(compiled) -> Dict[str, int]:
    """`{"code_bytes": n}` where the executable says how large its
    generated code is (about what its persistent-cache entry holds)."""
    try:
        n = compiled.memory_analysis().generated_code_size_in_bytes
    except Exception:                    # a backend without the analysis
        return {}
    return {"code_bytes": int(n)} if n else {}


def _data_shards(mesh) -> int:
    from ..parallel.mesh import data_shards
    return data_shards(mesh)


def _stable_digest(obj: Any) -> str:
    """Deterministic short digest of a repr-stable structure (node sigs
    are tuples of strings/ints/frozen dataclasses — repr is canonical)."""
    return hashlib.sha1(repr(obj).encode()).hexdigest()[:16]


def _avals_of(tree) -> Tuple:
    """(treedef, ((shape, dtype), ...)) fingerprint of a pytree of arrays
    OR ShapeDtypeStructs — the part of the jit signature the static salt
    can't see. Identical for an abstract eval_shape walk and the live
    arrays it predicts, so pre-warmed entries are dispatch hits."""
    from jax.tree_util import tree_flatten
    leaves, treedef = tree_flatten(tree)
    return treedef, tuple((tuple(l.shape), str(l.dtype)) for l in leaves)


def _sds_of(tree, mesh=None):
    """ShapeDtypeStruct mirror of a pytree of concrete arrays (what the
    background thread lowers against — never the live buffers). For a
    mesh-sharded signature the leaves' NamedShardings ride along — a
    plain SDS would lower a single-device layout the mesh-placed epoch
    arrays could never feed."""
    import jax
    if mesh is None:
        return jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), tree)
    from jax.sharding import NamedSharding

    def sds(l):
        sh = getattr(l, "sharding", None)
        return jax.ShapeDtypeStruct(
            l.shape, l.dtype,
            sharding=sh if isinstance(sh, NamedSharding) else None)

    return jax.tree_util.tree_map(sds, tree)


def clone_nodes(nodes) -> List[Any]:
    """Shallow-copy a node list so capacity presets for bucket pre-warm
    never touch the live program (a mutated live node would silently
    shift `_mut_sig` under the dispatcher's feet)."""
    out = []
    for n in nodes:
        c = copy.copy(n)
        if hasattr(c, "ms_caps"):
            c.ms_caps = list(c.ms_caps)
        out.append(c)
    return out


def abstract_program_avals(nodes, epoch_events: int, mesh=None):
    """Per-node (state, ins, extra) ShapeDtypeStruct trees from an
    abstract `jax.eval_shape` walk — the same dataflow FusedProgram.epoch
    runs, with zero FLOPs and zero HBM. Lets the service lower shapes
    that have never executed (CREATE-time cold start, predicted growth
    buckets). With a mesh, the walk mirrors the SHARDED dataflow: states
    carry the leading shard axis, exchanged inputs take the routed
    [n_shards * exch]-row shape, and every sharded leaf carries its
    NamedSharding so the lowered executables match live dispatch.

    Returns the per-node (state, ins, extra) aval trees. The in-program
    exchange stages are NOT lowered here — they are small programs that
    jit inline on first dispatch (`shard_exec._exchange_jit`) and land in
    the persistent XLA cache like any other trace; only the per-node
    epoch steps are compile-service-managed."""
    import jax
    import jax.numpy as jnp
    from .fused import MVKeyedNode
    if mesh is not None:
        return _abstract_sharded_avals(nodes, epoch_events, mesh)
    states = [jax.eval_shape(n.init_state) for n in nodes]
    outs: List[Any] = []
    auxes: List[Any] = []
    per_node = []
    for i, node in enumerate(nodes):
        ins = tuple(outs[j] for j in node.inputs)
        if node.takes_event_lo:
            extra = jax.ShapeDtypeStruct((), jnp.int64)
        elif node.takes_feed:
            # host-ingest feed: fixed pow2 capacity = the epoch cadence,
            # so the staged buffers of EVERY epoch (whatever row count a
            # poll window admitted) hit this one pre-lowered signature
            extra = node.feed_sds(epoch_events)
        elif isinstance(node, MVKeyedNode):
            extra = auxes[node.inputs[0]]
        else:
            extra = None
        st, out, _stats, aux = jax.eval_shape(
            lambda s, i_, e, _n=node: _n.apply(s, list(i_), e, epoch_events),
            states[i], ins, extra)
        per_node.append((states[i], ins, extra))
        outs.append(out)
        auxes.append(aux)
    return per_node


def _abstract_sharded_avals(nodes, epoch_events: int, mesh):
    """The sharded mirror of `abstract_program_avals`: lift each node's
    local state to [n_shards, ...], route exchange inputs through the
    shape-faithful abstract exchange, and walk the per-shard steps."""
    import jax
    import jax.numpy as jnp
    from .fused import MVKeyedNode
    from ..parallel.mesh import data_shards
    from .shard_exec import exchange_apply, sds_sharded, sharded_apply
    n = data_shards(mesh)

    def lift_sds(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct((n,) + tuple(s.shape), s.dtype),
            tree)

    states = [sds_sharded(lift_sds(jax.eval_shape(node.init_state)), mesh)
              for node in nodes]
    outs: List[Any] = []
    auxes: List[Any] = []
    per_node = []
    for i, node in enumerate(nodes):
        ins = [outs[j] for j in node.inputs]
        if node.exch is not None:
            for xi, ex in enumerate(node.shard_spec().exchanges):
                routed = jax.eval_shape(
                    lambda d, _x=xi: exchange_apply(mesh, node, _x, d,
                                                    abstract=True)[0],
                    ins[ex.input])
                ins[ex.input] = sds_sharded(routed, mesh)
        ins = tuple(ins)
        if node.takes_event_lo:
            extra = jax.ShapeDtypeStruct((), jnp.int64)
        elif node.takes_feed:
            # per-shard feed blocks: the stager's host-side bucketing
            # cuts ceil-div event blocks, so each shard's buffer is the
            # same `feed_capacity` the live device_put ships
            from .ingest import feed_capacity
            extra = sds_sharded(
                lift_sds(node.feed_sds(feed_capacity(epoch_events, n))),
                mesh)
        elif isinstance(node, MVKeyedNode):
            extra = auxes[node.inputs[0]]
        else:
            extra = None
        st, out, _stats, aux = jax.eval_shape(
            lambda s, i_, e, _n=node: sharded_apply(
                mesh, _n, epoch_events, s, tuple(i_), e, abstract=True),
            states[i], ins, extra)
        per_node.append((states[i], ins, extra))
        outs.append(sds_sharded(out, mesh))
        auxes.append(sds_sharded(aux, mesh))
    return per_node


class CompileEntry:
    """One (signature, capacity bucket, avals) executable and its
    lifecycle: pending -> ready | failed. `jobs` maps job name -> True
    when this job's request triggered the compile (fresh) / False when
    the entry was already ready or in flight (cached/shared)."""

    __slots__ = ("key", "digest", "label", "status", "compiled", "seconds",
                 "bucket", "kind", "cache_hit", "persistent", "error",
                 "jobs", "sds", "node", "epoch_events", "salt", "profiler",
                 "mesh")

    def __init__(self, key, digest, label, node, epoch_events, salt, sds,
                 kind, profiler, mesh=None):
        self.key = key
        self.digest = digest
        self.label = label
        self.node = node
        self.epoch_events = epoch_events
        self.salt = salt
        self.sds = sds                  # (state, ins, extra) SDS trees
        self.status = "pending"
        self.compiled = None
        self.seconds = 0.0
        self.bucket = salt              # the capacity bucket(s) of the trace
        self.kind = kind                # "compile" | "retrace"
        self.cache_hit = False          # the manifest knew the digest
        self.persistent: Optional[str] = None  # jax: hit | miss | off
        self.error: Optional[str] = None
        self.jobs: Dict[str, bool] = {}
        self.profiler = profiler
        self.mesh = mesh                # device mesh of a sharded trace

    def state_for(self, job: str) -> str:
        if self.status != "ready":
            return self.status
        return "ready" if self.jobs.get(job) else "cached"


class CompileService:
    """Process-global compile owner for the fused device path. One
    instance serves every Database in the process — that sharing IS the
    zero-compile warm start for DROP + re-CREATE and identically-shaped
    jobs (entries key on structural signatures, never job names)."""

    def __init__(self, workers: int = _WORKERS):
        self._entries: Dict[Tuple, CompileEntry] = {}
        self._lock = threading.Lock()
        self._queue: deque = deque()
        self._cv = threading.Condition(self._lock)
        self._workers: List[threading.Thread] = []
        self._n_workers = max(1, workers)
        self._stop = False
        self._inflight = 0
        # test/diagnostic hook: when set, workers block here before
        # compiling (lets tests pin the pending window open)
        self.hold: Optional[threading.Event] = None
        # counters (bench warmup decomposition / compile-status)
        self.compiles_done = 0
        self.compiles_failed = 0
        self.cache_hits = 0             # by the manifest's word
        # by jax's: programs it built, read from the persistent cache,
        # and built although the manifest knew them
        self.built = self.loaded = self.lost = 0
        self.compiled_steps = 0
        # steps served by the inline-jit fallback of a FAILED entry
        self.inline_steps = 0
        # seconds the dispatcher spent waiting on pending compiles: the
        # sum of its `rw:compile_wait` spans
        self.await_s = 0.0
        self._manifest: Dict[str, Any] = {}
        self._manifest_loaded = False
        self._manifest_dirty = False
        # data directories that get a copy of the compile manifest on
        # every save: `risectl compile-status --offline` reads it from a
        # DEAD data dir, no live process or XLA cache dir needed
        self._mirror_dirs: set = set()

    # ---- worker pool ----------------------------------------------------
    def _ensure_workers(self) -> None:
        # under _lock
        self._stop = False
        while len(self._workers) < self._n_workers:
            t = threading.Thread(target=self._worker_loop,
                                 name=f"rw-aot-{len(self._workers)}",
                                 daemon=True)
            self._workers.append(t)
            t.start()

    def _worker_loop(self) -> None:
        while True:
            with self._cv:
                while not self._queue and not self._stop:
                    self._cv.wait(1.0)
                if self._stop:
                    return
                _ent, task = self._queue.popleft()
                self._inflight += 1
            try:
                task()
            except Exception:            # a compile failure must never
                pass                     # take the worker (or the job) down
            finally:
                with self._cv:
                    self._inflight -= 1
                    self._cv.notify_all()

    def _submit(self, task) -> None:
        with self._cv:
            self._ensure_workers()
            self._queue.append((None, task))
            self._cv.notify_all()

    def wait_idle(self, timeout: Optional[float] = None) -> bool:
        """Block until every queued/in-flight compile finished (tests,
        `risectl compile-status --wait`, session teardown)."""
        from ..utils.profile import span
        deadline = None if timeout is None else time.monotonic() + timeout
        with span("rw:compile_drain"):
            with self._cv:
                while self._queue or self._inflight:
                    left = None if deadline is None \
                        else deadline - time.monotonic()
                    if left is not None and left <= 0:
                        return False
                    self._cv.wait(0.1 if left is None else min(0.1, left))
            self._save_manifest()
        return True

    def shutdown(self, join: bool = True, timeout: float = 30.0) -> None:
        """Stop the pool (joining in-flight compiles) — the pytest
        sessionfinish guard against leaked-thread flakes. The service
        stays usable: the next request re-spawns workers."""
        if join:
            self.wait_idle(timeout)
        with self._cv:
            self._stop = True
            # an entry whose queued compile is dropped here would stay
            # pending for good: forget it (the next request for its
            # signature queues a fresh compile) and release any
            # dispatcher waiting on it to the inline-jit fallback
            for ent, _task in self._queue:
                if ent is not None and ent.status == "pending":
                    ent.status = "failed"
                    ent.error = "dropped: service shut down"
                    self._entries.pop(ent.key, None)
            self._queue.clear()
            workers, self._workers = self._workers, []
            self._cv.notify_all()
        for t in workers:
            t.join(timeout)
        self._save_manifest()

    # ---- keys / manifest ------------------------------------------------
    @staticmethod
    def _key(node, epoch_events: int, state, ins, extra, mesh=None) -> Tuple:
        from .shard_exec import mesh_fingerprint
        return (type(node).__name__, node._sig(), node._mut_sig(),
                epoch_events, mesh_fingerprint(mesh),
                _avals_of((state, ins, extra)))

    @staticmethod
    def _digest(node, epoch_events: int, salt, meshfp, avals) -> str:
        # the mesh fingerprint keys sharded executables apart from
        # single-chip ones (and 4-chip from 8-chip): "(plan hash, mesh
        # shape)" at the per-signature grain. meshfp=None (single-chip)
        # keeps the pre-mesh tuple shape so persistent manifest digests
        # from older releases stay valid across the upgrade
        if meshfp is None:
            return _stable_digest((type(node).__name__, node._sig(), salt,
                                   epoch_events, avals[1]))
        return _stable_digest((type(node).__name__, node._sig(), salt,
                               epoch_events, meshfp, avals[1]))

    def _manifest_path(self) -> Optional[str]:
        from . import compile_cache_dir
        d = compile_cache_dir()
        return os.path.join(d, MANIFEST_FILE) if d else None

    def _load_manifest(self) -> None:
        if self._manifest_loaded:
            return
        self._manifest_loaded = True
        path = self._manifest_path()
        if path and os.path.exists(path):
            try:
                with open(path) as f:
                    self._manifest = json.load(f)
            except (OSError, ValueError):
                self._manifest = {}
        self._manifest.setdefault("keys", {})
        self._manifest.setdefault("plans", {})

    def attach_dir(self, data_dir: str) -> None:
        """Mirror the compile manifest into this data directory (written
        at every save), so a dead data dir still answers `risectl
        compile-status --offline` — the PR 6 residual."""
        with self._lock:
            self._load_manifest()
            self._mirror_dirs.add(data_dir)
            self._manifest_dirty = True
        # flush immediately: a warm-started job (zero fresh compiles, so
        # no per-compile flush ever fires) must still leave its dir's
        # mirror readable if the process dies before idle/shutdown
        self._save_manifest()

    def _save_manifest(self) -> None:
        # the writes happen under the lock too: a save that serialized an
        # older manifest must not land AFTER a newer one (worker threads
        # flush per compile) — the files are tiny, the hold is cheap
        with self._lock:
            if not self._manifest_dirty:
                return
            blob = json.dumps(self._manifest, indent=1, sort_keys=True)
            paths = [p for p in [self._manifest_path()] if p] + \
                [os.path.join(d, MANIFEST_FILE) for d in self._mirror_dirs]
            self._manifest_dirty = False
            for path in paths:
                try:
                    os.makedirs(os.path.dirname(path), exist_ok=True)
                    tmp = path + ".tmp"
                    with open(tmp, "w") as f:
                        f.write(blob)
                    os.replace(tmp, path)
                except OSError:
                    pass                 # manifests are advisory only

    def note_plan(self, plan_hash: str, job: str, labels: List[str]) -> None:
        with self._lock:
            self._load_manifest()
            rec = self._manifest["plans"].setdefault(
                plan_hash, {"nodes": sorted(set(labels))})
            rec["last_job"] = job
            self._manifest_dirty = True

    def plan_known(self, plan_hash: str) -> bool:
        """True when some earlier process compiled this plan shape (its
        executables should be persistent-cache hits)."""
        with self._lock:
            self._load_manifest()
            return plan_hash in self._manifest["plans"]

    # ---- the dispatch seam ---------------------------------------------
    def node_step(self, node, epoch_events: int, state, ins, extra, *,
                  label: str, job: Optional[str] = None, profiler=None,
                  kind: Optional[str] = None, mesh=None):
        """The fused epoch step, compile-service-managed:

        ready  -> call the AOT executable (zero trace, zero compile)
        pending-> WAIT for the background compile (`_await`), then as
                  ready. The workers compile a program's nodes in
                  parallel; the dispatcher takes them as they land
        failed -> permanent inline-jit fallback for this signature,
                  counted in `inline_steps` (the failure itself was
                  logged and counted when it happened)

        `mesh` selects the shard_map'd step (device/shard_exec.py): the
        executable is lowered through `sharded_jit_step`, keyed apart by
        the mesh fingerprint, and served the same way.
        """
        key = self._key(node, epoch_events, state, ins, extra, mesh)
        with self._lock:
            ent = self._entries.get(key)
            if ent is None:
                ent = self._request_locked(
                    key, node, epoch_events,
                    _sds_of((state, ins, extra), mesh),
                    label=label, job=job, profiler=profiler,
                    kind=kind or "compile", mesh=mesh)
            elif job is not None and job not in ent.jobs:
                ent.jobs[job] = False    # shared/cached for this job
        if ent.status == "pending":
            self._await(ent, profiler)
        if ent.status == "ready":
            try:
                out = ent.compiled(state, ins, extra)
                with self._lock:
                    self.compiled_steps += 1
                return out
            except Exception as e:
                # TRANSIENT device-path faults (injected fused.* points,
                # XLA runtime errors) belong to the job's in-place
                # recovery — re-raise; demoting the entry would leave a
                # healthy executable permanently on the inline-jit
                # fallback after the job heals (fault-tolerance v3).
                from .fused import _is_device_fault
                if _is_device_fault(e):
                    raise
                # aval/placement drift: permanent fallback
                ent.status = "failed"
                ent.error = f"dispatch: {type(e).__name__}: {e}"
        with self._lock:
            self.inline_steps += 1
        if mesh is not None:
            from .shard_exec import sharded_node_step
            return sharded_node_step(mesh, node, epoch_events, state,
                                     ins, extra)
        from .fused import _node_step
        return _node_step(node, epoch_events, state, ins, extra)

    def _await(self, ent: CompileEntry, profiler=None) -> None:
        """Block the dispatcher until `ent`'s background compile lands
        (a `rw:compile_wait` span of the waiting job's profiler; timed
        all the same when the job has none).
        Running the step some other way meanwhile is a loss: measured on
        a v5e (PR 22, CHANGES.md), an op-by-op first epoch of the 4-node
        bid group-by took 280 s against a 130 s critical-path compile —
        every eager primitive is its own compile, racing the AOT workers
        for the same cores. Raises after `AWAIT_LIMIT_S` rather than
        wait for good on a compile that never lands."""
        from ..utils.profile import Span
        attrs = {"node": ent.node.stable_name(), "cache_hit": ent.cache_hit}
        wait = profiler.span("rw:compile_wait", **attrs) \
            if profiler is not None and profiler.enabled \
            else Span("rw:compile_wait", attrs, record=False)
        t0 = time.perf_counter()
        with wait:
            with self._cv:
                while ent.status == "pending":
                    left = AWAIT_LIMIT_S - (time.perf_counter() - t0)
                    if left <= 0:
                        raise TimeoutError(
                            f"AOT compile of {ent.label} still pending "
                            f"after {AWAIT_LIMIT_S:.0f}s")
                    self._cv.wait(min(0.5, left))
        with self._lock:
            self.await_s += wait.seconds

    def _request_locked(self, key, node, epoch_events, sds, *, label, job,
                        profiler, kind, mesh=None) -> CompileEntry:
        self._load_manifest()
        digest = self._digest(node, epoch_events, key[2], key[4], key[5])
        ent = CompileEntry(key, digest, label, node, epoch_events, key[2],
                           sds, kind, profiler, mesh=mesh)
        ent.cache_hit = digest in self._manifest["keys"]
        if job is not None:
            ent.jobs[job] = True         # this job pays for the compile
        self._entries[key] = ent
        self._queue.append((ent, self._compile_task(ent)))
        self._ensure_workers()
        self._cv.notify_all()
        return ent

    def _compile_task(self, ent: CompileEntry):
        def task():
            if self.hold is not None:
                ent_hold = self.hold
                ent_hold.wait()
            from ..utils.profile import span, take_compiled
            from .fused import _jit_step
            state_s, ins_s, extra_s = ent.sds
            t0 = time.perf_counter()
            # the compile's one record: a span of the requester's
            # profiler (nothing where that is off), of no job where the
            # requester has none
            open_span = ent.profiler.span if ent.profiler is not None \
                else span
            try:
                with open_span(
                        "rw:compile", node=ent.node.stable_name(),
                        label=ent.label, kind=ent.kind, aot=True,
                        bucket=repr(ent.bucket), cache_hit=ent.cache_hit,
                        ok=False) as sp:
                    take_compiled()      # what this thread compiled before
                    if ent.mesh is not None:
                        from .shard_exec import sharded_jit_step
                        step = sharded_jit_step(ent.mesh, ent.node)
                    else:
                        step = _jit_step(ent.node)
                    lowered = step.lower(
                        state_s, ins_s, extra_s, node=ent.node,
                        epoch_events=ent.epoch_events, salt=ent.salt)
                    ent.compiled = lowered.compile()
                    did = take_compiled(ent.cache_hit)
                    ent.persistent = did.get("persistent")
                    sp.set(ok=True, **did, **_code_bytes(ent.compiled))
            except Exception as e:
                ent.seconds = time.perf_counter() - t0
                ent.error = f"{type(e).__name__}: {e}"
                with self._lock:
                    self.compiles_failed += 1
                # once per signature (a failed entry never re-queues):
                # the inline-jit fallback it now takes must not be quiet.
                # Counted and said BEFORE the status is published: a
                # dispatcher that sees "failed" goes on at once, and
                # whoever watches it must find the failure on record
                _log.warning("AOT compile of %s failed after %.1fs, "
                             "falling back to inline jit: %s",
                             ent.label, ent.seconds, ent.error)
                ent.status = "failed"
                _trim_heap(ent.seconds)
                return
            ent.seconds = time.perf_counter() - t0
            ent.status = "ready"
            del lowered
            with self._lock:
                # counters are asserted on exactly (zero-compile warm
                # starts); worker threads race, so never bare +=
                self.compiles_done += 1
                if ent.cache_hit:
                    self.cache_hits += 1
                if ent.persistent == "hit":
                    self.loaded += 1
                elif ent.persistent is not None:
                    self.built += 1
                self.lost += bool(did.get("lost"))
                rec = {"label": ent.label, "s": round(ent.seconds, 3)}
                if ent.mesh is not None:
                    from ..parallel.mesh import data_shards
                    rec["shards"] = data_shards(ent.mesh)
                self._manifest["keys"][ent.digest] = rec
                self._manifest_dirty = True
            # flush now (cheap, small json): a process that dies mid-run
            # still leaves its mirror manifests readable offline
            self._save_manifest()
            _trim_heap(ent.seconds)
        return task

    # ---- pre-warm -------------------------------------------------------
    def prewarm_program(self, nodes, epoch_events: int, *, job: str,
                        profiler=None, plan_hash: Optional[str] = None,
                        caps: Optional[Dict[int, Dict[str, int]]] = None,
                        labels: Optional[List[str]] = None,
                        mesh=None) -> None:
        """Schedule background AOT for a program's node shapes — the
        current ones (caps=None) or a predicted growth bucket (caps =
        {node index: {slot: capacity}}). With a mesh, the walk and the
        lowering both take the sharded path, so warm starts of
        mesh-sharded jobs are zero-compile too. The abstract aval walk
        AND the lowering both run on the worker pool; the caller returns
        immediately (CREATE-time kickoff must not block the session)."""
        cloned = clone_nodes(nodes)
        for i, c in (caps or {}).items():
            if 0 <= int(i) < len(cloned):
                cloned[int(i)].preset_caps(dict(c))
        if plan_hash is not None:
            self.note_plan(plan_hash, job,
                           labels if labels is not None else [])

        def task():
            if self.hold is not None:
                self.hold.wait()
            try:
                per_node = abstract_program_avals(cloned, epoch_events,
                                                  mesh)
            except Exception:
                return                   # unwalkable plan: dispatch-time
            with self._lock:             # scheduling still covers it
                for i, (node, (st, ins, extra)) in enumerate(
                        zip(cloned, per_node)):
                    key = self._key(node, epoch_events, st, ins, extra,
                                    mesh)
                    ent = self._entries.get(key)
                    if ent is None:
                        lab = labels[i] if labels and i < len(labels) else \
                            f"{i}:{type(node).__name__}"
                        self._request_locked(
                            key, node, epoch_events, (st, ins, extra),
                            label=lab, job=job, profiler=profiler,
                            kind="compile", mesh=mesh)
                    elif job not in ent.jobs:
                        ent.jobs[job] = False
        self._submit(task)

    # ---- surfaces -------------------------------------------------------
    def status(self, job: Optional[str] = None) -> List[Dict[str, Any]]:
        """Per-signature rows for `risectl compile-status`: pending /
        ready (this job compiled it) / cached (compiled before this job
        asked) / failed."""
        with self._lock:
            ents = [e for e in self._entries.values()
                    if job is None or job in e.jobs]
        return [{"label": e.label, "bucket": repr(e.bucket),
                 "state": e.status if job is None else e.state_for(job),
                 "kind": e.kind, "s": round(e.seconds, 3),
                 "shards": (_data_shards(e.mesh)
                            if e.mesh is not None else 1),
                 "cache_hit": e.cache_hit, "persistent": e.persistent,
                 "error": e.error}
                for e in sorted(ents, key=lambda e: e.label)]

    def summary(self) -> Dict[str, Any]:
        with self._lock:
            pending = sum(1 for e in self._entries.values()
                          if e.status == "pending")
        return {"compiles": self.compiles_done,
                "failed": self.compiles_failed,
                "cache_hits": self.cache_hits,
                "built": self.built, "loaded": self.loaded,
                "lost": self.lost,
                "pending": pending,
                "inline_steps": self.inline_steps,
                "compiled_steps": self.compiled_steps,
                "await_s": round(self.await_s, 1)}


# ---------------------------------------------------------------------------
# offline manifest reading (risectl compile-status --offline)
# ---------------------------------------------------------------------------


def read_manifest(data_dir: Optional[str] = None) -> Optional[Dict]:
    """Load a compile manifest WITHOUT a live process: prefer the data
    dir's mirror copy (written by `attach_dir` at every save), fall back
    to the persistent-cache dir in force (`device.compile_cache_dir`).
    Returns None when neither exists — the dir predates manifest
    mirroring or never ran with AOT on."""
    from . import compile_cache_dir
    candidates = []
    if data_dir:
        candidates.append(os.path.join(data_dir, MANIFEST_FILE))
    cache = compile_cache_dir()
    if cache:
        candidates.append(os.path.join(cache, MANIFEST_FILE))
    for path in candidates:
        if not os.path.exists(path):
            continue
        try:
            with open(path) as f:
                m = json.load(f)
        except (OSError, ValueError):
            continue
        m.setdefault("keys", {})
        m.setdefault("plans", {})
        m["_path"] = path
        return m
    return None


def offline_report(manifest: Dict) -> Dict[str, Any]:
    """Dead-data-dir compile-status: which plan shapes and signatures
    were ever compiled (their executables are persistent-cache hits for
    the next process), and what the compiles cost."""
    keys = manifest.get("keys", {})
    return {
        "manifest": manifest.get("_path"),
        "plans": manifest.get("plans", {}),
        "signatures": len(keys),
        "sharded_signatures": sum(1 for v in keys.values()
                                  if v.get("shards", 1) > 1),
        "compile_seconds": round(sum(v.get("s") or 0
                                     for v in keys.values()), 3),
        "keys": keys,
    }


_SERVICE: Optional[CompileService] = None
_SERVICE_LOCK = threading.Lock()


def get_service() -> CompileService:
    global _SERVICE
    with _SERVICE_LOCK:
        if _SERVICE is None:
            _SERVICE = CompileService()
        return _SERVICE


def shutdown(join: bool = True, timeout: float = 30.0) -> None:
    """Join/stop the process-global service's workers (pytest session
    guard; safe when the service was never used)."""
    with _SERVICE_LOCK:
        svc = _SERVICE
    if svc is not None:
        svc.shutdown(join=join, timeout=timeout)
