"""Fused fragment runtime: a whole SQL dataflow as ONE jitted epoch program.

This is the TPU-first answer to the reference's actor pipeline (SURVEY §3.2
source -> dispatch -> agg/join -> materialize): instead of per-operator
host round trips (r02's bottleneck: one host sync per operator), the fuse
planner (`device/fuse_planner.py`) lowers an eligible MV fragment into a
stage graph whose per-epoch step — on-device datagen, expression eval, hop
expansion, agg (`agg_step.epoch_core_full`), join (`join_step.join_core`)
with on-device pair netting, MV apply — is one traced XLA program over
device-resident state. The host barrier loop only *dispatches* (async);
it synchronizes exclusively at checkpoints and SELECTs, the barrier-
boundary parity license the reference's shared buffer exploits
(`materialize.rs:166`, `hash_agg.rs:411`).

Exactness: no hashing anywhere. Group/join/row-identity keys are LOSSLESS
bit-packings chosen by static interval analysis (offset/stride/bits per
column) and *verified on device* — any value outside its proven range
raises at the next sync instead of corrupting state. Row identity for
retractable change streams packs (stream key, payload) so an update never
nets against its own retraction (the r02 pair-resurrection lesson).

Recovery: fused fragments run over DETERMINISTIC replayable sources
(nexmark/datagen), so recovery = regenerate: restore the committed event
counter and re-run the epoch loop device-side (the Kafka-offset-rewind
analog of `source_executor.rs` split state — state reconstruction at HBM
speed instead of trickling LSM rows over the host link). The MV contents
are additionally persisted to the MV state table at every checkpoint, so
non-device readers (system catalogs, risectl) see committed data.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..core import dtypes as T
from ..core.dtypes import DataType, TypeKind
from ..utils.failpoint import FailpointError, declare, failpoint
from ..utils.profile import NULL_PROFILER
from .mv_mirror import MirrorImage, MVColumns, mirror_batch

# Fused device-path failure seams (fault-tolerance v3): each hook sits at
# the point where a real device fault would surface — the async epoch
# dispatch, the blocking device_get of a sync, the growth-replay
# re-dispatch, and the checkpoint commit. An armed point (or a real
# dispatch/runtime exception) routes the job through IN-PLACE recovery
# (`FusedJob._recover_in_place`), never a DDL-replay restart.
declare("fused.dispatch",
        "fail a fused epoch dispatch (device-path fault mid-epoch)")
declare("fused.device_sync",
        "fail the blocking device sync of a fused checkpoint/SELECT")
declare("fused.growth_replay",
        "fail a fused capacity growth replay mid-re-dispatch")
declare("fused.checkpoint_commit",
        "fail a fused job-state checkpoint commit")


EPOCH_LOG_SPILL = "epoch_log_spill.jsonl"


class _EpochLog:
    """Bounded coordinator-side epoch event log — the retained crash
    window an in-place recovery re-dispatches. Entries are tiny
    ((event_lo, events) pairs), but a degraded-mode job under stretched
    cadence with a long checkpoint window must not trade queue growth
    for event-log growth: past `RW_FUSED_EPOCH_LOG_BYTES` the oldest
    half spills to a jsonl file beside epoch_profile.jsonl and reloads
    transparently when `entries()` (recovery) asks for the full window.
    `clear()` (the checkpoint trim) drops both tiers. Without a data
    directory there is nowhere durable to spill, so the log stays
    in-memory (the pre-bound behavior)."""

    ENTRY_BYTES = 16               # accounting unit per (lo, events) pair

    def __init__(self, cap_bytes: int, dir_of):
        self.cap_entries = max(8, int(cap_bytes) // self.ENTRY_BYTES)
        self._dir_of = dir_of      # () -> Optional[data_dir]; late-bound
        self._mem: List[Tuple[int, int]] = []
        self.spilled = 0           # entries currently in the spill file
        self.spill_total = 0       # lifetime spilled entries

    def _spill_path(self) -> Optional[str]:
        import os
        d = self._dir_of()
        return os.path.join(d, EPOCH_LOG_SPILL) if d else None

    def append(self, lo: int, events: int) -> None:
        self._mem.append((int(lo), int(events)))
        if len(self._mem) <= self.cap_entries:
            return
        path = self._spill_path()
        if path is None:
            return                 # no data dir: in-memory fallback
        import json
        cut = len(self._mem) // 2
        # first spill of a window truncates: a stale file from a crashed
        # predecessor must never splice into this window
        with open(path, "w" if self.spilled == 0 else "a") as f:
            for pair in self._mem[:cut]:
                f.write(json.dumps(pair) + "\n")
        self.spilled += cut
        self.spill_total += cut
        del self._mem[:cut]

    def entries(self) -> List[Tuple[int, int]]:
        """The full retained window, oldest first (spill tier, then
        memory) — what `_recover_in_place` replays."""
        import json
        import os
        out: List[Tuple[int, int]] = []
        if self.spilled:
            path = self._spill_path()
            if path and os.path.exists(path):
                with open(path) as f:
                    for ln in f:
                        ln = ln.strip()
                        if ln:
                            lo, ev = json.loads(ln)
                            out.append((int(lo), int(ev)))
        out.extend(self._mem)
        return out

    def clear(self) -> None:
        import os
        self._mem.clear()
        path = self._spill_path()
        if path is not None:
            try:
                os.remove(path)
            except OSError:
                pass
        self.spilled = 0

    def __len__(self) -> int:
        return self.spilled + len(self._mem)


def _is_device_fault(e: BaseException) -> bool:
    """Failures the in-place recovery path may absorb: injected fused.*
    failpoints and the runtime errors jax surfaces on a genuine
    device-path fault. Correctness errors (packed-key bounds violations
    raise a plain RuntimeError) and control-flow exceptions always
    propagate — replaying them would loop on a real bug. So does running
    out of device memory (RESOURCE_EXHAUSTED arrives as the same
    XlaRuntimeError): a replay of the same shapes exhausts it again."""
    if isinstance(e, FailpointError):
        return True
    if isinstance(e, (KeyboardInterrupt, SystemExit)):
        return False
    if "RESOURCE_EXHAUSTED" in str(e):
        return False
    return type(e).__name__ in ("XlaRuntimeError", "JaxRuntimeError",
                                "InternalError", "UnavailableError",
                                "DataLoss")

# ---------------------------------------------------------------------------
# Delta: the traced value flowing between stages (NOT a jit boundary type)
# ---------------------------------------------------------------------------


@dataclass
class Delta:
    """A batch of signed rows on device. `cols` is positional (aligned with
    the producing operator's schema); `pk`/`pk2` carry row identity for
    joins and pair MVs. Pure arrays — a jit-boundary pytree; the static
    metadata (decoders, dtypes, ranges) lives on the NODES that produce
    and consume the delta (fuse_planner.Meta), not the runtime value. All
    columns are non-null by construction (fuse eligibility rejects
    nullable flows)."""
    cols: List[Any]
    sign: Any
    mask: Any
    pk: Optional[Any] = None
    pk2: Optional[Any] = None

    @property
    def size(self) -> int:
        return int(self.mask.shape[0])


def _delta_flatten(d: Delta):
    return (tuple(d.cols), d.sign, d.mask, d.pk, d.pk2), None


def _delta_unflatten(_aux, children):
    cols, sign, mask, pk, pk2 = children
    return Delta(list(cols), sign, mask, pk, pk2)


def _register_delta():
    import jax
    jax.tree_util.register_pytree_node(Delta, _delta_flatten,
                                       _delta_unflatten)


_register_delta()


NUM = ("num",)


@dataclass(frozen=True)
class ShardExchange:
    """One input of a node that must be vnode-routed before the node's
    per-shard local step can run: rows of `inputs[input]` whose key
    (packed from `key_idx` with the node's PackPlan) hashes to another
    shard's vnode block travel over the in-program ICI exchange
    (`shard_exec.exchange_delta`). `carry_pk` keeps the delta's row
    identity through the shuffle (joins net pairs by it). `ref_idx`
    names the input columns the node actually reads (None = all): only
    those are buffered and shipped over ICI — the routed delta zero-
    fills the rest, which the node by declaration never touches."""
    input: int
    key_idx: Tuple[int, ...]
    carry_pk: bool = False
    ref_idx: Optional[Tuple[int, ...]] = None
    # the routing key column already IS the packed key (pre-combined agg
    # deltas carry it as column 0) — the exchange must not re-pack it
    packed: bool = False


@dataclass(frozen=True)
class ShardSpec:
    """A node's declarative mesh-sharding contract (the ROADMAP-
    anticipated fuse-planner refactor): `state` says how the node's
    device state partitions over the shard axis — "local" (stateless, or
    per-shard private) vs "vnode" (keyed by the vnode of its group/join/
    pk key, the contiguous-block layout of `parallel/mesh.py`) — and
    `exchanges` names the inputs that need the cross-vnode shuffle
    first. The planner and `shard_exec` consume this; nothing here is
    specific to any one node class."""
    state: str = "local"
    exchanges: Tuple[ShardExchange, ...] = ()


def _nrows(mask):
    """Device row count of a boolean mask (profiler stats: one scalar in
    the existing stats vector, no extra sync)."""
    import jax.numpy as jnp
    return jnp.sum(mask, dtype=jnp.int64)


# ---------------------------------------------------------------------------
# lossless key packing
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class PackField:
    offset: int
    stride: int
    bits: int


@dataclass(frozen=True)
class PackPlan:
    """key = sum_i ((col_i - offset_i) // stride_i) << shift_i, proven
    lossless by interval analysis and re-verified on device (`check`)."""
    fields: Tuple[PackField, ...]

    @staticmethod
    def plan(ranges: Sequence[Optional[Tuple[int, int, int]]]
             ) -> Optional["PackPlan"]:
        fields = []
        total = 0
        for r in ranges:
            if r is None:
                return None
            lo, hi, stride = r
            stride = max(1, stride)
            span = max(0, hi - lo) // stride
            bits = max(1, int(span).bit_length())
            fields.append(PackField(lo, stride, bits))
            total += bits
        if total > 62:        # keys must stay clear of EMPTY_KEY (2^63-1)
            return None
        return PackPlan(tuple(fields))

    def pack(self, cols: Sequence[Any]):
        import jax.numpy as jnp
        key = jnp.zeros_like(cols[0])
        shift = 0
        for c, f in zip(cols, self.fields):
            v = (c - f.offset) // f.stride if f.stride > 1 else c - f.offset
            key = key + (v.astype(jnp.int64) << shift)
            shift += f.bits
        return key

    def unpack(self, key) -> List[Any]:
        import jax.numpy as jnp
        out = []
        shift = 0
        for f in self.fields:
            v = (key >> shift) & ((1 << f.bits) - 1)
            out.append((v * f.stride + f.offset).astype(jnp.int64))
            shift += f.bits
        return out

    def check(self, cols: Sequence[Any], mask):
        """int64 violation flag (0 = all rows within their proven ranges)."""
        import jax.numpy as jnp
        bad = jnp.zeros((), jnp.int64)
        for c, f in zip(cols, self.fields):
            r = c - f.offset
            v = r // f.stride if f.stride > 1 else r
            row_bad = (r < 0) | (v >= (1 << f.bits))
            if f.stride > 1:
                row_bad |= (r % f.stride) != 0
            bad = bad | jnp.where(mask & row_bad, 1, 0).max()
        return bad


# ---------------------------------------------------------------------------
# stage nodes
# ---------------------------------------------------------------------------


def _expr_sig(e) -> Tuple:
    """Structural signature of a device expression — captures everything
    that shapes its trace (class, return type, column indices, literals,
    function names, constant shifts). Unknown expr classes fall back to
    identity, which disables sharing but can never alias two different
    computations."""
    kids = tuple(_expr_sig(c)
                 for c in (e.children() if hasattr(e, "children") else []))
    base: Tuple = (type(e).__name__, str(getattr(e, "return_type", None)))
    from ..expr.expression import FunctionCall, InputRef, Literal
    if isinstance(e, InputRef):
        base += (e.index,)
    elif isinstance(e, Literal):
        base += (repr(e.value),)
    elif isinstance(e, FunctionCall):
        base += (e.name,)
    elif hasattr(e, "delta"):          # fuse_planner._TsShift
        base += (e.delta,)
    else:
        base += (id(e),)
    return base + (kids,)


_NAME_MAX = 48


def _name_token(x) -> str:
    """Letters, digits and `_` of `x` (names end up in XLA module names
    and span attributes)."""
    import re
    return re.sub(r"[^A-Za-z0-9]+", "_", str(x)).strip("_")


def _expr_tag(sig: Tuple) -> str:
    """Short tag of an `_expr_sig`: `c<i>` for a column, else the
    function's name (or the class's) over its arguments' tags."""
    cls, _rtype, what, kids = sig[0], sig[1], sig[2], sig[-1]
    if cls == "InputRef":
        return f"c{what}"
    head = _name_token(what if cls == "FunctionCall" else cls).lower()
    return "_".join([head] + [_expr_tag(k) for k in kids])


def _calls_tag(calls) -> str:
    """`count_sum1_max2` of an agg's (kind, argument column) calls."""
    return "_".join(f"{k}{'' if j is None else j}" for k, j in calls)


def _named(fn, name: str):
    """`fn` under `name`: jax names the XLA module of a jitted function
    `jit_<its __name__>`."""
    fn.__name__ = fn.__qualname__ = name
    return fn


class Node:
    """Static stage config. `inputs` are node indices; state is one pytree
    slot per node (None when stateless).
    `takes_event_lo`: this node's `extra` is the epoch's first event id.

    Nodes hash/compare STRUCTURALLY (`_sig`): two nodes with the same
    signature trace identically given the same input avals, so the jit
    cache (which keys on (node, avals)) is shared across programs and
    Database instances in one process — q5's duplicated hop+agg chain
    compiles once, and a warmup Database pre-compiles the measured one.
    Anything shape-affecting that the avals can't see (JoinNode.m) must
    be part of the signature.

    Each node's `apply` is jitted SEPARATELY (`_node_step`): compiles are
    small, localized (capacity growth re-traces one node, not the whole
    program), and dedupe across programs via the persistent compilation
    cache — the r03 fix for whole-program epoch compiles taking minutes
    per query shape on a TPU. The host loop
    between nodes only routes device-array handles; dispatch stays async.
    """
    inputs: Tuple[int, ...] = ()
    # this node's `extra` is a HOST-STAGED device feed (device/ingest.py
    # (count, pk, *cols) buffers) delivered per epoch by the owning
    # FusedJob's HostIngest stager — the host-ingest twin of
    # takes_event_lo below
    takes_feed: bool = False
    stat_names: Tuple[str, ...] = ()
    # subset of stat_names that accumulate across epochs by SUM (row-flow
    # counters); everything else accumulates by MAX (capacity needs,
    # violation flags). The job's stats accumulator honors this split.
    # Under mesh sharding the same split picks the in-program collective:
    # psum for sums, pmax for high-water needs (shard_exec.sharded_apply).
    stat_sums: Tuple[str, ...] = ()
    takes_event_lo: bool = False
    # mesh sharding (device/shard_exec.py): per-(source,dest) send-bucket
    # capacity of the in-program all_to_all exchange. None = this node
    # runs un-exchanged (stateless nodes, or a single-chip program);
    # stateful Agg/Join nodes get it via enable_exchange when the owning
    # FusedProgram has a mesh. A real capacity slot ("exch"): observed
    # per-epoch bucket high-water rides the stats vector and the normal
    # grow+replay path resizes it.
    exch: Optional[int] = None
    # HBM bytes per exch slot (budget math): one buffered row across the
    # n_shards destination buckets; the planner sets the exact per-row
    # width when it arms the exchange (enable_exchange caller).
    exch_bytes: int = 256
    # key-skew telemetry (device/skew_stats.py): keyed nodes compute a
    # vnode-occupancy histogram + per-epoch top-K heavy hitters inside
    # their traced step when armed (enable_skew). False everywhere else.
    skew: bool = False
    # hot-key replication policy (device/shard_exec.py; JoinNode only):
    # keys (40-bit-truncated, matching the heavy-hitter evidence) whose
    # rows the exchange special-cases — input `hot_rep_side`'s rows
    # BROADCAST to every shard, the other input's rows salt round-robin
    # by row identity. Routing-only: the node's local step is unchanged.
    # Adopted exclusively through FusedJob's checkpoint-time policy
    # switch (rebuild-replay), so placement stays consistent with the
    # state the shards already hold. Part of the EXCHANGE trace salt,
    # never of `_mut_sig` (node-step executables must survive a policy
    # change untouched — that is the zero-compile contract).
    hot_keys: Tuple[int, ...] = ()
    hot_rep_side: int = 1
    # armed by the planner when DeviceConfig.hot_key_rep is on AND the
    # node's exchanges carry pks (joins): makes the node a candidate for
    # the checkpoint-time hot-key policy (no-op until hot_keys lands)
    hotrep: bool = False
    # state tiering (device/tiering.py): keyed nodes carry a
    # last-touched-epoch column beside their key table and report
    # residency/coldness scalars on the stats vector when armed
    # (enable_tiering). False everywhere else.
    tier: bool = False
    # flow telemetry (device/skew_stats.py): keyed nodes compute a
    # 16-bucket per-epoch routed-row (traffic) histogram inside their
    # traced step when armed (enable_flow); the slots accumulate by SUM
    # across epochs and shards. False everywhere else.
    flow: bool = False
    # per-shard occupancy under a mesh (device/shard_exec.py): the
    # high-water stats whose sum is this node's live entries, and whether
    # `enable_shard_live` has armed one "live<s>" stat a shard (never on
    # single-chip programs or un-keyed nodes)
    live_stats: Tuple[str, ...] = ()
    shard_live: bool = False

    def init_state(self):
        return None

    def enable_skew(self) -> None:
        """Arm skew telemetry for this node (planner-called, once,
        BEFORE the program is built: the skew scalars extend both the
        stat layout and the traced step, so arming is part of the
        node's structural signature). No-op for un-keyed nodes."""

    def enable_flow(self) -> None:
        """Arm traffic-per-vnode telemetry for this node
        (planner-called, once, BEFORE the program is built — the
        traffic scalars extend the stat layout and the traced step, so
        arming is part of the structural signature, exactly like
        enable_skew). No-op for un-keyed nodes."""

    def enable_tiering(self) -> None:
        """Arm recency tracking for this node (planner-called, once,
        BEFORE the program is built — the touch column wraps the state
        pytree and two scalars extend the stat layout, so arming is
        part of the structural signature, exactly like enable_skew).
        No-op for un-keyed nodes."""

    # ---- mesh sharding (declarative; device/shard_exec.py executes) ----
    def shard_spec(self) -> ShardSpec:
        """How this node shards over the device mesh. Default: stateless/
        local — runs per shard over whatever rows arrive, no exchange.
        Stateful keyed nodes override with state="vnode" (+ exchanges)."""
        return ShardSpec()

    def enable_shard_live(self, n_shards: int) -> None:
        """Arm per-shard occupancy for a vnode-sharded node
        (planner-called, once, under a mesh, BEFORE enable_exchange — the
        host-spliced exchange stats stay last — and before the program is
        built): "live<s>" = shard s's live entries, the sum of
        `live_stats`, read out of the all_gather that already reduces
        those stats (`shard_exec.sharded_apply`). High-waters, like the
        stats they come from. No-op for a node without `live_stats`."""
        if self.live_stats and not self.shard_live:
            assert not set(self.live_stats) & set(self.stat_sums)
            self.shard_live = True
            self.stat_names = tuple(self.stat_names) + tuple(
                f"live{s}" for s in range(n_shards))

    def enable_exchange(self, cap: int, n_shards: int,
                        slot_bytes: Optional[int] = None) -> None:
        """Arm the in-program exchange stage for this node's flagged
        inputs (planner-called, once, before the program is built): the
        [n_shards, exch] send-bucket capacity becomes a real capacity
        slot whose per-epoch high-water ("exch", appended to stat_names)
        rides the stats vector through the normal grow+replay path.
        `slot_bytes` is the planner's estimate of one buffered row's HBM
        width across all destination buckets (budget math). The live
        rows each of the `n_shards` shards receives from each stage ride
        along as SUM stats "xin<stage>_<shard>" (what the exchange's
        one-hot already counts, psum'd per destination)."""
        assert self.shard_spec().exchanges, "node has no exchange stage"
        if self.exch is None:
            xin = tuple(f"xin{xi}_{s}" for xi in range(len(
                self.shard_spec().exchanges)) for s in range(n_shards))
            self.stat_names = tuple(self.stat_names) + ("exch",) + xin
            self.stat_sums = tuple(self.stat_sums) + xin
        self.exch = int(cap)
        if slot_bytes is not None:
            self.exch_bytes = int(slot_bytes)

    # ---- capacity lifecycle (FusedJob.sync / recover drive these) -------
    # Capacity is declarative: a node names its capacity slots and reports
    # per-slot observed needs from its pulled stats; the JOB owns the
    # growth policy (predictive sizing, HBM budget, replay accounting) and
    # hands back bucketed targets. preset_caps (before init_state) serves
    # high-water presizing; cap_resize pads live state mid-run.
    def cap_current(self) -> Dict[str, int]:
        """slot name -> current capacity (empty = stateless node)."""
        return {}

    def cap_needs(self, stats: Dict[str, int]) -> Dict[str, int]:
        """slot name -> observed slots needed, from this node's stats.
        This is the TOTAL need — the overflow check and the correctness
        floor; the predictor extrapolates the split views below."""
        return {}

    def cap_needs_cum(self, stats: Dict[str, int]) -> Dict[str, int]:
        """Cumulative component of the need (entries that accumulate with
        total events — group counts, join-side rows): the part the
        predictor may extrapolate linearly over the event horizon."""
        return self.cap_needs(stats)

    def cap_needs_epoch(self, stats: Dict[str, int]) -> Dict[str, int]:
        """Per-epoch-bounded component (join pair buffers, agg `touched`
        compaction bounds): resets every epoch, so horizon extrapolation
        over-shoots it — the predictor gives it flat headroom instead
        (capacity.project_epoch)."""
        return {}

    def cap_bytes(self) -> Dict[str, int]:
        """slot name -> approximate HBM bytes per slot (budget math)."""
        return {}

    def preset_caps(self, caps: Dict[str, int]) -> None:
        """Adopt capacities BEFORE init_state (high-water presizing)."""

    def cap_resize(self, state, caps: Dict[str, int]):
        """Pad live state to the given (pow2, >= current) capacities and
        adopt them; slots absent from `caps` keep their size."""
        return state

    def apply(self, state, ins: List[Optional[Delta]], extra,
              epoch_events: int):
        """-> (state', out Delta | None, [stat scalars], aux pytree | None).
        `extra` is this node's cross-node input (SourceNode: event_lo;
        MVKeyedNode: its agg's change set) — part of the jit signature."""
        raise NotImplementedError

    def span_attrs(self) -> Dict[str, Any]:
        """What this node's `rw:step` span says beside `node` and `i`:
        a decision the node took from its own plan when it was traced."""
        return {}

    def _sig(self) -> Tuple:
        return (id(self),)            # default: no structural sharing

    def _name_parts(self) -> Tuple:
        """What tells this node apart in its plan, from its STRUCTURE
        only (never its program position, an MV's name or `hash()`): two
        programs sharing a signature share the name, and with it the
        XLA module and its compile."""
        return ()

    def stable_name(self) -> str:
        """`<type>_<parts>` in letters, digits and `_`, the same in
        every process: the node's name in XLA module names
        (`jit_step_<name>`), span attributes and compile labels. A name
        over `_NAME_MAX` characters is cut and closed with a digest of
        the whole, so that two long names stay two names."""
        name = self.__dict__.get("_stable_name")
        if name is None:        # structure only: fixed at construction
            kind = type(self).__name__.removesuffix("Node").lower()
            name = "_".join([kind] + [t for t in map(
                _name_token, self._name_parts()) if t])
            if len(name) > _NAME_MAX:
                from .compile_service import _stable_digest
                name = f"{name[:_NAME_MAX - 7]}_{_stable_digest(name)[:6]}"
            self._stable_name = name
        return name

    def _mut_sig(self) -> Tuple:
        """Trace-shaping attributes that `grow` MUTATES (JoinNode.m).
        jit static arguments must be immutable — jax's dispatch fast path
        keys on object identity, so a mutated node would silently reuse
        the executable traced with the OLD value (the r03 q5 growth bug).
        These ride as a separate static argument that changes value."""
        return ()

    def __hash__(self):
        return hash((type(self).__name__,) + self._sig())

    def __eq__(self, other):
        return type(self) is type(other) and self._sig() == other._sig()


def _jit_step(node: "Node"):
    """The jitted per-node step, one per node NAME (lazy): the XLA
    module is `jit_step_<node.stable_name()>`, so a device trace says
    which node a module is. Nodes of one name share the function (the
    node itself is a static argument). The compile service AOT-lowers
    through the SAME accessor so an inline jit call and a background
    `.lower().compile()` of one signature are the same trace (and the
    same persistent-cache entry)."""
    import jax
    name = node.stable_name()
    fn = _JIT_STEPS.get(name)
    if fn is None:
        def step(state, ins, extra, *, node, epoch_events, salt):
            return node.apply(state, ins, extra, epoch_events)
        fn = _JIT_STEPS[name] = jax.jit(
            _named(step, f"step_{name}"),
            static_argnames=("node", "epoch_events", "salt"))
    return fn


def _node_step(node: Node, epoch_events: int, state, ins, extra):
    return _jit_step(node)(state, ins, extra, node=node,
                           epoch_events=epoch_events, salt=node._mut_sig())


_JIT_STEPS: Dict[str, Any] = {}
_STACK_JIT = None
_FOLD_JIT = None


def _stack_stats(stats: Tuple):
    """Jitted stack of the per-epoch stat scalars (one dispatched
    program per epoch; the jit cache keys on the tuple length)."""
    import jax
    import jax.numpy as jnp
    global _STACK_JIT
    if _STACK_JIT is None:
        def stats_stack(xs):
            return jnp.stack(xs)
        _STACK_JIT = jax.jit(stats_stack)
    return _STACK_JIT(stats)


def _fold_stats(vec, acc, sum_mask):
    """Jitted accumulator combine: sum slots add, max slots high-water."""
    import jax
    import jax.numpy as jnp
    global _FOLD_JIT
    if _FOLD_JIT is None:
        def stats_fold(v, a, m):
            return jnp.where(m, a + v, jnp.maximum(a, v))
        _FOLD_JIT = jax.jit(stats_fold)
    return _FOLD_JIT(vec, acc, sum_mask)


from .capacity import bucket as _bucket  # noqa: E402  (pow2 sizing)
from .capacity import ladder as _ladder  # noqa: E402  (pre-warm rungs)


class SourceNode(Node):
    """On-device exact Nexmark/datagen events for this epoch's id range.

    The source makes its own table's rows: it enumerates the table's
    event ids at or after `event_lo` over `nexmark_gen.source_lanes`
    lanes (person 32,768, auction 65,536 and bid 1,048,576 of a
    1,048,576-event epoch) and masks only the lanes past the window's
    end (and past `max_events`): the live rows are a dense prefix, in
    event-id order, and `pk` is the event id. Everything downstream
    works over those lanes; its `rw:step` span says how many."""

    takes_event_lo = True
    stat_names = ("rows_out",)
    stat_sums = ("rows_out",)

    def __init__(self, table: str, gencfg, col_names: Sequence[str],
                 rowid_pos: Optional[int], max_events: Optional[int],
                 schema_dtypes: Sequence[DataType]):
        from .nexmark_gen import SURROGATE, column_bounds
        self.table = table
        self.gencfg = gencfg
        self.col_names = list(col_names)
        self.rowid_pos = rowid_pos
        self.max_events = max_events
        self.dtypes = list(schema_dtypes)
        self.decoders = []
        self.ranges: List[Optional[Tuple[int, int, int]]] = []
        for i, nm in enumerate(self.col_names):
            if i == rowid_pos:
                self.decoders.append(NUM)
                self.ranges.append((0, max_events or (1 << 40), 1))
                continue
            self.decoders.append(SURROGATE[table][nm])
            lo, hi = column_bounds(gencfg, table, nm, max_events)
            stride = gencfg.inter_event_gap_usecs \
                if SURROGATE[table][nm] == ("ts",) and nm == "date_time" else 1
            self.ranges.append((lo, hi, stride))

    def _sig(self):
        return (self.table, self.gencfg, tuple(self.col_names),
                self.rowid_pos, self.max_events)

    def _name_parts(self):
        return (self.table,)

    def apply(self, state, ins, extra, epoch_events):
        import jax.numpy as jnp
        from .nexmark_gen import gen_table, own_event_ids, source_lanes
        ids = own_event_ids(self.table, extra,
                            source_lanes(self.table, epoch_events))
        mask = ids < extra + epoch_events
        if self.max_events is not None:
            mask = mask & (ids < self.max_events)
        all_cols = gen_table(self.gencfg, self.table, ids)
        cols = [ids if i == self.rowid_pos else all_cols[nm]
                for i, nm in enumerate(self.col_names)]
        d = Delta(cols, jnp.ones(ids.shape, jnp.int32), mask, pk=ids)
        return state, d, [_nrows(mask)], None


class IngestNode(Node):
    """Host-fed twin of SourceNode (device/ingest.py): the epoch's rows
    arrive as a PRE-STAGED device buffer — (count, pk, *cols), packed
    and transferred by the HostIngest stager ahead of the dispatch —
    instead of being regenerated on device. The feed buffer is a fixed
    pow2 capacity (the epoch cadence) with the live row count masked in,
    so every epoch shares ONE aval signature with the compile service
    regardless of how many rows the poll window admitted. Carries the
    same static column metadata as SourceNode (dtypes, surrogate
    decoders, proven ranges) so downstream packing proofs are identical
    — a host-fed program is the device-datagen program with one leaf
    swapped."""

    takes_feed = True
    stat_names = ("rows_out",)
    stat_sums = ("rows_out",)

    def __init__(self, table: str, gencfg, col_names: Sequence[str],
                 rowid_pos: Optional[int], max_events: Optional[int],
                 schema_dtypes: Sequence[DataType]):
        from .nexmark_gen import SURROGATE, column_bounds
        self.table = table
        self.gencfg = gencfg
        self.col_names = list(col_names)
        self.rowid_pos = rowid_pos
        self.max_events = max_events
        self.dtypes = list(schema_dtypes)
        self.decoders = []
        self.ranges: List[Optional[Tuple[int, int, int]]] = []
        for i, nm in enumerate(self.col_names):
            if i == rowid_pos:
                self.decoders.append(NUM)
                self.ranges.append((0, max_events or (1 << 40), 1))
                continue
            self.decoders.append(SURROGATE[table][nm])
            lo, hi = column_bounds(gencfg, table, nm, max_events)
            stride = gencfg.inter_event_gap_usecs \
                if SURROGATE[table][nm] == ("ts",) and nm == "date_time" \
                else 1
            self.ranges.append((lo, hi, stride))
        # feed-column pruning (planner-armed via set_live BEFORE the
        # program is built): only these column positions ship over the
        # H2D seam; the rest are proven-dead downstream and zero-fill
        # in-trace. None = every column ships. The host-side twin of
        # the dead-code elimination the device generator gets from XLA.
        self.live: Optional[Tuple[int, ...]] = None

    def set_live(self, live: Sequence[int]) -> None:
        live = tuple(sorted(set(int(i) for i in live)))
        if len(live) < len(self.col_names):
            self.live = live

    def live_names(self) -> Optional[Tuple[str, ...]]:
        if self.live is None:
            return None
        return tuple(self.col_names[i] for i in self.live)

    def _sig(self):
        return ("ingest", self.table, self.gencfg, tuple(self.col_names),
                self.rowid_pos, self.max_events, self.live)

    def _name_parts(self):
        return (self.table,)

    def feed_sds(self, cap: int):
        """ShapeDtypeStruct mirror of one (per-shard) feed — what the
        compile service's abstract walks lower against."""
        import jax
        import jax.numpy as jnp
        ncols = len(self.live) if self.live is not None \
            else len(self.col_names)
        col = jax.ShapeDtypeStruct((cap,), jnp.int64)
        return ((jax.ShapeDtypeStruct((), jnp.int64),
                 col) + (col,) * ncols)

    def apply(self, state, ins, extra, epoch_events):
        import jax.numpy as jnp
        cnt, pk = extra[0], extra[1]
        shipped = list(extra[2:])
        n = pk.shape[0]
        if self.live is None:
            cols = shipped
        else:
            # dead columns never reach a downstream read (liveness is
            # proven by the planner walk) — zero-fill keeps the delta's
            # positional schema without paying their transfer
            zero = jnp.zeros((n,), jnp.int64)
            cols = [zero] * len(self.col_names)
            for k, ci in enumerate(self.live):
                cols[ci] = shipped[k]
        # the staged buffer is capacity-padded; only the first `cnt`
        # rows are this epoch's (slots past it hold stale bytes from the
        # reused staging buffer — masked, exactly like the device
        # generator's other-kind event slots)
        mask = jnp.arange(n, dtype=jnp.int64) < cnt
        d = Delta(cols, jnp.ones((n,), jnp.int32), mask, pk=pk)
        return state, d, [_nrows(mask)], None


class MapNode(Node):
    """Project: device-evaluable expressions over the input delta."""

    stat_names = ("rows_in", "rows_out")
    stat_sums = ("rows_in", "rows_out")

    def __init__(self, input: int, exprs: Sequence[Any]):
        self.inputs = (input,)
        self.exprs = list(exprs)

    def _sig(self):
        # "rio" versions the signature: the rows_in/rows_out stat
        # outputs extended the traced step, and a persisted compile
        # manifest keyed by the OLD digest must miss (not falsely
        # report the new trace as cached)
        return tuple(_expr_sig(e) for e in self.exprs) + ("rio",)

    def _name_parts(self):
        return tuple(_expr_tag(_expr_sig(e)) for e in self.exprs)

    def apply(self, state, ins, extra, epoch_events):
        d = ins[0]
        cols = [e.eval_device(d.cols)[0] for e in self.exprs]
        out = Delta(cols, d.sign, d.mask, pk=d.pk, pk2=d.pk2)
        n = _nrows(d.mask)
        return state, out, [n, n], None


class FilterNode(Node):
    # rows_in alongside rows_out: EXPLAIN ANALYZE derives per-node
    # selectivity/amplification without walking the producer
    stat_names = ("rows_in", "rows_out")
    stat_sums = ("rows_in", "rows_out")

    def __init__(self, input: int, pred: Any):
        self.inputs = (input,)
        self.pred = pred

    def _sig(self):
        return (_expr_sig(self.pred), "rio")   # see MapNode._sig

    def _name_parts(self):
        return (_expr_tag(_expr_sig(self.pred)),)

    def apply(self, state, ins, extra, epoch_events):
        d = ins[0]
        ok, valid = self.pred.eval_device(d.cols)
        out = Delta(d.cols, d.sign, d.mask & ok & valid, pk=d.pk, pk2=d.pk2)
        return state, out, [_nrows(d.mask), _nrows(out.mask)], None


class HopNode(Node):
    """Row -> size/hop windowed copies, appending window_start/window_end
    (`HopWindowExecutor` / TUMBLE when hop == size). Row identity extends
    with the window ordinal so each copy stays unique."""

    stat_names = ("rows_in", "rows_out")
    stat_sums = ("rows_in", "rows_out")

    def __init__(self, input: int, time_col: int, hop_usecs: int,
                 size_usecs: int):
        assert size_usecs % hop_usecs == 0
        self.inputs = (input,)
        self.time_col = time_col
        self.hop = hop_usecs
        self.size = size_usecs
        self.n = size_usecs // hop_usecs

    def _sig(self):
        return (self.time_col, self.hop, self.size, "rio")  # see MapNode

    def _name_parts(self):
        return (f"c{self.time_col}", f"h{self.hop}", f"s{self.size}")

    def span_attrs(self):
        return {"fanout": self.n}

    def apply(self, state, ins, extra, epoch_events):
        import jax
        import jax.numpy as jnp
        d = ins[0]
        n = self.n
        rep = lambda a: jnp.repeat(a, n)
        with jax.named_scope("hop.expand"):    # HLO metadata only
            ts = d.cols[self.time_col]
            first = (ts // self.hop) * self.hop
            k = jnp.tile(jnp.arange(n, dtype=jnp.int64), ts.shape[0])
            starts = rep(first) - k * self.hop
            cols = [rep(c) for c in d.cols] + [starts, starts + self.size]
            pk = rep(d.pk) * n + k if d.pk is not None else None
            out = Delta(cols, rep(d.sign), rep(d.mask), pk=pk)
        return state, out, [_nrows(d.mask), _nrows(out.mask)], None


class ChainNode(Node):
    """A maximal run of stateless single-consumer nodes (Source/Map/Filter/
    Hop) traced as ONE program. The payoff on the host side is fewer
    per-epoch dispatches; the payoff inside XLA is fusion + dead-code
    elimination — a source column no downstream expression reads is never
    materialized to HBM (the datagen of q4's 5 unused bid columns folds
    away entirely)."""

    def __init__(self, chain: List[Node], inputs: Tuple[int, ...]):
        self.chain = list(chain)
        self.inputs = tuple(inputs)
        self.takes_event_lo = bool(getattr(chain[0], "takes_event_lo",
                                           False))
        # source-rooted chains have no input delta to count; consuming
        # chains report rows_in so amplification is derivable per node
        self.stat_names = ("rows_in", "rows_out") if inputs \
            else ("rows_out",)
        self.stat_sums = self.stat_names

    def _sig(self):
        return tuple((type(n).__name__,) + n._sig() for n in self.chain)

    def _name_parts(self):
        # the members' kinds (a source with its table), then a digest of
        # their full names: two chains of the same kinds stay two names
        from .compile_service import _stable_digest
        names = [n.stable_name() for n in self.chain]
        kinds = [nm if isinstance(n, (SourceNode, IngestNode))
                 else nm.split("_", 1)[0]
                 for n, nm in zip(self.chain, names)]
        return tuple(kinds) + (_stable_digest(names)[:6],)

    def apply(self, state, ins, extra, epoch_events):
        out = None
        for i, n in enumerate(self.chain):
            node_ins = ins if i == 0 else [out]
            _, out, _, _ = n.apply(None, node_ins,
                                   extra if i == 0 else None, epoch_events)
        stats = [_nrows(out.mask)]
        if self.inputs:
            stats = [_nrows(ins[0].mask)] + stats
        return None, out, stats, None


_CHAINABLE = ()          # filled below once all node classes exist


def _chain_nodes(nodes: List[Node]) -> Tuple[List[Node], Dict[int, int]]:
    """Greedily absorb stateless single-consumer runs into ChainNodes.
    Returns (new_nodes, remap old->new index). Only the LAST member of a
    chain may have external consumers (enforced by the single-consumer
    rule), so remapping its index covers every reference."""
    consumers: Dict[int, List[int]] = {i: [] for i in range(len(nodes))}
    for i, n in enumerate(nodes):
        for j in n.inputs:
            consumers[j].append(i)
    absorbed = set()
    new_nodes: List[Node] = []
    remap: Dict[int, int] = {}
    for i, n in enumerate(nodes):
        if i in absorbed:
            continue
        if isinstance(n, _CHAINABLE):
            chain = [n]
            cur = i
            while len(consumers[cur]) == 1:
                nxt = consumers[cur][0]
                if isinstance(nodes[nxt], _CHAINABLE) \
                        and nodes[nxt].inputs == (cur,):
                    chain.append(nodes[nxt])
                    absorbed.add(nxt)
                    cur = nxt
                else:
                    break
            ins = tuple(remap[j] for j in n.inputs)
            if len(chain) > 1:
                new = ChainNode(chain, ins)
            else:
                n.inputs = ins
                new = n
            new_nodes.append(new)
            remap[cur] = len(new_nodes) - 1
            remap[i] = len(new_nodes) - 1
        else:
            if not isinstance(n, ChainNode):   # idempotent re-wrap guard
                n.inputs = tuple(remap[j] for j in n.inputs)
            new_nodes.append(n)
            remap[i] = len(new_nodes) - 1
    return new_nodes, remap


class PrecombineNode(Node):
    """Local pre-combine stage ahead of an AggNode (the "Global Hash
    Tables Strike Back!" per-partition pre-aggregation): the epoch's raw
    input rows collapse to one partial-aggregate row per unique group
    key BEFORE the agg's state merge — and, under mesh sharding, BEFORE
    the ICI exchange, which is the skew defense: a hot key costs one
    combined row per (source shard, epoch) on the wire and in the owning
    shard's merge, instead of every raw row. Output delta layout:
    cols = [packed group key, raw-row count, *per-column partial deltas
    (spec.kinds layout)], live rows compacted to a prefix. Stateless;
    runs shard-local (never exchanged itself). The planner inserts it
    only for exactly-combinable aggs: no retractable min/max multisets,
    no float SUM columns (float addition is order-sensitive — combining
    locally would break bit-identity with the raw path)."""

    stat_names = ("rows_in", "rows_out", "packbad")
    stat_sums = ("rows_in", "rows_out")

    def __init__(self, input: int, group_idx: Sequence[int], calls,
                 pack: PackPlan, spec):
        self.inputs = (input,)
        self.group_idx = list(group_idx)
        self.calls = list(calls)
        self.pack = pack
        self.spec = spec

    def _sig(self):
        return ("pre", tuple(self.group_idx),
                tuple((c.kind, c.arg.index if c.arg is not None else None)
                      for c in self.calls),
                self.pack, self.spec)

    def _name_parts(self):
        return ("k" + "_".join(map(str, self.group_idx)),
                _calls_tag((c.kind, c.arg.index if c.arg is not None
                            else None) for c in self.calls))

    def apply(self, state, ins, extra, epoch_events):
        import jax.numpy as jnp
        from .agg_step import precombine_core
        d = ins[0]
        live = d.mask & (d.sign != 0)
        gcols = [d.cols[i] for i in self.group_idx]
        packbad = self.pack.check(gcols, live)
        keys = self.pack.pack(gcols)
        inputs = []
        for c in self.calls:
            if c.arg is None:
                z = jnp.zeros_like(keys)
                inputs.append((z, jnp.ones(z.shape, bool)))
            else:
                inputs.append((d.cols[c.arg.index],
                               jnp.ones(keys.shape, bool)))
        from .sorted_state import EMPTY_KEY
        ukeys, ucnt, udeltas = precombine_core(
            self.spec, keys, d.sign, d.mask, tuple(inputs))
        out_live = ukeys != EMPTY_KEY
        out = Delta([ukeys, ucnt] + list(udeltas),
                    jnp.where(out_live, 1, 0).astype(jnp.int32), out_live)
        return state, out, [_nrows(live), _nrows(out_live), packbad], None


class AggNode(Node):
    """epoch_core_full behind a packed group key; emits the change stream
    as a signed delta (old rows retract, new rows insert; unchanged groups
    suppressed). Change-set internals are exposed via ctx for a terminal
    keyed MV. With `combined` armed (enable_precombine), the input is a
    PrecombineNode's partial-aggregate delta instead of raw rows: on one
    chip that node's own output, taken as it is (one row a key, key-sorted,
    reduced once); behind an exchange (`exch` armed, under a mesh) the
    source shards' partials, which the step re-combines. The node reads
    which of the two off its own plan when it is traced (`recombine`)."""

    def __init__(self, input: int, group_idx: Sequence[int], calls,
                 pack: PackPlan, spec, capacity: int,
                 pk_pack: Optional[PackPlan]):
        self.inputs = (input,)
        self.group_idx = list(group_idx)
        self.calls = list(calls)
        self.pack = pack
        self.spec = spec
        self.capacity = capacity
        # per-minput multiset capacities (tracked on the node so presizing
        # can set them before init_state builds the arrays)
        self.ms_caps = [capacity] * len(spec.minputs)
        # row identity of emitted change rows = pack(group, outputs); None
        # when no join/pair-MV consumes this stream (pk then unused)
        self.pk_pack = pk_pack
        # False when only a terminal MVKeyedNode consumes this agg (via the
        # aux change set): the signed delta stream — unpack + concat +
        # compact over up-to-2*capacity rows — is then never built, and the
        # aux is pruned to the entries the MV apply reads (XLA DCEs the
        # rest). Set by FusedProgram's consumer analysis.
        self.emit_out = True
        # True after enable_precombine: the input delta is a
        # PrecombineNode's partial-aggregate layout ([key, count,
        # *deltas]) instead of raw rows
        self.combined = False
        self.stat_names = tuple(["needed", "touched"]
                                + [f"ms{i}" for i in range(len(spec.minputs))]
                                + ["packbad", "rows_in", "rows_out"])
        self.stat_sums = ("rows_in", "rows_out")
        self.live_stats = ("needed",)

    def enable_skew(self):
        from .skew_stats import SKEW_STAT_NAMES
        if not self.skew:
            self.skew = True
            self.stat_names = tuple(self.stat_names) + SKEW_STAT_NAMES

    def enable_flow(self):
        # traffic slots are row-flow counters: SUM across epochs, psum
        # across shards (exact — each input row lands in exactly one
        # bucket on exactly one shard after the exchange routes it)
        from .skew_stats import TRAFFIC_STAT_NAMES
        if not self.flow:
            self.flow = True
            self.stat_names = tuple(self.stat_names) + TRAFFIC_STAT_NAMES
            self.stat_sums = tuple(self.stat_sums) + TRAFFIC_STAT_NAMES

    def enable_tiering(self):
        # tres = live groups, tcold = live groups untouched >= TIER_TTL
        # epochs. MAX-accumulated (not in stat_sums) so the job sees the
        # window high-water; pmax across shards would double-count
        # nothing (per-shard tables are disjoint) but the coordinator
        # reads residency from the D2H pull, so max is the right fold.
        if not self.tier:
            self.tier = True
            self.stat_names = tuple(self.stat_names) + ("tres", "tcold")

    def enable_precombine(self) -> None:
        """Arm the pre-combined input mode (planner-called, once, BEFORE
        the program is built — the combined layout changes the traced
        step, so it is part of the structural signature). The planner
        guarantees the spec is exactly combinable (no multisets, no
        float SUM columns); assert the invariant here."""
        import numpy as np
        from .sorted_state import ReduceKind
        assert not self.spec.minputs, "pre-combine over multiset state"
        assert not any(k == ReduceKind.SUM
                       and np.issubdtype(np.dtype(dt), np.floating)
                       for k, dt in zip(self.spec.kinds, self.spec.dtypes)
                       ), "pre-combine over a float SUM column"
        self.combined = True

    @property
    def recombine(self) -> bool:
        """Of a `combined` node: does the step reduce its pre-combined
        delta a second time? True behind an exchange (one partial a
        source shard and key), False where the delta is one
        PrecombineNode's output. Read off the node's own plan."""
        return self.exch is not None

    def span_attrs(self):
        attrs = {"recombine": self.recombine} if self.combined else {}
        if self.spec.minputs:
            attrs["minputs"] = len(self.spec.minputs)
        return attrs

    def shard_spec(self):
        if self.combined:
            # the pre-combined delta carries its packed group key as
            # column 0 — route by it verbatim; every column (key, count,
            # partial deltas) is read by the merge, so all ship
            return ShardSpec("vnode",
                             (ShardExchange(0, (0,), packed=True),))
        # state partitions by the vnode of the packed group key; the one
        # input shuffles rows to their group's owning shard first. Only
        # the columns apply() reads (group key + agg args) ship over ICI
        refs = sorted(set(self.group_idx)
                      | {c.arg.index for c in self.calls
                         if c.arg is not None})
        return ShardSpec("vnode",
                         (ShardExchange(0, tuple(self.group_idx),
                                        ref_idx=tuple(refs)),))

    def init_state(self):
        from .agg_step import DeviceAggState
        from .minput import ms_make
        state = DeviceAggState(self.spec.make_state(self.capacity),
                               tuple(ms_make(c) for c in self.ms_caps))
        if self.tier:
            import jax.numpy as jnp
            from .tiering import TieredState
            return TieredState(state,
                               jnp.zeros((self.capacity,), jnp.int64),
                               jnp.zeros((), jnp.int64))
        return state

    def cap_current(self):
        caps = {"main": self.capacity}
        for i, c in enumerate(self.ms_caps):
            caps[f"ms{i}"] = c
        if self.exch is not None:
            caps["exch"] = self.exch
        return caps

    def cap_needs(self, stats):
        # `touched` guards the change-set compaction bound (2 * capacity):
        # an epoch touching more unique groups than capacity must grow and
        # replay even if enough groups died for the merge itself to fit
        needs = {"main": max(stats["needed"], stats.get("touched", 0))}
        for i in range(len(self.ms_caps)):
            needs[f"ms{i}"] = stats[f"ms{i}"]
        if self.exch is not None:
            needs["exch"] = stats.get("exch", 0)
        return needs

    def cap_needs_cum(self, stats):
        # live groups + multiset entries accumulate across epochs
        needs = {"main": stats["needed"]}
        for i in range(len(self.ms_caps)):
            needs[f"ms{i}"] = stats[f"ms{i}"]
        return needs

    def cap_needs_epoch(self, stats):
        # groups TOUCHED in one epoch bound the change-set compaction but
        # reset at every epoch — window queries touch (and retire) far
        # more groups per epoch than ever stay live. The exchange send
        # bucket re-fills from scratch every epoch too.
        needs = {"main": stats.get("touched", 0)}
        if self.exch is not None:
            needs["exch"] = stats.get("exch", 0)
        return needs

    def cap_bytes(self):
        from .minput import MS_SLOT_BYTES
        caps = {"main": 8 * (1 + len(self.spec.dtypes))}
        for i in range(len(self.ms_caps)):
            caps[f"ms{i}"] = MS_SLOT_BYTES
        if self.exch is not None:
            caps["exch"] = self.exch_bytes
        return caps

    def preset_caps(self, caps):
        self.capacity = max(self.capacity, caps.get("main", 0))
        for i in range(len(self.ms_caps)):
            self.ms_caps[i] = max(self.ms_caps[i], caps.get(f"ms{i}", 0))
        if self.exch is not None:
            self.exch = max(self.exch, caps.get("exch", 0))

    def cap_resize(self, state, caps):
        import jax.numpy as jnp
        from .agg_step import DeviceAggState
        from .minput import ms_grow
        from .sorted_state import grow_state
        tstate = None
        if self.tier:
            from .tiering import TieredState
            tstate = state
            state = tstate.inner
        if self.exch is not None and caps.get("exch", 0) > self.exch:
            self.exch = caps["exch"]   # jit-static: _mut_sig salts the trace
        main = state.main
        if caps.get("main", 0) > main.capacity:
            self.capacity = caps["main"]
            main = grow_state(main, self.capacity, self.spec.kinds)
        ms = list(state.minputs)
        for i in range(len(ms)):
            c = caps.get(f"ms{i}", 0)
            if c > ms[i].capacity:
                self.ms_caps[i] = c
                ms[i] = ms_grow(ms[i], c)
        out = DeviceAggState(main, tuple(ms))
        if tstate is None:
            return out
        # touch rows ride positionally with the key table: grow_state
        # tail-pads keys with EMPTY_KEY, so zero-padding the touch tail
        # keeps the alignment (EMPTY rows carry touch 0 by invariant)
        from .tiering import TieredState
        touch = tstate.touch
        pad = main.capacity - touch.shape[0]
        if pad > 0:
            touch = jnp.concatenate(
                [touch, jnp.zeros((pad,), jnp.int64)])
        return TieredState(out, touch, tstate.tick)

    def _call_outputs(self, ch, which: str):
        """Per-call (array, null) at the touched keys, old or new."""
        outs, nulls = [], []
        for ci, dc in enumerate(self.spec.calls):
            if dc.minput is not None:
                sub = ch[f"minput{dc.minput}"]
                v = sub[f"{which}_max"] if self.calls[ci].kind == "max" \
                    else sub[f"{which}_min"]
                outs.append(v)
                nulls.append(~sub[f"{which}_found"])
            else:
                outs.append(ch[f"{which}_out"][ci])
                nulls.append(ch[f"{which}_null"][ci])
        return outs, nulls

    def _sig(self):
        sig = (tuple(self.group_idx),
               tuple((c.kind, c.arg.index if c.arg is not None else None)
                     for c in self.calls),
               self.pack, self.pk_pack, self.spec, self.emit_out)
        # the combined-input mode reads a different delta layout — a
        # whole different trace. Conditional for the same reason as
        # "skew" below: un-armed signatures stay byte-identical to
        # previous releases.
        if self.combined:
            sig = sig + ("pre",)
        # skew telemetry extends the traced step (and the stats layout):
        # an armed node must never share an executable with an un-armed
        # twin. Appended conditionally so un-armed signatures — and the
        # plan hashes / manifests built from them — stay byte-identical
        # to previous releases.
        if self.skew:
            sig = sig + ("skew",)
        # flow telemetry extends the traced step and the stats layout
        # the same way — unarmed signatures stay byte-identical
        if self.flow:
            sig = sig + ("flow",)
        # same contract for tiering: the touch column wraps the state
        # pytree and two stats extend the layout
        if self.tier:
            sig = sig + ("tier",)
        return sig

    def _name_parts(self):
        return ("k" + "_".join(map(str, self.group_idx)),
                _calls_tag((c.kind, c.arg.index if c.arg is not None
                            else None) for c in self.calls))

    def _mut_sig(self):
        # grow mutates both; capacity shapes `bound`, exch the exchange.
        # exch=None (single-chip) keeps the pre-mesh salt shape so
        # persistent manifest digests from older releases stay valid
        if self.exch is None:
            return (self.capacity,)
        return (self.capacity, self.exch)

    def _tier_tail(self, tstate, new_state, trail):
        """Touch-column maintenance inside the traced step: the stamps
        ride the merge the step already performs. `trail` (the agg
        merge's `MergeTrail`) says which input row every new slot came
        from, so a surviving group that the epoch's delta names — also
        one whose delta nets to nothing — reads this tick, an untouched
        one its old stamp by position, a dead or empty slot 0; then
        (tres, tcold). Two gathers over the capacity, no search by key,
        no extra program, no sync."""
        import jax
        import jax.numpy as jnp
        from .sorted_state import EMPTY_KEY, merged_src
        from .tiering import TIER_TTL, TieredState
        touch, tick = tstate.touch, tstate.tick
        with jax.named_scope("tier.touch"):
            c = touch.shape[0]
            src = merged_src(trail, last=True)
            live = new_state.main.keys != EMPTY_KEY
            ntouch = jnp.where(
                live, jnp.where(src >= c, tick,
                                touch[jnp.minimum(src, c - 1)]), 0)
            tres = jnp.sum(live).astype(jnp.int64)
            tcold = jnp.sum(live & (tick - ntouch >= TIER_TTL)) \
                .astype(jnp.int64)
        return (TieredState(new_state, ntouch, tick + 1),
                [tres, tcold])

    def apply(self, state, ins, extra, epoch_events):
        import jax.numpy as jnp
        from .agg_step import DeviceAggState, local_epoch_step
        tstate = None
        if self.tier:
            tstate = state
            state = tstate.inner
        d = ins[0]
        if self.combined:
            # pre-combined input ([key, raw-row count, *partial deltas],
            # PrecombineNode layout) — no packing (key pre-packed, bounds
            # pre-checked upstream), no multisets (enable_precombine
            # forbids them). Behind an exchange a key arrives once from
            # each source shard and the partials are re-combined; with
            # none the delta is the one PrecombineNode's output
            # (`precombine_core`'s contract) and is merged as it is
            from .agg_step import epoch_core_combined
            keys = d.cols[0]
            cnt = d.cols[1]
            dvals = list(d.cols[2:2 + len(self.spec.kinds)])
            live = d.mask & (d.sign != 0)
            new_main, needed, ch = epoch_core_combined(
                self.spec, state.main, keys, cnt, dvals, live, self.tier,
                recombine=self.recombine)
            new_state = DeviceAggState(new_main, ())
            packbad = jnp.zeros((), jnp.int64)
            rows_in = ch["rows_in"].astype(jnp.int64)
            stats_tail: List[Any] = []
            sk: List[Any] = []
            if self.skew:
                # heavy hitters from the EXACT combined per-key counts
                # (weighted_topk) — same evidence the raw path's
                # sort/segment pass produces, one top_k cheaper
                from .skew_stats import vnode_occupancy, weighted_topk
                from .sorted_state import EMPTY_KEY
                sk = vnode_occupancy(new_main.keys, EMPTY_KEY) \
                    + weighted_topk(ch["keys"], ch["in_counts"],
                                    EMPTY_KEY)
            if self.flow:
                # traffic weighted by the combined rows' RAW-row counts,
                # so totals match the uncombined run exactly (the 1-vs-N
                # shard sum invariant survives pre-combine)
                from .skew_stats import vnode_traffic
                sk = sk + vnode_traffic(keys, live,
                                        weights=jnp.abs(cnt))
        else:
            gcols = [d.cols[i] for i in self.group_idx]
            packbad = self.pack.check(gcols, d.mask & (d.sign != 0))
            keys = self.pack.pack(gcols)
            inputs = []
            for c in self.calls:
                if c.arg is None:
                    z = jnp.zeros_like(keys)
                    inputs.append((z, jnp.ones(z.shape, bool)))
                else:
                    inputs.append((d.cols[c.arg.index],
                                   jnp.ones(keys.shape, bool)))
            new_state, _needed, ch = local_epoch_step(
                self.spec, state, keys, d.sign, d.mask, tuple(inputs),
                self.tier)
            needed, ms_needed = _needed
            rows_in = _nrows(d.mask & (d.sign != 0))
            stats_tail = [m.astype(jnp.int64) for m in ms_needed]
            sk = []
            if self.skew:
                # vnode-occupancy of the LIVE group table + this epoch's
                # top-K hot group keys, riding the stats vector (max
                # across epochs; pmax across shards — exact, vnode
                # blocks are disjoint). See device/skew_stats.py.
                from .skew_stats import epoch_topk, vnode_occupancy
                from .sorted_state import EMPTY_KEY
                sk = vnode_occupancy(new_state.main.keys, EMPTY_KEY) \
                    + epoch_topk(keys, d.mask & (d.sign != 0), EMPTY_KEY)
            if self.flow:
                # this epoch's ROUTED rows per vnode bucket (sum slots:
                # psum across shards, sum across epochs — exact totals)
                from .skew_stats import vnode_traffic
                sk = sk + vnode_traffic(keys, d.mask & (d.sign != 0))
        # the merge's trail is the tier tail's alone: no consumer of the
        # change set may keep it alive as a step output
        trail = ch.pop("merge_trail", None)
        if not self.emit_out:
            # terminal agg: only the MV apply reads the change set — keep
            # just what it needs; the delta stream is never materialized
            aux = {"keys": ch["keys"], "old_found": ch["old_found"],
                   "new_found": ch["new_found"], "new_out": ch["new_out"],
                   "new_null": ch["new_null"]}
            for mi in range(len(self.spec.minputs)):
                sub = ch[f"minput{mi}"]
                aux[f"minput{mi}"] = {k: sub[k] for k in
                                     ("new_found", "new_min", "new_max")}
            # no delta stream is materialized: rows_out counts the change
            # set the terminal MV applies (upserts + deletes)
            rows_out = _nrows(ch["old_found"] | ch["new_found"])
            stats = [needed.astype(jnp.int64),
                     ch["count"].astype(jnp.int64)] + stats_tail \
                + [packbad, rows_in, rows_out] + sk
            if tstate is not None:
                new_state, tstats = self._tier_tail(tstate, new_state,
                                                    trail)
                stats = stats + tstats
            return new_state, None, stats, aux
        # ---- change stream: old rows (-1) then new rows (+1) ------------
        old_found, new_found = ch["old_found"], ch["new_found"]
        old_outs, _ = self._call_outputs(ch, "old")
        new_outs, _ = self._call_outputs(ch, "new")
        changed = ~(old_found & new_found)
        for ov, nv in zip(old_outs, new_outs):
            changed = changed | (ov != nv)
        ug = self.pack.unpack(ch["keys"])
        cat = lambda a, b: jnp.concatenate([a, b])
        cols = [cat(g, g) for g in ug]
        for ov, nv in zip(old_outs, new_outs):
            cols.append(cat(ov, nv).astype(jnp.int64)
                        if not jnp.issubdtype(ov.dtype, jnp.floating)
                        else cat(ov, nv))
        n = ch["keys"].shape[0]
        sign = cat(-jnp.ones(n, jnp.int32), jnp.ones(n, jnp.int32))
        mask = cat(old_found & changed, new_found & changed)
        # Bound the emitted change set by 2 * capacity: an epoch cannot
        # touch more groups than the state holds without growing (the
        # `touched` stat triggers grow+replay before truncation could ever
        # drop a live row). Without this, downstream static shapes inherit
        # this node's INPUT row bound — q5's hop(5x) -> agg -> agg cascade
        # compiled 5.2M-row programs the remote compile helper OOM-killed.
        bound = 2 * min(n, self.capacity)
        if bound < 2 * n:
            from .sorted_state import compact_rows
            out_rows = compact_rows(
                mask, [], cols + [sign], bound,
                [0] * len(cols) + [0])
            cols, sign = list(out_rows[:-1]), out_rows[-1]
            mask = sign != 0
        pk = None
        if self.pk_pack is not None:
            pk = self.pk_pack.pack(cols)
            packbad = packbad | self.pk_pack.check(cols, mask)
        out = Delta(cols, sign, mask, pk=pk)
        stats = [needed.astype(jnp.int64),
                 ch["count"].astype(jnp.int64)] + stats_tail \
            + [packbad, rows_in, _nrows(mask)] + sk
        if tstate is not None:
            new_state, tstats = self._tier_tail(tstate, new_state, trail)
            stats = stats + tstats
        return new_state, out, stats, ch


class JoinNode(Node):
    """join_core + on-device cross-delta pair netting (the r02 resurrection
    fix, moved into the traced program) + optional non-equi condition over
    the pair columns. Output pair identity = (left pk, right pk)."""

    def __init__(self, left: int, right: int, l_keys: Sequence[int],
                 r_keys: Sequence[int], pack: PackPlan,
                 cond: Optional[Any], capacity: int, pair_capacity: int,
                 l_val_dtypes, r_val_dtypes):
        self.inputs = (left, right)
        self.l_keys = list(l_keys)
        self.r_keys = list(r_keys)
        self.pack = pack
        self.cond = cond
        self.cap_a = self.cap_b = self.capacity = capacity
        self.m = pair_capacity
        self.l_val_dtypes = list(l_val_dtypes)
        self.r_val_dtypes = list(r_val_dtypes)
        self.stat_names = ("need_a", "need_b", "need_pairs", "packbad",
                           "rows_in", "rows_out")
        self.stat_sums = ("rows_in", "rows_out")
        self.live_stats = ("need_a", "need_b")

    def enable_skew(self):
        from .skew_stats import SKEW_STAT_NAMES
        if not self.skew:
            self.skew = True
            self.stat_names = tuple(self.stat_names) + SKEW_STAT_NAMES

    def enable_flow(self):
        # see AggNode.enable_flow; traffic spans BOTH input deltas
        from .skew_stats import TRAFFIC_STAT_NAMES
        if not self.flow:
            self.flow = True
            self.stat_names = tuple(self.stat_names) + TRAFFIC_STAT_NAMES
            self.stat_sums = tuple(self.stat_sums) + TRAFFIC_STAT_NAMES

    def enable_tiering(self):
        # see AggNode.enable_tiering; tres/tcold span BOTH build sides
        if not self.tier:
            self.tier = True
            self.stat_names = tuple(self.stat_names) + ("tres", "tcold")

    def shard_spec(self):
        # both build sides partition by the vnode of the packed join key;
        # both input deltas shuffle first, keeping row identity (pair
        # netting needs each side's pk through the exchange)
        return ShardSpec("vnode",
                         (ShardExchange(0, tuple(self.l_keys), True),
                          ShardExchange(1, tuple(self.r_keys), True)))

    def init_state(self):
        from .join_step import make_side
        state = (make_side(self.cap_a, self.l_val_dtypes),
                 make_side(self.cap_b, self.r_val_dtypes))
        if self.tier:
            import jax.numpy as jnp
            from .tiering import TieredState
            return TieredState(state,
                               (jnp.zeros((self.cap_a,), jnp.int64),
                                jnp.zeros((self.cap_b,), jnp.int64)),
                               jnp.zeros((), jnp.int64))
        return state

    def cap_current(self):
        caps = {"a": self.cap_a, "b": self.cap_b, "pairs": self.m}
        if self.exch is not None:
            caps["exch"] = self.exch
        return caps

    def cap_needs(self, stats):
        needs = {"a": stats["need_a"], "b": stats["need_b"],
                 "pairs": stats["need_pairs"]}
        if self.exch is not None:
            needs["exch"] = stats.get("exch", 0)
        return needs

    def cap_needs_cum(self, stats):
        # build sides accumulate rows; the pair buffer does not
        return {"a": stats["need_a"], "b": stats["need_b"]}

    def cap_needs_epoch(self, stats):
        # the probe-output pair buffer is re-filled from scratch every
        # epoch — per-epoch-bounded, never horizon-extrapolated; same for
        # the exchange send bucket
        needs = {"pairs": stats["need_pairs"]}
        if self.exch is not None:
            needs["exch"] = stats.get("exch", 0)
        return needs

    def cap_bytes(self):
        # pair buffer: two probe outputs carry both sides' payloads + ids
        pair = 16 * (3 + len(self.l_val_dtypes) + len(self.r_val_dtypes))
        caps = {"a": 8 * (2 + len(self.l_val_dtypes)),
                "b": 8 * (2 + len(self.r_val_dtypes)),
                "pairs": pair}
        if self.exch is not None:
            caps["exch"] = self.exch_bytes
        return caps

    def preset_caps(self, caps):
        self.cap_a = max(self.cap_a, caps.get("a", 0))
        self.cap_b = max(self.cap_b, caps.get("b", 0))
        self.m = max(self.m, caps.get("pairs", 0))
        self.capacity = max(self.cap_a, self.cap_b)
        if self.exch is not None:
            self.exch = max(self.exch, caps.get("exch", 0))

    def cap_resize(self, state, caps):
        import jax.numpy as jnp
        from .join_step import grow_side
        tstate = None
        if self.tier:
            from .tiering import TieredState
            tstate = state
            state = tstate.inner
        if self.exch is not None and caps.get("exch", 0) > self.exch:
            self.exch = caps["exch"]   # jit-static: _mut_sig salts the trace
        a, b = state
        if caps.get("a", 0) > a.jk.shape[0]:
            self.cap_a = caps["a"]
            a = grow_side(a, self.cap_a)
        if caps.get("b", 0) > b.jk.shape[0]:
            self.cap_b = caps["b"]
            b = grow_side(b, self.cap_b)
        self.capacity = max(self.cap_a, self.cap_b)
        if caps.get("pairs", 0) > self.m:
            self.m = caps["pairs"]    # jit-static: _mut_sig salts the trace
        if tstate is None:
            return (a, b)
        from .tiering import TieredState
        ta, tb = tstate.touch
        if a.jk.shape[0] > ta.shape[0]:
            ta = jnp.concatenate(
                [ta, jnp.zeros((a.jk.shape[0] - ta.shape[0],),
                               jnp.int64)])
        if b.jk.shape[0] > tb.shape[0]:
            tb = jnp.concatenate(
                [tb, jnp.zeros((b.jk.shape[0] - tb.shape[0],),
                               jnp.int64)])
        return TieredState((a, b), (ta, tb), tstate.tick)

    def _sig(self):
        sig = (tuple(self.l_keys), tuple(self.r_keys), self.pack,
               _expr_sig(self.cond) if self.cond is not None else None,
               tuple(str(d) for d in self.l_val_dtypes),
               tuple(str(d) for d in self.r_val_dtypes))
        # see AggNode._sig: armed skew telemetry changes the trace
        if self.skew:
            sig = sig + ("skew",)
        if self.flow:
            sig = sig + ("flow",)
        if self.tier:
            sig = sig + ("tier",)
        return sig

    def _name_parts(self):
        return ("l" + "_".join(map(str, self.l_keys)),
                "r" + "_".join(map(str, self.r_keys)))

    def _mut_sig(self):
        # grow mutates the pair capacity and the exchange bucket capacity
        # (exch=None single-chip keeps the pre-mesh salt shape — see AggNode)
        if self.exch is None:
            return (self.m,)
        return (self.m, self.exch)

    def apply(self, state, ins, extra, epoch_events):
        import jax.numpy as jnp
        from .join_step import local_join_step
        tstate = None
        if self.tier:
            tstate = state
            state = tstate.inner
        A, B = ins
        packbad = jnp.zeros((), jnp.int64)
        sides = []
        for d, keys in ((A, self.l_keys), (B, self.r_keys)):
            kcols = [d.cols[i] for i in keys]
            packbad = packbad | self.pack.check(kcols, d.mask & (d.sign != 0))
            jk = self.pack.pack(kcols)
            vals = tuple(c if jnp.issubdtype(c.dtype, jnp.floating)
                         else c.astype(jnp.int64) for c in d.cols)
            sides.append((jk, d.pk, d.sign, d.mask, vals))
        a, b = state
        (ajk, apk, asg, amk, avals) = sides[0]
        (bjk, bpk, bsg, bmk, bvals) = sides[1]
        # per-shard local step under mesh sharding, the whole step on one
        # chip: probe + merge + cross-delta pair netting (join_step)
        new_a, new_b, njk, npk, nsign, nvals, needed, *trails = \
            local_join_step(a, b, ajk, apk, asg, amk, avals,
                            bjk, bpk, bsg, bmk, bvals, self.m, self.tier)
        omask = nsign != 0
        ocols = list(nvals)
        if self.cond is not None:
            ok, valid = self.cond.eval_device(ocols)
            omask = omask & ok & valid
        out = Delta(ocols, nsign, omask, pk=njk, pk2=npk)
        rows_in = _nrows(A.mask & (A.sign != 0)) \
            + _nrows(B.mask & (B.sign != 0))
        stats = [needed["a"].astype(jnp.int64),
                 needed["b"].astype(jnp.int64),
                 needed["pairs"].astype(jnp.int64), packbad,
                 rows_in, _nrows(omask)]
        if self.skew:
            # occupancy over BOTH build sides (same key space, summed
            # per bucket) + this epoch's hot join keys across both input
            # deltas — the JSPIM hot-build-key replication evidence
            from .skew_stats import epoch_topk, vnode_occupancy
            from .sorted_state import EMPTY_KEY
            occ_a = vnode_occupancy(new_a.jk, EMPTY_KEY)
            occ_b = vnode_occupancy(new_b.jk, EMPTY_KEY)
            cat_keys = jnp.concatenate([ajk, bjk])
            cat_live = jnp.concatenate([amk & (asg != 0),
                                        bmk & (bsg != 0)])
            stats += [a + b for a, b in zip(occ_a, occ_b)] \
                + epoch_topk(cat_keys, cat_live, EMPTY_KEY)
        if self.flow:
            # routed rows across BOTH input deltas per vnode bucket —
            # the traffic this join's exchange actually moved this epoch
            from .skew_stats import vnode_traffic
            stats += vnode_traffic(
                jnp.concatenate([ajk, bjk]),
                jnp.concatenate([amk & (asg != 0), bmk & (bsg != 0)]))
        if tstate is None:
            return (new_a, new_b), out, stats, None
        # touch at JOIN-KEY granularity (every row of one jk shares the
        # stamp — demotion/promotion move whole jk groups so probe
        # results never see a partial build side). An arriving delta on
        # EITHER input touches the jk on BOTH sides. The stamps ride each
        # side's merge by position (its `MergeTrail`); the epoch's
        # touched keys are searched INTO the side — one query per delta
        # row, none per slot — and mark their runs.
        import jax
        from .join_step import mark_key_runs
        from .sorted_state import EMPTY_KEY, merged_src
        from .tiering import TIER_TTL, TieredState
        tick = tstate.tick

        def side_touch(old_touch, new_side, trail, tkeys):
            c = old_touch.shape[0]
            src = merged_src(trail, last=False)
            carried = jnp.where(src < c,
                                old_touch[jnp.minimum(src, c - 1)], 0)
            hit = mark_key_runs(new_side.jk, tkeys)
            live = new_side.jk != EMPTY_KEY
            ntouch = jnp.where(live, jnp.where(hit, tick, carried), 0)
            return ntouch, live, live & (tick - ntouch >= TIER_TTL)

        with jax.named_scope("tier.touch"):
            tkeys = jnp.concatenate(
                [jnp.where(amk & (asg != 0), ajk, EMPTY_KEY),
                 jnp.where(bmk & (bsg != 0), bjk, EMPTY_KEY)])
            ta, tb = tstate.touch
            trail_a, trail_b = trails[0]
            nta, live_a, cold_a = side_touch(ta, new_a, trail_a, tkeys)
            ntb, live_b, cold_b = side_touch(tb, new_b, trail_b, tkeys)
            tres = (jnp.sum(live_a) + jnp.sum(live_b)).astype(jnp.int64)
            tcold = (jnp.sum(cold_a) + jnp.sum(cold_b)).astype(jnp.int64)
        stats = stats + [tres, tcold]
        return (TieredState((new_a, new_b), (nta, ntb), tick + 1),
                out, stats, None)


class MVKeyedNode(Node):
    """Terminal MV over an agg change set: upsert-by-group-key table
    (`device/materialize.py`), zero host traffic until a pull."""

    def __init__(self, input: int, agg_node: AggNode, capacity: int):
        self.inputs = (input,)
        self.agg = agg_node
        self.capacity = capacity
        self.stat_names = ("needed", "rows_in")
        self.stat_sums = ("rows_in",)
        self.live_stats = ("needed",)

    def shard_spec(self):
        # co-partitioned with its agg (the change set arrives already on
        # the group key's owning shard) — exchange-free, as NoShuffle
        # dictates for Materialize over an agg
        return ShardSpec("vnode")

    def init_state(self):
        from .materialize import make_mv_state
        dts = [c.acc_dtype for c in self.agg.spec.calls]
        return make_mv_state(self.capacity, dts)

    def cap_current(self):
        return {"main": self.capacity}

    def cap_needs(self, stats):
        return {"main": stats["needed"]}

    def cap_bytes(self):
        # key + liveness + (value, null) per call (bools cost a byte but
        # the budget math rounds to words)
        return {"main": 8 * (2 + 2 * len(self.agg.spec.calls))}

    def preset_caps(self, caps):
        self.capacity = max(self.capacity, caps.get("main", 0))

    def cap_resize(self, state, caps):
        from .materialize import mv_kinds
        from .sorted_state import grow_state
        if caps.get("main", 0) > state.capacity:
            self.capacity = caps["main"]
            return grow_state(state, self.capacity,
                              mv_kinds(len(self.agg.spec.calls)))
        return state

    def _sig(self):
        return ("mvk",) + self.agg._sig()

    def _name_parts(self):
        return self.agg._name_parts()

    def apply(self, state, ins, extra, epoch_events):
        import jax.numpy as jnp
        from .materialize import mv_apply_changes
        ch = extra
        upsert = ch["new_found"]
        delete = ch["old_found"] & ~ch["new_found"]
        outs, nulls = self.agg._call_outputs(ch, "new")
        state, needed = mv_apply_changes(
            state, ch["keys"], upsert, delete,
            [o.astype(v.dtype) for o, v in
             zip(outs, [state.vals[1 + 2 * i] for i in range(len(outs))])],
            nulls)
        return state, None, [needed.astype(jnp.int64),
                             _nrows(upsert | delete)], None


class MVPairNode(Node):
    """Terminal MV over a join's pair stream: a sorted multimap keyed by
    (left pk, right pk) holding the output columns (merge_side upsert)."""

    def __init__(self, input: int, val_dtypes, capacity: int):
        self.inputs = (input,)
        self.val_dtypes = list(val_dtypes)
        self.capacity = capacity
        self.stat_names = ("needed", "rows_in")
        self.stat_sums = ("rows_in",)
        self.live_stats = ("needed",)

    def shard_spec(self):
        # co-partitioned with its join: a pair lives on the shard owning
        # its join key's vnode block, and pair identity (left pk, right
        # pk) is globally unique — exchange-free
        return ShardSpec("vnode")

    def init_state(self):
        from .join_step import make_side
        return make_side(self.capacity, self.val_dtypes)

    def cap_current(self):
        return {"main": self.capacity}

    def cap_needs(self, stats):
        return {"main": stats["needed"]}

    def cap_bytes(self):
        return {"main": 8 * (2 + len(self.val_dtypes))}

    def preset_caps(self, caps):
        self.capacity = max(self.capacity, caps.get("main", 0))

    def cap_resize(self, state, caps):
        from .join_step import grow_side
        if caps.get("main", 0) > state.jk.shape[0]:
            self.capacity = caps["main"]
            return grow_side(state, self.capacity)
        return state

    def _sig(self):
        return (tuple(str(d) for d in self.val_dtypes),)

    def _name_parts(self):
        return (f"{len(self.val_dtypes)}c",)

    def apply(self, state, ins, extra, epoch_events):
        import jax
        import jax.numpy as jnp
        from .join_step import merge_side
        d = ins[0]
        sign = jnp.where(d.mask, d.sign, 0)
        vals = tuple(c if jnp.issubdtype(c.dtype, jnp.floating)
                     else c.astype(jnp.int64) for c in d.cols)
        with jax.named_scope("mv.apply"):      # HLO metadata only
            state, needed = merge_side(state, d.pk, d.pk2, sign, vals)
        return state, None, [needed.astype(jnp.int64),
                             _nrows(sign != 0)], None


# HopNode stays un-chained: fusing the 5x window expansion into the
# datagen program produced XLA graphs the remote-compile helper could not
# finish (observed wedge, round 5); as its own program it compiles fine.
_CHAINABLE = (SourceNode, MapNode, FilterNode)


# ---------------------------------------------------------------------------
# Tiered-state device surgery (policy in device/tiering.py; FusedJob
# drives). Evict compacts demoted keys out of a table IN PLACE at the
# SAME capacity — the node step's executable is untouched (same avals,
# same _mut_sig), which is the zero-compile contract for demotion.
# Promote is sorted_state.merge / join_step.merge_side with the exact
# stored payload: an absent key inserts its delta verbatim, so a
# demote->promote round trip is bit-exact. These helpers jit OUTSIDE
# the compile service on purpose: its counters are the "zero fresh
# compiles at adoption" assertion surface and tier surgery is not a
# node-step compile.

_TIER_JITS: Dict[Any, Any] = {}


def _tier_jit(name: Tuple[str, int], fn, static=("node",)):
    """`fn` jitted once per (surgery, vmapped over shards?) as XLA
    module `jit_tier_<surgery>[_sharded]`."""
    import jax
    if name not in _TIER_JITS:
        def tier(*args, **statics):
            return fn(*args, **statics)
        op, vmapped = name
        _TIER_JITS[name] = jax.jit(
            _named(tier, f"tier_{op}" + ("_sharded" if vmapped else "")),
            static_argnames=static)
    return _TIER_JITS[name]


def _agg_evict_core(tstate, dkeys, *, node):
    """Demote `dkeys` (sorted, EMPTY-padded) from a tiered agg state:
    returns (state without those rows — same capacity, count reduced —,
    found[L], payload vals at dkeys, touch at dkeys)."""
    import jax.numpy as jnp
    from .agg_step import DeviceAggState
    from .sorted_state import (EMPTY_KEY, SortedState, _neutral,
                               compact_rows, lookup)
    from .tiering import TieredState
    inner, touch, tick = tstate.inner, tstate.touch, tstate.tick
    main = inner.main
    cap = main.keys.shape[0]
    found, dvals = lookup(main, dkeys)
    idx = jnp.clip(jnp.searchsorted(main.keys, dkeys), 0, cap - 1)
    dtouch = jnp.where(found, touch[idx], 0)
    ridx = jnp.clip(jnp.searchsorted(dkeys, main.keys), 0,
                    dkeys.shape[0] - 1)
    hit = (dkeys[ridx] == main.keys) & (main.keys != EMPTY_KEY)
    alive = (main.keys != EMPTY_KEY) & ~hit
    fills = [EMPTY_KEY] + [_neutral(k, v.dtype)
                           for v, k in zip(main.vals, node.spec.kinds)] \
        + [0]
    rows = compact_rows(alive, [main.keys],
                        list(main.vals) + [touch], cap, fills)
    ncount = jnp.minimum(jnp.sum(alive).astype(jnp.int32), cap)
    nmain = SortedState(rows[0], ncount, tuple(rows[1:-1]))
    return (TieredState(DeviceAggState(nmain, inner.minputs),
                        rows[-1], tick), found, dvals, dtouch)


def _mv_evict_core(state, dkeys, *, node):
    """Lockstep MV demotion (MVKeyedNode SortedState, no touch col)."""
    import jax.numpy as jnp
    from .materialize import mv_kinds
    from .sorted_state import (EMPTY_KEY, SortedState, _neutral,
                               compact_rows, lookup)
    cap = state.keys.shape[0]
    found, dvals = lookup(state, dkeys)
    ridx = jnp.clip(jnp.searchsorted(dkeys, state.keys), 0,
                    dkeys.shape[0] - 1)
    hit = (dkeys[ridx] == state.keys) & (state.keys != EMPTY_KEY)
    alive = (state.keys != EMPTY_KEY) & ~hit
    kinds = mv_kinds(len(node.agg.spec.calls))
    fills = [EMPTY_KEY] + [_neutral(k, v.dtype)
                           for v, k in zip(state.vals, kinds)]
    rows = compact_rows(alive, [state.keys], list(state.vals), cap,
                        fills)
    ncount = jnp.minimum(jnp.sum(alive).astype(jnp.int32), cap)
    return (SortedState(rows[0], ncount, tuple(rows[1:])), found, dvals)


def _join_evict_core(tstate, dkeys, *, node, side):
    """Demote every row of the given jks from ONE build side: returns
    (new tiered state, demoted jk/pk/vals/touch compacted to a prefix,
    n_demoted)."""
    import jax.numpy as jnp
    from .join_step import JoinSide
    from .sorted_state import EMPTY_KEY, compact_rows
    from .tiering import TieredState
    a, b = tstate.inner
    ta, tb = tstate.touch
    s, st = (a, ta) if side == 0 else (b, tb)
    cap = s.jk.shape[0]
    ridx = jnp.clip(jnp.searchsorted(dkeys, s.jk), 0,
                    dkeys.shape[0] - 1)
    hit = (dkeys[ridx] == s.jk) & (s.jk != EMPTY_KEY)
    alive = (s.jk != EMPTY_KEY) & ~hit
    fills = [EMPTY_KEY, EMPTY_KEY] + [0] * len(s.vals) + [0]
    cols = list(s.vals) + [st]
    arows = compact_rows(alive, [s.jk, s.pk], cols, cap, fills)
    drows = compact_rows(hit, [s.jk, s.pk], cols, cap, fills)
    ncount = jnp.minimum(jnp.sum(alive).astype(jnp.int32), cap)
    ns = JoinSide(arows[0], arows[1], ncount, tuple(arows[2:-1]))
    nst = arows[-1]
    ndem = jnp.sum(hit).astype(jnp.int32)
    new = ((ns, b), (nst, tb)) if side == 0 else ((a, ns), (ta, nst))
    return (TieredState(new[0], new[1], tstate.tick),
            drows[0], drows[1], tuple(drows[2:-1]), drows[-1], ndem)


def _agg_promote_core(tstate, pkeys, pvals, ptouch, acc, *, node):
    """Insert promoted rows (exact stored payload + touch) back into a
    tiered agg state; EMPTY-padded buffer rows are no-ops. Returns the
    new state and the max-folded `needed` accumulator (promotion can
    overflow capacity like any merge — the job folds this into the
    normal grow+replay remedy at the next sync)."""
    import jax.numpy as jnp
    from .agg_step import DeviceAggState
    from .sorted_state import EMPTY_KEY, merge
    from .tiering import TieredState
    inner, touch, tick = tstate.inner, tstate.touch, tstate.tick
    main = inner.main
    new_main, needed = merge(main, pkeys, pvals, node.spec.kinds)
    keys = new_main.keys
    cap = keys.shape[0]
    oidx = jnp.clip(jnp.searchsorted(main.keys, keys), 0, cap - 1)
    ofound = main.keys[oidx] == keys
    pidx = jnp.clip(jnp.searchsorted(pkeys, keys), 0,
                    pkeys.shape[0] - 1)
    pfound = pkeys[pidx] == keys
    ntouch = jnp.where(keys != EMPTY_KEY,
                       jnp.where(ofound, touch[oidx],
                                 jnp.where(pfound, ptouch[pidx], 0)),
                       0)
    nacc = jnp.maximum(acc, needed.astype(jnp.int64))
    return (TieredState(DeviceAggState(new_main, inner.minputs),
                        ntouch, tick), nacc)


def _mv_promote_core(state, pkeys, pvals, acc, *, node):
    import jax.numpy as jnp
    from .materialize import mv_kinds
    from .sorted_state import merge
    new_state, needed = merge(state, pkeys, pvals,
                              mv_kinds(len(node.agg.spec.calls)))
    return new_state, jnp.maximum(acc, needed.astype(jnp.int64))


def _join_promote_core(tstate, pa, pb, acc, *, node):
    """Promote cold rows into BOTH build sides ((jk, pk, vals, jk-touch)
    per side, (jk,pk)-sorted, EMPTY-padded). `acc` is an (a, b) pair of
    per-side needed accumulators (per-side capacities grow separately)."""
    import jax.numpy as jnp
    from .join_step import merge_side
    from .sorted_state import EMPTY_KEY
    from .tiering import TieredState
    a, b = tstate.inner
    ta, tb = tstate.touch
    tick = tstate.tick

    def one(side, st, buf):
        jk, pk, vals, pt = buf
        sign = jnp.where(jk != EMPTY_KEY, 1, 0).astype(jnp.int32)
        ns, needed = merge_side(side, jk, pk, sign, vals)
        nk = ns.jk
        oc = side.jk.shape[0]
        oidx = jnp.clip(jnp.searchsorted(side.jk, nk, side="left"),
                        0, oc - 1)
        ofound = side.jk[oidx] == nk
        pix = jnp.clip(jnp.searchsorted(jk, nk, side="left"), 0,
                       jk.shape[0] - 1)
        pfound = jk[pix] == nk
        nst = jnp.where(nk != EMPTY_KEY,
                        jnp.where(ofound, st[oidx],
                                  jnp.where(pfound, pt[pix], 0)), 0)
        return ns, nst, needed

    na, nta, need_a = one(a, ta, pa)
    nb, ntb, need_b = one(b, tb, pb)
    return (TieredState((na, nb), (nta, ntb), tick),
            (jnp.maximum(acc[0], need_a.astype(jnp.int64)),
             jnp.maximum(acc[1], need_b.astype(jnp.int64))))


def _tier_call(name: str, core, shards: int, args, statics: Dict):
    """Run a surgery core single-chip or vmapped over the shard axis.
    `args[0]` is the (per-shard, under mesh) state; the rest follow the
    core's positional signature. Evict cores get ONE shared key buffer
    across shards (each shard evicts the subset it holds — no host
    routing needed); promote cores get per-shard [S, L] buffers."""
    import jax
    snames = tuple(statics.keys())
    if shards <= 1:
        return _tier_jit((name, 0), core, snames)(*args, **statics)
    shared_keys = "evict" in name

    def vm(*a, **kw):
        if shared_keys:
            state, rest = a[0], a[1:]
            return jax.vmap(lambda ts: core(ts, *rest, **kw))(state)
        return jax.vmap(lambda *xs: core(*xs, **kw))(*a)

    return _tier_jit((name, 1), vm, snames)(*args, **statics)


# ---------------------------------------------------------------------------
# program: topo-ordered nodes -> one traced epoch function
# ---------------------------------------------------------------------------


def node_shape_key(node: Node) -> str:
    """Deterministic digest of a node's structural signature — stable
    across processes and planner refactors (unlike `hash()`, which is
    PYTHONHASHSEED-salted for strings, and unlike program indices, which
    a planner change renumbers). Keys the high-water presize registry
    AND the AOT compile manifest, so both survive planner refactors
    together. Nodes whose signatures fall back to `id()` (unknown expr
    classes) get a per-process key — they lose sharing, never alias."""
    import hashlib
    sig = repr((type(node).__name__, node._sig()))
    return hashlib.sha1(sig.encode()).hexdigest()[:16]


def plan_shape_hash(nodes: Sequence[Node], epoch_events: int,
                    mesh_shards: int = 1) -> str:
    """Structural hash of a fused plan: node signatures (types, exprs,
    dtypes, pack plans), topology (input edges), the epoch cadence, and
    the mesh shard count — everything that shapes the traced programs,
    and nothing that doesn't (names, program indices). Two CREATEs of
    identically-shaped jobs collide here by design: that collision is
    the zero-compile warm start. An n-shard and a 1-shard plan never
    collide — their executables, state layouts, and capacity high-water
    marks are per-shard vs global quantities."""
    import hashlib
    parts = [(node_shape_key(n), n.inputs) for n in nodes]
    if mesh_shards > 1:
        parts.append(("mesh_shards", mesh_shards))
    return hashlib.sha1(repr((parts, epoch_events)).encode()).hexdigest()[:16]


@dataclass
class MVPull:
    """How the host materializes the terminal MV state into SQL rows."""
    kind: str                      # "keyed" | "pair"
    node_idx: int
    dtypes: List[DataType]
    decoders: List[Tuple]
    # keyed only: final column <- ("g", group_pos) | ("c", call_pos)
    agg: Optional[AggNode] = None
    out_map: Optional[List[Tuple[str, int]]] = None


class FusedProgram:
    def __init__(self, nodes: List[Node], epoch_events: int, mesh=None):
        self.nodes, self.remap = _chain_nodes(nodes)
        self.epoch_events = epoch_events
        # device mesh for shard_map'd execution (device/shard_exec.py);
        # None = the single-chip path, byte-for-byte the pre-mesh
        # program. A cadence that does not divide the shard count is
        # fine: the tail event block pads (shard_exec.sharded_apply)
        self.mesh = mesh
        # each node's stable name (`rw:step` spans, module names) and
        # its compile-event label
        self.node_names = [n.stable_name() for n in self.nodes]
        self._labels: Dict[int, str] = {}
        # rows-wide shape each node's step was handed last epoch (its
        # input deltas' lanes, all shards'; a source's: the lanes it
        # made) — static shapes the host loop reads off the handles it
        # routes, for `FusedJob.flow_report`
        self.lanes: List[Optional[int]] = [None] * len(self.nodes)
        # vnode-block bounds the exchange routes by: None = the uniform
        # `vnode_block_bounds` layout; a rebalanced job carries the
        # custom bounds chosen at a checkpoint barrier. Routing-only
        # policy — node-step traces never see it (zero-compile switch).
        self.vnode_bounds: Optional[Tuple[int, ...]] = None
        # aval mirror of each exchange stage's last input delta, keyed
        # (node idx, exchange idx) — what the policy pre-warm lowers the
        # re-routed exchange against (shard_exec.prewarm_exchange)
        self._exch_sds: Dict[Tuple[int, int], Any] = {}
        # an agg whose only consumers are terminal MV appliers never needs
        # its change-delta stream (they read the aux change set instead)
        delta_consumed: Dict[int, bool] = {}
        for n in self.nodes:
            for j in n.inputs:
                if not isinstance(n, MVKeyedNode):   # MVKeyed reads aux only
                    delta_consumed[j] = True
        for i, n in enumerate(self.nodes):
            if isinstance(n, AggNode) and not delta_consumed.get(i):
                n.emit_out = False
        self.stat_layout = []
        for i, n in enumerate(self.nodes):
            for s in n.stat_names:
                self.stat_layout.append((i, s))
        # which stats_acc slots accumulate by SUM (row-flow counters) vs
        # MAX (capacity needs / violation flags) — see Node.stat_sums
        self._sum_mask = np.array(
            [name in self.nodes[ni].stat_sums
             for ni, name in self.stat_layout] or [False], dtype=bool)
        # epoch profiler (utils/profile.py), attached by the owning
        # FusedJob; None (or disabled) = zero per-node instrumentation
        self.profiler = None
        # AOT compile service (device/compile_service.py) + owning job
        # name, attached by FusedJob when DeviceConfig.aot_compile is on;
        # None = inline jit compiles on the epoch loop (the old path)
        self.compile_service = None
        self.job_name: Optional[str] = None

    def init_states(self):
        states = tuple(n.init_state() for n in self.nodes)
        if self.mesh is not None:
            # every node's local state gains the leading shard axis and
            # lands mesh-sharded (identical empty shards -> broadcast)
            from .shard_exec import lift_tree
            states = tuple(lift_tree(s, self.mesh) for s in states)
        return states

    def resize_state(self, i: int, state, caps):
        """Grow node i's state to `caps` — through the shard axis when
        the program is mesh-sharded (per-shard capacities; every shard
        grows to the pmax'd high-water need)."""
        node = self.nodes[i]
        if self.mesh is not None:
            from .shard_exec import sharded_resize
            return sharded_resize(node, state, caps, self.mesh)
        return node.cap_resize(state, caps)

    def _node_label(self, i: int) -> str:
        """Compile-event label `<position>:<stable name>:<digest of the
        structural signature>` — two programs sharing a node signature
        share its compile, and the label makes that dedupe visible in
        the warmup decomposition. The same in every process (no
        `hash()`), so compile events compare between runs."""
        label = self._labels.get(i)
        if label is None:
            from .compile_service import _stable_digest
            n = self.nodes[i]
            sig = _stable_digest((type(n).__name__,) + n._sig())[:8]
            label = self._labels[i] = f"{i}:{self.node_names[i]}:{sig}"
        return label

    def epoch(self, states, event_lo, feeds=None):
        """Host loop over per-node jitted steps -> (states', the epoch's
        stat scalars): each call dispatches
        async; only device-array handles flow between nodes. With a live
        profiler, each step is a `rw:step` span (`node`, `i`, `label`);
        a compile jax makes inside it — the service off, or its fallback
        — is a `rw:compile.inline` span under it and the job's
        compile/retrace record (utils/profile.py).

        `feeds` maps node index -> staged device feed for `takes_feed`
        (host-ingest) nodes; the owning FusedJob's HostIngest stager
        supplies one per dispatched epoch."""
        import jax.numpy as jnp
        spans = self.profiler or NULL_PROFILER
        svc = self.compile_service
        mesh = self.mesh
        outs: List[Optional[Delta]] = []
        auxes: List[Any] = []
        new_states = list(states)
        stats: List[Any] = []
        for i, node in enumerate(self.nodes):
            ins = [outs[j] for j in node.inputs]
            exch_need, exch_rows = None, []
            if mesh is not None and node.exch is not None:
                # in-program ICI shuffle: route each flagged input's rows
                # to the shard owning their key's vnode block. Its own
                # span, so the profiler splits "exchange" out of
                # "dispatch" (dispatch is async — this wall is enqueue
                # cost, the device-side ICI time lands in device_sync
                # like all device compute)
                from ..parallel.mesh import data_shards
                from .shard_exec import delta_sds, exchange_delta
                shards = data_shards(mesh)
                for xi, ex in enumerate(node.shard_spec().exchanges):
                    with spans.span("rw:exchange", node=self.node_names[i],
                                    xi=xi, shards=shards, exch=node.exch,
                                    rows_slots=shards * node.exch):
                        self._exch_sds[(i, xi)] = delta_sds(ins[ex.input])
                        ins[ex.input], need, rows_in = exchange_delta(
                            mesh, node, xi, ins[ex.input],
                            bounds=self.vnode_bounds)
                    exch_need = need if exch_need is None \
                        else jnp.maximum(exch_need, need)
                    exch_rows.extend(rows_in)
            ins = tuple(ins)
            if node.takes_event_lo:
                extra = jnp.int64(event_lo) if not hasattr(
                    event_lo, 'dtype') else event_lo
            elif node.takes_feed:
                extra = (feeds or {})[i]
            elif isinstance(node, MVKeyedNode):
                extra = auxes[node.inputs[0]]
            else:
                extra = None
            with spans.span("rw:step", node=self.node_names[i], i=i,
                            label=self._node_label(i),
                            **node.span_attrs()) as sp:
                if svc is not None:
                    # compile-service path: ready executables dispatch
                    # with zero trace; a pending one is waited for (the
                    # service's `rw:compile` span is the compile's
                    # record, the wait a `rw:compile_wait`)
                    kind = (self.profiler.pending_compile.pop(i, None)
                            if self.profiler is not None else None)
                    st, out, s, aux = svc.node_step(
                        node, self.epoch_events, states[i], ins, extra,
                        label=self._node_label(i), job=self.job_name,
                        profiler=self.profiler, kind=kind, mesh=mesh)
                elif mesh is not None:
                    from .shard_exec import sharded_node_step
                    st, out, s, aux = sharded_node_step(
                        mesh, node, self.epoch_events, states[i], ins,
                        extra)
                else:
                    st, out, s, aux = _node_step(node, self.epoch_events,
                                                 states[i], ins, extra)
                if node.takes_event_lo:
                    # a device source says the lanes it made (all
                    # shards'), read off its delta
                    sp.set(lanes=out.mask.size, of=self.epoch_events)
            new_states[i] = st
            outs.append(out)
            auxes.append(aux)
            handed = [d for d in (ins if node.inputs else (out,))
                      if d is not None]
            self.lanes[i] = sum(d.mask.size for d in handed) \
                if handed else None
            if exch_need is not None:
                # the "exch" stat and the "xin" stats after it (appended
                # to the node's stat_names by enable_exchange) are
                # produced by the exchange stages, not the node's apply —
                # splice them in here
                s = list(s) + [exch_need] + exch_rows
            stats.extend(s)
        return tuple(new_states), tuple(stats)

    def step_fn(self):
        """(states, event_lo, stats_acc) -> (states', combine(stats_acc,
        vec)) where capacity/flag slots combine by max and row-flow
        counters by sum (`_sum_mask`). A host closure — per-node jits
        re-trace on their own when a grown node's shapes change; ungrown
        nodes keep their compiled steps."""
        import jax.numpy as jnp
        sum_mask = jnp.asarray(self._sum_mask)

        def step(states, event_lo, stats_acc, feeds=None):
            new_states, stats = self.epoch(states, event_lo, feeds=feeds)
            # ONE jitted program stacks the stat scalars and one folds
            # them into the accumulator. The eager `jnp.stack` this
            # replaces dispatched ~2 tiny programs PER SCALAR
            # (expand_dims each, then concatenate) — on a sharded
            # program those are dozens of per-epoch collective-bearing
            # mini-programs whose rendezvous, in flight together with
            # the node steps, can deadlock XLA:CPU's thread pool on
            # small hosts (observed: skew-armed q5 at 8 virtual devices
            # wedging in an AllReduce rendezvous); on any backend they
            # are pure dispatch overhead
            with (self.profiler or NULL_PROFILER).span("rw:stats_fold"):
                vec = _stack_stats(stats) if stats \
                    else jnp.zeros((1,), jnp.int64)
                acc = _fold_stats(vec, stats_acc, sum_mask)
            return new_states, acc

        return step

    def node_stats(self, i: int, vec: np.ndarray) -> Dict[str, int]:
        return {name: int(vec[k]) for k, (ni, name)
                in enumerate(self.stat_layout) if ni == i}


# ---------------------------------------------------------------------------
# FusedJob: the host-side driver behind Database.tick
# ---------------------------------------------------------------------------


# job state table key schema (pk = key). Key 0 predates the capacity
# lifecycle (old stores hold only it); cumulative growth counters and
# per-node capacity high-water marks live at reserved keys so restarts
# and re-created MVs presize instead of re-climbing the growth ladder.
_JS_COUNTER = 0              # committed event counter
_JS_REPLAYS = 1              # cumulative growth replays
_JS_RETRACES = 2             # cumulative node re-traces from growth
_JS_GROWTHS = 3              # cumulative capacity-slot increases
_JS_CAP_BASE = 16            # + node_idx * stride + slot ordinal
_JS_CAP_STRIDE = 16          # minimum per-node key stride; a program
                             # whose widest node has more capacity slots
                             # gets a wider stride (deterministic from the
                             # plan, so recovery decodes the same keys)
# Skew-routing policy rows (barrier-time vnode rebalancing + hot-key
# replication): the chosen routing must survive restart — recovery
# replays history through the exchange, and replaying under different
# bounds than the persisted capacities were sized for would re-climb
# the growth ladder. Values are VERSIONED (policy seq in the high bits)
# because recovery max-combines duplicate keys: the newest policy's
# rows always win, and every policy change rewrites EVERY slot.
_JS_POLICY_SEQ = 4           # bare policy sequence number
_JS_VB_BASE = 5              # + s: inner bound s+1; value = seq<<16|bound
_JS_VB_MAX = 10              # keys 5..14 stay clear of _JS_CAP_BASE —
                             # bounds persist only for mesh_shards <= 11
_JS_REBALANCES = 15          # cumulative adopted policy switches
_JS_HOT_BASE = 1 << 40       # + node*(SK_TOPK+1) + rank; value =
                             # seq<<41 | key40<<1 | present. The extra
                             # rank slot (rank == SK_TOPK) holds
                             # seq<<2 | hot_rep_side<<1 | armed.

# offline skew snapshot beside epoch_profile.jsonl (risectl skew)
SKEW_FILE = "skew_stats.json"

# live skew-policy pre-warm threads (FusedJob._stage_policy): tracked so
# a test session can join them before interpreter teardown — a daemon
# thread dying inside an XLA compile at exit aborts the process
_PREWARM_THREADS: List[Any] = []


def join_prewarm_threads(timeout: float = 30.0) -> None:
    import time as _time
    deadline = _time.monotonic() + timeout
    for t in list(_PREWARM_THREADS):
        t.join(max(0.0, deadline - _time.monotonic()))
    _PREWARM_THREADS[:] = [t for t in _PREWARM_THREADS if t.is_alive()]


class FusedJob:
    """Owns the device state of one fused MV fragment.

    Barrier protocol: `on_barrier` DISPATCHES one epoch (async — no device
    sync); checkpoint barriers sync, verify the accumulated stats (pack
    bounds, capacity overflow), persist the MV + committed event counter,
    and advance the restore snapshot. Capacity overflow restores the last
    snapshot, grows, and deterministically replays — barrier-boundary
    exactness is never compromised by the async window.

    Capacity lifecycle: overflow replays are PREDICTIVE and cascade-free —
    one overflow re-sizes every node in the program from its observed
    entries-per-event rate extrapolated over `max_events` (clamped by the
    HBM budget), so the replay at larger capacity does not immediately
    overflow a downstream node and re-enter the loop. Per-node capacity
    high-water marks checkpoint into the job state table; `recover()`
    presizes from them, making restart replays growth-free.
    """

    def __init__(self, name: str, program: FusedProgram, pull: MVPull,
                 max_events: Optional[int],
                 mv_state_table=None, job_state_table=None,
                 mv_schema_len: Optional[int] = None,
                 persist_every: int = 1,
                 predictive: bool = True, hbm_budget_mb: int = 4096,
                 profile: bool = True, aot_compile: bool = False,
                 compile_buckets: int = 4,
                 plan_hash: Optional[str] = None,
                 rebalance: bool = True, rebalance_threshold: float = 2.0,
                 hot_key_rep: bool = True, hot_key_frac: float = 0.125,
                 ingest=None,
                 state_tiering: bool = True, tier_plans=None):
        import jax.numpy as jnp
        from ..utils.profile import JobProfiler
        self.name = name
        self.program = program
        from ..parallel.mesh import data_shards
        self.mesh_shards = (data_shards(program.mesh)
                            if program.mesh is not None else 1)
        # epoch-timeline profiler: phase-split spans + compile events
        # (utils/profile.py). Every node's first step is a cold compile.
        self.profiler = JobProfiler(name, enabled=profile,
                                    shards=self.mesh_shards)
        self.profiler.pending_compile = {
            i: "compile" for i in range(len(program.nodes))}
        program.profiler = self.profiler
        # structural identity of this plan (node sigs + topology + epoch
        # cadence + mesh shards): keys the warm-start presize registry
        # and the AOT compile manifest — survives DROP/re-CREATE,
        # restarts, renames
        self.plan_hash = plan_hash or plan_shape_hash(program.nodes,
                                                      program.epoch_events,
                                                      self.mesh_shards)
        # AOT compile service: compiles move off the epoch loop onto a
        # background pool, a program's nodes in parallel; a step waits
        # for a pending signature (device/compile_service.py). Off =
        # inline jit compiles.
        self.compile_service = None
        self.compile_buckets = max(0, compile_buckets)
        self._prewarm_rounds = 0
        self._prewarmed: Dict[Tuple[int, str], int] = {}
        self._last_prewarm_needs: Optional[Dict] = None
        if aot_compile:
            from .compile_service import get_service
            self.compile_service = get_service()
            program.compile_service = self.compile_service
            program.job_name = name
        # host-ingest stager (device/ingest.py): when set, every epoch's
        # source input is a pre-staged device buffer taken from it
        # instead of device-regenerated events; None = the datagen path
        self.ingest = ingest
        if ingest is not None:
            ingest.profiler = self.profiler     # the stager's spans
        # tiered state (device/tiering.py): per-node host cold stores +
        # demotion journal + Xor8 negative caches. Armed by the planner
        # (enable_tiering on the nodes, TierPlans derived from the
        # ingest wiring); off — or no eligible node — keeps this job
        # byte-identical to the untiered build. The cold snapshot pairs
        # with `self.snapshot`: a growth replay must rewind BOTH tiers
        # to the same commit point, because window promotions move rows
        # out of the stores mid-window.
        self.state_tiering = bool(state_tiering) and bool(tier_plans)
        self.tiering = None
        self._cold_snapshot = None
        # promotion merges report truncation like any other step: the
        # per-slot `needed` high-water folds here host-side (promotions
        # are rare and already host-heavy) and joins the next sync's
        # overflow check instead of riding a device accumulator
        self._promo_need: Dict[int, Dict[str, int]] = {}
        if self.state_tiering:
            from .tiering import TieringManager
            self.tiering = TieringManager(tier_plans, self.mesh_shards)
        # node indices predate the chain transform — remap through it
        pull.node_idx = program.remap.get(pull.node_idx, pull.node_idx)
        self.pull = pull
        self.max_events = max_events
        self.mv_state_table = mv_state_table
        self.job_state_table = job_state_table
        self.mv_schema_len = mv_schema_len or len(pull.dtypes)
        # mirror the MV into the host state table every N epochs-worth of
        # checkpoints (pull + diff + row writes are host work that would
        # otherwise throttle every epoch); drain always mirrors
        self.persist_every = max(1, persist_every)
        self._last_persist = -1
        self.predictive = predictive
        self.hbm_budget_mb = hbm_budget_mb
        # growth accounting (risectl fused-stats / bench detail blocks);
        # cumulative across restarts (recover() restores the persisted
        # values, checkpoints write them back)
        self.growth_replays = 0
        self.retraces = 0
        self.growths = 0
        # coordinator-side epoch event log: one (event_lo, events) entry
        # per epoch dispatched since the last checkpoint — the retained
        # crash window an IN-PLACE recovery re-dispatches (sources are
        # deterministic, so the log of ranges IS the log of events).
        # Trimmed at every checkpoint commit; BOUNDED — entries past
        # RW_FUSED_EPOCH_LOG_BYTES spill beside epoch_profile.jsonl and
        # reload transparently on recovery (stretched cadence must not
        # trade queue growth for event-log growth).
        from ..config import ROBUSTNESS as _rob
        self._epoch_log = _EpochLog(_rob.fused_epoch_log_bytes,
                                    lambda: self.data_dir)
        # overload ladder (utils/overload): epochs dispatched per
        # barrier. >1 on the degraded/shedding rungs — same AOT-cached
        # executable every dispatch, so a cadence-stretch transition is
        # zero-fresh-compile by construction; results stay bit-identical
        # (the MV is a function of the event counter, not of where the
        # barrier boundaries fell).
        self.cadence_stretch = 1
        # in-place recoveries from device-path failures (no DDL replay);
        # attempts reset on a successful checkpoint
        self.recoveries = 0
        self._recovery_attempts = 0
        # barrier-time skew-routing policy (vnode rebalancing + hot-key
        # replication — the skew defenses that change EXCHANGE routing):
        # decided at checkpoints from the window's skew evidence, pre-
        # warmed in the background, adopted at a later checkpoint via
        # rebuild-replay. Single-chip programs never retune.
        self.rebalance = rebalance and program.mesh is not None
        self.rebalance_threshold = float(rebalance_threshold)
        self.hot_key_rep = hot_key_rep and program.mesh is not None
        self.hot_key_frac = float(hot_key_frac)
        self.rebalances = 0          # adopted policy switches
        self._policy_seq = 0
        # staged policy: (bounds, {node idx: (hot_keys, side)}, ready)
        self._pending_policy: Optional[Tuple] = None
        # data directory (database attaches it): offline skew snapshots
        # land here beside epoch_profile.jsonl
        self.data_dir: Optional[str] = None
        # key stride of the capacity rows: plan-derived (deterministic on
        # recovery), widened past the minimum when a node has more slots
        self._js_stride = max([_JS_CAP_STRIDE]
                              + [len(n.cap_current())
                                 for n in program.nodes])
        self._js_written: Dict[int, int] = {}
        self.counter = 0
        self.committed = 0
        # wall-clock anchor for live eps columns (EXPLAIN ANALYZE)
        import time as _time
        self.t_created = _time.monotonic()
        # source->MV freshness (utils/freshness.py): the Database
        # attaches its tracker; each checkpoint then records
        # commit_wall - dispatch_wall of the OLDEST epoch in the window.
        # For a fused job ingest IS the dispatch — events are generated
        # on device during the epoch, so the dispatch stamp is the
        # moment the epoch's data came into existence.
        self.freshness = None
        self._window_ingest: Optional[float] = None
        self.states = program.init_states()
        self.snapshot = (self.states, 0)
        self._zero_stats = jnp.zeros((max(1, len(program.stat_layout)),),
                                     jnp.int64)
        if program.mesh is not None:
            # sharded epochs emit mesh-replicated stat scalars; the
            # accumulator must live on the same device set
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P
            self._zero_stats = jax.device_put(
                self._zero_stats, NamedSharding(program.mesh, P()))
        self.stats_acc = self._zero_stats
        self._step = program.step_fn()
        # the MV as the state table last took it (mv_mirror.MirrorImage)
        self._persisted = MirrorImage()
        # last device-pulled stats vector (sync) + job-lifetime totals
        # (sum slots accumulate, max slots high-water — _accum_totals):
        # the rw_fused_node_stats / node_report substrate
        self._last_stats = np.zeros(len(self.stats_acc), np.int64)
        self._stat_totals = np.zeros(len(self.stats_acc), np.int64)
        # flow telemetry host side: per-node EWMA over checkpoint-window
        # traffic deltas (burst-vs-sustained discrimination for
        # skew_report's traffic_burst rows), fed at every checkpoint
        # from the cumulative tv* totals
        self._traffic_ewma: Dict[int, Any] = {}

    # ---- barrier protocol ----------------------------------------------
    @property
    def drained(self) -> bool:
        return self.max_events is not None \
            and self.counter >= self.max_events

    def on_barrier(self, barrier) -> None:
        # no span for post-drain barriers: a drained job keeps seeing
        # ticks forever, and zero-event records would evict the real
        # epoch history from the profile ring (sync/commit at a
        # post-drain checkpoint still lands in the phase totals)
        prof = self.profiler if self.profiler.enabled \
            and not self.drained else None
        # overload cadence stretch: dispatch `stretch` epochs under this
        # one barrier (bigger batch per barrier overhead; freshness p99
        # traded against eps, measured by rw_mv_freshness)
        stretch = max(1, int(self.cadence_stretch))
        e = self.program.epoch_events
        planned = stretch * e
        if self.max_events is not None:
            planned = min(planned, max(0, self.max_events - self.counter))
        if prof is not None:
            prof.begin_epoch(self.counter, planned or e,
                             epoch=barrier.epoch.curr)
        # fault-tolerance v3: a device-path failure anywhere in the
        # barrier's work (dispatch, sync, growth replay, commit — real
        # exception or armed fused.* failpoint) recovers IN PLACE and the
        # barrier's remaining work retries. `dispatched` makes the retry
        # idempotent: a failure after the dispatch (e.g. in the
        # checkpoint sync) must not dispatch the epoch twice — recovery
        # already re-dispatched it from the epoch event log.
        dispatched = False
        todo = stretch
        if self.ingest is not None and not self.drained:
            # barrier-time admission refill (the SourceExecutor
            # contract): one token authorizes one window per source; a
            # stretched barrier needs `stretch` or the tail defers
            self.ingest.epoch_refill(stretch)
        while True:
            try:
                if not self.drained and not dispatched:
                    # `todo` survives a mid-stretch device fault: the
                    # recovery replays what WAS logged, the retry then
                    # dispatches only the epochs still owed this barrier
                    while todo > 0 and not self.drained:
                        if not self._dispatch_epoch(prof):
                            # host-ingest window deferred (admission) or
                            # empty: the data stays at the connector —
                            # give the barrier's remaining budget back
                            break
                        todo -= 1
                    dispatched = True
                if barrier.is_checkpoint:
                    self._checkpoint(barrier.epoch.curr)
                break
            except Exception as err:
                if not _is_device_fault(err):
                    raise
                self._recover_in_place(err)
        if prof is not None:
            prof.end_epoch()
        if self.profiler.enabled and barrier.is_checkpoint:
            # flush AFTER end_epoch so the checkpoint epoch's own record
            # (the one carrying device_sync/commit splits) reaches the
            # jsonl now, not one checkpoint later — `risectl profile`
            # against a wedged process must see the newest checkpoint
            self.profiler.flush()

    def _dispatch_epoch(self, prof) -> bool:
        """Dispatch ONE epoch (async) and log it into the epoch event
        log — the coordinator-side record an in-place recovery replays.
        Returns False when a host-ingest window was deferred (admission)
        — nothing was dispatched and the counter did not move."""
        import jax.numpy as jnp
        import time as _time
        if failpoint("fused.dispatch"):
            raise FailpointError("fused.dispatch")
        spans = prof or NULL_PROFILER
        feeds = None
        events = self.program.epoch_events
        ingest_ts = None
        with spans.span("rw:pack") as pack:
            if self.ingest is not None:
                # the staged window at the event counter: pre-packed,
                # pre-transferred by the staging thread when the double
                # buffer is warm — pack/h2d then collapse to the lock
                # wait, which is the whole point (the profiler's
                # evidence surface for the overlap). The h2d wall is the
                # stager's, handed over: it comes out of this span's
                # seconds and goes to its own phase
                w, _pack_s, h2d_s = self.ingest.take(self.counter)
                if w.events <= 0:
                    return False
                if prof is not None and h2d_s > 0.0:
                    pack.excluded += h2d_s
                    prof.phase("h2d", h2d_s)
                feeds, events, ingest_ts = w.feeds, w.events, w.ingest_ts
            if self._window_ingest is None:
                # first dispatch since the last checkpoint: freshness of
                # the NEXT commit is measured against this moment — for
                # ingest jobs the moment the window's rows came off the
                # connector
                self._window_ingest = ingest_ts if ingest_ts is not None \
                    else _time.time()
            elif ingest_ts is not None:
                self._window_ingest = min(self._window_ingest, ingest_ts)
            # a scalar host-to-device put: it blocks when the runtime's
            # queue is full, so it has a span of its own
            with spans.span("rw:event_lo"):
                lo = jnp.int64(self.counter)
        if self.tiering is not None:
            # touch-promotion BEFORE the step: probe the window's keys
            # against the negative caches and restore any cold hits, so
            # the device step always sees a complete working set
            self._tier_promote(self.counter, events, prof)
        # the ICI shuffle's enqueue wall is its own phase (`rw:exchange`
        # inside, FusedProgram.epoch) and comes out of this one's seconds
        with spans.span("rw:dispatch"):
            self.states, self.stats_acc = self._step(
                self.states, lo, self.stats_acc, feeds=feeds)
        self._epoch_log.append(self.counter, events)
        self.counter += events
        return True

    def _recover_in_place(self, err: BaseException) -> None:
        """In-place recovery from a device-path failure: NO DDL-replay
        restart. Rebuild program state from the last checkpointed state
        tables' committed view (the event counter + capacity high-water
        marks are already live on this job — `recover()` presized them at
        open), then re-dispatch the retained crash-window epochs from the
        coordinator-side epoch event log. Every node signature and
        capacity is unchanged, so the whole rebuild dispatches on the
        AOT-cached executables — ZERO fresh compiles — and deterministic
        sources regenerate bit-identical state. Bounded attempts
        (`RW_FUSED_RECOVERY_ATTEMPTS`); past the bound the original error
        propagates and the classic DDL-replay recovery takes over."""
        import time as _time
        from ..config import ROBUSTNESS
        from ..utils.metrics import REGISTRY
        self._recovery_attempts += 1
        if self._recovery_attempts > max(1, ROBUSTNESS.fused_recovery_attempts):
            raise err
        t_rec = _time.perf_counter()
        target = self.committed
        # the full retained window — spilled prefix reloaded from disk
        # plus the in-memory tail (the epoch-log byte bound's contract)
        window = self._epoch_log.entries()
        # the log must be contiguous from the committed counter — a torn
        # log cannot be replayed exactly, so escalate instead of guessing
        expect = target
        for lo, ev in window:
            if lo != expect:
                raise err
            expect += ev
        # rebuild: empty state at the CURRENT (>= persisted high-water)
        # capacities, regenerate the checkpointed history device-side,
        # re-anchor the growth snapshot at the checkpoint, then replay
        # the crash window — the same barrier boundaries, so the MV is
        # bit-identical to an undisturbed run
        self.states = self.program.init_states()
        self.stats_acc = self._zero_stats
        self.counter = 0
        if self.tiering is not None:
            self.tiering.reset_stores()
            self._promo_need = {}
        if target:
            self._replay_history(target)
            self.counter = target
            self.sync()
        self.snapshot = (self.states, target)
        if self.tiering is not None:
            # cold snapshot BEFORE the crash window: its promotions must
            # rewind with the device snapshot on a later growth replay
            self._cold_snapshot = self.tiering.snapshot()
        self.stats_acc = self._zero_stats
        if expect > target:
            self._dispatch_range(target, expect)
            self.counter = expect
        self.recoveries += 1
        REGISTRY.counter(
            "fused_recoveries_total",
            "in-place fused-job recoveries (device-path failures healed "
            "without a DDL-replay restart)",
            labels=("job",)).labels(self.name).inc()
        REGISTRY.histogram(
            "fused_recovery_seconds",
            "wall seconds one in-place fused recovery took").observe(
            _time.perf_counter() - t_rec)
        from ..utils.blackbox import RECORDER
        RECORDER.record("recovery", {
            "job": self.name, "attempt": self._recovery_attempts,
            "replayed_epochs": int(expect - target),
            "error": type(err).__name__,
            "wall_s": round(_time.perf_counter() - t_rec, 4)})
        RECORDER.maybe_dump("in_place_recovery")

    # ---- sync / growth / replay ----------------------------------------
    def _dispatch_range(self, lo: int, hi: int) -> None:
        """Replay/recovery epochs are PURE device dispatch: the epoch's
        event_lo advances as a device-side scalar add instead of a fresh
        host->device transfer per epoch (one host sync each),
        and no per-epoch host work (stats pulls, MV mirroring, tracer
        spans) happens until the terminal sync/checkpoint.

        Host-ingest jobs replay through the stager instead: retained
        windows re-pack verbatim, committed history re-derives from the
        sources' deterministic range contract (`HostIngest.replay_range`)
        — the staged-window replay the epoch event log promises."""
        import jax.numpy as jnp
        if self.ingest is not None:
            for wlo, _ev, feeds in self.ingest.replay_range(lo, hi):
                if self.tiering is not None:
                    # replayed windows promote exactly like live ones
                    # (window-boundary independent — a re-cut cadence
                    # still meets every key before its step)
                    self._tier_promote(wlo, _ev, None)
                self.states, self.stats_acc = self._step(
                    self.states, jnp.int64(wlo), self.stats_acc,
                    feeds=feeds)
            return
        e = self.program.epoch_events
        lo_dev = jnp.int64(lo)
        c = lo
        while c < hi:
            self.states, self.stats_acc = self._step(
                self.states, lo_dev, self.stats_acc)
            lo_dev = lo_dev + e
            c += e

    def _predict_caps(self, needs: Dict[int, Dict[str, int]],
                      needs_cum: Optional[Dict[int, Dict[str, int]]] = None,
                      needs_epoch: Optional[Dict[int, Dict[str, int]]] = None
                      ) -> Dict[int, Dict[str, int]]:
        """Bucketed capacity targets for EVERY node (cascade-free): each
        slot's CUMULATIVE component is sized from its observed
        entries-per-event rate extrapolated over max_events, its
        PER-EPOCH component (join pair buffers, agg `touched`) gets flat
        headroom instead of horizon scaling, and everything is scaled
        down toward the observed need when the summed projection exceeds
        the HBM budget (correctness floor: never below need or
        current). Without the split views (legacy callers), the whole
        need extrapolates — the pre-ISSUE-6 behavior."""
        from .capacity import project, project_epoch
        if not self.predictive:
            out: Dict[int, Dict[str, int]] = {}
            for i, node in enumerate(self.program.nodes):
                cur = node.cap_current()
                nd = needs.get(i) or {}
                grown = {s: _bucket(nd[s], lo=cur[s] * 2)
                         for s in cur if nd.get(s, 0) > cur[s]}
                if grown:
                    out[i] = grown
            return out
        events = max(1, self.counter)
        plans = []           # [node, slot, need, current, bytes/slot, proj]
        for i, node in enumerate(self.program.nodes):
            cur = node.cap_current()
            if not cur:
                continue
            bpe = node.cap_bytes()
            nd = needs.get(i) or {}
            ndc = (needs_cum or {}).get(i) if needs_cum is not None else nd
            nde = (needs_epoch or {}).get(i) or {}
            for s, c in cur.items():
                n = nd.get(s, 0)
                cum = (ndc or {}).get(s, 0)
                p = max(c, n, project(cum, events, self.max_events),
                        project_epoch(nde.get(s, 0)))
                plans.append([i, s, n, c, bpe.get(s, 16), p])
        budget = self.hbm_budget_mb << 20
        total = sum(_bucket(p[5]) * p[4] for p in plans)
        if total > budget:
            scale = budget / total
            for p in plans:
                p[5] = max(p[2], p[3], int(p[5] * scale))
        out = {}
        for i, s, n, c, _, p in plans:
            out.setdefault(i, {})[s] = _bucket(max(n, p), lo=c)
        return out

    def sync(self) -> None:
        """Block; verify stats; grow + replay from snapshot when any state
        overflowed its static capacity. The blocking device_get is the
        epoch timeline's `device_sync` phase: it covers every epoch
        dispatched since the last sync (growth replays included)."""
        with self.profiler.span("rw:device_sync"):
            self._sync_inner()

    def _sync_inner(self) -> None:
        import jax
        while True:
            if failpoint("fused.device_sync"):
                raise FailpointError("fused.device_sync")
            # the blocking call of a sync: every epoch enqueued since
            # the last one has to finish before the vector is there
            with self.profiler.span("rw:stats_pull"):
                vec = np.asarray(jax.device_get(self.stats_acc))
            self._last_stats = vec
            for k, (ni, nm) in enumerate(self.program.stat_layout):
                if nm == "packbad" and vec[k] != 0:
                    raise RuntimeError(
                        f"fused job {self.name}: packed-key bounds violated "
                        f"at node {ni} ({type(self.program.nodes[ni]).__name__}"
                        ") — a column left its statically proven range. "
                        "Re-create this MV with device='off'.")
            needs, needs_cum, needs_epoch = {}, {}, {}
            for i, node in enumerate(self.program.nodes):
                st = self.program.node_stats(i, vec)
                needs[i] = node.cap_needs(st)
                needs_cum[i] = node.cap_needs_cum(st)
                needs_epoch[i] = node.cap_needs_epoch(st)
            # promotion merges can truncate too — their host-folded
            # `needed` high-waters join the same overflow/growth check
            for i, nd in self._promo_need.items():
                for s, v in nd.items():
                    if v > needs.get(i, {}).get(s, 0):
                        needs.setdefault(i, {})[s] = v
                    if v > needs_cum.get(i, {}).get(s, 0):
                        needs_cum.setdefault(i, {})[s] = v
            overflow = any(
                needs[i].get(s, 0) > c
                for i, node in enumerate(self.program.nodes)
                for s, c in node.cap_current().items())
            if not overflow:
                # no growth due — but the observed rates now seed the
                # bucket ladder: pre-compile the predicted growth shapes
                # in the background so a later overflow lands on a ready
                # executable instead of a retrace
                self._prewarm_predicted(needs, needs_cum, needs_epoch)
                return
            with self.profiler.span("rw:growth") as growth:
                self._grow_and_replay(
                    self._predict_caps(needs, needs_cum, needs_epoch),
                    growth)

    def _grow_and_replay(self, targets, growth) -> None:
        """The overflow branch of a sync: resize every node that has to
        grow from the last snapshot and replay the window's epochs."""
        snap_states, snap_counter = self.snapshot
        new_states = []
        grew = {}                      # node name -> (caps from, caps to)
        for i, node in enumerate(self.program.nodes):
            cur = node.cap_current()
            want = targets.get(i) or {}
            grown = {s: want[s] for s in want if want[s] > cur.get(s, 0)}
            if grown:
                self.retraces += 1
                self.growths += len(grown)
                grew[self.program.node_names[i]] = (
                    {s: cur.get(s, 0) for s in grown}, grown)
                # the grown node's next step call re-traces: flag it so
                # the profiler attributes that wall to compile, not
                # steady-state dispatch
                self.profiler.pending_compile[i] = "retrace"
                new_states.append(self.program.resize_state(
                    i, snap_states[i], grown))
            else:
                new_states.append(snap_states[i])
        growth.set(nodes=sorted(grew),
                   **{"from": {n: f for n, (f, _t) in grew.items()},
                      "to": {n: t for n, (_f, t) in grew.items()}})
        self.growth_replays += 1
        if failpoint("fused.growth_replay"):
            raise FailpointError("fused.growth_replay")
        target = self.counter
        self.states = tuple(new_states)
        self.snapshot = (self.states, snap_counter)
        self.counter = snap_counter
        self.stats_acc = self._zero_stats
        if self.tiering is not None and self._cold_snapshot is not None:
            # rewind the cold tier to the same commit point: window
            # promotions popped rows out of the stores, and the
            # replay below will promote them again. No journal
            # re-enactment is due — demotions only happen at
            # checkpoint commits, i.e. at snap_counter itself.
            self.tiering.restore(self._cold_snapshot)
        self._promo_need = {}
        self._dispatch_range(snap_counter, target)
        self.counter = target

    def _job_state_rows(self) -> List[Tuple[int, int]]:
        """Growth counters + per-node capacity high-water marks, in the
        job-state key schema (see _JS_*)."""
        rows = [(_JS_REPLAYS, self.growth_replays),
                (_JS_RETRACES, self.retraces),
                (_JS_GROWTHS, self.growths),
                (_JS_REBALANCES, self.rebalances)]
        stride = self._js_stride
        for i, node in enumerate(self.program.nodes):
            cur = node.cap_current()
            for si, s in enumerate(sorted(cur)):
                rows.append((_JS_CAP_BASE + i * stride + si, cur[s]))
        return rows

    # ---- tiered state (cold demotion + touch-promotion) ----------------
    def _tier_journal(self):
        """The TieringManager with its journal path bound (lazy — the
        Database attaches data_dir after construction)."""
        import os
        tm = self.tiering
        if tm is not None and tm.journal_path is None \
                and self.data_dir is not None:
            tm.set_journal_path(os.path.join(
                self.data_dir, f"tiering_journal_{self.name}.jsonl"))
        return tm

    def _lead(self, x) -> np.ndarray:
        """Host view of a device leaf, normalized to a leading shard
        axis ([1, ...] single-chip)."""
        a = np.asarray(x)
        return a if self.mesh_shards > 1 else a[None]

    def _set_state(self, i: int, st) -> None:
        """Install a surgery output as node i's state. Vmapped surgery
        outputs land unsharded — re-place them under the mesh sharding
        so the next step call sees the layout it was traced for."""
        if self.program.mesh is not None:
            import jax
            from ..parallel.mesh import state_sharding
            sh = state_sharding(self.program.mesh)
            st = jax.tree_util.tree_map(
                lambda x: jax.device_put(x, sh), st)
        states = list(self.states)
        states[i] = st
        self.states = tuple(states)

    def _fold_promo(self, i: int, slot: str, need) -> None:
        """Promotion-merge truncation high-water (host-side — see
        __init__); joins the next sync's overflow check."""
        need = int(need)
        if need <= 0:
            return
        d = self._promo_need.setdefault(i, {})
        if need > d.get(slot, 0):
            d[slot] = need

    def _probe_counters(self, store, shard: int, cand: np.ndarray):
        """One negative-cache probe with the counter bookkeeping."""
        tm = self.tiering
        hits, probes, positives = store.probe(shard, cand)
        tm.counters["filter_probes"] += probes
        tm.counters["filter_hits"] += positives
        if probes and not store.filter_live[shard]:
            # Xor8.build failed (or no filter yet): every candidate
            # paid the dict lookup — correct, just not cheap
            tm.counters["filter_fallbacks"] += probes
        return hits

    def _tier_promote(self, lo: int, events: int, prof) -> None:
        """Touch-promotion for the window at `lo`: derive each tiered
        node's candidate keys from the window's host rows (the recipes'
        lineage walk), probe the per-shard Xor8 negative caches, and
        merge cold hits back into the device tables BEFORE the step —
        the step then sees a complete working set and the MV stays
        bit-identical to the untiered run. Promotion is window-boundary
        independent (any window containing the key restores it first),
        so replays with a re-cut cadence stay exact."""
        tm = self.tiering
        if tm is None or self.ingest is None or not tm.any_cold():
            return
        with (prof or NULL_PROFILER).span("rw:promote_h2d"):
            per_source = None
            for plan in tm.plans:
                if not plan.recipes:
                    continue
                if plan.kind == "agg":
                    if not len(tm.store(plan.node_idx, -1)):
                        continue
                elif not len(tm.store(plan.node_idx, 0)) \
                        and not len(tm.store(plan.node_idx, 1)):
                    continue
                if per_source is None:
                    per_source = self.ingest.host_window(lo, events)
                cand = np.unique(np.concatenate(
                    [r.keys_for(per_source) for r in plan.recipes]))
                if not len(cand):
                    continue
                if plan.kind == "agg":
                    self._promote_agg(plan, cand)
                else:
                    self._promote_join(plan, cand)

    def _promote_agg(self, plan, cand: np.ndarray) -> None:
        import jax
        from .sorted_state import EMPTY_KEY
        from .tiering import _pad_pow2
        tm = self.tiering
        i = plan.node_idx
        store = tm.store(i, -1)
        shards = self.mesh_shards
        hits = [sorted(self._probe_counters(store, s, cand))
                for s in range(shards)]
        nhit = sum(len(h) for h in hits)
        if not nhit:
            return
        node = self.program.nodes[i]
        tstate = self.states[i]
        main = tstate.inner.main
        vdt = [np.dtype(v.dtype) for v in main.vals]
        L = _pad_pow2(max(len(h) for h in hits))
        pkeys = np.full((shards, L), EMPTY_KEY, np.int64)
        pvals = [np.zeros((shards, L), d) for d in vdt]
        ptouch = np.zeros((shards, L), np.int64)
        mvstore = tm.stores.get((i, "mv")) if plan.mv_idx is not None \
            else None
        if mvstore is not None:
            mvst = self.states[plan.mv_idx]
            mdt = [np.dtype(v.dtype) for v in mvst.vals]
            mkeys = np.full((shards, L), EMPTY_KEY, np.int64)
            mvals = [np.zeros((shards, L), d) for d in mdt]
        for s, h in enumerate(hits):
            if not h:
                continue
            # arena gather: one fancy-index slice per payload column
            hk = np.asarray(h, np.int64)
            m = len(hk)
            vcols, tchs = store.take_agg_rows(s, hk)
            pkeys[s, :m] = hk
            ptouch[s, :m] = tchs
            for c in range(len(vdt)):
                pvals[c][s, :m] = vcols[c]
            if mvstore is not None:
                mf, mcols = mvstore.take_flat_rows(s, hk)
                if mf.any():
                    idx = np.nonzero(mf)[0]
                    mkeys[s, idx] = hk[mf]
                    for c in range(len(mdt)):
                        mvals[c][s, idx] = mcols[c]
        tm.counters["promotions"] += nhit

        def shp(a):
            return a if shards > 1 else a[0]
        acc = np.zeros((shards,), np.int64) if shards > 1 \
            else np.int64(0)
        ntstate, nacc = _tier_call(
            "agg_promote", _agg_promote_core, shards,
            (tstate, shp(pkeys), tuple(shp(c) for c in pvals),
             shp(ptouch), acc), {"node": node})
        self._set_state(i, ntstate)
        self._fold_promo(i, "main",
                         np.max(np.asarray(jax.device_get(nacc))))
        if mvstore is not None:
            nst, mnacc = _tier_call(
                "mv_promote", _mv_promote_core, shards,
                (mvst, shp(mkeys), tuple(shp(c) for c in mvals), acc),
                {"node": self.program.nodes[plan.mv_idx]})
            self._set_state(plan.mv_idx, nst)
            self._fold_promo(plan.mv_idx, "main",
                             np.max(np.asarray(jax.device_get(mnacc))))

    def _promote_join(self, plan, cand: np.ndarray) -> None:
        import jax
        from .sorted_state import EMPTY_KEY
        from .tiering import _pad_pow2
        tm = self.tiering
        i = plan.node_idx
        shards = self.mesh_shards
        node = self.program.nodes[i]
        tstate = self.states[i]
        bufs = []
        total = 0
        for side in (0, 1):
            store = tm.store(i, side)
            sd = tstate.inner[side]
            vdt = [np.dtype(v.dtype) for v in sd.vals]
            per_shard = []
            for s in range(shards):
                ks = sorted(self._probe_counters(store, s, cand))
                per_shard.append(store.take_join_rows(s, ks))
            L = _pad_pow2(max(len(t[0]) for t in per_shard))
            jk = np.full((shards, L), EMPTY_KEY, np.int64)
            pk = np.full((shards, L), EMPTY_KEY, np.int64)
            vals = [np.zeros((shards, L), d) for d in vdt]
            tch = np.zeros((shards, L), np.int64)
            for s, (sjk, spk, svals, stch) in enumerate(per_shard):
                m = len(sjk)
                if not m:
                    continue
                # (jk, pk) is a unique pair identity: lexsort == the
                # old per-row stable sort, arena gather is one
                # fancy-index slice per column
                order = np.lexsort((spk, sjk))
                jk[s, :m] = sjk[order]
                pk[s, :m] = spk[order]
                tch[s, :m] = stch[order]
                for c in range(len(vdt)):
                    vals[c][s, :m] = svals[c][order]
                total += m
            bufs.append((jk, pk, tuple(vals), tch))
        if not total:
            return
        tm.counters["promotions"] += total

        def shp(t):
            if shards > 1:
                return t
            jk, pk, vals, tch = t
            return (jk[0], pk[0], tuple(v[0] for v in vals), tch[0])
        z = np.zeros((shards,), np.int64) if shards > 1 else np.int64(0)
        ntstate, (na, nb) = _tier_call(
            "join_promote", _join_promote_core, shards,
            (tstate, shp(bufs[0]), shp(bufs[1]), (z, z)),
            {"node": node})
        self._set_state(i, ntstate)
        self._fold_promo(i, "a", np.max(np.asarray(jax.device_get(na))))
        self._fold_promo(i, "b", np.max(np.asarray(jax.device_get(nb))))

    def _tier_demote_tick(self, prof) -> None:
        """The commit-phase half of demotion, two-phase so the D2H
        never blocks an epoch: HARVEST the recency pull issued at the
        LAST checkpoint (its transfer overlapped this whole window's
        dispatch), select + evict the cold keys it names, then ISSUE
        the next async pull for any node whose window residency
        high-water crossed the high-water fraction of capacity."""
        from .capacity import tier_waters
        from .skew_stats import SK_KEY_MASK, hot_key_set
        from .tiering import select_cold
        tm = self._tier_journal()
        if tm is None:
            return
        with (prof or NULL_PROFILER).span("rw:demote_d2h") as sp:
            did = False
            high, _low = tier_waters()
            vec = np.maximum(self._stat_totals, self._last_stats) \
                if len(self._stat_totals) == len(self._last_stats) \
                else self._last_stats
            for plan in tm.plans:
                if not plan.recipes:
                    continue                   # demotion-inert (stats only)
                i = plan.node_idx
                node = self.program.nodes[i]
                pend = tm.pending.pop(i, None)
                if pend is not None:
                    did = True
                    hot = hot_key_set(self.program.node_stats(i, vec)) \
                        if node.skew else ()
                    sel = []
                    if plan.kind == "agg":
                        keys, touch, count = (self._lead(x) for x in pend)
                        cap = keys.shape[1]
                        for s in range(self.mesh_shards):
                            d = select_cold(keys[s], touch[s],
                                            int(count[s]), cap, hot,
                                            SK_KEY_MASK)
                            if d is not None:
                                sel.append(d)
                    else:
                        ka, ta, ca, kb, tb, cb = (self._lead(x)
                                                  for x in pend)
                        for k, t, c in ((ka, ta, ca), (kb, tb, cb)):
                            cap = k.shape[1]
                            for s in range(self.mesh_shards):
                                d = select_cold(k[s], t[s], int(c[s]), cap,
                                                hot, SK_KEY_MASK)
                                if d is not None:
                                    sel.append(d)
                    if sel:
                        self._tier_demote_enact(
                            plan, np.unique(np.concatenate(sel)),
                            record=True)
                # issue the NEXT pull when the window's residency
                # high-water says pressure (stats already on host — the
                # sync pulled them; no extra device round trip here, the
                # copy below is async by construction)
                st = self.program.node_stats(i, self._last_stats)
                tres = int(st.get("tres", 0))
                tstate = self.states[i]
                if plan.kind == "agg":
                    pressure = tres > high * node.capacity
                    leaves = (tstate.inner.main.keys, tstate.touch,
                              tstate.inner.main.count)
                else:
                    pressure = tres > high * min(node.cap_a, node.cap_b)
                    a, b = tstate.inner
                    ta, tb = tstate.touch
                    leaves = (a.jk, ta, a.count, b.jk, tb, b.count)
                if pressure:
                    did = True
                    for x in leaves:
                        x.copy_to_host_async()
                    tm.pending[i] = leaves
            if not did:
                sp.no_phase()      # nothing harvested, nothing issued

    def _tier_demote_enact(self, plan, keys: np.ndarray,
                           record: bool) -> None:
        """Evict `keys` from the device table(s) into the cold store
        (exact payload + touch stamp), rebuild the negative caches, and
        journal the event. The selection may be stale (it came from the
        previous checkpoint's pull) — the evict cores report `found`
        per key, and only found rows move, so a key promoted or died
        since selection is simply skipped. With record=False this
        re-enacts a journaled event during a history replay."""
        import jax
        from .sorted_state import EMPTY_KEY
        from .tiering import _pad_pow2
        tm = self.tiering
        i = plan.node_idx
        node = self.program.nodes[i]
        shards = self.mesh_shards
        keys = np.unique(np.asarray(keys, dtype=np.int64))
        if not len(keys):
            return
        dbuf = np.full((_pad_pow2(len(keys)),), EMPTY_KEY, np.int64)
        dbuf[:len(keys)] = keys
        stored = 0
        if plan.kind == "agg":
            ntstate, found, dvals, dtouch = _tier_call(
                "agg_evict", _agg_evict_core, shards,
                (self.states[i], dbuf), {"node": node})
            self._set_state(i, ntstate)
            fnd = self._lead(jax.device_get(found))
            dvs = [self._lead(v) for v in jax.device_get(list(dvals))]
            dts = self._lead(jax.device_get(dtouch))
            store = tm.store(i, -1)
            for s in range(shards):
                idx = np.nonzero(fnd[s])[0]
                if len(idx):
                    # arena append: one slice-assign per payload column
                    store.put_agg_rows(s, dbuf[idx],
                                       [v[s, idx] for v in dvs],
                                       dts[s, idx])
                    stored += len(idx)
                store.rebuild_filter(s)
            if plan.mv_idx is not None:
                # lockstep MV demotion: the SAME groups leave the
                # terminal MV table, merged back at SELECT time
                # (_tier_merge_mv_rows) or on promotion
                nst, mfnd, mdvals = _tier_call(
                    "mv_evict", _mv_evict_core, shards,
                    (self.states[plan.mv_idx], dbuf),
                    {"node": self.program.nodes[plan.mv_idx]})
                self._set_state(plan.mv_idx, nst)
                mf = self._lead(jax.device_get(mfnd))
                mdv = [self._lead(v)
                       for v in jax.device_get(list(mdvals))]
                mstore = tm.store(i, "mv")
                for s in range(shards):
                    idx = np.nonzero(mf[s])[0]
                    if len(idx):
                        mstore.put_flat_rows(s, dbuf[idx],
                                             [v[s, idx] for v in mdv])
                    # no filter rebuild: the MV store is only ever
                    # probed in lockstep by its agg's hit keys
        else:
            tstate = self.states[i]
            for side in (0, 1):
                out = _tier_call(
                    "join_evict", _join_evict_core, shards,
                    (tstate, dbuf), {"node": node, "side": side})
                tstate, djk, dpk, dvals, dtouch, ndem = out
                jks = self._lead(jax.device_get(djk))
                pks = self._lead(jax.device_get(dpk))
                dvs = [self._lead(v)
                       for v in jax.device_get(list(dvals))]
                dts = self._lead(jax.device_get(dtouch))
                nd = self._lead(jax.device_get(ndem))
                store = tm.store(i, side)
                for s in range(shards):
                    n = int(nd[s])
                    if n:
                        store.extend_join_rows(
                            s, jks[s, :n], pks[s, :n],
                            [v[s, :n] for v in dvs], dts[s, :n])
                    stored += n
                    store.rebuild_filter(s)
            self._set_state(i, tstate)
        if record:
            tm.record(self.counter, i, -1, keys)
            tm.counters["demote_events"] += 1
            tm.counters["demotions"] += stored

    def _replay_history(self, target: int) -> None:
        """From-zero history regeneration with tier re-enactment: split
        the committed range at the journal's demotion counters and
        re-enact each event in place — payloads regenerate from the
        replayed (deterministic) state, so BOTH tiers rebuild
        bit-identically. Falls back to a plain dispatch when untiered
        or nothing was ever demoted."""
        if target <= 0:
            return
        tm = self.tiering
        events = tm.events_between(0, target) if tm is not None else []
        if not events:
            self._dispatch_range(0, target)
            return
        plans = {p.node_idx: p for p in tm.plans}
        lo = 0
        for c, evs in events:
            if c > lo:
                self._dispatch_range(lo, c)
                lo = c
            for n, _side, keys in evs:
                p = plans.get(n)
                if p is not None:
                    self._tier_demote_enact(
                        p, np.asarray(keys, np.int64), record=False)
        if target > lo:
            self._dispatch_range(lo, target)

    def _tier_merge_mv_rows(self, keys, cols, nulls):
        """SELECT-time merge of the terminal MV's cold rows with the
        device pull, in ascending-key order — packed keys are globally
        unique across tiers AND shards, so the merged order is exactly
        the untiered pull's order."""
        tm = self.tiering
        store = None
        for p in tm.plans:
            if p.mv_idx == self.pull.node_idx:
                store = tm.stores.get((p.node_idx, "mv"))
        if store is None or not len(store):
            return keys, cols, nulls
        # arena gather: each shard's demoted rows come back as column
        # views (no per-key dict walk), cast to the pull's dtypes
        parts = [store.flat_columns(s) for s in range(len(store.rows))]
        parts = [(k, cs) for k, cs in parts if len(k)]
        keys = np.asarray(keys)
        cols = [np.asarray(c) for c in cols]
        nulls = [np.asarray(nl) for nl in nulls]
        ckeys = np.concatenate([k for k, _ in parts]).astype(np.int64)
        keys_all = np.concatenate([keys, ckeys])
        order = np.argsort(keys_all, kind="stable")
        ncalls = len(cols)
        out_cols, out_nulls = [], []
        for j in range(ncalls):
            cc = np.concatenate(
                [cs[1 + 2 * j] for _, cs in parts]).astype(
                cols[j].dtype, copy=False)
            cn = np.concatenate(
                [cs[2 + 2 * j] for _, cs in parts]).astype(
                nulls[j].dtype, copy=False)
            out_cols.append(np.concatenate([cols[j], cc])[order])
            out_nulls.append(np.concatenate([nulls[j], cn])[order])
        return keys_all[order], out_cols, out_nulls

    def tiering_report(self) -> List[Tuple]:
        """Rows for `rw_state_tiering` / `risectl tiering`: per tiered
        node (node, kind, resident high-water, cold rows, filter live,
        promotable) + the job-wide demotion/promotion/filter counters
        repeated on every row (the rw_key_skew flat-row pattern)."""
        tm = self.tiering
        if tm is None:
            return []
        vec = np.maximum(self._stat_totals, self._last_stats) \
            if len(self._stat_totals) == len(self._last_stats) \
            else self._last_stats
        resident = {
            p.node_idx:
                self.program.node_stats(p.node_idx, vec).get("tres", 0)
            for p in tm.plans}
        c = tm.counters
        tail = (c["demotions"], c["promotions"], c["demote_events"],
                c["filter_probes"], c["filter_hits"],
                c["filter_fallbacks"])
        return [row + tail
                for row in tm.report_rows(self.program.nodes, resident)]

    def _checkpoint(self, epoch: int) -> None:
        import time as _time
        self.sync()
        # fold the checkpoint window's stats into job-lifetime totals
        # BEFORE the accumulator resets (sum slots add, max slots
        # high-water — mirrors the device-side combine). Unconditional:
        # the vector was pulled by the sync regardless, and the
        # rw_fused_node_stats surface must stay truthful with the
        # profiler off
        self._accum_totals(self._last_stats)
        prof = self.profiler if self.profiler.enabled else None
        spans = prof or NULL_PROFILER
        # the end of this span is when events [seq_from, seq_to) became
        # durable
        with spans.span("rw:commit", seq_from=self.committed,
                        seq_to=self.counter):
            due = self.counter != self._last_persist and (
                self.drained
                or self.counter - max(0, self._last_persist)
                >= self.persist_every * self.program.epoch_events)
            if due:
                self._persist_mv(epoch)
                self._last_persist = self.counter
            if failpoint("fused.checkpoint_commit"):
                raise FailpointError("fused.checkpoint_commit")
            if self.job_state_table is not None:
                with spans.span("rw:commit.job_state"):
                    dirty = False
                    if self.committed != self.counter or self.committed == 0:
                        self.job_state_table.insert(
                            (_JS_COUNTER, self.counter))
                        dirty = True
                    for k, v in self._job_state_rows():
                        if self._js_written.get(k) != v:
                            self.job_state_table.insert((k, v))
                            self._js_written[k] = v
                            dirty = True
                    if dirty:
                        self.job_state_table.commit(epoch)
            if prof is not None:
                with prof.span("rw:commit.gauges") as gauges:
                    self._export_hbm_gauges()
                    gauges.set(flow_report=self.flow_report())
                    if self.program.mesh is not None:
                        gauges.set(shard_report=self.shard_report())
        if self.freshness is not None and self._window_ingest is not None:
            # end-to-end staleness of this commit: the oldest epoch in
            # the checkpoint window was dispatched (= its events came
            # into existence) at _window_ingest; everything up to the
            # verified sync + state-table commit is inside the measure
            self.freshness.commit(self.name, epoch, self._window_ingest,
                                  _time.time())
        self._window_ingest = None
        # cold demotion rides the commit phase: harvest the D2H pull
        # issued at the LAST checkpoint (it overlapped the whole
        # window's dispatch), evict the selected cold keys, then issue
        # the next pull if this window's residency crossed high-water
        self._tier_demote_tick(prof)
        self.snapshot = (self.states, self.counter)
        if self.tiering is not None:
            self._cold_snapshot = self.tiering.snapshot()
        self._promo_need = {}
        self.stats_acc = self._zero_stats
        self.committed = self.counter
        # the checkpoint closed the window: trim the epoch event log and
        # reset the in-place recovery attempt budget (attempts bound
        # failures per window, not per job lifetime)
        self._epoch_log.clear()
        if self.ingest is not None:
            # committed windows are durable — drop their retained host
            # arrays (the crash-window retention contract)
            self.ingest.trim(self.committed)
        self._recovery_attempts = 0
        # flow telemetry: fold this window's traffic into the per-node
        # EWMA rings (burst-vs-sustained), then leave a checkpoint
        # breadcrumb in the flight recorder (tiering counters ride it
        # when armed — evidence, not policy)
        self._update_traffic_ewma()
        from ..utils.blackbox import RECORDER
        rec: Dict[str, Any] = {"job": self.name, "epoch": int(epoch),
                               "events": int(self.counter)}
        if self.tiering is not None:
            rec["tiering"] = {k: int(v)
                              for k, v in self.tiering.counters.items()}
        RECORDER.record("checkpoint", rec)
        # skew defenses that change exchange routing adopt HERE — the
        # only point where committed == counter and the whole history is
        # deterministically replayable under the new policy
        self._maybe_retune(epoch)
        self._write_skew_snapshot()

    # ---- MV materialization --------------------------------------------
    def _pull_need(self) -> int:
        """Live-row high-water of the terminal MV node (per shard): the
        max of the job-lifetime totals and the current window — the
        window vector resets at checkpoints, so a post-drain SELECT
        must read the lifetime high-water."""
        vec = np.maximum(self._stat_totals, self._last_stats) \
            if len(self._stat_totals) == len(self._last_stats) \
            else self._last_stats
        return self.program.node_stats(
            self.pull.node_idx, vec).get("needed", 0)

    def _pull_rows(self) -> List[Tuple]:
        """The MV's rows as tuples: the columnar pull, each column
        formatted whole, rows from `zip`."""
        return self._pull_cols().rows()

    def _pull_cols(self) -> MVColumns:
        """The MV as host columns (per MV column the values in the
        device's own domain and the null mask). The columns that are not
        numbers on the host (VARCHAR: a surrogate the generator's pools
        turn back into the string) are decoded here, inside
        `rw:commit.mirror.decode` (`rows`, `string_cols`): under the
        mirror's pull and under a SELECT's, what a string column costs is
        a span of its own. An all-number MV leaves none."""
        import jax
        mesh = self.program.mesh
        if mesh is None:
            # mesh pulls count inside merge_*_pull (replica-aware); the
            # single-chip device_get below is one pull all the same —
            # the serving cache's coalescing assertion reads one counter
            from .shard_exec import _count_pull
            _count_pull()
        if self.pull.kind == "keyed":
            from .materialize import mv_rows
            st = self.states[self.pull.node_idx]
            dts = [c.acc_dtype for c in self.pull.agg.spec.calls]
            if mesh is not None:
                # per-shard sorted runs merge by ascending packed key —
                # keys are globally unique (each lives on its vnode's
                # shard), so the merged order IS the 1-shard order. The
                # merge is an IN-PROGRAM all_gather + device-side live
                # compaction: ONE device_get per SELECT regardless of
                # shard count (the bound comes from the "needed" stat
                # the sync already pulled; a stale bound falls back to
                # the capacity-sliced second pull inside)
                from .shard_exec import merge_keyed_pull
                keys, cols, nulls = merge_keyed_pull(
                    st, mesh, dts,
                    live_bound=self._pull_need() * self.mesh_shards)
            else:
                keys, cols, nulls = mv_rows(st, dts)
            if self.tiering is not None:
                # demoted groups live in the host cold store — merge
                # them back in key order so the result is bit-identical
                # (row order included) to the untiered pull
                keys, cols, nulls = self._tier_merge_mv_rows(
                    keys, cols, nulls)
            gcols_np = _np_unpack(self.pull.agg.pack, keys)
            pulled = [(gcols_np[j], None) if kind == "g"
                      else (cols[j], nulls[j])
                      for kind, j in self.pull.out_map]
            n = len(keys)
        else:
            side = self.states[self.pull.node_idx]
            if mesh is not None:
                from .shard_exec import merge_pair_pull
                n, vals = merge_pair_pull(
                    side, mesh,
                    live_bound=self._pull_need() * self.mesh_shards)
            else:
                n = int(side.count)
                vals = jax.device_get([v[:n] if hasattr(v, "shape") else v
                                       for v in side.vals])
            pulled = [(vals[i], None)
                      for i in range(len(self.pull.dtypes))]
        cols = MVColumns(self.pull.dtypes, self.pull.decoders, pulled, n)
        if cols.strings:
            with self.profiler.span("rw:commit.mirror.decode", rows=n,
                                    string_cols=len(cols.strings)):
                cols.decode()
        return cols

    def mv_rows_now(self) -> List[Tuple]:
        """Query serving: sync and pull the CURRENT MV rows (full schema,
        hidden stream-key columns included). A device fault during the
        SELECT's sync routes through the same `_is_device_fault` ->
        `_recover_in_place` path as the barrier loop and the query
        retries — a transient device fault must not surface an
        XlaRuntimeError to pgwire (the PR 12 SELECT-path residual)."""
        while True:
            try:
                self.sync()
                break
            except Exception as e:
                if not _is_device_fault(e):
                    raise
                # bounded by RW_FUSED_RECOVERY_ATTEMPTS: past the bound
                # _recover_in_place re-raises and the error surfaces
                self._recover_in_place(e)
        return self._pull_rows()

    def mv_rows_versioned(self) -> Tuple[int, List[Tuple]]:
        """`mv_rows_now` stamped with the committed epoch it reflects —
        the serving cache's fill primitive. A pull that loses the race
        with a barrier commit (another thread advances `committed`
        mid-pull) could return a torn pre/post-commit mix of shards, so
        the loop re-reads the epoch around the pull and retries against
        the new epoch until one pull lands entirely within a commit
        window. The stamp is the epoch COUNTER (every dispatched epoch
        changes the MV; commits only seal them), checked alongside
        `committed` so a mid-pull commit also retries."""
        while True:
            c0, e0 = self.counter, self.committed
            rows = self.mv_rows_now()
            if self.counter == c0 and self.committed == e0:
                return int(c0), rows

    def _persist_mv(self, epoch: int) -> None:
        """Diff the pulled MV against the last persisted image and write
        the change into the MV state table (checkpoint visibility for
        non-device readers + the recovery contract's committed view)."""
        if self.mv_state_table is None:
            return
        span = self.profiler.span
        table = self.mv_state_table
        with span("rw:commit.mirror") as mirror:
            with span("rw:commit.mirror.pull"):
                cols = self._pull_cols()
            with span("rw:commit.mirror.diff"):
                # a row that changed is put under the key it had: what
                # delete(old) then insert(new) left
                self._persisted, keys, rows, counts = mirror_batch(
                    self._persisted, cols, table)
                table.write_batch(keys, rows,
                                  ascending=not counts["deleted"])
            mirror.set(**counts)
            with span("rw:commit.mirror.table_commit"):
                table.commit(epoch)

    # ---- recovery -------------------------------------------------------
    def recover(self) -> None:
        """Deterministic-source recovery: restore the committed event
        counter, presize every node from its persisted capacity high-water
        mark (the replay then performs ZERO growth replays), and
        regenerate state device-side (offset rewind)."""
        # a fresh process must not splice a crashed predecessor's spilled
        # epoch-log tail into its own window
        self._epoch_log.clear()
        if self.job_state_table is None:
            return
        rows: Dict[int, int] = {}
        for row in self.job_state_table.iter_all():
            k = int(row[0])
            rows[k] = max(rows.get(k, 0), int(row[1]))
        target = rows.get(_JS_COUNTER, 0)
        # growth counters are cumulative across restarts
        self.growth_replays = rows.get(_JS_REPLAYS, 0)
        self.retraces = rows.get(_JS_RETRACES, 0)
        self.growths = rows.get(_JS_GROWTHS, 0)
        self.rebalances = rows.get(_JS_REBALANCES, 0)
        # skew-routing policy must reinstall BEFORE the replay: the
        # persisted capacities were sized under it
        self._policy_seq = rows.get(_JS_POLICY_SEQ, 0)
        if self._policy_seq and self.program.mesh is not None:
            self._restore_policy(rows)
        preset = False
        for i, node in enumerate(self.program.nodes):
            cur = node.cap_current()
            caps = {}
            for si, s in enumerate(sorted(cur)):
                v = rows.get(_JS_CAP_BASE + i * self._js_stride + si, 0)
                if v > cur[s]:
                    caps[s] = v
            if caps:
                node.preset_caps(caps)
                preset = True
        self._js_written = {k: v for k, v in rows.items() if k != _JS_COUNTER}
        if preset:
            # nothing dispatched yet — rebuild empty state at full size
            self.states = self.program.init_states()
            self.snapshot = (self.states, 0)
        tm = self._tier_journal()
        if target == 0:
            if tm is not None:
                # a crashed predecessor's journal is stale history — the
                # state tables say nothing committed, so neither tier did
                tm.clear_journal()
            return
        if tm is not None:
            # the demotion journal is the cold tier's redo log: load it,
            # drop any torn tail past the committed counter, and let the
            # replay re-enact each event at its recorded position —
            # payloads regenerate from the (deterministic) replayed
            # state, so both tiers rebuild bit-identically
            tm.load_journal()
            tm.truncate_journal(target)
            tm.reset_stores()
        self._replay_history(target)
        self.counter = target
        self.sync()
        # the replay's pulled stats seed the job-lifetime totals — the
        # rw_fused_node_stats / rw_key_skew surfaces are truthful right
        # after recovery, not one checkpoint later
        self._accum_totals(self._last_stats)
        self.snapshot = (self.states, target)
        if tm is not None:
            self._cold_snapshot = tm.snapshot()
        self.stats_acc = self._zero_stats
        self._promo_need = {}
        self.committed = target
        if self.mv_state_table is not None:
            # the table's keys stand, its values are not read back: the
            # next mirror puts every row again and tombstones every key
            # the pull lacks, through the same diff
            table = self.mv_state_table
            self._persisted = MirrorImage.of_keys(
                [table.key_of(r) for r in table.iter_all()])
        self._last_persist = -1     # mirror may be stale: refresh next ckpt

    # ---- skew-routing policy (vnode rebalance + hot-key replication) ----
    def _current_bounds(self) -> Tuple[int, ...]:
        """The vnode-block bounds the exchange currently routes by."""
        from ..core.vnode import VNODE_COUNT
        from ..parallel.mesh import vnode_block_bounds
        if self.program.vnode_bounds is not None:
            return self.program.vnode_bounds
        return tuple(int(v) for v in vnode_block_bounds(
            self.mesh_shards, VNODE_COUNT))

    def _maybe_retune(self, epoch: int) -> None:
        """Checkpoint-time skew-policy loop: read the window's skew
        evidence (vnode-occupancy histograms, heavy-hitter counters —
        already on host from the sync), decide whether routing should
        change (rebalanced vnode-block bounds and/or per-join hot-key
        sets), PRE-WARM the re-routed exchange executables in the
        background, and adopt a staged policy at the first checkpoint
        that finds its pre-warm finished. Node-step executables are
        untouched by design (routing never enters `_mut_sig`), so the
        whole switch is zero-fresh-compile."""
        if self.program.mesh is None \
                or not (self.rebalance or self.hot_key_rep):
            return
        if self._pending_policy is not None:
            bounds, hot_map, ready = self._pending_policy
            if ready.is_set():
                self._pending_policy = None
                self._apply_policy(epoch, bounds, hot_map)
            return
        from .skew_stats import (SK_BUCKETS, SK_TOPK, balanced_bounds,
                                 shard_skew_ratio, unpack_hot)
        # lifetime high-water evidence, not just the last checkpoint
        # window: occupancy/heavy-hitter slots combine by max, and the
        # window vector zeroes at quiescent (post-drain) checkpoints
        vec = np.maximum(self._stat_totals, self._last_stats) \
            if len(self._stat_totals) == len(self._last_stats) \
            else self._last_stats
        occ_total = [0] * SK_BUCKETS
        hot_map: Dict[int, Tuple[Tuple[int, ...], int]] = {}
        for i, node in enumerate(self.program.nodes):
            if not node.skew or node.exch is None:
                continue
            st = self.program.node_stats(i, vec)
            for b in range(SK_BUCKETS):
                occ_total[b] += st.get(f"skv{b}", 0)
            if self.hot_key_rep and node.hotrep:
                hots = []
                for r in range(SK_TOPK):
                    key40, cnt = unpack_hot(st.get(f"skh{r}", 0))
                    if cnt >= self.hot_key_frac \
                            * self.program.epoch_events:
                        hots.append(key40)
                hk = tuple(sorted(set(hots)))
                if hk and hk != node.hot_keys:
                    # replicate the SMALLER build side (broadcasting the
                    # dimension-like side is cheap; salting the firehose
                    # side is the win), keep it sticky once chosen
                    side = 0 if st.get("need_a", 0) \
                        <= st.get("need_b", 0) else 1
                    hot_map[i] = (hk, side)
        new_bounds = None
        cur = self._current_bounds()
        if self.rebalance and sum(occ_total) > 0 \
                and shard_skew_ratio(occ_total, cur) \
                > self.rebalance_threshold:
            nb = balanced_bounds(occ_total, self.mesh_shards)
            if nb != cur:
                new_bounds = nb
        if new_bounds is None and not hot_map:
            return
        self._stage_policy(new_bounds or cur, hot_map)

    def _stage_policy(self, bounds: Tuple[int, ...],
                      hot_map: Dict[int, Tuple[Tuple[int, ...], int]]
                      ) -> None:
        """Stage a routing-policy change: compile every re-routed
        exchange program on a background thread (against the avals the
        last epoch actually dispatched), then let a later checkpoint
        adopt it — the AOT-compile-service pattern, applied to the
        exchange seam so the switch itself never compiles."""
        import threading
        from ..core.vnode import VNODE_COUNT
        from ..parallel.mesh import vnode_block_bounds
        mesh = self.program.mesh
        ready = threading.Event()
        # normalize to the exact trace-salt form dispatch will use after
        # adoption: uniform bounds ride as None (the pre-policy salt), so
        # a hot-only policy pre-warms against the bounds it will keep
        uniform = tuple(int(v) for v in vnode_block_bounds(
            self.mesh_shards, VNODE_COUNT))
        salt_bounds = None if tuple(bounds) == uniform else tuple(bounds)
        work = []
        for i, node in enumerate(self.program.nodes):
            if node.exch is None:
                continue
            hk, side = hot_map.get(i, (node.hot_keys, node.hot_rep_side))
            for xi in range(len(node.shard_spec().exchanges)):
                sds = self.program._exch_sds.get((i, xi))
                if sds is not None:
                    work.append((node, xi, sds, hk, side))

        def run():
            from .shard_exec import prewarm_exchange
            for node, xi, sds, hk, side in work:
                try:
                    prewarm_exchange(mesh, node, xi, sds,
                                     bounds=salt_bounds,
                                     hot_keys=hk, hot_rep_side=side)
                except Exception:
                    # pre-warm is advisory: a failed lower falls back to
                    # an inline compile at the switch, never blocks it
                    pass
            ready.set()

        t = threading.Thread(target=run, daemon=True,
                             name=f"rw-skew-prewarm-{self.name}")
        _PREWARM_THREADS[:] = [x for x in _PREWARM_THREADS
                               if x.is_alive()]
        _PREWARM_THREADS.append(t)
        t.start()
        self._pending_policy = (tuple(bounds), hot_map, ready)

    def _apply_policy(self, epoch: int, bounds: Tuple[int, ...],
                      hot_map: Dict[int, Tuple[Tuple[int, ...], int]]
                      ) -> None:
        """Adopt a staged routing policy at this checkpoint: swap the
        bounds/hot-sets, persist them (restart must replay under the
        same routing the capacities were sized for), then rebuild-replay
        — the in-place-recovery maneuver: empty state at current (>=
        high-water) capacities, regenerate the committed history under
        the NEW routing, re-anchor the snapshot. Deterministic sources
        make the result bit-identical; unchanged node signatures make it
        zero-fresh-compile."""
        import time as _time
        from ..core.vnode import VNODE_COUNT
        from ..parallel.mesh import vnode_block_bounds
        from ..utils.metrics import REGISTRY
        t0 = _time.perf_counter()
        uniform = tuple(int(v) for v in vnode_block_bounds(
            self.mesh_shards, VNODE_COUNT))
        self.program.vnode_bounds = None if tuple(bounds) == uniform \
            else tuple(bounds)
        for i, (hk, side) in hot_map.items():
            node = self.program.nodes[i]
            node.hot_keys = tuple(hk)
            node.hot_rep_side = int(side)
        self._policy_seq += 1
        # counted BEFORE persisting: the commit that records policy seq
        # N must also carry rebalances == N's count, or a crash before
        # the next checkpoint under-reports adopted switches
        self.rebalances += 1
        self._persist_policy(epoch)
        target = self.committed
        self.states = self.program.init_states()
        self.stats_acc = self._zero_stats
        self.counter = 0
        self.snapshot = (self.states, 0)
        if self.tiering is not None:
            self.tiering.reset_stores()
            self._promo_need = {}
            self._cold_snapshot = self.tiering.snapshot()
        if target:
            self._replay_history(target)
            self.counter = target
            self.sync()
        self.snapshot = (self.states, target)
        if self.tiering is not None:
            self._cold_snapshot = self.tiering.snapshot()
        self.stats_acc = self._zero_stats
        # the superseded policy's pre-warmed exchange executables are
        # dead weight now — drop them (keyed by node shape, so only
        # this plan's stale salts go)
        from .shard_exec import prune_exchange_aot
        prune_exchange_aot(
            self.program.mesh,
            [(n, self.program.vnode_bounds)
             for n in self.program.nodes if n.exch is not None])
        REGISTRY.counter(
            "fused_rebalances_total",
            "checkpoint-time skew-routing policy switches (vnode "
            "rebalance / hot-key replication)",
            labels=("job",)).labels(self.name).inc()
        REGISTRY.histogram(
            "fused_rebalance_seconds",
            "wall seconds one skew-policy rebuild-replay took").observe(
            _time.perf_counter() - t0)
        from ..utils.blackbox import RECORDER
        RECORDER.record("rebalance", {
            "job": self.name, "epoch": int(epoch),
            "policy_seq": self._policy_seq,
            "bounds": [int(b) for b in bounds],
            "hot_nodes": sorted(int(i) for i in hot_map),
            "wall_s": round(_time.perf_counter() - t0, 4)})

    def _persist_policy(self, epoch: int) -> None:
        """Write the routing policy into the job state table (versioned
        values — see the _JS_* schema note). Every slot rewrites on
        every change so recovery's max-combine always reconstructs one
        consistent policy generation."""
        if self.job_state_table is None:
            return
        from .skew_stats import SK_KEY_MASK, SK_TOPK
        seq = self._policy_seq
        rows = [(_JS_POLICY_SEQ, seq),
                (_JS_REBALANCES, self.rebalances)]
        n = self.mesh_shards
        bounds = self._current_bounds()
        if 0 < n - 1 <= _JS_VB_MAX:
            for s in range(n - 1):
                rows.append((_JS_VB_BASE + s,
                             (seq << 16) | int(bounds[s + 1])))
        for i, node in enumerate(self.program.nodes):
            if not node.hotrep:
                continue
            base = _JS_HOT_BASE + i * (SK_TOPK + 1)
            for r in range(SK_TOPK):
                v = seq << 41
                if r < len(node.hot_keys):
                    v |= ((node.hot_keys[r] & SK_KEY_MASK) << 1) | 1
                rows.append((base + r, v))
            rows.append((base + SK_TOPK,
                         (seq << 2) | (int(node.hot_rep_side) << 1) | 1))
        dirty = False
        for k, v in rows:
            if self._js_written.get(k) != v:
                self.job_state_table.insert((k, v))
                self._js_written[k] = v
                dirty = True
        if dirty:
            self.job_state_table.commit(epoch)

    def _restore_policy(self, rows: Dict[int, int]) -> None:
        """Recovery-side decode of `_persist_policy`'s rows: reinstall
        the routing policy BEFORE the history replay, so the replayed
        exchange routes exactly like the run that sized the persisted
        capacities."""
        from ..core.vnode import VNODE_COUNT
        from ..parallel.mesh import vnode_block_bounds
        from .skew_stats import SK_KEY_MASK, SK_TOPK
        n = self.mesh_shards
        if 0 < n - 1 <= _JS_VB_MAX:
            inner = [rows.get(_JS_VB_BASE + s) for s in range(n - 1)]
            if all(v is not None for v in inner):
                bounds = (0,) + tuple(v & 0xFFFF for v in inner) \
                    + (VNODE_COUNT,)
                if all(bounds[s] <= bounds[s + 1] for s in range(n)) \
                        and bounds[-2] <= VNODE_COUNT:
                    uniform = tuple(int(v) for v in vnode_block_bounds(
                        n, VNODE_COUNT))
                    self.program.vnode_bounds = \
                        None if bounds == uniform else bounds
        for i, node in enumerate(self.program.nodes):
            base = _JS_HOT_BASE + i * (SK_TOPK + 1)
            srow = rows.get(base + SK_TOPK)
            if srow is None or not (srow & 1):
                continue
            node.hot_rep_side = (srow >> 1) & 1
            hots = []
            for r in range(SK_TOPK):
                v = rows.get(base + r, 0)
                if v & 1:
                    hots.append((v >> 1) & SK_KEY_MASK)
            node.hot_keys = tuple(sorted(set(hots)))

    def _write_skew_snapshot(self) -> None:
        """Offline skew surface (`risectl skew`): mirror the rw_key_skew
        rows + routing policy into the data dir at every checkpoint —
        the dead-data-dir contract of epoch_profile.jsonl and
        compile_manifest.json, applied to skew evidence."""
        if not self.data_dir \
                or not any(n.skew or n.flow for n in self.program.nodes):
            return
        import json
        import os
        import time as _time
        path = os.path.join(self.data_dir, SKEW_FILE)
        doc: Dict[str, Any] = {"jobs": {}}
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            pass
        doc.setdefault("jobs", {})
        doc["jobs"][self.name] = {
            "ts": _time.time(),
            "epoch_events": self.program.epoch_events,
            "mesh_shards": self.mesh_shards,
            "committed_events": self.committed,
            "vnode_bounds": (list(self._current_bounds())
                             if self.program.mesh is not None else None),
            "rebalances": self.rebalances,
            "rows": [list(r) for r in self.skew_report()],
        }
        tmp = path + ".tmp"
        try:
            with open(tmp, "w") as f:
                json.dump(doc, f, indent=1, sort_keys=True)
            os.replace(tmp, path)
        except OSError:
            pass

    # ---- AOT pre-warm ----------------------------------------------------
    def prewarm(self) -> None:
        """CREATE-time kickoff: schedule background AOT of every node at
        its CURRENT capacities (post-presize, so warm starts compile the
        shapes they will actually run). Returns immediately — the first
        epoch waits for the executables as they land."""
        svc = self.compile_service
        if svc is None:
            return
        svc.prewarm_program(
            self.program.nodes, self.program.epoch_events, job=self.name,
            profiler=self.profiler,
            plan_hash=self.plan_hash, mesh=self.program.mesh,
            labels=[self.program._node_label(i)
                    for i in range(len(self.program.nodes))])

    def _prewarm_predicted(self, needs, needs_cum, needs_epoch) -> None:
        """Background AOT of the predicted growth buckets: once observed
        rates exist, the predictor's extrapolation seeds the bucket
        ladder (`capacity.ladder`) and those shapes compile ahead of any
        overflow. Two joint shapes per round — the FIRST ladder rung
        (where a mis-predicted or budget-clamped growth lands) and the
        predicted TOP bucket (where cascade-free growth jumps). Bounded
        by `compile_buckets` rounds per job, deduped per (node, slot,
        bucket), and skipped entirely while observed needs are unchanged
        (steady state pays one dict compare, not a re-projection)."""
        svc = self.compile_service
        if svc is None or not self.predictive \
                or self._prewarm_rounds >= self.compile_buckets:
            return
        if needs == self._last_prewarm_needs:
            return
        self._last_prewarm_needs = needs
        targets = self._predict_caps(needs, needs_cum, needs_epoch)
        low: Dict[int, Dict[str, int]] = {}
        high: Dict[int, Dict[str, int]] = {}
        for i, caps in targets.items():
            cur = self.program.nodes[i].cap_current()
            for s, c in caps.items():
                if c > cur.get(s, 0) and self._prewarmed.get((i, s)) != c:
                    rungs = _ladder(cur[s], c, rungs=2)  # [first, top]
                    low.setdefault(i, dict(cur))[s] = rungs[0]
                    high.setdefault(i, dict(cur))[s] = rungs[-1]
                    self._prewarmed[(i, s)] = c
        for caps in [low] if low == high else [low, high]:
            if not caps or self._prewarm_rounds >= self.compile_buckets:
                break
            self._prewarm_rounds += 1
            svc.prewarm_program(
                self.program.nodes, self.program.epoch_events,
                job=self.name,
                profiler=self.profiler,
                plan_hash=self.plan_hash, caps=caps,
                mesh=self.program.mesh,
                labels=[self.program._node_label(i)
                        for i in range(len(self.program.nodes))])

    def shape_hints(self) -> Dict[str, Dict[str, int]]:
        """Per-node capacity high-water keyed by the node's STRUCTURAL
        shape key (node_shape_key) — the registry form that survives
        planner refactors and job renames (the plan-shape-hash warm-start
        registry stores these; cap_hints() keeps the index-keyed view for
        introspection). Structurally identical nodes (q5's duplicated
        hop+agg chain) merge by max."""
        out: Dict[str, Dict[str, int]] = {}
        for node in self.program.nodes:
            cur = node.cap_current()
            if not cur:
                continue
            k = node_shape_key(node)
            prev = out.setdefault(k, {})
            for s, c in cur.items():
                prev[s] = max(prev.get(s, 0), c)
        return out

    # ---- profiler / metrics surfaces -------------------------------------
    def _accum_totals(self, vec: np.ndarray) -> None:
        sm = self.program._sum_mask
        if len(vec) != len(self._stat_totals):
            return                      # defensive: layout mismatch
        self._stat_totals = np.where(sm, self._stat_totals + vec,
                                     np.maximum(self._stat_totals, vec))

    def _update_traffic_ewma(self) -> None:
        """Feed each flow-armed node's EWMA ring from the CUMULATIVE
        tv* totals (the EWMA differences consecutive checkpoints
        internally — sum slots only ever grow, so the delta is this
        window's traffic). Checkpoint-cadence host work: one dict walk,
        no device traffic."""
        from .skew_stats import SK_BUCKETS, TrafficEwma
        for i, node in enumerate(self.program.nodes):
            if not node.flow:
                continue
            st = self.program.node_stats(i, self._stat_totals)
            ew = self._traffic_ewma.get(i)
            if ew is None:
                ew = self._traffic_ewma[i] = TrafficEwma()
            ew.update([st.get(f"tv{b}", 0) for b in range(SK_BUCKETS)])

    def _export_hbm_gauges(self) -> None:
        """rw_hbm_bytes{job,node,shards} + budget utilization: the HBM
        footprint the capacity lifecycle actually allocated, checkpoint-
        fresh. Bytes are PER SHARD (capacities are per-shard and the
        budget is per-chip HBM); the `shards` label says how many chips
        each carry that footprint."""
        from ..utils.metrics import REGISTRY
        from .capacity import node_hbm_bytes
        shards = str(self.mesh_shards)
        g = REGISTRY.gauge("rw_hbm_bytes",
                           "fused per-node device state bytes (per shard)",
                           labels=("job", "node", "shards"))
        total = 0
        for i, node in enumerate(self.program.nodes):
            if not node.cap_current():
                continue
            nbytes = node_hbm_bytes(node)
            g.labels(self.name, f"{i}:{type(node).__name__}",
                     shards).set(nbytes)
            total += nbytes
        REGISTRY.gauge("rw_hbm_budget_utilization",
                       "fused job per-chip HBM footprint over hbm_budget_mb",
                       labels=("job", "shards")).labels(self.name,
                                                        shards).set(
            total / float(self.hbm_budget_mb << 20))

    def node_report(self) -> List[Tuple]:
        """Per-node/per-slot attribution rows (rw_fused_node_stats):
        (node, type, slot, rows_in, rows_out, entries, capacity,
        occupancy, hbm_mb, overflowed). Row counters are job-lifetime
        sums; `entries` is the slot's high-water observed need — all of
        it from the stats vector the regular syncs already pull, no extra
        device traffic."""
        out: List[Tuple] = []
        totals = self._stat_totals
        for i, node in enumerate(self.program.nodes):
            st = self.program.node_stats(i, totals)
            rows_in = st.get("rows_in", 0)
            rows_out = st.get("rows_out", 0)
            cur = node.cap_current()
            tname = type(node).__name__
            if not cur:
                out.append((i, tname, "-", rows_in, rows_out,
                            0, 0, 0.0, 0.0, False))
                continue
            bpe = node.cap_bytes()
            needs = node.cap_needs(st)
            for s in sorted(cur):
                cap = cur[s]
                entries = needs.get(s, 0)
                out.append((i, tname, s, rows_in, rows_out, entries, cap,
                            entries / cap if cap else 0.0,
                            cap * bpe.get(s, 0) / float(1 << 20),
                            entries > cap))
        return out

    def skew_report(self) -> List[Tuple]:
        """rw_key_skew rows for this job's skew-armed keyed nodes:
        (node, type, metric, ordinal, key, value, share) —
        metric='vnode_occ': ordinal = bucket index, value = live keys
        whose vnode falls in the bucket (high-water), share = the
        bucket's fraction of the live total; metric='hot_key': ordinal =
        rank, key = the 40-bit-truncated hot key, value = its per-epoch
        row count (the hottest (key, epoch) observed — see
        device/skew_stats.py for the exact semantics); on a mesh, also
        metric='shard_live' / 'exchange_rows_in' (ordinal = shard; see
        `shard_report`), whether or not skew telemetry is armed. All read
        from the stats the regular syncs already pulled — zero extra
        device traffic."""
        from .skew_stats import (SK_BUCKETS, SK_TOPK, skew_ratio,
                                 traffic_divergence, unpack_hot)
        out: List[Tuple] = []
        totals = self._stat_totals
        # what the shards themselves report (`shard_report`), armed with
        # the mesh and not with the skew telemetry: 'shard_live' = live
        # entries of shard `ordinal`, 'exchange_rows_in' = live rows shard
        # `ordinal` received from exchange stage `key`
        shard = self.shard_report() or {"keyed": [], "exchanges": []}
        for metric, entries, per_shard in (
                ("shard_live", shard["keyed"], "live"),
                ("exchange_rows_in", shard["exchanges"], "rows_in")):
            for e in entries:
                tname = type(self.program.nodes[e["i"]]).__name__
                tot = sum(e[per_shard])
                out.extend((e["i"], tname, metric, s, e.get("xi"), int(v),
                            v / tot if tot else 0.0)
                           for s, v in enumerate(e[per_shard]))
        for i, node in enumerate(self.program.nodes):
            if not (node.skew or node.flow):
                continue
            st = self.program.node_stats(i, totals)
            tname = type(node).__name__
            occ = [st.get(f"skv{b}", 0) for b in range(SK_BUCKETS)]
            if node.skew:
                total = sum(occ)
                for b, c in enumerate(occ):
                    out.append((i, tname, "vnode_occ", b, None, c,
                                c / total if total else 0.0))
                out.append((i, tname, "skew_ratio", 0, None,
                            int(sum(occ)), skew_ratio(occ)))
                for r in range(SK_TOPK):
                    key, count = unpack_hot(st.get(f"skh{r}", 0))
                    if count > 0:
                        out.append((i, tname, "hot_key", r, key, count,
                                    None))
            if node.flow:
                # flow telemetry: where rows WENT (sum totals), next to
                # where state LIVES (occupancy high-water). The
                # divergence row is the "hot flow over cold state"
                # signal an occupancy-only view cannot produce.
                tv = [st.get(f"tv{b}", 0) for b in range(SK_BUCKETS)]
                ttot = sum(tv)
                for b, c in enumerate(tv):
                    out.append((i, tname, "vnode_traffic", b, None, c,
                                c / ttot if ttot else 0.0))
                out.append((i, tname, "traffic_skew", 0, None, int(ttot),
                            skew_ratio(tv)))
                if node.skew:
                    out.append((i, tname, "traffic_div", 0, None,
                                int(ttot), traffic_divergence(tv, occ)))
                ew = self._traffic_ewma.get(i)
                if ew is not None:
                    out.append((i, tname, "traffic_burst", 0, None,
                                int(ttot), ew.burst_ratio()))
            if node.skew and self.program.mesh is not None:
                # per-SHARD load implied by the histogram under the
                # CURRENT routing bounds — the quantity vnode
                # rebalancing actually evens out (skew_ratio above is
                # bounds-independent raw key skew)
                from .skew_stats import shard_loads, shard_skew_ratio
                bounds = self._current_bounds()
                loads = shard_loads(occ, bounds)
                tot = sum(loads)
                for s, ld in enumerate(loads):
                    out.append((i, tname, "shard_load", s, None,
                                int(ld), ld / tot if tot else 0.0))
                out.append((i, tname, "shard_skew", 0, None, int(tot),
                            shard_skew_ratio(occ, bounds)))
            if node.hot_keys:
                # adopted hot-key replication policy (value = the side
                # whose rows broadcast)
                for r, hk in enumerate(node.hot_keys):
                    out.append((i, tname, "hot_policy", r, hk,
                                node.hot_rep_side, None))
        return out

    def shard_report(self) -> Optional[Dict[str, Any]]:
        """What each shard of a mesh-sharded job holds and receives, from
        the stats the regular syncs already pull (job-lifetime totals,
        checkpoint-fresh; no device traffic): per exchange stage the
        bucket capacity `exch`, the `slots` a shard's step is handed an
        epoch (shards x exch) and `rows_in[shard]`, the live rows each
        shard received, summed over the job's epochs; per keyed node
        `live[shard]`, each shard's live entries (high-water);
        `rebalances`, the routing switches adopted. None on one chip.
        JSON-able: every checkpoint leaves it on its `rw:commit.gauges`
        span, where it outlives the job."""
        if self.program.mesh is None:
            return None
        n = self.mesh_shards
        exchanges, keyed = [], []
        for i, node in enumerate(self.program.nodes):
            st = self.program.node_stats(i, self._stat_totals)
            name = self.program.node_names[i]
            if node.exch is not None:
                exchanges.extend(
                    {"node": name, "i": i, "xi": xi, "exch": node.exch,
                     "slots": n * node.exch,
                     "rows_in": [st[f"xin{xi}_{s}"] for s in range(n)]}
                    for xi in range(len(node.shard_spec().exchanges)))
            if node.shard_live:
                keyed.append({"node": name, "i": i,
                              "live": [st[f"live{s}"] for s in range(n)]})
        return {"shards": n, "rebalances": self.rebalances,
                "exchanges": exchanges, "keyed": keyed}

    def flow_report(self) -> Dict[str, Any]:
        """What flowed through each node of the job and how full its
        state is, from the stats the regular syncs already pull (job-
        lifetime totals, checkpoint-fresh; no device traffic): per node
        `node`, `i`, `kind`, `rows_in` / `rows_out` (live rows, summed
        over the job's epochs) and `lanes`, the rows-wide shape its step
        was handed an epoch (`FusedProgram.lanes`) — `rows_in` over
        `lanes` x epochs is how much of what a step works over is not
        padding; for a node with state `live` and `capacity`, the
        high-water entries and the slots of its fullest keyed slot (per
        shard on a mesh); for a join `need_pairs`, the most pairs an
        epoch emitted, and its `pairs` buffer; `slots`, the same two
        numbers for EVERY slot of `cap_current()` (an agg's `main` and
        each multiset `ms<i>`, a join's `a`, `b` and `pairs`: the ones
        that accumulate by their high-water entries, the per-epoch ones
        by the most an epoch needed). JSON-able: every
        checkpoint leaves it on its `rw:commit.gauges` span, where it
        outlives the job."""
        nodes = []
        for i, node in enumerate(self.program.nodes):
            st = self.program.node_stats(i, self._stat_totals)
            entry = {"node": self.program.node_names[i], "i": i,
                     "kind": type(node).__name__,
                     "rows_in": st.get("rows_in", 0),
                     "rows_out": st.get("rows_out", 0),
                     "lanes": self.program.lanes[i]}
            cur = node.cap_current()
            if cur:
                live = node.cap_needs_cum(st)
                slot = max(live, key=lambda s: live[s] / cur[s])
                entry.update(live=live[slot], capacity=cur[slot])
                if "pairs" in cur:
                    entry.update(need_pairs=st["need_pairs"],
                                 pairs=cur["pairs"])
                high = {**node.cap_needs_epoch(st), **live}
                entry["slots"] = {s: {"live": high.get(s, 0), "capacity": c}
                                  for s, c in cur.items()}
            nodes.append(entry)
        return {"events": self.counter,
                "epoch_events": self.program.epoch_events, "nodes": nodes}

    def node_skew_ratio(self, i: int) -> Optional[float]:
        """Occupancy skew ratio (max/mean bucket) of node i, or None
        when the node carries no skew telemetry."""
        from .skew_stats import SK_BUCKETS, skew_ratio
        node = self.program.nodes[i]
        if not node.skew:
            return None
        st = self.program.node_stats(i, self._stat_totals)
        return skew_ratio([st.get(f"skv{b}", 0) for b in range(SK_BUCKETS)])

    # ---- capacity introspection -----------------------------------------
    def cap_report(self) -> Dict[str, Any]:
        """Growth accounting + live per-node capacities (risectl
        fused-stats, bench detail blocks)."""
        nodes = {}
        for i, node in enumerate(self.program.nodes):
            cur = node.cap_current()
            if cur:
                nodes[f"{i}:{type(node).__name__}"] = dict(cur)
        return {"growth_replays": self.growth_replays,
                "retraces": self.retraces, "growths": self.growths,
                "committed_events": self.committed, "nodes": nodes}

    def cap_hints(self) -> Dict[int, Dict[str, Any]]:
        """Per-node capacity snapshot keyed by program node index — the
        INTROSPECTION view (each entry carries the node's structural
        hash so a reader can tell which plan it belongs to). The
        warm-start presize path does NOT consume this: `shape_hints()`
        (keyed by `node_shape_key`) feeds `Database._fused_cap_hw`,
        which `try_fuse(cap_registry=...)` reads by plan-shape hash."""
        out = {}
        for i, node in enumerate(self.program.nodes):
            cur = node.cap_current()
            if cur:
                out[i] = {"type": type(node).__name__, "sig": hash(node),
                          "caps": dict(cur)}
        return out


def _np_unpack(pack: PackPlan, keys: np.ndarray) -> List[np.ndarray]:
    out = []
    shift = 0
    for f in pack.fields:
        v = (keys >> shift) & ((1 << f.bits) - 1)
        out.append(v * f.stride + f.offset)
        shift += f.bits
    return out
