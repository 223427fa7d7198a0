"""Fuse planner: recognize an eligible executor tree and lower it to a
FusedProgram (`device/fused.py`).

This is the dispatch seam one level up from `ops/device_agg.py`: instead
of swapping ONE executor onto the device, an entire MV fragment —
source(nexmark/datagen) -> project/filter/hop -> agg/join -> materialize —
becomes one traced epoch program. Recognition is conservative: anything
outside the proven shape (nullable flows, non-device expressions, unpackable
keys, watermarks, EOWC, outer joins, DISTINCT/filtered aggregates) returns
None and the normal per-operator path runs unchanged.

Static analysis carried per stream column: SQL dtype, surrogate decoder
(strings ride as injective int64 surrogates; only projection / group-by /
equi-join use is allowed), and (lo, hi, stride) integer range — the proof
obligations for lossless key packing, re-verified on device at runtime.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, replace
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..core import dtypes as T
from ..core.dtypes import DataType, TypeKind
from ..expr.expression import Expr, FunctionCall, InputRef, Literal
from .fused import (AggNode, Delta, FilterNode, FusedJob, FusedProgram,
                    HopNode, IngestNode, JoinNode, MapNode, MVKeyedNode,
                    MVPairNode, MVPull, Node, PackPlan, PrecombineNode,
                    SourceNode, node_shape_key, plan_shape_hash)

NUM = ("num",)
TS = ("ts",)
_HORIZON = 1 << 33          # event horizon assumed for unbounded sources
# fused epoch cadence = source events_per_poll * EPOCH_POLLS (the
# SourceExecutor poll budget per barrier); module-level so tests can pin
# a cadence that does NOT divide the shard count (tail-padding coverage)
EPOCH_POLLS = 64


class FuseReject(Exception):
    """Plan shape outside the fused subset — fall back silently."""


@dataclass
class Meta:
    """Walker-side static description of one node's output delta."""
    idx: int                              # node index in the program
    dtypes: List[DataType]
    decoders: List[Tuple]
    ranges: List[Optional[Tuple[int, int, int]]]
    rows_bound: int
    append_only: bool
    agg: Optional[AggNode] = None         # set when this delta IS an agg's
    is_pair: bool = False                 # carries (pk, pk2) pair identity


class _TsShift(Expr):
    """ts +/- INTERVAL const, device-lowered (the host registers
    ts_*_interval without a device impl — fused plans need it)."""

    def __init__(self, arg: Expr, delta_usecs: int):
        self.arg = arg
        self.delta = int(delta_usecs)
        self.return_type = T.TIMESTAMP

    def children(self):
        return [self.arg]

    def eval(self, chunk):
        from ..core.chunk import Column
        c = self.arg.eval(chunk)
        return Column(T.TIMESTAMP, c.values + self.delta, c.validity)

    def supports_device(self):
        return self.arg.supports_device()

    def eval_device(self, cols):
        v, ok = self.arg.eval_device(cols)
        return v + self.delta, ok


def _devify(e: Expr) -> Expr:
    """Rewrite for device evaluability (constant interval arithmetic);
    raises FuseReject when the expression has no device path."""
    if isinstance(e, FunctionCall) and e.name.startswith("ts_") \
            and e.name.endswith("_interval"):
        iv = e.args[1]
        if isinstance(iv, Literal) and getattr(iv.value, "months", 1) == 0:
            us = iv.value.days * 86_400_000_000 + iv.value.usecs
            return _TsShift(_devify(e.args[0]),
                            us if "add" in e.name else -us)
        raise FuseReject(f"non-constant interval in {e.name}")
    if isinstance(e, FunctionCall):
        e = FunctionCall(e.name, [_devify(a) for a in e.args],
                         e.return_type, e.sig)
    if isinstance(e, InputRef):
        # verbatim column refs are always device-safe here: variable-width
        # columns ride as int64 surrogates, and _surrogate_safe forbids
        # computing over them
        return e
    if not e.supports_device():
        raise FuseReject(f"no device path for {e!r}")
    return e


def _surrogate_safe(e: Expr, decoders: Sequence[Tuple]) -> None:
    """Surrogate columns may only be projected verbatim (or used as keys,
    which the caller handles) — any computation on them would act on pool
    indices, not strings."""
    if isinstance(e, InputRef):
        return                      # verbatim projection is fine
    stack = list(e.children() if hasattr(e, "children") else [])
    while stack:
        c = stack.pop()
        if isinstance(c, InputRef) and decoders[c.index] not in (NUM, TS):
            raise FuseReject("computation over a string surrogate column")
        stack.extend(c.children() if hasattr(c, "children") else [])


def _range_of(e: Expr, ranges) -> Optional[Tuple[int, int, int]]:
    """Interval analysis for packing proofs. None = unbounded/unknown."""
    if isinstance(e, InputRef):
        return ranges[e.index]
    if isinstance(e, Literal):
        if isinstance(e.value, (int, np.integer)) \
                and not isinstance(e.value, bool):
            v = int(e.value)
            return (v, v, max(1, abs(v)))
        return None
    if isinstance(e, _TsShift):
        r = _range_of(e.arg, ranges)
        if r is None:
            return None
        return (r[0] + e.delta, r[1] + e.delta,
                math.gcd(r[2], abs(e.delta)) or 1)
    if isinstance(e, FunctionCall) and e.name in ("add", "subtract") \
            and len(e.args) == 2:
        a = _range_of(e.args[0], ranges)
        b = _range_of(e.args[1], ranges)
        if a is None or b is None:
            return None
        if e.name == "add":
            lo, hi = a[0] + b[0], a[1] + b[1]
        else:
            lo, hi = a[0] - b[1], a[1] - b[0]
        return (lo, hi, math.gcd(a[2], b[2]) or 1)
    if isinstance(e, FunctionCall) and e.return_type.kind == TypeKind.BOOLEAN:
        return (0, 1, 1)
    return None


# ---------------------------------------------------------------------------
# the walker
# ---------------------------------------------------------------------------


def _env_bool(name: str, default: bool) -> bool:
    """RW_* operational overrides for the skew-defense knobs: force on or
    off without code changes (the RW_SKEW_STATS pattern)."""
    import os as _os
    v = _os.environ.get(name)
    if v is None:
        return default
    return v.strip().lower() not in ("0", "false", "off")


class _Fuser:
    def __init__(self, device_cfg, epoch_events_cap: Optional[int] = None):
        self.nodes: List[Node] = []
        self.capacity = getattr(device_cfg, "capacity", 1 << 14) or (1 << 14)
        self.epoch_events: Optional[int] = epoch_events_cap
        self._source_cache: Dict[int, Meta] = {}
        self.max_events: Optional[int] = None
        # local pre-combine (skew defense 1): duplicate-key agg input
        # rows combine to one partial row per key before the state
        # merge / ICI exchange. Armed per agg when exactly combinable.
        self.precombine = _env_bool(
            "RW_AGG_PRECOMBINE",
            getattr(device_cfg, "agg_precombine", True))
        # host-ingest mode (device/ingest.py): sources become IngestNodes
        # fed from pre-staged host buffers instead of device-regenerated
        # events — the production source path. DeviceConfig.host_ingest
        # (RW_HOST_INGEST override) arms it globally; a single source
        # opts in via WITH (nexmark.ingest='host').
        self.host_ingest = _env_bool(
            "RW_HOST_INGEST", getattr(device_cfg, "host_ingest", False))
        self.ingest_nodes: Dict[int, "_NexmarkDesc"] = {}
        # every source desc by node index: if ANY source of the job opts
        # into host feed, the REST promote too (try_fuse) — a mixed job
        # would desync the shared event clock the moment admission
        # throttles an ingest window (the device-datagen source would
        # still generate a full epoch range and re-emit the overlap)
        self.source_descs: Dict[int, "_NexmarkDesc"] = {}

    def add(self, node: Node) -> int:
        self.nodes.append(node)
        return len(self.nodes) - 1

    # ---- leaf: source chain --------------------------------------------
    def _source(self, execu) -> Meta:
        from ..connectors.nexmark import NexmarkReader
        from ..ops import (MaterializeExecutor, RowIdGenExecutor,
                           SourceExecutor)
        from ..ops.executor import SharedStreamPort
        from ..sql.database import _Backfill
        desc = getattr(execu, "virtual_source", None)
        e = execu
        while desc is None:
            if isinstance(e, _Backfill):
                if e.snapshot is not None and e.snapshot.capacity:
                    raise FuseReject("source already has history (backfill "
                                     "snapshot) — fused jobs start at 0")
                e = e.port
            elif isinstance(e, SharedStreamPort):
                e = e.shared.upstream
            elif isinstance(e, (MaterializeExecutor, RowIdGenExecutor)):
                e = e.input
            elif isinstance(e, SourceExecutor):
                if not isinstance(e.reader, NexmarkReader):
                    raise FuseReject(f"unfusable reader "
                                     f"{type(e.reader).__name__}")
                if e.reader.next_event:
                    raise FuseReject("source already advanced")
                nm = e.name
                if nm.startswith("Source(") and nm.endswith(")"):
                    nm = nm[len("Source("):-1]
                desc = _NexmarkDesc.from_reader(e.reader, e.schema, nm)
            else:
                raise FuseReject(f"unfusable source chain node "
                                 f"{type(e).__name__}")
        key = desc.cache_key
        if key in self._source_cache:
            return self._source_cache[key]
        from .nexmark_gen import GenCfg
        cfg = desc.gencfg
        if self.max_events is None:
            self.max_events = desc.max_events
        elif desc.max_events != self.max_events:
            raise FuseReject("sources disagree on max_events")
        ee = desc.events_per_poll * EPOCH_POLLS
        if self.epoch_events is None:
            self.epoch_events = ee
        elif self.epoch_events != ee:
            raise FuseReject("sources disagree on epoch cadence")
        if self.host_ingest or desc.ingest == "host":
            # host-feed mode: the per-epoch input is a pre-staged device
            # buffer (device/ingest.py) — same column metadata, so every
            # downstream packing proof is identical to the datagen plan
            node: Node = IngestNode(desc.table, cfg, desc.col_names,
                                    desc.rowid_pos, desc.max_events,
                                    desc.dtypes)
            idx = self.add(node)
            self.ingest_nodes[idx] = desc
            meta = Meta(idx, list(node.dtypes), list(node.decoders),
                        list(node.ranges),
                        rows_bound=desc.max_events or _HORIZON,
                        append_only=True)
            self._source_cache[key] = meta
            return meta
        node = SourceNode(desc.table, cfg, desc.col_names, desc.rowid_pos,
                          desc.max_events, desc.dtypes)
        idx = self.add(node)
        self.source_descs[idx] = desc
        meta = Meta(idx, list(node.dtypes), list(node.decoders),
                    list(node.ranges),
                    rows_bound=desc.max_events or _HORIZON,
                    append_only=True)
        self._source_cache[key] = meta
        return meta

    # ---- recursive build ------------------------------------------------
    def build(self, execu, need_pk: bool) -> Meta:
        from ..ops import (FilterExecutor, HashAggExecutor, HashJoinExecutor,
                           HopWindowExecutor, JoinType, ProjectExecutor)
        from ..ops.device_agg import DeviceHashAggExecutor
        from ..ops.device_join import DeviceHashJoinExecutor

        if isinstance(execu, ProjectExecutor):
            m = self.build(execu.input, need_pk)
            return self._map(m, execu.exprs)
        if isinstance(execu, FilterExecutor):
            m = self.build(execu.input, need_pk)
            pred = _devify(execu.predicate)
            _surrogate_safe(pred, m.decoders)
            idx = self.add(FilterNode(m.idx, pred))
            return replace(m, idx=idx, agg=None)
        if isinstance(execu, HopWindowExecutor):
            m = self.build(execu.input, need_pk)
            if m.agg is not None or m.is_pair:
                raise FuseReject("hop over non-source stream")
            node = HopNode(m.idx, execu.time_col, execu.hop_usecs,
                           execu.size_usecs)
            idx = self.add(node)
            tr = m.ranges[execu.time_col]
            if tr is None:
                raise FuseReject("hop over unbounded time column")
            ws = ((tr[0] // execu.hop_usecs - node.n) * execu.hop_usecs,
                  tr[1], execu.hop_usecs)
            we = (ws[0] + execu.size_usecs, tr[1] + execu.size_usecs,
                  execu.hop_usecs)
            return Meta(idx, m.dtypes + [T.TIMESTAMP, T.TIMESTAMP],
                        m.decoders + [TS, TS], m.ranges + [ws, we],
                        rows_bound=m.rows_bound * node.n,
                        append_only=m.append_only)
        if isinstance(execu, (DeviceHashAggExecutor, HashAggExecutor)):
            return self._agg(execu, need_pk)
        if isinstance(execu, (DeviceHashJoinExecutor, HashJoinExecutor)):
            if isinstance(execu, HashJoinExecutor) \
                    and execu.join_type != JoinType.INNER:
                raise FuseReject("non-inner join")
            return self._join(execu)
        # source chains (backfill/port/virtual) end the recursion
        return self._source(execu)

    def _map(self, m: Meta, exprs: Sequence[Expr]) -> Meta:
        dexprs, dts, decs, rngs = [], [], [], []
        for e in exprs:
            de = _devify(e)
            _surrogate_safe(de, m.decoders)
            dexprs.append(de)
            dts.append(e.return_type)
            if isinstance(de, InputRef):
                decs.append(m.decoders[de.index])
            elif e.return_type.kind in (TypeKind.TIMESTAMP, TypeKind.DATE):
                decs.append(TS)
            else:
                decs.append(NUM)
            rngs.append(_range_of(de, m.ranges))
        idx = self.add(MapNode(m.idx, dexprs))
        return Meta(idx, dts, decs, rngs, m.rows_bound, m.append_only,
                    is_pair=m.is_pair)

    def _agg(self, execu, need_pk: bool) -> Meta:
        from .agg_step import DeviceAggSpec
        m = self.build(execu.input, need_pk=False)
        gidx = list(execu.group_key_indices)
        calls = list(execu.calls)
        kinds, arg_dtypes, arg_ids = [], [], []
        out_dt, out_dec, out_rng = [], [], []
        for i in gidx:
            out_dt.append(m.dtypes[i])
            out_dec.append(m.decoders[i])
            out_rng.append(m.ranges[i])
        for ci, c in enumerate(calls):
            if c.distinct or c.filter is not None:
                raise FuseReject("DISTINCT / FILTER aggregate")
            k = "count_star" if c.kind == "count" and c.arg is None \
                else c.kind
            if k not in ("count_star", "count", "sum", "min", "max"):
                raise FuseReject(f"aggregate {c.kind} not fused")
            if c.arg is not None:
                if not isinstance(c.arg, InputRef):
                    raise FuseReject("non-column aggregate argument")
                if m.decoders[c.arg.index] not in (NUM, TS):
                    raise FuseReject("aggregate over string surrogate")
                dd = c.arg.return_type.device_dtype
                if dd is None:
                    raise FuseReject("aggregate arg has no device dtype")
                if k in ("min", "max") and not m.append_only \
                        and np.issubdtype(np.dtype(dd), np.floating):
                    # retractable min/max multisets hold order-encoded
                    # int64; the fused path doesn't order-encode floats
                    raise FuseReject("retractable float min/max not fused")
                arg_dtypes.append(np.dtype(dd))
                arg_ids.append(("ref", c.arg.index))
            else:
                arg_dtypes.append(np.int64)
                arg_ids.append(("call", ci))
            out_dt.append(c.return_type)
            if k in ("count_star", "count"):
                out_dec.append(NUM)
                out_rng.append((0, m.rows_bound, 1))
            elif k == "sum":
                out_dec.append(NUM)
                out_rng.append(None)
            else:                    # min / max: value from the arg column
                out_dec.append(m.decoders[c.arg.index])
                out_rng.append(m.ranges[c.arg.index])
        pack = PackPlan.plan([m.ranges[i] for i in gidx])
        if pack is None:
            raise FuseReject("group key not losslessly packable")
        spec = DeviceAggSpec.build(
            ["count_star" if c.kind == "count" and c.arg is None else c.kind
             for c in calls],
            arg_dtypes, append_only=m.append_only, arg_ids=arg_ids)
        pk_pack = None
        if need_pk:
            pk_pack = PackPlan.plan(out_rng)
            if pk_pack is None:
                raise FuseReject("agg change-row identity not packable")
        in_idx = m.idx
        if self.precombine and self._combinable(spec):
            # skew defense 1 (local pre-combine): a stateless combine
            # stage collapses the epoch's duplicate-key rows to one
            # partial-aggregate row per key BEFORE the agg — and, under
            # mesh sharding, before the ICI exchange (the agg's shard
            # spec then routes the combined delta by its packed key)
            in_idx = self.add(PrecombineNode(m.idx, gidx, calls, pack,
                                             spec))
        node = AggNode(in_idx, gidx, calls, pack, spec, self.capacity,
                       pk_pack)
        if in_idx != m.idx:
            node.enable_precombine()
        idx = self.add(node)
        return Meta(idx, out_dt, out_dec, out_rng,
                    rows_bound=2 * m.rows_bound, append_only=False,
                    agg=node)

    @staticmethod
    def _combinable(spec) -> bool:
        """Exact pre-combine eligibility: the per-key deltas must combine
        by associative, order-independent reductions — which rules out
        retractable min/max multisets (multiset entries key by (group,
        value), not group) and float SUM columns (float addition is not
        associative bit-for-bit; combining locally would break the
        raw-path bit-identity contract)."""
        from .sorted_state import ReduceKind
        if spec.minputs:
            return False
        return not any(k == ReduceKind.SUM
                       and np.issubdtype(np.dtype(dt), np.floating)
                       for k, dt in zip(spec.kinds, spec.dtypes))

    def _join(self, execu) -> Meta:
        from ..ops.device_join import DeviceHashJoinExecutor
        if isinstance(execu, DeviceHashJoinExecutor):
            lkeys, rkeys = execu.key_idx["a"], execu.key_idx["b"]
            lex, rex = execu.left_exec, execu.right_exec
            cond = execu.condition
        else:
            lkeys, rkeys = execu.left_keys, execu.right_keys
            lex, rex = execu.left_exec, execu.right_exec
            cond = execu.condition
        lm = self.build(lex, need_pk=True)
        rm = self.build(rex, need_pk=True)
        merged = []
        for li, ri in zip(lkeys, rkeys):
            a, b = lm.ranges[li], rm.ranges[ri]
            if a is None or b is None:
                raise FuseReject("join key not packable")
            if (lm.decoders[li] in (NUM, TS)) != (rm.decoders[ri] in (NUM,
                                                                      TS)):
                raise FuseReject("join between surrogate and plain column")
            merged.append((min(a[0], b[0]), max(a[1], b[1]),
                           math.gcd(a[2], b[2]) or 1))
        pack = PackPlan.plan(merged)
        if pack is None:
            raise FuseReject("join key not losslessly packable")
        out_dt = lm.dtypes + rm.dtypes
        out_dec = lm.decoders + rm.decoders
        out_rng = lm.ranges + rm.ranges
        dcond = None
        if cond is not None:
            dcond = _devify(cond)
            _surrogate_safe(dcond, out_dec)
        import jax.numpy as jnp
        to_dev = lambda dts: [jnp.float64 if d.np_dtype is not None
                              and np.issubdtype(d.np_dtype, np.floating)
                              else jnp.int64 for d in dts]
        node = JoinNode(lm.idx, rm.idx, lkeys, rkeys, pack, dcond,
                        self.capacity, 4 * self.capacity,
                        to_dev(lm.dtypes), to_dev(rm.dtypes))
        idx = self.add(node)
        rb = min(lm.rows_bound * rm.rows_bound, _HORIZON)
        return Meta(idx, out_dt, out_dec, out_rng, rows_bound=rb,
                    append_only=lm.append_only and rm.append_only,
                    is_pair=True)


@dataclass(frozen=True)
class _NexmarkDesc:
    table: str
    gencfg: Any
    col_names: Tuple[str, ...]
    dtypes: Tuple[DataType, ...]
    rowid_pos: Optional[int]
    max_events: Optional[int]
    events_per_poll: int
    cache_key: Tuple
    # catalog source name (admission-bucket / provenance key) and the
    # per-source ingest opt-in (WITH (nexmark.ingest='host'))
    src_name: str = ""
    ingest: str = ""

    @staticmethod
    def from_reader(reader, schema, src_name: str = "") -> "_NexmarkDesc":
        from .nexmark_gen import GenCfg
        names = [f.name for f in schema.fields]
        rowid = names.index("_row_id") if "_row_id" in names else None
        return _NexmarkDesc(
            reader.table, GenCfg.from_config(reader.gen.cfg), tuple(names),
            tuple(f.dtype for f in schema.fields), rowid,
            reader.max_events, reader.events_per_poll,
            (reader.table, id(reader.gen)), src_name,
            getattr(reader, "ingest_mode", "") or "")


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def try_fuse(execu, ns, device_cfg, name: str,
             mv_state_table=None, make_state=None,
             cap_registry=None) -> Optional[FusedJob]:
    """Lower a planned MV executor tree to a FusedJob, or None.

    `execu` is the tree Database._create_mv would hand to Materialize;
    `ns` its namespace (schema + stream key + visibility).
    `cap_registry` maps plan-shape hash -> {node shape key -> caps}
    (FusedJob.shape_hints of previous incarnations): the program's nodes
    presize from it BEFORE state allocation, so a re-created MV with the
    same plan — under ANY name, after ANY planner refactor that keeps
    the node structurally identical — never re-climbs the capacity
    growth ladder. Hints match on structural shape keys, never program
    indices, so a different plan can never inherit them.
    """
    from ..ops import ProjectExecutor
    if device_cfg is None:
        return None
    try:
        f = _Fuser(device_cfg)
        if not isinstance(execu, ProjectExecutor):
            raise FuseReject(f"unexpected terminal {type(execu).__name__}")
        inner = execu.input
        from ..ops import HashAggExecutor
        from ..ops.device_agg import DeviceHashAggExecutor
        if isinstance(inner, (DeviceHashAggExecutor, HashAggExecutor)):
            # keyed MV straight off the agg change set
            m = f.build(inner, need_pk=False)
            agg = m.agg
            out_map, dts, decs = [], [], []
            ng = len(agg.group_idx)
            for e in execu.exprs:
                if not isinstance(e, InputRef):
                    raise FuseReject("computed column over aggregation "
                                     "output")
                out_map.append(("g", e.index) if e.index < ng
                               else ("c", e.index - ng))
                dts.append(e.return_type)
                decs.append(m.decoders[e.index])
            mv_idx = f.add(MVKeyedNode(m.idx, agg, agg.capacity))
            pull = MVPull("keyed", mv_idx, dts, decs, agg=agg,
                          out_map=out_map)
        else:
            m = f.build(inner, need_pk=False)
            if not m.is_pair:
                raise FuseReject("terminal stream has no pair identity "
                                 "(plain stateless MVs stay on host)")
            m = f._map(m, execu.exprs)
            mv_idx = f.add(MVPairNode(m.idx,
                                      _side_dtypes(m.dtypes),
                                      f.capacity))
            pull = MVPull("pair", mv_idx, m.dtypes, m.decoders)
        ee = f.epoch_events or 8192 * 64
        # operational kill switch / force-on without code changes
        # (tier-1 pins it off for compile budget; the dedicated skew
        # tests force it on)
        skew_on = _env_bool("RW_SKEW_STATS",
                            getattr(device_cfg, "skew_stats", True))
        if skew_on:
            # arm key-skew telemetry on every keyed node BEFORE the
            # exchange is armed (the host-spliced "exch" stat must stay
            # last in the layout) and before the plan hash is taken
            # (skew extends the traced step — see AggNode._sig)
            for node in f.nodes:
                node.enable_skew()
        flow_on = _env_bool("RW_FLOW_STATS",
                            getattr(device_cfg, "flow_stats", True))
        if flow_on:
            # arm traffic-per-vnode telemetry — same ordering contract
            # as skew (before tiering/exchange, before the plan hash);
            # the tv* slots join stat_sums so sharded_apply psums them
            for node in f.nodes:
                node.enable_flow()
        tier_on = _env_bool("RW_STATE_TIERING",
                            getattr(device_cfg, "state_tiering", True))
        if tier_on:
            # arm the tiered-state recency column on every keyed
            # stateful node — after skew (stat order), before the
            # exchange (the spliced "exch" stat stays last) and before
            # the plan hash (the touch column extends the traced step)
            for node in f.nodes:
                node.enable_tiering()
        mesh = _fused_mesh(device_cfg, ee)
        if mesh is not None:
            # arm the declarative exchange stages: every node whose
            # shard_spec names exchange inputs (aggs route on the group
            # key, joins on both join keys) gets its [n_shards, exch]
            # send bucket sized from the epoch cadence; overflow rides
            # the "exch" stat into the normal grow+replay path
            from .capacity import exchange_cap
            from ..parallel.mesh import data_shards
            n = data_shards(mesh)
            cap0 = exchange_cap(ee, n)
            for node in f.nodes:
                # per-shard occupancy first: the exchange's host-spliced
                # stats stay last in the node's layout
                if node.shard_spec().state == "vnode":
                    node.enable_shard_live(n)
                if node.shard_spec().exchanges:
                    node.enable_exchange(
                        cap0, n,
                        slot_bytes=8 * n * _exchange_row_width(node))
        hot_on = _env_bool("RW_HOT_KEY_REP",
                           getattr(device_cfg, "hot_key_rep", True))
        if mesh is not None and skew_on and hot_on:
            # skew defense 2 (hot-key replication): joins become
            # candidates for the checkpoint-time hot-key policy — the
            # heavy-hitter counters ARE the evidence, so the defense
            # needs skew telemetry armed. Candidate-arming only: the
            # exchange routes normally until a policy lands hot_keys.
            for node in f.nodes:
                if isinstance(node, JoinNode):
                    node.hotrep = True
        if f.ingest_nodes and f.source_descs:
            # one source opted into host feed: promote the job's OTHER
            # sources too. All sources share one event clock, and a
            # mixed job would double-ingest the datagen sources' rows
            # the moment admission shrinks a staged window (the ingest
            # counter would advance by less than the device-generated
            # range). Bit-identical either way — promotion only moves
            # where the rows are produced.
            for idx, desc in f.source_descs.items():
                node = IngestNode(desc.table, desc.gencfg,
                                  desc.col_names, desc.rowid_pos,
                                  desc.max_events, desc.dtypes)
                f.nodes[idx] = node
                f.ingest_nodes[idx] = desc
            f.source_descs.clear()
        if f.ingest_nodes:
            # feed-column pruning: only source columns some downstream
            # node can actually read ship over the H2D seam (must land
            # BEFORE the program/plan hash — liveness is part of the
            # IngestNode trace)
            _prune_ingest_columns(f.nodes, f.ingest_nodes)
        program = FusedProgram(f.nodes, ee, mesh=mesh)
        ingest = None
        if f.ingest_nodes:
            # host-ingest stager: one multiplexed event clock across the
            # job's ingest sources, feeds keyed by POST-CHAIN node index
            from .ingest import HostIngest, NexmarkIngestSource
            srcs = []
            for idx, desc in f.ingest_nodes.items():
                srcs.append((program.remap.get(idx, idx),
                             NexmarkIngestSource(
                                 desc.src_name or desc.table, desc.table,
                                 desc.gencfg, desc.col_names,
                                 desc.rowid_pos, desc.max_events,
                                 live=f.nodes[idx].live)))
            ingest = HostIngest(srcs, ee, mesh=mesh,
                                max_events=f.max_events)
        tier_plans = []
        if tier_on:
            # demotion plans: one per keyed stateful node, with
            # promotion-candidate recipes derived by walking the key
            # columns' lineage back to an ingest source's shipped host
            # columns. A node whose lineage can't be traced (device
            # datagen, computed keys, pre-combined input, multiset
            # aggs) keeps recency stats but never demotes — safe.
            from .tiering import TierPlan, derive_recipe
            source_ords = {idx: k for k, (idx, _s)
                           in enumerate(ingest.sources)} \
                if ingest is not None else {}
            mv_of = {}
            for j, node in enumerate(program.nodes):
                if isinstance(node, MVKeyedNode):
                    mv_of[node.inputs[0]] = j
            for j, node in enumerate(program.nodes):
                if isinstance(node, AggNode):
                    recipes = ()
                    if not node.spec.minputs and not node.combined:
                        r = derive_recipe(
                            program.nodes, node.inputs[0],
                            node.group_idx, node.pack.fields,
                            source_ords)
                        if r is not None:
                            recipes = (r,)
                    tier_plans.append(TierPlan(j, "agg", recipes,
                                               mv_of.get(j)))
                elif isinstance(node, JoinNode):
                    rl = derive_recipe(program.nodes, node.inputs[0],
                                       node.l_keys, node.pack.fields,
                                       source_ords)
                    rr = derive_recipe(program.nodes, node.inputs[1],
                                       node.r_keys, node.pack.fields,
                                       source_ords)
                    # promotion must see EVERY window key that can
                    # touch either side — a one-sided lineage can't
                    # prove that, so such a join demotes nothing
                    recipes = (rl, rr) \
                        if rl is not None and rr is not None else ()
                    tier_plans.append(TierPlan(j, "join", recipes))
        from ..parallel.mesh import data_shards
        ph = plan_shape_hash(program.nodes, program.epoch_events,
                             data_shards(mesh) if mesh is not None else 1)
        hints = (cap_registry or {}).get(ph) or {}
        if hints:
            # structural shape keys must match exactly: a hint from a
            # DIFFERENT plan can never presize this one, and hints keep
            # preset capacities to values a budget-governed run of the
            # SAME plan shape actually reached
            for node in program.nodes:
                caps = hints.get(node_shape_key(node))
                if caps:
                    node.preset_caps(dict(caps))
        job_table = make_state([T.INT64, T.INT64], [0]) if make_state \
            else None
        return FusedJob(name, program, pull, f.max_events,
                        mv_state_table=mv_state_table,
                        job_state_table=job_table,
                        mv_schema_len=len(ns.cols),
                        persist_every=getattr(device_cfg,
                                              "mv_persist_every", 1),
                        predictive=getattr(device_cfg,
                                           "predictive_growth", True),
                        hbm_budget_mb=getattr(device_cfg,
                                              "hbm_budget_mb", 4096),
                        profile=getattr(device_cfg, "profile", True),
                        aot_compile=getattr(device_cfg, "aot_compile",
                                            False),
                        compile_buckets=getattr(device_cfg,
                                                "compile_buckets", 4),
                        plan_hash=ph,
                        rebalance=_env_bool(
                            "RW_VNODE_REBALANCE",
                            getattr(device_cfg, "vnode_rebalance", True))
                        and skew_on,
                        rebalance_threshold=getattr(
                            device_cfg, "rebalance_threshold", 2.0),
                        hot_key_rep=hot_on and skew_on,
                        hot_key_frac=getattr(device_cfg,
                                             "hot_key_frac", 0.125),
                        ingest=ingest,
                        state_tiering=tier_on,
                        tier_plans=tuple(tier_plans))
    except FuseReject:
        return None


def _expr_col_refs(e: Expr) -> set:
    """Every InputRef index an expression tree reads."""
    out = set()
    stack = [e]
    while stack:
        c = stack.pop()
        if isinstance(c, InputRef):
            out.add(c.index)
        stack.extend(c.children() if hasattr(c, "children") else [])
    return out


def _prune_ingest_columns(nodes, ingest_nodes) -> None:
    """Feed-column liveness: which of an IngestNode's output columns can
    any downstream node actually READ? Only those ship over the H2D
    seam (`IngestNode.set_live`) — the host-side twin of the XLA
    dead-code elimination that makes the device generator free to
    "generate" columns nobody uses. The walk is conservative: any
    consumer it cannot reason about (joins read every column, pair MVs
    store every column, unknown node kinds) keeps the whole schema
    live. Must run BEFORE the program is built: liveness is part of the
    node's structural signature (it shapes the feed avals)."""
    consumers: Dict[int, List[int]] = {i: [] for i in range(len(nodes))}
    for j, nd in enumerate(nodes):
        for i in nd.inputs:
            consumers[i].append(j)
    memo: Dict[int, Optional[set]] = {}

    def need(i: int, arity: int) -> Optional[set]:
        """Live output-column set of node i (None = all), given its
        output arity (for pass-through consumers)."""
        if i in memo:
            return memo[i]
        memo[i] = None               # cycle guard: DAG, but stay safe
        out: set = set()
        for j in consumers[i]:
            c = nodes[j]
            if isinstance(c, MapNode):
                # a Map evaluates every expression regardless of its
                # own downstream needs — its refs are terminal
                r: Optional[set] = set()
                for e in c.exprs:
                    r |= _expr_col_refs(e)
            elif isinstance(c, FilterNode):
                down = need(j, arity)     # output cols = input cols
                r = None if down is None \
                    else _expr_col_refs(c.pred) | down
            elif isinstance(c, HopNode):
                down = need(j, arity + 2)
                r = None if down is None \
                    else {c.time_col} | {x for x in down if x < arity}
            elif isinstance(c, (AggNode, PrecombineNode)):
                r = set(c.group_idx)
                for call in c.calls:
                    if call.arg is not None:
                        r.add(call.arg.index)
            else:
                # JoinNode ships/stores every input column; MV pair
                # nodes store every column; anything unrecognized keeps
                # the schema whole
                r = None
            if r is None:
                memo[i] = None
                return None
            out |= r
        memo[i] = out
        return out

    for idx, _desc in ingest_nodes.items():
        node = nodes[idx]
        live = need(idx, len(node.col_names))
        if live is not None:
            node.set_live(live)
        memo.clear()                 # arity context is per ingest root


def _fused_mesh(device_cfg, epoch_events: int):
    """The 1-D device mesh a fused program shards over, or None for the
    single-chip path. `DeviceConfig.mesh_shards` opts in, and the default
    platform must actually have the devices: `mesh_shards=n` on fewer
    than n devices fails the CREATE MATERIALIZED VIEW (make_mesh raises)
    rather than running on one chip without saying so. Only the serving
    replicas degrade: a platform too small for the replica grid keeps
    the data parallelism and drops the replica axis.
    An epoch cadence that does NOT divide the shard count does not
    degrade either: each shard's contiguous event block is ceil-div sized
    and the tail block is PADDED (the over-generated ids mask out inside
    the traced step, `shard_exec.sharded_apply`), so all chips engage at
    any cadence."""
    import os
    n = max(1, int(getattr(device_cfg, "mesh_shards", 1) or 1))
    if n <= 1:
        return None
    r = os.environ.get("RW_MESH_REPLICAS")
    r = int(r) if r else max(1, int(getattr(device_cfg, "replicas", 1) or 1))
    from ..parallel.mesh import make_mesh
    if r > 1:
        try:
            return make_mesh(n, replicas=r)
        except ValueError:
            pass            # no room for the replica grid: shards only
    return make_mesh(n)


def _exchange_row_width(node) -> int:
    """Arrays one exchanged row actually buffers (shard_exec
    `_exchange_local`: the exchange's declared ref columns — or every
    input column when undeclared — plus sign, plus pk when carried),
    worst case across the node's exchange stages. Budget math only."""
    widths = []
    for ex in node.shard_spec().exchanges:
        if ex.ref_idx is not None:
            w = len(ex.ref_idx)
        elif isinstance(node, JoinNode):
            # a join side's input delta carries exactly its val columns
            w = (len(node.l_val_dtypes), len(node.r_val_dtypes))[ex.input]
        elif isinstance(node, AggNode) and node.combined:
            # pre-combined delta: packed key + raw-row count + one
            # partial delta per payload column
            w = 2 + len(node.spec.kinds)
        else:
            w = 3
        widths.append(w + 1 + (1 if ex.carry_pk else 0))
    return max(widths, default=4)


def _side_dtypes(dts: Sequence[DataType]):
    import jax.numpy as jnp
    return [jnp.float64 if d.np_dtype is not None
            and np.issubdtype(d.np_dtype, np.floating) else jnp.int64
            for d in dts]
