"""Jitted hash-aggregation epoch step over sorted-run state.

Device analog of `HashAggExecutor::apply_chunk` + barrier `flush_data`
(`src/stream/src/executor/aggregate/hash_agg.rs:331,411`), re-shaped for XLA:
the whole epoch's rows are applied as ONE traced program —

    rows -> per-key deltas -> merge -> change set, read off the merge by
         position (insert / delete / update-pair material)

so the device never sees data-dependent control flow, and barrier-granular
batching (parity is defined at barrier boundaries; intra-epoch order is free)
is the optimization license, exactly the reference's shared-buffer trick.
The change set — what every touched key held before and holds after — is
not searched for: the merge's own sort put each delta row next to the
state row of its key, and `sorted_state.merge_changes` reads it there.

Supported device aggregates: count / count(col) / sum / avg (retractable),
min / max — either append-only single-extreme state (cheapest, the fused
pipeline's choice) or exact-under-retraction via a sorted-multiset side
state per input column (`device/minput.py`, the `MaterializedInput` analog,
`aggregate/minput.rs`). The host executor keeps the exact path for
everything else (decimals, strings, DISTINCT, exotic kinds).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .minput import (SortedMultiset, ms_batch_reduce, ms_find,
                     ms_group_minmax, ms_grow, ms_make, ms_merge)
from .sorted_state import (EMPTY_KEY, ReduceKind, SortedState, _neutral,
                           batch_reduce, grow_state, make_state, merge,
                           merge_changes, sanitize_keys)

# Aggregate kinds the device step supports.
DEVICE_AGG_KINDS = ("count", "count_star", "sum", "avg", "min", "max")


@dataclass(frozen=True)
class DeviceCall:
    """One aggregate call, lowered: which payload columns it owns and how to
    turn them into an output."""
    kind: str                   # one of DEVICE_AGG_KINDS
    acc_dtype: Any              # jnp dtype of the accumulator / output
    cols: Tuple[int, ...]       # payload column indices (in state.vals)
    minput: Optional[int] = None  # index into spec.minputs (retractable m/m)


@dataclass(frozen=True)
class MinputDesc:
    """One retractable min/max multiset state (minput.py). Shared by every
    min/max call over the same input column (ms_group_minmax returns both
    extremes from one search); call_idx names the value-source call."""
    call_idx: int


class DeviceAggState(NamedTuple):
    """Main sorted-run state + one sorted multiset per retractable
    min/max call."""
    main: SortedState
    minputs: Tuple[SortedMultiset, ...]


@dataclass(frozen=True)
class DeviceAggSpec:
    """Static layout of the state payload.

    Payload column 0 is always row_count (SUM of signs) — group liveness,
    as in `agg_group.rs`. Each call then owns payload columns:
      count      -> [valid_count SUM]
      sum        -> [sum SUM, valid_count SUM]     (NULL when no valid rows)
      avg        -> [sum SUM, valid_count SUM]
      min / max  -> append-only build: [extreme MIN/MAX, valid_count SUM];
                    retractable build: [valid_count SUM] + a SortedMultiset
                    side state (`spec.minputs`, the minput.rs analog)
    """
    calls: Tuple[DeviceCall, ...]
    kinds: Tuple[ReduceKind, ...]
    dtypes: Tuple[Any, ...]
    append_only: bool
    minputs: Tuple[MinputDesc, ...] = ()

    @staticmethod
    def build(call_kinds: Sequence[str], in_dtypes: Sequence[Any],
              append_only: bool = True,
              arg_ids: Optional[Sequence[Any]] = None) -> "DeviceAggSpec":
        """append_only=True keeps min/max as one extreme column (cheapest;
        raises on retraction). append_only=False gives min/max calls a
        multiset side state — exact under deletes, the SQL default.
        arg_ids (hashable per call) lets min(x) and max(x) over the same
        column share one multiset."""
        kinds: List[ReduceKind] = [ReduceKind.SUM]       # row_count
        dtypes: List[Any] = [jnp.int64]
        calls: List[DeviceCall] = []
        minputs: List[MinputDesc] = []
        minput_by_arg: Dict[Any, int] = {}
        has_ao_minmax = False
        for i, (k, dt) in enumerate(zip(call_kinds, in_dtypes)):
            if k not in DEVICE_AGG_KINDS:
                raise ValueError(f"agg kind {k!r} has no device path")
            dt = jnp.dtype(dt)
            acc = (jnp.dtype(jnp.float64)
                   if jnp.issubdtype(dt, jnp.floating) else jnp.dtype(jnp.int64))
            if k in ("count", "count_star"):
                c0 = len(kinds)
                kinds.append(ReduceKind.SUM); dtypes.append(jnp.int64)
                calls.append(DeviceCall(k, jnp.dtype(jnp.int64), (c0,)))
            elif k in ("sum", "avg"):
                c0 = len(kinds)
                kinds += [ReduceKind.SUM, ReduceKind.SUM]
                dtypes += [acc, jnp.int64]
                calls.append(DeviceCall(k, acc, (c0, c0 + 1)))
            elif append_only:  # min / max, single-extreme state
                has_ao_minmax = True
                c0 = len(kinds)
                kinds += [ReduceKind.MIN if k == "min" else ReduceKind.MAX,
                          ReduceKind.SUM]
                dtypes += [acc, jnp.int64]
                calls.append(DeviceCall(k, acc, (c0, c0 + 1)))
            else:  # min / max, retractable multiset state
                c0 = len(kinds)
                kinds.append(ReduceKind.SUM); dtypes.append(jnp.int64)
                aid = arg_ids[i] if arg_ids is not None else ("call", i)
                mi = minput_by_arg.get(aid)
                if mi is None:
                    mi = len(minputs)
                    minput_by_arg[aid] = mi
                    minputs.append(MinputDesc(len(calls)))
                calls.append(DeviceCall(k, acc, (c0,), minput=mi))
        return DeviceAggSpec(tuple(calls), tuple(kinds), tuple(dtypes),
                             has_ao_minmax, tuple(minputs))

    def make_state(self, capacity: int) -> SortedState:
        return make_state(capacity, self.dtypes, self.kinds)


def _row_deltas(spec: DeviceAggSpec, signs, mask,
                inputs: Sequence[Tuple[Any, Any]]) -> List[jax.Array]:
    """Per-row payload delta columns from raw rows.
    inputs[i] = (values[B], valid[B]) for call i (count_star passes anything).
    """
    s64 = jnp.where(mask, signs, 0).astype(jnp.int64)
    deltas: List[Optional[jax.Array]] = [None] * len(spec.kinds)
    deltas[0] = s64
    for call, (vals, valid) in zip(spec.calls, inputs):
        sv = s64 * valid.astype(jnp.int64)
        if call.kind == "count_star":
            deltas[call.cols[0]] = s64
        elif call.kind == "count":
            deltas[call.cols[0]] = sv
        elif call.kind in ("sum", "avg"):
            v = jnp.where(valid & mask, vals, 0).astype(call.acc_dtype)
            deltas[call.cols[0]] = v * sv.astype(call.acc_dtype)
            deltas[call.cols[1]] = sv
        elif call.minput is not None:
            # retractable min/max: main state keeps only valid_count; the
            # values live in the multiset side state (epoch_core_full)
            deltas[call.cols[0]] = sv
        else:  # min / max — append-only: neutral where invalid
            kind = spec.kinds[call.cols[0]]
            v = jnp.where(valid & mask, vals.astype(call.acc_dtype),
                          _neutral(kind, call.acc_dtype))
            deltas[call.cols[0]] = v
            deltas[call.cols[1]] = sv
    return deltas  # type: ignore[return-value]


def _outputs(spec: DeviceAggSpec, vals: Sequence[jax.Array]
             ) -> Tuple[List[jax.Array], List[jax.Array]]:
    """Payload columns -> (per-call output arrays, per-call NULL masks)."""
    outs, nulls = [], []
    for call in spec.calls:
        if call.kind in ("count", "count_star"):
            outs.append(vals[call.cols[0]])
            nulls.append(jnp.zeros_like(vals[call.cols[0]], dtype=bool))
        elif call.kind == "sum":
            outs.append(vals[call.cols[0]])
            nulls.append(vals[call.cols[1]] == 0)
        elif call.kind == "avg":
            cnt = vals[call.cols[1]]
            denom = jnp.where(cnt == 0, 1, cnt).astype(jnp.float64)
            outs.append(vals[call.cols[0]].astype(jnp.float64) / denom)
            nulls.append(cnt == 0)
        elif call.minput is not None:
            # placeholder: real values come from the multiset via
            # epoch_core_full's minput change entries (SQL path formats
            # host-side); NULL mask from valid_count is still meaningful
            outs.append(jnp.zeros_like(vals[call.cols[0]]))
            nulls.append(vals[call.cols[0]] == 0)
        else:
            outs.append(vals[call.cols[0]])
            nulls.append(vals[call.cols[1]] == 0)
    return outs, nulls


def _core_tail(spec: DeviceAggSpec, state: SortedState,
               ukeys: jax.Array, udeltas, ucount: jax.Array,
               trail: bool = False):
    """The merge half of the epoch pipeline: unique per-key deltas ->
    state merge + old/new change set. Shared by the raw-row path
    (`epoch_core`) and the pre-combined path (`epoch_core_combined`),
    which arrive at the same unique-delta representation from different
    inputs. The change set is read off the merge by position
    (`sorted_state.merge_changes`: the merge's `MergeTrail` says which
    state row each delta key met), so the step searches the state for no
    key; a truncated merge (`needed` > capacity, replayed on a grown state
    by every caller) reads as the truncated state would. With `trail`
    (every epoch_core* passes it down) the change set also holds that
    trail as "merge_trail": a column kept beside the state — the tier's
    touch stamps — follows its rows through it by position."""
    with jax.named_scope("agg.merge"):
        new_state, needed, mtrail = merge(state, ukeys, udeltas, spec.kinds,
                                          return_trail=True)
        old_found, old_vals, new_found, new_vals = merge_changes(
            state, new_state, ukeys, udeltas, spec.kinds, mtrail)
    old_out, old_null = _outputs(spec, old_vals)
    new_out, new_null = _outputs(spec, new_vals)
    changes = {
        "keys": ukeys, "count": ucount,
        "old_found": old_found, "new_found": new_found,
        "old_out": tuple(old_out), "old_null": tuple(old_null),
        "new_out": tuple(new_out), "new_null": tuple(new_null),
        # raw payload columns at the touched keys — the SQL executor derives
        # outputs host-side from these (exact Decimal semantics for int
        # sum/avg) and persists them to the state table for recovery
        "old_vals": tuple(old_vals), "new_vals": tuple(new_vals),
    }
    if trail:
        changes["merge_trail"] = mtrail
    return new_state, needed, changes


def epoch_core(spec: DeviceAggSpec, state: SortedState,
               keys: jax.Array, signs: jax.Array, mask: jax.Array,
               inputs: Tuple[Tuple[jax.Array, jax.Array], ...],
               trail: bool = False):
    """The (un-jitted) epoch pipeline under the jitted steps below and the
    fused job's `AggNode` (device/fused.py)."""
    with jax.named_scope("agg.reduce_delta"):
        deltas = _row_deltas(spec, signs, mask, inputs)
        ukeys, udeltas, ucount = batch_reduce(keys, mask, deltas,
                                              spec.kinds)
    return _core_tail(spec, state, ukeys, udeltas, ucount, trail)


def precombine_core(spec: DeviceAggSpec,
                    keys: jax.Array, signs: jax.Array, mask: jax.Array,
                    inputs: Tuple[Tuple[jax.Array, jax.Array], ...]):
    """Local pre-combine ("Global Hash Tables Strike Back!": per-partition
    pre-aggregation before the global merge): collapse an epoch's raw
    rows to ONE partial-aggregate row per unique group key. Returns
    (ukeys, ucnt, udeltas); `ucnt` is the exact raw-row count behind each
    combined row (the downstream rows_in stat and the heavy-hitter
    evidence).

    THE OUTPUT CONTRACT (what `batch_reduce` leaves; stated here once and
    named where it is relied on — `epoch_core_combined`'s pass-through,
    `sorted_state.merge_changes`): keys UNIQUE and ASCENDING, the live
    rows a PREFIX, every row behind them a pad of EMPTY_KEY with the
    neutral payload of its column's kind (`ucnt` 0).

    Exactness: the per-key delta columns combine by the SAME associative
    reductions (`spec.kinds`) the state merge applies, so combining here
    — and, behind an exchange, re-combining the source shards' partials —
    is bit-identical to merging raw rows; the caller guarantees
    integer-only SUM columns (float sums are order-sensitive) and no
    multiset side state."""
    with jax.named_scope("agg.reduce_delta"):
        live = mask & (signs != 0)
        deltas = _row_deltas(spec, signs, mask, inputs)
        cnt = jnp.where(live, 1, 0).astype(jnp.int64)
        ukeys, uvals, _ = batch_reduce(keys, live, [cnt] + list(deltas),
                                       (ReduceKind.SUM,) + spec.kinds)
    return ukeys, uvals[0], tuple(uvals[1:])


def epoch_core_combined(spec: DeviceAggSpec, state: SortedState,
                        keys: jax.Array, counts: jax.Array,
                        dvals, mask: jax.Array, trail: bool = False,
                        recombine: bool = True):
    """Epoch pipeline over PRE-COMBINED rows: each input row is already a
    (key, raw-row count, per-column partial delta) tuple. Returns
    (new_state, needed, changes) exactly like `epoch_core`, plus
    changes["rows_in"] = total raw rows behind the combined input (the
    flow stat the raw path would have counted) and changes["in_counts"],
    those counts per unique key.

    One algorithm — unique per-key deltas -> `_core_tail` — whose first
    stage is needed or not by what the input is (static: the caller
    knows its plan at trace time, `AggNode.apply`):

    * `recombine` (the default: any rows): a key may arrive several
      times — behind a mesh exchange once from each source shard — and
      `batch_reduce` sorts and combines the partials to one row a key.
    * not `recombine`: the rows are ONE `precombine_core`'s output as it
      left it (its output contract, with `mask` = key is not EMPTY_KEY),
      which already IS the unique, key-sorted, pads-last delta a
      `batch_reduce` over it would return bit for bit — so it is passed
      through: no sort, no gather, no scatter, no segment reduction.
      The elementwise select below only re-states the pads as neutral."""
    kinds = (ReduceKind.SUM,) + spec.kinds
    vals = [counts.astype(jnp.int64)] + list(dvals)
    with jax.named_scope("agg.reduce_delta"):
        if recombine:
            ukeys, uvals, ucount = batch_reduce(keys, mask, vals, kinds)
        else:
            with jax.named_scope("passthrough"):
                ukeys = jnp.where(mask, keys, EMPTY_KEY)
                uvals = tuple(jnp.where(mask, v, _neutral(k, v.dtype))
                              for v, k in zip(vals, kinds))
                ucount = jnp.sum(mask).astype(jnp.int32)
    new_state, needed, ch = _core_tail(spec, state, ukeys, uvals[1:],
                                       ucount, trail)
    ch["rows_in"] = jnp.sum(uvals[0])
    ch["in_counts"] = uvals[0]
    return new_state, needed, ch


def epoch_core_full(spec: DeviceAggSpec, state: DeviceAggState,
                    keys: jax.Array, signs: jax.Array, mask: jax.Array,
                    inputs: Tuple[Tuple[jax.Array, jax.Array], ...],
                    trail: bool = False):
    """epoch_core + the retractable min/max multisets: one traced program
    covering main-state merge and every minput's sort-merge + extremes.

    changes gains, per minput i, a dict `minput{i}`:
      old_min/old_max/new_min/new_max — group extremes (order-encoded
      int64) aligned with changes["keys"], gated by the main valid_count;
      u1/u2/u_cnt — touched (group, value) pairs and their post-merge
      multiplicities (0 = pair died), for host-side state persistence.
    """
    new_main, needed, ch = epoch_core(spec, state.main, keys, signs, mask,
                                      inputs, trail)
    s64 = jnp.where(mask, signs, 0).astype(jnp.int64)
    new_ms: List[SortedMultiset] = []
    ms_needed: List[jax.Array] = []
    for mi, desc in enumerate(spec.minputs):
        vals, valid = inputs[desc.call_idx]
        u1, u2, ud = ms_batch_reduce(keys, vals.astype(jnp.int64), s64,
                                     mask & valid)
        old_f, old_mn, old_mx = ms_group_minmax(state.minputs[mi],
                                                ch["keys"])
        nms, need = ms_merge(state.minputs[mi], u1, u2, ud)
        new_f, new_mn, new_mx = ms_group_minmax(nms, ch["keys"])
        pf, pc = ms_find(nms, u1, u2)
        ch[f"minput{mi}"] = {
            "old_found": old_f, "old_min": old_mn, "old_max": old_mx,
            "new_found": new_f, "new_min": new_mn, "new_max": new_mx,
            "u1": u1, "u2": u2, "u_cnt": jnp.where(pf, pc, 0),
        }
        new_ms.append(nms)
        ms_needed.append(need)
    return (DeviceAggState(new_main, tuple(new_ms)),
            (needed, tuple(ms_needed)), ch)


def local_epoch_step(spec: DeviceAggSpec, state: DeviceAggState,
                     keys: jax.Array, signs: jax.Array, mask: jax.Array,
                     inputs: Tuple[Tuple[jax.Array, jax.Array], ...],
                     trail: bool = False):
    """One epoch's LOCAL aggregation step over the rows this program
    instance owns. On a single chip that is every row; under mesh
    sharding (`device/shard_exec.py`) it is the shard's exchange-routed
    rows. The step is closed under vnode partitioning: groups partition
    by the vnode of their packed key, every row of a group reaches the
    group's owning shard (in global event order — the exchange flatten
    is source-major over contiguous event blocks), and count/sum/min/max
    reductions touch no cross-group state — so running it per shard is
    bit-identical to the global step, and the returned capacity needs
    are per-shard needs the pmax'd stats contract reports as the fleet
    high-water."""
    return epoch_core_full(spec, state, keys, signs, mask, inputs, trail)


@partial(jax.jit, static_argnames=("spec",))
def agg_epoch_step_full(spec: DeviceAggSpec, state: DeviceAggState,
                        keys: jax.Array, signs: jax.Array, mask: jax.Array,
                        inputs: Tuple[Tuple[jax.Array, jax.Array], ...]):
    return epoch_core_full(spec, state, keys, signs, mask, inputs)


@partial(jax.jit, static_argnames=("spec",))
def agg_epoch_step_packed(spec: DeviceAggSpec, state: DeviceAggState,
                          p64: jax.Array, p8: jax.Array):
    """agg_epoch_step_full fed from two packed host buffers — every
    host->device transfer has a fixed cost, so the host ships ONE int64 matrix
    (row 0: keys; row 1+i: call i's values, floats as raw f64 bits) and
    ONE int8 matrix (row 0: signs; row 1: row mask; row 2+i: call i's
    validity) instead of 3 + 2*n_calls separate arrays."""
    keys = p64[0]
    signs = p8[0].astype(jnp.int32)
    mask = p8[1] != 0
    ins = []
    for i, call in enumerate(spec.calls):
        v = p64[1 + i]
        # minput values are order-encoded int64 even for float columns
        if call.minput is None and jnp.issubdtype(call.acc_dtype,
                                                  jnp.floating):
            v = jax.lax.bitcast_convert_type(v, jnp.float64)
        ins.append((v, p8[2 + i] != 0))
    return epoch_core_full(spec, state, keys, signs, mask, tuple(ins))


@partial(jax.jit, static_argnames=("spec",))
def agg_epoch_step(spec: DeviceAggSpec, state: SortedState,
                   keys: jax.Array, signs: jax.Array, mask: jax.Array,
                   inputs: Tuple[Tuple[jax.Array, jax.Array], ...]):
    """Apply one epoch of rows; return (new_state, needed, change set).

    Change set arrays are sized [B] (unique touched keys); host assembles the
    barrier change chunk from them (insert/delete/update-pair per key).
    """
    return epoch_core(spec, state, keys, signs, mask, inputs)


# change-set entries only the fused pipeline (device/pipeline.py) reads;
# the SQL executor derives outputs from the raw payload columns instead,
# so flush_epoch skips transferring these to host
_PULL_DROP = ("old_out", "new_out", "old_null", "new_null")
# minput entries aligned with changes["keys"] (sliceable to its live head)
_MINPUT_KEYS_ALIGNED = ("old_found", "old_min", "old_max",
                        "new_found", "new_min", "new_max")


@partial(jax.jit, static_argnames=("m",))
def _slice_head(tree, m: int):
    return jax.tree_util.tree_map(
        lambda a: a[:m] if getattr(a, "ndim", 0) >= 1 else a, tree)


def _pull_changes(changes: Dict[str, Any], formatted: bool = True,
                  count: Optional[int] = None) -> Dict[str, Any]:
    """Device change set -> host numpy, minimizing the transfer: drop
    pipeline-only entries when unwanted, slice keys-aligned arrays to the
    live-prefix pow2 bucket (batch_reduce compacts live keys to a prefix),
    then one batched device_get. minput u1/u2/u_cnt have their own
    (possibly longer) live prefix, so they transfer unsliced."""
    ch = {k: v for k, v in changes.items()
          if formatted or k not in _PULL_DROP}
    b = ch["keys"].shape[0]
    if count is None:
        count = int(ch["count"])
    m = _bucket(count, lo=256)
    if m < b:
        flat = {k: v for k, v in ch.items() if not k.startswith("minput")}
        sliced = dict(_slice_head(flat, m))
        for k, v in ch.items():
            if k.startswith("minput"):
                sub = dict(v)
                head = _slice_head(
                    {kk: sub[kk] for kk in _MINPUT_KEYS_ALIGNED}, m)
                sub.update(head)
                sliced[k] = sub
        ch = sliced
    return jax.device_get(ch)


from .capacity import bucket as _bucket  # noqa: E402  (pow2 sizing)


def _acc_cast(v: np.ndarray) -> np.ndarray:
    """Host -> device accumulator dtype: floats widen to f64, ints to i64."""
    return v.astype(np.float64 if np.issubdtype(v.dtype, np.floating)
                    else np.int64)


class DeviceHashAgg:
    """Host wrapper: owns the state, buffers the epoch's rows, applies at
    barrier, grows capacity on overflow (recompile per pow2 bucket)."""

    def __init__(self, spec: DeviceAggSpec, capacity: int = 1024,
                 pull_formatted: bool = True):
        self.spec = spec
        # False = flush_epoch skips transferring the device-formatted
        # output entries (the SQL executor formats from raw payloads)
        self.pull_formatted = pull_formatted
        self.state = spec.make_state(capacity)
        self.minputs: Tuple[SortedMultiset, ...] = tuple(
            ms_make(capacity) for _ in spec.minputs)
        self._keys: List[np.ndarray] = []
        self._signs: List[np.ndarray] = []
        self._inputs: List[List[Tuple[np.ndarray, np.ndarray]]] = []

    def load_state(self, keys: np.ndarray,
                   vals: Sequence[np.ndarray]) -> None:
        """Recovery: install (key, payload...) rows as the current state
        (rows come from the persisted state table at the committed epoch)."""
        keys = sanitize_keys(keys)
        order = np.argsort(keys, kind="stable")
        n = len(keys)
        cap = _bucket(max(n, self.state.capacity))
        st = self.spec.make_state(cap)
        new_keys = np.asarray(st.keys).copy()
        new_keys[:n] = keys[order]
        new_vals = []
        for v0, v in zip(st.vals, vals):
            arr = np.asarray(v0).copy()
            arr[:n] = np.asarray(v)[order]
            new_vals.append(jnp.asarray(arr))
        self.state = SortedState(jnp.asarray(new_keys),
                                 jnp.asarray(np.int32(n)), tuple(new_vals))

    def live_main(self) -> Tuple[np.ndarray, List[np.ndarray]]:
        """Host pull of the live (key, payload...) rows — watermark state
        cleaning filters these and re-installs via load_state."""
        n = int(self.state.count)
        return (np.asarray(self.state.keys)[:n],
                [np.asarray(v)[:n] for v in self.state.vals])

    def live_minput(self, mi: int) -> Tuple[np.ndarray, np.ndarray,
                                            np.ndarray]:
        ms = self.minputs[mi]
        n = int(ms.count)
        return (np.asarray(ms.k1)[:n], np.asarray(ms.k2)[:n],
                np.asarray(ms.cnt)[:n])

    def load_minput(self, mi: int, k1: np.ndarray, k2: np.ndarray,
                    cnt: np.ndarray) -> None:
        """Recovery: install a minput multiset's (group, value, count) rows.
        Values (k2) are NOT sanitized — padding is k1-discriminated."""
        k1 = sanitize_keys(k1)
        k2 = np.asarray(k2, np.int64)
        order = np.lexsort((k2, k1))
        n = len(k1)
        cap = _bucket(max(n, self.minputs[mi].capacity))
        gk1 = np.full(cap, EMPTY_KEY, np.int64)
        gk2 = np.full(cap, EMPTY_KEY, np.int64)
        gc = np.zeros(cap, np.int64)
        gk1[:n], gk2[:n] = k1[order], k2[order]
        gc[:n] = np.asarray(cnt, np.int64)[order]
        ms = SortedMultiset(jnp.asarray(gk1), jnp.asarray(gk2),
                            jnp.asarray(np.int32(n)), jnp.asarray(gc))
        self.minputs = self.minputs[:mi] + (ms,) + self.minputs[mi + 1:]

    def push_rows(self, keys: np.ndarray, signs: np.ndarray,
                  inputs: Sequence[Tuple[np.ndarray, np.ndarray]]) -> None:
        if self.spec.append_only and (np.asarray(signs) < 0).any():
            raise ValueError(
                "retraction through an append-only (min/max) device agg — "
                "use the exact host path (aggregate/minput.rs analog)")
        self._keys.append(sanitize_keys(keys))
        self._signs.append(signs.astype(np.int32))
        self._inputs.append([(np.asarray(v), np.asarray(m)) for v, m in inputs])

    def flush_epoch(self) -> Optional[Dict[str, Any]]:
        """Run the epoch step; returns the change set (host numpy) or None.

        The pull is transfer-optimized for remote devices: formatted
        output entries (the fused-pipeline surface, unused by the SQL
        executor) are not transferred, keys-aligned arrays are sliced on
        device to the live-prefix bucket, and everything comes back in one
        batched `jax.device_get` instead of one round-trip per leaf.
        """
        if not self._keys:
            return None
        keys = np.concatenate(self._keys)
        signs = np.concatenate(self._signs)
        ncalls = len(self.spec.calls)
        ins = []
        for i in range(ncalls):
            vs = np.concatenate([b[i][0] for b in self._inputs])
            ms = np.concatenate([b[i][1] for b in self._inputs])
            ins.append((vs, ms))
        self._keys, self._signs, self._inputs = [], [], []
        b = _bucket(len(keys))
        n = len(keys)
        ncalls = len(self.spec.calls)
        # two packed buffers -> two H2D transfers total (see
        # agg_epoch_step_packed): int64 values (floats bit-cast) + int8 flags
        p64 = np.zeros((1 + ncalls, b), dtype=np.int64)
        p8 = np.zeros((2 + ncalls, b), dtype=np.int8)
        p64[0, :n] = keys
        p8[0, :n] = signs
        p8[1, :n] = 1
        for i, (v, m) in enumerate(ins):
            av = _acc_cast(v)
            p64[1 + i, :n] = av.view(np.int64) \
                if av.dtype == np.float64 else av
            p8[2 + i, :n] = m.astype(np.int8)
        jp64, jp8 = jnp.asarray(p64), jnp.asarray(p8)
        while True:
            full = DeviceAggState(self.state, self.minputs)
            new_full, (needed, ms_needed), changes = agg_epoch_step_packed(
                self.spec, full, jp64, jp8)
            # one pull for every control scalar (each device_get is a
            # host sync, so per-scalar int() calls add up)
            needed_h, ms_needed_h, count_h = jax.device_get(
                (needed, ms_needed, changes["count"]))
            # predictive growth (device/capacity.py): size ahead of the
            # observed need so one grow skips the intermediate pow2
            # buckets (each bucket is a retrace)
            from .capacity import predict_capacity
            grown = False
            if int(needed_h) > self.state.capacity:
                self.state = grow_state(
                    self.state,
                    predict_capacity(int(needed_h), self.state.capacity),
                    self.spec.kinds)
                grown = True
            for i, nd in enumerate(ms_needed_h):
                if int(nd) > self.minputs[i].capacity:
                    ms = ms_grow(self.minputs[i],
                                 predict_capacity(int(nd),
                                                  self.minputs[i].capacity))
                    self.minputs = (self.minputs[:i] + (ms,)
                                    + self.minputs[i + 1:])
                    grown = True
            if grown:
                continue
            self.state, self.minputs = new_full.main, new_full.minputs
            return _pull_changes(changes, self.pull_formatted,
                                 count=int(count_h))
