"""Sorted-run keyed state in HBM + functional epoch-merge ops.

The device analog of `StateTable` + executor caches
(`src/stream/src/common/table/state_table.rs:91`,
`src/stream/src/executor/aggregate/hash_agg.rs:52`): a fixed-capacity,
key-sorted set of (key, payload...) slots. All ops are pure functions of
jax arrays with static shapes, so an epoch apply is one jitted XLA program:

    delta rows --batch_reduce--> unique per-key deltas
               --merge--------> new state (+ needed-slot count for resize)
               --merge_changes-> each delta key's old and new payloads
    queries    --lookup-------> gathered payloads (keys no merge is moving)

Whatever has to follow rows through a merge does so by position, never by
searching for the keys again: `merge(..., return_trail=True)` also says
how the rows moved (`MergeTrail`). Its `first` / `last` name the input rows
each output slot's run came from — the merge itself moves every payload
column through them, once — so a column that lives BESIDE the state (the
state tier's touch stamps, `device/tiering.py`) is re-aligned with one
gather; `merge_changes` reads it from the delta's side — which state row
each delta key met and what the pair combined to — which is the agg
step's whole change set.

Empty slots hold EMPTY_KEY (int64 max) so they sort past every live key and
binary search stays valid. Capacity growth is host-driven: `merge` reports
how many slots it *needed*; when that exceeds capacity the host re-pads the
old state to 2x and re-runs (one recompile per capacity bucket).
"""
from __future__ import annotations

import enum
from typing import Any, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# RESERVED KEY (device API boundary): int64 max marks padding slots. Host
# key projections must never emit it — core/vnode.py remaps hash64 outputs,
# and `sanitize_keys` below remaps raw int64 keys at the device wrappers'
# push boundary. A key equal to EMPTY_KEY would be masked from batch_reduce,
# dropped by merge, and filtered from the all-to-all receive mask.
EMPTY_KEY = np.int64(np.iinfo(np.int64).max)


def sanitize_keys(keys: np.ndarray) -> np.ndarray:
    """Remap a legitimate key equal to the EMPTY_KEY sentinel to
    EMPTY_KEY-1 (merging those two key values is the accepted, documented
    collision — vanishingly rarer than the hash64 collision class)."""
    keys = np.asarray(keys, dtype=np.int64)
    return np.where(keys == EMPTY_KEY, EMPTY_KEY - 1, keys)


class ReduceKind(enum.IntEnum):
    """How a payload column combines across rows of the same key."""
    SUM = 0      # additive (counts, sums; retraction = sign-weighted add)
    MIN = 1      # append-only min
    MAX = 2      # append-only max
    REPLACE = 3  # newest wins (MV upsert columns; delta overwrites state)


def _neutral(kind: ReduceKind, dtype) -> jnp.ndarray:
    if kind in (ReduceKind.SUM, ReduceKind.REPLACE):
        return jnp.zeros((), dtype=dtype)
    if jnp.issubdtype(jnp.dtype(dtype), jnp.bool_):
        return jnp.zeros((), dtype=dtype)
    big = (jnp.iinfo(dtype).max if jnp.issubdtype(dtype, jnp.integer)
           else jnp.asarray(jnp.inf, dtype=dtype))
    small = (jnp.iinfo(dtype).min if jnp.issubdtype(dtype, jnp.integer)
             else jnp.asarray(-jnp.inf, dtype=dtype))
    return jnp.asarray(big if kind == ReduceKind.MIN else small, dtype=dtype)


def _combine(kind: ReduceKind, a, b):
    """a = the state-side row, b = the delta-side row (stable sort keeps
    state first within an equal-key pair — merge() relies on this order)."""
    if kind == ReduceKind.SUM:
        return a + b
    if kind == ReduceKind.REPLACE:
        return b
    return jnp.minimum(a, b) if kind == ReduceKind.MIN else jnp.maximum(a, b)


class SortedState(NamedTuple):
    """keys sorted ascending; slots >= count hold EMPTY_KEY / neutral vals."""
    keys: jax.Array                  # int64 (C,)
    count: jax.Array                 # int32 scalar — live slots
    vals: Tuple[jax.Array, ...]      # each (C,), payload columns

    @property
    def capacity(self) -> int:
        return self.keys.shape[0]


def make_state(capacity: int, val_dtypes: Sequence, kinds: Sequence[ReduceKind]
               ) -> SortedState:
    keys = jnp.full((capacity,), EMPTY_KEY, dtype=jnp.int64)
    vals = tuple(jnp.full((capacity,), _neutral(k, jnp.dtype(d)), dtype=d)
                 for d, k in zip(val_dtypes, kinds))
    return SortedState(keys=keys, count=jnp.zeros((), jnp.int32), vals=vals)


def grow_state(state: SortedState, new_capacity: int,
               kinds: Sequence[ReduceKind]) -> SortedState:
    """Host-side re-pad (not jitted); sorted order is preserved because pads
    are EMPTY_KEY at the tail."""
    c = state.capacity
    assert new_capacity >= c
    pad = new_capacity - c
    keys = jnp.concatenate([state.keys,
                            jnp.full((pad,), EMPTY_KEY, dtype=jnp.int64)])
    vals = tuple(
        jnp.concatenate([v, jnp.full((pad,), _neutral(k, v.dtype),
                                     dtype=v.dtype)])
        for v, k in zip(state.vals, kinds))
    return SortedState(keys=keys, count=state.count, vals=vals)


def batch_reduce(keys: jax.Array, mask: jax.Array,
                 vals: Sequence[jax.Array], kinds: Sequence[ReduceKind]
                 ) -> Tuple[jax.Array, Tuple[jax.Array, ...], jax.Array]:
    """Pre-reduce a row batch to unique per-key deltas.

    Masked-out rows are neutralized (key -> EMPTY_KEY, value -> neutral).
    Returns (ukeys[B], uvals[B each], ucount) where only the first `ucount`
    slots are live; the rest are EMPTY_KEY. Output is key-sorted.
    """
    b = keys.shape[0]
    keys = jnp.where(mask, keys, EMPTY_KEY)
    vals = [jnp.where(mask, v, _neutral(k, v.dtype))
            for v, k in zip(vals, kinds)]
    # original row position, for REPLACE (last write in arrival order wins)
    arrival = jnp.where(mask, jnp.arange(b), -1)
    (keys,), sorted_cols = sort_cols([keys], [arrival] + list(vals))
    arrival, vals = sorted_cols[0], list(sorted_cols[1:])
    boundary = jnp.concatenate(
        [jnp.ones((1,), bool), keys[1:] != keys[:-1]])
    seg = running_sum(boundary) - 1
    ukeys = jnp.full((b,), EMPTY_KEY, dtype=jnp.int64).at[seg].set(keys)
    out = []
    for v, k in zip(vals, kinds):
        if k == ReduceKind.SUM:
            r = jax.ops.segment_sum(v, seg, num_segments=b)
        elif k == ReduceKind.MIN:
            r = jax.ops.segment_min(v, seg, num_segments=b)
        elif k == ReduceKind.REPLACE:
            last = jax.ops.segment_max(arrival, seg, num_segments=b)
            safe = jnp.where(arrival >= 0, arrival, b)  # b = OOB, dropped
            inv = jnp.zeros(b, dtype=jnp.int32).at[safe].set(
                jnp.arange(b, dtype=jnp.int32), mode="drop")
            r = jnp.where(last >= 0, v[inv[jnp.clip(last, 0)]],
                          _neutral(k, v.dtype))
        else:
            r = jax.ops.segment_max(v, seg, num_segments=b)
        # untouched segments get segment-op defaults; force neutral dtype-wise
        live = jnp.arange(b) <= seg[-1]
        r = jnp.where(live, r.astype(v.dtype), _neutral(k, v.dtype))
        out.append(r)
    ucount = jnp.sum(boundary & (keys != EMPTY_KEY)).astype(jnp.int32)
    # EMPTY_KEY rows sorted last => their segment is the final one; clear it
    out = [jnp.where(ukeys == EMPTY_KEY, _neutral(k, v.dtype), v)
           for v, k in zip(out, kinds)]
    return ukeys, tuple(out), ucount


_CHEAP_COMPILE: Optional[bool] = None


def cheap_compile() -> bool:
    """Kernel-form policy: ONE form on every backend, the compile-cheap
    one. XLA's compile time for the variadic-sort forms (multi-operand
    `lax.sort`, cumsum, searchsorted(method='sort')) falls off a cliff
    between 4 K and 16 K state slots on the TPU compiler as on the CPU's
    — five to six minutes for ONE node program at bench shapes, against
    one to two for the gather/scan forms (PR 22 offline compiles for a
    v5e, CHANGES.md) — and a fused database has a dozen such programs,
    so the variadic forms do not start inside any serving budget.
    r04/r05 measured them up to 3x faster at RUN time for a 2^20-slot
    agg; `RW_TPU_CHEAP_COMPILE=0` keeps them reachable until a benchmark
    settles that trade (ROADMAP D3)."""
    global _CHEAP_COMPILE
    if _CHEAP_COMPILE is None:
        import os
        _CHEAP_COMPILE = os.environ.get("RW_TPU_CHEAP_COMPILE", "1") \
            not in ("", "0", "false")
    return _CHEAP_COMPILE


def search_method() -> str:
    return "scan" if cheap_compile() else "sort"


def running_sum(x: jax.Array) -> jax.Array:
    """Inclusive prefix sum of an int mask/count vector."""
    if cheap_compile():
        return jax.lax.associative_scan(jnp.add, x.astype(jnp.int64))
    return jnp.cumsum(x.astype(jnp.int64))


def sort_cols(keys: Sequence[jax.Array], cols: Sequence[jax.Array],
              return_perm: bool = False) -> Tuple:
    """Stable sort of payload columns by key columns: rank-sort + gathers
    beyond 2 payloads (fastest compile — see cheap_compile), else one
    variadic `lax.sort`. With `return_perm` the rank-sort form is taken
    whatever the payload count and its int32 permutation (sorted position
    -> input row) is returned third."""
    nk = len(keys)
    if (len(cols) <= 2 or not cheap_compile()) and not return_perm:
        out = jax.lax.sort(list(keys) + list(cols), num_keys=nk,
                           is_stable=True)
        return tuple(out[:nk]), tuple(out[nk:])
    n = keys[0].shape[0]
    rank = jnp.arange(n, dtype=jnp.int32)
    out = jax.lax.sort(list(keys) + [rank], num_keys=nk, is_stable=True)
    idx = out[nk]
    res = tuple(out[:nk]), tuple(c[idx] for c in cols)
    return res + (idx,) if return_perm else res


def compact_rows(alive: jax.Array, keys: Sequence[jax.Array],
                 cols: Sequence[jax.Array], out_len: int,
                 fills: Sequence[Any], return_perm: bool = False) -> Tuple:
    """Stable compaction of alive rows to the front, dead rows replaced by
    `fills`, result truncated to out_len. Implemented as one variadic sort
    on (dead, position) — NOT a scatter (see sort_cols). Row order among
    alive rows is preserved, so key-sorted input stays key-sorted. With
    `return_perm` the index form is taken whatever the column count and
    its int32 permutation (output slot -> input row) is returned last."""
    n = alive.shape[0]
    rank = jnp.where(alive, 0, n).astype(jnp.int32) \
        + jnp.arange(n, dtype=jnp.int32)
    masked = [jnp.where(alive, a, f) for a, f in
              zip(list(keys) + list(cols), fills)]
    if (len(masked) <= 3 or not cheap_compile()) and not return_perm:
        out = jax.lax.sort([rank] + masked, num_keys=1, is_stable=False)
        return tuple(a[:out_len] for a in out[1:])
    _, idx = jax.lax.sort([rank, jnp.arange(n, dtype=jnp.int32)],
                          num_keys=1, is_stable=False)
    idx = idx[:out_len]
    out = tuple(a[idx] for a in masked)
    return out + (idx,) if return_perm else out


def _words(cols: Sequence[jax.Array]) -> jax.Array:
    """Columns as the rows of one int32 matrix (words, n) — a 64-bit
    column is two words (low, high), a narrower one one word — so that one
    gather along its second axis moves every column at once: on the chip a
    gather costs by the index, hardly by the words (PR 38: 2^20 indices
    into 2^21 rows take 17.4 ms for one int64 column, 25.6 ms for twelve
    words). `_unwords` gives the columns back bit for bit."""
    out = []
    for c in cols:
        dt = jnp.dtype(c.dtype)
        if dt == jnp.bool_:
            out.append(c.astype(jnp.int32))
        elif dt.itemsize == 8:
            x = c if dt == jnp.int64 else \
                jax.lax.bitcast_convert_type(c, jnp.int64)
            lo = (x & 0xFFFFFFFF).astype(jnp.uint32)
            out += [jax.lax.bitcast_convert_type(lo, jnp.int32),
                    (x >> 32).astype(jnp.int32)]
        elif dt.itemsize == 4:
            out.append(c if dt == jnp.int32 else
                       jax.lax.bitcast_convert_type(c, jnp.int32))
        else:
            narrow = jnp.int8 if dt.itemsize == 1 else jnp.int16
            out.append(jax.lax.bitcast_convert_type(c, narrow)
                       .astype(jnp.int32))
    return jnp.stack(out)


def _unwords(w: jax.Array, dtypes: Sequence) -> Tuple[jax.Array, ...]:
    """The columns `_words` packed, with these dtypes, from its rows."""
    cols, i = [], 0
    for dt in map(jnp.dtype, dtypes):
        if dt == jnp.bool_:
            cols.append(w[i] != 0)
        elif dt.itemsize == 8:
            lo = jax.lax.bitcast_convert_type(w[i], jnp.uint32)
            x = (w[i + 1].astype(jnp.int64) << 32) | lo.astype(jnp.int64)
            cols.append(x if dt == jnp.int64 else
                        jax.lax.bitcast_convert_type(x, dt))
            i += 1
        elif dt.itemsize == 4:
            cols.append(w[i] if dt == jnp.int32 else
                        jax.lax.bitcast_convert_type(w[i], dt))
        else:
            narrow = jnp.int8 if dt.itemsize == 1 else jnp.int16
            cols.append(jax.lax.bitcast_convert_type(w[i].astype(narrow),
                                                     dt))
        i += 1
    return tuple(cols)


def _take(cols: Sequence[jax.Array], idx: jax.Array) -> Tuple[jax.Array, ...]:
    """`tuple(c[idx] for c in cols)` as one gather of their words."""
    return _unwords(_words(cols)[:, idx], [c.dtype for c in cols])


class MergeTrail(NamedTuple):
    """How `merge(..., return_trail=True)` moved its rows. Indices are
    input rows: into concat(state rows, delta rows), so one < capacity is
    that state row and one >= capacity the delta row `index - capacity`.

    `first` / `last` are the two permutations already composed: for every
    output slot, the input row of the first and of the last row of the
    key's run — the state row where the key had one, the delta row where
    the delta has one; equal for a run of one. A column kept beside the
    state (the tier's touch stamps) follows its rows with one gather
    through either. They are defined in LIVE slots only (an empty slot
    reads garbage: gate on the new state's key). `sort_perm` and
    `same_next` are the sort's own, n = capacity + delta rows wide: what
    `merge_changes` reads each delta key's partner off."""
    first: jax.Array            # int32 (C,) output slot -> its run's first row
    last: jax.Array             # int32 (C,) output slot -> its run's last row
    sort_perm: jax.Array        # int32 (n,) sorted position -> input row
    same_next: jax.Array        # bool (n,) the next sorted row has this key


def merge_changes(state: SortedState, new_state: SortedState,
                  dkeys: jax.Array, dvals: Sequence[jax.Array],
                  kinds: Sequence[ReduceKind], trail: MergeTrail,
                  drop_dead: bool = True, dead_col: int = 0) -> Tuple:
    """What `merge(state, dkeys, dvals, ...)` did to each delta key, read
    off the merge by position: (old_found, old_vals, new_found, new_vals),
    each aligned with `dkeys` — exactly `lookup(state, dkeys)` and
    `lookup(new_state, dkeys)` wherever found (elsewhere garbage, as
    there; gate on found), without a search: O(B) gathers where two
    lookups walk log2(capacity) rounds each.

    The old side: the stable sort put a key's state row directly before
    its delta row, so in sorted space a delta row's partner is the input
    row of the position before it (`met`, -1 where the state had none;
    read off the trail's `sort_perm` and `same_next`). The trail's
    `first` / `last` cannot say it: they name the output slots, and a
    group that died or a truncated merge's tail has none. What brings
    `met` back to delta order is one two-operand sort over "is a delta
    row" (`compact_rows` with the answer as its one column — NOT a
    scatter, and nothing gathered): the delta is key-sorted with its pads
    last (what `batch_reduce` leaves: `agg_step.precombine_core`'s output
    contract) and the merge's sort is stable, so the j-th delta row in
    sorted order IS delta row j. Then one gather of the state's words
    (`_take`): every payload column at once.
    The new side needs no gather at all: a key's run is its state row and
    its delta row, so the merged payload is their `_combine` (the delta's
    own value where the state had none), the group is alive by the merge's
    own rule (`dead_col` != 0), and of a truncated merge (`needed` >
    capacity: the caller grows and replays) the new state holds the alive
    keys up to its last slot's."""
    c, b = state.capacity, dkeys.shape[0]
    with jax.named_scope("by_position"):
        sp = trail.sort_perm
        same_prev = jnp.concatenate([jnp.zeros((1,), bool),
                                     trail.same_next[:-1]])
        met = jnp.where(same_prev, jnp.concatenate([sp[:1], sp[:-1]]), -1)
        (met,) = compact_rows(sp >= c, [met], [], b, [-1])
        real = dkeys != EMPTY_KEY       # a pad's neighbour is another pad
        old_found = (met >= 0) & real
        old_vals = _take(state.vals, jnp.clip(met, 0, c - 1))
        dvals = [dv.astype(ov.dtype) for dv, ov in zip(dvals, old_vals)]
        new_vals = tuple(jnp.where(old_found, _combine(k, ov, dv), dv)
                         for k, ov, dv in zip(kinds, old_vals, dvals))
        new_found = real & (dkeys <= new_state.keys[c - 1])
        if drop_dead:
            new_found &= new_vals[dead_col] != 0
    return old_found, old_vals, new_found, new_vals


def merge(state: SortedState, dkeys: jax.Array,
          dvals: Sequence[jax.Array], kinds: Sequence[ReduceKind],
          drop_dead: bool = True, dead_col: int = 0,
          return_trail: bool = False) -> Tuple:
    """Merge unique per-key deltas (from `batch_reduce`) into the state.

    Every key appears at most once in `state` and at most once in the delta,
    so after the stable merge-sort (state side first on ties) each key forms
    a run of length <= 2 — combining is a single shifted compare, no segment
    scan. With `drop_dead`, rows whose combined `dead_col` payload
    (row_count) hits 0 are compacted away — group death (`hash_agg.rs`
    emits DELETE and drops state when count reaches 0).

    Each payload column moves once, from its input row straight into its
    output slot: one stable sort of the keys (carrying `dead_col`, the one
    column whose merged value decides which runs live, and the row ranks)
    gives the sort's permutation; one sort of the alive runs to the front
    carries the input rows of each run's first and last row (the two
    permutations composed, `MergeTrail.first` / `.last`); then the key
    and every column are read at `last` — a REPLACE column's value —
    and, where any column combines, at `first`, each a single gather of
    all their words (`_words`). Nothing is gathered into sorted order.
    Under `cheap_compile()` off and with no trail the one variadic sort of
    every column (`_merge_variadic`) is taken instead.

    Returns (new_state, needed) — `needed` > capacity means the merge was
    truncated and must be retried on a grown state. With `return_trail` a
    third value, the `MergeTrail`, says how the rows moved: a column kept
    beside the state rides the merge through its `first` or `last`, and
    `merge_changes` reads each delta key's old and new payloads off it (the
    agg step's change set).
    """
    if not (cheap_compile() or return_trail):
        return _merge_variadic(state, dkeys, dvals, kinds, drop_dead,
                               dead_col)
    c = state.capacity
    cols = [jnp.concatenate([sv, dv.astype(sv.dtype)])
            for sv, dv in zip(state.vals, dvals)]
    # named scopes are HLO metadata only: they put these stages' device
    # time under a name in a profiler trace and change no instruction
    with jax.named_scope("merge.sort"):
        keys_in = jnp.concatenate([state.keys, dkeys])
        n = keys_in.shape[0]
        rank = jnp.arange(n, dtype=jnp.int32)
        carried = [cols[dead_col]] if drop_dead else []
        keys, *dead, perm = jax.lax.sort([keys_in] + carried + [rank],
                                         num_keys=1, is_stable=True)
    same_next = jnp.concatenate([keys[:-1] == keys[1:], jnp.zeros((1,), bool)])
    same_prev = jnp.concatenate([jnp.zeros((1,), bool), keys[1:] == keys[:-1]])
    alive = ~same_prev & (keys != EMPTY_KEY)
    if drop_dead:
        nxt = jnp.concatenate([dead[0][1:], dead[0][-1:]])
        alive &= jnp.where(same_next, _combine(kinds[dead_col], dead[0], nxt),
                           dead[0]) != 0
    needed = jnp.sum(alive).astype(jnp.int32)
    with jax.named_scope("merge.compact"):
        perm_last = jnp.where(same_next,
                              jnp.concatenate([perm[1:], perm[-1:]]), perm)
        front = jnp.where(alive, 0, n).astype(jnp.int32) + rank
        _, first, last = jax.lax.sort([front, perm, perm_last], num_keys=1,
                                      is_stable=False)
        first, last = first[:c], last[:c]
    with jax.named_scope("merge.gather"):
        # a run's key and values: its last row's for REPLACE, else its
        # first row's combined with its last's where it has two (state
        # row first); the keys ride along in rows a TPU tile pads anyway
        # (the bid agg's words are 14 of 16, the MV's 12)
        words = _words([keys_in] + cols)
        dtypes = [keys_in.dtype] + [col.dtype for col in cols]
        okeys, *at_last = _unwords(words[:, last], dtypes)
        at_first = _unwords(words[:, first], dtypes)[1:] \
            if any(k != ReduceKind.REPLACE for k in kinds) else at_last
        two = first != last
        live = jnp.arange(c) < needed
        okeys = jnp.where(live, okeys, EMPTY_KEY)
        vals = tuple(
            jnp.where(live, b if k == ReduceKind.REPLACE else
                      jnp.where(two, _combine(k, a, b), a),
                      _neutral(k, b.dtype))
            for a, b, k in zip(at_first, at_last, kinds))
    new = SortedState(okeys, jnp.minimum(needed, c), vals)
    if return_trail:
        return new, needed, MergeTrail(first, last, perm, same_next)
    return new, needed


def _merge_variadic(state: SortedState, dkeys: jax.Array,
                    dvals: Sequence[jax.Array], kinds: Sequence[ReduceKind],
                    drop_dead: bool, dead_col: int) -> Tuple:
    """`merge` as one variadic sort of every column, a neighbour combine
    and one variadic compaction (`RW_TPU_CHEAP_COMPILE=0`, ROADMAP D3)."""
    c = state.capacity
    with jax.named_scope("merge.sort"):
        keys = jnp.concatenate([state.keys, dkeys])
        vals = [jnp.concatenate([sv, dv.astype(sv.dtype)])
                for sv, dv in zip(state.vals, dvals)]
        (keys,), vals = sort_cols([keys], vals)
    same_next = jnp.concatenate([keys[:-1] == keys[1:], jnp.zeros((1,), bool)])
    same_prev = jnp.concatenate([jnp.zeros((1,), bool), keys[1:] == keys[:-1]])
    merged = []
    for v, k in zip(vals, kinds):
        nxt = jnp.concatenate([v[1:], v[-1:]])
        merged.append(jnp.where(same_next, _combine(k, v, nxt), v))
    alive = ~same_prev & (keys != EMPTY_KEY)
    if drop_dead:
        alive &= merged[dead_col] != 0
    needed = jnp.sum(alive).astype(jnp.int32)
    with jax.named_scope("merge.compact"):
        out = compact_rows(alive, [keys], merged, c,
                           [EMPTY_KEY] + [_neutral(k, v.dtype)
                                          for v, k in zip(merged, kinds)])
    return SortedState(out[0], jnp.minimum(needed, c), tuple(out[1:])), needed


def lookup(state: SortedState, qkeys: jax.Array
           ) -> Tuple[jax.Array, Tuple[jax.Array, ...]]:
    """Binary-search gather, for keys no merge is moving: the tier's
    evict cores look up the keys they are about to take out. (What a merge
    did to its own delta keys is `merge_changes`, by position; this is its
    plain reference in the tests.) Returns (found[B], vals at match —
    neutral-ish garbage where not found; gate on `found`)."""
    with jax.named_scope("lookup"):
        idx = jnp.searchsorted(state.keys, qkeys, method=search_method())
        idx = jnp.minimum(idx, state.capacity - 1)
        found = (state.keys[idx] == qkeys) & (qkeys != EMPTY_KEY)
        return found, tuple(v[idx] for v in state.vals)
