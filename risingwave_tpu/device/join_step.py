"""Jitted streaming hash-join epoch step (inner equi-join).

Device analog of `HashJoinExecutor`'s eq-join hot loop
(`src/stream/src/executor/hash_join.rs:575-686`), re-shaped for XLA: each
side's state is a SORTED MULTIMAP — rows ordered by (join_key, pk) in
fixed-capacity HBM arrays — so a probe is a `searchsorted` range lookup and
the per-epoch maintenance is the same sort-merge pattern as the agg state
(sorted_state.py). The incremental-join algebra per epoch:

    out  =  dA >< B_old   +   A_new >< dB          (A_new = A_old + dA)

A probe does ONE binary search a probe row (where its key's run starts in
the side) and none a pair slot: where the run ends is read off the side (a
reverse prefix minimum over its "last of its run" flags), and the ragged
match output becomes static-shape via a counted expansion — every probe row
marks the pair slot its running match count names, a prefix sum of the marks
maps pair t back to its probe row.
Inner joins only — outer/semi/anti need degree bookkeeping and stay on the
exact host path (join.py), the same split the reference draws between its
fast append-only executors and the general ones.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .sorted_state import EMPTY_KEY, running_sum, sanitize_keys, search_method


class JoinSide(NamedTuple):
    """Sorted-by-(jk, pk) multimap; empty slots hold EMPTY_KEY twice."""
    jk: jax.Array                   # int64 (C,) join key
    pk: jax.Array                   # int64 (C,) row identity (stream key)
    count: jax.Array                # int32 scalar
    vals: Tuple[jax.Array, ...]     # payload columns (C,)


def make_side(capacity: int, val_dtypes: Sequence) -> JoinSide:
    return JoinSide(
        jnp.full((capacity,), EMPTY_KEY, dtype=jnp.int64),
        jnp.full((capacity,), EMPTY_KEY, dtype=jnp.int64),
        jnp.zeros((), jnp.int32),
        tuple(jnp.zeros((capacity,), dtype=d) for d in val_dtypes))


def grow_side(side: JoinSide, new_capacity: int) -> JoinSide:
    pad = new_capacity - side.jk.shape[0]
    assert pad >= 0
    return JoinSide(
        jnp.concatenate([side.jk, jnp.full((pad,), EMPTY_KEY, jnp.int64)]),
        jnp.concatenate([side.pk, jnp.full((pad,), EMPTY_KEY, jnp.int64)]),
        side.count,
        tuple(jnp.concatenate([v, jnp.zeros((pad,), v.dtype)])
              for v in side.vals))


def batch_reduce_rows(jk, pk, signs, mask, vals):
    """Unique (jk, pk) deltas: net sign (sum), payload (last write wins).
    Rows whose net sign is 0 are dropped at merge. Output is (jk,pk)-sorted
    with EMPTY padding."""
    from .sorted_state import sort_cols
    b = jk.shape[0]
    jk = jnp.where(mask, jk, EMPTY_KEY)
    pk = jnp.where(mask, pk, EMPTY_KEY)
    signs = jnp.where(mask, signs, 0)
    (jk, pk), out = sort_cols([jk, pk], [signs] + list(vals))
    signs, vals = out[0], list(out[1:])
    same = jnp.concatenate([jnp.zeros((1,), bool),
                            (jk[1:] == jk[:-1]) & (pk[1:] == pk[:-1])])
    seg = running_sum(~same) - 1
    usign = jax.ops.segment_sum(signs.astype(jnp.int32), seg, num_segments=b)
    ujk = jnp.full((b,), EMPTY_KEY, jnp.int64).at[seg].set(jk)
    upk = jnp.full((b,), EMPTY_KEY, jnp.int64).at[seg].set(pk)
    # last write per segment
    arrival = jnp.where(jk != EMPTY_KEY, jnp.arange(b), -1)
    last = jax.ops.segment_max(arrival, seg, num_segments=b)
    uvals = tuple(v[jnp.clip(last, 0)] for v in vals)
    live = ujk != EMPTY_KEY
    usign = jnp.where(live, usign, 0)
    return ujk, upk, usign, uvals


def merge_side(side: JoinSide, djk, dpk, dsign, dvals,
               return_trail: bool = False) -> Tuple:
    """Apply unique (jk,pk) deltas: +1 insert/upsert, -1 delete, 0 no-op.

    One stable variadic lexsort (state rows concatenated first, so they
    precede their delta on ties — sorted_state.sort_cols rationale) +
    combine + sort-based compaction. Zero-sign deltas merge as no-ops:
    they pair with their state row (if any) contributing pres 0, and
    compact away alone (pres_m == 0).

    Returns (new_side, needed); with `return_trail` also the merge's
    `MergeTrail` (sorted_state.merge has the contract), without it the
    traced program is the one it always was."""
    from .sorted_state import MergeTrail, compact_rows, sort_cols
    c = side.jk.shape[0]
    jk = jnp.concatenate([side.jk, djk])
    pk = jnp.concatenate([side.pk, dpk])
    pres = jnp.concatenate([(side.jk != EMPTY_KEY).astype(jnp.int32),
                            dsign.astype(jnp.int32)])
    vals = [jnp.concatenate([sv, dv.astype(sv.dtype)])
            for sv, dv in zip(side.vals, dvals)]
    (jk, pk), out, *sperm = sort_cols([jk, pk], [pres] + vals,
                                      return_perm=return_trail)
    pres, vals = out[0], list(out[1:])
    same_next = jnp.concatenate(
        [(jk[:-1] == jk[1:]) & (pk[:-1] == pk[1:]), jnp.zeros((1,), bool)])
    same_prev = jnp.concatenate(
        [jnp.zeros((1,), bool), (jk[1:] == jk[:-1]) & (pk[1:] == pk[:-1])])
    nxt = lambda a: jnp.concatenate([a[1:], a[-1:]])
    pres_m = jnp.where(same_next, jnp.clip(pres + nxt(pres), 0, 1), pres)
    vals_m = [jnp.where(same_next & (nxt(pres) > 0), nxt(v), v)
              for v in vals]   # upsert takes the delta payload
    alive = ~same_prev & (jk != EMPTY_KEY) & (pres_m > 0)
    needed = jnp.sum(alive).astype(jnp.int32)
    out = compact_rows(alive, [jk, pk], vals_m, c,
                       [EMPTY_KEY, EMPTY_KEY] + [0] * len(vals_m),
                       return_perm=return_trail)
    new = JoinSide(out[0], out[1], jnp.minimum(needed, c),
                   tuple(out[2:2 + len(vals_m)]))
    if return_trail:
        return new, needed, MergeTrail(sperm[0], same_next, out[-1])
    return new, needed


def mark_key_runs(jk: jax.Array, queries: jax.Array) -> jax.Array:
    """bool (C,): the rows of a jk-sorted side whose join key is among
    `queries` (keys in any order, repeats welcome, EMPTY_KEY = no query).
    One binary search per QUERY and none per row: a query finds the first
    row of its key's run and marks it (a scatter of as many elements as
    there are queries), and the mark spreads along the run in a prefix
    max over (run start, mark) codes — so the searches' cost follows the
    deltas that ask, not the capacity of the side."""
    c = jk.shape[0]
    lo = jnp.minimum(jnp.searchsorted(jk, queries, side="left",
                                      method=search_method()), c - 1)
    ok = (jk[lo] == queries) & (queries != EMPTY_KEY)
    mark = jnp.zeros((c,), jnp.int32).at[jnp.where(ok, lo, c)].set(
        1, mode="drop")
    first = jnp.concatenate([jnp.ones((1,), bool), jk[1:] != jk[:-1]])
    code = jnp.where(first, 2 * jnp.arange(c, dtype=jnp.int32) + mark, -1)
    return (jax.lax.associative_scan(jnp.maximum, code) & 1) == 1


def probe(side: JoinSide, qjk, qmask, m: int):
    """All matches of each probe key: (probe_row[m], state_idx[m], mask[m],
    needed_pairs). One binary search a probe row, none a pair slot.

    `range`: `lo`, where a key's run starts in the side, is searched; where
    it ends is a property of the sorted SIDE, not of the query — one pass
    over the capacity gives every slot the last slot of its run (a reverse
    prefix minimum over "last of its run" positions), and `hi` is one gather
    a probe row. `expand`: pair slot t belongs to the probe row whose running
    match count is the first above t, i.e. to as many rows as have counts
    <= t; the slots are 0..m-1, so every row marks the slot its running
    count names (one scatter a ROW, counts past the buffer dropped) and a
    prefix sum spreads the marks."""
    c = side.jk.shape[0]
    qjk = jnp.where(qmask, qjk, EMPTY_KEY)
    with jax.named_scope("range"):
        lo = jnp.searchsorted(side.jk, qjk, side="left",
                              method=search_method())
        last = jnp.concatenate([side.jk[1:] != side.jk[:-1],
                                jnp.ones((1,), bool)])
        run_end = jax.lax.associative_scan(
            jnp.minimum, jnp.where(last, jnp.arange(c, dtype=jnp.int32), c),
            reverse=True)
        at = jnp.minimum(lo, c - 1)
        hi = jnp.where((lo < c) & (side.jk[at] == qjk), run_end[at] + 1, lo)
        cnt = jnp.where(qmask & (qjk != EMPTY_KEY), hi - lo, 0)
    with jax.named_scope("expand"):
        off = running_sum(cnt)
        total = off[-1]
        t = jnp.arange(m)
        row = running_sum(jnp.zeros((m,), jnp.int32).at[off].add(
            1, mode="drop")).astype(jnp.int32)
        row_c = jnp.clip(row, 0, qjk.shape[0] - 1)
        prev = jnp.where(row_c > 0, off[row_c - 1], 0)
        sidx = lo[row_c] + (t - prev)
        mask = t < total
    return row_c, jnp.clip(sidx, 0, c - 1), mask, total


def join_core(a: JoinSide, b: JoinSide,
              a_jk, a_pk, a_sign, a_mask, a_vals,
              b_jk, b_pk, b_sign, b_mask, b_vals, m: int,
              trail: bool = False):
    """One epoch of both sides' rows -> (new states, pair change set).
    Unjitted core, shared by the per-operator engine's step below and the
    fused `JoinNode` (device/fused.py). With `trail` a sixth value holds the
    two sides' `MergeTrail`s (merge_side).

    Pair change set: for each emitted pair, sign = producing delta's sign
    (+1 insert pair, -1 retract pair); payloads gathered from both sides,
    plus both sides' pks so a payload-free (SQL) run can materialize rows
    host-side.
    """
    # (named scopes: HLO metadata only, see sorted_state.merge)
    with jax.named_scope("join.reduce_delta"):
        dajk, dapk, dasign, davals = batch_reduce_rows(a_jk, a_pk, a_sign,
                                                       a_mask, a_vals)
        dbjk, dbpk, dbsign, dbvals = batch_reduce_rows(b_jk, b_pk, b_sign,
                                                       b_mask, b_vals)
    # dA >< B_old
    with jax.named_scope("join.probe"):
        r1, s1, m1, need1 = probe(b, dajk, dasign != 0, m)
    with jax.named_scope("join.emit"):
        out1 = {
            "sign": jnp.where(m1, dasign[r1], 0),
            "jk": dajk[r1],
            "a_pk": dapk[r1], "b_pk": b.pk[s1],
            "a_vals": tuple(v[r1] for v in davals),
            "b_vals": tuple(v[s1] for v in b.vals),
            "mask": m1,
        }
    with jax.named_scope("join.merge"):
        new_a, needed_a, *trail_a = merge_side(a, dajk, dapk, dasign,
                                               davals, trail)
        new_b, needed_b, *trail_b = merge_side(b, dbjk, dbpk, dbsign,
                                               dbvals, trail)
    # A_new >< dB
    with jax.named_scope("join.probe"):
        r2, s2, m2, need2 = probe(new_a, dbjk, dbsign != 0, m)
    with jax.named_scope("join.emit"):
        out2 = {
            "sign": jnp.where(m2, dbsign[r2], 0),
            "jk": dbjk[r2],
            "a_pk": new_a.pk[s2], "b_pk": dbpk[r2],
            "a_vals": tuple(v[s2] for v in new_a.vals),
            "b_vals": tuple(v[r2] for v in dbvals),
            "mask": m2,
        }
    needed = {"a": needed_a, "b": needed_b,
              "pairs": jnp.maximum(need1, need2)}
    if trail:
        return (new_a, new_b, out1, out2, needed,
                (trail_a[0], trail_b[0]))
    return new_a, new_b, out1, out2, needed


@partial(jax.jit, static_argnames=("m",))
def join_epoch_step(a: JoinSide, b: JoinSide,
                    a_jk, a_pk, a_sign, a_mask, a_vals,
                    b_jk, b_pk, b_sign, b_mask, b_vals, m: int):
    return join_core(a, b, a_jk, a_pk, a_sign, a_mask, a_vals,
                     b_jk, b_pk, b_sign, b_mask, b_vals, m)


def local_join_step(a: JoinSide, b: JoinSide,
                    a_jk, a_pk, a_sign, a_mask, a_vals,
                    b_jk, b_pk, b_sign, b_mask, b_vals, m: int,
                    trail: bool = False):
    """One epoch's LOCAL join step: join_core plus cross-delta pair
    netting (the r02 pair-resurrection fix) over the rows this program
    instance owns. On a single chip that is every row; under mesh
    sharding (`device/shard_exec.py`) it is the shard's exchange-routed
    rows — the step is closed under vnode partitioning because every row
    of one join key lands on the key's owning shard, so probe, merge,
    and netting each see exactly the rows they would have seen globally.

    Returns (new_a, new_b, njk, npk, nsign, nvals, needed): netted
    unique pairs keyed by (left pk, right pk), payload columns
    last-write-wins, plus the capacity-need stats of join_core — and,
    with `trail`, join_core's pair of merge trails last."""
    new_a, new_b, o1, o2, needed, *trails = join_core(
        a, b, a_jk, a_pk, a_sign, a_mask, a_vals,
        b_jk, b_pk, b_sign, b_mask, b_vals, m, trail)
    cat = lambda k: jnp.concatenate([o1[k], o2[k]])
    catv = lambda k, i: jnp.concatenate([o1[k][i], o2[k][i]])
    with jax.named_scope("join.net"):
        sign = cat("sign")
        mask = cat("mask") & (sign != 0)
        pvals = [catv("a_vals", i) for i in range(len(a_vals))] \
            + [catv("b_vals", i) for i in range(len(b_vals))]
        njk, npk, nsign, nvals = batch_reduce_rows(
            cat("a_pk"), cat("b_pk"), sign, mask, pvals)
    return (new_a, new_b, njk, npk, nsign, nvals, needed, *trails)


class DeviceHashJoin:
    """Host wrapper: epoch buffering + state/pair-capacity growth."""

    def __init__(self, a_dtypes: Sequence, b_dtypes: Sequence,
                 capacity: int = 1024, pair_capacity: int = 4096):
        self.a = make_side(capacity, a_dtypes)
        self.b = make_side(capacity, b_dtypes)
        self.m = pair_capacity
        self._buf = {"a": [], "b": []}

    def live_side(self, side: str) -> Tuple[np.ndarray, np.ndarray]:
        """Host pull of a side's live (jk, pk) rows (state cleaning)."""
        s = self.a if side == "a" else self.b
        n = int(s.count)
        return np.asarray(s.jk)[:n], np.asarray(s.pk)[:n]

    def load_side(self, side: str, jk, pk, vals=()) -> None:
        """Recovery: install a side's (jk, pk, payload...) rows as current
        state (sorted by (jk, pk))."""
        jk = sanitize_keys(np.asarray(jk, np.int64))
        pk = sanitize_keys(np.asarray(pk, np.int64))
        order = np.lexsort((pk, jk))
        n = len(jk)
        cur = self.a if side == "a" else self.b
        from .agg_step import _bucket
        cap = _bucket(max(n, cur.jk.shape[0]))
        gjk = np.full(cap, EMPTY_KEY, np.int64)
        gpk = np.full(cap, EMPTY_KEY, np.int64)
        gjk[:n], gpk[:n] = jk[order], pk[order]
        gvals = []
        for v0, v in zip(cur.vals, vals):
            arr = np.zeros(cap, np.asarray(v0).dtype)
            arr[:n] = np.asarray(v)[order]
            gvals.append(jnp.asarray(arr))
        new = JoinSide(jnp.asarray(gjk), jnp.asarray(gpk),
                       jnp.asarray(np.int32(n)), tuple(gvals))
        if side == "a":
            self.a = new
        else:
            self.b = new

    def push_rows(self, side: str, jk, pk, signs, vals) -> None:
        self._buf[side].append((sanitize_keys(np.asarray(jk, np.int64)),
                                sanitize_keys(np.asarray(pk, np.int64)),
                                np.asarray(signs, np.int32),
                                [np.asarray(v) for v in vals]))

    @staticmethod
    def _concat(buf, nvals):
        if not buf:
            return (np.zeros(0, np.int64), np.zeros(0, np.int64),
                    np.zeros(0, np.int32), [np.zeros(0, np.int64)] * nvals)
        jk = np.concatenate([x[0] for x in buf])
        pk = np.concatenate([x[1] for x in buf])
        sg = np.concatenate([x[2] for x in buf])
        vals = [np.concatenate([x[3][i] for x in buf])
                for i in range(nvals)]
        return jk, pk, sg, vals

    def flush_epoch(self):
        from .agg_step import _acc_cast, _bucket
        na, nb = len(self.a.vals), len(self.b.vals)
        ajk, apk, asg, avals = self._concat(self._buf["a"], na)
        bjk, bpk, bsg, bvals = self._concat(self._buf["b"], nb)
        self._buf = {"a": [], "b": []}

        def pad(arrs, bsz):
            jk, pk, sg, vals = arrs
            p = bsz - len(jk)
            return (jnp.asarray(np.pad(jk, (0, p))),
                    jnp.asarray(np.pad(pk, (0, p))),
                    jnp.asarray(np.pad(sg, (0, p))),
                    jnp.asarray(np.concatenate(
                        [np.ones(len(jk), bool), np.zeros(p, bool)])),
                    tuple(jnp.asarray(np.pad(_acc_cast(v), (0, p)))
                          for v in vals))
        bsz = _bucket(max(len(ajk), len(bjk), 1), lo=64)
        A = pad((ajk, apk, asg, avals), bsz)
        B = pad((bjk, bpk, bsg, bvals), bsz)
        from .capacity import predict_capacity
        while True:
            new_a, new_b, o1, o2, needed = join_epoch_step(
                self.a, self.b, *A, *B, m=self.m)
            na_, nb_, np_ = (int(needed["a"]), int(needed["b"]),
                             int(needed["pairs"]))
            if np_ > self.m:
                # predictive (device/capacity.py): jump past the
                # intermediate pow2 buckets — each bucket is a retrace
                self.m = predict_capacity(np_, self.m)
                continue
            grown = False
            if na_ > self.a.jk.shape[0]:
                self.a = grow_side(self.a,
                                   predict_capacity(na_,
                                                    self.a.jk.shape[0]))
                grown = True
            if nb_ > self.b.jk.shape[0]:
                self.b = grow_side(self.b,
                                   predict_capacity(nb_,
                                                    self.b.jk.shape[0]))
                grown = True
            if grown:
                continue
            self.a, self.b = new_a, new_b
            return (jax.tree_util.tree_map(np.asarray, o1),
                    jax.tree_util.tree_map(np.asarray, o2))
