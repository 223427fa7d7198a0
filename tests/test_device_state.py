"""Device sorted-run state + agg epoch step, vs a dict-based oracle."""
import numpy as np
import pytest

import jax.numpy as jnp

from risingwave_tpu.device import (EMPTY_KEY, ReduceKind, batch_reduce,
                                   lookup, make_state, merge)
from risingwave_tpu.device.agg_step import DeviceAggSpec, DeviceHashAgg


def np_state(state):
    n = int(state.count)
    return {int(k): tuple(float(v[i]) for v in state.vals)
            for i, k in enumerate(np.asarray(state.keys)[:n])}


def test_batch_reduce_unique_sums():
    keys = jnp.asarray([5, 3, 5, 3, 5, 9], dtype=jnp.int64)
    mask = jnp.asarray([1, 1, 1, 1, 1, 0], dtype=bool)
    vals = [jnp.asarray([1, 10, 2, 20, 3, 99], dtype=jnp.int64)]
    uk, uv, uc = batch_reduce(keys, mask, vals, [ReduceKind.SUM])
    assert int(uc) == 2
    got = {int(k): int(v) for k, v in zip(np.asarray(uk), np.asarray(uv[0]))
           if k != EMPTY_KEY}
    assert got == {3: 30, 5: 6}


def test_merge_insert_update_delete():
    st = make_state(8, [jnp.int64, jnp.int64], [ReduceKind.SUM, ReduceKind.SUM])
    # insert keys 1,2 with row_count 2,1
    dk = jnp.asarray([1, 2] + [int(EMPTY_KEY)] * 2, dtype=jnp.int64)
    dv = [jnp.asarray([2, 1, 0, 0], dtype=jnp.int64),
          jnp.asarray([20, 10, 0, 0], dtype=jnp.int64)]
    st, needed = merge(st, dk, dv, [ReduceKind.SUM, ReduceKind.SUM])
    assert int(needed) == 2 and np_state(st) == {1: (2, 20), 2: (1, 10)}
    # retract key 2 fully, update key 1, insert 7 — delta deliberately
    # UNSORTED: merge's variadic sort handles any delta order
    dk = jnp.asarray([2, 1, 7, int(EMPTY_KEY)], dtype=jnp.int64)
    dv = [jnp.asarray([-1, 1, 3, 0], dtype=jnp.int64),
          jnp.asarray([-10, 5, 7, 0], dtype=jnp.int64)]
    st, needed = merge(st, dk, dv, [ReduceKind.SUM, ReduceKind.SUM])
    assert np_state(st) == {1: (3, 25), 7: (3, 7)}
    found, vals = lookup(st, jnp.asarray([1, 2, 7], dtype=jnp.int64))
    assert list(np.asarray(found)) == [True, False, True]
    assert int(vals[1][0]) == 25 and int(vals[1][2]) == 7


def test_merge_overflow_reports_needed():
    st = make_state(4, [jnp.int64], [ReduceKind.SUM])
    dk = jnp.asarray([1, 2, 3, 4, 5, 6], dtype=jnp.int64)
    dv = [jnp.ones(6, dtype=jnp.int64)]
    st, needed = merge(st, dk, dv, [ReduceKind.SUM])
    assert int(needed) == 6  # > capacity: caller must grow and retry


def random_oracle_run(seed, kinds, n_epochs=6, rows=200, keyspace=17):
    rng = np.random.default_rng(seed)
    spec = DeviceAggSpec.build(kinds, [np.int64] * len(kinds))
    agg = DeviceHashAgg(spec, capacity=8)  # force growth
    oracle = {}  # key -> list of multisets? maintain sums/counts
    out_oracle = {}
    for _ in range(n_epochs):
        keys = rng.integers(0, keyspace, size=rows).astype(np.int64)
        vals = rng.integers(-50, 50, size=rows).astype(np.int64)
        valid = rng.random(rows) > 0.1
        if any(k in ("min", "max") for k in kinds):
            signs = np.ones(rows, dtype=np.int32)
        else:
            signs = np.where(rng.random(rows) > 0.3, 1, -1).astype(np.int32)
            # keep oracle row counts non-negative: flip deletes of absent keys
            cnt = dict.fromkeys(range(keyspace), 0)
            for i in range(rows):
                k = int(keys[i])
                c = cnt.get(k, 0) + oracle.get(k, {"rc": 0})["rc"]
                if signs[i] < 0 and c <= 0:
                    signs[i] = 1
                cnt[k] = cnt.get(k, 0) + int(signs[i])
        agg.push_rows(keys, signs,
                      [(vals, valid) for _ in kinds])
        # oracle update
        for i in range(rows):
            k = int(keys[i]); s = int(signs[i])
            e = oracle.setdefault(k, {"rc": 0, "sum": 0, "cnt": 0,
                                      "min": None, "max": None})
            e["rc"] += s
            if valid[i]:
                e["sum"] += s * int(vals[i]); e["cnt"] += s
                v = int(vals[i])
                e["min"] = v if e["min"] is None else min(e["min"], v)
                e["max"] = v if e["max"] is None else max(e["max"], v)
        # group death is a barrier-time event (hash_agg.rs flush_data), not a
        # mid-epoch one: additive state survives transient row_count == 0
        for k in [k for k, e in oracle.items() if e["rc"] == 0]:
            del oracle[k]
        changes = agg.flush_epoch()
        assert changes is not None
        # apply change set to materialized output oracle
        n = int(changes["count"])
        for i in range(n):
            k = int(changes["keys"][i])
            if bool(changes["new_found"][i]):
                row = []
                for c, kind in enumerate(kinds):
                    if bool(changes["new_null"][c][i]):
                        row.append(None)
                    else:
                        row.append(changes["new_out"][c][i])
                out_oracle[k] = row
            elif bool(changes["old_found"][i]):
                out_oracle.pop(k, None)
    # final: materialized outputs must match oracle
    assert set(out_oracle) == set(oracle)
    for k, row in out_oracle.items():
        e = oracle[k]
        for kind, got in zip(kinds, row):
            if kind == "count_star":
                assert int(got) == e["rc"], (k, kind)
            elif kind == "count":
                assert int(got) == e["cnt"], (k, kind)
            elif kind == "sum":
                exp = e["sum"] if e["cnt"] != 0 else None
                assert (got is None) == (exp is None)
                if exp is not None:
                    assert int(got) == exp, (k, kind)
            elif kind == "avg":
                if e["cnt"]:
                    assert abs(float(got) - e["sum"] / e["cnt"]) < 1e-9
            elif kind == "min":
                assert (got is None and e["min"] is None) or int(got) == e["min"]
            elif kind == "max":
                assert (got is None and e["max"] is None) or int(got) == e["max"]


def test_agg_retractable_vs_oracle():
    random_oracle_run(1, ["count_star", "sum", "count", "avg"])


def test_agg_append_only_minmax_vs_oracle():
    random_oracle_run(2, ["min", "max", "sum"])


def test_capacity_growth():
    spec = DeviceAggSpec.build(["sum"], [np.int64])
    agg = DeviceHashAgg(spec, capacity=8)
    keys = np.arange(1000, dtype=np.int64)
    agg.push_rows(keys, np.ones(1000, dtype=np.int32),
                  [(keys * 2, np.ones(1000, dtype=bool))])
    ch = agg.flush_epoch()
    assert int(ch["count"]) == 1000
    assert agg.state.capacity >= 1000 and int(agg.state.count) == 1000


def test_sort_cols_stable_and_compact_rows():
    """Variadic-sort building blocks: stable multi-key sort + stable
    front-compaction with fills (the merge kernels' primitives)."""
    from risingwave_tpu.device.sorted_state import compact_rows, sort_cols
    rng = np.random.default_rng(7)
    for _ in range(10):
        n = int(rng.integers(1, 60))
        k1 = rng.integers(0, 6, size=n).astype(np.int64)
        k2 = rng.integers(0, 6, size=n).astype(np.int64)
        v = np.arange(n, dtype=np.int64)
        (s1, s2), (sv,) = sort_cols([jnp.asarray(k1), jnp.asarray(k2)],
                                    [jnp.asarray(v)])
        order = np.lexsort((v, k2, k1))   # stable: position breaks ties
        assert list(np.asarray(s1)) == list(k1[order])
        assert list(np.asarray(s2)) == list(k2[order])
        assert list(np.asarray(sv)) == list(v[order])
        # compact: keep even-valued rows, truncate to n, fill with -1
        alive = (sv % 2) == 0
        out = compact_rows(alive, [s1], [sv], n, [-1, -1])
        want = [int(x) for x, a in zip(np.asarray(sv), np.asarray(alive))
                if a]
        got = list(np.asarray(out[1]))
        assert got[:len(want)] == want
        assert all(x == -1 for x in got[len(want):])


# ---------------------------------------------------------------------------
# the agg step's change set by position (ISSUE 29): `_core_tail` reads what
# each delta key held and holds off the merge's trail. Its plain reference is
# the by-key `lookup` of the old and of the new state (which stays in
# sorted_state.py), and a dictionary kept by key in plain Python.
# ---------------------------------------------------------------------------

CS_CAP = 32         # slots before the grow (then 64)
CS_EPOCHS = 18
CS_FILL_AT = 8      # the epoch that leaves the state filled to capacity
CS_OVER_AT = 10     # the epoch whose merge does not fit: needed > capacity

# case -> (entry, call kinds, value dtype, append_only, delta rows an epoch)
CS_CASES = {
    "raw-count-sum": ("raw", ["count_star", "sum"], np.int64, False, 24),
    # two payload columns: took the variadic sort before the trail
    "raw-count": ("raw", ["count_star"], np.int64, False, 24),
    "raw-float-avg": ("raw", ["count", "avg"], np.float64, False, 24),
    # a delta longer than the state (a mesh shard's received delta)
    "raw-long-delta": ("raw", ["count_star", "sum"], np.int64, False, 80),
    # the bid group-by's spec, pre-combined rows
    "combined-count-sum-max": ("combined", ["count_star", "sum", "max"],
                               np.int64, True, 24),
    "combined-long-delta": ("combined", ["count_star", "sum"], np.int64,
                            False, 80),
    # retractable max: the multiset side state beside the main run
    "full-retractable-max": ("full", ["count_star", "max"], np.int64,
                             False, 24),
}


def _cs_epochs(rng, rows, live_keys):
    """CS_EPOCHS epochs of [(sign, masked, key, value)]; `live_keys()` is
    the reference's live key set right now (the script fills the state to
    exactly its capacity, then overflows it). Beside random traffic over 24
    keys with retractions: scripted groups (100..104), a retraction to
    group death (3), a delta that nets to nothing on a live key and one on
    an absent key (4), a masked-in row of sign 0 on a live and on an absent
    key (5), an all-masked epoch (6), rows of EMPTY_KEY (7: a mesh shard's
    padding), the fill (8), an epoch on the full state with one death and
    one birth (9), the overflow (10), then traffic on the grown state."""
    held = {}                       # key -> values inserted, not retracted

    def ins(k, v=None):
        v = int(rng.integers(1, 50)) if v is None else v
        held.setdefault(k, []).append(v)
        return (1, True, k, v)

    def retract(k):
        return (-1, True, k, held[k].pop())

    for e in range(CS_EPOCHS):
        rows_e = []
        if e == 0:
            rows_e = [ins(k, 7) for k in (100, 101, 102, 103, 104)]
        elif e == 3:
            rows_e = [retract(104)]
        elif e == 4:
            rows_e = [(1, True, 100, 9), (-1, True, 100, 9),
                      (1, True, 300, 4), (-1, True, 300, 4)]
        elif e == 5:
            rows_e = [(0, True, 101, 5), (0, True, 301, 5)]
        elif e == 6:
            rows_e = [(1, False, int(rng.integers(0, 24)), 3)
                      for _ in range(rows)]
        elif e == 7:
            rows_e = [(1, False, int(EMPTY_KEY), 1)] * 3
        elif e == CS_FILL_AT:
            fresh = iter(range(200, 200 + CS_CAP))
            rows_e = [ins(next(fresh))
                      for _ in range(CS_CAP - len(live_keys()))]
            assert len(rows_e) <= rows, "the fill does not fit one delta"
        elif e == CS_FILL_AT + 1:
            live = sorted(live_keys())
            assert len(live) == CS_CAP
            dying = next(k for k in live if len(held[k]) == 1)
            rows_e = [retract(dying), ins(400)] + [ins(k) for k in live[:6]
                                                   if k != dying]
        elif e == CS_OVER_AT:
            # new keys before, between and behind the live ones: some push
            # live groups out of the truncated state, some fall out of it
            rows_e = [ins(k) for k in (-5, -4, 150, 151, 500, 501, 502)] \
                + [ins(k) for k in sorted(live_keys())[-3:]]
        if e not in (6, CS_FILL_AT, CS_FILL_AT + 1, CS_OVER_AT):
            for _ in range(int(rng.integers(4, 12))):
                k = int(rng.integers(0, 24))
                if held.get(k) and rng.random() < 0.45:
                    rows_e.append(retract(k))
                elif e < CS_FILL_AT or e > CS_OVER_AT or k in live_keys():
                    rows_e.append(ins(k))
            rows_e.append((1, False, 105, 3))       # masked out: no row
        assert len(rows_e) <= rows
        yield e, rows_e


def _cs_pad(rows_e, rows, dtype):
    sign = np.zeros(rows, np.int32)
    mask = np.zeros(rows, bool)
    keys = np.zeros(rows, np.int64)
    vals = np.zeros(rows, dtype)
    for i, (s, m, k, v) in enumerate(rows_e):
        sign[i], mask[i], keys[i] = s, m, k
        vals[i] = v / 4 if dtype == np.float64 else v   # exact in binary
    return (jnp.asarray(keys), jnp.asarray(sign), jnp.asarray(mask),
            jnp.asarray(vals))


def _cs_fold(groups, kinds, keys, mask, deltas):
    """The dictionary's merge: fold masked rows' payload deltas into
    key -> [payload...] by each column's ReduceKind; a group whose
    row_count reaches 0 at the end of the epoch is gone. Returns the keys
    the epoch named."""
    named = set()
    for i in np.flatnonzero(mask):
        k = int(keys[i])
        named.add(k)
        row = [d[i].item() for d in deltas]
        g = groups.get(k)
        if g is None:
            groups[k] = row
            continue
        for c, kind in enumerate(kinds):
            if kind == ReduceKind.SUM:
                g[c] += row[c]
            else:
                g[c] = (min if kind == ReduceKind.MIN else max)(g[c], row[c])
    for k in [k for k, g in groups.items() if g[0] == 0]:
        del groups[k]
    return named


@pytest.mark.parametrize("case", list(CS_CASES))
def test_change_set_by_position_equals_lookup(case):
    """Drive `_core_tail` through its entries and hold, after every epoch,
    the change set (`old_found`, `new_found`, the payload and output
    columns wherever found) to the by-key `lookup` of the old and of the
    new state, and the new state, `needed` and the found flags to the
    dictionary. A merge that does not fit (`needed` > capacity) reads as
    the truncated state does; then the state is grown as `cap_resize` and
    `flush_epoch` grow it (`grow_state`) and the epoch replayed."""
    import jax
    from risingwave_tpu.device import grow_state
    from risingwave_tpu.device.agg_step import (
        DeviceAggState, _outputs, _row_deltas, epoch_core,
        epoch_core_combined, epoch_core_full)
    from risingwave_tpu.device.minput import ms_make
    entry, call_kinds, dtype, append_only, rows = CS_CASES[case]
    spec = DeviceAggSpec.build(call_kinds, [dtype] * len(call_kinds),
                               append_only=append_only)
    step = jax.jit({"raw": epoch_core, "combined": epoch_core_combined,
                    "full": epoch_core_full}[entry], static_argnums=0)
    row_deltas = jax.jit(_row_deltas, static_argnums=0)
    state = spec.make_state(CS_CAP)
    # (the multisets get room for the whole run: only the main run grows)
    minputs = tuple(ms_make(16 * CS_CAP) for _ in spec.minputs)
    groups = {}                     # key -> [payload...], the reference
    seen = set()
    rng = np.random.default_rng(29)
    for e, rows_e in _cs_epochs(rng, rows, lambda: set(groups)):
        keys, sign, mask, vals = _cs_pad(rows_e, rows, dtype)
        inputs = tuple((vals, jnp.ones(rows, bool)) for _ in call_kinds)
        deltas = row_deltas(spec, sign, mask, inputs)
        if entry == "combined":     # AggNode.apply's mask: sign 0 is no row
            mask = mask & (sign != 0)
        before = {k: list(g) for k, g in groups.items()}
        named = _cs_fold(groups, spec.kinds, np.asarray(keys),
                         np.asarray(mask), [np.asarray(d) for d in deltas])
        while True:
            if entry == "raw":
                new, needed, ch = step(spec, state, keys, sign, mask, inputs)
            elif entry == "combined":
                new, needed, ch = step(spec, state, keys,
                                       jnp.ones(rows, jnp.int64), deltas,
                                       mask)
            else:
                full, (needed, ms_needed), ch = step(
                    spec, DeviceAggState(state, minputs), keys, sign, mask,
                    inputs)
                new, new_ms = full
                assert all(int(m) <= 16 * CS_CAP for m in ms_needed)
            cap = state.capacity
            fits = int(needed) <= cap
            assert int(needed) == len(groups), (case, e)
            # -- the by-key reference -----------------------------------
            ck = np.asarray(ch["keys"])
            n = int(ch["count"])
            assert ck[:n].tolist() == sorted(named) and \
                (ck[n:] == EMPTY_KEY).all(), (case, e)
            for side, st in (("old", state), ("new", new)):
                found, ref = lookup(st, ch["keys"])
                found = np.asarray(found)
                assert (np.asarray(ch[f"{side}_found"]) == found).all(), \
                    (case, e, side)
                outs, nulls = _outputs(spec, ref)
                for name, want in ((f"{side}_vals", ref),
                                   (f"{side}_out", outs),
                                   (f"{side}_null", nulls)):
                    for got, w in zip(ch[name], want):
                        assert got.dtype == w.dtype
                        assert (np.asarray(got)[found]
                                == np.asarray(w)[found]).all(), \
                            (case, e, name)
            # -- the dictionary -----------------------------------------
            new_keys = np.asarray(new.keys)
            kept = set(sorted(groups)[:cap])
            for j in range(n):
                k = int(ck[j])
                assert bool(ch["old_found"][j]) == (k in before), (case, e)
                assert bool(ch["new_found"][j]) == (k in kept), (case, e)
                if k in before:
                    assert [v[j].item() for v in ch["old_vals"]] \
                        == before[k], (case, e, k)
                if k in kept:
                    assert [v[j].item() for v in ch["new_vals"]] \
                        == groups[k], (case, e, k)
            live = new_keys != EMPTY_KEY
            assert int(new.count) == min(len(groups), cap) == live.sum()
            assert {int(k): [v[i].item() for v in new.vals]
                    for i, k in enumerate(new_keys) if live[i]} \
                == {k: groups[k] for k in kept}, (case, e)
            seen.add("fits" if fits else "truncated")
            if fits:
                break
            # replay on a grown state, as every caller does
            assert e == CS_OVER_AT, (case, e)
            state = grow_state(state, 2 * cap, spec.kinds)
        if e == CS_FILL_AT:
            assert len(groups) == CS_CAP
        state = new
        if entry == "full":
            minputs = new_ms
    assert seen == {"fits", "truncated"} and state.capacity == 2 * CS_CAP
    assert len(groups) > CS_CAP


@pytest.mark.parametrize("entry", ["raw", "combined", "full-append-only"])
def test_agg_entries_search_the_state_for_no_key(entry, jaxpr_loops):
    """The change set comes by position: no entry of the agg step holds a
    loop (`searchsorted` of the compile-cheap form is one) unless its spec
    has retractable min / max, whose multiset side state keeps its own
    searches."""
    import jax
    from risingwave_tpu.device.agg_step import (
        DeviceAggState, epoch_core, epoch_core_combined, epoch_core_full)
    spec = DeviceAggSpec.build(["count_star", "sum", "max"], [np.int64] * 3)
    rows, cap = 64, 64
    z = jnp.zeros(rows, jnp.int64)
    state = spec.make_state(cap)
    if entry == "combined":
        jaxpr = jax.make_jaxpr(
            lambda st: epoch_core_combined(spec, st, z, z, [z] * 6,
                                           z == 0))(state)
    else:
        fn, st = (epoch_core, state) if entry == "raw" else \
            (epoch_core_full, DeviceAggState(state, ()))
        jaxpr = jax.make_jaxpr(
            lambda st: fn(spec, st, z, z.astype(jnp.int32), z == 0,
                          tuple((z, z == 0) for _ in spec.calls)))(st)
    loops, _ = jaxpr_loops
    assert loops(jaxpr.jaxpr) == []
    assert "sort[" in str(jaxpr)    # (the probe does read primitives)


# ---------------------------------------------------------------------------
# reduce once (ISSUE 31): where no exchange stands between a pre-combine and
# its agg, `epoch_core_combined` takes `precombine_core`'s output as it is.
# Its plain reference is the form it keeps behind an exchange: `batch_reduce`
# over the same rows, which must be an identity on them.
# ---------------------------------------------------------------------------

PT_HOT = 777        # the key of the two hot-key epochs behind the script
PT_HOT_AT = CS_EPOCHS       # the epoch whose every row is that key

# case -> (call kinds, append_only, delta rows an epoch); `spec.kinds` has
# 2, 4 and 6 payload columns, as PR 29's cases
PT_CASES = {
    "count-2col": (["count_star"], False, 24),
    "count-sum-4col": (["count_star", "sum"], False, 24),
    "count-sum-max-6col": (["count_star", "sum", "max"], True, 24),
    # the delta as long as the state, and longer (a mesh shard's)
    "count-sum-delta-as-long-as-state": (["count_star", "sum"], False,
                                         CS_CAP),
    "count-sum-max-long-delta": (["count_star", "sum", "max"], True, 80),
}


def _pt_epochs(rng, rows, live_keys):
    """`_cs_epochs`' script (group death, net-zero deltas, a row of sign
    0, an all-masked epoch, EMPTY_KEY rows, the fill, the overflow and
    its replay), then: every row one key, and a hot key among others."""
    yield from _cs_epochs(rng, rows, live_keys)
    yield PT_HOT_AT, [(1, True, PT_HOT, int(rng.integers(1, 50)))
                      for _ in range(rows)]
    yield PT_HOT_AT + 1, [
        (1, True, PT_HOT if i % 10 else int(rng.integers(0, 24)),
         int(rng.integers(1, 50))) for i in range(rows)]


def _tree_eq(a, b):
    import jax
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    assert ta == tb
    for x, y in zip(la, lb):
        assert x.dtype == y.dtype and x.shape == y.shape
        assert (np.asarray(x) == np.asarray(y)).all()


@pytest.mark.parametrize("trail", [False, True], ids=["plain", "trail"])
@pytest.mark.parametrize("case", list(PT_CASES))
def test_passthrough_equals_recombine_on_a_precombined_delta(case, trail):
    """Over `precombine_core`'s own output the pass-through form of
    `epoch_core_combined` equals the `batch_reduce` form leaf for leaf:
    the new state, `needed`, every entry of the change set (`rows_in`,
    `in_counts`, `count`, with `trail` the merge's trail too). Both are
    driven from the same state through the script, the truncated merge
    (`needed` > capacity) and its replay after `grow_state` included."""
    import jax
    from risingwave_tpu.device import grow_state
    from risingwave_tpu.device.agg_step import (epoch_core_combined,
                                                precombine_core)
    call_kinds, append_only, rows = PT_CASES[case]
    spec = DeviceAggSpec.build(call_kinds, [np.int64] * len(call_kinds),
                               append_only=append_only)
    pre = jax.jit(precombine_core, static_argnums=0)
    step = jax.jit(epoch_core_combined, static_argnums=(0, 6, 7))
    state = spec.make_state(CS_CAP)

    def live_keys():
        return set(np.asarray(state.keys)[:int(state.count)].tolist())

    seen = set()
    for e, rows_e in _pt_epochs(np.random.default_rng(31), rows, live_keys):
        keys, sign, mask, vals = _cs_pad(rows_e, rows, np.int64)
        inputs = tuple((vals, jnp.ones(rows, bool)) for _ in call_kinds)
        ukeys, ucnt, udeltas = pre(spec, keys, sign, mask, inputs)
        # the contract the pass-through rests on
        uk = np.asarray(ukeys)
        n = int((uk != EMPTY_KEY).sum())
        assert (np.diff(uk[:n]) > 0).all() and (uk[n:] == EMPTY_KEY).all()
        live = ukeys != EMPTY_KEY       # PrecombineNode's mask (sign 1)
        raw = np.asarray(mask) & (np.asarray(sign) != 0) \
            & (np.asarray(keys) != EMPTY_KEY)
        while True:
            want = step(spec, state, ukeys, ucnt, udeltas, live, trail,
                        True)
            got = step(spec, state, ukeys, ucnt, udeltas, live, trail,
                       False)
            _tree_eq(got, want)
            new, needed, ch = got
            assert ("merge_trail" in ch) == trail
            assert int(ch["count"]) == n and int(ch["rows_in"]) == raw.sum()
            assert (np.asarray(ch["in_counts"])[n:] == 0).all()
            fits = int(needed) <= state.capacity
            seen.add((e, "fits" if fits else "truncated"))
            if fits:
                break
            state = grow_state(state, 2 * state.capacity, spec.kinds)
        if e == PT_HOT_AT:
            assert n == 1 and int(ch["in_counts"][0]) == rows
        state = new
    assert {(CS_OVER_AT, "truncated"), (CS_OVER_AT, "fits"),
            (6, "fits"), (PT_HOT_AT + 1, "fits")} <= seen
    assert state.capacity == 2 * CS_CAP


@pytest.mark.parametrize("recombine", [False, True],
                         ids=["one-chip", "behind-an-exchange"])
def test_reduce_stage_of_the_combined_entry(recombine, jaxpr_prims_under):
    """Reduce once: with no exchange before it the combined entry's reduce
    stage (`agg.reduce_delta`, all of it the `passthrough` scope) holds no
    sort, gather, scatter or segment reduction; behind an exchange it
    keeps `batch_reduce`, sort and scatters and all."""
    import jax
    from risingwave_tpu.device.agg_step import epoch_core_combined
    spec = DeviceAggSpec.build(["count_star", "sum", "max"], [np.int64] * 3)
    z = jnp.zeros(64, jnp.int64)
    jaxpr = jax.make_jaxpr(
        lambda st: epoch_core_combined(spec, st, z, z, [z] * 6, z == 0,
                                       recombine=recombine))(
        spec.make_state(64)).jaxpr
    stage = jaxpr_prims_under(jaxpr, "agg.reduce_delta")
    heavy = {p for p in stage if p.startswith(("sort", "scatter", "gather",
                                               "segment", "cum", "while",
                                               "scan"))}
    if recombine:
        assert {"sort", "scatter", "scatter-add", "scatter-max"} <= heavy
        assert jaxpr_prims_under(jaxpr, "passthrough") == set()
    else:
        assert heavy == set()
        assert stage == jaxpr_prims_under(jaxpr, "passthrough") != set()
    # the merge behind it is the same stage either way
    assert "sort" in jaxpr_prims_under(jaxpr, "agg.merge")
