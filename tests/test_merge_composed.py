"""`sorted_state.merge` (one gather of every column's words through the
composed permutations) against a per-key dict reference, and its trail's
consumers against the parent's two-gather form, kept here as the yardstick."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from risingwave_tpu.device import sorted_state
from risingwave_tpu.device.sorted_state import (
    EMPTY_KEY, ReduceKind, SortedState, _combine, _neutral, _unwords, _words,
    compact_rows, merge, merge_changes, sort_cols)

RK = ReduceKind
AGG = (RK.SUM, RK.SUM, RK.SUM, RK.MAX, RK.MIN)
AGG_DT = (np.int64, np.int64, np.float64, np.int64, np.int32)
MV = (RK.REPLACE,) * 5
MV_DT = (np.int32, np.int64, np.bool_, np.float64, np.bool_)


# -- the parent's form (PR 37): rank-sort + a gather a column n wide, then a
# -- compaction that gathers every column again C wide ----------------------

def parent_merge(state, dkeys, dvals, kinds, drop_dead=True, dead_col=0):
    c = state.capacity
    keys = jnp.concatenate([state.keys, dkeys])
    vals = [jnp.concatenate([sv, dv.astype(sv.dtype)])
            for sv, dv in zip(state.vals, dvals)]
    (keys,), vals, sperm = sort_cols([keys], vals, return_perm=True)
    same_next = jnp.concatenate([keys[:-1] == keys[1:], jnp.zeros((1,), bool)])
    same_prev = jnp.concatenate([jnp.zeros((1,), bool), keys[1:] == keys[:-1]])
    merged = [jnp.where(same_next,
                        _combine(k, v, jnp.concatenate([v[1:], v[-1:]])), v)
              for v, k in zip(vals, kinds)]
    alive = ~same_prev & (keys != EMPTY_KEY)
    if drop_dead:
        alive &= merged[dead_col] != 0
    needed = jnp.sum(alive).astype(jnp.int32)
    out = compact_rows(alive, [keys], merged, c,
                       [EMPTY_KEY] + [_neutral(k, v.dtype)
                                      for v, k in zip(merged, kinds)],
                       return_perm=True)
    new = SortedState(out[0], jnp.minimum(needed, c), tuple(out[1:-1]))
    return new, needed, (sperm, same_next, out[-1])


def parent_merged_src(trail, last):
    sp, same_next, compact_perm = trail
    if last:
        sp = jnp.where(same_next, jnp.concatenate([sp[1:], sp[-1:]]), sp)
    return sp[compact_perm]


def parent_merge_changes(state, new_state, dkeys, dvals, kinds, trail,
                         drop_dead=True, dead_col=0):
    c, b = state.capacity, dkeys.shape[0]
    sp, same_next, _ = trail
    same_prev = jnp.concatenate([jnp.zeros((1,), bool), same_next[:-1]])
    met = jnp.where(same_prev, jnp.concatenate([sp[:1], sp[:-1]]), -1)
    (met,) = compact_rows(sp >= c, [met], [], b, [-1])
    real = dkeys != EMPTY_KEY
    old_found = (met >= 0) & real
    row = jnp.clip(met, 0, c - 1)
    old_vals = tuple(v[row] for v in state.vals)
    dvals = [dv.astype(ov.dtype) for dv, ov in zip(dvals, old_vals)]
    new_vals = tuple(jnp.where(old_found, _combine(k, ov, dv), dv)
                     for k, ov, dv in zip(kinds, old_vals, dvals))
    new_found = real & (dkeys <= new_state.keys[c - 1])
    if drop_dead:
        new_found &= new_vals[dead_col] != 0
    return old_found, old_vals, new_found, new_vals


# -- seeded inputs ------------------------------------------------------------

def _col(rng, dt, n, kind, dead):
    if dt == np.bool_:
        return rng.random(n) < 0.5
    if dead and kind == RK.REPLACE:
        return (rng.random(n) < 0.8).astype(dt)      # MV liveness 0 / 1
    if np.issubdtype(dt, np.floating):
        v = rng.integers(-4, 5, n).astype(dt) / 4
        v[rng.random(n) < 0.1] = -0.0
        return v
    return rng.integers(-3, 6, n).astype(dt)


def make_inputs(seed, kinds, dtypes, cap, live, dlanes, dlive, sorted_delta):
    """A sorted state of `live` keys in `cap` slots (EMPTY pads behind) and
    `dlanes` delta lanes holding `dlive` unique keys, half of them state keys;
    a state row's dead column is never 0 (what a merge leaves)."""
    rng = np.random.default_rng(seed)
    pool = rng.choice(4 * (live + dlive) + 8, live + dlive, replace=False)
    skeys = np.sort(pool[:live]).astype(np.int64)
    hits = rng.choice(skeys, min(live, dlive // 2), replace=False) \
        if live else np.zeros(0, np.int64)
    dk = np.concatenate([hits, pool[live:live + dlive - len(hits)]])
    dk = np.sort(dk) if sorted_delta else rng.permutation(dk)
    st_keys = np.full(cap, EMPTY_KEY, np.int64)
    st_keys[:live] = skeys
    d_keys = np.full(dlanes, EMPTY_KEY, np.int64)
    d_keys[:len(dk)] = dk
    svals, dvals = [], []
    for i, (k, dt) in enumerate(zip(kinds, dtypes)):
        sv = np.array([_neutral(k, jnp.dtype(dt))] * cap, dtype=dt)
        sv[:live] = _col(rng, dt, live, k, i == 0)
        if i == 0:
            sv[:live] = np.where(sv[:live] == 0, 1, sv[:live])
        dv = np.array([_neutral(k, jnp.dtype(dt))] * dlanes, dtype=dt)
        dv[:len(dk)] = _col(rng, dt, len(dk), k, i == 0)
        if i == 0 and k == RK.SUM:   # some groups die: the delta cancels them
            at = {int(x): j for j, x in enumerate(skeys)}
            for j, x in enumerate(dk):
                if int(x) in at and rng.random() < 0.3:
                    dv[j] = -sv[at[int(x)]]
        svals.append(sv)
        dvals.append(dv)
    state = SortedState(jnp.asarray(st_keys), jnp.asarray(np.int32(live)),
                        tuple(map(jnp.asarray, svals)))
    return state, jnp.asarray(d_keys), tuple(map(jnp.asarray, dvals))


def reference(state, dkeys, dvals, kinds, drop_dead, dead_col=0):
    """Per-key dict: {key: row} after the merge, and `needed`."""
    rows = {}
    sk = np.asarray(state.keys)
    for i in range(int(state.count)):
        rows[int(sk[i])] = [np.asarray(v)[i] for v in state.vals]
    for j, key in enumerate(np.asarray(dkeys)):
        if key == EMPTY_KEY:
            continue
        d = [np.asarray(dv)[j].astype(np.asarray(sv).dtype)
             for dv, sv in zip(dvals, state.vals)]
        old = rows.get(int(key))
        rows[int(key)] = d if old is None else [
            np.asarray(_combine(k, a, b)) for k, a, b in zip(kinds, old, d)]
    if drop_dead:
        rows = {k: r for k, r in rows.items() if r[dead_col] != 0}
    return rows, len(rows)


def bits(x):
    x = np.atleast_1d(np.asarray(x))
    return x.view(np.uint8) if x.dtype != np.bool_ else x


CASES = {
    # name: (kinds, dtypes, cap, live, dlanes, dlive, drop_dead, sorted)
    "agg": (AGG, AGG_DT, 64, 30, 64, 24, True, True),
    "agg_keep_dead": (AGG, AGG_DT, 64, 30, 64, 24, False, True),
    "agg_truncated": (AGG, AGG_DT, 32, 30, 32, 24, True, True),
    "agg_empty_state": (AGG, AGG_DT, 32, 0, 32, 20, True, True),
    "agg_unsorted_delta": (AGG, AGG_DT, 64, 30, 64, 24, True, False),
    "mv": (MV, MV_DT, 64, 40, 64, 30, True, True),
    "mv_wide_delta": (MV, MV_DT, 32, 20, 128, 40, True, True),
    "mv_truncated": (MV, MV_DT, 32, 28, 64, 30, True, True),
    "replace_keep": (MV, MV_DT, 64, 40, 64, 30, False, True),
    "mixed": ((RK.SUM, RK.REPLACE, RK.MIN, RK.MAX, RK.REPLACE),
              (np.int64, np.float64, np.float64, np.int32, np.bool_),
              64, 30, 64, 24, True, True),
}


@pytest.mark.parametrize("form", ["trail", "cheap", "variadic"])
@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("case", sorted(CASES))
def test_merge_equals_dict_reference(case, seed, form, monkeypatch):
    """Every form `merge` takes: with a trail, without (the MV apply), and
    the variadic sort `RW_TPU_CHEAP_COMPILE=0` selects without a trail."""
    monkeypatch.setattr(sorted_state, "_CHEAP_COMPILE", form != "variadic")
    trail = form == "trail"
    kinds, dtypes, cap, live, dlanes, dlive, drop, srt = CASES[case]
    state, dk, dv = make_inputs(seed, kinds, dtypes, cap, live, dlanes,
                                dlive, srt)
    new, needed, *_ = merge(state, dk, dv, kinds, drop_dead=drop,
                            return_trail=trail)
    rows, want_needed = reference(state, dk, dv, kinds, drop)
    assert int(needed) == want_needed
    assert int(new.count) == min(want_needed, cap)
    keys = np.asarray(new.keys)
    want_keys = sorted(rows)[:cap]
    n = len(want_keys)
    assert list(keys[:n]) == want_keys
    assert (keys[n:] == EMPTY_KEY).all()
    for i, (k, v) in enumerate(zip(kinds, new.vals)):
        v = np.asarray(v)
        want = np.array([rows[key][i] for key in want_keys], dtype=v.dtype)
        assert (bits(v[:n]) == bits(want)).all(), (case, i)
        pad = np.asarray(_neutral(k, v.dtype))
        assert (bits(v[n:]) == bits(np.full(cap - n, pad, v.dtype))).all()


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
@pytest.mark.parametrize("case", sorted(c for c in CASES
                                        if CASES[c][-1]))
def test_trail_consumers_equal_parent_form(case, seed):
    """Equal to the parent's form on the same inputs: the merged state,
    `merged_src(last=True/False)` (the trail's `last` / `first`, in live
    slots), `merge_changes` whole, and the tier's touch column."""
    kinds, dtypes, cap, live, dlanes, dlive, drop, _ = CASES[case]
    state, dk, dv = make_inputs(seed, kinds, dtypes, cap, live, dlanes,
                                dlive, True)
    new, needed, tr = merge(state, dk, dv, kinds, drop_dead=drop,
                            return_trail=True)
    pnew, pneeded, ptr = parent_merge(state, dk, dv, kinds, drop_dead=drop)
    assert int(needed) == int(pneeded)
    for a, b in zip(jax.tree_util.tree_leaves(new),
                    jax.tree_util.tree_leaves(pnew)):
        assert np.asarray(a).dtype == np.asarray(b).dtype
        assert (bits(a) == bits(b)).all()
    live_slot = np.asarray(new.keys) != EMPTY_KEY
    for last, got in ((True, tr.last), (False, tr.first)):
        want = np.asarray(parent_merged_src(ptr, last))
        assert (np.asarray(got)[live_slot] == want[live_slot]).all()
    got = merge_changes(state, new, dk, dv, kinds, tr, drop_dead=drop)
    want = parent_merge_changes(state, pnew, dk, dv, kinds, ptr,
                                drop_dead=drop)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert (bits(a) == bits(b)).all()
    # the tier's stamp carry (`fused.AggNode._tier_tail`), both sources
    touch = jnp.asarray(np.random.default_rng(seed).integers(1, 9, cap))

    def stamps(src):
        return np.asarray(jnp.where(
            new.keys != EMPTY_KEY,
            jnp.where(src >= cap, 10, touch[jnp.minimum(src, cap - 1)]), 0))
    assert (stamps(tr.last) == stamps(parent_merged_src(ptr, True))).all()


@pytest.mark.parametrize("dtype", [np.bool_, np.int8, np.int16, np.int32,
                                   np.uint32, np.int64, np.uint64,
                                   np.float32, np.float64, jnp.bfloat16])
def test_words_round_trip_bit_for_bit(dtype):
    rng = np.random.default_rng(3)
    if dtype == np.bool_:
        col = rng.random(37) < 0.5
    elif np.issubdtype(dtype, np.integer):
        info = np.iinfo(dtype)
        col = rng.integers(info.min, info.max, 37, dtype=dtype,
                           endpoint=True)
        col[:2] = [info.min, info.max]
    else:
        col = (rng.standard_normal(37) * 1e3).astype(dtype)
        col[:4] = np.array([-0.0, np.inf, -np.inf, np.nan]).astype(dtype)
    col = jnp.asarray(col)
    w = _words([col, col[::-1]])
    assert w.dtype == jnp.int32
    back = _unwords(w, [col.dtype, col.dtype])
    assert (bits(back[0]) == bits(col)).all()
    assert (bits(back[1]) == bits(col[::-1])).all()
    idx = jnp.asarray(rng.integers(0, 37, 50).astype(np.int32))
    (taken,) = _unwords(_words([col])[:, idx], [col.dtype])
    assert (bits(taken) == bits(col[idx])).all()
