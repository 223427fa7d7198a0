"""Tiered state beyond HBM (ISSUE 16).

The contract under test: the hot/cold state tier (device/tiering.py +
the FusedJob wiring) — cold-group demotion to per-node host ColdStores
off the commit phase, touch-promotion gated by Xor8 negative caches
probed per ingest window, `rw_key_skew` heavy hitters never demoted —
is gated by `DeviceConfig.state_tiering` / RW_STATE_TIERING, BIT-
IDENTICAL to the untiered run (row order included) at 1 and 8 shards,
keeps the device footprint inside the capacity clamp (no growth where
the untiered run grows), and every rebuild path (growth replay, restart
recovery, `fused.*` in-place recovery) reconstructs BOTH tiers.

The conftest pins RW_STATE_TIERING off suite-wide for compile budget;
every test here forces it back on via monkeypatch (read at CREATE
time). Promotion needs the host-ingest window (the recipes re-derive
candidate keys from the shipped host columns), so RW_HOST_INGEST goes
on too. RW_AGG_PRECOMBINE stays off — combined aggs are demotion-inert
by design (their input is the pre-combine output, not an ingest
lineage)."""
import os

import numpy as np
import pytest

from risingwave_tpu.config import DeviceConfig
from risingwave_tpu.sql import Database

N = 16384
N_SMALL = 8192
CHUNK = 32          # fused epoch = 64 * CHUNK = 2048 events

BID_SRC = ("CREATE SOURCE bid (auction BIGINT, bidder BIGINT,"
           " price BIGINT, channel VARCHAR, url VARCHAR,"
           " date_time TIMESTAMP, extra VARCHAR) WITH"
           " (connector='nexmark', nexmark.table='bid',"
           " nexmark.max.events='{n}', nexmark.chunk.size='{c}'{kd})")
AUCTION_SRC = ("CREATE SOURCE auction (id BIGINT, item_name VARCHAR,"
               " description VARCHAR, initial_bid BIGINT,"
               " reserve BIGINT, date_time TIMESTAMP, expires TIMESTAMP,"
               " seller BIGINT, category BIGINT, extra VARCHAR) WITH"
               " (connector='nexmark', nexmark.table='auction',"
               " nexmark.max.events='{n}', nexmark.chunk.size='{c}')")

# q8-style unbounded key space: auction ids keep growing with the
# stream, so the live group set outruns any fixed capacity clamp
QA_MV = ("CREATE MATERIALIZED VIEW qa AS SELECT auction,"
         " count(*) AS n, sum(price) AS dol FROM bid GROUP BY auction")
Q3_MV = ("CREATE MATERIALIZED VIEW q3a AS SELECT b.auction, b.price,"
         " a.seller, a.category FROM bid b JOIN auction a"
         " ON b.auction = a.id WHERE b.price > 900")


def _arm(monkeypatch, high="0.35", low="0.15", skew="0"):
    monkeypatch.setenv("RW_STATE_TIERING", "1")
    monkeypatch.setenv("RW_HOST_INGEST", "1")
    monkeypatch.setenv("RW_TIER_HIGH_WATER", high)
    monkeypatch.setenv("RW_TIER_LOW_WATER", low)
    monkeypatch.setenv("RW_SKEW_STATS", skew)


def _run(mv_sql, name, shards, cap, tier, srcs=(BID_SRC,), kd=None,
         n=N, data_dir=None, keep=False, aot=False, arm=None,
         hbm_mb=4096, chunk=CHUNK):
    """One fused run to drain; `tier` overrides RW_STATE_TIERING for
    THIS create (the env is read at plan time)."""
    os.environ["RW_STATE_TIERING"] = tier
    db = Database(device=DeviceConfig(capacity=cap, mesh_shards=shards,
                                      aot_compile=aot, compile_buckets=0,
                                      hbm_budget_mb=hbm_mb),
                  data_dir=data_dir)
    kdc = f", nexmark.key.dist='{kd}'" if kd else ""
    for s in srcs:
        db.run(s.format(n=n, c=chunk, kd=kdc))
    db.run(mv_sql)
    job = db.catalog.get(name).runtime["fused_job"]
    assert job is not None, f"{name} must fuse"
    if arm is not None:
        from risingwave_tpu.utils import failpoint as fp
        fp.arm(*arm)
    try:
        for _ in range(n // (64 * chunk) + 3):
            db.tick()
        job.sync()
        db.tick()
    finally:
        if arm is not None:
            fp.reset()
    rows = db.query(f"SELECT * FROM {name}")
    return (rows, job, db) if keep else (rows, job, None)


def _store_dump(tm):
    """Canonical, comparison-stable image of every cold store: nested
    python scalars only (numpy scalars compare fine, but a canonical
    dump makes assertion diffs readable)."""
    def scal(v):
        return v.item() if hasattr(v, "item") else v

    def row(r):
        if isinstance(r, tuple) and len(r) == 2 \
                and isinstance(r[0], tuple):        # agg: (vals, touch)
            return (tuple(scal(v) for v in r[0]), scal(r[1]))
        if isinstance(r, list):                     # join: [(pk, vals, t)]
            return sorted((scal(pk), tuple(scal(v) for v in vs), scal(t))
                          for pk, vs, t in r)
        return tuple(scal(v) for v in r)            # mv: vals tuple

    out = {}
    for (node, side), store in tm.stores.items():
        out[(node, side)] = [
            sorted((scal(k), row(r)) for k, r in d.items())
            for d in store.rows]
    return out


# ---------------------------------------------------------------------------
# host-side policy units (fast, no device)
# ---------------------------------------------------------------------------


def test_select_cold_oldest_first_excludes_hot():
    from risingwave_tpu.device.tiering import select_cold
    keys = np.arange(100, dtype=np.int64)
    touch = np.arange(100, dtype=np.int64)[::-1].copy()  # key 99 oldest
    # no pressure below high water
    assert select_cold(keys, touch, 10, 100, (), 0xFF) is None
    # pressure: oldest-touched first, drains to low water
    os.environ["RW_TIER_HIGH_WATER"] = "0.5"
    os.environ["RW_TIER_LOW_WATER"] = "0.2"
    try:
        sel = select_cold(keys, touch, 100, 100, (), (1 << 40) - 1)
        assert sel is not None and len(sel) == 80       # 100 - 0.2*100
        assert sel[0] == 99 and sel[-1] == 20           # oldest first
        # heavy hitters are excluded even when stone cold
        sel = select_cold(keys, touch, 100, 100, (99, 98), (1 << 40) - 1)
        assert 99 not in sel and 98 not in sel
        assert sel[0] == 97
    finally:
        del os.environ["RW_TIER_HIGH_WATER"]
        del os.environ["RW_TIER_LOW_WATER"]


def test_xor8_build_none_and_store_fallback(monkeypatch):
    from risingwave_tpu.device.tiering import ColdStore, key_bytes
    from risingwave_tpu.state import hummock
    # a healthy filter: no false negatives, dedupe-hardened build
    keys = [key_bytes(k) for k in range(500)] + [key_bytes(7)] * 3
    f = hummock.Xor8.build(keys)
    assert f is not None, "duplicate keys must not fail the build"
    assert all(f.may_contain(key_bytes(k)) for k in range(500))
    # store with a live filter
    st = ColdStore(1)
    st.rows[0] = {k: ((k,), 0) for k in range(64)}
    st.rebuild_filter(0)
    assert st.filter_live[0]
    hits, probes, pos = st.probe(0, np.arange(32, 96, dtype=np.int64))
    assert sorted(hits) == list(range(32, 64)) and probes == 64
    # Xor8.build returning None degrades to always-probe, same hits
    monkeypatch.setattr(hummock.Xor8, "build",
                        staticmethod(lambda keys, seed=0: None))
    st2 = ColdStore(1)
    st2.rows[0] = dict(st.rows[0])
    st2.rebuild_filter(0)
    assert not st2.filter_live[0] and st2.filters[0] is None
    hits2, probes2, pos2 = st2.probe(0, np.arange(32, 96,
                                                  dtype=np.int64))
    assert sorted(hits2) == sorted(hits)      # correctness unchanged
    assert pos2 == len(hits2)                 # every probe paid the dict


# ---------------------------------------------------------------------------
# bit-identity + budget clamp (agg, 1 shard)
# ---------------------------------------------------------------------------


@pytest.mark.tiering
def test_agg_demotion_bit_identity_and_no_growth(monkeypatch):
    """The q8-style unbounded-key agg under a capacity clamp BELOW the
    live key count: the untiered run must grow; the tiered run demotes
    instead, stays inside the clamp, and serves the bit-identical MV
    (cold rows merged at SELECT time)."""
    _arm(monkeypatch)
    # 512-event fused epochs: demotion runs off every checkpoint, so
    # the drain keeps pace with new-key arrival (two-phase demotion is
    # one epoch behind — at 2048-event epochs the lag alone overshoots
    # a 512-slot clamp)
    r_off, j_off, _ = _run(QA_MV, "qa", 1, 512, "0", chunk=8)
    assert j_off.growth_replays >= 1, "untiered clamp must overflow"
    r_on, j_on, db = _run(QA_MV, "qa", 1, 512, "1", keep=True,
                          hbm_mb=1, chunk=8)
    assert r_off == r_on                 # bit-identical, order included
    assert len(r_on) > 512               # more groups than device slots
    assert j_on.growth_replays == 0, "the tier must absorb the overflow"
    agg = next(n for n in j_on.program.nodes
               if type(n).__name__ == "AggNode")
    assert agg.capacity == 512           # never grew past the clamp
    tm = j_on.tiering
    assert tm.counters["demotions"] > 0
    assert tm.counters["promotions"] > 0
    assert tm.counters["demote_events"] > 0
    assert tm.counters["filter_probes"] > 0
    # phases surfaced disjointly in the epoch profile
    assert j_on.profiler.totals.get("demote_d2h", 0.0) > 0.0
    assert j_on.profiler.totals.get("promote_h2d", 0.0) > 0.0
    prow = db.query("SELECT * FROM rw_epoch_profile")
    assert prow and len(prow[0]) == 13
    # HBM stayed inside the (1 MB) budget: the gauge is the acceptance
    # surface for "high-water <= budget"
    from risingwave_tpu.utils.metrics import REGISTRY
    text = REGISTRY.expose()
    vals = [float(line.rsplit(" ", 1)[1]) for line in text.splitlines()
            if line.startswith("rw_hbm_budget_utilization")
            and 'job="qa"' in line]
    assert vals and all(v <= 1.0 for v in vals), vals
    # rw_state_tiering reports the two tiers
    trows = db.query("SELECT * FROM rw_state_tiering")
    mine = [r for r in trows if r[0] == "qa"]
    assert mine and any(r[4] > 0 for r in mine)          # cold rows
    assert any(r[6] for r in mine)                       # promotable


@pytest.mark.mesh
@pytest.mark.tiering
def test_agg_demotion_bit_identity_mesh(monkeypatch):
    """Same contract at 8 mesh shards (per-shard capacities, per-shard
    cold stores, demoted rows return to the shard that owns them)."""
    _arm(monkeypatch)
    r1, _, _ = _run(QA_MV, "qa", 1, 4096, "0")
    r8, j8, _ = _run(QA_MV, "qa", 8, 256, "1")
    assert r1 == r8                      # bit-identical, order included
    tm = j8.tiering
    assert tm.counters["demotions"] > 0
    assert tm.counters["demote_events"] > 0
    assert j8.growth_replays == 0
    # the per-shard stores are genuinely spread, not one hot shard
    store = tm.store(next(p.node_idx for p in tm.plans), -1)
    assert sum(1 for d in store.rows if d) >= 2


# ---------------------------------------------------------------------------
# joins: both sides demote in lockstep, growth replay rebuilds the tier
# ---------------------------------------------------------------------------


@pytest.mark.tiering
def test_join_demotion_bit_identity_and_growth_replay(monkeypatch):
    """q3-shaped join under tier pressure: cold join keys demote BOTH
    build sides in one journal event, later bids for a demoted auction
    promote the pair back, and the mid-run capacity growth replay
    (the unbounded bid side outruns its clamp once) re-enacts the
    demotion journal — both tiers bit-identical through it all."""
    _arm(monkeypatch, high="0.1", low="0.02")
    r_off, _, _ = _run(Q3_MV, "q3a", 1, 4096, "0",
                       srcs=(BID_SRC, AUCTION_SRC), n=N_SMALL)
    r_on, job, _ = _run(Q3_MV, "q3a", 1, 4096, "1",
                        srcs=(BID_SRC, AUCTION_SRC), n=N_SMALL)
    assert r_off == r_on
    tm = job.tiering
    assert tm.counters["demotions"] > 0
    assert tm.counters["promotions"] > 0, \
        "a bid for a demoted auction must promote the pair back"
    assert tm.counters["filter_probes"] > 0
    assert job.growth_replays >= 1, \
        "this shape is sized to grow mid-run (replays the journal)"
    # both sides' stores saw traffic
    i = next(p.node_idx for p in tm.plans if p.kind == "join")
    assert len(tm.store(i, 0)) + len(tm.store(i, 1)) > 0


# ---------------------------------------------------------------------------
# durability: restart recovery + fused.* in-place recovery
# ---------------------------------------------------------------------------


@pytest.mark.tiering
def test_restart_recovery_rebuilds_both_tiers(monkeypatch, tmp_path):
    """A restart (new Database over the same data dir) replays the
    demotion journal beside the job state tables: the device resident
    tier AND the host cold stores come back bit-identical — same MV,
    same per-shard cold rows."""
    _arm(monkeypatch)
    d = str(tmp_path / "d")
    rows, job, db = _run(QA_MV, "qa", 1, 512, "1", data_dir=d,
                         keep=True)
    tm = job.tiering
    assert tm.counters["demote_events"] > 0
    want_stores = _store_dump(tm)
    assert any(any(s for s in shards) for shards in want_stores.values())
    assert os.path.exists(os.path.join(d, "tiering_journal_qa.jsonl"))
    del db, job
    os.environ["RW_STATE_TIERING"] = "1"
    db2 = Database(device=DeviceConfig(capacity=512, mesh_shards=1,
                                       aot_compile=False,
                                       compile_buckets=0), data_dir=d)
    job2 = db2.catalog.get("qa").runtime["fused_job"]
    assert job2.tiering is not None
    assert _store_dump(job2.tiering) == want_stores
    assert db2.query("SELECT * FROM qa") == rows


@pytest.mark.tiering
def test_inplace_recovery_failpoint_rebuilds_both_tiers(monkeypatch):
    """A fused.dispatch fault mid-run (fires once, after demotions have
    happened) heals in place: the history replay re-enacts the journal
    into fresh cold stores and the final MV is bit-identical to the
    untiered run."""
    _arm(monkeypatch)
    want, _, _ = _run(QA_MV, "qa", 1, 4096, "0")
    got, job, _ = _run(QA_MV, "qa", 1, 512, "1",
                       arm=("fused.dispatch", 1.0, 0, 1))
    assert job.recoveries == 1
    assert got == want
    tm = job.tiering
    assert tm.counters["demote_events"] > 0
    assert any(len(s) for s in tm.stores.values()), \
        "recovery must rebuild the cold tier, not just the device tier"


# ---------------------------------------------------------------------------
# policy: rw_key_skew heavy hitters never demote
# ---------------------------------------------------------------------------


@pytest.mark.tiering
def test_heavy_hitters_never_demoted(monkeypatch):
    """Under zipf:1.5 the rank-1 auction takes a dominant share of
    events; demoting it would make every window pay a promotion. The
    selector excludes the `rw_key_skew` top-K — the hot keys must never
    appear in any cold store shard, while plenty of tail keys do."""
    from risingwave_tpu.device.skew_stats import SK_KEY_MASK, hot_key_set
    _arm(monkeypatch, skew="1")
    _, job, _ = _run(QA_MV, "qa", 1, 512, "1", kd="zipf:1.5")
    tm = job.tiering
    assert tm.counters["demotions"] > 0
    i = next(p.node_idx for p in tm.plans)
    stats = job.program.node_stats(
        i, np.maximum(job._stat_totals, job._last_stats))
    hot = hot_key_set(stats)
    assert hot, "zipf:1.5 must register heavy hitters"
    demoted = set()
    for (node, _side), store in tm.stores.items():
        if node != i:
            continue
        for d in store.rows:
            demoted.update(int(k) & SK_KEY_MASK for k in d)
    assert demoted, "tail keys must still demote"
    assert not (set(hot) & demoted), \
        f"heavy hitters {set(hot) & demoted} were demoted"


# ---------------------------------------------------------------------------
# zero-compile adoption
# ---------------------------------------------------------------------------


@pytest.mark.aot
@pytest.mark.tiering
def test_demotion_promotion_zero_fresh_compile(monkeypatch):
    """Tier surgery adopts via rebuild-replay on the already-compiled
    node steps: across a window full of demotions AND promotions the
    compile service's counter must not move (the evict/promote jits are
    deliberately outside the service — its counters are the adoption
    assertion surface)."""
    from risingwave_tpu.device.compile_service import get_service
    _arm(monkeypatch)
    os.environ["RW_STATE_TIERING"] = "1"
    # capacity 1024 holds the whole run without growth (growth replays
    # legitimately recompile at the new capacity — not what we measure)
    db = Database(device=DeviceConfig(capacity=1024, mesh_shards=1,
                                      aot_compile=True,
                                      compile_buckets=0))
    db.run(BID_SRC.format(n=N, c=CHUNK, kd=""))
    db.run(QA_MV)
    job = db.catalog.get("qa").runtime["fused_job"]
    for _ in range(5):                   # first demote+promote cycle
        db.tick()                        # (high water ~358 keys; the
    # stream brings ~150/epoch, so pressure lands around tick 3-4 and
    # the two-phase enact one checkpoint later)
    job.sync()
    tm = job.tiering
    assert tm.counters["demote_events"] > 0
    svc = get_service()
    assert svc.wait_idle(120.0)
    before = svc.summary()["compiles"]
    ev0, pr0 = tm.counters["demote_events"], tm.counters["promotions"]
    for _ in range(N // (64 * CHUNK)):
        db.tick()
    job.sync()
    db.tick()
    assert tm.counters["demote_events"] > ev0
    assert tm.counters["promotions"] > pr0
    assert svc.wait_idle(120.0)
    assert svc.summary()["compiles"] == before, \
        "tier surgery must not trigger fresh node-step compiles"


# ---------------------------------------------------------------------------
# observability: rw_state_tiering + risectl tiering
# ---------------------------------------------------------------------------


@pytest.mark.tiering
def test_ctl_tiering_report(monkeypatch, tmp_path, capsys):
    from risingwave_tpu import ctl
    _arm(monkeypatch)
    d = str(tmp_path / "d")
    _, _, db = _run(QA_MV, "qa", 1, 512, "1", data_dir=d, keep=True)
    rc = ctl.main(["tiering", "--data-dir", d])
    out = capsys.readouterr().out
    assert rc == 0
    assert "qa" in out and "AggNode" in out and "resident" in out
    assert ctl.main(["tiering", "nosuch", "--data-dir", d]) == 1
    # DROP clears the demotion journal: a re-created MV under the same
    # name must not replay a predecessor's evictions
    jp = os.path.join(d, "tiering_journal_qa.jsonl")
    assert os.path.exists(jp)
    db.run("DROP MATERIALIZED VIEW qa")
    assert not os.path.exists(jp)


# ---------------------------------------------------------------------------
# the touch stamp rides the merge (ISSUE 27): traced steps against a
# by-key dictionary, and the shape of their programs
# ---------------------------------------------------------------------------

STEP_CAP = 32       # slots before the scheduled grow (then 64)
STEP_ROWS = 24      # delta rows an epoch (padded)
STEP_EPOCHS = 16
GROW_AT = 9         # the epoch that runs right after cap_resize


def _tier_agg_node(combined, capacity=STEP_CAP):
    """A tier-armed count(*) / sum(c1) GROUP BY c0 AggNode, built as the
    fuse planner builds it."""
    from types import SimpleNamespace as NS
    import jax.numpy as jnp
    from risingwave_tpu.device.agg_step import DeviceAggSpec
    from risingwave_tpu.device.fused import AggNode, PackPlan
    spec = DeviceAggSpec.build(["count_star", "sum"], [jnp.int64] * 2,
                               append_only=False)
    calls = [NS(kind="count", arg=None), NS(kind="sum", arg=NS(index=1))]
    node = AggNode(0, [0], calls, PackPlan.plan([(0, 4095, 1)]), spec,
                   capacity, None)
    if combined:
        node.enable_precombine()
    return node


def _tier_join_node(capacity=STEP_CAP, pairs=256):
    import jax.numpy as jnp
    from risingwave_tpu.device.fused import JoinNode, PackPlan
    return JoinNode(0, 1, [0], [0], PackPlan.plan([(0, 4095, 1)]), None,
                    capacity, pairs, [jnp.int64] * 2, [jnp.int64] * 2)


def _pad(rows, width, n=STEP_ROWS):
    """rows: [(sign, masked, *ints)] -> (sign, mask, cols) padded to n."""
    assert len(rows) <= n, len(rows)
    sign = np.zeros(n, np.int32)
    mask = np.zeros(n, bool)
    cols = [np.zeros(n, np.int64) for _ in range(width)]
    for i, (s, m, *vs) in enumerate(rows):
        sign[i], mask[i] = s, m
        for c, v in zip(cols, vs):
            c[i] = v
    return sign, mask, cols


def _agg_delta(rows, node):
    """[(sign, masked, key, value)] as the node's input delta: raw rows,
    or — for a `combined` node — what the PrecombineNode the planner puts
    before it makes of them ([key, raw rows, *partial deltas], one row a
    key: with no exchange the agg takes exactly that)."""
    import jax.numpy as jnp
    from risingwave_tpu.device.fused import Delta, PrecombineNode
    sign, mask, cols = _pad(rows, 2)
    raw = Delta([jnp.asarray(c) for c in cols], jnp.asarray(sign),
                jnp.asarray(mask))
    if not node.combined:
        return raw
    pre = PrecombineNode(0, node.group_idx, node.calls, node.pack, node.spec)
    return pre.apply(None, [raw], None, STEP_ROWS)[1]


def _agg_epochs(rng, combined):
    """STEP_EPOCHS epochs of [(sign, masked, key, value)] with every case
    the carry has to get right, beside random traffic over 20 keys:
    groups that go cold (keys 100..103 after epoch 0), a retraction to
    group death (epoch 3), a delta that nets to nothing on a live key
    (epoch 4, key 100 — touched all the same), a masked-in row of sign 0
    (epoch 5, raw rows only: it names key 101), an empty epoch (6)."""
    held = {}                       # key -> values inserted, not retracted
    out = []
    for e in range(STEP_EPOCHS):
        rows = []
        if e == 0:
            rows = [(1, True, k, 7) for k in (100, 101, 102, 103, 104)]
        elif e == 3:
            rows = [(-1, True, 104, 7)]
        elif e == 4:
            rows = [(1, True, 100, 9), (-1, True, 100, 9)]
        elif e == 5 and not combined:
            rows = [(0, True, 101, 5)]
        if e != 6:
            for _ in range(int(rng.integers(4, 14))):
                k = int(rng.integers(0, 20))
                if held.get(k) and rng.random() < 0.45:
                    rows.append((-1, True, k, held[k].pop()))
                else:
                    v = int(rng.integers(1, 50))
                    held.setdefault(k, []).append(v)
                    rows.append((1, True, k, v))
            rows.append((1, False, 105, 3))         # masked out: no row
        out.append(rows)
    return out


def _stat(node, stats, name):
    return int(stats[node.stat_names.index(name)])


@pytest.mark.tiering
@pytest.mark.parametrize("case", ["agg", "agg-combined", "join-side-a",
                                  "join-side-b"])
def test_touch_rides_merge_against_dictionary(case):
    """Drive the traced tier-armed step and hold the touch column, `tres`
    and `tcold` to a dictionary kept by key in plain Python, after every
    epoch: a surviving key the epoch names reads this tick, any other its
    old stamp, a dead or empty slot 0."""
    from risingwave_tpu.device.fused import _node_step
    from risingwave_tpu.device.sorted_state import EMPTY_KEY
    from risingwave_tpu.device.tiering import TIER_TTL
    rng = np.random.default_rng(27)
    if case.startswith("agg"):
        combined = case == "agg-combined"
        node = _tier_agg_node(combined)
        node.enable_tiering()
        state = node.init_state()
        groups, stamp = {}, {}          # key -> [rows, sum]; key -> tick
        for tick, rows in enumerate(_agg_epochs(rng, combined)):
            if tick == GROW_AT:
                state = node.cap_resize(state, {"main": 2 * STEP_CAP})
            state, _, stats, _ = _node_step(
                node, STEP_ROWS, state, [_agg_delta(rows, node)], None)
            named = set()
            for s, m, k, v in rows:
                if m:
                    named.add(k)
                    g = groups.setdefault(k, [0, 0])
                    g[0] += s
                    g[1] += s * v
            for k in [k for k, g in groups.items() if g[0] == 0]:
                del groups[k]
                stamp.pop(k, None)
            for k in named & groups.keys():
                stamp[k] = tick
            keys = np.asarray(state.inner.main.keys)
            touch = np.asarray(state.touch)
            live = keys != EMPTY_KEY
            assert dict(zip(keys[live].tolist(), touch[live].tolist())) \
                == stamp, (case, tick)
            assert not touch[~live].any(), (case, tick)
            assert int(state.tick) == tick + 1
            assert _stat(node, stats, "tres") == len(stamp)
            assert _stat(node, stats, "tcold") == sum(
                tick - t >= TIER_TTL for t in stamp.values()), (case, tick)
        assert len(keys) == 2 * STEP_CAP and len(stamp) > STEP_CAP // 2
        assert any(STEP_EPOCHS - 1 - t >= TIER_TTL for t in stamp.values())
        return
    _drive_join_against_dictionary(rng, 0 if case == "join-side-a" else 1)


def _drive_join_against_dictionary(rng, side):
    """The join's stamp is per join key and a delta on EITHER input
    stamps the key on BOTH sides. Scripted on `side`: rows whose key the
    OTHER input alone touches (epoch 3, key 100), a key that goes cold
    (101), a delete of one of two rows of a key (epoch 4, key 102: the
    row that stays is stamped), a masked-in row of sign 0 (epoch 5: it
    touches nothing), an empty epoch (6)."""
    import jax.numpy as jnp
    from risingwave_tpu.device.fused import Delta, _node_step
    from risingwave_tpu.device.sorted_state import EMPTY_KEY
    from risingwave_tpu.device.tiering import TIER_TTL
    node = _tier_join_node()
    node.enable_tiering()
    state = node.init_state()
    rows = ({}, {})                 # per side: pk -> jk
    stamp = ({}, {})                # per side: jk -> tick
    next_pk = [1000]

    def ins(s, jk):
        next_pk[0] += 1
        return (1, True, jk, next_pk[0], s)

    def delete(s, jk):
        pk = next(p for p, k in rows[s].items() if k == jk)
        return (-1, True, jk, pk, s)

    other = 1 - side
    for tick in range(STEP_EPOCHS):
        ops = []                    # (sign, masked, jk, pk, side)
        if tick == 0:
            ops = [ins(side, 100), ins(side, 101), ins(other, 101),
                   ins(side, 102), ins(side, 102)]
        elif tick == 3:
            ops = [ins(other, 100)]
        elif tick == 4:
            ops = [delete(side, 102)]
        elif tick == 5:
            ops = [(0, True, 101, 999, side)]
        if tick not in (0, 6):
            for _ in range(int(rng.integers(3, 12))):
                s = int(rng.integers(0, 2))
                jk = int(rng.integers(0, 12))
                gone = {o[3] for o in ops}
                mine = [p for p, k in rows[s].items()
                        if k == jk and p not in gone]
                if mine and rng.random() < 0.4:
                    ops.append((-1, True, jk, mine[0], s))
                else:
                    ops.append(ins(s, jk))
            ops.append((1, False, 103, 998, side))      # masked out
        if tick == GROW_AT:
            state = node.cap_resize(state, {"a": 2 * STEP_CAP,
                                            "b": 2 * STEP_CAP})
        deltas = []
        for s in (0, 1):
            sign, mask, (jk, pk) = _pad(
                [o[:4] for o in ops if o[4] == s], 2)
            deltas.append(Delta([jnp.asarray(jk), jnp.asarray(pk)],
                                jnp.asarray(sign), jnp.asarray(mask),
                                pk=jnp.asarray(pk)))
        state, _, stats, _ = _node_step(node, STEP_ROWS, state, deltas,
                                        None)
        touched = {o[2] for o in ops if o[1] and o[0] != 0}
        for sign, m, jk, pk, s in ops:
            if m and sign > 0:
                rows[s][pk] = jk
            elif m and sign < 0:
                del rows[s][pk]
        for s in (0, 1):
            livek = set(rows[s].values())
            for k in [k for k in stamp[s] if k not in livek]:
                del stamp[s][k]
            for k in livek & touched:
                stamp[s][k] = tick
            assert livek == stamp[s].keys()
        jk = np.asarray(state.inner[side].jk)
        touch = np.asarray(state.touch[side])
        live = jk != EMPTY_KEY
        assert sorted(jk[live].tolist()) == sorted(rows[side].values())
        assert {(k, t) for k, t in zip(jk[live].tolist(),
                                       touch[live].tolist())} \
            == set(stamp[side].items()), (side, tick)
        assert not touch[~live].any(), (side, tick)
        assert _stat(node, stats, "tres") == len(rows[0]) + len(rows[1])
        assert _stat(node, stats, "tcold") == sum(
            tick - stamp[s][k] >= TIER_TTL
            for s in (0, 1) for k in rows[s].values()), (side, tick)
    assert len(jk) == 2 * STEP_CAP
    assert stamp[side][100] >= 3 and stamp[side][101] == 0


def _step_jaxpr(node, n_ins, rows=48):
    import jax
    import jax.numpy as jnp
    from risingwave_tpu.device.fused import Delta
    z = jnp.zeros((rows,), jnp.int64)
    ins = [Delta([z] * (6 if getattr(node, "combined", False) else 2),
                 jnp.zeros((rows,), jnp.int32), jnp.zeros((rows,), bool),
                 pk=z) for _ in range(n_ins)]
    return jax.make_jaxpr(lambda st, ds: node.apply(st, ds, None, rows))(
        node.init_state(), ins).jaxpr


@pytest.mark.tiering
@pytest.mark.parametrize("combined", [False, True])
def test_tier_armed_agg_step_adds_no_loop(combined, jaxpr_loops):
    """Neither the plain nor the tier-armed agg step searches its state:
    no `scan` / `while` of the step reads, carries or produces an array of
    `capacity` rows or more (`searchsorted` of the compile-cheap form is
    such a loop). The change set comes off the merge by position
    (`sorted_state.merge_changes`) and the stamp rides the same trail, so
    arming the tier adds gathers, never a search. (A spec with retractable
    min / max keeps the multiset side state's own searches.)"""
    loops, loops_over = jaxpr_loops
    cap = 1024
    plain, armed = _tier_agg_node(combined, cap), _tier_agg_node(combined, cap)
    armed.enable_tiering()
    for node in (plain, armed):
        for rows in (48, cap):      # a short delta, and one as long as the state
            jaxpr = _step_jaxpr(node, 1, rows)
            assert loops_over(jaxpr, cap) == [], (node.tier, rows)
    assert len(loops(_step_jaxpr(armed, 1))) \
        <= len(loops(_step_jaxpr(plain, 1)))


@pytest.mark.tiering
def test_tier_armed_join_step_searches_follow_the_delta(jaxpr_loops):
    """No loop of the tier-armed join step carries an array as long as a
    side: whatever it searches for, it asks once per delta row (48) or
    pair slot (256), never once per slot of the side (1024)."""
    cap = 1024
    armed = _tier_join_node(cap)
    armed.enable_tiering()
    loops = jaxpr_loops[0](_step_jaxpr(armed, 2))
    assert loops
    for eqn in loops:
        side_long = [v.aval.shape for v in eqn.outvars
                     if getattr(v.aval, "shape", ()) and
                     v.aval.shape[0] >= cap]
        assert not side_long, (eqn.primitive.name, side_long)
