"""Mesh-sharded fused epoch programs (device/shard_exec.py).

The contract under test: `DeviceConfig.mesh_shards=8` executes a fused
MV as ONE shard_map'd program over the 8-device mesh (vnode-block state
partitioning, in-program all_to_all exchange, psum/pmax stats) and is a
pure execution detail — results are BIT-IDENTICAL to the single-chip
path, including row order, on q1/q3/q5-shaped Nexmark plans. The
conftest forces 8 virtual CPU devices so all of this runs in tier-1.
"""
import os

import numpy as np
import pytest

from risingwave_tpu.config import DeviceConfig
from risingwave_tpu.core.vnode import VNODE_COUNT
from risingwave_tpu.parallel.mesh import shard_of_vnode, vnode_block_bounds
from risingwave_tpu.sql import Database

N = 4096
CHUNK = 32          # fused epoch = 64 * CHUNK = 2048 events

BID_SRC = ("CREATE SOURCE bid (auction BIGINT, bidder BIGINT, price BIGINT,"
           " channel VARCHAR, url VARCHAR, date_time TIMESTAMP,"
           " extra VARCHAR) WITH (connector='nexmark',"
           " nexmark.table='bid', nexmark.max.events='{n}',"
           " nexmark.chunk.size='{c}')")
AUCTION_SRC = ("CREATE SOURCE auction (id BIGINT, item_name VARCHAR,"
               " description VARCHAR, initial_bid BIGINT, reserve BIGINT,"
               " date_time TIMESTAMP, expires TIMESTAMP, seller BIGINT,"
               " category BIGINT, extra VARCHAR) WITH (connector='nexmark',"
               " nexmark.table='auction', nexmark.max.events='{n}',"
               " nexmark.chunk.size='{c}')")

# q1-shaped: stateless projection arithmetic folded into a grouped agg
# (a bare stateless MV stays on host by design — no pair identity)
Q1_MV = ("CREATE MATERIALIZED VIEW q1a AS SELECT bidder,"
         " count(*) AS n, sum(price) AS dol, max(price) AS top"
         " FROM bid GROUP BY bidder")
# q3-shaped: filtered equi-join with pair-identity MV
Q3_MV = ("CREATE MATERIALIZED VIEW q3a AS SELECT b.auction, b.price,"
         " a.seller, a.category FROM bid b JOIN auction a"
         " ON b.auction = a.id WHERE b.price > 500")
# q5 (reference SQL): hop windows, two agg chains, non-equi join
Q5_MV = """CREATE MATERIALIZED VIEW q5 AS
SELECT AuctionBids.auction, AuctionBids.num FROM (
    SELECT bid.auction, count(*) AS num, window_start AS starttime
    FROM HOP(bid, date_time, INTERVAL '2' SECOND, INTERVAL '10' SECOND)
    GROUP BY window_start, bid.auction
) AS AuctionBids
JOIN (
    SELECT max(CountBids.num) AS maxn, CountBids.starttime_c
    FROM (
        SELECT count(*) AS num, window_start AS starttime_c
        FROM HOP(bid, date_time, INTERVAL '2' SECOND, INTERVAL '10' SECOND)
        GROUP BY bid.auction, window_start
    ) AS CountBids
    GROUP BY CountBids.starttime_c
) AS MaxBids
ON AuctionBids.starttime = MaxBids.starttime_c
   AND AuctionBids.num >= MaxBids.maxn"""


def _run(mv_sql, name, shards, srcs=(BID_SRC,), n=N, capacity=512,
         aot=False, data_dir=None, keep=False, int_device=False):
    if int_device:
        # the user's spelling of "n chips"; sized like every other run of
        # this file so that it compiles no program of its own
        db = Database(device=shards, data_dir=data_dir)
        assert db.device.mesh_shards == shards
        db.device.capacity, db.device.aot_compile = capacity, aot
    else:
        db = Database(device=DeviceConfig(capacity=capacity,
                                          mesh_shards=shards,
                                          aot_compile=aot),
                      data_dir=data_dir)
    for s in srcs:
        db.run(s.format(n=n, c=CHUNK))
    db.run(mv_sql)
    job = db.catalog.get(name).runtime["fused_job"]
    assert job is not None, f"{name} must fuse"
    if shards > 1:
        assert job.program.mesh is not None \
            and job.program.mesh.devices.size == shards
    else:
        assert job.program.mesh is None
    for _ in range(n // (64 * CHUNK) + 3):
        db.tick()
    job.sync()
    rows = db.query(f"SELECT * FROM {name}")
    return (rows, job, db) if keep else (rows, job, None)


# ---------------------------------------------------------------------------
# vnode -> shard mapping edges
# ---------------------------------------------------------------------------


def test_vnode_block_bounds_edges():
    """Contiguous blocks must cover every vnode exactly once for ANY
    shard count — including ones that do not divide VNODE_COUNT — with
    block sizes differing by at most one (balanced)."""
    for n in (1, 2, 3, 5, 7, 8, 100, VNODE_COUNT):
        b = vnode_block_bounds(n)
        assert b[0] == 0 and b[-1] == VNODE_COUNT
        sizes = np.diff(b)
        assert (sizes >= 0).all() and sizes.sum() == VNODE_COUNT
        assert sizes.max() - sizes.min() <= 1
        # shard_of_vnode must agree with the block bounds exactly
        vn = np.arange(VNODE_COUNT)
        s = shard_of_vnode(vn, n)
        for k in range(n):
            blk = vn[(vn >= b[k]) & (vn < b[k + 1])]
            assert (s[blk] == k).all()
        assert s.min() == 0 and s.max() == n - 1 if n <= VNODE_COUNT else True


def test_vnode_one_shard_degenerate():
    assert (shard_of_vnode(np.arange(VNODE_COUNT), 1) == 0).all()
    assert list(vnode_block_bounds(1)) == [0, VNODE_COUNT]


def test_vnode_rescale_block_boundaries():
    """Doubling the shard count is a block-boundary SPLIT: every old
    boundary survives (bounds(n) is a subset of bounds(2n)), so rescale
    moves contiguous sub-blocks instead of reshuffling keys."""
    for n in (1, 2, 4, 8, 16):
        coarse = set(vnode_block_bounds(n).tolist())
        fine = set(vnode_block_bounds(2 * n).tolist())
        assert coarse <= fine


# ---------------------------------------------------------------------------
# sharded-vs-single bit-identity (q1/q3/q5-shaped fused plans)
# ---------------------------------------------------------------------------


@pytest.mark.mesh
def test_q1_agg_bit_identity():
    r1, j1, _ = _run(Q1_MV, "q1a", 1)
    r8, j8, _ = _run(Q1_MV, "q1a", 8)
    assert r1 == r8                     # bit-identical, ORDER included
    assert j8.plan_hash != j1.plan_hash  # per-shard state never collides


@pytest.mark.mesh
def test_device_int_fuses_over_the_mesh():
    """`Database(device=8)` is `mesh_shards=8`: the group-by fuses into
    one program over all 8 devices (`_run` asserts the mesh's size) and
    its MV is the one-chip run's, row for row."""
    r8, _, _ = _run(Q1_MV, "q1a", 8, int_device=True)
    r1, _, _ = _run(Q1_MV, "q1a", 1)
    assert len(r1) > 0 and r8 == r1


def test_device_int_keeps_the_nexmark_source_virtual():
    """Under `device=8` fused MVs make their events on the device, so the
    source starts no host datagen until a non-fusable consumer appears."""
    db = Database(device=8)
    db.run(BID_SRC.format(n=N, c=CHUNK))
    assert db.catalog.get("bid").runtime["virtual"] is True
    assert "bid" not in db._iters


@pytest.mark.mesh
def test_q3_join_bit_identity():
    r1, _, _ = _run(Q3_MV, "q3a", 1, srcs=(BID_SRC, AUCTION_SRC))
    r8, j8, _ = _run(Q3_MV, "q3a", 8, srcs=(BID_SRC, AUCTION_SRC))
    assert len(r1) > 0
    assert r1 == r8
    # the join's two inputs were exchange-routed in-program
    from risingwave_tpu.device.fused import JoinNode
    joins = [n for n in j8.program.nodes if isinstance(n, JoinNode)]
    assert joins and all(n.exch is not None for n in joins)


@pytest.mark.mesh
def test_non_dividing_cadence_pads_and_engages_8_shards(monkeypatch):
    """ROADMAP mesh residual closed: an epoch cadence that does not
    divide the shard count used to degrade SILENTLY to one chip. Now
    each shard's event block is ceil-div sized and the tail block pads
    (over-generated ids mask out inside the traced step) — all 8 shards
    engage at cadence 2015 (2015 % 8 == 7) and the MV stays
    bit-identical to the single-chip run."""
    from risingwave_tpu.device import fuse_planner
    monkeypatch.setattr(fuse_planner, "EPOCH_POLLS", 65)
    n, chunk = 4096, 31            # cadence = 65 * 31 = 2015

    def run(shards):
        db = Database(device=DeviceConfig(capacity=512,
                                          mesh_shards=shards))
        db.run(BID_SRC.format(n=n, c=chunk))
        db.run(Q1_MV)
        job = db.catalog.get("q1a").runtime["fused_job"]
        assert job is not None and job.program.epoch_events == 2015
        for _ in range(n // 2015 + 4):
            db.tick()
        job.sync()
        return db.query("SELECT * FROM q1a"), job

    r8, j8 = run(8)
    assert j8.program.mesh is not None \
        and j8.program.mesh.devices.size == 8, \
        "non-dividing cadence must still engage the full mesh"
    r1, j1 = run(1)
    assert j1.program.mesh is None
    assert len(r1) > 0 and r8 == r1
    # the flow stats are exact too: the padded tail's masked events are
    # recounted out of rows_out before the psum
    src = 0
    assert j8.program.node_stats(src, j8._stat_totals).get("rows_out") \
        == j1.program.node_stats(src, j1._stat_totals).get("rows_out")


@pytest.mark.mesh
def test_q5_hop_agg_join_bit_identity():
    r1, _, _ = _run(Q5_MV, "q5", 1, n=2048)
    r8, j8, _ = _run(Q5_MV, "q5", 8, n=2048)
    assert len(r1) > 0
    assert r1 == r8


# ---------------------------------------------------------------------------
# exchange capacity lifecycle
# ---------------------------------------------------------------------------


@pytest.mark.mesh
def test_exchange_overflow_grows_and_replays(monkeypatch):
    """A send bucket too small for the epoch's skew must overflow the
    `exch` stat, grow through the NORMAL replay path, and still produce
    the single-chip answer — correctness never depends on the initial
    exchange sizing."""
    from risingwave_tpu.device import capacity as cap_mod
    monkeypatch.setattr(cap_mod, "exchange_cap",
                        lambda epoch_events, n_shards, lo=4: 4)
    r8, j8, _ = _run(Q1_MV, "q1a", 8)
    r1, _, _ = _run(Q1_MV, "q1a", 1)
    assert r8 == r1
    assert j8.growth_replays >= 1
    grown = [n.exch for n in j8.program.nodes if n.exch is not None]
    assert grown and all(e > 4 for e in grown)


@pytest.mark.mesh
def test_sharded_capacity_growth_replay():
    """Tiny main capacity on the sharded path: per-shard overflow is
    pmax-reported, the growth replay runs through the shard axis, and
    the answer still matches the single chip."""
    r8, j8, _ = _run(Q1_MV, "q1a", 8, capacity=4)
    r1, _, _ = _run(Q1_MV, "q1a", 1)
    assert r8 == r1
    assert len(r1) > 8 * 4              # per-shard groups really overflow
    assert j8.growth_replays >= 1


# ---------------------------------------------------------------------------
# observability: shards dimension + exchange phase
# ---------------------------------------------------------------------------


@pytest.mark.mesh
def test_profiler_shards_and_exchange_phase():
    _, job, db = _run(Q1_MV, "q1a", 8, keep=True)
    assert job.profiler.shards == 8
    assert job.profiler.totals.get("exchange", 0.0) > 0.0
    rows = db.query("SELECT * FROM rw_epoch_profile")
    assert rows
    dispatched = 0
    for j, seq, events, shards, hp, h2d, pro, disp, exch, sync, dem, \
            commit, wall in rows:
        assert shards == 8
        phases = hp + h2d + pro + disp + exch + sync + dem + commit
        # the exchange split must stay disjoint from dispatch: phase
        # sums within 10% of wall (epsilon for sub-ms timer noise)
        assert phases <= wall * 1.001 + 0.05
        if wall > 1.0:
            assert phases >= wall * 0.9
        if events and exch > 0.0:
            dispatched += 1
    assert dispatched, "dispatched epochs must time the exchange stage"
    from risingwave_tpu.utils.metrics import REGISTRY
    text = REGISTRY.expose()
    assert 'rw_hbm_bytes{job="q1a"' in text and 'shards="8"' in text


# ---------------------------------------------------------------------------
# durability: device marker + recovery + offline compile-status
# ---------------------------------------------------------------------------


@pytest.mark.mesh
def test_mesh_marker_and_recovery(tmp_path):
    d = str(tmp_path / "data")
    r8, job, db = _run(Q1_MV, "q1a", 8, data_dir=d, keep=True)
    committed = job.committed
    assert committed >= N
    del db
    # same shard count: recovery replays device-side and presizes
    db2 = Database(data_dir=d, device=DeviceConfig(capacity=512,
                                                   mesh_shards=8))
    j2 = db2._fused["q1a"]
    assert j2.committed == committed
    assert db2.query("SELECT * FROM q1a") == r8
    del db2
    # different shard count: state layouts differ per shard — fail fast
    with pytest.raises(ValueError, match="device="):
        Database(data_dir=d, device=DeviceConfig(capacity=512))


@pytest.mark.mesh
@pytest.mark.aot
def test_offline_compile_status_dead_dir(tmp_path, capsys, monkeypatch):
    """`risectl compile-status --offline` must answer from a dead data
    dir via the compile_manifest.json mirror — no Database, no rebuild,
    no recompiles (the PR 6 residual)."""
    d = str(tmp_path / "data")
    _, job, db = _run(Q1_MV, "q1a", 8, aot=True, data_dir=d, keep=True)
    plan_hash = job.plan_hash
    from risingwave_tpu.device.compile_service import get_service
    assert get_service().wait_idle(60.0)
    del db
    assert os.path.exists(os.path.join(d, "compile_manifest.json"))
    from risingwave_tpu import ctl
    rc = ctl.main(["compile-status", "--data-dir", d, "--offline"])
    out = capsys.readouterr().out
    assert rc == 0
    assert plan_hash in out             # the plan shape is on record
    assert '"shards": 8' in out         # sharded executables are labeled


@pytest.mark.mesh
def test_too_few_devices_fails_loudly():
    """No quiet road to fewer chips: a mesh larger than the default
    platform raises, and so does the CREATE MATERIALIZED VIEW that asked
    for it — the MV must not land on one chip (or the host) unsaid."""
    import jax
    from risingwave_tpu.parallel.mesh import make_mesh
    n = 2 * len(jax.devices())           # 16 on the 8-device platform
    with pytest.raises(ValueError, match=f"need {n} devices"):
        make_mesh(n)
    db = Database(device=DeviceConfig(capacity=512, mesh_shards=n,
                                      aot_compile=False))
    db.run(BID_SRC.format(n=N, c=CHUNK))
    with pytest.raises(ValueError, match=f"need {n} devices"):
        db.run(Q1_MV)
    assert "q1a" not in db._fused

