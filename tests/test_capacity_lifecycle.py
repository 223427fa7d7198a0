"""Capacity lifecycle of fused device programs: predictive growth,
cascade-free replay accounting, high-water persistence, and the
persistent-compile-cache knob.

The growth-ladder contract (ISSUE 4): a fused MV forced to start at a
tiny capacity must (a) produce rows bit-identical to the same query with
device='off', (b) reach steady state in at most 2 growth replays with
prediction on, and (c) recover()/re-create with ZERO growth replays
thanks to persisted high-water marks.
"""
import json
import os

import pytest

from risingwave_tpu.config import DeviceConfig, resolve_device
from risingwave_tpu.device.capacity import (bucket, predict_capacity,
                                            project)
from risingwave_tpu.sql import Database

N = 5_000
CHUNK = 32          # fused epoch = 64 * CHUNK = 2048 events

BID_SRC = ("CREATE SOURCE bid (auction BIGINT, bidder BIGINT, price BIGINT,"
           " channel VARCHAR, url VARCHAR, date_time TIMESTAMP,"
           " extra VARCHAR) WITH (connector='nexmark',"
           " nexmark.table='bid', nexmark.max.events='{n}',"
           " nexmark.chunk.size='{c}')")
Q4 = ("CREATE MATERIALIZED VIEW q4 AS SELECT auction, count(*) AS c,"
      " sum(price) AS s, max(price) AS m FROM bid GROUP BY auction")


def drive(db, n=N, chunk=CHUNK):
    for _ in range(n // (64 * chunk) + 3):
        db.tick()


def host_rows():
    db = Database(device="off")
    db.run(BID_SRC.format(n=N, c=CHUNK))
    db.run(Q4)
    drive(db)
    return sorted(db.query("SELECT * FROM q4"))


@pytest.fixture(scope="module")
def oracle():
    return host_rows()


# ---------------------------------------------------------------------------
# predictor math
# ---------------------------------------------------------------------------

def test_project_extrapolates_rate():
    assert project(0, 1_000, 100_000) == 0
    # 100 entries after 1k events, 100k horizon: at least the linear
    # extrapolation (headroom on top), never less than the observed need
    assert project(100, 1_000, 100_000) >= 100 * 100
    assert project(100, 1_000, 100_000) >= 100
    # no horizon at all: a fixed step ahead of the need
    assert project(100, 0, None) == 400
    # horizon reached (sync at drain): the need is final — size exactly
    assert project(100, 1_000, 500) == 100
    assert project(100, 1_000, 1_000) == 100


def test_predict_capacity_invariants():
    assert predict_capacity(10, 256) == 256          # fits: unchanged
    for need, cur in [(300, 256), (5_000, 1_024), (70, 64)]:
        got = predict_capacity(need, cur)
        assert got >= need and got >= cur
        assert got & (got - 1) == 0                  # pow2 bucket
    # with a horizon, the projection rides the observed rate
    got = predict_capacity(300, 256, events_seen=100, horizon=200)
    assert got == bucket(project(300, 100, 200))


def test_fused_predict_caps_respects_budget_floor():
    """The HBM budget trims headroom, never correctness: clamped targets
    stay >= the observed need and >= the current capacity."""
    db = Database(device=DeviceConfig(capacity=64))
    db.run(BID_SRC.format(n=N, c=CHUNK))
    db.run(Q4)
    job = db._fused["q4"]
    job.counter = 2048
    job.hbm_budget_mb = 1          # absurdly small: everything clamps
    needs = {i: {s: c * 100 for s, c in node.cap_current().items()}
             for i, node in enumerate(job.program.nodes)}
    targets = job._predict_caps(needs)
    for i, node in enumerate(job.program.nodes):
        cur = node.cap_current()
        for s, c in cur.items():
            t = targets[i][s]
            assert t >= needs[i][s] and t >= c
            assert t & (t - 1) == 0


# ---------------------------------------------------------------------------
# the growth ladder
# ---------------------------------------------------------------------------

def test_tiny_capacity_bit_identical_and_few_replays(oracle):
    """(a) + (b): a 64-slot start must converge in <= 2 predictive growth
    replays and match the host path bit-for-bit."""
    db = Database(device=DeviceConfig(capacity=64))
    db.run(BID_SRC.format(n=N, c=CHUNK))
    db.run(Q4)
    job = db._fused["q4"]
    assert job.predictive
    drive(db)
    got = sorted(db.query("SELECT * FROM q4"))
    assert got == oracle
    assert job.growth_replays >= 1, "test must exercise the ladder"
    assert job.growth_replays <= 2, (
        f"predictive sizing regressed: {job.growth_replays} growth "
        f"replays (report: {job.cap_report()})")
    rep = job.cap_report()
    assert rep["retraces"] >= 1 and rep["growths"] >= 1
    assert any(c["main"] > 64 for c in rep["nodes"].values())


def test_blind_doubling_still_correct_but_replays_more(oracle):
    """predictive_growth=false restores the old one-bucket-at-a-time
    ladder — still exact, measurably more replays than the predictor."""
    db = Database(device=DeviceConfig(capacity=64, predictive_growth=False))
    db.run(BID_SRC.format(n=N, c=CHUNK))
    db.run(Q4)
    job = db._fused["q4"]
    drive(db)
    assert sorted(db.query("SELECT * FROM q4")) == oracle
    assert job.growth_replays >= 1


def test_recovery_presizes_from_high_water(tmp_path, oracle):
    """(c): a restart replays at the persisted high-water capacities —
    zero additional growth replays, same rows."""
    d = str(tmp_path / "data")
    db = Database(data_dir=d, device=DeviceConfig(capacity=64))
    db.run(BID_SRC.format(n=N, c=CHUNK))
    db.run(Q4)
    drive(db)
    job = db._fused["q4"]
    assert sorted(db.query("SELECT * FROM q4")) == oracle
    replays = job.growth_replays
    assert replays >= 1
    caps = {k: dict(v) for k, v in job.cap_report()["nodes"].items()}
    db.store.close()
    del db

    db2 = Database(data_dir=d, device=DeviceConfig(capacity=64))
    job2 = db2._fused["q4"]
    # counters restored (cumulative), and the recovery replay itself
    # performed no growth — the presized states absorbed every epoch
    assert job2.growth_replays == replays
    for k, v in job2.cap_report()["nodes"].items():
        for s, c in v.items():
            assert c >= caps[k][s]
    assert sorted(db2.query("SELECT * FROM q4")) == oracle


def test_recreated_mv_presizes_from_predecessor(oracle):
    """DROP + CREATE of the same plan starts at the dropped job's
    high-water capacities (Database cap-hint registry -> try_fuse) and
    never climbs the ladder again."""
    db = Database(device=DeviceConfig(capacity=64))
    db.run(BID_SRC.format(n=N, c=CHUNK))
    db.run(Q4)
    drive(db)
    job = db._fused["q4"]
    assert job.growth_replays >= 1
    caps = job.cap_hints()
    db.run("DROP MATERIALIZED VIEW q4")
    db.run(Q4)
    job2 = db._fused["q4"]
    assert job2 is not job
    for i, hint in caps.items():
        assert job2.program.nodes[i].cap_current() == hint["caps"]
    drive(db)
    assert job2.growth_replays == 0
    assert sorted(db.query("SELECT * FROM q4")) == oracle


def test_recreated_mv_different_plan_ignores_hints():
    """A DIFFERENT query under the same MV name must not inherit the old
    plan's capacities (hints match on the node's structural hash, not
    just index + type)."""
    db = Database(device=DeviceConfig(capacity=64))
    db.run(BID_SRC.format(n=N, c=CHUNK))
    db.run(Q4)
    drive(db)
    assert db._fused["q4"].growth_replays >= 1       # capacities grew
    db.run("DROP MATERIALIZED VIEW q4")
    db.run("CREATE MATERIALIZED VIEW q4 AS SELECT bidder, count(*) AS c"
           " FROM bid GROUP BY bidder")
    job2 = db._fused["q4"]
    for node in job2.program.nodes:
        for cap in node.cap_current().values():
            assert cap <= 4 * 64, "stale hint presized a different plan"


# ---------------------------------------------------------------------------
# persistence schema + risectl surface
# ---------------------------------------------------------------------------

def test_job_state_rows_schema(tmp_path):
    """High-water rows live above the reserved-counter keyspace and stay
    out of key 0 (the committed event counter old stores already hold)."""
    from risingwave_tpu.device import fused as F
    d = str(tmp_path / "data")
    db = Database(data_dir=d, device=DeviceConfig(capacity=64))
    db.run(BID_SRC.format(n=N, c=CHUNK))
    db.run(Q4)
    drive(db)
    job = db._fused["q4"]
    rows = {int(r[0]): int(r[1]) for r in job.job_state_table.iter_all()}
    assert rows[F._JS_COUNTER] >= N
    assert rows[F._JS_REPLAYS] == job.growth_replays
    cap_keys = [k for k in rows if k >= F._JS_CAP_BASE]
    assert cap_keys, "capacity high-water rows must persist"
    assert all(rows[k] > 0 for k in cap_keys)


def test_ctl_fused_stats(tmp_path, capsys):
    from risingwave_tpu import ctl
    d = str(tmp_path / "data")
    db = Database(data_dir=d, device=DeviceConfig(capacity=64))
    db.run(BID_SRC.format(n=N, c=CHUNK))
    db.run(Q4)
    drive(db)
    replays = db._fused["q4"].growth_replays
    db.store.close()
    del db
    assert ctl.main(["fused-stats", "--data-dir", d]) == 0
    out = json.loads(capsys.readouterr().out)
    assert "q4" in out
    rep = out["q4"]
    # cumulative counters survive the reopen; recovery added none
    assert rep["growth_replays"] == replays
    assert rep["committed_events"] >= N
    assert rep["nodes"] and all(v for v in rep["nodes"].values())


def test_ctl_fused_stats_no_jobs(tmp_path, capsys):
    from risingwave_tpu import ctl
    d = str(tmp_path / "data")
    db = Database(data_dir=d)
    db.run("CREATE TABLE t (k INT)")
    db.run("FLUSH")
    db.store.close()
    assert ctl.main(["fused-stats", "--data-dir", d]) == 0
    assert "no fused device jobs" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# persistent compile cache knob
# ---------------------------------------------------------------------------

def test_compile_cache_knob(tmp_path, monkeypatch):
    import jax

    from risingwave_tpu import device
    from risingwave_tpu.device import (compile_cache_dir,
                                       configure_compile_cache)
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        # no explicit directory: the fixed <checkout>/.jax_cache — never a
        # temporary name (the path is part of the cache key)
        assert configure_compile_cache() == device._DEFAULT_CACHE
        assert device._DEFAULT_CACHE == os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".jax_cache")
        want = str(tmp_path / "cc")
        assert configure_compile_cache(want) == want
        assert compile_cache_dir() == want
        # the DeviceConfig knob routes through resolve_device
        want2 = str(tmp_path / "cfg")
        resolve_device(DeviceConfig(compile_cache_dir=want2))
        assert compile_cache_dir() == want2
        # a cache placed from outside wins: jax read the variable itself
        # at import, and nothing in the program writes the option
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / "env"))
        assert configure_compile_cache(want) == want2
        resolve_device(DeviceConfig(compile_cache_dir=want))
        assert jax.config.jax_compilation_cache_dir == want2
        # the compile manifest asks the same resolver
        from risingwave_tpu.device.compile_service import (MANIFEST_FILE,
                                                           get_service)
        assert get_service()._manifest_path() == os.path.join(
            want2, MANIFEST_FILE)
        jax.config.update("jax_compilation_cache_dir", None)
        assert compile_cache_dir() is None
        assert get_service()._manifest_path() is None
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
