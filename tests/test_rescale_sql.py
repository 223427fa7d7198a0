"""ALTER MATERIALIZED VIEW ... SET PARALLELISM end-to-end: the statement
records a job's parallelism in the catalog and the DDL log at a barrier
boundary (kill/restart replays the log including the ALTER) and moves no
state; a database with a device policy refuses it, because a device job
takes its shard count from `DeviceConfig.mesh_shards` at creation.
Reference: `src/meta/src/stream/scale.rs:2329`."""
import pytest

from risingwave_tpu.sql import Database

NEXMARK_BID = ("CREATE SOURCE bid (auction BIGINT, bidder BIGINT, "
               "price BIGINT, channel VARCHAR, url VARCHAR, "
               "date_time TIMESTAMP, extra VARCHAR) WITH "
               "(connector='nexmark', nexmark.table='bid', "
               "nexmark.max.events='512', nexmark.chunk.size='8')")


def test_alter_parallelism_records_on_host_mv():
    db = Database(device="off")
    db.run("CREATE TABLE t (k INT, v BIGINT)")
    db.run("CREATE MATERIALIZED VIEW mv AS SELECT k, sum(v) AS s "
           "FROM t GROUP BY k")
    db.run("INSERT INTO t VALUES (1, 10), (2, 20), (1, 5)")
    out = db.run("ALTER MATERIALIZED VIEW mv SET PARALLELISM 4")
    assert out == ["ALTER_PARALLELISM_0"]
    assert db.catalog.get("mv").parallelism == 4
    db.run("INSERT INTO t VALUES (2, 7)")
    assert sorted(db.query("SELECT * FROM mv")) == [(1, 15), (2, 27)]
    with pytest.raises(ValueError, match="PARALLELISM must be >= 1"):
        db.run("ALTER MATERIALIZED VIEW mv SET PARALLELISM 0")


@pytest.mark.parametrize("kind", ["fused", "per_operator"])
def test_alter_parallelism_refused_on_device_job(kind):
    """Fused job or per-operator device executor alike: nothing on the
    device path re-shards, and the statement says where the shard count
    comes from instead of pretending."""
    db = Database(device="on")
    if kind == "fused":
        db.run(NEXMARK_BID)
        db.run("CREATE MATERIALIZED VIEW mv AS SELECT auction, "
               "count(*) AS c FROM bid GROUP BY auction")
    else:
        db.run("CREATE TABLE t (k INT, v BIGINT)")
        db.run("CREATE MATERIALIZED VIEW mv AS SELECT k, sum(v) AS s "
               "FROM t GROUP BY k")
    fused = db.catalog.get("mv").runtime.get("fused_job") is not None
    assert fused == (kind == "fused")
    before = db.catalog.get("mv").parallelism
    with pytest.raises(ValueError, match="DeviceConfig.mesh_shards"):
        db.run("ALTER MATERIALIZED VIEW mv SET PARALLELISM 2")
    assert db.catalog.get("mv").parallelism == before
    # refused before the DDL log: a restart has nothing to replay
    assert not any("PARALLELISM" in sql
                   for _, sql in db._ddl_log.iter_all())


def test_alter_parallelism_survives_restart(tmp_path):
    """The ALTER is DDL-logged: recovery replays it."""
    d = str(tmp_path)
    db = Database(data_dir=d, device="off")
    db.run("CREATE TABLE t (k INT, v BIGINT)")
    db.run("CREATE MATERIALIZED VIEW mv AS SELECT k, sum(v) AS s "
           "FROM t GROUP BY k")
    db.run("INSERT INTO t VALUES (1, 10), (2, 20), (1, 5)")
    db.run("ALTER MATERIALIZED VIEW mv SET PARALLELISM 2")
    db.run("INSERT INTO t VALUES (2, 7), (3, 1)")
    before = sorted(db.query("SELECT * FROM mv"))

    db2 = Database(data_dir=d, device="off")
    assert db2.catalog.get("mv").parallelism == 2
    assert sorted(db2.query("SELECT * FROM mv")) == before
    db2.run("DELETE FROM t WHERE v = 20")
    db2.run("INSERT INTO t VALUES (3, 4)")
    assert sorted(db2.query("SELECT * FROM mv")) == sorted(
        db2.query("SELECT k, sum(v) FROM t GROUP BY k"))


def test_alter_replay_does_not_tick_half_built_dataflow(tmp_path):
    """Regression (review finding): a replayed ALTER must not flush() —
    that ticks sources into only the already-replayed jobs, permanently
    diverging MVs created after the ALTER in the DDL log."""
    d = str(tmp_path)
    total = 600   # bounded source: drains fully, so counts are stable
    db = Database(data_dir=d, device="off")
    db.run("CREATE SOURCE s (v BIGINT) WITH (connector='datagen', "
           f"rows.per.poll='8', datagen.max.rows='{total}')")
    db.run("CREATE MATERIALIZED VIEW m1 AS SELECT v, count(*) AS c "
           "FROM s GROUP BY v")
    db.run("ALTER MATERIALIZED VIEW m1 SET PARALLELISM 2")
    db.run("CREATE MATERIALIZED VIEW m2 AS SELECT count(*) AS c FROM s")
    for _ in range(3):
        db.run("FLUSH")
    n1 = sum(r[1] for r in db.query("SELECT * FROM m1"))
    (n2,) = db.query("SELECT * FROM m2")[0]
    # sources are from-now streams: m2 (created after the ALTER, whose
    # barrier advanced the source) legitimately sees fewer rows
    assert n1 == total and 0 < n2 <= total

    db2 = Database(data_dir=d, device="off")
    m1 = sum(r[1] for r in db2.query("SELECT * FROM m1"))
    (m2,) = db2.query("SELECT * FROM m2")[0]
    # the replay invariant: restart must reproduce EXACTLY the committed
    # counts — a replayed ALTER that ticked would diverge them
    assert m1 == n1 and m2 == n2, (m1, m2, n1, n2)


def test_alter_replay_on_device_directory_records_and_opens(tmp_path):
    """A `single` device directory whose log holds an ALTER from before
    the statement was refused still opens: the replay records the
    parallelism and raises nothing."""
    d = str(tmp_path)
    db = Database(data_dir=d, device="on")
    db.run("CREATE TABLE t (k INT, v BIGINT)")
    db.run("CREATE MATERIALIZED VIEW mv AS SELECT k, sum(v) AS s "
           "FROM t GROUP BY k")
    db.run("INSERT INTO t VALUES (1, 10), (2, 20), (1, 5)")
    db._log_ddl("ALTER MATERIALIZED VIEW mv SET PARALLELISM 2")
    db2 = Database(data_dir=d, device="on")
    assert db2.catalog.get("mv").parallelism == 2
    assert sorted(db2.query("SELECT * FROM mv")) == [(1, 15), (2, 20)]


def test_alter_rejects_non_mv():
    db = Database(device="on")
    db.run("CREATE TABLE t (k INT)")
    with pytest.raises(ValueError, match="not a materialized view"):
        db.run("ALTER MATERIALIZED VIEW t SET PARALLELISM 2")
