"""ISSUE 20 sensory plane: flow telemetry, pressure attribution, and
the flight recorder.

Acceptance contract under test: per-vnode TRAFFIC histograms are exact
(unique-key workload: traffic == occupancy per bucket, totals equal the
row count; an 8-shard run's psum'd totals equal the 1-shard run's
bit-for-bit); zipf flow over a spread key set reads as traffic-vs-
occupancy divergence while a unique-key flow reads 0; the PressureBoard
scalar decomposes into labeled contributions that recombine to the
global EXACTLY (by construction — `pressure_of` IS
`combine_contributions(attribution(db))`) under the slow-sink and
slow-worker failpoints; a seeded device fault auto-dumps a flight-
recorder bundle readable from the DEAD data dir via `risectl blackbox`;
`trace export` stays valid Chrome JSON with the new instant events; and
the unarmed path leaves no tv* slots or `flow` signature flag behind.
"""
import json
import os
import time

import numpy as np
import pytest

from risingwave_tpu.config import DeviceConfig, ROBUSTNESS
from risingwave_tpu.sql import Database
from risingwave_tpu.utils import failpoint as fp
from risingwave_tpu.utils.overload import PRESSURE

pytestmark = pytest.mark.telemetry

N = 4096
CHUNK = 32

BID_SRC = ("CREATE SOURCE bid (auction BIGINT, bidder BIGINT, price BIGINT,"
           " channel VARCHAR, url VARCHAR, date_time TIMESTAMP,"
           " extra VARCHAR) WITH (connector='nexmark',"
           " nexmark.table='bid', nexmark.max.events='{n}',"
           " nexmark.chunk.size='{c}', nexmark.key.dist='{kd}')")
PERSON_SRC = ("CREATE SOURCE person (id BIGINT, name VARCHAR,"
              " email_address VARCHAR, credit_card VARCHAR, city VARCHAR,"
              " state VARCHAR, date_time TIMESTAMP, extra VARCHAR)"
              " WITH (connector='nexmark', nexmark.table='person',"
              " nexmark.max.events='{n}', nexmark.chunk.size='{c}')")
Q1_MV = ("CREATE MATERIALIZED VIEW q1a AS SELECT bidder,"
         " count(*) AS n, sum(price) AS dol, max(price) AS top"
         " FROM bid GROUP BY bidder")
PP_MV = ("CREATE MATERIALIZED VIEW pp AS SELECT id, count(*) AS c"
         " FROM person GROUP BY id")

_KNOBS = ("overload_window_s", "overload_high", "overload_low",
          "overload_hold_s", "serving_staleness_epochs",
          "exchange_credits")


@pytest.fixture(autouse=True)
def _clean():
    saved = {k: getattr(ROBUSTNESS, k) for k in _KNOBS}
    fp.reset()
    PRESSURE.reset()
    yield
    fp.reset()
    PRESSURE.reset()
    for k, v in saved.items():
        setattr(ROBUSTNESS, k, v)


def _arm_flow(monkeypatch, flow="1", skew="1", pre="0", hot="0", reb="0"):
    monkeypatch.setenv("RW_FLOW_STATS", flow)
    monkeypatch.setenv("RW_SKEW_STATS", skew)
    monkeypatch.setenv("RW_AGG_PRECOMBINE", pre)
    monkeypatch.setenv("RW_HOT_KEY_REP", hot)
    monkeypatch.setenv("RW_VNODE_REBALANCE", reb)


def _run(mv_sql, name, shards=1, srcs=(BID_SRC,), kd="zipf:4", n=N,
         capacity=2048, data_dir=None):
    db = Database(device=DeviceConfig(capacity=capacity,
                                      mesh_shards=shards,
                                      aot_compile=False,
                                      compile_buckets=0),
                  data_dir=data_dir)
    for s in srcs:
        db.run(s.format(n=n, c=CHUNK, kd=kd))
    db.run(mv_sql)
    job = db.catalog.get(name).runtime["fused_job"]
    assert job is not None, f"{name} must fuse"
    for _ in range(n // (64 * CHUNK) + 3):
        db.tick()
    job.sync()
    db.tick()
    return db, job


def _traffic(job, node_i):
    from risingwave_tpu.device.skew_stats import SK_BUCKETS
    st = job.program.node_stats(node_i, job._stat_totals)
    return [int(st.get(f"tv{b}", 0)) for b in range(SK_BUCKETS)]


def _flow_node(job):
    return next(i for i, nd in enumerate(job.program.nodes) if nd.flow)


# ---------------------------------------------------------------------------
# tentpole 1: traffic-per-vnode histograms
# ---------------------------------------------------------------------------


def test_traffic_histogram_exact_unique_keys(monkeypatch):
    """Unique group keys (person id): every routed row creates exactly
    one live key, so the traffic histogram must equal the occupancy
    histogram PER BUCKET and its total must equal the MV's row count —
    exact counts, hand-checkable against the MV itself. Unique keys
    also mean the flow goes exactly where the state lives: the
    traffic-vs-occupancy divergence must read 0."""
    from risingwave_tpu.device.skew_stats import SK_BUCKETS
    _arm_flow(monkeypatch)
    db, job = _run(PP_MV, "pp", srcs=(PERSON_SRC,), n=1024)
    i = _flow_node(job)
    tv = _traffic(job, i)
    st = job.program.node_stats(i, job._stat_totals)
    occ = [int(st[f"skv{b}"]) for b in range(SK_BUCKETS)]
    n_rows = len(db.query("SELECT * FROM pp"))
    assert n_rows > 0
    assert sum(tv) == n_rows, "every person row routed exactly once"
    assert tv == occ, "unique keys: traffic == occupancy per bucket"
    # the system-table surface carries the same numbers
    rows = db.query("SELECT * FROM rw_vnode_traffic WHERE job = 'pp'")
    vt = sorted(r for r in rows if r[3] == "vnode_traffic")
    assert [r[5] for r in vt] == tv
    assert abs(sum(r[6] for r in vt) - 1.0) < 1e-9   # shares sum to 1
    ts = [r for r in rows if r[3] == "traffic_skew"]
    assert len(ts) == 1 and ts[0][5] == sum(tv)
    div = [r for r in rows if r[3] == "traffic_div"]
    assert len(div) == 1 and div[0][6] == 0.0


def test_traffic_exact_through_precombine(monkeypatch):
    """The pre-combined agg path must weight each combined delta row by
    its raw-row count: the totals stay identical to the uncombined
    run — zipf keys so combining actually collapses rows."""
    _arm_flow(monkeypatch, pre="0")
    _, job_raw = _run(Q1_MV, "q1a")
    _arm_flow(monkeypatch, pre="1")
    _, job_pre = _run(Q1_MV, "q1a")
    from risingwave_tpu.device.fused import PrecombineNode
    assert any(isinstance(nd, PrecombineNode)
               for nd in job_pre.program.nodes)
    tv_raw = _traffic(job_raw, _flow_node(job_raw))
    tv_pre = _traffic(job_pre, _flow_node(job_pre))
    assert sum(tv_raw) > 0
    assert tv_raw == tv_pre


@pytest.mark.mesh
def test_traffic_sums_shard_invariant(monkeypatch):
    """The acceptance bar: the tv* slots ride `stat_sums`, so
    `sharded_apply` psums them — an 8-shard run's per-bucket totals
    equal the 1-shard run's EXACTLY (hot-key replication off: a
    broadcast row would legitimately count once per shard)."""
    _arm_flow(monkeypatch)
    _, job1 = _run(Q1_MV, "q1a", shards=1)
    _, job8 = _run(Q1_MV, "q1a", shards=8)
    tv1 = _traffic(job1, _flow_node(job1))
    tv8 = _traffic(job8, _flow_node(job8))
    assert sum(tv1) > 0
    assert tv1 == tv8


def test_traffic_divergence_zipf_flow_over_spread_state(monkeypatch):
    """Zipf bidder traffic over the (per-key-once) occupancy profile:
    the hot bucket's traffic share dwarfs its occupancy share — the
    'hot flow over cold state' signal occupancy-driven rebalancing
    cannot see. rw_key_skew alone would call this job balanced."""
    _arm_flow(monkeypatch)
    db, job = _run(Q1_MV, "q1a", kd="zipf:4")
    rows = db.query("SELECT * FROM rw_vnode_traffic WHERE job = 'q1a'")
    div = [r for r in rows if r[3] == "traffic_div"]
    assert div and div[0][6] > 0.1
    skew = [r for r in rows if r[3] == "traffic_skew"]
    assert skew and skew[0][6] > 2.0     # rank-1 bidder dominates
    # the EWMA ring saw at least one checkpoint window (a drained job's
    # final window is legitimately quiet, so only the row is guaranteed)
    burst = [r for r in rows if r[3] == "traffic_burst"]
    assert burst and burst[0][6] >= 0.0 and burst[0][5] > 0


def test_traffic_ewma_burst_vs_sustained():
    from risingwave_tpu.device.skew_stats import SK_BUCKETS, TrafficEwma
    ew = TrafficEwma(alpha=0.3)
    flat = [100] * SK_BUCKETS
    cum = [0] * SK_BUCKETS
    for _ in range(8):                     # sustained uniform flow
        cum = [c + f for c, f in zip(cum, flat)]
        ew.update(cum)
    sustained = ew.burst_ratio()
    assert 0.5 < sustained < 1.5           # converged toward 1
    spike = list(flat)
    spike[3] += 5000                       # one-off burst in bucket 3
    cum = [c + s for c, s in zip(cum, spike)]
    ew.update(cum)
    # the spike is already folded into the EWMA when the ratio reads,
    # so a fresh burst tops out near 1/alpha — still cleanly above the
    # sustained band
    assert ew.burst_ratio() > 2.5
    for _ in range(8):                     # burst decays back
        cum = [c + f for c, f in zip(cum, flat)]
        ew.update(cum)
    assert ew.burst_ratio() < 1.5


def test_flow_unarmed_no_slots_no_sig_flag(monkeypatch):
    """RW_FLOW_STATS=0 (the conftest default) must leave the program
    byte-identical to the pre-feature shape: no `flow` nodes, no tv*
    stat slots, no ('flow',) signature flag — zero fresh compiles for
    every existing cached signature."""
    monkeypatch.setenv("RW_FLOW_STATS", "0")
    _, job = _run(PP_MV, "pp", srcs=(PERSON_SRC,), n=1024)
    assert all(not nd.flow for nd in job.program.nodes)
    assert not any(s.startswith("tv")
                   for _i, s in job.program.stat_layout)
    assert all("flow" not in str(nd._sig())
               for nd in job.program.nodes)
    # armed: the flag and the slots appear
    monkeypatch.setenv("RW_FLOW_STATS", "1")
    _, job2 = _run(PP_MV, "pp", srcs=(PERSON_SRC,), n=1024)
    assert any(nd.flow for nd in job2.program.nodes)
    assert any(s.startswith("tv") for _i, s in job2.program.stat_layout)
    flagged = [nd for nd in job2.program.nodes if nd.flow]
    assert all("flow" in str(nd._sig()) for nd in flagged)


# ---------------------------------------------------------------------------
# tentpole 2: pressure attribution
# ---------------------------------------------------------------------------


def test_combine_contributions_math():
    from risingwave_tpu.utils.overload import (combine_contributions,
                                               dominant_contribution)
    # stall family sums (capped at 1); sink/queue take the max; the
    # combined scalar is the max of the two families
    rows = [("stall", "sink", 0.3), ("stall", "exchange_credit", 0.4),
            ("sink", "snk", 0.2), ("queue", "q:setA", 0.5)]
    assert abs(combine_contributions(rows) - 0.7) < 1e-12
    # dominant = the single loudest source, whatever its family
    assert dominant_contribution(rows) == "queue:q:setA"
    assert dominant_contribution(rows[:2]) == "stall:exchange_credit"
    # stall saturates: the cap lives in the combine, the split stays
    # uncapped so the decomposition remains visible
    rows = [("stall", "a", 0.9), ("stall", "b", 0.8)]
    assert combine_contributions(rows) == 1.0
    assert combine_contributions([]) == 0.0
    assert dominant_contribution([]) == ""


def test_pressure_board_by_kind_windows():
    board_cls = type(PRESSURE)
    b = board_cls()
    now = time.monotonic()
    b.note("sink", 3.0)
    b.note("exchange_credit", 1.0)
    by = b.by_kind(60.0)
    assert by["sink"] == pytest.approx(3.0)
    assert by["exchange_credit"] == pytest.approx(1.0)
    # the scalar is the capped sum over kinds — same events, one cap
    assert b.fraction(60.0) == pytest.approx(
        min(1.0, sum(by.values()) / 60.0))
    assert now is not None


def test_attribution_sums_to_global_slow_sink(tmp_path):
    """overload.slow_sink: the sink stalls, the board fills with stall
    evidence, and the per-source decomposition must recombine to the
    EXACT scalar the ladder saw (same attribution() call feeds both —
    the invariant holds by construction, this pins it)."""
    from risingwave_tpu.utils.overload import combine_contributions
    ROBUSTNESS.overload_hold_s = 0.0
    ROBUSTNESS.overload_window_s = 30.0
    ROBUSTNESS.overload_high, ROBUSTNESS.overload_low = 0.5, 0.1
    db = Database()
    db.run("CREATE TABLE t (k BIGINT, v BIGINT) WITH ("
           "connector='datagen', rows.per.poll='64')")
    path = str(tmp_path / "out.jsonl")
    db.run(f"CREATE SINK snk FROM t WITH (connector='fs',"
           f" fs.path='{path}', format='jsonl')")
    fp.arm("overload.slow_sink", 1.0, 0, None)
    for _ in range(6):
        db.tick()
        time.sleep(0.01)
    m = db._overload
    assert m.last_attribution, "stalled sink must attribute"
    assert m.last_pressure == combine_contributions(m.last_attribution)
    assert m.last_pressure > 0.0
    assert m.last_dominant != ""
    fams = {f for f, _s, _v in m.last_attribution}
    assert "sink" in fams or "stall" in fams
    # the system-table surface: per-source rows + the combined row,
    # exactly one row flagged dominant
    rows = db.query("SELECT * FROM rw_pressure_attrib")
    combined = [r for r in rows if r[0] == "combined"]
    assert len(combined) == 1
    assert combined[0][2] == pytest.approx(m.last_pressure)
    assert sum(1 for r in rows if r[3]) == 1
    dom = next(r for r in rows if r[3])
    assert f"{dom[0]}:{dom[1]}" == m.last_dominant
    # rw_overload names WHY each rung was taken
    ov = db.query("SELECT * FROM rw_overload WHERE job = 'snk'")
    assert ov and any(r[1] > 0 for r in ov), "transitions recorded"
    assert all(len(r) == 9 for r in ov)
    assert any(r[8] != "" for r in ov if r[1] > 0), \
        "transitions must carry dominant_source"


def test_attribution_sums_to_global_slow_worker(monkeypatch):
    """overload.slow_worker (armed in the workers via the environment):
    exchange credit starvation feeds stall evidence; the decomposition
    must name a stall source and recombine exactly. Bounded: the test
    needs the evidence, not job completion."""
    from risingwave_tpu.utils.overload import combine_contributions
    monkeypatch.setenv("RW_FAILPOINTS", "overload.slow_worker:1")
    ROBUSTNESS.overload_window_s = 2.0
    ROBUSTNESS.overload_high, ROBUSTNESS.overload_low = 0.15, 0.05
    ROBUSTNESS.overload_hold_s = 0.0
    ROBUSTNESS.exchange_credits = 4
    db = Database()
    db.run("SET streaming_parallelism = 2")
    db.run("SET streaming_placement TO process")
    db.run(BID_SRC.format(n=4000, c=64, kd="zipf:2"))
    db.run("CREATE MATERIALIZED VIEW q AS SELECT bidder,"
           " count(*) AS cnt FROM bid GROUP BY bidder")
    try:
        deadline = time.monotonic() + 45.0
        m = db._overload
        seen_stall = False
        while time.monotonic() < deadline:
            db.tick()
            if m.last_attribution:
                assert m.last_pressure == \
                    combine_contributions(m.last_attribution)
            if any(f == "stall" and v > 0
                   for f, _s, v in m.last_attribution):
                seen_stall = True
                break
        assert seen_stall, "credit starvation must attribute as stall"
        assert m.last_dominant != ""
    finally:
        from risingwave_tpu.sql.database import _walk_executors
        for obj in db.catalog.objects.values():
            rt = obj.runtime if isinstance(obj.runtime, dict) else None
            if rt and rt.get("shared") is not None:
                for e in _walk_executors(rt["shared"].upstream):
                    r = getattr(e, "_remote", None)
                    if r is not None:
                        r.shutdown()


# ---------------------------------------------------------------------------
# tentpole 3: flight recorder
# ---------------------------------------------------------------------------


def test_blackbox_auto_dump_and_offline_read(tmp_path, capsys,
                                             monkeypatch):
    """A seeded device fault (fused.dispatch) drives an in-place
    recovery, which auto-dumps a bundle; the dead directory then yields
    the ring + bundles to `risectl blackbox` with no process, and the
    chrome export carries the recovery as an instant event."""
    from risingwave_tpu import ctl
    from risingwave_tpu.utils.blackbox import (RECORDER, RING_FILE,
                                               list_bundles, read_bundle)
    RECORDER._last_dump.clear()        # earlier tests may have primed
    monkeypatch.setenv("RW_FLOW_STATS", "1")
    d = str(tmp_path / "d")
    db = Database(device=DeviceConfig(capacity=2048, aot_compile=False,
                                      compile_buckets=0),
                  data_dir=d)
    db.run(BID_SRC.format(n=N, c=CHUNK, kd="zipf:2"))
    db.run(Q1_MV)
    job = db.catalog.get("q1a").runtime["fused_job"]
    db.tick()
    fp.arm("fused.dispatch", 1.0, 0, 1)
    for _ in range(N // (64 * CHUNK) + 3):
        db.tick()
    fp.reset()
    job.sync()
    db.tick()
    assert job.recoveries >= 1, "the seeded fault must recover in place"
    # the always-on ring mirrored to disk...
    assert os.path.getsize(os.path.join(d, RING_FILE)) > 0
    # ...and the recovery auto-dumped a bundle
    bundles = list_bundles(d)
    assert bundles, "in-place recovery must auto-dump"
    name, manifest = bundles[-1]
    assert "in_place_recovery" in name
    assert manifest["schema"] == 1 and manifest["records"] > 0
    recs = read_bundle(d, name)
    kinds = {r["kind"] for r in recs}
    assert "recovery" in kinds and "boot" in kinds
    rec = next(r for r in recs if r["kind"] == "recovery")
    assert rec["job"] == "q1a" and rec["error"] and rec["wall_s"] >= 0
    # ---- the directory is now DEAD ----------------------------------
    del db, job
    assert ctl.main(["blackbox", "list", "--data-dir", d]) == 0
    out = capsys.readouterr().out
    assert "in_place_recovery" in out and "recovery" in out
    assert ctl.main(["blackbox", "dump", "--data-dir", d,
                     "--reason", "postmortem"]) == 0
    assert "postmortem" in capsys.readouterr().out
    post = list_bundles(d)
    assert len(post) == len(bundles) + 1
    assert ctl.main(["blackbox", "show", post[-1][0],
                     "--data-dir", d]) == 0
    shown = [json.loads(ln) for ln in
             capsys.readouterr().out.splitlines() if ln.strip()]
    assert any(r.get("kind") == "recovery" for r in shown)
    # a dir with no ring file degrades gracefully
    assert ctl.main(["blackbox", "dump",
                     "--data-dir", str(tmp_path)]) == 1
    # ---- chrome export with the new instant events ------------------
    from risingwave_tpu.utils.export import export_chrome, validate_chrome
    doc = export_chrome(d)
    assert validate_chrome(doc) == []
    instants = [e for e in doc["traceEvents"]
                if e["ph"] == "i" and e["pid"] == "control"]
    assert any(e["tid"] == "recovery" for e in instants)


def test_blackbox_ring_byte_bound_and_rate_limit(tmp_path):
    from risingwave_tpu.utils.blackbox import FlightRecorder
    r = FlightRecorder(max_bytes=2048)
    r.attach(str(tmp_path))
    for i in range(500):
        r.record("epoch", {"seq_no": i, "pad": "x" * 32})
    st = r.stats()
    assert st["bytes"] <= 2048 and st["dropped"] > 0
    assert st["records"] < 500
    # first auto-dump lands; an immediate retrigger of the SAME reason
    # coalesces; a DIFFERENT reason still dumps
    assert r.maybe_dump("wedge_reap") is not None
    assert r.maybe_dump("wedge_reap") is None
    assert r.maybe_dump("quarantine") is not None
    # unattached recorders record but cannot dump — and never raise
    lone = FlightRecorder()
    lone.record("epoch", {"x": object()})     # unserializable: fallback
    assert lone.dump("manual") is None
    assert lone.stats()["records"] == 1


# ---------------------------------------------------------------------------
# satellites: epoch-profile schema, served staleness, replica pulls,
# dead-telemetry lint
# ---------------------------------------------------------------------------


def test_profile_schema_dispatch(tmp_path):
    from risingwave_tpu.utils.profile import (PROFILE_SCHEMA,
                                              decode_epoch,
                                              summarize_file)
    assert PROFILE_SCHEMA == 2
    # the one schema written so far: the phase map passes through, as a
    # copy, and a record without phases decodes to none
    rec = {"schema": PROFILE_SCHEMA, "ph_ms": {"pack": 1.0, "h2d": 2.0}}
    assert decode_epoch(rec) == {"pack": 1.0, "h2d": 2.0}
    assert decode_epoch(rec) is not rec["ph_ms"]
    assert decode_epoch({"schema": PROFILE_SCHEMA}) == {}
    # every reader of a file summarizes on that one decode path
    path = str(tmp_path / "epoch_profile.jsonl")
    with open(path, "w") as f:
        f.write(json.dumps({"ev": "epoch", "schema": 2, "job": "j",
                            "seq": 1, "events": 10, "wall_ms": 5.0,
                            "ph_ms": {"pack": 1.0, "h2d": 2.0,
                                      "dispatch": 1.0}}) + "\n")
        f.write(json.dumps({"ev": "epoch", "schema": 2, "job": "j",
                            "seq": 2, "events": 10, "wall_ms": 4.0,
                            "ph_ms": {"pack": 2.5,
                                      "dispatch": 1.0}}) + "\n")
    out = summarize_file(path)
    assert out["j"]["epochs"] == 2
    assert out["j"]["phase_ms"]["pack"] == pytest.approx(3.5)
    assert out["j"]["phase_ms"]["h2d"] == pytest.approx(2.0)


def test_served_staleness_reported_for_cache_lagged_selects(monkeypatch):
    """The fix under test: a SELECT served from a cache snapshot OLDER
    than the last commit must surface the staleness the reader actually
    experienced in rw_mv_freshness — not the store's head freshness."""
    monkeypatch.setenv("RW_FLOW_STATS", "0")
    n = 4 * N                              # stream outlives the fill
    db = Database(device=DeviceConfig(capacity=4096, aot_compile=False,
                                      compile_buckets=0))
    db.run(BID_SRC.format(n=n, c=CHUNK, kd="zipf:2"))
    db.run(Q1_MV)
    job = db.catalog.get("q1a").runtime["fused_job"]
    db.tick()
    # a huge staleness budget pins the cache to its first snapshot
    # while the rest of the stream commits past it
    ROBUSTNESS.serving_staleness_epochs = 10_000
    assert db.query("SELECT * FROM q1a") is not None   # early fill
    fill_ts = db.read_cache.fill_time("q1a")
    assert fill_ts is not None
    for _ in range(n // (64 * CHUNK) + 3):
        db.tick()
    job.sync()
    db.tick()
    assert int(job.counter) > db.read_cache._entries["q1a"].epoch, \
        "commits must outrun the cached snapshot"
    db.query("SELECT * FROM q1a")                 # SERVED stale
    assert "q1a" in db._freshness._served
    row = next(r for r in db._freshness.rows() if r[0] == "q1a")
    # anchored at (or before) the snapshot's fill time, never the head
    assert row[5] >= time.time() - fill_ts - 0.5
    assert len(row) == 9                          # shape unchanged
    # an up-to-date serve clears the marker
    ROBUSTNESS.serving_staleness_epochs = 0
    db.query("SELECT * FROM q1a")
    assert "q1a" not in db._freshness._served


def test_rw_serving_pulls_and_replica_metric(monkeypatch):
    from risingwave_tpu.device.shard_exec import (PULL_STATS,
                                                  reset_pull_stats)
    from risingwave_tpu.utils.metrics import REGISTRY
    monkeypatch.setenv("RW_FLOW_STATS", "0")
    reset_pull_stats()
    db, _job = _run(Q1_MV, "q1a", n=2048)
    assert db.query("SELECT * FROM q1a")
    rows = db.query("SELECT * FROM rw_serving_pulls")
    total = next(r for r in rows if r[0] == -1)
    assert total[1] == PULL_STATS["device_pulls"] >= 1
    per_rep = [r for r in rows if r[0] >= 0]
    assert per_rep and sum(r[1] for r in per_rep) == total[1]
    exp = REGISTRY.expose()
    assert "serving_device_pulls_total" in exp
    assert "serving_replica_pulls_total" in exp


def test_dead_telemetry_lint():
    from risingwave_tpu.utils.metrics import (MetricsRegistry,
                                              dead_telemetry)
    reg = MetricsRegistry()
    reg.counter("live_total", "instantiated", labels=("job",)
                ).labels("j").inc()
    reg.counter("dead_total", "declared, never labeled", labels=("job",))
    reg.counter("plain_total", "unlabeled metrics are exempt").inc()
    flagged = dead_telemetry(reg)
    assert any("dead_total" in p for p in flagged)
    assert not any("live_total" in p for p in flagged)
    assert not any("plain_total" in p for p in flagged)
