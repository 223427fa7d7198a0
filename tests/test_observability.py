"""PR 5 observability plane: epoch-timeline profiler, cluster metrics
plane, worker liveness, and the risectl/ system-table surfaces.

Profiler contract under test (ISSUE 5 acceptance): a fused run yields
rw_epoch_profile rows whose phase splits sum to within 10% of the
measured wall per epoch; the node-stats table attributes rows/occupancy
per node; `risectl profile` prints the offline summary. Plane contract:
after a remote-fragment run, coordinator expose() carries
worker-originated counters, and a wedged (SIGSTOPped, alive) worker
shows in rw_worker_liveness before any spawn/drain deadline."""
import json
import os
import signal
import threading
import time

import pytest

from risingwave_tpu.config import DeviceConfig, ROBUSTNESS
from risingwave_tpu.sql import Database

N = 5_000
CHUNK = 32

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


def _fused_db(data_dir=None, profile=True, n=N):
    # aot_compile=False pins the INLINE compile lifecycle these tests
    # assert (synchronous compile events on the epoch loop: each a
    # `rw:compile.inline` span under its `rw:step`); the AOT service's
    # async event contract is covered by tests/test_compile_service.py
    db = Database(device=DeviceConfig(capacity=512, profile=profile,
                                      aot_compile=False),
                  data_dir=data_dir)
    db.run(BID_SRC.format(n=n, c=CHUNK))
    db.run(Q4)
    assert (db.catalog.get("q4").runtime or {}).get("fused_job") is not None
    drive(db, n)
    db._fused["q4"].sync()
    return db


# ---------------------------------------------------------------------------
# epoch-timeline profiler
# ---------------------------------------------------------------------------


def test_epoch_profile_rows_and_phase_sums(tmp_path):
    # (a stream no other test of the process has compiled: a compile
    # record is a compile jax really made, not a first step)
    db = _fused_db(str(tmp_path / "d"), n=N - 96)
    rows = db.query("SELECT * FROM rw_epoch_profile")
    assert rows, "a fused run must produce epoch profile rows"
    for job, seq, events, shards, hp, h2d, pro, disp, exch, sync, dem, \
            commit, wall in rows:
        assert job == "q4"
        assert shards == 1 and exch == 0.0   # single-chip job
        assert h2d == 0.0                    # no staged ingest transfers
        assert pro == 0.0 and dem == 0.0     # tiering off in tier-1
        phases = hp + h2d + pro + disp + exch + sync + dem + commit
        # the phases are disjoint parts of the epoch: they cannot add up
        # to more than its wall (an epsilon for the clock reads)
        assert phases <= wall * 1.001 + 0.05
    # ... and that holds by structure, not by timing: every phase span
    # lies inside its epoch's span, and no two of an epoch overlap
    from risingwave_tpu.utils.profile import PHASES, SPANS
    prof = db._fused["q4"].profiler
    spans = [s for s in SPANS if s.get("inst") == prof.instance]
    epochs = {s["id"]: s for s in spans if s["name"] == "rw:epoch"}
    assert len(epochs) == len(rows)
    by_epoch = {}
    for s in spans:
        if s["name"][3:] in PHASES and s["parent"] in epochs:
            by_epoch.setdefault(s["parent"], []).append(s)
    assert set(by_epoch) == set(epochs)
    for eid, phs in by_epoch.items():
        phs.sort(key=lambda s: s["t0"])
        assert epochs[eid]["t0"] <= phs[0]["t0"]
        assert phs[-1]["t1"] <= epochs[eid]["t1"]
        for a, b in zip(phs, phs[1:]):
            assert a["t1"] <= b["t0"], (a["name"], b["name"])
    # dispatched epochs carry the epoch's event budget
    assert any(r[2] == 64 * CHUNK for r in rows)
    # warmup is decomposable: the cold compiles were recorded and labeled
    assert prof.compiles, "cold per-node compiles must be recorded"
    kinds = {k for _l, k, _s in prof.compiles}
    assert kinds == {"compile"}            # no growth: nothing re-traced
    names = db._fused["q4"].program.node_names
    for label, _k, _s in prof.compiles:
        idx, name, sig = label.split(":")
        assert name == names[int(idx)] and len(sig) == 8
    # each is the close of a `rw:compile.inline` span under that node's
    # step: its seconds are jax's trace + lowering + backend compile, not
    # the wall of a step that was slow
    inline = [s for s in spans if s["name"] == "rw:compile.inline"
              and s["parent"] in {t["id"] for t in spans
                                  if t["name"] == "rw:step"}]
    assert [(s["t1"] - s["t0"]) / 1e9 for s in inline] \
        == [sec for _l, _k, sec in prof.compiles]
    assert all(r.get("persistent") == "off" and "aot" not in r
               for r in prof.compile_info)


def test_fused_node_stats_table(tmp_path):
    db = _fused_db(str(tmp_path / "d"))
    rows = db.query("SELECT * FROM rw_fused_node_stats")
    by_type = {r[2]: r for r in rows}
    assert "AggNode" in by_type and "MVKeyedNode" in by_type
    # the source chain generated every bid event exactly once
    chain = by_type["ChainNode"]
    n_bids = chain[5]
    assert 0 < n_bids <= N
    # agg consumed what the chain produced; occupancy = entries/capacity
    agg = by_type["AggNode"]
    assert agg[4] == n_bids                      # rows_in
    assert agg[3] == "main" and agg[7] == 512    # slot, capacity
    assert 0 < agg[8] <= 1.0 and agg[10] is False
    # HBM gauges rode along
    from risingwave_tpu.utils.metrics import REGISTRY
    text = REGISTRY.expose()
    assert 'rw_hbm_bytes{job="q4"' in text
    assert 'rw_hbm_budget_utilization{job="q4",shards="1"}' in text


def test_profile_file_and_risectl(tmp_path, capsys):
    d = str(tmp_path / "d")
    _fused_db(d)
    from risingwave_tpu.utils.profile import PROFILE_FILE
    assert os.path.exists(os.path.join(d, PROFILE_FILE))
    from risingwave_tpu import ctl
    assert ctl.main(["profile", "q4", "--data-dir", d, "--top", "3"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["q4"]["epochs"] >= 1
    assert set(out["q4"]["phase_ms"]) >= {"pack", "dispatch",
                                          "device_sync", "commit"}
    assert out["q4"]["slowest_epochs"]
    assert len(out["q4"]["slowest_epochs"]) <= 3
    # unknown job: explicit failure, not an empty report
    assert ctl.main(["profile", "nope", "--data-dir", d]) == 1
    capsys.readouterr()


def test_profile_off_keeps_surfaces_empty():
    db = _fused_db(profile=False)
    assert db.query("SELECT * FROM rw_epoch_profile") == []
    assert db._fused["q4"].profiler.compiles.__len__() == 0
    # results are identical either way (profiling must not affect data)
    assert len(db.query("SELECT * FROM q4")) > 0
    # node attribution stays truthful with the profiler off: the stats
    # vector is pulled at every sync regardless of the profile flag
    rows = db.query("SELECT * FROM rw_fused_node_stats")
    agg = next(r for r in rows if r[2] == "AggNode")
    assert agg[4] > 0 and agg[6] > 0      # rows_in, entries


# ---------------------------------------------------------------------------
# cluster metrics plane + liveness
# ---------------------------------------------------------------------------


SRC_REMOTE = BID_SRC
MV_REMOTE = Q4


def _remote_db(n=20_000, chunk=512, k=2):
    db = Database()
    db.run(SRC_REMOTE.format(n=n, c=chunk))
    db.run(f"SET streaming_parallelism = {k}")
    db.run("SET streaming_placement = 'process'")
    db.run(MV_REMOTE)
    return db


def _find_remote(db, name):
    for jname, r in db._remote_sets():
        if jname == name:
            return r
    raise AssertionError("no remote set")


def test_metrics_plane_cluster_expose():
    """Workers piggyback registry deltas on their result streams; the
    coordinator's expose() becomes cluster-wide."""
    from risingwave_tpu.utils.metrics import REGISTRY
    db = _remote_db()
    rfs = _find_remote(db, "q4")
    for _ in range(20_000 // (64 * 512) + 4):
        db.tick()
    rows = db.query("SELECT * FROM q4")
    assert rows
    text = REGISTRY.expose()
    # the registry is process-global: earlier tests may have merged other
    # worker kinds — assert on THIS run's partial-agg workers only
    worker_lines = [l for l in text.splitlines()
                    if l.startswith("worker_epochs_total{")
                    and 'worker="partial' in l]
    assert len(worker_lines) >= 2, text[:500]
    # liveness gauge: one series per worker slot, fresh heartbeats
    live = [l for l in text.splitlines()
            if l.startswith('worker_liveness{job="q4"')]
    assert len(live) >= 2
    assert any('worker="partial0"' in l for l in live)
    assert any('worker="partial1"' in l for l in live)
    # system table agrees
    lrows = db.query("SELECT * FROM rw_worker_liveness")
    assert len(lrows) == 2
    for job, worker, pid, last_epoch, age, state in lrows:
        assert job == "q4" and state == "ok" and pid > 0
    rfs.shutdown()


def _wait_all_ok(db, deadline_s=15.0):
    """Heartbeat frames are stamped by the drain threads asynchronously
    AFTER barrier delivery, and ages go stale between barriers under a
    tiny timeout — so keep ticking (fresh heartbeats) and poll instead
    of asserting at a single instant."""
    end = time.monotonic() + deadline_s
    rows = []
    while time.monotonic() < end:
        db.tick()
        rows = db._worker_liveness_rows()
        if rows and all(r[5] == "ok" for r in rows):
            return rows
        time.sleep(0.02)
    raise AssertionError(f"workers never all 'ok': {rows}")


def test_wedged_worker_detected_by_heartbeat_age():
    """A SIGSTOPped worker is alive-but-stuck: process poll() stays None
    (so the death sweep can't see it), but its heartbeat frames stop —
    rw_worker_liveness must flag it while a tick is still in flight,
    BEFORE any spawn/drain deadline trips.

    The timeout is shrunk ONLY for the stopped phase: heartbeats ride
    result barriers, so under a tiny timeout a healthy-but-slow pipeline
    (warmup ticks on a loaded host) would legitimately read as wedged
    too — the 'ok' baselines run under the default timeout."""
    saved = ROBUSTNESS.heartbeat_timeout_s
    # bounded source sized so the handful of liveness-poll ticks can
    # never drain it (drained workers exit -> 'dead', not 'ok')
    db = _remote_db(n=800_000, chunk=128)
    rfs = _find_remote(db, "q4")
    stopped = []
    try:
        db.tick()                      # healthy baseline, heartbeats flow
        _wait_all_ok(db)
        victim = rfs.workers[0].proc
        os.kill(victim.pid, signal.SIGSTOP)
        stopped.append(victim.pid)
        ROBUSTNESS.heartbeat_timeout_s = 0.4
        # drive ticks from a background thread: with a stopped worker the
        # barrier can't align, so the tick blocks — exactly the situation
        # an operator diagnoses through the liveness surface
        t = threading.Thread(target=lambda: [db.tick() for _ in range(3)],
                             daemon=True)
        t.start()
        deadline = time.monotonic() + 15
        wedged = None
        while time.monotonic() < deadline:
            rows = db._worker_liveness_rows()
            wedged = next((r for r in rows if r[1] == "partial0"
                           and r[5] == "wedged?"), None)
            if wedged is not None:
                break
            time.sleep(0.05)
        assert wedged is not None, rows
        assert victim.poll() is None, "worker must be alive (just stuck)"
        assert wedged[4] > ROBUSTNESS.heartbeat_timeout_s
        os.kill(victim.pid, signal.SIGCONT)
        stopped.clear()
        ROBUSTNESS.heartbeat_timeout_s = saved
        t.join(120)
        assert not t.is_alive(), "ticks must complete after SIGCONT"
        # recovered: heartbeats flow again
        _wait_all_ok(db)
    finally:
        for pid in stopped:
            os.kill(pid, signal.SIGCONT)
        ROBUSTNESS.heartbeat_timeout_s = saved
        rfs.shutdown()


# ---------------------------------------------------------------------------
# trace satellites: --stuck-only + constant-memory rotation
# ---------------------------------------------------------------------------


def test_trace_stuck_only(tmp_path, capsys):
    from risingwave_tpu.utils.trace import BarrierTracer, diagnose
    d = str(tmp_path)
    tr = BarrierTracer(d)
    s1 = tr.inject(1, "checkpoint")
    s1.job_start("mv_ok")
    s1.job_end("mv_ok")
    s1.commit()
    s2 = tr.inject(2, "barrier")
    s2.job_start("mv_stuck")                  # never ends, never commits
    path = os.path.join(d, "barrier_trace.jsonl")
    full = diagnose(path, last=10)
    assert "committed" in full and "OPEN" in full
    stuck = diagnose(path, last=10, stuck_only=True)
    assert "mv_stuck" in stuck and "committed" not in stuck
    # even when committed traffic pushed the stall out of the tail window
    for e in range(3, 40):
        s = tr.inject(e, "checkpoint")
        s.commit()
    assert "mv_stuck" in diagnose(path, last=5, stuck_only=True)
    assert "mv_stuck" not in diagnose(path, last=5)
    # risectl flag wiring
    from risingwave_tpu import ctl
    assert ctl.main(["trace", "--data-dir", d, "--stuck-only"]) == 0
    assert "mv_stuck" in capsys.readouterr().out


def test_rotate_tail_is_line_exact(tmp_path):
    from risingwave_tpu.utils.trace import rotate_tail
    path = str(tmp_path / "log.jsonl")
    with open(path, "w") as f:
        for e in range(10_000):
            f.write(json.dumps({"epoch": e, "pad": "x" * 40}) + "\n")
    before = os.path.getsize(path)
    rotate_tail(path)
    after = os.path.getsize(path)
    assert after <= before // 2 + 64
    with open(path) as f:
        recs = [json.loads(l) for l in f]     # every line intact JSON
    # the tail is contiguous and newest-preserving
    assert recs[-1]["epoch"] == 9_999
    assert recs[0]["epoch"] > 0
    assert [r["epoch"] for r in recs] == list(
        range(recs[0]["epoch"], 10_000))


def test_tracer_emit_rotates(tmp_path, monkeypatch):
    from risingwave_tpu.utils import trace as trace_mod
    monkeypatch.setattr(trace_mod, "_MAX_FILE_BYTES", 1 << 14)
    tr = trace_mod.BarrierTracer(str(tmp_path))
    path = os.path.join(str(tmp_path), trace_mod.TRACE_FILE)
    prev = 0
    shrinks = 0
    for e in range(6_000):        # 2 emits/span -> several rotation checks
        span = tr.inject(e, "barrier")
        span.commit()
        size = os.path.getsize(path)
        if size < prev:
            shrinks += 1
        prev = size
    # rotation fired (the file shrank mid-run) and the survivors are
    # intact JSON lines ending at the newest event
    assert shrinks >= 1
    with open(path) as f:
        recs = [json.loads(l) for l in f]
    assert recs[-1]["epoch"] == 5_999 and recs[0]["epoch"] > 0


# ---------------------------------------------------------------------------
# timer-driven worker-side heartbeat (ISSUE 6 satellite: coordinator-
# quiescent periods — long AOT compiles, paused injectors — must not
# read as a wedged worker)
# ---------------------------------------------------------------------------


def test_heartbeat_timer_fires_during_quiet_window():
    from risingwave_tpu.runtime.worker import HeartbeatTimer
    sends = []
    t = HeartbeatTimer(lambda e: sends.append((time.monotonic(), e)),
                       period=0.05)
    t.start()
    try:
        time.sleep(0.3)
        assert len(sends) >= 2, \
            "a quiet worker must keep emitting timer heartbeats"
    finally:
        t.stop()
    n = len(sends)
    time.sleep(0.15)
    assert len(sends) == n, "stop() must halt the timer"


def test_heartbeat_timer_suppressed_by_traffic():
    """While barrier-piggybacked heartbeats flow (mark()), the timer
    stays silent — no duplicate frames on a healthy stream."""
    from risingwave_tpu.runtime.worker import HeartbeatTimer
    sends = []
    t = HeartbeatTimer(lambda e: sends.append(e), period=0.2)
    t.start()
    try:
        end = time.monotonic() + 0.6
        while time.monotonic() < end:
            t.mark(epoch=7)
            time.sleep(0.02)
        assert sends == [], "traffic-proven liveness must hold the timer"
    finally:
        t.stop()


def test_heartbeat_timer_default_period_tracks_timeout():
    from risingwave_tpu.runtime.worker import HeartbeatTimer
    t = HeartbeatTimer(lambda e: None)
    assert 0 < t.period < ROBUSTNESS.heartbeat_timeout_s, \
        "the fallback must beat faster than the wedged threshold"
