"""Supervision v2 chaos suite: join-fragment in-place respawn,
incremental (diff) refresh, wedged-worker reaping, failpoint-ledger
replay, and sink-boundary dedupe.

Reference analogs: `GlobalBarrierWorker::recovery` restarting ANY actor
in place (`src/meta/src/barrier/worker.rs:664`), the madsim
deterministic kill tier (`src/tests/simulation/`), and the sink
log-store exactly-once contract. PanJoin's partition-organized join
state (PAPERS.md) is what makes per-worker re-seed of a join fragment
tractable: each worker's shadow partition is an independent re-seedable
unit.

Everything here is `chaos`-marked; soak-length variants carry `slow`
too so tier-1 stays fast.
"""
import json
import os
import re
import signal
import time

import pytest

from risingwave_tpu.config import ROBUSTNESS
from risingwave_tpu.sql import Database

pytestmark = pytest.mark.chaos

AUCTION_SRC = ("CREATE SOURCE auction (id BIGINT, item_name VARCHAR,"
               " description VARCHAR, initial_bid BIGINT, reserve BIGINT,"
               " date_time TIMESTAMP, expires TIMESTAMP, seller BIGINT,"
               " category BIGINT, extra VARCHAR) WITH (connector='nexmark',"
               " nexmark.table='auction', nexmark.max.events='{n}',"
               " nexmark.chunk.size='{c}')")
PERSON_SRC = ("CREATE SOURCE person (id BIGINT, name VARCHAR,"
              " email_address VARCHAR, credit_card VARCHAR, city VARCHAR,"
              " state VARCHAR, date_time TIMESTAMP, extra VARCHAR)"
              " WITH (connector='nexmark', nexmark.table='person',"
              " nexmark.max.events='{n}', nexmark.chunk.size='{c}')")
# q3-shaped: two-source equi-join (seller = person id), remote-placed
Q3_MV = ("CREATE MATERIALIZED VIEW q3 AS SELECT p.name, p.city, p.state,"
         " a.id FROM auction a JOIN person p ON a.seller = p.id")


def find_remote(db, name, kind=None):
    obj = db.catalog.get(name)
    stack = [obj.runtime["shared"].upstream]
    while stack:
        e = stack.pop()
        r = getattr(e, "_remote", None)
        if r is not None and (kind is None or r.kind == kind):
            return r
        for attr in ("input", "left_exec", "right_exec"):
            c = getattr(e, attr, None)
            if c is not None:
                stack.append(c)
    raise AssertionError(f"no remote fragment set ({kind}) in the plan")


@pytest.fixture(autouse=True)
def _restore_robustness():
    saved = (ROBUSTNESS.respawn_backoff_s, ROBUSTNESS.spawn_backoff_s,
             ROBUSTNESS.heartbeat_timeout_s, ROBUSTNESS.wedge_kill_factor,
             ROBUSTNESS.incremental_refresh)
    ROBUSTNESS.respawn_backoff_s = 0.001
    ROBUSTNESS.spawn_backoff_s = 0.001
    yield
    (ROBUSTNESS.respawn_backoff_s, ROBUSTNESS.spawn_backoff_s,
     ROBUSTNESS.heartbeat_timeout_s, ROBUSTNESS.wedge_kill_factor,
     ROBUSTNESS.incremental_refresh) = saved


def _q3_db(n, chunk, supervise=True):
    db = Database()
    db.run(AUCTION_SRC.format(n=n, c=chunk))
    db.run(PERSON_SRC.format(n=n, c=chunk))
    db.run("SET streaming_parallelism = 2")
    db.run("SET streaming_placement = 'process'")
    if supervise:
        db.run("SET streaming_supervision TO true")
    db.run(Q3_MV)
    return db


def _q3_oracle(n, chunk, ticks):
    db = _q3_db(n, chunk, supervise=False)
    for _ in range(ticks):
        db.tick()
    rows = sorted(db.query("SELECT * FROM q3"))
    rfs = find_remote(db, "q3")
    rfs.shutdown()
    # shutdown waits: a drain still reading its dead worker's last frames
    # would spend the NEXT test's armed `fragment.drain` on this set
    assert not any(w.drain_thread.is_alive() for w in rfs.workers)
    return rows


# ---------------------------------------------------------------------------
# tentpole 1: join-fragment in-place respawn, bit-identical MV
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("victim", [0, 1])
def test_q3_join_worker_killed_mid_epoch_bit_identical(victim):
    """Kill one q3 join worker MID-EPOCH (right after its 7th dispatched
    left-side chunk — deterministic, seeded by construction): the
    supervisor re-seeds a successor from BOTH side shadows rolled back
    to the last delivered epoch and replays the window on both
    dispatchers. The final MV must be bit-identical to an undisturbed
    run — no RemoteWorkerDied, no DDL replay."""
    from risingwave_tpu.core.chunk import StreamChunk
    n, chunk = 20_000, 64
    ticks = n // (64 * chunk) + 4
    db = _q3_db(n, chunk)
    rfs = find_remote(db, "q3")
    assert rfs.kind == "join"
    old_pid = rfs.workers[victim].proc.pid
    vin = rfs.in_channels[0][victim]
    orig_send, seen = vin.send, [0]

    def send_and_kill(msg):
        orig_send(msg)
        if isinstance(msg, StreamChunk):
            seen[0] += 1
            if seen[0] == 7:
                rfs.workers[victim].proc.kill()
                rfs.workers[victim].proc.wait()
    vin.send = send_and_kill
    for _ in range(ticks):
        db.tick()                      # must NOT raise RemoteWorkerDied
    assert find_remote(db, "q3") is rfs, \
        "job objects must survive (in-place recovery, no DDL replay)"
    assert rfs.supervisor.respawns == 1
    assert rfs.workers[victim].proc.pid != old_pid
    assert sorted(db.query("SELECT * FROM q3")) == _q3_oracle(n, chunk,
                                                              ticks)
    rfs.shutdown()


def test_q3_join_worker_seeded_failpoint_kill_converges():
    """A seeded `fragment.drain` failpoint (coordinator-side, fires
    once) aborts one q3 join worker's result drain mid-stream: the
    supervisor treats it as a worker failure, kills + respawns the
    slot through the two-input re-seed path, and the MV converges to
    the undisturbed oracle — repeatable because the fire is seeded and
    max_fires-bounded, the chaos-ledger-friendly arming style."""
    from risingwave_tpu.utils import failpoint as fp
    n, chunk = 12_000, 64
    ticks = n // (64 * chunk) + 4
    fp.arm("fragment.drain", prob=1.0, seed=0, max_fires=1)
    try:
        db = _q3_db(n, chunk)
        rfs = find_remote(db, "q3")
        for _ in range(ticks):
            db.tick()
        assert rfs.supervisor.respawns == 1
        got = sorted(db.query("SELECT * FROM q3"))
        rfs.shutdown()
    finally:
        fp.reset()
    assert got == _q3_oracle(n, chunk, ticks)


# ---------------------------------------------------------------------------
# tentpole 3: wedged-worker reaping (SIGSTOP -> SIGKILL -> respawn)
# ---------------------------------------------------------------------------


def test_sigstop_worker_reaped_and_respawned(monkeypatch):
    """A SIGSTOP'd supervised worker stops heartbeating but never exits:
    once its heartbeat age exceeds heartbeat_timeout_s *
    wedge_kill_factor the supervisor SIGKILLs it and routes the slot
    through the normal respawn path — the job completes with exact
    results and `supervisor_wedged_reaped_total` counts the reap."""
    from risingwave_tpu.utils.metrics import REGISTRY
    # spawned workers inherit the env: their heartbeat TIMER period is
    # timeout/4, so healthy-but-quiescent siblings keep proving liveness
    # well inside the shrunken kill window
    monkeypatch.setenv("RW_HEARTBEAT_TIMEOUT_S", "1.0")
    ROBUSTNESS.heartbeat_timeout_s = 1.0
    ROBUSTNESS.wedge_kill_factor = 1.5
    db = Database()
    db.run("CREATE TABLE t (k BIGINT, v BIGINT)")
    db.run("SET streaming_parallelism = 2")
    db.run("SET streaming_placement = 'process'")
    db.run("SET streaming_supervision TO true")
    db.run("CREATE MATERIALIZED VIEW ra AS SELECT k, count(*) AS c,"
           " sum(v) AS s FROM t GROUP BY k")
    rfs = find_remote(db, "ra")
    db.run("INSERT INTO t VALUES (1, 10), (2, 20), (3, 30), (1, 5)")
    for _ in range(4):
        db.tick()
    assert sorted(db.query("SELECT * FROM ra")) == \
        [(1, 2, 15), (2, 1, 20), (3, 1, 30)]
    victim = 0
    old_pid = rfs.workers[victim].proc.pid
    os.kill(old_pid, signal.SIGSTOP)
    # ticks stall on the stopped worker's barrier until the reaper fires
    # inside the merge idle loop; bound the wait, not the outcome
    deadline = time.monotonic() + 60
    while rfs.supervisor.reaped == 0 and time.monotonic() < deadline:
        db.tick()
    assert rfs.supervisor.reaped == 1, "wedge reaper never fired"
    assert rfs.supervisor.respawns == 1
    assert rfs.workers[victim].proc.pid != old_pid
    # the job completes: post-reap traffic aggregates exactly
    db.run("INSERT INTO t VALUES (2, 7)")
    for _ in range(4):
        db.tick()
    assert sorted(db.query("SELECT * FROM ra")) == \
        [(1, 2, 15), (2, 2, 27), (3, 1, 30)]
    assert "supervisor_wedged_reaped_total" in REGISTRY.expose()
    # the liveness surface reports the slot healthy again post-respawn
    rows = db.query("SELECT * FROM rw_worker_liveness")
    assert len(rows) == 2 and all(r[5] in ("ok", "wedged?") for r in rows)
    rfs.shutdown()


# ---------------------------------------------------------------------------
# tentpole 2: incremental refresh emits ⊆ changed groups
# ---------------------------------------------------------------------------


def _refresh_rows(mode):
    """Sum of worker_refresh_rows_total{mode=...} across the cluster
    expose (workers piggyback their registries to the coordinator)."""
    from risingwave_tpu.utils.metrics import REGISTRY
    total = 0.0
    for ln in REGISTRY.expose().splitlines():
        if ln.startswith("worker_refresh_rows_total{") \
                and f'mode="{mode}"' in ln:
            total += float(ln.rsplit(" ", 1)[1])
    return total


def test_incremental_refresh_emits_subset_of_changed_groups():
    """After a respawn, the diff refresh may only re-state groups whose
    value changed inside the crash window — not the whole owned-group
    set. 40 groups delivered, ≤3 touched in the window ⇒ the diff-mode
    refresh emits ≤ 3 rows cluster-wide and full-mode refresh stays
    unused."""
    from risingwave_tpu.core.chunk import StreamChunk
    base_diff, base_full = _refresh_rows("diff"), _refresh_rows("full")
    db = Database()
    db.run("CREATE TABLE t (k BIGINT, v BIGINT)")
    db.run("SET streaming_parallelism = 2")
    db.run("SET streaming_placement = 'process'")
    db.run("SET streaming_supervision TO true")
    db.run("CREATE MATERIALIZED VIEW ra AS SELECT k, count(*) AS c,"
           " sum(v) AS s FROM t GROUP BY k")
    rfs = find_remote(db, "ra")
    vals = ", ".join(f"({k}, {k * 10})" for k in range(40))
    db.run(f"INSERT INTO t VALUES {vals}")
    for _ in range(4):
        db.tick()
    assert len(db.query("SELECT * FROM ra")) == 40
    # crash window touches exactly 3 groups; the victim dies after its
    # next dispatched data chunk (mid-epoch, deterministic)
    victim = 0
    vin = rfs.in_channels[0][victim]
    orig_send = vin.send

    def send_and_kill(msg):
        orig_send(msg)
        if isinstance(msg, StreamChunk):
            vin.send = orig_send
            rfs.workers[victim].proc.kill()
            rfs.workers[victim].proc.wait()
    vin.send = send_and_kill
    db.run("INSERT INTO t VALUES (1, 1), (2, 2), (3, 3)")
    for _ in range(6):
        db.tick()
    assert rfs.supervisor.respawns == 1
    want = [(k, 2, k * 10 + k) if k in (1, 2, 3) else (k, 1, k * 10)
            for k in range(40)]
    assert sorted(db.query("SELECT * FROM ra")) == sorted(want)
    assert _refresh_rows("full") == base_full, \
        "v2 respawn must not fall back to the full owned-group refresh"
    emitted = _refresh_rows("diff") - base_diff
    assert emitted <= 3, \
        f"diff refresh emitted {emitted} rows for a 3-group crash window"
    rfs.shutdown()


# ---------------------------------------------------------------------------
# satellite: sink dedupe across a stateful respawn + refresh
# ---------------------------------------------------------------------------


def _replay_changelog(path):
    """Apply the sink's +/- changelog; returns the net row multiset and
    asserts multiplicities never go negative (a duplicate `+` would
    inflate one, a stale `-` would sink one below zero)."""
    state = {}
    for ln in open(path):
        rec = json.loads(ln)
        row = tuple(rec["row"][k] for k in sorted(rec["row"]))
        state[row] = state.get(row, 0) + (1 if rec["op"] == "+" else -1)
        assert state[row] >= 0, f"negative multiplicity for {row}"
        if state[row] == 0:
            del state[row]
    out = []
    for row, cnt in state.items():
        out.extend([row] * cnt)
    return sorted(out)


@pytest.mark.parametrize("incremental", [True, False])
def test_no_duplicate_rows_reach_sink_across_respawn(tmp_path,
                                                     incremental):
    """A stateful respawn + refresh must deliver ZERO duplicate rows to
    an attached sink. Incremental mode never produces them (per-epoch
    net diffs are exact); the v1 full-refresh fallback re-INSERTs every
    owned group and relies on the sink-boundary (pk, epoch) dedupe +
    the coordinator's vanished-group retraction — both paths must net
    to the exact MV, including a group fully retracted inside the crash
    window."""
    from risingwave_tpu.core.chunk import StreamChunk
    from risingwave_tpu.utils.metrics import REGISTRY
    ROBUSTNESS.incremental_refresh = incremental
    out = tmp_path / "out.jsonl"
    db = Database(data_dir=str(tmp_path / "data"))
    db.run("CREATE TABLE t (k BIGINT, v BIGINT)")
    db.run("SET streaming_parallelism = 2")
    db.run("SET streaming_placement = 'process'")
    db.run("SET streaming_supervision TO true")
    db.run("CREATE MATERIALIZED VIEW ra AS SELECT k, count(*) AS c,"
           " sum(v) AS s FROM t GROUP BY k")
    db.run(f"CREATE SINK snk FROM ra WITH (connector='fs',"
           f" fs.path='{out}')")
    rfs = find_remote(db, "ra")
    db.run("INSERT INTO t VALUES (1, 10), (2, 20), (3, 30), (4, 40)")
    for _ in range(4):
        db.tick()
    # crash window: group 2 fully retracted, group 1 changed, group 5
    # born; BOTH workers die mid-epoch so whichever owns group 2
    # exercises the retraction path
    for w in range(2):
        vin = rfs.in_channels[0][w]
        orig = vin.send

        def send_and_kill(msg, _w=w, _orig=orig, _vin=vin):
            _orig(msg)
            if isinstance(msg, StreamChunk):
                _vin.send = _orig      # one kill per worker
                rfs.workers[_w].proc.kill()
                rfs.workers[_w].proc.wait()
        vin.send = send_and_kill
    db.run("DELETE FROM t WHERE k = 2")
    db.run("INSERT INTO t VALUES (1, 1), (5, 50)")
    for _ in range(8):
        db.tick()
    assert rfs.supervisor.respawns == 2
    want = sorted(db.query("SELECT k, count(*), sum(v)"
                           " FROM t GROUP BY k"))
    got = sorted(db.query("SELECT * FROM ra"))
    assert got == want
    # exactly-once external delivery: the changelog's net result is the
    # MV — no duplicate `+`, no stale rows, group 2 fully gone
    net = _replay_changelog(out)
    # changelog rows come back in sorted-column-name order (c, k, s)
    want_rows = sorted(tuple(str(v) for v in (r[1], r[0], r[2]))
                       for r in want)
    net = sorted(tuple(str(v) for v in r) for r in net)
    assert net == want_rows, (net, want_rows)
    assert not any(r[1] == "2" for r in net), "group 2 must be retracted"
    if not incremental:
        text = REGISTRY.expose()
        assert "supervisor_refresh_retractions_total" in text
    rfs.shutdown()


# ---------------------------------------------------------------------------
# escalation hygiene: every _escalate call site cites a registered reason
# ---------------------------------------------------------------------------


def test_escalation_reasons_registered_and_distinct():
    """Every `_escalate` call site in remote_fragments must cite a
    reason from the ESCALATION_REASONS registry (the
    supervisor_escalations_total{reason} label values), every registered
    reason must have a call site, and each reason carries its own
    documentation — a dashboard must be able to tell WHY a fragment fell
    back to full recovery from the label alone."""
    import inspect
    from risingwave_tpu.runtime import remote_fragments as rf
    src = inspect.getsource(rf)
    cited = re.findall(
        r"_escalate\((?:[^()]|\([^()]*\))*?\"([a-z_]+)\"\)", src,
        re.DOTALL)
    assert cited, "no _escalate call sites found (regex rot?)"
    assert set(cited) == set(rf.ESCALATION_REASONS), (
        sorted(set(cited) ^ set(rf.ESCALATION_REASONS)))
    # registry hygiene: distinct, documented, label-grammar-safe
    assert len(rf.ESCALATION_REASONS) == len(set(rf.ESCALATION_REASONS))
    for reason, doc in rf.ESCALATION_REASONS.items():
        assert re.fullmatch(r"[a-z][a-z0-9_]*", reason), reason
        assert doc and len(doc) > 10, reason
    # the runtime enforces the registry too
    db = _q3_db(1_000, 64)
    rfs = find_remote(db, "q3")
    with pytest.raises(AssertionError, match="unregistered"):
        rfs.supervisor._escalate("x", "not_a_real_reason")
    rfs.shutdown()


# ---------------------------------------------------------------------------
# tentpole 4: ledger record/replay reproduces the fire sequence
# ---------------------------------------------------------------------------


def test_ledger_chaos_run_replays_identical_fire_sequence(tmp_path):
    """Record a chaos run's ledger, re-arm a second run from the file
    (the RW_FAILPOINT_LEDGER contract), and assert the two runs fired
    the identical (ordinal, point, hit) sequence."""
    from risingwave_tpu.utils import failpoint as fp

    def run():
        seq = []
        for i in range(120):
            if fp.failpoint("chaos.a"):
                seq.append(("a", i))
            if i % 3 == 0 and fp.failpoint("chaos.b"):
                seq.append(("b", i))
        return seq

    fp.reset()
    fp.clear_ledger()
    fp.arm("chaos.a", prob=0.3, seed=17)
    fp.arm("chaos.b", prob=0.5, seed=4)
    seq1 = run()
    rec = fp.ledger()
    assert rec and any(p == "chaos.b" for _, p, _t, _h in rec)
    path = str(tmp_path / "chaos.ledger")
    assert fp.dump_ledger(path) == len(rec)
    # second run: armed from the file alone — no probs, no seeds
    fp.reset()
    fp.clear_ledger()
    fp.arm_from_ledger(path)
    seq2 = run()
    rep = fp.ledger()
    assert seq1 == seq2
    assert [(o, p, h) for o, p, _t, h in rec] == \
        [(o, p, h) for o, p, _t, h in rep]
    fp.reset()
    fp.clear_ledger()


def test_ledger_cross_thread_fire_sets_replay(tmp_path):
    """Two threads hammering their own points race for global ordinals,
    but each point's per-hit fire decisions are what replay pins down:
    the replayed run must fire the same (point, hit) set."""
    import threading
    from risingwave_tpu.utils import failpoint as fp

    def hammer(name, n=200):
        for _ in range(n):
            fp.failpoint(name)

    def run():
        ts = [threading.Thread(target=hammer, args=(nm,))
              for nm in ("chaos.t1", "chaos.t2")]
        for t in ts:
            t.start()
        for t in ts:
            t.join()

    fp.reset()
    fp.clear_ledger()
    fp.arm("chaos.t1", prob=0.25, seed=5)
    fp.arm("chaos.t2", prob=0.4, seed=6)
    run()
    rec = {(p, h) for _o, p, _t, h in fp.ledger()}
    assert rec
    path = str(tmp_path / "threads.ledger")
    fp.dump_ledger(path)
    fp.reset()
    fp.clear_ledger()
    fp.arm_from_ledger(path)
    run()
    rep = {(p, h) for _o, p, _t, h in fp.ledger()}
    assert rec == rep
    fp.reset()
    fp.clear_ledger()
