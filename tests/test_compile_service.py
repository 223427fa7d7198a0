"""ISSUE 6 — AOT compile service: bucketed shapes, background AOT, and
zero-compile warm starts.

Contracts under test:
 * bit-identical MV results across a bucket-boundary growth with the
   service on;
 * one way to run a node step: a pending signature WAITS for its
   background compile (bounded — it raises with the label rather than
   wait for good), a failed one takes the counted inline-jit fallback,
   and a shutdown leaves no entry pending without a task;
 * zero-compile DROP + re-CREATE (and second identically-shaped job),
   asserted via profiler compile counts AND the service's fresh-compile
   counter;
 * the plan-shape hash keys the high-water presize registry, so a
   re-created plan presizes under ANY name (satellite of PR 4's
   index+type keying);
 * the per-epoch-bounded capacity model: `touched`/pair-buffer needs get
   flat headroom, never horizon extrapolation;
 * `risectl compile-status` reports pending/ready/cached per signature.
"""
import json
import time

import pytest

from risingwave_tpu.config import DeviceConfig
from risingwave_tpu.device.capacity import (EPOCH_HEADROOM, bucket, ladder,
                                            project, project_epoch)
from risingwave_tpu.sql import Database

N = 5_000
CHUNK = 32          # fused epoch = 64 * CHUNK = 2048 events

BID_SRC = ("CREATE SOURCE bid (auction BIGINT, bidder BIGINT, price BIGINT,"
           " channel VARCHAR, url VARCHAR, date_time TIMESTAMP,"
           " extra VARCHAR) WITH (connector='nexmark',"
           " nexmark.table='bid', nexmark.max.events='{n}',"
           " nexmark.chunk.size='{c}')")
Q4 = ("CREATE MATERIALIZED VIEW {name} AS SELECT auction, count(*) AS c,"
      " sum(price) AS s, max(price) AS m FROM bid GROUP BY auction")


def drive(db, n=N, chunk=CHUNK):
    for _ in range(n // (64 * chunk) + 3):
        db.tick()


def _svc():
    from risingwave_tpu.device.compile_service import get_service
    return get_service()


@pytest.fixture(scope="module")
def oracle():
    db = Database(device="off")
    db.run(BID_SRC.format(n=N, c=CHUNK))
    db.run(Q4.format(name="q4"))
    drive(db)
    return sorted(db.query("SELECT * FROM q4"))


# ---------------------------------------------------------------------------
# bucket ladder + per-epoch capacity model (pure math)
# ---------------------------------------------------------------------------


def test_ladder_rungs():
    # every rung pow2, strictly above current, topped by bucket(predicted)
    r = ladder(64, 5_000)
    assert r and all(c & (c - 1) == 0 for c in r)
    assert all(c > 64 for c in r)
    assert r[-1] == bucket(5_000, lo=1)
    assert r == sorted(r)
    # capped at `rungs`, keeping the first step and the top
    r = ladder(64, 1 << 20, rungs=3)
    assert len(r) == 3
    assert r[0] == 128 and r[-1] == 1 << 20
    # nothing to pre-compile when the prediction fits the current bucket
    assert ladder(1024, 900) == []
    assert ladder(1024, 1024) == []


def test_project_epoch_flat_headroom():
    assert project_epoch(0) == 0
    assert project_epoch(1000) == int(1000 * EPOCH_HEADROOM)
    # and NEVER scales with any horizon — unlike project()
    assert project_epoch(1000) < project(1000, 2_048, 10_000_000)


def test_node_level_need_split():
    """JoinNode pair buffers and agg `touched` are per-epoch-bounded;
    join sides and live groups are cumulative."""
    import jax.numpy as jnp
    from risingwave_tpu.device.fused import JoinNode, PackPlan
    pack = PackPlan.plan([(0, 1000, 1)])
    node = JoinNode(0, 1, [0], [0], pack, None, 256, 1024,
                    [jnp.int64], [jnp.int64])
    stats = {"need_a": 10, "need_b": 20, "need_pairs": 999,
             "packbad": 0, "rows_in": 0, "rows_out": 0}
    assert node.cap_needs(stats) == {"a": 10, "b": 20, "pairs": 999}
    assert node.cap_needs_cum(stats) == {"a": 10, "b": 20}
    assert node.cap_needs_epoch(stats) == {"pairs": 999}


def test_per_epoch_slot_not_horizon_inflated():
    """The predictor must size a `touched`-dominated agg from flat
    headroom, not extrapolate it over the event horizon (the window-query
    overshoot carried from PR 4)."""
    from risingwave_tpu.device.fused import AggNode
    db = Database(device=DeviceConfig(capacity=64, aot_compile=False))
    db.run(BID_SRC.format(n=N, c=CHUNK))
    db.run(Q4.format(name="q4"))
    job = db._fused["q4"]
    job.counter = 2_048
    job.max_events = 10_000_000          # long horizon: inflation territory
    agg_i = next(i for i, n in enumerate(job.program.nodes)
                 if isinstance(n, AggNode))
    # few live groups (cumulative=8), one epoch touched 1000 dying groups
    needs = {agg_i: {"main": 1_000}}
    cum = {agg_i: {"main": 8}}
    epoch = {agg_i: {"main": 1_000}}
    target = job._predict_caps(needs, cum, epoch)[agg_i]["main"]
    inflated = bucket(project(1_000, 2_048, 10_000_000))
    assert target >= 1_000                      # correctness floor
    assert target < inflated / 8, (
        f"per-epoch `touched` was horizon-extrapolated: {target} "
        f"(old model: {inflated})")
    # legacy call shape (no split views) keeps the old extrapolation
    legacy = job._predict_caps(needs)[agg_i]["main"]
    assert legacy == inflated


# ---------------------------------------------------------------------------
# plan-shape hash
# ---------------------------------------------------------------------------


def test_plan_shape_hash_stable_across_instances():
    """Two Databases planning the same SQL produce the same plan-shape
    hash and node shape keys; a different query differs."""
    hashes, keysets = [], []
    for _ in range(2):
        db = Database(device=DeviceConfig(aot_compile=False))
        db.run(BID_SRC.format(n=N, c=CHUNK))
        db.run(Q4.format(name="q4"))
        from risingwave_tpu.device.fused import node_shape_key
        job = db._fused["q4"]
        hashes.append(job.plan_hash)
        keysets.append(sorted(node_shape_key(n)
                              for n in job.program.nodes))
    assert hashes[0] == hashes[1]
    assert keysets[0] == keysets[1]
    db = Database(device=DeviceConfig(aot_compile=False))
    db.run(BID_SRC.format(n=N, c=CHUNK))
    db.run("CREATE MATERIALIZED VIEW q4 AS SELECT bidder, count(*) AS c"
           " FROM bid GROUP BY bidder")
    assert db._fused["q4"].plan_hash not in hashes


# ---------------------------------------------------------------------------
# background AOT: the dispatcher waits for pending compiles, bucket growth
# ---------------------------------------------------------------------------


@pytest.mark.aot
def test_held_compiles_block_dispatch_then_bit_identical():
    """With every background compile HELD, the first barrier must WAIT
    (no other way to run a step exists); after the hold lifts it
    completes on the compiled executables and the final MV is
    bit-identical to the host path — across a bucket-boundary growth
    (capacity=64 forces at least one). Uses a max.events no other test
    shares: the executable cache is process-global, and a plan another
    test already compiled would be READY despite the hold."""
    import threading
    n = N + 192
    host = Database(device="off")
    host.run(BID_SRC.format(n=n, c=CHUNK))
    host.run(Q4.format(name="q4"))
    drive(host, n=n)
    oracle = sorted(host.query("SELECT * FROM q4"))
    svc = _svc()
    hold = threading.Event()
    svc.hold = hold
    try:
        db = Database(device=DeviceConfig(capacity=64, aot_compile=True))
        db.run(BID_SRC.format(n=n, c=CHUNK))
        db.run(Q4.format(name="q4"))
        job = db._fused["q4"]
        assert job.compile_service is svc
        compiled0, inline0, await0 = (svc.compiled_steps, svc.inline_steps,
                                      svc.await_s)
        first = threading.Thread(target=db.tick, daemon=True)
        first.start()
        first.join(1.5)
        assert first.is_alive(), \
            "held compiles must hold the first barrier back"
        assert svc.compiled_steps == compiled0
    finally:
        svc.hold = None
        hold.set()
    first.join(120)
    assert not first.is_alive(), "the barrier must finish once compiles land"
    assert svc.await_s > await0, "the wait is accounted for"
    drive(db, n=n)
    assert svc.compiled_steps > compiled0
    assert svc.inline_steps == inline0, "no step took the inline fallback"
    assert job.growth_replays >= 1, "test must cross a bucket boundary"
    assert sorted(db.query("SELECT * FROM q4")) == oracle


@pytest.mark.aot
def test_compile_events_labeled():
    """Service compiles land in the requesting job's profiler with
    `aot`/`bucket` labels and the idx:name:sighash label grammar. Uses a
    plan shape no other test compiles (distinct max.events changes the
    source signature) so fresh events are guaranteed despite the shared
    process-global executable cache."""
    n = N - 64
    db = Database(device=DeviceConfig(capacity=64, aot_compile=True))
    db.run(BID_SRC.format(n=n, c=CHUNK))
    db.run(Q4.format(name="q4"))
    drive(db, n=n)
    assert _svc().wait_idle(120)
    job = db._fused["q4"]
    evs = [r for r in job.profiler.compile_info]
    assert evs, "AOT compiles must be recorded in the profiler"
    for rec in evs:
        assert rec["aot"] is True
        idx, name, sig = rec["label"].split(":")
        assert name == job.program.node_names[int(idx)] and len(sig) == 8
        assert "bucket" in rec
        assert rec["persistent"] == "off"    # jax's word: no cache here
    # each record is the close of the compile's one span: same label,
    # same seconds, on the worker that compiled
    from risingwave_tpu.utils.profile import SPANS
    spans = {s["label"]: s for s in SPANS if s["name"] == "rw:compile"
             and s.get("inst") == job.profiler.instance}
    assert {r["label"]: r["s"] for r in evs} \
        == {k: (s["t1"] - s["t0"]) / 1e9 for k, s in spans.items()}
    assert all(s["ok"] and s["kind"] == "compile" for s in spans.values())
    assert db.query("SELECT * FROM q4")


# ---------------------------------------------------------------------------
# zero-compile warm starts
# ---------------------------------------------------------------------------


@pytest.mark.aot
def test_zero_compile_drop_recreate(oracle):
    """DROP + re-CREATE of the same plan performs ZERO fresh compiles
    (service cache keyed on structural signatures) and zero growth
    replays (presize registry keyed on the plan-shape hash).

    compile_buckets=0 pins the count to DISPATCH-shaped compiles: the
    predicted-bucket pre-warm (exercised elsewhere) schedules shapes
    from stats snapshots whose sync timing differs between the first
    and second incarnation, which would make the fresh-compile counter
    nondeterministic."""
    svc = _svc()
    db = Database(device=DeviceConfig(capacity=64, aot_compile=True,
                                      compile_buckets=0))
    db.run(BID_SRC.format(n=N, c=CHUNK))
    db.run(Q4.format(name="q4"))
    drive(db)
    assert db._fused["q4"].growth_replays >= 1
    assert svc.wait_idle(120)
    db.run("DROP MATERIALIZED VIEW q4")
    fresh0 = svc.compiles_done + svc.compiles_failed
    db.run(Q4.format(name="q4"))
    job2 = db._fused["q4"]
    drive(db)
    assert svc.wait_idle(120)
    assert svc.compiles_done + svc.compiles_failed == fresh0, \
        "re-CREATE of an identical plan must not compile anything"
    assert len(job2.profiler.compiles) == 0, \
        "zero compile events for the re-created job"
    assert job2.growth_replays == 0, \
        "plan-hash presize registry must absorb the growth ladder"
    assert sorted(db.query("SELECT * FROM q4")) == oracle


@pytest.mark.aot
def test_zero_compile_identically_shaped_second_job(oracle):
    """A SECOND job with the same plan shape — different name, first one
    still running — dispatches entirely from the shared executable
    cache: zero fresh compiles, `cached` in compile-status.
    (compile_buckets=0 for the same determinism reason as the
    drop/re-create test.)"""
    svc = _svc()
    db = Database(device=DeviceConfig(capacity=64, aot_compile=True,
                                      compile_buckets=0))
    db.run(BID_SRC.format(n=N, c=CHUNK))
    db.run(Q4.format(name="q4"))
    drive(db)
    assert svc.wait_idle(120)
    fresh0 = svc.compiles_done + svc.compiles_failed
    db.run(Q4.format(name="q4_twin"))
    twin = db._fused["q4_twin"]
    assert twin.plan_hash == db._fused["q4"].plan_hash
    drive(db)
    assert svc.wait_idle(120)
    assert svc.compiles_done + svc.compiles_failed == fresh0
    assert len(twin.profiler.compiles) == 0
    assert sorted(db.query("SELECT * FROM q4_twin")) == oracle
    states = {r["state"] for r in svc.status("q4_twin")}
    assert states and states <= {"cached"}, states


@pytest.mark.aot
def test_registry_presize_survives_rename(oracle):
    """The high-water presize registry keys on the PLAN-SHAPE hash, not
    the job name: a re-created identical plan under a new name starts at
    the predecessor's capacities."""
    db = Database(device=DeviceConfig(capacity=64, aot_compile=True))
    db.run(BID_SRC.format(n=N, c=CHUNK))
    db.run(Q4.format(name="q4"))
    drive(db)
    job1 = db._fused["q4"]
    assert job1.growth_replays >= 1
    hints = job1.shape_hints()
    db.run("DROP MATERIALIZED VIEW q4")
    db.run(Q4.format(name="renamed"))
    job2 = db._fused["renamed"]
    assert job2.plan_hash == job1.plan_hash
    got = job2.shape_hints()
    for k, caps in hints.items():
        for s, c in caps.items():
            assert got[k][s] >= c, (k, s)
    drive(db)
    assert job2.growth_replays == 0
    assert sorted(db.query("SELECT * FROM renamed")) == oracle


def test_different_plan_never_inherits():
    """A different query under a recycled name gets neither presize
    hints nor executables (plan hash + structural keys differ)."""
    db = Database(device=DeviceConfig(capacity=64, aot_compile=True))
    db.run(BID_SRC.format(n=N, c=CHUNK))
    db.run(Q4.format(name="q4"))
    drive(db)
    db.run("DROP MATERIALIZED VIEW q4")
    db.run("CREATE MATERIALIZED VIEW q4 AS SELECT bidder, count(*) AS c"
           " FROM bid GROUP BY bidder")
    for node in db._fused["q4"].program.nodes:
        for cap in node.cap_current().values():
            assert cap <= 4 * 64, "stale hint presized a different plan"


# ---------------------------------------------------------------------------
# surfaces: compile-status ctl + service summary
# ---------------------------------------------------------------------------


@pytest.mark.aot
def test_ctl_compile_status(tmp_path, capsys, oracle):
    from risingwave_tpu import ctl
    d = str(tmp_path / "data")
    db = Database(data_dir=d, device=DeviceConfig(capacity=64,
                                                  aot_compile=True))
    db.run(BID_SRC.format(n=N, c=CHUNK))
    db.run(Q4.format(name="q4"))
    drive(db)
    assert _svc().wait_idle(120)
    db.store.close()
    del db
    assert ctl.main(["compile-status", "q4", "--data-dir", d,
                     "--wait", "120"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert set(out) == {"q4"}
    rep = out["q4"]
    assert rep["aot"] is True and rep["plan_hash"]
    assert rep["signatures"], "per-signature rows must be reported"
    states = {r["state"] for r in rep["signatures"]}
    assert states <= {"ready", "cached"}, states
    assert rep["counts"]["pending"] == 0
    # two words a row: the manifest's and jax's (no persistent cache in
    # tier-1: whatever the manifest knew, jax built in this process)
    assert all(isinstance(r["cache_hit"], bool) and r["persistent"] == "off"
               for r in rep["signatures"])
    # unknown job: explicit failure
    with pytest.raises(SystemExit):
        ctl.main(["compile-status", "nope", "--data-dir", d])
    capsys.readouterr()


def _source_step_args(n=N):
    """(node, epoch_events, state, ins, extra) of a bid source's node
    step — the smallest real program to hand a PRIVATE service (the
    process-global one stays clean)."""
    import jax.numpy as jnp
    db = Database(device=DeviceConfig(capacity=256, aot_compile=False))
    db.run(BID_SRC.format(n=n, c=CHUNK))
    db.run(Q4.format(name="q4"))
    job = db._fused["q4"]
    node = job.program.nodes[0]
    assert node.takes_event_lo
    return node, job.program.epoch_events, job.states[0], (), jnp.int64(0)


@pytest.mark.aot
def test_await_raises_after_its_limit(monkeypatch):
    """A compile that never lands must not hold the dispatcher for good:
    past `AWAIT_LIMIT_S` the step raises, naming the node."""
    import threading

    from risingwave_tpu.device import compile_service as cs
    monkeypatch.setattr(cs, "AWAIT_LIMIT_S", 0.3)
    svc = cs.CompileService(workers=1)
    svc.hold = hold = threading.Event()
    try:
        with pytest.raises(TimeoutError, match="0:TheNode"):
            svc.node_step(*_source_step_args(), label="0:TheNode")
    finally:
        svc.hold = None
        hold.set()
    assert svc.wait_idle(60)
    svc.shutdown()


@pytest.mark.aot
def test_shutdown_leaves_no_entry_pending_without_a_task():
    """shutdown() drops queued compiles; their entries must not stay
    `pending` (the next step on that signature would wait for good):
    they are forgotten, and the next request compiles afresh."""
    import threading

    from risingwave_tpu.device.compile_service import CompileService
    node, ee, state, ins, extra = _source_step_args()
    svc = CompileService(workers=1)
    svc.hold = hold = threading.Event()
    got = {}

    def step(name, epoch_events):
        got[name] = svc.node_step(node, epoch_events, state, ins, extra,
                                  label=name)

    # the single worker holds signature A in flight; B stays queued
    ta = threading.Thread(target=step, args=("a", ee), daemon=True)
    ta.start()
    while not svc._inflight:
        time.sleep(0.01)
    tb = threading.Thread(target=step, args=("b", 2 * ee), daemon=True)
    tb.start()
    while len(svc._entries) < 2:
        time.sleep(0.01)
    svc.shutdown(join=False, timeout=0.1)
    tb.join(60)          # released to the inline fallback, not left waiting
    assert not tb.is_alive() and got["b"] is not None
    assert svc.inline_steps == 1
    assert [e.label for e in svc._entries.values()] == ["a"]
    svc.hold = None
    hold.set()
    ta.join(60)
    assert not ta.is_alive() and got["a"] is not None
    step("b", 2 * ee)    # a fresh entry: compiled, not inline
    assert svc.summary()["pending"] == 0 and svc.inline_steps == 1
    assert svc.compiled_steps == 2 and svc.summary()["failed"] == 0
    svc.shutdown()


@pytest.mark.aot
def test_heap_is_trimmed_after_a_long_compile(monkeypatch):
    """The compiler's freed scratch goes back to the OS after a compile
    that ran for seconds (and only then: the trim walks every arena)."""
    from risingwave_tpu.device import compile_service as cs
    node, ee, state, ins, extra = _source_step_args()
    trims = []
    monkeypatch.setattr(cs, "_MALLOC_TRIM", trims.append)
    svc = cs.CompileService(workers=1)
    monkeypatch.setattr(cs, "TRIM_AFTER_S", float("inf"))
    svc.node_step(node, ee, state, ins, extra, label="short")
    # the worker publishes "ready" BEFORE it trims, so the step above can
    # return while the trim is still to come: wait for the task's end
    assert svc.wait_idle(30) and trims == []
    monkeypatch.setattr(cs, "TRIM_AFTER_S", 0.0)
    svc.node_step(node, 2 * ee, state, ins, extra, label="long")
    assert svc.wait_idle(30)
    assert trims == [0] and svc.summary()["compiles"] == 2
    svc.shutdown()


@pytest.mark.aot
def test_pending_signature_waits_for_its_compile(oracle):
    """A pending signature WAITS for its background compile — the one
    way a step runs on every backend: every step on a compiled
    executable, none inline, same MV. Uses a capacity no other test in
    this file shares, so the signatures start pending."""
    svc = _svc()
    inline0, compiled0, await0 = (svc.inline_steps, svc.compiled_steps,
                                  svc.await_s)
    db = Database(device=DeviceConfig(capacity=8192 + 4096,
                                      aot_compile=True,
                                      compile_buckets=0))
    db.run(BID_SRC.format(n=N, c=CHUNK))
    db.run(Q4.format(name="q4"))
    drive(db)
    assert svc.inline_steps == inline0
    assert svc.compiled_steps > compiled0
    assert svc.await_s > await0, "the dispatcher waited on the compiles"
    assert sorted(db.query("SELECT * FROM q4")) == oracle


@pytest.mark.aot
def test_failed_aot_compile_is_loud_and_counted(monkeypatch, caplog):
    """A failed background compile may fall back to inline jit, but not
    quietly: one warning with the node label and the compiler's message,
    and `summary()["failed"]` counts it (chip_smoke.py fails on that).
    Runs on a PRIVATE service so the process-global one stays clean."""
    import logging

    from risingwave_tpu.device import fused
    from risingwave_tpu.device.compile_service import CompileService

    args = _source_step_args()
    real = fused._jit_step(args[0])

    class RefusingCompiler:
        def lower(self, *a, **k):
            raise RuntimeError("RESOURCE_EXHAUSTED: boom from the compiler")

        def __call__(self, *a, **k):
            return real(*a, **k)

    monkeypatch.setattr(fused, "_jit_step", lambda node: RefusingCompiler())
    svc = CompileService(workers=1)
    with caplog.at_level(logging.WARNING,
                         logger="risingwave_tpu.device.compile_service"):
        out = svc.node_step(*args, label="0:TheNode")   # failed -> inline
    assert out is not None
    assert svc.summary()["failed"] == 1
    assert svc.summary()["inline_steps"] == 1
    warned = [r.getMessage() for r in caplog.records
              if "0:TheNode" in r.getMessage()]
    assert len(warned) == 1 and "boom from the compiler" in warned[0]
    svc.shutdown()


def test_out_of_memory_is_not_a_recoverable_device_fault():
    """HBM exhaustion arrives as the same XlaRuntimeError a transient
    device fault does; replaying the same shapes exhausts it again, so
    in-place recovery must not absorb it."""
    from risingwave_tpu.device.fused import _is_device_fault
    XlaRuntimeError = type("XlaRuntimeError", (RuntimeError,), {})
    assert _is_device_fault(XlaRuntimeError("INTERNAL: core halted"))
    assert not _is_device_fault(XlaRuntimeError(
        "RESOURCE_EXHAUSTED: Ran out of memory in memory space hbm"))


@pytest.mark.aot
def test_service_summary_counters():
    svc = _svc()
    s = svc.summary()
    assert set(s) >= {"compiles", "failed", "cache_hits", "pending",
                      "inline_steps", "compiled_steps"}
    assert s["failed"] == 0, \
        f"background AOT compiles failed during this suite: {svc.status()}"


def test_aot_off_restores_inline_compiles(oracle):
    """DeviceConfig.aot_compile=False keeps the pre-ISSUE-6 lifecycle:
    no service attached, inline compile events on the epoch loop (what
    jax compiled inside a step; a growth's re-trace is a `retrace`)."""
    db = Database(device=DeviceConfig(capacity=64, aot_compile=False))
    db.run(BID_SRC.format(n=N, c=CHUNK))
    db.run(Q4.format(name="q4"))
    job = db._fused["q4"]
    assert job.compile_service is None
    assert job.program.compile_service is None
    drive(db)
    assert sorted(db.query("SELECT * FROM q4")) == oracle
    # a plan of its own (another source signature, other aggregates), so
    # that jax compiles whatever the process compiled before: every
    # node's first compile, and the grown nodes' re-trace after a growth
    db2 = Database(device=DeviceConfig(capacity=64, aot_compile=False))
    db2.run(BID_SRC.format(n=N - 160, c=CHUNK))
    db2.run("CREATE MATERIALIZED VIEW q4 AS SELECT auction, sum(price) AS s,"
            " sum(bidder) AS t, max(bidder) AS m, count(*) AS c FROM bid"
            " GROUP BY auction")
    job = db2._fused["q4"]
    drive(db2, n=N - 160)
    assert job.growth_replays >= 1
    compiles = job.profiler.compiles
    assert compiles, "inline path must record its compiles"
    firsts = [lab for lab, k, _s in compiles if k == "compile"]
    assert sorted(firsts) == [job.program._node_label(i)
                              for i in range(len(job.program.nodes))]
    again = {lab for lab, k, _s in compiles if k == "retrace"}
    assert again and again < set(firsts)
    assert all("aot" not in r for r in job.profiler.compile_info)
