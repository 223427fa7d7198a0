"""Spans inside the program (utils/profile.py) and the names on what the
device runs: the span tree of a barrier, the phase totals the old readers
read, `profile=False`, stable module names and scopes, the benchmark's six
readers on a hand-made ring, and the annotations in a profiler trace."""
import contextlib
import importlib.util
import os
import re
import subprocess
import sys
from collections import deque

import pytest

from risingwave_tpu.config import DeviceConfig
from risingwave_tpu.sql import Database
from risingwave_tpu.utils import profile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
N = 3_968                  # a stream no other test file compiles
CHUNK = 32
BID_SRC = ("CREATE SOURCE bid (auction BIGINT, bidder BIGINT, price BIGINT,"
           " channel VARCHAR, url VARCHAR, date_time TIMESTAMP,"
           " extra VARCHAR) WITH (connector='nexmark',"
           " nexmark.table='bid', nexmark.max.events='{n}',"
           " nexmark.chunk.size='{c}')")
Q4 = ("CREATE MATERIALIZED VIEW q4 AS SELECT auction, count(*) AS c,"
      " sum(price) AS s, max(price) AS m FROM bid GROUP BY auction")

# the vocabulary of utils/profile.py's docstring
NAMES = {"rw:" + p for p in profile.PHASES} | {
    "rw:barrier", "rw:store_commit", "rw:epoch", "rw:event_lo", "rw:step",
    "rw:stats_fold", "rw:stats_pull", "rw:compile_wait", "rw:compile",
    "rw:compile.inline", "rw:compile_drain", "rw:boot", "rw:boot.start",
    "rw:boot.import", "rw:boot.backend",
    "rw:growth", "rw:commit.mirror", "rw:commit.mirror.pull",
    "rw:commit.mirror.diff", "rw:commit.mirror.table_commit",
    "rw:commit.mirror.decode",
    "rw:commit.job_state", "rw:commit.gauges", "rw:ingest.poll",
    "rw:ingest.pack", "rw:ingest.h2d", "rw:ingest.wait", "rw:sql",
    "rw:sql.fuse_plan"}


def fused_db(n=N, **device):
    db = Database(device=DeviceConfig(capacity=512, **device))
    db.run(BID_SRC.format(n=n, c=CHUNK))
    db.run(Q4)
    job = db._fused["q4"]
    for _ in range(n // (64 * CHUNK) + 3):
        db.tick()
    job.sync()
    return db, job


@pytest.fixture(scope="module")
def run():
    """One small fused job, driven to its drain: (job, the spans the
    process recorded while it ran)."""
    first = len(profile.SPANS)
    db, job = fused_db()
    assert len(db.query("SELECT * FROM q4")) > 0
    return job, list(profile.SPANS)[first:]


def test_every_barrier_yields_the_span_tree(run):
    job, spans = run
    by_id = {s["id"]: s for s in spans}
    assert {s["name"] for s in spans} <= NAMES
    for s in spans:
        assert s["name"].startswith("rw:") and s["t1"] >= s["t0"]
        if s["parent"] is not None:
            parent = by_id[s["parent"]]           # every parent exists
            assert parent["t0"] <= s["t0"] and s["t1"] <= parent["t1"]
            assert parent["thread"] == s["thread"]
    barriers = [s for s in spans if s["name"] == "rw:barrier"]
    epochs = [s for s in spans if s["name"] == "rw:epoch"]
    assert len(epochs) == job.profiler.epochs > 1
    assert len(barriers) >= len(epochs)
    for ep in epochs:
        bar = by_id[ep["parent"]]
        assert bar["name"] == "rw:barrier" and bar["epoch"] == ep["epoch"]
        assert (ep["job"], ep["inst"]) == ("q4", job.profiler.instance)
        kids = [s for s in spans if s["parent"] == ep["id"]]
        names = [s["name"] for s in kids]
        assert names[:2] == ["rw:pack", "rw:dispatch"]
        assert set(names) <= {"rw:" + p for p in profile.PHASES}
        # the identifiers of the barrier's work reach the leaves
        disp = kids[1]
        steps = [s for s in spans if s["parent"] == disp["id"]
                 and s["name"] == "rw:step"]
        assert [s["i"] for s in steps] == list(range(len(job.program.nodes)))
        assert [s["node"] for s in steps] == job.program.node_names
        # (an agg over raw rows has no pre-combined delta to speak of)
        assert not any("recombine" in s for s in steps)
        # the source says the lanes it makes: bid's are the epoch's events
        assert [(s["lanes"], s["of"]) for s in steps if "lanes" in s] \
            == [(job.program.epoch_events,) * 2]
        assert "lanes" in steps[0]
        assert all((s["seq"], s["epoch"], s["inst"])
                   == (ep["seq"], ep["epoch"], ep["inst"]) for s in steps)
        lo = [s for s in spans if s["parent"] == kids[0]["id"]]
        assert [s["name"] for s in lo] == ["rw:event_lo"]
    # a checkpoint's epoch: device_sync with its blocking pull, and the
    # commit whose end is when events [seq_from, seq_to) became durable
    commits = [s for s in spans if s["name"] == "rw:commit"]
    assert commits and commits[-1]["seq_to"] == job.committed >= N
    assert any(s["name"] == "rw:stats_pull"
               and by_id[s["parent"]]["name"] == "rw:device_sync"
               for s in spans)
    mirror = [s for s in spans if s["name"] == "rw:commit.mirror"]
    # the image is the MV as columns; a mirror says what it wrote
    assert mirror and mirror[-1]["rows"] == len(job._persisted)
    for m in mirror:
        assert {"rows", "inserted", "updated", "deleted",
                "keys_vectorised"} <= set(m)
        assert m["inserted"] + m["updated"] <= m["rows"]
        assert m["keys_vectorised"] is True and m["deleted"] == 0
    assert sum(m["inserted"] for m in mirror) == len(job._persisted)
    assert by_id[mirror[-1]["parent"]]["name"] == "rw:commit"
    sql = [s for s in spans if s["name"] == "rw:sql"]
    assert [s["kind"] for s in sql] == ["create_source", "create_mv",
                                       "select"]
    fuse = [s for s in spans if s["name"] == "rw:sql.fuse_plan"]
    assert len(fuse) == 1 and fuse[0]["parent"] == sql[1]["id"]


def test_phase_spans_add_up_to_the_phase_totals(run):
    """`phase_s` is what the spans named after the phases add up to: the
    readers of `summary()`, `rw_epoch_profile` and `risectl profile` read
    what they read before there were spans."""
    job, spans = run
    mine = [s for s in spans if s.get("inst") == job.profiler.instance]
    phase_s = job.profiler.summary()["phase_s"]
    assert set(phase_s) == set(profile.PHASES)
    for phase, total in phase_s.items():
        secs = sum(s["t1"] - s["t0"] for s in mine
                   if s["name"] == "rw:" + phase) / 1e9
        assert secs == pytest.approx(total, abs=1e-3), phase
    assert phase_s["dispatch"] > 0 and phase_s["device_sync"] > 0
    # and per epoch, in the ring's records
    ph_ms = {r["seq"]: r["ph_ms"] for r in job.profiler.ring}
    for ep in (s for s in mine if s["name"] == "rw:epoch"):
        kids = [s for s in mine if s["parent"] == ep["id"]]
        for phase, ms in ph_ms[ep["seq"]].items():
            secs = sum(s["t1"] - s["t0"] for s in kids
                       if s["name"] == "rw:" + phase) / 1e6
            assert secs == pytest.approx(ms, abs=1e-3)


def test_host_fed_job_has_the_stagers_spans():
    """Host ingest: the stager's poll / pack / h2d are spans of the job
    (on its own thread once it prefetches), the dispatch thread's wait for
    it is a leaf under `rw:pack`, and the h2d seconds handed over to their
    own phase come out of `pack`'s."""
    first = len(profile.SPANS)
    db, job = fused_db(host_ingest=True)
    assert job.ingest is not None and len(db.query("SELECT * FROM q4")) > 0
    job.ingest.close()
    spans = [s for s in list(profile.SPANS)[first:]
             if s.get("inst") == job.profiler.instance]
    by_id = {s["id"]: s for s in spans}
    packs = [s for s in spans if s["name"] == "rw:pack"]
    waits = [s for s in spans if s["name"] == "rw:ingest.wait"]
    assert len(packs) == len(waits) == job.profiler.epochs
    assert all(by_id[w["parent"]]["name"] == "rw:pack" for w in waits)
    staged = {n: [s for s in spans if s["name"] == "rw:ingest." + n]
              for n in ("poll", "pack", "h2d")}
    windows = [w["window"] for w in waits]
    for n, ss in staged.items():
        assert sorted(s["window"] for s in ss) == sorted(windows), n
    # staged on the dispatch thread (first window) or prefetched
    threads = {s["tname"] for s in staged["h2d"]}
    assert threads <= {packs[0]["tname"], "rw-ingest-stage"}
    assert all(s["parent"] is None for s in staged["h2d"]
               if s["tname"] == "rw-ingest-stage")
    phase_s = job.profiler.summary()["phase_s"]
    assert phase_s["h2d"] > 0
    assert phase_s["pack"] + phase_s["h2d"] == pytest.approx(
        sum(s["t1"] - s["t0"] for s in packs) / 1e9, abs=1e-3)


def _compiles_done():
    """No background compile of an earlier job may land (and record its
    `rw:compile`) while a test counts spans."""
    from risingwave_tpu.device.compile_service import get_service
    assert get_service().wait_idle(300)


def test_profile_off_records_nothing():
    from jax._src import monitoring
    _compiles_done()

    def ring():
        # (the drain of `_compiles_done` is this test's own span)
        mine = [s for s in profile.SPANS if s["name"] != "rw:compile_drain"]
        return len(mine), mine[-1]["id"] if mine else None

    before, listeners = ring(), len(monitoring.get_event_listeners())
    compiled = sum(profile.COMPILES[k] for k in ("built", "small", "loaded"))
    db, job = fused_db(n=N - 64, profile=False)
    assert len(db.query("SELECT * FROM q4")) > 0
    _compiles_done()
    assert job.profiler.span("rw:anything") is profile.NULL_SPAN
    assert db._span is profile.null_span
    assert ring() == before
    # jax compiled this stream's source all the same, and was counted:
    # by the one pair of listeners, nothing registered per job or call
    assert sum(profile.COMPILES[k]
               for k in ("built", "small", "loaded")) > compiled
    assert len(monitoring.get_event_listeners()) == listeners
    assert job.profiler.compiles == []
    assert job.profiler.summary()["phase_s"] == {p: 0.0
                                                 for p in profile.PHASES}
    with profile.NULL_SPAN as sp:         # one shared object, no state
        sp.set(rows=1)
    assert not hasattr(sp, "attrs") and sp.seconds == 0.0


def test_a_span_is_named_rw_and_a_stale_one_goes_with_its_parent():
    with pytest.raises(ValueError):
        profile.span("sync")      # the benchmark's reduction keeps `sync`
    first = len(profile.SPANS)
    with profile.span("rw:barrier", epoch=7) as outer:
        stale = profile.span("rw:epoch", seq=1)
        stale.__enter__()                               # not closed in time
    with profile.span("rw:barrier", epoch=8):
        stale.__exit__(None, None, None)    # late: it went with its parent
    a, b = list(profile.SPANS)[first:]
    assert (a["id"], a["parent"], a["epoch"]) == (outer.id, None, 7)
    assert (b["parent"], b["epoch"]) == (None, 8)       # not inside `a`


# ---- what jax did for a compile, on the span of that compile ---------------

JAX_DID = {"fun_name", "persistent", "backend_compile_s", "trace_s",
           "lower_s"}


def _compile_spans(since):
    return [s for s in list(profile.SPANS)[since:]
            if s["name"] in profile.COMPILE_SPANS]


def _counted():
    return {k: profile.COMPILES[k] for k in ("built", "small", "loaded",
                                             "lost")}


def test_a_service_compile_is_one_span_and_its_close_the_record(tmp_path):
    """A compile of the service is ONE `rw:compile` span that says what jax
    did, and the job's labeled compile record is that span, closed: the
    old keys, plus `persistent`; in memory, in the file, in `summary()`."""
    import json
    _compiles_done()
    first = len(profile.SPANS)
    db = Database(device=DeviceConfig(capacity=512, compile_buckets=0),
                  data_dir=str(tmp_path))
    db.run(BID_SRC.format(n=N - 128, c=CHUNK))   # a source not yet compiled
    db.run(Q4)
    job = db._fused["q4"]
    for _ in range(4):
        db.tick()
    job.sync()
    _compiles_done()
    spans = [s for s in _compile_spans(first) if s["name"] == "rw:compile"
             and s.get("inst") == job.profiler.instance]
    assert spans and all(s["ok"] and s["aot"] for s in spans)
    for s in spans:
        assert JAX_DID <= set(s) and s["programs"] == 1
        assert s["persistent"] == "off"          # tier-1 places no cache
        assert s["backend_compile_s"] > 0 and s["trace_s"] > 0 \
            and s["lower_s"] > 0
        assert s["fun_name"] == f"jit(step_{s['node']})"
        assert s["backend_compile_s"] + s["trace_s"] + s["lower_s"] \
            <= (s["t1"] - s["t0"]) / 1e9
        assert "lost" not in s and s["tname"].startswith("rw-aot-")
    recs = list(job.profiler.compile_info)
    assert sorted(r["label"] for r in recs) == sorted(s["label"]
                                                      for s in spans)
    by_label = {s["label"]: s for s in spans}
    for r in recs:
        s = by_label[r["label"]]
        assert set(r) >= {"ev", "job", "label", "kind", "s", "ts", "bucket",
                          "aot", "persistent"}
        assert (r["ev"], r["job"], r["kind"], r["aot"]) \
            == ("compile", "q4", s["kind"], True)
        assert r["s"] == (s["t1"] - s["t0"]) / 1e9    # the span's clock
        assert (r["bucket"], r["persistent"]) == (s["bucket"], "off")
    assert [e["label"] for e in job.profiler.summary()["compile_events"]] \
        == [r["label"] for r in recs]
    job.profiler.flush()
    with open(os.path.join(str(tmp_path), profile.PROFILE_FILE)) as f:
        filed = [r for r in map(json.loads, f) if r["ev"] == "compile"]
    assert filed == recs
    from risingwave_tpu.device.compile_service import get_service
    rows = get_service().status("q4")
    assert rows and {r["persistent"] for r in rows
                     if r["state"] == "ready"} == {"off"}


@pytest.fixture
def placed_cache(tmp_path):
    """jax's persistent cache placed in a directory of the test's, every
    compile written to it; taken away again afterwards."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = (jax.config.jax_compilation_cache_dir,
           jax.config.jax_persistent_cache_min_compile_time_secs)
    _compiles_done()
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cc.reset_cache()
    yield str(tmp_path)
    jax.config.update("jax_compilation_cache_dir", was[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", was[1])
    cc.reset_cache()


def _source_step(n):
    import jax.numpy as jnp
    db = Database(device=DeviceConfig(capacity=512, aot_compile=False))
    db.run(BID_SRC.format(n=n, c=CHUNK))
    db.run(Q4)
    job = db._fused["q4"]
    return (job.program.nodes[0], job.program.epoch_events, job.states[0],
            (), jnp.int64(0))


@pytest.mark.parametrize("entry_kept", [True, False],
                         ids=["hit", "lost"])
def test_persistent_says_what_jax_did_with_the_cache(placed_cache,
                                                     entry_kept):
    """The same program through three services that share a cache
    directory, as three processes would: jax builds it and writes the entry
    (`miss`); a second service finds the digest in the manifest
    (`cache_hit`) and jax reads the entry (`hit`, `retrieval_s`); with the
    entry deleted and the manifest kept, `cache_hit` still and `miss`
    again: `lost`. jax's own counter moves with each."""
    from risingwave_tpu.device import fused
    from risingwave_tpu.device.compile_service import (MANIFEST_FILE,
                                                       CompileService)
    args = _source_step(N - 192 - 64 * entry_kept)

    def compile_in_a_new_service():
        # a fresh jit of the same text: what this process has traced and
        # compiled for the node is another process's to this one
        fused._JIT_STEPS.pop(args[0].stable_name())
        first, was = len(profile.SPANS), _counted()
        svc = CompileService(workers=1)
        svc.node_step(*args, label="0:source")       # no profiler
        assert svc.wait_idle(60)
        svc.shutdown()
        (span,) = [s for s in _compile_spans(first)
                   if s["name"] == "rw:compile"]
        assert "job" not in span and span["label"] == "0:source"
        moved = {k: v - was[k] for k, v in _counted().items() if v != was[k]}
        return span, svc.summary(), moved

    span, summary, moved = compile_in_a_new_service()
    assert (span["cache_hit"], span["persistent"]) == (False, "miss")
    assert "retrieval_s" not in span and "lost" not in span
    assert (summary["built"], summary["loaded"], summary["lost"],
            summary["cache_hits"]) == (1, 0, 0, 0)
    assert moved == {"small": 1}       # a build of under a second
    entries = [f for f in os.listdir(placed_cache) if f != MANIFEST_FILE]
    assert entries and MANIFEST_FILE in os.listdir(placed_cache)
    if not entry_kept:
        for f in entries:
            os.remove(os.path.join(placed_cache, f))
    span, summary, moved = compile_in_a_new_service()
    assert span["cache_hit"] is True        # the manifest's word, kept
    if entry_kept:
        assert span["persistent"] == "hit" and span["retrieval_s"] > 0
        assert "lost" not in span
        assert (summary["built"], summary["loaded"], summary["lost"],
                summary["cache_hits"]) == (0, 1, 0, 1)
        assert moved == {"loaded": 1}
    else:
        assert span["persistent"] == "miss" and span["lost"] is True
        assert (summary["built"], summary["loaded"], summary["lost"],
                summary["cache_hits"]) == (1, 0, 1, 1)
        assert moved == {"small": 1, "lost": 1}


def test_a_build_too_short_for_the_cache_is_never_lost():
    """jax writes no entry for a build shorter than
    `jax_persistent_cache_min_compile_time_secs`: the manifest knows such
    a program and the cache never held it, so a miss of it is no loss."""
    import jax
    least = jax.config.jax_persistent_cache_min_compile_time_secs
    assert least > 0
    was = profile.COMPILES["lost"]
    for seconds, lost in ((least / 2, False), (least * 2, True)):
        profile._OPEN.compiled = {"persistent": "miss", "programs": 1,
                                  "backend_compile_s": seconds}
        assert ("lost" in profile.take_compiled(True)) is lost
    profile._OPEN.compiled = {"persistent": "hit", "programs": 1,
                              "backend_compile_s": least * 2}
    assert "lost" not in profile.take_compiled(True)
    assert profile.COMPILES["lost"] == was + 1


def test_a_jit_outside_the_service_leaves_one_inline_span():
    """A jit called where no `rw:compile` is open — the stats fold, a
    tier or gather program, an eager primitive — leaves exactly one
    `rw:compile.inline` under the span that is open on that thread; under
    an open `rw:compile` it leaves none (the service takes it); with no
    span open, none either. jax's counter counts all three."""
    import jax
    import jax.numpy as jnp
    x = jnp.arange(8)
    jax.block_until_ready(x + 1)            # eager programs compiled here
    first, was = len(profile.SPANS), _counted()
    with profile.span("rw:stats_fold", job="j", inst=0, seq=3) as outer:
        jax.jit(lambda v: v * 5 + 2)(x)
    with profile.span("rw:compile", node="n"):
        profile.take_compiled()      # what this thread compiled before
        jax.jit(lambda v: v * 6 + 2)(x)
        taken = profile.take_compiled()
        assert profile.take_compiled() == {}       # taken once
    jax.jit(lambda v: v * 7 + 2)(x)
    (inline,) = _compile_spans(first)[:1]
    names = [s["name"] for s in list(profile.SPANS)[first:]]
    assert names == ["rw:compile.inline", "rw:stats_fold", "rw:compile"]
    assert inline["parent"] == outer.id and JAX_DID <= set(inline)
    assert (inline["job"], inline["seq"]) == ("j", 3)     # its parent's
    assert outer.t0 <= inline["t0"] <= inline["t1"] <= outer.t1
    assert inline["fun_name"] == "jit(<lambda>)" \
        and inline["persistent"] == "off"
    assert (inline["t1"] - inline["t0"]) / 1e9 \
        >= inline["backend_compile_s"] > 0
    assert taken["programs"] == 1 and taken["fun_name"] == "jit(<lambda>)"
    moved = {k: v - was[k] for k, v in _counted().items()}
    assert moved["built"] + moved["small"] == 3 and moved["loaded"] == 0


def test_jaxs_counter_equals_the_spans_sums(run):
    """Every backend compile jax reported while the job ran is in exactly
    one compile span: `COMPILES` moved by the spans' programs and their
    backend seconds."""
    import jax
    import jax.numpy as jnp
    _compiles_done()
    first, was = len(profile.SPANS), dict(profile.COMPILES)
    with profile.span("rw:sql", kind="select"):
        for k in (11, 12, 13):
            jax.jit(lambda v, _k=k: v * _k - 1)(jnp.arange(4))
    db, job = fused_db(n=N - 320)
    _compiles_done()
    spans = _compile_spans(first)
    assert {s["name"] for s in spans} == set(profile.COMPILE_SPANS)
    now = profile.COMPILES
    assert now["loaded"] == was["loaded"] and now["lost"] == was["lost"]
    assert sum(s.get("programs", 1) for s in spans) \
        == now["built"] + now["small"] - was["built"] - was["small"]
    assert sum(s["backend_compile_s"] for s in spans) == pytest.approx(
        now["built_s"] + now["small_s"] - was["built_s"] - was["small_s"])


def test_an_inline_step_compile_is_the_jobs_record_under_its_step():
    """Service off: what jax compiled inside a node's `rw:step` is a
    `rw:compile.inline` span under it, and that span's close the job's
    record — labeled from the step span, `compile` for the node's first,
    no `aot`; a step that compiled nothing leaves no record however long
    it took."""
    first = len(profile.SPANS)
    db, job = fused_db(n=N - 384, aot_compile=False)
    spans = list(profile.SPANS)[first:]
    by_id = {s["id"]: s for s in spans}
    inline = [s for s in spans if s["name"] == "rw:compile.inline"
              and by_id.get(s["parent"], {}).get("name") == "rw:step"]
    assert inline and all(s["inst"] == job.profiler.instance
                          for s in inline)
    recs = list(job.profiler.compile_info)
    assert [r["label"] for r in recs] == [by_id[s["parent"]]["label"]
                                          for s in inline]
    for r, s in zip(recs, inline):
        step = by_id[s["parent"]]
        assert s["node"] == step["node"]
        assert step["label"] == job.program._node_label(step["i"])
        assert (r["kind"], r["persistent"]) == ("compile", "off")
        assert "aot" not in r and r["s"] == (s["t1"] - s["t0"]) / 1e9
    # one record a node that compiled, none for the steps after
    assert len({r["label"] for r in recs}) == len(recs)
    steps = [s for s in spans if s["name"] == "rw:step"]
    assert len(steps) > len(recs)


def test_the_drain_and_the_boot_are_spans(monkeypatch):
    from risingwave_tpu.device.compile_service import get_service
    first = len(profile.SPANS)
    assert get_service().wait_idle(60)
    assert [s["name"] for s in list(profile.SPANS)[first:]] \
        == ["rw:compile_drain"]
    # the import of risingwave_tpu.device recorded the boot (this
    # process's ring may have turned over since: record it again)
    import time

    import risingwave_tpu
    assert profile._BOOTED and profile._LISTENING
    monkeypatch.setattr(profile, "_BOOTED", False)
    first, now = len(profile.SPANS), time.perf_counter_ns()
    profile.boot_done(risingwave_tpu._T_IMPORT)
    profile.boot_done(risingwave_tpu._T_IMPORT)          # once a process
    boot, start, imp = list(profile.SPANS)[first:]
    assert [s["name"] for s in (boot, start, imp)] \
        == ["rw:boot", "rw:boot.start", "rw:boot.import"]
    assert start["parent"] == imp["parent"] == boot["id"]
    assert boot["t0"] == start["t0"] < start["t1"] == imp["t0"] \
        == risingwave_tpu._T_IMPORT < imp["t1"] == boot["t1"]
    assert now <= boot["t1"]
    # the OS's word for the start of this process: before the package's
    # first line, and not by much more than the interpreter's own start
    # plus whatever the test runner imported first
    assert 0 < (start["t1"] - start["t0"]) / 1e9 < 600
    monkeypatch.setattr(profile, "_process_start_ns", lambda: None)
    monkeypatch.setattr(profile, "_BOOTED", False)
    first = len(profile.SPANS)
    profile.boot_done(risingwave_tpu._T_IMPORT)
    assert [s["name"] for s in list(profile.SPANS)[first:]] \
        == ["rw:boot", "rw:boot.import"]                 # failing that


def test_the_first_device_database_touches_the_backend(monkeypatch):
    monkeypatch.setattr(profile, "_BACKEND_TOUCHED", False)
    first = len(profile.SPANS)
    Database(device=DeviceConfig(capacity=512, profile=False))
    assert len(profile.SPANS) == first and profile._BACKEND_TOUCHED
    monkeypatch.setattr(profile, "_BACKEND_TOUCHED", False)
    Database(device="off")                          # no device: no touch
    assert not profile._BACKEND_TOUCHED
    Database(device=DeviceConfig(capacity=512))
    Database(device=DeviceConfig(capacity=512))     # once a process
    (span,) = list(profile.SPANS)[first:]
    assert span["name"] == "rw:boot.backend" and span["parent"] is None
    assert (span["platform"], span["devices"]) == ("cpu", 8)


def _lower_step(job, i):
    from risingwave_tpu.device.compile_service import abstract_program_avals
    from risingwave_tpu.device.fused import _jit_step
    prog = job.program
    node = prog.nodes[i]
    sds = abstract_program_avals(prog.nodes, prog.epoch_events)[i]
    return node, _jit_step(node).lower(
        *sds, node=node, epoch_events=prog.epoch_events,
        salt=node._mut_sig())


def _agg_step(job):
    from risingwave_tpu.device.fused import AggNode
    return _lower_step(job, next(i for i, n in enumerate(job.program.nodes)
                                 if isinstance(n, AggNode)))


def test_module_is_named_after_its_node_and_scopes_are_metadata(
        run, monkeypatch):
    import jax
    job, _spans = run
    node, lowered = _agg_step(job)
    name = node.stable_name()
    assert name == "agg_k0_count_sum1_max2" and re.fullmatch(r"\w+", name)
    hlo = lowered.compile().as_text()
    assert hlo.startswith(f"HloModule jit_step_{name},")
    assert "lambda" not in hlo.split("\n", 1)[0]
    ops = re.findall(r'op_name="([^"]*)"', hlo)
    assert any("agg.merge/merge.sort" in o for o in ops)
    assert any("agg.merge/by_position" in o for o in ops)
    assert not any("lookup" in o for o in ops)
    # scopes are metadata only: without them, the same program
    text = lowered.as_text()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    from risingwave_tpu.device import fused
    monkeypatch.setattr(fused, "_JIT_STEPS", {})
    _node, bare = _agg_step(job)
    assert "merge.sort" not in bare.as_text(debug_info=True)
    assert "merge.sort" in lowered.as_text(debug_info=True)
    assert bare.as_text() == text
    # the other jits the served path dispatches carry names too
    jnp = jax.numpy
    assert fused._stack_stats((jnp.int64(1),)).shape == (1,)
    assert fused._STACK_JIT.lower((jnp.int64(1),)).as_text().startswith(
        "module @jit_stats_stack")
    assert fused._named(lambda: 0, "tier_x").__name__ == "tier_x"


def test_source_step_says_the_lanes_it_makes():
    """A person source makes one row of fifty events: its `rw:step` span
    carries `lanes` (the pow2 bucket of the table's rows an epoch, what
    `flow_report()` reads off the delta it handed on) and `of`, the
    epoch's events; no other step says either."""
    from bench import PERSON_SRC
    first = len(profile.SPANS)
    db = Database(device=DeviceConfig(capacity=512))
    db.run(PERSON_SRC.format(n=4 * 64 * 512, c=512))
    db.run("CREATE MATERIALIZED VIEW by_state AS SELECT state, count(*) AS c"
           " FROM person GROUP BY state")
    job = db._fused["by_state"]
    for _ in range(7):
        db.tick()
    job.sync()
    assert sum(r[1] for r in db.query("SELECT * FROM by_state")) \
        == 4 * 64 * 512 // 50 + 1
    epoch = job.program.epoch_events
    assert epoch == 32_768
    steps = [s for s in list(profile.SPANS)[first:]
             if s["name"] == "rw:step"
             and s.get("inst") == job.profiler.instance]
    src = [s for s in steps if s["i"] == 0]
    assert len(src) == 4 and "source_person" in src[0]["node"]
    # (32,768 // 50 + 2) rows -> 1,024 lanes
    assert all((s["lanes"], s["of"]) == (1024, epoch) for s in src)
    assert not any("lanes" in s or "of" in s for s in steps if s["i"] != 0)
    assert job.flow_report()["nodes"][0]["lanes"] == 1024


def test_precombined_agg_step_says_it_reduces_once(monkeypatch):
    """With the pre-combine armed (the default the conftest pins off) and
    no exchange, the agg's `rw:step` spans read `recombine=False`, its
    module's whole reduce stage is the `agg.reduce_delta/passthrough`
    scope with no sort under it, and the pre-combine's module keeps the
    one real reduce (`agg.reduce_delta`, a sort)."""
    from risingwave_tpu.device.fused import AggNode, PrecombineNode
    monkeypatch.setenv("RW_AGG_PRECOMBINE", "1")
    first = len(profile.SPANS)
    _db, job = fused_db(n=N - 64 * CHUNK)       # a stream of its own
    spans = [s for s in list(profile.SPANS)[first:]
             if s.get("inst") == job.profiler.instance]
    nodes = job.program.nodes
    agg = next(i for i, n in enumerate(nodes) if isinstance(n, AggNode))
    pre = next(i for i, n in enumerate(nodes)
               if isinstance(n, PrecombineNode))
    assert nodes[agg].combined and nodes[agg].exch is None
    steps = [s for s in spans if s["name"] == "rw:step"]
    assert steps and {s["i"] for s in steps} == set(range(len(nodes)))
    for s in steps:
        if s["i"] == agg:
            assert s["recombine"] is False
        else:
            assert "recombine" not in s
    _node, lowered = _agg_step(job)
    hlo = lowered.compile().as_text()
    reduce_ops = [o for o in re.findall(r'op_name="([^"]*)"', hlo)
                  if "agg.reduce_delta" in o]
    assert reduce_ops and all("agg.reduce_delta/passthrough" in o
                              for o in reduce_ops)
    assert not any(re.search(r"/(sort|scatter|gather)", o)
                   for o in reduce_ops)
    text = _lower_step(job, pre)[1].as_text(debug_info=True)
    assert re.search(r'agg\.reduce_delta[^"]*sort', text)
    assert "passthrough" not in text


_NAMES_SCRIPT = """
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, {root!r})
from risingwave_tpu.config import DeviceConfig
from risingwave_tpu.sql import Database
db = Database(device=DeviceConfig(capacity=512, aot_compile=False))
db.run({src!r})
db.run({mv!r})
prog = db._fused["q4"].program
from risingwave_tpu.device.fused import _jit_step
for i, node in enumerate(prog.nodes):
    print(prog._node_label(i), _jit_step(node).__name__)
"""


def test_labels_and_module_names_are_the_same_in_every_process():
    script = _NAMES_SCRIPT.format(root=ROOT, src=BID_SRC.format(n=N, c=CHUNK),
                                  mv=Q4)
    procs = [subprocess.Popen(
        [sys.executable, "-c", script], stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True,
        env={**os.environ, "PYTHONHASHSEED": seed}) for seed in ("1", "2")]
    outs = []
    for p in procs:
        out, err = p.communicate(timeout=300)
        assert p.returncode == 0, err[-2000:]
        outs.append(out.strip().splitlines())
    assert outs[0] == outs[1] and len(outs[0]) >= 3
    for line in outs[0]:
        label, module = line.split()
        idx, name, sig = label.split(":")
        assert module == f"step_{name}" and len(sig) == 8
        assert re.fullmatch(r"[a-z0-9_]+", name)


# ---- the benchmark's readers on a hand-made ring ---------------------------

def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _ring():
    """Two instances of job `mv` on thread 1, times in ms: the set-up pass
    (barriers at 100..400 and 400..900, 250 ms of compile waits, 50 ms
    before them) and the window (barriers 2000..3000 and 3000..4000, a
    closing sync to 4100), CREATEs of 30 + 70 and 40 + 60 ms before each."""
    ms = 1_000_000
    out, ids = [], iter(range(1, 1000))

    def add(name, t0, t1, parent=None, thread=1, **kw):
        out.append({"id": next(ids), "parent": parent, "name": name,
                    "t0": t0 * ms, "t1": t1 * ms, "thread": thread,
                    "tname": f"t{thread}", **kw})
        return out[-1]["id"]

    boot = add("rw:boot", -600, -100)          # the process began at -600
    add("rw:boot.start", -600, -550, boot)                  # leaf: 50
    add("rw:boot.import", -550, -100, boot)                 # leaf: 450
    add("rw:boot.backend", -80, -60, platform="cpu")        # leaf: 20
    add("rw:sql", 0, 30, kind="create_source")              # leaf: 30
    q = add("rw:sql", 30, 100, kind="create_mv")
    # what jax did (seconds, whatever the ring's scale): an eager
    # primitive under the CREATE, a program built though the manifest
    # knew it, one read from the persistent cache
    add("rw:compile.inline", 40, 60, q, fun_name="jit(iota)",   # leaf: 20
        persistent="miss", backend_compile_s=0.01)
    add("rw:compile", 35, 300, thread=2, job="mv", inst=1, node="agg",
        cache_hit=True, persistent="miss", backend_compile_s=1.5, lost=True)
    add("rw:compile", 300, 330, thread=2, job="mv", inst=1, node="mv",
        cache_hit=True, persistent="hit", backend_compile_s=0.3,
        retrieval_s=0.25)
    for t0, t1, wait in ((100, 400, (120, 320)), (400, 900, (450, 500))):
        b = add("rw:barrier", t0, t1, epoch=t0)
        e = add("rw:epoch", t0 + 5, t1 - 5, b, job="mv", inst=1, epoch=t0)
        d = add("rw:dispatch", t0 + 10, t1 - 10, e, job="mv", inst=1)
        s = add("rw:step", t0 + 10, t1 - 10, d, job="mv", inst=1)
        add("rw:compile_wait", *wait, s, job="mv", inst=1)
    add("rw:sql", 1000, 1040, kind="create_source")
    add("rw:sql", 1040, 1100, kind="create_mv")
    add("rw:sql", 1100, 1150, kind="select")           # not a create
    b = add("rw:barrier", 2000, 3000, epoch=2000)
    e = add("rw:epoch", 2000, 3000, b, job="mv", inst=2)
    p = add("rw:pack", 2000, 2100, e, job="mv", inst=2)
    add("rw:event_lo", 2010, 2100, p, job="mv", inst=2)     # leaf: 90
    add("rw:dispatch", 2100, 2900, e, job="mv", inst=2)     # leaf: 800
    b = add("rw:barrier", 3000, 4000, epoch=3000)            # drain: no epoch
    y = add("rw:device_sync", 3000, 3500, b, job="mv", inst=2)
    add("rw:stats_pull", 3000, 3100, y, job="mv", inst=2)   # leaf: 100
    g = add("rw:growth", 3100, 3500, y, job="mv", inst=2)
    add("rw:step", 3100, 3480, g, job="mv", inst=2)         # leaf: 380
    c = add("rw:commit", 3500, 3900, b, job="mv", inst=2)
    m = add("rw:commit.mirror", 3500, 3800, c, job="mv", inst=2, rows=9)
    add("rw:commit.mirror.pull", 3500, 3600, m, job="mv", inst=2)  # 100
    add("rw:commit.mirror.diff", 3600, 3800, m, job="mv", inst=2)  # 200
    add("rw:commit.job_state", 3800, 3900, c, job="mv", inst=2)    # 100
    add("rw:device_sync", 4000, 4100, job="mv", inst=2)     # closing: 100
    add("rw:ingest.h2d", 2000, 4100, thread=3, job="mv", inst=2)   # stager
    q = add("rw:sql", 5000, 5100, kind="select")        # after the window
    add("rw:device_sync", 5000, 5050, q, job="mv", inst=2)
    return out


# leaves on thread 1 inside [2000, 4100): 90+800+100+380+100+200+100+100;
# inside [-600, 2000): 50+450+20+30+20, the waits' 250, the later SQL's 150
READINGS = {
    "setup_boot_s": (0.6, None),
    "setup_compiles": (1, None),
    "setup_compile_s": (1.5, None),
    "setup_cache_load_s": (0.25, None),
    "setup_cache_lost": (1, None),
    "setup_span_coverage_pct": (100.0 * 970 / 2600, None),
    "commit_mirror_ms_per_ckpt": (150.0, 0.0),   # 300 ms over 2 checkpoints
    "growth_replay_ms": (400.0, 0.0),
    "host_span_coverage_pct": (100.0 * 1870 / 2100, None),
    "setup_create_s": (0.2, 0.0),
    "setup_await_s": (0.25, 0.0),
    "setup_pass_s": (0.8, None),
}
# what each reading is of: with those spans gone, the second value
GONE = {
    "setup_boot_s": lambda s: s["name"] == "rw:boot",
    "setup_compiles": lambda s: "persistent" in s,     # a program that
    "setup_compile_s": lambda s: "persistent" in s,    # does not say
    "setup_cache_load_s": lambda s: "persistent" in s,
    "setup_cache_lost": lambda s: "persistent" in s,
    "setup_span_coverage_pct": lambda s: s["name"] == "rw:boot",
    "commit_mirror_ms_per_ckpt": lambda s: s["name"] == "rw:commit.mirror",
    "growth_replay_ms": lambda s: s["name"] == "rw:growth",
    "host_span_coverage_pct": lambda s: s["name"] == "rw:barrier",
    "setup_create_s": lambda s: s.get("kind", "").startswith("create_"),
    "setup_await_s": lambda s: s["name"] == "rw:compile_wait",
    "setup_pass_s": lambda s: s.get("inst") == 1,
}


@pytest.mark.parametrize("metric", sorted(READINGS))
def test_reader_on_a_hand_made_ring(metric, monkeypatch):
    monkeypatch.syspath_prepend(os.path.join(BENCH, "lib"))
    reader = _load(os.path.join(BENCH, "metrics", metric + ".py"),
                   "bench_metric_" + metric)
    run = {"checkpoints": 2}
    value, without = READINGS[metric]
    monkeypatch.setattr(profile, "SPANS", deque(_ring()))
    assert reader.read(run) == pytest.approx(value, rel=1e-12)
    monkeypatch.setattr(profile, "SPANS", deque(
        s for s in _ring() if not GONE[metric](s)))
    got = reader.read(run)
    assert got == without if without is None \
        else got == pytest.approx(without)
    # a program with no span ring, or an empty one: nothing to read
    monkeypatch.setattr(profile, "SPANS", deque())
    assert reader.read(run) is None
    monkeypatch.delattr(profile, "SPANS")
    assert reader.read(run) is None


def test_annotations_reach_the_profiler_trace(tmp_path, monkeypatch):
    """Under a profiler session the spans are on the host plane of the
    .xplane.pb under their own names, read as the benchmark reads a
    trace, and none of them under a name the runner's spans use."""
    import jax
    trace_lib = _load(os.path.join(BENCH, "lib", "trace.py"), "bench_trace")
    db = Database(device=DeviceConfig(capacity=512, compile_buckets=0))
    db.run(BID_SRC.format(n=N, c=CHUNK))
    db.run(Q4)
    db.tick()                                   # compiles: not traced
    _compiles_done()
    first = len(profile.SPANS)
    trace_lib.start(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("window"):
            db.tick()
            db._fused["q4"].sync()
    finally:
        jax.profiler.stop_trace()
    ring = [s["name"] for s in list(profile.SPANS)[first:]]
    path = trace_lib.find_xplane(str(tmp_path))
    traced = [n for _p, _l, n, _s, _d in
              trace_lib.load(path, keep_host=("rw:",))]
    assert sorted(traced) == sorted(ring)
    assert {"rw:barrier", "rw:epoch", "rw:dispatch", "rw:step"} <= set(ring)
    assert not [n for n in traced
                if n.startswith(trace_lib.RUNNER_SPANS)]
    # the runner's own reading of the same file does not see them
    assert [n for _p, _l, n, _s, _d in trace_lib.load(path)] == ["window"]
