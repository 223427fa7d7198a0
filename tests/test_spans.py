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
    assert mirror and mirror[-1]["rows"] == len(job._persisted)
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
    _compiles_done()
    before = (len(profile.SPANS), profile.SPANS[-1]["id"]
              if profile.SPANS else None)
    db, job = fused_db(n=N - 64, profile=False)
    assert len(db.query("SELECT * FROM q4")) > 0
    _compiles_done()
    assert job.profiler.span("rw:anything") is profile.NULL_SPAN
    assert db._span is profile.null_span
    assert (len(profile.SPANS), profile.SPANS[-1]["id"]
            if profile.SPANS else None) == before
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

    add("rw:sql", 0, 30, kind="create_source")
    add("rw:sql", 30, 100, kind="create_mv")
    add("rw:compile", 35, 300, thread=2, job="mv", inst=1, node="agg")
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


# leaves on thread 1 inside [2000, 4100): 90+800+100+380+100+200+100+100
READINGS = {
    "commit_mirror_ms_per_ckpt": (150.0, 0.0),   # 300 ms over 2 checkpoints
    "growth_replay_ms": (400.0, 0.0),
    "host_span_coverage_pct": (100.0 * 1870 / 2100, None),
    "setup_create_s": (0.2, 0.0),
    "setup_await_s": (0.25, 0.0),
    "setup_pass_s": (0.8, None),
}
# what each reading is of: with those spans gone, the second value
GONE = {
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
