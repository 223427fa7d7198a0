"""NEXmark q5 alone through `Database` with the benchmark's defaults: the
deployment the cell `q5.device` measures on one chip (`nexmark-q5`: hop x5,
the hop-to-count chain twice, a retractable max over the counts' change
stream, a join on the window and `num >= maxn`), at a small size on the CPU.

Tier-1 pins the default-on traced features off (conftest); the benchmark
runs the defaults, so this file forces them back on. The yardstick is the
benchmark's frozen numpy reference (`benchmarks/lib/nexmark_ref.py`,
`benchmarks/configs/nexmark-q5.py`), which imports nothing of the program;
one test here ties it to the program's generator and host executors at this
commit.
"""
import collections
import importlib.util
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the configuration's .py imports the frozen stream from the benchmark's
# lib; the rehearsal is the benchmark's runner
sys.path[:0] = [os.path.join(ROOT, "benchmarks", "lib"),
                os.path.join(ROOT, "benchmarks")]

from risingwave_tpu.config import DeviceConfig  # noqa: E402
from risingwave_tpu.sql import Database  # noqa: E402
from risingwave_tpu.utils.profile import SPANS  # noqa: E402

ARMED = ("RW_SKEW_STATS", "RW_FLOW_STATS", "RW_AGG_PRECOMBINE",
         "RW_STATE_TIERING")
# (events a poll, polls an epoch, events, capacity). A slide is 20,000
# events (2 s at 100 us an event). "aligned": 8 epochs of 5,000, every
# fourth border a slide's (the benchmark's CPU rehearsal, 40 epochs of
# 1,024, has the slides' borders inside an epoch). "grows": a preset a
# quarter of what the groups need.
CADENCES = {"aligned": (100, 50, 40_000, 32_768),
            "grows": (100, 50, 10_000, 1_024)}
REHEARSAL = (40_960, 1_024)        # events, events an epoch
SEEDS = (1, 2**31 + 5)
STEPS = ["source_bid", "hop_c5_h2000000_s10000000", "map_c8_c0",
         "precombine_k0_1_count", "agg_k0_1_count", "map_c1_c2_c0",
         "hop_c5_h2000000_s10000000", "map_c0_c8", "precombine_k0_1_count",
         "agg_k0_1_count", "chain_map_map_67bf2c", "agg_k0_max1",
         "map_c1_c0", "join_l2_r1", "map_c0_c1_c2_c4", "mvpair_4c"]


def _config_code():
    """The configuration's .py, loaded as the benchmark's runner loads it."""
    path = os.path.join(ROOT, "benchmarks", "configs", "nexmark-q5.py")
    spec = importlib.util.spec_from_file_location("bench_config_q5", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


CODE = _config_code()


@pytest.fixture(scope="module")
def armed():
    """The benchmark's configuration: every default-on traced feature on."""
    mp = pytest.MonkeyPatch()
    for k in ARMED:
        mp.setenv(k, "1")
    yield mp
    mp.undo()


def _drain(db, job=None):
    if job is None:                       # host executors: no job to ask
        for _ in range(40):
            db.tick()
        return
    while job.counter < job.max_events or job.committed < job.counter:
        db.tick()
    job.sync()


_RUNS = {}


def _drive(armed, seed, cadence):
    """One drained run of the cell's statements (cached per module):
    (MV rows, job, the job's spans)."""
    key = (seed, cadence)
    if key in _RUNS:
        return _RUNS[key]
    from risingwave_tpu.connectors.nexmark import (NexmarkConfig,
                                                   NexmarkGenerator)
    from risingwave_tpu.device import fuse_planner
    chunk, polls, events, capacity = CADENCES[cadence]
    armed.setattr(fuse_planner, "EPOCH_POLLS", polls)
    db = Database(device=DeviceConfig(capacity=capacity, mv_persist_every=64),
                  checkpoint_frequency=8)
    db._nexmark_gen = NexmarkGenerator(NexmarkConfig(seed=seed))
    for sql in CODE.SOURCES:
        db.run(sql.format(events=events, chunk=chunk))
    db.run(CODE.MV_SQL)
    job = db.catalog.get(CODE.MV).runtime["fused_job"]
    assert job is not None and job.program.epoch_events == chunk * polls
    _drain(db, job)
    rows = CODE.normalise(db.query(CODE.READ_SQL))
    spans = [s for s in SPANS if s.get("inst") == job.profiler.instance]
    _RUNS[key] = rows, job, spans
    return _RUNS[key]


@pytest.mark.parametrize("seed", SEEDS)
def test_equals_the_frozen_reference(armed, seed):
    """Epoch borders on the slides' borders; `test_the_rehearsal_...` is
    the same comparison with the borders inside an epoch."""
    rows, job, _ = _drive(armed, seed, "aligned")
    events = CADENCES["aligned"][2]
    assert job.counter == job.committed == events
    assert job.growth_replays == 0 and job.recoveries == 0
    assert job.program.mesh is None
    kinds = collections.Counter(type(n).__name__ for n in job.program.nodes)
    assert kinds["SourceNode"] == 1 and kinds["HopNode"] == 2 \
        and kinds["PrecombineNode"] == 2 and kinds["AggNode"] == 3 \
        and kinds["JoinNode"] == 1, \
        "one source, the hop-to-count chain twice, the max agg, the join"
    want = CODE.reference(seed, events)
    c = CODE.counts(seed, events, job.program.epoch_events)
    # 4 s of bids lie in the windows that start 8 s before them and later
    assert len(want) >= c["windows"] >= 6, "a hottest auction a window"
    assert collections.Counter(rows) == collections.Counter(want)
    assert c["groups_met_again"] > 0 and c["windows_max_changed"] > 0


@pytest.mark.parametrize("seed", SEEDS)
def test_the_frozen_reference_is_the_programs_at_this_commit(seed):
    """The reference against the program's generator through its host
    executors (`device="off"`: no fused job, no device state) on the same
    events, and its SQL against `bench.py`'s: the yardstick starts out as
    the program's answer, and only a change to the program can part them."""
    import bench
    from risingwave_tpu.connectors.nexmark import (NexmarkConfig,
                                                   NexmarkGenerator)
    events = 40_000
    db = Database(device="off")
    db._nexmark_gen = NexmarkGenerator(NexmarkConfig(seed=seed))
    for sql in CODE.SOURCES:
        db.run(sql.format(events=events, chunk=1000))
    db.run(CODE.MV_SQL)
    assert db.catalog.get(CODE.MV).runtime.get("fused_job") is None
    _drain(db)
    rows = CODE.normalise(db.query(CODE.READ_SQL))
    assert collections.Counter(rows) == collections.Counter(
        CODE.reference(seed, events))
    assert CODE.MV_SQL == bench.Q5_MV
    assert CODE.SOURCES[0].format(events=7, chunk=3).split() \
        == bench.BID_SRC.format(n=7, c=3).split()


@pytest.mark.parametrize("seed", SEEDS)
def test_the_control_is_not_correct(seed):
    """Counts are not idempotent: the last epoch applied twice moves a
    window's largest count, so rows are missing AND unexpected."""
    events, epoch = REHEARSAL
    want = collections.Counter(CODE.reference(seed, events))
    got = collections.Counter(CODE.control(seed, events, epoch))
    assert want - got and got - want


def test_the_rehearsal_of_the_cell_is_correct(armed):
    """`benchmarks/run.py --workload q5.device --rehearse`, in process:
    40 epochs of 1,024 events, the slides' borders (events 20,000 and
    40,000) inside an epoch, against the frozen reference."""
    import discover
    import run
    from risingwave_tpu.device import fuse_planner
    armed.setattr(fuse_planner, "EPOCH_POLLS", 64)   # the files' cadence
    cell = discover.Cell("q5.device")
    own = [m["name"] for m, _ in cell.metrics("per_layer")
           if "workloads" in m]
    assert own == ["join_fill_pct", "pairs_fill_pct", "slots_used_pct"]
    # the stream is 40 epochs whatever the seconds, which only stop the
    # admission: 22 s alone here, over 30 s beside five busy test workers
    result = run.run_cell(cell, SEEDS[1], 120.0, trace=True, rehearse=True)
    assert result["correct"], result["compared"]
    assert result["rehearsal"] and result["metrics"] == {}
    assert result["counts"] == {"events_committed": 40_960, "epochs": 40,
                                "checkpoints": 6, "growth_replays": 0,
                                "window_compiles": 0}
    assert set(own) <= set(result["metric_names"])


def test_flow_report_equals_the_references_counts(armed):
    """`flow_report()` against `counts`, to the row: the hop makes five
    rows a bid, both count aggs and the join's side `a` hold the distinct
    (window, auction) groups, the max agg's `main` the windows and its
    multiset the distinct (window, count) pairs, the join is handed the
    change rows the reference counts, and `slots` names every slot."""
    seed = SEEDS[0]
    rows, job, spans = _drive(armed, seed, "aligned")
    _, _, events, capacity = CADENCES["aligned"]
    epoch = job.program.epoch_events
    c = CODE.counts(seed, events, epoch)
    report = job.flow_report()
    assert report["events"] == events and report["epoch_events"] == epoch
    nodes = report["nodes"]
    assert [n["node"] for n in nodes] == STEPS
    source, hop, hop2 = nodes[0], nodes[1], nodes[6]
    assert source["rows_out"] == c["bids"] == hop["rows_in"]
    assert hop["rows_out"] == hop2["rows_out"] == c["expanded_rows"] \
        == 5 * c["bids"]
    assert nodes[2]["lanes"] == 5 * hop["lanes"], "five lanes a lane"
    for agg in (nodes[4], nodes[9]):
        assert agg["rows_in"] == c["expanded_rows"]
        assert agg["slots"] == {"main": {"live": c["groups"],
                                         "capacity": capacity}}
        # a new group one row, a group met again a retraction and a row
        assert agg["rows_out"] == c["groups"] + 2 * c["groups_met_again"]
    top = nodes[11]
    assert top["slots"]["main"] == {"live": c["windows"],
                                    "capacity": capacity}
    assert top["slots"]["ms0"]["capacity"] == capacity
    assert top["slots"]["ms0"]["live"] >= c["window_count_pairs"] > 100
    assert top["rows_out"] == c["windows"] + 2 * c["windows_max_changed"]
    join = nodes[13]
    assert join["rows_in"] == c["join_change_rows"] \
        == nodes[4]["rows_out"] + top["rows_out"]
    assert join["slots"]["a"] == {"live": c["groups"], "capacity": capacity}
    assert join["slots"]["b"] == {"live": c["windows"], "capacity": capacity}
    assert join["slots"]["pairs"] == {"live": join["need_pairs"],
                                      "capacity": join["pairs"]}
    assert (join["live"], join["capacity"]) == (c["groups"], capacity)
    assert 0 < join["need_pairs"] <= join["pairs"] == 4 * capacity
    # an agg hands on a change set of twice its lanes or its slots,
    # whichever is less, whatever it holds; the join is handed both
    assert join["lanes"] == 2 * min(nodes[4]["lanes"], capacity) \
        + 2 * min(top["lanes"], capacity)
    mv = nodes[15]
    assert mv["slots"]["main"]["live"] >= len(rows) == c["mv_changes"]
    assert "slots" not in source and "slots" not in hop
    gauges = [s for s in spans if s["name"] == "rw:commit.gauges"]
    assert gauges and gauges[-1]["flow_report"] == report


def test_a_step_says_what_it_fans_out_and_what_it_keeps(armed):
    """The `rw:step` span of a hop says `fanout`, of an agg with multisets
    `minputs`; the two hop-to-count chains share their names (and with
    them their programs)."""
    _, job, spans = _drive(armed, SEEDS[0], "aligned")
    assert job.program.node_names == STEPS and len(set(STEPS)) == 13
    steps = [s for s in spans if s["name"] == "rw:step"]
    assert {(s["i"], s["node"]) for s in steps} == set(enumerate(STEPS))
    assert {s["i"]: s["fanout"] for s in steps if "fanout" in s} \
        == {1: 5, 6: 5}
    assert {s["i"]: s["minputs"] for s in steps if "minputs" in s} \
        == {11: 1}
    assert {s["i"] for s in steps if "recombine" in s} == {4, 9}


def test_a_preset_too_small_grows_and_still_matches(armed):
    """1,024 slots where 10,000 events make some 4,000 groups: the job
    grows by replay (what the configuration's preset goes around, ROADMAP
    R3) and the MV is the reference's all the same."""
    seed = SEEDS[1]
    rows, job, _ = _drive(armed, seed, "grows")
    _, _, events, capacity = CADENCES["grows"]
    assert job.counter == job.committed == events
    assert job.growth_replays >= 1
    groups = CODE.counts(seed, events, job.program.epoch_events)["groups"]
    slots = {n["node"]: n["slots"] for n in job.flow_report()["nodes"]
             if "slots" in n}
    assert slots["agg_k0_1_count"]["main"]["live"] == groups > capacity
    assert slots["agg_k0_1_count"]["main"]["capacity"] >= groups
    assert collections.Counter(rows) == collections.Counter(
        CODE.reference(seed, events))


def _searched_probe(side, qjk, qmask, m):
    """The plain reference of `join_step.probe`: three binary searches —
    where a probe row's key starts in the side, where its run ends, and for
    every pair slot how many running match counts are <= its number."""
    import jax.numpy as jnp
    from risingwave_tpu.device.sorted_state import EMPTY_KEY
    qjk = jnp.where(qmask, qjk, EMPTY_KEY)
    lo = jnp.searchsorted(side.jk, qjk, side="left")
    hi = jnp.searchsorted(side.jk, qjk, side="right")
    off = jnp.cumsum(jnp.where(qmask & (qjk != EMPTY_KEY), hi - lo, 0)
                     .astype(jnp.int64))
    t = jnp.arange(m)
    row = jnp.clip(jnp.searchsorted(off, t, side="right"),
                   0, qjk.shape[0] - 1)
    prev = jnp.where(row > 0, off[row - 1], 0)
    sidx = jnp.clip(lo[row] + (t - prev), 0, side.jk.shape[0] - 1)
    return row, sidx, t < off[-1], off[-1]


def _probe_is_the_searched_one(jk, qjk, qmask, m):
    """`probe` against the reference on every lane of `row`, `sidx`, `mask`
    and of `total`; returns `total`."""
    import jax.numpy as jnp
    import numpy as np
    from risingwave_tpu.device.join_step import JoinSide, probe
    side = JoinSide(jnp.asarray(jk, jnp.int64),
                    jnp.arange(len(jk), dtype=jnp.int64),
                    jnp.int32(0), ())      # `count` is not read by a probe
    qjk, qmask = jnp.asarray(qjk, jnp.int64), jnp.asarray(qmask, bool)
    searched = _searched_probe(side, qjk, qmask, m)
    ours = probe(side, qjk, qmask, m)
    for s, o in zip(searched, ours):
        assert np.array_equal(np.asarray(s), np.asarray(o))
    return int(ours[3])


@pytest.mark.parametrize("m", [16, 64, 512])
def test_the_counted_expansion_is_the_searched_one(m):
    """`join_step.probe` (every probe row marks the pair slot its running
    match count names, a prefix sum spreads the marks; a key's run ends
    where the side says it does) gives what three searches give on every
    lane — the lanes past the matches and a buffer the matches overflow
    included."""
    import numpy as np
    from risingwave_tpu.device.sorted_state import EMPTY_KEY
    rng = np.random.default_rng(m)
    overflowed = 0
    for _ in range(8):
        live = int(rng.integers(0, 65))
        jk = np.concatenate([np.sort(rng.integers(0, 12, live)),
                             np.full(64 - live, EMPTY_KEY)])
        total = _probe_is_the_searched_one(
            jk, np.sort(rng.integers(0, 14, 32)), rng.random(32) < 0.7, m)
        overflowed += total > m
    assert overflowed or m == 512


def _range_end_cases():
    import numpy as np
    from risingwave_tpu.device.sorted_state import EMPTY_KEY
    pads = lambda n: np.full(n, EMPTY_KEY)
    some = np.concatenate([np.repeat([2, 5, 5, 9], [3, 1, 4, 2]), pads(6)])
    q = np.array([0, 2, 2, 5, 7, 9, 9, 11])
    yes = np.ones(8, bool)
    return {   # name: (side keys, query keys, query mask, m, matches)
        "empty_side": (pads(16), q, yes, 32, 0),
        "full_side_no_pads": (np.repeat([1, 2, 5, 9], 4), q, yes, 64, 20),
        "one_key_fills_the_side": (np.full(16, 5), q, yes, 64, 16),
        "every_query_absent": (some, np.array([0, 1, 3, 4, 6, 7, 8, 10]),
                               yes, 32, 0),
        "every_query_masked": (some, q, ~yes, 32, 0),
        "a_query_is_the_sides_last_key": (
            np.repeat([1, 2, 5, 9], 4), np.array([9] * 8), yes, 64, 32),
        "a_query_is_the_last_live_key": (some, np.array([9] * 8), yes, 32,
                                         16),
        "a_query_is_the_pad_key": (some, np.concatenate([q[:7], pads(1)]),
                                   yes, 32, 15),
        "m_smaller_than_the_matches": (some, q, yes, 8, 15),
        "a_side_of_one_slot": (np.array([5]), q, yes, 8, 1),
    }


@pytest.mark.parametrize("case", sorted(_range_end_cases()))
def test_a_match_range_ends_where_the_sides_run_ends(case):
    """The probe reads where a key's run ends off the side (one pass over
    the capacity, one gather a probe row) where the reference searches:
    equal on every lane at the edges of the side and of the pair buffer."""
    jk, qjk, qmask, m, matches = _range_end_cases()[case]
    assert _probe_is_the_searched_one(jk, qjk, qmask, m) == matches
