"""NEXmark q8 alone through `Database` with the benchmark's defaults: the
deployment the cell `q8.device` measures on one chip (`nexmark-q8`: two
sources in one job, two de-duplicating group-bys, an agg-to-agg window join,
a VARCHAR in the MV), at a small size on the CPU.

Tier-1 pins the default-on traced features off (conftest); the benchmark
runs the defaults, so this file forces them back on. The yardstick is the
benchmark's frozen numpy reference (`benchmarks/lib/nexmark_ref_entities.py`,
`benchmarks/configs/nexmark-q8.py`), which imports nothing of the program;
one test here ties the frozen streams to the program's generator at this
commit.
"""
import collections
import importlib.util
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the configuration's .py imports the frozen streams from the benchmark's lib
sys.path.insert(0, os.path.join(ROOT, "benchmarks", "lib"))

import nexmark_ref_entities as ent  # noqa: E402

from risingwave_tpu.config import DeviceConfig  # noqa: E402
from risingwave_tpu.sql import Database  # noqa: E402
from risingwave_tpu.utils.profile import SPANS  # noqa: E402

CAPACITY = 8192
ARMED = ("RW_SKEW_STATS", "RW_FLOW_STATS", "RW_AGG_PRECOMBINE",
         "RW_STATE_TIERING")
# (events a poll, polls an epoch, events). A tumbling window is 100,000
# events (10 s at 100 us an event). "cuts": epochs of 32,768, the fourth
# holds the border at event 100,000; "aligned": epochs of 25,000, the border
# is an epoch's border. Either way (seller, window) groups are met again in
# a later epoch of their window.
# "rehearsal": the benchmark's CPU rehearsal cadence (`traffic/device-1m.json`
# `rehearse`: 64 polls of 128), 16 epochs of 8,192.
CADENCES = {"cuts": (512, 64, 131_072), "aligned": (500, 50, 125_000),
            "rehearsal": (128, 64, 131_072)}
# lanes the person and the auction source make an epoch: the pow2 bucket of
# (epoch // 50 + 2) rows x 1 and x 3 (`nexmark_gen.source_lanes`); the parent
# made the epoch's events each
SOURCE_LANES = {"aligned": (512, 2048), "rehearsal": (256, 512)}
SEEDS = (1, 2**31 + 5)
STEPS = ["source_person", "hop_c6_h10000000_s10000000", "map_c0_c1_c9_c10",
         "precombine_k0_1_2_3", "agg_k0_1_2_3", "map_c0_c1_c2_c3",
         "source_auction", "hop_c5_h10000000_s10000000", "map_c7_c11_c12",
         "precombine_k0_1_2", "agg_k0_1_2", "map_c0_c1_c2",
         "join_l0_2_3_r0_1_2", "map_c0_c1_c2_c3_c4_c5_c6", "mvpair_7c"]


def _config_code():
    """The configuration's .py, loaded as the benchmark's runner loads it."""
    path = os.path.join(ROOT, "benchmarks", "configs", "nexmark-q8.py")
    spec = importlib.util.spec_from_file_location("bench_config_q8", path)
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
    chunk, polls, events = CADENCES[cadence]
    armed.setattr(fuse_planner, "EPOCH_POLLS", polls)
    db = Database(device=DeviceConfig(capacity=CAPACITY, mv_persist_every=64),
                  checkpoint_frequency=8)
    db._nexmark_gen = NexmarkGenerator(NexmarkConfig(seed=seed))
    for sql in CODE.SOURCES:
        db.run(sql.format(events=events, chunk=chunk))
    db.run(CODE.MV_SQL)
    job = db.catalog.get(CODE.MV).runtime["fused_job"]
    assert job is not None and job.program.epoch_events == chunk * polls
    while job.counter < job.max_events or job.committed < job.counter:
        db.tick()
    job.sync()
    rows = CODE.normalise(db.query(CODE.READ_SQL))
    spans = [s for s in SPANS if s.get("inst") == job.profiler.instance]
    _RUNS[key] = rows, job, spans
    return _RUNS[key]


@pytest.mark.parametrize("cadence", sorted(CADENCES))
@pytest.mark.parametrize("seed", SEEDS)
def test_equals_the_frozen_reference(armed, seed, cadence):
    rows, job, _ = _drive(armed, seed, cadence)
    events = CADENCES[cadence][2]
    assert job.counter == job.committed == events
    assert job.growth_replays == 0 and job.recoveries == 0
    assert job.program.mesh is None
    kinds = collections.Counter(type(n).__name__ for n in job.program.nodes)
    assert kinds["SourceNode"] == 2 and kinds["PrecombineNode"] == 2 \
        and kinds["AggNode"] == 2 and kinds["JoinNode"] == 1, \
        "the defaults pre-combine both group-bys of the one job"
    want = CODE.reference(seed, events)
    # both windows hold rows, and a name is a string
    assert len({w for _, _, w in want}) == 2 and len(want) > 500
    assert all(isinstance(name, str) and " " in name for _, name, _ in rows)
    assert collections.Counter(rows) == collections.Counter(want)
    # the windows' epochs met groups again: the join emitted nothing twice
    assert CODE.counts(seed, events, job.program.epoch_events)[
        "groups_met_again"] > 0


@pytest.mark.parametrize("seed", SEEDS)
def test_the_frozen_streams_are_the_programs_at_this_commit(seed):
    """The benchmark's frozen person and auction columns, names and DDLs
    against `connectors.nexmark` and `bench.py`: the yardstick starts out
    as a copy, and only a change to the program can part them."""
    import bench
    from risingwave_tpu.connectors.nexmark import (NexmarkConfig,
                                                   gen_surrogates)
    from risingwave_tpu.device.nexmark_gen import SURROGATE, decode_column
    lo, hi = 1_000_003, 1_300_007
    ids = np.arange(lo, hi, dtype=np.int64)
    kinds = ids % 50
    pid, aid = ent.person_event_ids(lo, hi), ent.auction_event_ids(lo, hi)
    assert np.array_equal(pid, ids[kinds == 0])
    assert np.array_equal(aid, ids[(kinds >= 1) & (kinds <= 3)])
    cfg = NexmarkConfig(seed=seed)
    person = gen_surrogates(cfg, "person", pid, ("id", "name", "date_time"))
    frozen = ent.person_columns(seed, pid)
    assert np.array_equal(frozen["id"], person["id"])
    assert np.array_equal(frozen["date_time"], person["date_time"])
    names = decode_column(SURROGATE["person"]["name"], person["name"])
    assert list(frozen["name"]) == list(names) and len(set(names)) == 99
    auction = gen_surrogates(cfg, "auction", aid, ("seller", "date_time"))
    frozen = ent.auction_columns(seed, aid)
    assert np.array_equal(frozen["seller"], auction["seller"])
    assert np.array_equal(frozen["date_time"], auction["date_time"])
    assert ent.PERSON_SOURCE_SQL.format(events=7, chunk=3).split() \
        == bench.PERSON_SRC.format(n=7, c=3).split()
    assert ent.AUCTION_SOURCE_SQL.format(events=7, chunk=3).split() \
        == bench.AUCTION_SRC.format(n=7, c=3).split()
    assert CODE.MV_SQL == bench.Q8_MV


@pytest.mark.parametrize("seed", SEEDS)
def test_a_lost_epoch_shows_and_a_replayed_epoch_does_not(seed):
    """Why this configuration's control is not the others': the last epoch
    applied twice leaves the same MV (both group-bys de-duplicate), the
    last epoch lost leaves rows missing and none unexpected."""
    events, epoch = 262_144, 32_768
    want = collections.Counter(CODE.reference(seed, events))
    assert collections.Counter(CODE.replayed(seed, events, epoch)) == want
    lost = collections.Counter(CODE.control(seed, events, epoch))
    assert sum((want - lost).values()) > 100 and not lost - want


@pytest.mark.parametrize("cadence", sorted(SOURCE_LANES))
def test_flow_report_reads_fill_and_live_entries(armed, cadence):
    """`flow_report()`: each source makes its own table's lanes (not the
    epoch's events) and emits 1/50 and 3/50 of the events, every step down
    to the aggs is handed its source's lanes and the join twice their sum,
    `live` is the reference's group counts, and every checkpoint of a
    one-chip job leaves the report on its `rw:commit.gauges` span."""
    seed = SEEDS[0]
    rows, job, spans = _drive(armed, seed, cadence)
    events, epoch = CADENCES[cadence][2], job.program.epoch_events
    epochs = events // epoch
    report = job.flow_report()
    assert report["events"] == events and report["epoch_events"] == epoch
    nodes = {n["node"]: n for n in report["nodes"]}
    assert [n["i"] for n in report["nodes"]] == list(range(15))
    person, auction = nodes["source_person"], nodes["source_auction"]
    assert person["kind"] == auction["kind"] == "SourceNode"
    assert (person["lanes"], auction["lanes"]) == SOURCE_LANES[cadence]
    counts = CODE.counts(seed, events, epoch)
    assert person["rows_out"] == counts["persons"] >= events // 50
    assert auction["rows_out"] == counts["auctions"] >= 3 * (events // 50)
    # three of four lanes and more hold a row (78 % and 85 %: the pow2
    # bucket's slack; the parent: 4 %)
    assert person["rows_out"] + auction["rows_out"] \
        > 0.75 * epochs * (person["lanes"] + auction["lanes"])
    for name in STEPS[1:4]:                     # hop, map, pre-combine
        assert nodes[name]["lanes"] == person["lanes"], name
    for name in STEPS[7:10]:
        assert nodes[name]["lanes"] == auction["lanes"], name
    p_agg, a_agg = nodes["agg_k0_1_2_3"], nodes["agg_k0_1_2"]
    assert (p_agg["live"], a_agg["live"]) == (counts["person_groups"],
                                              counts["auction_groups"])
    assert p_agg["capacity"] == a_agg["capacity"] == CAPACITY
    assert p_agg["rows_in"] == counts["persons"] \
        and a_agg["rows_in"] == counts["auctions"]
    join = nodes["join_l0_2_3_r0_1_2"]
    assert join["live"] == max(counts["person_groups"],
                               counts["auction_groups"])
    assert join["capacity"] == CAPACITY and join["pairs"] >= CAPACITY
    # each agg hands on a change delta of twice its input's lanes; the
    # parent's join was handed 4 x min(epoch, capacity)
    assert join["lanes"] == 2 * (person["lanes"] + auction["lanes"]) \
        < 4 * min(epoch, CAPACITY)
    assert 0 < join["need_pairs"] <= join["pairs"]
    # a group met again changes nothing downstream of its agg: the join is
    # handed each new group once
    assert join["rows_in"] == counts["person_groups"] \
        + counts["auction_groups"]
    assert join["rows_out"] == len(rows) == nodes["mvpair_7c"]["live"]
    assert "live" not in person and "need_pairs" not in p_agg
    gauges = [s for s in spans if s["name"] == "rw:commit.gauges"]
    assert len(gauges) >= 2 and all("flow_report" in s for s in gauges)
    assert gauges[-1]["flow_report"] == report
    assert "shard_report" not in gauges[-1]


def test_every_step_has_a_name_of_its_own(armed):
    _, job, spans = _drive(armed, SEEDS[0], "cuts")
    assert job.program.node_names == STEPS and len(set(STEPS)) == 15
    steps = [s for s in spans if s["name"] == "rw:step"]
    assert {(s["i"], s["node"]) for s in steps} == set(enumerate(STEPS))
    assert len(steps) == 15 * (CADENCES["cuts"][2]
                               // job.program.epoch_events)
    # a source's span says the lanes it makes, of the epoch's events
    epoch = job.program.epoch_events
    said = {s["node"]: (s["lanes"], s["of"]) for s in steps if "lanes" in s}
    assert said == {"source_person": (1024, epoch),
                    "source_auction": (2048, epoch)}


def test_the_string_decode_is_a_span(armed):
    """The MV's VARCHAR column: one `rw:commit.mirror.decode` under the
    mirror's pull and one under the SELECT's, `string_cols` 1, every row."""
    rows, _, spans = _drive(armed, SEEDS[1], "cuts")
    by_id = {s["id"]: s for s in spans}
    decode = [s for s in spans if s["name"] == "rw:commit.mirror.decode"]
    assert decode and all(s["string_cols"] == 1 for s in decode)
    assert decode[-1]["rows"] == len(rows)
    parents = [by_id.get(s["parent"], {}).get("name") for s in decode]
    assert parents.count("rw:commit.mirror.pull") == 1
    # the last is the SELECT's (after the drain), not a commit's
    assert parents[-1] != "rw:commit.mirror.pull"
