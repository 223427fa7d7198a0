"""The bid group-by at `mesh_shards=4` with the benchmark's defaults: the
deployment the cell `bid-agg.mesh4` measures on four chips, at a small size
on four of the CPU's virtual devices.

Tier-1 pins the default-on traced features off (conftest); the benchmark
runs the defaults, so this file forces them back on. The yardstick is the
benchmark's frozen numpy reference (`benchmarks/lib/nexmark_ref.py`) and the
configuration's own `counts` (`benchmarks/configs/nexmark-bid-agg-p4.py`),
neither of which imports the program.
"""
import collections
import importlib.util
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the configuration's .py imports `nexmark_ref` from the benchmark's lib
sys.path.insert(0, os.path.join(ROOT, "benchmarks", "lib"))

from risingwave_tpu.config import DeviceConfig  # noqa: E402
from risingwave_tpu.core.vnode import VNODE_COUNT  # noqa: E402
from risingwave_tpu.parallel.mesh import shard_of_vnode  # noqa: E402
from risingwave_tpu.sql import Database  # noqa: E402
from risingwave_tpu.utils.profile import SPANS  # noqa: E402

pytestmark = pytest.mark.mesh

SHARDS = 4
CAPACITY = 1024
ARMED = ("RW_SKEW_STATS", "RW_FLOW_STATS", "RW_AGG_PRECOMBINE",
         "RW_STATE_TIERING")
# (events a poll, polls an epoch, events: four epochs): 64 polls of 32 events
# divide by 4 shards, 65 polls of 31 (2,015 events) do not: the last shard's
# block is padded
CADENCES = {"divides": (32, 64, 8192), "padded": (31, 65, 8060)}
SEEDS = (1, 2**31 + 5)


def _config_code():
    """The configuration's .py, loaded as the benchmark's runner loads it."""
    path = os.path.join(ROOT, "benchmarks", "configs", "nexmark-bid-agg-p4.py")
    spec = importlib.util.spec_from_file_location("bench_config_p4", path)
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


def _drive(armed, seed, cadence, shards=SHARDS):
    """One drained run of the cell's statements (cached per module):
    (MV rows, job, the job's spans)."""
    key = (seed, cadence, shards)
    if key in _RUNS:
        return _RUNS[key]
    from risingwave_tpu.connectors.nexmark import (NexmarkConfig,
                                                   NexmarkGenerator)
    from risingwave_tpu.device import fuse_planner
    chunk, polls, events = CADENCES[cadence]
    armed.setattr(fuse_planner, "EPOCH_POLLS", polls)
    db = Database(device=DeviceConfig(capacity=CAPACITY, mesh_shards=shards,
                                      mv_persist_every=64),
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
    assert job.program.mesh is not None \
        and job.program.mesh.devices.size == SHARDS
    assert job.counter == job.committed == CADENCES[cadence][2]
    assert job.growth_replays == 0 and job.recoveries == 0
    assert any(type(n).__name__ == "PrecombineNode"
               for n in job.program.nodes), "the defaults pre-combine"
    want = CODE.reference(seed, job.counter)
    assert len(want) > 100
    assert collections.Counter(rows) == collections.Counter(want)


def _keyed_state_keys(job):
    """{node index: [live packed keys of shard s, ...]} of the keyed nodes,
    read from the device state."""
    from risingwave_tpu.device.sorted_state import EMPTY_KEY
    out = {}
    for k in job.shard_report()["keyed"]:
        st = job.states[k["i"]]
        st = getattr(st, "inner", st)           # the state tier's wrapper
        keys = np.asarray(getattr(st, "main", st).keys)
        assert keys.shape[0] == SHARDS
        out[k["i"]] = [row[row != EMPTY_KEY] for row in keys]
    return out


@pytest.mark.parametrize("cadence", sorted(CADENCES))
def test_the_shares_add_up(armed, cadence):
    """Per-shard live groups sum to the reference's group count, every key
    sits on the shard that owns its vnode, and no key is on two."""
    seed = SEEDS[0]
    rows, job, _ = _drive(armed, seed, cadence)
    groups = len(CODE.reference(seed, CADENCES[cadence][2]))
    report = job.shard_report()
    assert report["shards"] == SHARDS and report["rebalances"] == 0
    assert [type(job.program.nodes[k["i"]]).__name__
            for k in report["keyed"]] == ["AggNode", "MVKeyedNode"]
    state = _keyed_state_keys(job)
    for k in report["keyed"]:
        assert sum(k["live"]) == groups
        per_shard = state[k["i"]]
        assert [len(x) for x in per_shard] == k["live"]
        every = np.concatenate(per_shard)
        assert len(np.unique(every)) == len(every) == groups
        for s, keys in enumerate(per_shard):
            owner = shard_of_vnode(
                compute_vnodes_of(keys), SHARDS, VNODE_COUNT)
            assert (owner == s).all()
    # the same rows through the SQL surface
    live = {(node, shard): value
            for node, _type, metric, shard, _key, value, _share
            in job.skew_report() if metric == "shard_live"}
    assert live == {(k["i"], s): v for k in report["keyed"]
                    for s, v in enumerate(k["live"])}


def compute_vnodes_of(keys):
    """Vnode of packed int64 keys: CRC32 of the 8 big-endian bytes, as the
    host-side `compute_vnodes` has it for one int64 column."""
    import zlib
    return np.array([zlib.crc32(int(k).to_bytes(8, "big", signed=True))
                     % VNODE_COUNT for k in keys], dtype=np.int64)


@pytest.mark.parametrize("cadence", sorted(CADENCES))
def test_exchange_spans_and_rows_in(armed, cadence):
    """One `rw:exchange` span an exchange stage and epoch, inside
    `rw:dispatch`, with the stage's sizes; the rows the shards received are
    the pre-combined rows the configuration's `counts` reckons."""
    seed = SEEDS[1]
    _, job, spans = _drive(armed, seed, cadence)
    chunk, polls, events = CADENCES[cadence]
    epochs = events // (chunk * polls)
    agg = next(i for i, n in enumerate(job.program.nodes)
               if type(n).__name__ == "AggNode")
    exch = job.program.nodes[agg].exch
    by_id = {s["id"]: s for s in spans}
    exchange = [s for s in spans if s["name"] == "rw:exchange"]
    assert len(exchange) == epochs
    for s in exchange:
        assert (s["node"], s["xi"], s["shards"], s["exch"],
                s["rows_slots"]) == (job.program.node_names[agg], 0,
                                     SHARDS, exch, SHARDS * exch)
        outer = by_id[s["parent"]]
        assert outer["name"] == "rw:dispatch"
        assert outer["t0"] <= s["t0"] and s["t1"] <= outer["t1"]
    (stage,) = job.shard_report()["exchanges"]
    assert (stage["i"], stage["xi"], stage["exch"], stage["slots"]) \
        == (agg, 0, exch, SHARDS * exch)
    pre = next(i for i, n in enumerate(job.program.nodes)
               if type(n).__name__ == "PrecombineNode")
    sent = job.program.node_stats(pre, job._stat_totals)["rows_out"]
    assert sum(stage["rows_in"]) == sent
    counts = CODE.counts(seed, events, chunk * polls)
    assert sent == counts["exchange_rows"]
    assert counts["exchange_rows_fullest"] * SHARDS >= sent
    # the last checkpoint left the same report on its span
    gauges = [s for s in spans if s["name"] == "rw:commit.gauges"]
    assert gauges[-1]["shard_report"] == job.shard_report()


def _lowered(idx, program):
    """Node `idx`'s one-chip step as the compile service lowers it, with
    the operations' name stacks in the text."""
    from risingwave_tpu.device.compile_service import abstract_program_avals
    from risingwave_tpu.device.fused import _jit_step
    node = program.nodes[idx]
    sds = abstract_program_avals(program.nodes, program.epoch_events,
                                 None)[idx]
    return _jit_step(node).lower(
        *sds, node=node, epoch_events=program.epoch_events,
        salt=node._mut_sig()).as_text(debug_info=True)


def _planned(shards):
    """The cell's statements planned at `mesh_shards=shards`, nothing run:
    the fused job."""
    db = Database(device=DeviceConfig(capacity=CAPACITY, mesh_shards=shards,
                                      aot_compile=False))
    for sql in CODE.SOURCES:
        db.run(sql.format(events=8192, chunk=32))
    db.run(CODE.MV_SQL)
    return db.catalog.get(CODE.MV).runtime["fused_job"]


def test_one_chip_programs_carry_nothing_of_the_mesh(armed):
    """`mesh_shards=1`: no exchange scope in the agg, pre-combine and MV
    steps, no per-shard stat in their layout, no shard report."""
    job = _planned(1)
    assert job.program.mesh is None and job.shard_report() is None
    seen = set()
    for i, node in enumerate(job.program.nodes):
        kind = type(node).__name__
        assert not node.shard_live and node.exch is None
        assert not [s for s in node.stat_names
                    if s.startswith(("live", "xin")) or s == "exch"]
        if kind in ("AggNode", "PrecombineNode", "MVKeyedNode"):
            text = _lowered(i, job.program)
            if kind == "AggNode":       # the text does carry scopes
                assert "agg.merge" in text
            assert "exchange." not in text and "all_to_all" not in text
            seen.add(kind)
    assert seen == {"AggNode", "PrecombineNode", "MVKeyedNode"}


def _agg_step_jaxpr(shards):
    """The deployment's agg step as planned at `mesh_shards=shards`
    (`shard_map` over four devices, a received delta of `shards * exch`
    rows a shard; or the one-chip step), the benchmark's defaults armed:
    (node, jaxpr)."""
    import jax
    from risingwave_tpu.device.compile_service import abstract_program_avals
    from risingwave_tpu.device.fused import _jit_step
    from risingwave_tpu.device.shard_exec import sharded_jit_step
    program = _planned(shards).program
    assert (program.mesh is not None) == (shards > 1)
    idx, node = next((i, n) for i, n in enumerate(program.nodes)
                     if type(n).__name__ == "AggNode")
    assert node.combined and node.tier and node.capacity == CAPACITY
    sds = abstract_program_avals(program.nodes, program.epoch_events,
                                 program.mesh)[idx]
    step = _jit_step(node) if program.mesh is None \
        else sharded_jit_step(program.mesh, node)
    jaxpr = jax.make_jaxpr(lambda *a: step(
        *a, node=node, epoch_events=program.epoch_events,
        salt=node._mut_sig()))(*sds).jaxpr
    assert ("shard_map" in str(jaxpr)) == (shards > 1)
    return node, jaxpr


@pytest.mark.parametrize("shards", [SHARDS, 1])
def test_agg_step_searches_its_state_for_no_key(armed, shards, jaxpr_loops):
    """The agg step of the deployment, sharded and on one chip: no `scan`
    / `while` of it reads, carries or produces an array of `capacity` rows
    or more. The change set is read off the merge by position
    (`sorted_state.merge_changes`), not looked up by key."""
    _node, jaxpr = _agg_step_jaxpr(shards)
    _loops, loops_over = jaxpr_loops
    assert loops_over(jaxpr, CAPACITY) == []


# ---- reduce once (ISSUE 31): the agg step re-combines its pre-combined
# delta only behind an exchange; on one chip it takes it as it is ----------

def _agg(job):
    idx = next(i for i, n in enumerate(job.program.nodes)
               if type(n).__name__ == "AggNode")
    return idx, job.program.nodes[idx]


def _agg_state_rows(job, idx):
    """The agg's live state rows, every shard's together, sorted by key:
    (keys, [payload column...])."""
    from risingwave_tpu.device.sorted_state import EMPTY_KEY
    st = job.states[idx]
    st = getattr(st, "inner", st).main          # the state tier's wrapper
    keys = np.asarray(st.keys).reshape(-1)
    live = keys != EMPTY_KEY
    order = np.argsort(keys[live], kind="stable")
    return keys[live][order], [np.asarray(v).reshape(-1)[live][order]
                               for v in st.vals]


def _step_spans(spans, idx):
    return [s for s in spans if s["name"] == "rw:step" and s["i"] == idx]


@pytest.mark.parametrize("cadence", sorted(CADENCES))
def test_partials_from_two_source_shards_are_one_state_row(armed, cadence):
    """Behind the exchange a key arrives once from each source shard that
    saw it: the agg step still combines the partials to one state row
    (`recombine`; the pass-through taken here would merge a delta with a
    key twice and fail every line below)."""
    seed = SEEDS[0]
    _, job, spans = _drive(armed, seed, cadence)
    chunk, polls, events = CADENCES[cadence]
    counts = CODE.counts(seed, events, chunk * polls)
    # some key left two source shards in one epoch
    assert counts["exchange_rows"] > counts["groups_touched"]
    idx, node = _agg(job)
    assert node.combined and node.exch is not None and node.recombine
    keys, vals = _agg_state_rows(job, idx)
    want = CODE.reference(seed, events)
    assert len(np.unique(keys)) == len(keys) == len(want)
    by_count = sorted(c for _a, c, _s, _m in want)
    assert sorted(vals[0].tolist()) == by_count        # row_count a group
    assert vals[0].sum() == counts["bids"]
    stats = job.program.node_stats(idx, job._stat_totals)
    assert stats["rows_in"] == counts["bids"]
    steps = _step_spans(spans, idx)
    assert steps and all(s["recombine"] is True for s in steps)
    assert all("recombine" not in s for s in spans
               if s["name"] == "rw:step" and s["i"] != idx)


def test_one_shard_takes_the_delta_as_it_is_and_equals_four(armed):
    """`mesh_shards=1`: no exchange, so the step passes the pre-combine's
    delta through (`recombine` false on its spans) — and the MV, the agg's
    state rows and its row-flow stats equal the four-shard run's."""
    seed, cadence = SEEDS[0], "divides"
    rows4, job4, _ = _drive(armed, seed, cadence)
    rows1, job1, spans1 = _drive(armed, seed, cadence, shards=1)
    assert job1.program.mesh is None
    idx, node = _agg(job1)
    assert node.combined and node.exch is None and not node.recombine
    steps = _step_spans(spans1, idx)
    assert steps and all(s["recombine"] is False for s in steps)
    assert collections.Counter(rows1) == collections.Counter(rows4)
    k1, v1 = _agg_state_rows(job1, idx)
    k4, v4 = _agg_state_rows(job4, _agg(job4)[0])
    assert (k1 == k4).all() and len(v1) == len(v4)
    for a, b in zip(v1, v4):
        assert a.dtype == b.dtype and (a == b).all()
    for stat in ("rows_in", "rows_out"):
        assert job1.program.node_stats(idx, job1._stat_totals)[stat] \
            == job4.program.node_stats(_agg(job4)[0],
                                       job4._stat_totals)[stat]


@pytest.mark.parametrize("shards", [SHARDS, 1])
def test_reduce_stage_of_the_planned_agg_step(armed, shards,
                                              jaxpr_prims_under):
    """The deployment's agg step as planned: sharded, its reduce stage is
    `batch_reduce` (sort and scatters); on one chip it is the pass-through
    and holds neither — the node reads which off `exch`, no option."""
    node, jaxpr = _agg_step_jaxpr(shards)
    assert node.recombine == (shards > 1)
    stage = jaxpr_prims_under(jaxpr, "agg.reduce_delta")
    heavy = {p for p in stage
             if p.startswith(("sort", "scatter", "gather"))}
    if shards > 1:
        assert {"sort", "scatter", "scatter-add", "scatter-max"} <= heavy
        assert not jaxpr_prims_under(jaxpr, "passthrough")
    else:
        assert heavy == set()
        assert stage == jaxpr_prims_under(jaxpr, "passthrough") != set()


SELLERS_MV = ("CREATE MATERIALIZED VIEW sellers AS SELECT seller,"
              " count(*) AS n, max(date_time) AS last FROM auction"
              " GROUP BY seller")


def _drive_sellers(armed, seed, shards):
    """The auction stream grouped by seller at the "padded" cadence (2,015
    events an epoch, 504 a shard, the last block 3 short): (rows, job, the
    job's spans)."""
    import nexmark_ref_entities as ent
    from risingwave_tpu.connectors.nexmark import (NexmarkConfig,
                                                   NexmarkGenerator)
    from risingwave_tpu.device import fuse_planner
    chunk, polls, events = CADENCES["padded"]
    armed.setattr(fuse_planner, "EPOCH_POLLS", polls)
    db = Database(device=DeviceConfig(capacity=CAPACITY, mesh_shards=shards,
                                      mv_persist_every=64),
                  checkpoint_frequency=8)
    db._nexmark_gen = NexmarkGenerator(NexmarkConfig(seed=seed))
    db.run(ent.AUCTION_SOURCE_SQL.format(events=events, chunk=chunk))
    db.run(SELLERS_MV)
    job = db.catalog.get("sellers").runtime["fused_job"]
    assert job is not None and job.program.epoch_events == chunk * polls
    while job.counter < job.max_events or job.committed < job.counter:
        db.tick()
    job.sync()
    rows = sorted(tuple(int(v) for v in r)
                  for r in db.query("SELECT * FROM sellers"))
    return rows, job, [s for s in SPANS
                       if s.get("inst") == job.profiler.instance]


@pytest.mark.parametrize("seed", SEEDS)
def test_an_auction_rooted_group_by_on_four_shards(armed, seed):
    """A source that makes only its own table's ids, under `shard_map` at a
    cadence that does not divide by 4: every shard makes the auctions of its
    504-event block over 256 lanes, the padded tail (ids of the next epoch)
    is masked, and the MV equals the one-shard run's and the frozen
    reference's (`nexmark_ref_entities`)."""
    import nexmark_ref_entities as ent
    from risingwave_tpu.device.nexmark_gen import source_lanes
    chunk, polls, events = CADENCES["padded"]
    epoch = chunk * polls
    assert epoch % SHARDS and -(-epoch // SHARDS) == 504
    rows4, job4, spans4 = _drive_sellers(armed, seed, SHARDS)
    rows1, job1, spans1 = _drive_sellers(armed, seed, 1)
    assert job4.program.mesh.devices.size == SHARDS \
        and job1.program.mesh is None
    assert job4.growth_replays == job1.growth_replays == 0
    auction = ent.auction_columns(seed, ent.auction_event_ids(0, events))
    want = {}
    for seller, ts in zip(auction["seller"].tolist(),
                          auction["date_time"].tolist()):
        n, last = want.get(seller, (0, 0))
        want[seller] = (n + 1, max(last, ts))
    want = sorted((s, n, last) for s, (n, last) in want.items())
    assert len(want) > 100 and rows4 == rows1 == want
    # lanes: what the rule gives a 504-event block, four times; one shard
    # the epoch's; the flow counters agree on the live rows
    for job, spans, shards in ((job4, spans4, SHARDS), (job1, spans1, 1)):
        lanes = shards * source_lanes("auction", -(-epoch // shards))
        assert lanes == {SHARDS: 1024, 1: 256}[shards] < epoch
        src = job.flow_report()["nodes"][0]
        assert src["node"].startswith("chain_source_auction")
        assert src["lanes"] == lanes
        assert src["rows_out"] == len(auction["seller"])
        steps = _step_spans(spans, 0)
        assert steps and all((s["lanes"], s["of"]) == (lanes, epoch)
                             for s in steps)
