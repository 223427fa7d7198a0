"""`run.sizes`: the traffic file's fields win over the configuration's, and a
rehearsal takes each file's `rehearse` block, never a real size of the
traffic's."""
import json
import os
import types

import pytest

import discover
import run

CFG = {"events": 8_388_608, "checkpoint_frequency": 8,
       "device": {"capacity": 1_048_576, "mv_persist_every": 64},
       "rehearse": {"events": 65_536,
                    "device": {"capacity": 4_096, "mv_persist_every": 64}}}
PLAIN = {"name": "plain", "chunk_size": 16_384, "epoch_events": 1_048_576,
         "device": {"host_ingest": True},
         "rehearse": {"chunk_size": 128, "epoch_events": 8_192}}
SIZED = {"name": "sized", "chunk_size": 16_384, "epoch_events": 1_048_576,
         "events": 33_554_432,
         "device": {"host_ingest": True, "capacity": 4_194_304},
         "rehearse": {"chunk_size": 128, "epoch_events": 8_192,
                      "events": 262_144, "device": {"capacity": 32_768}}}


def _cell(traffic):
    return types.SimpleNamespace(config=CFG, traffic=traffic)


@pytest.mark.parametrize("traffic,rehearse,want", [
    # a traffic `events` and `capacity` override the configuration's
    (SIZED, False, {"events": 33_554_432, "chunk": 16_384,
                    "epoch_events": 1_048_576, "checkpoint_frequency": 8,
                    "device": {"capacity": 4_194_304, "mv_persist_every": 64,
                               "host_ingest": True}}),
    # rehearsing, the traffic's rehearse sizes win over everything
    (SIZED, True, {"events": 262_144, "chunk": 128, "epoch_events": 8_192,
                   "checkpoint_frequency": 8,
                   "device": {"capacity": 32_768, "mv_persist_every": 64,
                              "host_ingest": True}}),
    # a traffic file without `events` gives the configuration's
    (PLAIN, False, {"events": 8_388_608, "chunk": 16_384,
                    "epoch_events": 1_048_576, "checkpoint_frequency": 8,
                    "device": {"capacity": 1_048_576, "mv_persist_every": 64,
                               "host_ingest": True}}),
    (PLAIN, True, {"events": 65_536, "chunk": 128, "epoch_events": 8_192,
                   "checkpoint_frequency": 8,
                   "device": {"capacity": 4_096, "mv_persist_every": 64,
                              "host_ingest": True}}),
], ids=["sized", "sized-rehearse", "plain", "plain-rehearse"])
def test_sizes(traffic, rehearse, want):
    assert run.sizes(_cell(traffic), rehearse) == want


@pytest.mark.parametrize("key", ["events", "chunk_size", "capacity"])
def test_a_real_size_never_reaches_a_rehearsal(key):
    small = json.loads(json.dumps(SIZED["rehearse"]))
    del (small["device"] if key == "capacity" else small)[key]
    with pytest.raises(SystemExit, match=key):
        run.sizes(_cell({**SIZED, "rehearse": small}), True)


def _workloads():
    spec = discover.load_json(os.path.join(discover.ROOT, "BENCHMARK.json"))
    return [w["name"] for w in spec["workloads"]]


@pytest.mark.parametrize("workload", _workloads())
@pytest.mark.parametrize("rehearse", [False, True])
def test_every_cell_sizes_from_its_files(workload, rehearse):
    """A cell's events come from its traffic file where that names them and
    from its configuration otherwise; its capacity likewise."""
    cell = discover.Cell(workload)
    cfg, tr = cell.config, cell.traffic
    if rehearse:
        cfg, tr = cfg["rehearse"], tr["rehearse"]
    sz = run.sizes(cell, rehearse)
    assert sz["events"] == tr.get("events", cfg.get("events",
                                                    cell.config["events"]))
    assert sz["device"]["capacity"] == tr.get("device", {}).get(
        "capacity", cfg["device"]["capacity"])
    assert sz["epoch_events"] == tr["epoch_events"]


def test_rehearse_size_of_hostfed28_fills_as_the_real_one():
    """At the rehearse size of `hostfed-28x1m` the reference and `counts`
    agree on the group count, and it fills the rehearse capacity about as
    the full stream's 1,760,350 groups fill 2^22 slots (42 %, seed 7)."""
    cell = discover.Cell("bid-agg.hostfed28")
    sz, code = run.sizes(cell, True), cell.config_code
    groups = len(code.reference(7, sz["events"]))
    assert code.counts(7, sz["events"], sz["events"])["groups_touched"] \
        == groups
    assert abs(groups / sz["device"]["capacity"]
               - 1_760_350 / 4_194_304) < 0.02
