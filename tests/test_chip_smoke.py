"""chip_smoke.py: it cannot pass without a chip, and its phases are right.

(a) The script itself, in a fresh process pinned to the CPU, must exit
non-zero within seconds and must not print the `ok` line. (b) The phase
functions `main()` runs on the chip are called here in-process at tiny sizes
with "cpu" — the first rehearsal of the on-chip-measurement guide: same
SQL, same oracles, same device-path assertions, only the sizes and the
platform differ.
"""
import json
import os
import subprocess
import sys

import pytest

import chip_smoke          # conftest puts the repo root on sys.path

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

EVENTS, CAPACITY, CHUNK = 65_536, 4096, 128   # 8,192-event epochs
# the join-dense programs compile ~40 CPU programs: a quarter of the events
# in 2,048-event epochs keeps the rehearsal inside a minute
WJ_EVENTS, WJ_CHUNK = 16_384, 32


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_refuses_without_a_chip(argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "chip_smoke.py", *argv], cwd=REPO,
                       env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0, r.stdout
    lines = r.stdout.strip().splitlines()
    assert lines and lines[0].startswith("device: cpu")
    assert '"ok"' not in lines[-1]
    assert "refusing to run" in r.stderr


@pytest.fixture(scope="module")
def agg_db():
    db, rec = chip_smoke.phase_agg("cpu", EVENTS, CAPACITY, CHUNK)
    assert rec["ok"] and rec["groups"] > 1000
    json.dumps(rec)                      # the phase line must serialize
    return db


def test_phase_agg(agg_db):
    job = agg_db.catalog.get("q4").runtime["fused_job"]
    assert job.counter == EVENTS and job.recoveries == 0


def test_phase_read(agg_db):
    rec = chip_smoke.phase_read(agg_db, "cpu")
    assert rec["ok"] and rec["mv_rows"] > 1000 and rec["agg_rows"] == 1


def test_phase_window_join():
    """As main() runs it: q5 cut to fewer events in a database of its
    own, q7 + q8 together at the full count."""
    cuts = {"q5": {"events": WJ_EVENTS // 2, "compile_buckets": 0}}
    rec = chip_smoke.phase_window_join("cpu", WJ_EVENTS, CAPACITY, WJ_CHUNK,
                                       cuts=cuts)
    assert rec["ok"] and rec["cuts"] == cuts
    assert [(r["queries"], r["events"]) for r in rec["runs"]] == \
        [(["q5"], WJ_EVENTS // 2), (["q7", "q8"], WJ_EVENTS)]
    assert all(n for r in rec["runs"] for n in r["rows"].values())
    json.dumps(rec)


def test_phase_ingest():
    _, rec = chip_smoke.phase_ingest("cpu", EVENTS, CAPACITY, CHUNK)
    assert rec["ok"] and rec["ingest"]["windows"] == EVENTS // (64 * CHUNK)


def test_phase_rejects_wrong_platform(agg_db):
    """The platform argument is an assertion, not a label."""
    with pytest.raises(AssertionError):
        chip_smoke.phase_read(agg_db, "tpu")


@pytest.mark.mesh
@pytest.mark.parametrize("workload,events,chunk", [
    ("bid_groupby", EVENTS, CHUNK), ("q7", WJ_EVENTS, WJ_CHUNK)])
def test_phase_mesh_4_virtual_devices(workload, events, chunk):
    rec = chip_smoke.phase_mesh("cpu", 4, workload, events, CAPACITY, chunk)
    assert rec["ok"] and rec["phase"] == f"mesh_{workload}"
    (job4,), (job1,) = rec["x4"]["jobs"].values(), rec["x1"]["jobs"].values()
    assert len(job4["state_devices"]) == 4 and job4["mesh_shards"] == 4
    assert len(job1["state_devices"]) == 1 and job1["mesh_shards"] == 1
    json.dumps(rec)
