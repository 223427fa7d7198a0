"""The six set-up readers of PR 36 by hand on the CPU, over a recorded span
list of one run's set-up (process start, two CREATEs, a set-up pass of two
barriers, the drain, the CREATEs again, the window's first barrier) in three
states of the machine: warm (every program read from the persistent cache),
one program built (a seed not seen: the source), and one executable lost (the
manifest knew the join, the cache no longer held it). And over the same
list as the parent of PR 36 records it — `rw:compile` without a word of what
jax did, no `rw:boot` — where each reader has nothing to read and says so by
leaving its metric out. Every cell reports the six (no `workloads` list).
"""
import pytest

import discover
import spans

OWN = ["setup_boot_s", "setup_compiles", "setup_compile_s",
       "setup_cache_load_s", "setup_cache_lost", "setup_span_coverage_pct"]
MS = 1_000_000
# per node of a q7-like job: (node, backend seconds of a build, seconds of
# a cache read)
PROGRAMS = [("source_bid", 27.5, 0.4), ("agg_k0_max1", 61.0, 1.1),
            ("join_l2_r0", 126.0, 3.2)]


def recorded(built=(), lost=(), says=True):
    """The set-up's spans, times in ms on thread 1 (workers on 2): the
    programs named in `built` were compiled, the rest read from the cache;
    those in `lost` the manifest had promised. `says=False`: as a program
    before PR 36 records the same run."""
    out, ids = [], iter(range(1, 1000))

    def add(name, t0, t1, parent=None, thread=1, **kw):
        out.append({"id": next(ids), "parent": parent, "name": name,
                    "t0": t0 * MS, "t1": t1 * MS, "thread": thread,
                    "tname": f"t{thread}", **kw})
        return out[-1]["id"]

    if says:
        boot = add("rw:boot", 0, 4000)
        add("rw:boot.start", 0, 100, boot)
        add("rw:boot.import", 100, 4000, boot)
        add("rw:boot.backend", 9000, 9010, platform="tpu", devices=1)
    add("rw:sql", 10_000, 10_010, kind="create_source")
    q = add("rw:sql", 10_010, 11_000, kind="create_mv")
    add("rw:sql.fuse_plan", 10_020, 10_900, q)
    if says:       # an eager primitive under the CREATE: never a program
        add("rw:compile.inline", 10_950, 10_990, q, fun_name="jit(iota)",
            persistent="miss", backend_compile_s=0.03)
    t = 11_000
    for node, build_s, load_s in PROGRAMS:
        jax_did = {}
        if says:
            jax_did = {"persistent": "miss", "backend_compile_s": build_s} \
                if node in built else {"persistent": "hit",
                                       "backend_compile_s": load_s + 0.01,
                                       "retrieval_s": load_s}
            if node in lost:
                jax_did["lost"] = True
        add("rw:compile", t, t + 500, thread=2, job="mv", inst=1, node=node,
            cache_hit=node not in built or node in lost, ok=True, **jax_did)
        t += 500
    for t0, t1 in ((11_000, 14_000), (14_000, 20_000)):
        b = add("rw:barrier", t0, t1, epoch=t0)
        e = add("rw:epoch", t0, t1, b, job="mv", inst=1, epoch=t0)
        d = add("rw:dispatch", t0, t1 - 100, e, job="mv", inst=1)
        s = add("rw:step", t0, t1 - 100, d, job="mv", inst=1)
        add("rw:compile_wait", t0 + 100, t1 - 200, s, job="mv", inst=1)
    if says:
        add("rw:compile_drain", 20_500, 20_510)
    add("rw:sql", 21_000, 21_010, kind="create_source")
    add("rw:sql", 21_010, 22_000, kind="create_mv")
    b = add("rw:barrier", 24_000, 30_000, epoch=24_000)      # the window
    e = add("rw:epoch", 24_000, 30_000, b, job="mv", inst=2)
    add("rw:dispatch", 24_000, 29_000, e, job="mv", inst=2)
    # a program the window built would not be the set-up's
    if says:
        add("rw:compile", 25_000, 26_000, thread=2, job="mv", inst=2,
            node="late", persistent="miss", backend_compile_s=9.0, lost=True)
    return out


def readers():
    cell = discover.Cell("q7.device")
    return {m["name"]: r for m, r in cell.metrics("per_layer")
            if m["name"] in OWN}


def read(monkeypatch, ring):
    monkeypatch.setattr(spans, "ring", lambda: ring)
    return {name: r.read({}) for name, r in readers().items()}


# leaves of thread 1 in [0, 24,000): boot 100 + 3,900, backend 10, the first
# CREATE 10, its plan 880 and the eager compile 40, the waits 2,700 + 5,700,
# the drain 10, the CREATEs again 10 + 990
COVERED_MS = 100 + 3900 + 10 + 10 + 880 + 40 + 2700 + 5700 + 10 + 10 + 990


def test_every_cell_reports_the_six():
    bench = discover.Cell("q7.device").bench
    for cell in (w["name"] for w in bench["workloads"]):
        names = [m["name"] for m, _ in discover.Cell(cell).metrics("per_layer")]
        assert [n for n in names if n in OWN] == OWN
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert all(entries[n]["moves"] == "setup_s" and "workloads"
               not in entries[n] and entries[n]["source"] == "program_span"
               for n in OWN)


@pytest.mark.parametrize("built, lost, want", [
    ((), (), {"setup_compiles": 0, "setup_compile_s": 0,
              "setup_cache_load_s": 0.4 + 1.1 + 3.2, "setup_cache_lost": 0}),
    (("source_bid",), (), {"setup_compiles": 1, "setup_compile_s": 27.5,
                           "setup_cache_load_s": 1.1 + 3.2,
                           "setup_cache_lost": 0}),
    (("source_bid", "join_l2_r0"), ("join_l2_r0",),
     {"setup_compiles": 2, "setup_compile_s": 27.5 + 126.0,
      "setup_cache_load_s": 1.1, "setup_cache_lost": 1}),
], ids=["warm", "one_built", "one_lost"])
def test_the_readers_over_a_recorded_set_up(monkeypatch, built, lost, want):
    got = read(monkeypatch, recorded(built, lost))
    assert list(got) == OWN
    for name, value in want.items():
        assert got[name] == pytest.approx(value), name
    # whatever jax did: the first statement began 10 s after the process,
    # and the same leaves cover the same share of the 24 s to the window
    assert got["setup_boot_s"] == 10.0
    assert got["setup_span_coverage_pct"] == pytest.approx(
        100.0 * COVERED_MS / 24_000)


def test_a_program_that_does_not_say_leaves_the_six_out(monkeypatch):
    got = read(monkeypatch, recorded(("source_bid",), says=False))
    assert got == {name: None for name in OWN}
    assert read(monkeypatch, []) == {name: None for name in OWN}
    assert read(monkeypatch, None) == {name: None for name in OWN}
