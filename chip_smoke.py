"""Chip smoke: the served fused-SQL path on one TPU chip, at the bench's sizes.

    python chip_smoke.py            # one chip: agg, read, window_join, ingest
    python chip_smoke.py --chips 4  # four chips: mesh_shards=4 vs 1, nothing else
                                    # (append bid_groupby or q7 to run one of the two)

Since PR 28 the `bid_groupby` half of `--chips 4` is covered by the
benchmark's cell `bid-agg.mesh4` (`benchmarks/`, the same group-by at
`mesh_shards=4` in steady state, checked against the frozen numpy reference
in every run); the q7 half is still only here.

One process, JAX imported once. It exits non-zero unless `jax.devices()`
reports the `tpu` platform (and, with --chips 4, four of them) — there is no
road back to the CPU. Every phase drives the system through SQL exactly as
`bench.py` builds its stages, checks the MV against bench's numpy oracle over
the same event stream (exact multiset equality) and asserts the device did
the work. Each phase prints one JSON line; the LAST line of stdout is
`{"ok": true, "device": {...}}` only if every phase passed.

The phase functions take their sizes and the platform their state must sit
on; `main()` passes the real sizes and "tpu" and has no option that relaxes
either. `tests/test_chip_smoke.py` rehearses the same functions at tiny
sizes with "cpu".
"""
import json
import resource
import socket
import struct
import sys
import time
import traceback

import jax

# seconds a phase waits for the background compile service to drain
IDLE_TIMEOUT_S = 900.0


_T0 = time.perf_counter()


def emit(rec):
    print(json.dumps(rec, default=str), flush=True)


def note(msg):
    """Progress on stderr: what a run cut at its time limit leaves behind
    (seconds, host memory now / at its peak)."""
    with open("/proc/self/statm") as f:
        now_gb = int(f.read().split()[1]) * resource.getpagesize() / 1e9
    print(f"[{time.perf_counter() - _T0:7.1f}s {now_gb:5.1f}/"
          f"{_rss_gb():4.1f}GB] {msg}", file=sys.stderr, flush=True)


def _rss_gb():
    """Peak host memory of this process so far (the chip machine ends a
    command that outgrows its host RAM)."""
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6


# ---------------------------------------------------------------------------
# what every phase asserts about the device path
# ---------------------------------------------------------------------------

def _aot_summary():
    from risingwave_tpu.device.compile_service import get_service
    return get_service().summary()


def _drive(db, n_events, chunk):
    """bench.drive's closed loop, stopping when every fused source has
    drained (bench ticks a fixed count; the bound here is the same count
    plus slack, so a stuck source fails instead of spinning)."""
    jobs = list(db._fused.values())
    for i in range(n_events // (64 * chunk) + 8):
        if all(j.counter >= j.max_events for j in jobs):
            break
        db.tick()
        note(f"tick {i}: " + ", ".join(f"{j.name}={j.counter}"
                                       for j in jobs))
    db.tick()                    # one more barrier: the drain checkpoint
    for job in jobs:
        job.sync()
    note("drained and synced")
    for job in jobs:
        assert job.counter >= job.max_events, \
            f"{job.name}: source not drained ({job.counter}/{job.max_events})"


def _state_devices(job, platform):
    """(platform, id) of every device holding a leaf of the job's state;
    all of them must be `platform`."""
    devs = sorted({(d.platform, d.id)
                   for leaf in jax.tree_util.tree_leaves(job.states)
                   for d in leaf.devices()})
    assert devs and all(p == platform for p, _ in devs), \
        f"{job.name}: state on {devs}, wanted platform {platform!r}"
    return devs


def _device_report(db, names, platform, aot_before):
    """The per-phase evidence that the device did the work. Asserts what
    the run may not quietly lose (fusion, placement, recoveries, failed
    compiles); reports the rest without judging it."""
    from risingwave_tpu.device.compile_service import get_service
    svc = get_service()
    t0 = time.perf_counter()
    idle = svc.wait_idle(IDLE_TIMEOUT_S)
    wait_idle_s = time.perf_counter() - t0
    note(f"compile service idle={idle} after {wait_idle_s:.1f}s")
    now = svc.summary()
    aot = {k: now[k] - aot_before.get(k, 0) for k in now if k != "pending"}
    aot["pending"] = now["pending"]
    jobs = {}
    for name in names:
        job = db.catalog.get(name).runtime["fused_job"]
        assert job is not None, f"{name}: MV is not fused (host executors)"
        devs = _state_devices(job, platform)
        assert job.recoveries == 0, f"{name}: {job.recoveries} recoveries"
        events = job.profiler.summary()["compile_events"]
        jobs[name] = {
            "state_devices": [f"{p}:{i}" for p, i in devs],
            "mesh_shards": job.mesh_shards,
            "recoveries": job.recoveries,
            "growth_replays": job.growth_replays,
            "compile_event_s": round(sum(e.get("s") or 0 for e in events), 1),
            "compile_events": [[e.get("label"), e.get("kind"),
                                round(e.get("s") or 0, 1)] for e in events],
            "cap": job.cap_report(),
        }
    assert idle, f"compile service still busy after {IDLE_TIMEOUT_S}s: {now}"
    assert aot["failed"] == 0, f"AOT compiles failed: {svc.status()}"
    assert aot["compiled_steps"] > 0 and aot["inline_steps"] == 0, \
        f"steps of this phase that ran a compiled program / inline jit: {aot}"
    return {"aot": aot, "wait_idle_s": round(wait_idle_s, 1), "jobs": jobs}


def _device_cfg(capacity, **kw):
    from risingwave_tpu.config import DeviceConfig
    import bench
    return DeviceConfig(capacity=capacity,
                        mv_persist_every=bench.MV_PERSIST_EVERY, **kw)


def _bid_groupby(cfg, n_events, chunk):
    """The bench's bid group-by ("q4") through SQL, driven to the drain."""
    import bench
    from risingwave_tpu.sql import Database
    db = Database(device=cfg, checkpoint_frequency=bench.CKPT_EVERY)
    db.run(bench.BID_SRC.format(n=n_events, c=chunk))
    db.run(bench.Q4_MV)
    _drive(db, n_events, chunk)
    return db


def _check_q4(rows, n_events):
    import bench
    import numpy as np
    cols = bench.nexmark_host_columns(n_events)["bid"]
    oracle = bench.numpy_q4(cols[0].astype(np.int64),
                            cols[2].astype(np.int64))
    got = {int(a): (int(c), int(s), int(m)) for a, c, s, m in rows}
    assert len(rows) == len(got) == len(oracle), \
        f"q4: {len(rows)} rows, {len(got)} keys, oracle {len(oracle)}"
    assert got == oracle, "q4: MV differs from the numpy oracle"
    return len(oracle)


def _q7_oracle(bid):
    import bench
    import numpy as np
    return bench.numpy_q7(*(bid[i].astype(np.int64) for i in (0, 1, 2, 5)))


def _q7_rows(rows):
    return sorted((int(a), int(p), int(b), int(t)) for a, p, b, t in rows)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_agg(platform, n_events, capacity, chunk):
    """Bid group-by through SQL (bench.py `_q4_db`): device datagen ->
    pre-combine -> agg merge -> MV apply, groups live on the device."""
    before = _aot_summary()
    t0 = time.perf_counter()
    db = _bid_groupby(_device_cfg(capacity), n_events, chunk)
    cold_s = time.perf_counter() - t0
    rows = db.query("SELECT * FROM q4")
    rec = {"phase": "agg", "events": n_events, "capacity": capacity,
           "epoch_events": 64 * chunk, "cold_s": round(cold_s, 1)}
    rec["groups"] = _check_q4(rows, n_events)
    rec.update(_device_report(db, ["q4"], platform, before))
    rec["ok"] = True
    return db, rec


def _window_join_oracles(n_events):
    """{query: sorted oracle rows} over the connector's own event stream."""
    import bench
    import numpy as np
    c = bench.nexmark_host_columns(n_events)
    bid, auc, per = c["bid"], c["auction"], c["person"]
    return {
        "q5": lambda: bench.numpy_q5(bid[0].astype(np.int64),
                                     bid[5].astype(np.int64)),
        "q7": lambda: _q7_oracle(bid),
        "q8": lambda: bench.numpy_q8(
            per[0].astype(np.int64), per[1], per[6].astype(np.int64),
            auc[7].astype(np.int64), auc[5].astype(np.int64)),
    }


_WJ_ROWS = {
    "q5": lambda rows: sorted((int(a), int(n)) for a, n in rows),
    "q7": _q7_rows,
    "q8": lambda rows: sorted((int(i), str(nm), int(w))
                              for i, nm, w in rows),
}


def phase_window_join(platform, n_events, capacity, chunk, cuts=None):
    """NEXmark q5 / q7 / q8 through SQL (bench.py `_qx_db`): hop and tumble
    windows, the nested max, both self-joins, in one database. `cuts` =
    {query: {"events": fewer, **DeviceConfig fields}} are the listed cuts
    (main() says which and why): the sources carry the event count, so a
    query cut differently runs in a database of its own."""
    import bench
    from risingwave_tpu.sql import Database
    cuts = cuts or {}
    groups = {}                  # same cut -> same database
    for q in ("q5", "q7", "q8"):
        groups.setdefault(json.dumps(cuts.get(q, {}), sort_keys=True),
                          []).append(q)
    rec = {"phase": "window_join", "capacity": capacity,
           "epoch_events": 64 * chunk, "cuts": cuts, "runs": []}
    for cut, queries in groups.items():
        cfg = json.loads(cut)
        n = cfg.pop("events", n_events)
        before = _aot_summary()
        t0 = time.perf_counter()
        db = Database(device=_device_cfg(capacity, **cfg),
                      checkpoint_frequency=bench.CKPT_EVERY)
        for src in (bench.BID_SRC, bench.AUCTION_SRC, bench.PERSON_SRC):
            db.run(src.format(n=n, c=chunk))
        for q in queries:
            db.run(getattr(bench, f"{q.upper()}_MV"))
        _drive(db, n, chunk)
        cold_s = time.perf_counter() - t0
        names = [f"nexmark_{q}" for q in queries]
        got = {q: db.query(f"SELECT * FROM nexmark_{q}") for q in queries}
        oracles = _window_join_oracles(n)
        for q in queries:
            assert _WJ_ROWS[q](got[q]) == oracles[q](), \
                f"{q}: MV differs from the numpy oracle"
        rec["runs"].append({
            "queries": queries, "events": n, "cold_s": round(cold_s, 1),
            "rows": {q: len(got[q]) for q in queries},
            **_device_report(db, names, platform, before)})
    rec["ok"] = True
    return rec


def phase_ingest(platform, n_events, capacity, chunk):
    """The same bid group-by fed from the host (bench.py `_ingest_arm`):
    the staged, double-buffered H2D feed crosses the host<->device link
    once per window."""
    before = _aot_summary()
    t0 = time.perf_counter()
    db = _bid_groupby(_device_cfg(capacity, host_ingest=True), n_events,
                      chunk)
    cold_s = time.perf_counter() - t0
    job = db._fused["q4"]
    assert job.ingest is not None, "host_ingest asked for, no stager armed"
    st = job.ingest.stats()
    rec = {"phase": "ingest", "events": n_events, "capacity": capacity,
           "epoch_events": 64 * chunk, "cold_s": round(cold_s, 1),
           "ingest": {k: (round(v, 4) if isinstance(v, float) else v)
                      for k, v in st.items()}}
    rec["groups"] = _check_q4(db.query("SELECT * FROM q4"), n_events)
    rec.update(_device_report(db, ["q4"], platform, before))
    rec["ok"] = True
    return db, rec


class _WireClient:
    """Just enough of the Postgres v3 simple-query protocol for one
    SELECT over a socket (text format)."""

    def __init__(self, host, port):
        self.sock = socket.create_connection((host, port), timeout=120)
        self.buf = b""
        body = struct.pack(">I", 196608) + b"user\0smoke\0database\0dev\0\0"
        self.sock.sendall(struct.pack(">I", len(body) + 4) + body)
        self._until(b"Z")

    def _recv(self, n):
        while len(self.buf) < n:
            got = self.sock.recv(1 << 20)
            if not got:
                raise ConnectionError("server closed the connection")
            self.buf += got
        out, self.buf = self.buf[:n], self.buf[n:]
        return out

    def _until(self, stop):
        msgs = []
        while True:
            tag = self._recv(1)
            (ln,) = struct.unpack(">I", self._recv(4))
            msgs.append((tag, self._recv(ln - 4)))
            if tag == stop:
                return msgs

    def query(self, sql):
        payload = sql.encode() + b"\0"
        self.sock.sendall(b"Q" + struct.pack(">I", len(payload) + 4)
                          + payload)
        rows = []
        for tag, b in self._until(b"Z"):
            if tag == b"E":
                raise RuntimeError(f"wire error: {b!r}")
            if tag != b"D":
                continue
            (n,) = struct.unpack(">H", b[:2])
            pos, row = 2, []
            for _ in range(n):
                (ln,) = struct.unpack(">i", b[pos:pos + 4])
                pos += 4
                row.append(None if ln < 0 else b[pos:pos + ln].decode())
                pos += max(ln, 0)
            rows.append(tuple(row))
        return rows

    def close(self):
        self.sock.close()


def phase_read(db, platform):
    """Read the agg phase's MV through pgwire: one socket client, the MV
    and an aggregate over it, compared with `db.query`."""
    from risingwave_tpu.pgwire import PgServer
    srv = PgServer(db).start()
    try:
        cli = _WireClient(srv.host, srv.port)
        out = {}
        for key, sql in (("mv", "SELECT * FROM q4"),
                         ("agg", "SELECT count(*), sum(c), max(m) FROM q4")):
            t0 = time.perf_counter()
            wire = cli.query(sql)
            out[f"{key}_wire_s"] = round(time.perf_counter() - t0, 3)
            want = db.query(sql)
            assert sorted(tuple(int(v) for v in r) for r in wire) == \
                sorted(tuple(int(v) for v in r) for r in want), \
                f"pgwire rows differ from db.query for: {sql}"
            out[f"{key}_rows"] = len(wire)
        cli.close()
    finally:
        srv.stop()
    job = db.catalog.get("q4").runtime["fused_job"]
    assert job is not None and job.recoveries == 0
    _state_devices(job, platform)
    return {"phase": "read", **out, "ok": True}


def _check_q7(rows, n_events):
    import bench
    bid = bench.nexmark_host_columns(n_events)["bid"]
    assert _q7_rows(rows) == _q7_oracle(bid), \
        "q7: MV differs from the numpy oracle"


# name: (sources, MV sql, MV name, check(rows, n_events)) — bench.py names
MESH_WORKLOADS = {
    "bid_groupby": (("BID_SRC",), "Q4_MV", "q4", _check_q4),
    "q7": (("BID_SRC",), "Q7_MV", "nexmark_q7", _check_q7),
}


def phase_mesh(platform, shards, workload, n_events, capacity, chunk):
    """The engine's only scale-out for a fused job, DeviceConfig.mesh_shards
    (bench.py `_shards_pass`): one workload sharded and single,
    bit-identical to each other and to the oracle, the state shards on
    `shards` distinct devices."""
    import bench
    from risingwave_tpu.sql import Database
    srcs, mv_sql, mv, check = MESH_WORKLOADS[workload]
    rec = {"phase": f"mesh_{workload}", "shards": shards,
           "events": n_events, "capacity": capacity,
           "epoch_events": 64 * chunk}
    rows = {}
    for s in (shards, 1):
        before = _aot_summary()
        t0 = time.perf_counter()
        db = Database(device=_device_cfg(capacity, mesh_shards=s),
                      checkpoint_frequency=bench.CKPT_EVERY)
        for src in srcs:
            db.run(getattr(bench, src).format(n=n_events, c=chunk))
        db.run(getattr(bench, mv_sql))
        _drive(db, n_events, chunk)
        cold_s = time.perf_counter() - t0
        rows[s] = sorted(db.query(f"SELECT * FROM {mv}"))
        job = db.catalog.get(mv).runtime["fused_job"]
        assert job is not None, f"{mv}: not fused at mesh_shards={s}"
        if s > 1:
            assert job.program.mesh is not None \
                and job.program.mesh.devices.size == s, \
                f"{mv}: asked for {s} shards, program mesh is " \
                f"{job.program.mesh}"
            # the leading axis of every state leaf is the shard axis:
            # its shards must sit one per device, not all on the first
            leaf = jax.tree_util.tree_leaves(job.states)[0]
            homes = {sh.device.id for sh in leaf.addressable_shards}
            assert len(homes) == s, \
                f"{mv}: {s} state shards on devices {sorted(homes)}"
        else:
            assert job.program.mesh is None
        rec[f"x{s}"] = {"cold_s": round(cold_s, 1), "rows": len(rows[s]),
                        **_device_report(db, [mv], platform, before)}
    assert rows[shards] == rows[1], \
        f"{mv}: mesh_shards={shards} MV differs from mesh_shards=1"
    check(rows[1], n_events)
    rec["ok"] = True
    return rec


# ---------------------------------------------------------------------------
# entry
# ---------------------------------------------------------------------------

# Listed cuts of the one-chip run (events only; widths, key space, capacity
# and features are the bench's). q7 and q8 take the full 1,048,576 events.
# q5 takes ONE 131,072-event epoch, the most its 2^16-slot state holds
# without growing: at the full count its hop x5 + agg + agg + join cascade
# grows in 4 successive replays that re-trace 10 nodes at ~150 s of TPU
# compile apiece, which fits neither the script's 1,200 s nor, beside the
# other phases' programs, the one-chip host's 40 GiB (CHANGES.md PR 22).
# q7 keeps the growth path exercised (its join side grows 2^16 -> 2^20).
# compile_buckets=0 for q5: the predicted-bucket pre-warm would queue 5
# further multi-minute compiles that the run never uses and then waits on.
Q5_CUTS = {"q5": {"events": 131_072, "compile_buckets": 0}}


def main(argv):
    modes = {(): ("one", ()),
             ("--chips", "4"): ("mesh", tuple(MESH_WORKLOADS)),
             **{("--chips", "4", w): ("mesh", (w,)) for w in MESH_WORKLOADS}}
    if tuple(argv) not in modes:
        print("usage: python chip_smoke.py [--chips 4 "
              f"[{' | '.join(MESH_WORKLOADS)}]]", file=sys.stderr)
        return 2
    mode, mesh_workloads = modes[tuple(argv)]
    chips = 4 if mode == "mesh" else 1
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(f"device: {device['platform']} / {device['kind']} / "
          f"{device['count']}", flush=True)
    if device["platform"] != "tpu" or device["count"] < chips:
        print(f"chip_smoke needs {chips} tpu device(s); refusing to run on "
              f"{device}", file=sys.stderr)
        return 1
    import jaxlib
    try:
        from importlib.metadata import version
        libtpu = version("libtpu")
    except Exception as e:      # version print only; never decides the run
        libtpu = f"unknown ({e})"
    import bench
    import risingwave_tpu.native as native
    from risingwave_tpu.device import compile_cache_dir
    from risingwave_tpu.device.sorted_state import cheap_compile
    print(f"versions: jax {jax.__version__} / jaxlib {jaxlib.__version__} / "
          f"libtpu {libtpu}", flush=True)
    print(f"compile cache: {compile_cache_dir()}", flush=True)
    print(f"native: {native.available()}", flush=True)
    print(f"kernel form: {'compile-cheap' if cheap_compile() else 'variadic-sort'}",
          flush=True)

    t_run = time.perf_counter()
    failed = []

    def run(name, fn, *args, **kw):
        t0 = time.perf_counter()
        try:
            out = fn(*args, **kw)
        except Exception as e:   # reported, counted, and the run exits != 0
            failed.append(name)
            emit({"phase": name, "ok": False,
                  "seconds": round(time.perf_counter() - t0, 1),
                  "error": f"{type(e).__name__}: {e}",
                  "trace": traceback.format_exc()[-2000:]})
            return None
        db, rec = out if isinstance(out, tuple) else (None, out)
        rec["seconds"] = round(time.perf_counter() - t0, 1)
        emit(rec)
        return db

    qx = (bench.QX_SQL_EVENTS[0], bench.QX_CAPACITY, bench.QX_CHUNK)
    if mode == "mesh":
        sizes = {"bid_groupby": (bench.SHARDS_Q4_EVENTS, 1 << 19,
                                 bench.Q4_CHUNK), "q7": qx}
        for w in mesh_workloads:
            run(f"mesh_{w}", phase_mesh, "tpu", 4, w, *sizes[w])
    else:
        agg_db = run("agg", phase_agg, "tpu",
                     bench.Q4_SQL_EVENTS[0], 1 << 20, bench.Q4_CHUNK)
        if agg_db is not None:
            run("read", phase_read, agg_db, "tpu")
        else:
            failed.append("read")
            emit({"phase": "read", "ok": False, "error": "agg failed"})
        del agg_db
        run("window_join", phase_window_join, "tpu", *qx, cuts=Q5_CUTS)
        run("ingest", phase_ingest, "tpu",
            1_048_576, 1 << 18, bench.INGEST_CHUNK)
    print(f"total: {time.perf_counter() - t_run:.1f} s, peak host rss "
          f"{_rss_gb():.1f} GB, aot {_aot_summary()}", flush=True)
    if failed:
        print(f"FAILED phases: {failed}", file=sys.stderr)
        return 1
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
