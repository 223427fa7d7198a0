"""Nexmark benchmarks: device (TPU) vs honest CPU baselines — timeout-proof.

Workloads (BASELINE.json targets; reference SQL from
`/root/reference/src/tests/simulation/src/nexmark/q{5,7,8}.sql`):

1. **q4 fused ceiling** — bid datagen + group-by agg + MV upsert as one
   jitted program per epoch, everything resident in HBM
   (`device/pipeline.py`). This is the architecture's headline number.
2. **q4 through SQL** — `CREATE SOURCE ... nexmark` + `CREATE MATERIALIZED
   VIEW` with the device dispatch seam on: host datagen, chunks through the
   executor stack, epochs on the TPU, recovery persistence on. Ingest-
   inclusive (host->device transfer is in the measured path).
3. **q5 / q7 / q8 through SQL** — the full reference queries (hop/tumble
   windows, self-joins) on the device path.

Baselines, stated per workload:
- `numpy_batch_eps`: a vectorized single-node CPU implementation of the
  same query (sort/reduceat groupby — the strongest simple CPU baseline;
  batch one-shot, no incremental maintenance, no durability).
- `host_sql_eps`: this framework's exact host executor path (device off),
  measured at a smaller scale (it is per-row Python).

Correctness: every SQL workload's final MV is compared against an
independently computed numpy oracle over the SAME event stream (bit-exact
multiset equality). The fused ceiling is verified against the numpy
groupby of its on-device-generated stream.

**Un-killable by construction** (BENCH_r03 was rc=124 with zero output —
never again): every stage runs in its own subprocess under a wall-clock
budget; a stage that overruns is SIGKILLed and retried at a smaller scale;
results accumulate in `bench_progress.json` after every stage; the final
aggregate prints even on SIGTERM/SIGINT. A transient device stall
can cost one stage, not the whole run.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "detail"}.
Flags: --smoke (tiny scales, <2 min); env RW_BENCH_BUDGET=secs total.
"""
import json
import multiprocessing as mp
import os
import signal
import sys
import time

import numpy as np

# q4 fused-ceiling scale
EPOCHS = 50
ROWS = 262_144
N_AUCTIONS = 10_000
# SQL-path scales (events are 1:3:46 person:auction:bid out of 50).
# Every retry stays at the SAME scale: a killed attempt's finished
# compiles persist in the cache, so same-scale retries converge, while a
# different scale would re-trace (the programs embed the event bound).
Q4_SQL_EVENTS = (8_388_608,)
# qx runs at the scale/capacity pairing that was measured to complete on
# the chip (r05): larger capacities make each epoch's sorts so heavy that a
# single pass outruns any stage budget, and larger scales grow capacity
# mid-run (each growth replays every epoch since the last checkpoint).
# The honest note: qx device throughput is growth-replay-bound at this
# configuration; q4 is the device path's headline.
QX_SQL_EVENTS = (1_048_576,)
QX_CAPACITY = 1 << 16
HOST_SQL_EVENTS = 131_072                # host path is per-row Python
HOST_QX_EVENTS = 16_384                  # hop expansion is 5x rows on host
Q4_CHUNK = 16384                         # 1M-row fused epochs
CKPT_EVERY = 8                           # checkpoint every 8 barriers
# Fused jobs mirror their MV into the host state table every N checkpoints
# (readers are served from live device state either way; recovery needs
# only the committed event counter, which commits at every checkpoint).
# 64 keeps the Python-side mirror out of the steady-state loop.
MV_PERSIST_EVERY = 64

USEC = 1_000_000
PROGRESS_PATH = os.environ.get("RW_BENCH_PROGRESS", "bench_progress.json")

BID_SRC = ("CREATE SOURCE bid (auction BIGINT, bidder BIGINT, price BIGINT,"
           " channel VARCHAR, url VARCHAR, date_time TIMESTAMP,"
           " extra VARCHAR) WITH (connector='nexmark',"
           " nexmark.table='bid', nexmark.max.events='{n}',"
           " nexmark.chunk.size='{c}')")
AUCTION_SRC = ("CREATE SOURCE auction (id BIGINT, item_name VARCHAR,"
               " description VARCHAR, initial_bid BIGINT, reserve BIGINT,"
               " date_time TIMESTAMP, expires TIMESTAMP, seller BIGINT,"
               " category BIGINT, extra VARCHAR) WITH (connector='nexmark',"
               " nexmark.table='auction', nexmark.max.events='{n}',"
               " nexmark.chunk.size='{c}')")
PERSON_SRC = ("CREATE SOURCE person (id BIGINT, name VARCHAR,"
              " email_address VARCHAR, credit_card VARCHAR, city VARCHAR,"
              " state VARCHAR, date_time TIMESTAMP, extra VARCHAR)"
              " WITH (connector='nexmark', nexmark.table='person',"
              " nexmark.max.events='{n}', nexmark.chunk.size='{c}')")

Q4_MV = ("CREATE MATERIALIZED VIEW q4 AS SELECT auction, count(*) AS c,"
         " sum(price) AS s, max(price) AS m FROM bid GROUP BY auction")

Q5_MV = """CREATE MATERIALIZED VIEW nexmark_q5 AS
SELECT AuctionBids.auction, AuctionBids.num FROM (
    SELECT bid.auction, count(*) AS num, window_start AS starttime
    FROM HOP(bid, date_time, INTERVAL '2' SECOND, INTERVAL '10' SECOND)
    GROUP BY window_start, bid.auction
) AS AuctionBids
JOIN (
    SELECT max(CountBids.num) AS maxn, CountBids.starttime_c
    FROM (
        SELECT count(*) AS num, window_start AS starttime_c
        FROM HOP(bid, date_time, INTERVAL '2' SECOND, INTERVAL '10' SECOND)
        GROUP BY bid.auction, window_start
    ) AS CountBids
    GROUP BY CountBids.starttime_c
) AS MaxBids
ON AuctionBids.starttime = MaxBids.starttime_c
   AND AuctionBids.num >= MaxBids.maxn"""

Q7_MV = """CREATE MATERIALIZED VIEW nexmark_q7 AS
SELECT B.auction, B.price, B.bidder, B.date_time
FROM bid B
JOIN (
    SELECT MAX(price) AS maxprice, window_end as date_time
    FROM TUMBLE(bid, date_time, INTERVAL '10' SECOND)
    GROUP BY window_end
) B1 ON B.price = B1.maxprice
WHERE B.date_time BETWEEN B1.date_time - INTERVAL '10' SECOND
      AND B1.date_time"""

Q8_MV = """CREATE MATERIALIZED VIEW nexmark_q8 AS
SELECT P.id, P.name, P.starttime
FROM (
    SELECT id, name, window_start AS starttime, window_end AS endtime
    FROM TUMBLE(person, date_time, INTERVAL '10' SECOND)
    GROUP BY id, name, window_start, window_end
) P
JOIN (
    SELECT seller, window_start AS starttime, window_end AS endtime
    FROM TUMBLE(auction, date_time, INTERVAL '10' SECOND)
    GROUP BY seller, window_start, window_end
) A ON P.id = A.seller AND P.starttime = A.starttime
   AND P.endtime = A.endtime"""


# ---------------------------------------------------------------------------
# numpy batch baselines / oracles (vectorized single-node CPU)
# ---------------------------------------------------------------------------

def groupby_reduce(keys: np.ndarray, cols):
    """Sort-reduceat groupby: [(reduce, col), ...] -> (ukeys, results)."""
    order = np.argsort(keys, kind="stable")
    k = keys[order]
    bounds = np.flatnonzero(np.r_[True, k[1:] != k[:-1]])
    out = []
    for how, c in cols:
        if how == "count":
            out.append(np.diff(np.r_[bounds, len(k)]))
            continue
        c = c[order]
        if how == "sum":
            out.append(np.add.reduceat(c, bounds))
        elif how == "max":
            out.append(np.maximum.reduceat(c, bounds))
    return k[bounds], out


def numpy_q4(auction, price):
    keys, (c, s, m) = groupby_reduce(
        auction, [("count", None), ("sum", price), ("max", price)])
    return {int(k): (int(cc), int(ss), int(mm))
            for k, cc, ss, mm in zip(keys, c, s, m)}


def _hop_expand(ts, hop, size):
    """Per-row window_starts for HOP (latest aligned start <= ts, n back)."""
    n = size // hop
    first = (ts // hop) * hop
    offs = (np.arange(n) * hop)[None, :]
    return (first[:, None] - offs).reshape(-1)   # row-major: row i repeats n


def numpy_q5(auction, ts):
    hop, size = 2 * USEC, 10 * USEC
    n = size // hop
    ws = _hop_expand(ts, hop, size)
    au = np.repeat(auction, n)
    # normalize window starts to small hop ordinals so the composite
    # (window, auction) key fits in int64
    wn = (ws - ws.min()) // hop
    composite = wn * np.int64(1 << 32) + au      # auction ids << 2^32
    keys, (num,) = groupby_reduce(composite, [("count", None)])
    kws, kau = keys >> 32, keys & ((1 << 32) - 1)
    out = {}
    for w in np.unique(kws):
        sel = kws == w
        mx = num[sel].max()
        for a, c in zip(kau[sel][num[sel] >= mx], num[sel][num[sel] >= mx]):
            out[(int(w), int(a))] = int(c)
    # multiset of output rows (auction, num)
    rows = sorted((a, c) for (_w, a), c in out.items())
    return rows


def numpy_q7(auction, bidder, price, ts):
    size = 10 * USEC
    wend = (ts // size) * size + size
    keys, (mp_,) = groupby_reduce(wend, [("max", price)])
    rows = []
    for e, m in zip(keys, mp_):
        sel = (price == m) & (ts >= e - size) & (ts <= e)
        for i in np.flatnonzero(sel):
            rows.append((int(auction[i]), int(price[i]), int(bidder[i]),
                         int(ts[i])))
    return sorted(rows)


def numpy_q8(p_id, p_name, p_ts, a_seller, a_ts):
    size = 10 * USEC
    pw = (p_ts // size) * size
    aw = (a_ts // size) * size
    persons = {(int(i), str(nm), int(w)) for i, nm, w in zip(p_id, p_name, pw)}
    sellers = {(int(s), int(w)) for s, w in zip(a_seller, aw)}
    rows = [(i, nm, w) for (i, nm, w) in persons if (i, w) in sellers]
    return sorted(rows)


# ---------------------------------------------------------------------------
# stage bodies (each runs in a fresh subprocess under a wall budget)
# ---------------------------------------------------------------------------

def stage_fused(epochs, rows):
    """Workload 1: fused device ceiling + oracle verify + CPU baselines."""
    import jax
    import jax.numpy as jnp
    from risingwave_tpu.device.agg_step import DeviceAggSpec
    from risingwave_tpu.device.datagen import gen_bids
    from risingwave_tpu.device.materialize import mv_rows
    from risingwave_tpu.device.pipeline import bid_agg_epoch, make_bid_pipeline

    spec = DeviceAggSpec.build(["count_star", "sum", "max"],
                               [np.int64, np.int64, np.int64])
    agg, mv = make_bid_pipeline(spec, 1 << 14)
    rng = jax.random.PRNGKey(42)
    zero = jnp.zeros((), jnp.int32)
    t_c = time.perf_counter()
    a, m, r, mn = bid_agg_epoch(spec, rows, N_AUCTIONS, agg, mv, rng, zero)
    jax.block_until_ready(mn)      # compile
    compile_s = time.perf_counter() - t_c
    rng = jax.random.PRNGKey(42)
    mn = zero
    t0 = time.perf_counter()
    for _ in range(epochs):
        agg, mv, rng, mn = bid_agg_epoch(spec, rows, N_AUCTIONS, agg, mv,
                                         rng, mn)
    jax.block_until_ready(mn)
    dt = time.perf_counter() - t0
    assert int(mn) <= agg.keys.shape[0], "state overflow: results invalid"
    fused_eps = epochs * rows / dt

    # replay the on-device generator (device arrays accumulate, ONE
    # batched pull — remote links pay per transfer)
    rng = jax.random.PRNGKey(42)
    auctions, prices = [], []
    for _ in range(epochs):
        auction, price, rng = gen_bids(rng, rows, N_AUCTIONS)
        auctions.append(auction)
        prices.append(price)
    auctions, prices = jax.device_get((auctions, prices))
    auction = np.concatenate(auctions)
    price = np.concatenate(prices)

    t0 = time.perf_counter()
    oracle = numpy_q4(auction, price)
    numpy_q4_eps = len(auction) / (time.perf_counter() - t0)

    keys, cols, nulls = mv_rows(mv, [c.acc_dtype for c in spec.calls])
    assert len(keys) == len(oracle), (len(keys), len(oracle))
    for i, key in enumerate(keys.tolist()):
        got = (int(cols[0][i]), int(cols[1][i]), int(cols[2][i]))
        assert got == oracle[key], (key, got, oracle[key])

    dict_eps = host_dict_eps(auction, price)
    return {
        "platform": jax.devices()[0].platform,
        "q4_fused": {
            "device_eps": round(fused_eps),
            "compile_s": round(compile_s, 1),
            "numpy_batch_eps": round(numpy_q4_eps),
            "python_dict_eps": round(dict_eps),
            "events": epochs * rows, "groups": len(oracle),
            "mv_verified": True,
            "note": "datagen on device; numpy baseline is compute-only "
                    "sort-reduce over the identical replayed stream",
        },
    }


def host_dict_eps(auction, price, n=2 * ROWS):
    """The per-row Python loop (this framework's exact host agg hot loop) —
    kept for continuity with BENCH_r01; NOT the honest CPU baseline."""
    from risingwave_tpu.expr.agg import AggCall, create_agg_state
    from risingwave_tpu.expr.expression import InputRef
    from risingwave_tpu.core import dtypes as T
    n = min(n, len(auction))
    price_ref = InputRef(1, T.INT64)
    calls = [AggCall("count"), AggCall("sum", price_ref),
             AggCall("max", price_ref)]
    groups = {}
    t0 = time.perf_counter()
    for i in range(n):
        g = groups.get(auction[i])
        if g is None:
            g = groups[auction[i]] = [create_agg_state(c) for c in calls]
        g[0].apply(1, 1)
        g[1].apply(1, int(price[i]))
        g[2].apply(1, int(price[i]))
    return n / (time.perf_counter() - t0)


def nexmark_host_columns(n_events):
    """Replay the SQL connector's generator host-side (same seed/config)."""
    from risingwave_tpu.connectors.nexmark import NexmarkGenerator
    chunks = NexmarkGenerator().gen_range(0, n_events)
    out = {}
    for name, ch in chunks.items():
        if ch is not None:
            out[name] = [c.values for c in ch.columns]
    return out


def drive(db, n_events, chunk=8192):
    """Tick until the bounded sources drain; return wall seconds.
    Fused jobs dispatch asynchronously, so the clock stops only after
    their device work is DONE (sync), not merely enqueued."""
    ticks = n_events // (64 * chunk) + 3
    t0 = time.perf_counter()
    for _ in range(ticks):
        db.tick()
    for job in db._fused.values():
        job.sync()
    return time.perf_counter() - t0


def _device_cfg(on, capacity):
    if not on:
        return "off"
    from risingwave_tpu.config import DeviceConfig
    return DeviceConfig(capacity=capacity,
                        mv_persist_every=MV_PERSIST_EVERY)


def _cap_stats(db):
    """Per-fused-job capacity lifecycle: whether a (future) regression is
    capacity-churn or compute lives in these counters."""
    return {name: job.cap_report() for name, job in db._fused.items()}


def _profile_stats(db):
    """Per-fused-job epoch-timeline summary (utils/profile.py): phase
    totals + compile events + slowest epochs, so eps regressions are
    attributable to a PHASE (compile vs dispatch vs device vs commit)
    instead of a single end-to-end number."""
    return {name: job.profiler.summary() for name, job in db._fused.items()}


def _warmup_stats(db, warmup_s):
    """Warmup decomposition (ISSUE 6): how much of the wall was compile,
    how many compiles/retraces/growth-replays happened, and what the AOT
    service did (background compiles, cache hits, seconds the dispatcher
    waited on them) — the numbers that prove (or disprove) the warmup wall is
    gone, recorded into the BENCH json."""
    events = [e for job in db._fused.values()
              for e in job.profiler.summary()["compile_events"]]
    out = {
        "warmup_s": round(warmup_s, 1),
        "compile_s": round(sum(e.get("s") or 0 for e in events), 1),
        "compiles": sum(1 for e in events if e.get("kind") == "compile"),
        "retraces": sum(1 for e in events if e.get("kind") == "retrace"),
        "growth_replays": sum(j.growth_replays for j in db._fused.values()),
        "plan_hashes": {n: j.plan_hash for n, j in db._fused.items()},
    }
    try:
        from risingwave_tpu.device.compile_service import get_service
        out["aot"] = get_service().summary()
    except ImportError:
        pass
    return out


def _freshness_stats(db):
    """Per-MV source->commit freshness quantiles (utils/freshness.py):
    p50/p99/last over the run's commits — eps without freshness is half
    the perf story (a fast-but-stale engine fails the paper's
    serve-production-traffic bar), so the trajectory records both."""
    return db._freshness.summary()


def _q4_db(on, n_events, chunk=None):
    from risingwave_tpu.sql import Database
    chunk = chunk or (Q4_CHUNK if on else 8192)
    db = Database(device=_device_cfg(on, 1 << 20),
                  checkpoint_frequency=CKPT_EVERY if on else 1)
    db.run(BID_SRC.format(n=n_events, c=chunk))
    db.run(Q4_MV)
    dt = drive(db, n_events, chunk=chunk)
    rows = db.query("SELECT * FROM q4")
    return (n_events / dt, rows, _cap_stats(db), _profile_stats(db),
            _warmup_stats(db, dt), _freshness_stats(db))


def stage_q4_device(n_events):
    """Workload 2: q4 through SQL on the device path + oracle verify.

    Runs TWICE in-process: the first (warmup) pass compiles every epoch
    program — node steps hash structurally, so the second Database reuses
    the in-process jit cache and the measured pass is pure execution, the
    steady state a long-running stream job lives in. Compile cost is
    reported separately (`warmup_s`); cache entries also persist to disk
    (.jax_cache) so later processes skip the compile entirely."""
    t0 = time.perf_counter()
    _, _, _, _, warm, _ = _q4_db(True, n_events)
    warmup_s = time.perf_counter() - t0
    warm["warmup_s"] = round(warmup_s, 1)
    eps, rows, caps, prof, _, fresh = _q4_db(True, n_events)
    cols = nexmark_host_columns(n_events)["bid"]
    oracle = numpy_q4(cols[0].astype(np.int64), cols[2].astype(np.int64))
    assert len(rows) == len(oracle)
    for a, c, s, m in rows:
        assert oracle[int(a)] == (int(c), int(s), int(m)), a
    return {"q4_sql": {
        "device_eps": round(eps), "events": n_events, "groups": len(rows),
        "warmup_s": round(warmup_s, 1),
        "warmup": warm,
        "capacity": caps,
        "profile": prof,
        "freshness": fresh,
        "mv_verified": True,
        "note": "full SQL stack on device (fused epoch programs, "
                "checkpoint every 8 barriers); warmup_s = first full "
                "pass incl. compile/cache-load, device_eps = steady "
                "state (second pass, jit-cached); profile block = "
                "measured-pass epoch timeline (phase_s splits the wall "
                "into host-pack/dispatch/device-sync/commit; "
                "compile_events decompose any residual warmup); "
                "freshness block = per-MV source->commit p50/p99 "
                "seconds (rw_mv_freshness over the measured pass)",
    }}


def stage_q4_host(n_events):
    out = _q4_db(False, n_events)
    return {"q4_sql_host": {"host_sql_eps": round(out[0]),
                            "events": n_events,
                            "freshness": out[5]}}


QX_CHUNK = 2048   # smaller fused epochs: q5's hop(5x)+agg cascade compiles
                  # ~25x smaller programs than at 8192 (remote-compile RAM
                  # killed the big ones), and growth replays stay short


def _qx_db(on, n_events, capacity):
    """q5+q7+q8 in one database (sources shared, compile cache shared)."""
    from risingwave_tpu.sql import Database
    db = Database(device=_device_cfg(on, capacity),
                  checkpoint_frequency=CKPT_EVERY if on else 1)
    db.run(BID_SRC.format(n=n_events, c=QX_CHUNK))
    db.run(AUCTION_SRC.format(n=n_events, c=QX_CHUNK))
    db.run(PERSON_SRC.format(n=n_events, c=QX_CHUNK))
    db.run(Q5_MV)
    db.run(Q7_MV)
    db.run(Q8_MV)
    dt = drive(db, n_events, chunk=QX_CHUNK)
    out = {
        "q5": db.query("SELECT * FROM nexmark_q5"),
        "q7": db.query("SELECT * FROM nexmark_q7"),
        "q8": db.query("SELECT * FROM nexmark_q8"),
    }
    return (n_events / dt, out, _cap_stats(db), _profile_stats(db),
            _warmup_stats(db, dt), _freshness_stats(db))


def stage_qx_device(n_events):
    """Workload 3: q5/q7/q8 through SQL on the device path + oracles.
    SINGLE pass (unlike q4): qx throughput is growth-replay-bound, so a
    separate warmup pass would double a stage that already brushes its
    budget without changing the steady-state story; compiled programs
    persist in the cache across attempts either way."""
    t0 = time.perf_counter()
    eps, qx, caps, prof, warm, fresh = _qx_db(True, n_events, QX_CAPACITY)
    warmup_s = round(time.perf_counter() - t0, 1)
    warm["warmup_s"] = warmup_s
    c = nexmark_host_columns(n_events)
    bid, auc, per = c["bid"], c["auction"], c["person"]
    t0 = time.perf_counter()
    q5_oracle = numpy_q5(bid[0].astype(np.int64), bid[5].astype(np.int64))
    q5_np_eps = len(bid[0]) / (time.perf_counter() - t0)
    t0 = time.perf_counter()
    q7_oracle = numpy_q7(bid[0].astype(np.int64), bid[1].astype(np.int64),
                         bid[2].astype(np.int64), bid[5].astype(np.int64))
    q7_np_eps = len(bid[0]) / (time.perf_counter() - t0)
    t0 = time.perf_counter()
    q8_oracle = numpy_q8(per[0].astype(np.int64), per[1],
                         per[6].astype(np.int64),
                         auc[7].astype(np.int64), auc[5].astype(np.int64))
    q8_np_eps = (len(per[0]) + len(auc[0])) / (time.perf_counter() - t0)
    assert sorted((int(a), int(n)) for a, n in qx["q5"]) == q5_oracle
    assert sorted((int(a), int(p), int(b), int(t))
                  for a, p, b, t in qx["q7"]) == q7_oracle
    assert sorted((int(i), str(nm), int(w))
                  for i, nm, w in qx["q8"]) == q8_oracle
    return {"q5_q7_q8_sql": {
        "device_eps": round(eps), "events": n_events,
        "warmup_s": round(warmup_s, 1),
        "warmup": warm,
        "capacity": caps,
        "profile": prof,
        "freshness": fresh,
        "numpy_batch_eps": {"q5": round(q5_np_eps), "q7": round(q7_np_eps),
                            "q8": round(q8_np_eps)},
        "rows": {k: len(v) for k, v in qx.items()},
        "mv_verified": True,
        "note": "three reference-SQL MVs concurrently over shared "
                "sources; device_eps counts each source event once; "
                "single pass (warmup_s = its wall incl. cache loads); "
                "capacity block = predictive-growth lifecycle counters "
                "(replays should be <=2/job; more means the predictor "
                "regressed); profile block attributes the wall to "
                "compile vs dispatch vs device-sync vs commit per job; "
                "oracles computed independently in numpy",
    }}


def stage_qx_host(n_events):
    out = _qx_db(False, n_events, QX_CAPACITY)
    return {"q5_q7_q8_sql_host": {"host_sql_eps": round(out[0]),
                                  "events": n_events,
                                  "freshness": out[5]}}


# ---------------------------------------------------------------------------
# mesh-shard sweep (ISSUE 7): the same fused SQL on 1 vs 8 chips
# ---------------------------------------------------------------------------

SHARDS_SWEEP = (1, 8)
SHARDS_Q4_EVENTS = 2_097_152      # a quarter of the headline scale: the
                                  # sweep runs FOUR q4 passes (warm +
                                  # measured per shard count)


def _shards_pass(shards, mv_sqls, mv_names, srcs, n_events, chunk,
                 capacity):
    """One sweep pass at the given mesh_shards: eps, exchange-stage wall,
    the shard count the planner achieved (the CREATE fails when the
    platform lacks the devices), and sorted MV rows for cross-verify."""
    from risingwave_tpu.config import DeviceConfig
    from risingwave_tpu.sql import Database
    db = Database(device=DeviceConfig(capacity=capacity,
                                      mesh_shards=shards,
                                      mv_persist_every=MV_PERSIST_EVERY),
                  checkpoint_frequency=CKPT_EVERY)
    for s in srcs:
        db.run(s.format(n=n_events, c=chunk))
    for mv in mv_sqls:
        db.run(mv)
    dt = drive(db, n_events, chunk=chunk)
    jobs = db._fused
    eff = max([j.mesh_shards for j in jobs.values()] or [1])
    exch = sum(j.profiler.totals.get("exchange", 0.0)
               for j in jobs.values())
    rows = {m: sorted(db.query(f"SELECT * FROM {m}")) for m in mv_names}
    return n_events / dt, exch, eff, rows, _cap_stats(db)


def _shards_sweep(key, mv_sqls, mv_names, srcs, n_events, chunk, capacity,
                  warm_pass):
    out = {"events": n_events, "note":
           "same fused SQL, DeviceConfig.mesh_shards swept; device_eps = "
           "steady state" + (" (second pass, jit-cached)" if warm_pass
                             else " (single pass incl. warmup)") +
           "; exchange_s = wall of the in-program all_to_all dispatch "
           "stage; MV rows cross-verified bit-identical between shard "
           "counts"}
    rows_ref = None
    for shards in SHARDS_SWEEP:
        if warm_pass:
            _shards_pass(shards, mv_sqls, mv_names, srcs, n_events, chunk,
                         capacity)
        eps, exch, eff, rows, caps = _shards_pass(
            shards, mv_sqls, mv_names, srcs, n_events, chunk, capacity)
        if rows_ref is None:
            rows_ref = rows
        else:
            assert rows == rows_ref, "sharded MV diverged from 1-shard"
        out[str(shards)] = {"device_eps": round(eps),
                            "exchange_s": round(exch, 2),
                            "effective_shards": eff,
                            "capacity": caps}
        out["mv_verified"] = rows_ref is not None
    lo, hi = str(SHARDS_SWEEP[0]), str(SHARDS_SWEEP[-1])
    if out.get(lo, {}).get("device_eps"):
        out[f"speedup_{hi}v{lo}"] = round(
            out[hi]["device_eps"] / out[lo]["device_eps"], 3)
    return {key: out}


def stage_shards_q4(n_events):
    return _shards_sweep("shards_sweep_q4", [Q4_MV], ["q4"], [BID_SRC],
                        n_events, Q4_CHUNK, 1 << 19, warm_pass=True)


def stage_shards_qx(n_events):
    return _shards_sweep(
        "shards_sweep_q5_q7_q8", [Q5_MV, Q7_MV, Q8_MV],
        ["nexmark_q5", "nexmark_q7", "nexmark_q8"],
        [BID_SRC, AUCTION_SRC, PERSON_SRC],
        n_events, QX_CHUNK, QX_CAPACITY, warm_pass=False)


# ---------------------------------------------------------------------------
# Zipfian skew sweep (ISSUE 13): power-law keys, defenses off vs on
# ---------------------------------------------------------------------------


def _skew_src(src_sql, s):
    return src_sql.replace("connector='nexmark'",
                           f"connector='nexmark', "
                           f"nexmark.key.dist='zipf:{s}'")


def _skew_pass(shards, defenses, mv_sqls, mv_names, srcs, n_events, chunk,
               capacity, s, threshold):
    """One Zipfian pass: eps, achieved shards, per-job skew report
    (raw key skew_ratio, per-shard load ratio under the current routing
    bounds, adopted policy counters), sorted MV rows for cross-verify."""
    import time as _t
    os.environ["RW_SKEW_STATS"] = "1"   # the defenses need the evidence
    from risingwave_tpu.config import DeviceConfig
    from risingwave_tpu.sql import Database
    db = Database(device=DeviceConfig(capacity=capacity,
                                      mesh_shards=shards,
                                      mv_persist_every=MV_PERSIST_EVERY,
                                      agg_precombine=defenses,
                                      hot_key_rep=defenses,
                                      vnode_rebalance=defenses,
                                      rebalance_threshold=threshold),
                  checkpoint_frequency=CKPT_EVERY)
    for src in srcs:
        db.run(_skew_src(src.format(n=n_events, c=chunk), s))
    for mv in mv_sqls:
        db.run(mv)
    dt = drive(db, n_events, chunk=chunk)
    jobs = db._fused
    # let a staged routing policy (background pre-warm) adopt
    for j in jobs.values():
        for _ in range(100):
            if j._pending_policy is None:
                break
            _t.sleep(0.1)
            db.tick()
    db.tick()
    eff = max([j.mesh_shards for j in jobs.values()] or [1])
    skew = {}
    for name, j in jobs.items():
        rep = j.skew_report()
        ratios = [r[6] for r in rep if r[2] == "skew_ratio"]
        shard_r = [r[6] for r in rep if r[2] == "shard_skew"]
        # max per-epoch ICI send-bucket fill: pre-combine's wire win —
        # one combined row per key per (shard, epoch) instead of every
        # raw row — shows up directly here
        exch_hw = max([r[5] for r in j.node_report() if r[2] == "exch"]
                      or [0])
        skew[name] = {
            "skew_ratio": round(max(ratios or [0.0]), 3),
            "shard_skew_ratio": round(max(shard_r or [0.0]), 3),
            "rebalances": j.rebalances,
            "hot_keys": sum(len(nd.hot_keys)
                            for nd in j.program.nodes),
            "exch_rows_high_water": int(exch_hw),
        }
    rows = {m: sorted(db.query(f"SELECT * FROM {m}")) for m in mv_names}
    return n_events / dt, eff, skew, rows


def _skew_sweep(key, mv_sqls, mv_names, srcs, n_events, chunk, capacity,
                s=1.5, threshold=1.5):
    """The same Zipfian SQL at 1 vs 8 shards, skew defenses off vs on:
    the number that matters is speedup_8v1 per arm — a power-law key
    distribution collapses it toward 1x without the defenses; the
    defenses (pre-combine, hot-key replication, vnode rebalancing) are
    what keep '8 chips' meaning '8x'. MVs are cross-verified
    bit-identical across every arm (the defenses are pure routing)."""
    out = {"events": n_events, "zipf_s": s,
           "note": "nexmark.key.dist=zipf:%s; defenses_off/on x 1/8 "
                   "shards; skew_ratio = raw key skew (max/mean vnode "
                   "bucket, bounds-independent), shard_skew_ratio = "
                   "per-shard load under the CURRENT routing bounds "
                   "(what rebalancing reduces); MV rows cross-verified "
                   "bit-identical across all four arms" % s}
    rows_ref = None
    for defenses in (False, True):
        sub = {}
        for shards in SHARDS_SWEEP:
            eps, eff, skew, rows = _skew_pass(
                shards, defenses, mv_sqls, mv_names, srcs, n_events,
                chunk, capacity, s, threshold)
            if rows_ref is None:
                rows_ref = rows
            else:
                assert rows == rows_ref, "skew-defense MV diverged"
            sub[str(shards)] = {"device_eps": round(eps),
                                "effective_shards": eff,
                                "skew": skew}
        lo, hi = str(SHARDS_SWEEP[0]), str(SHARDS_SWEEP[-1])
        if sub.get(lo, {}).get("device_eps"):
            sub["speedup_8v1"] = round(
                sub[hi]["device_eps"] / sub[lo]["device_eps"], 3)
        out["defenses_on" if defenses else "defenses_off"] = sub
    out["mv_verified"] = rows_ref is not None
    return {key: out}


def stage_skew_q4(n_events):
    return _skew_sweep("skew_q4", [Q4_MV], ["q4"], [BID_SRC], n_events,
                       Q4_CHUNK, 1 << 19)


def stage_skew_qx(n_events):
    # q5: the join-bearing reference query — exercises hot-key
    # replication and the pre-combined hop+agg chain together
    return _skew_sweep("skew_qx", [Q5_MV], ["nexmark_q5"], [BID_SRC],
                       n_events, QX_CHUNK, QX_CAPACITY)


def stage_chaos_mttr(n_events):
    """Workload: recovery MTTR under chaos (fault-tolerance v3).

    Two halves, both deterministic:
    * kill a SUPERVISED worker mid-run (SIGKILL) — time until the
      FragmentSupervisor's in-place respawn converges, then measure the
      post-recovery throughput of fresh traffic;
    * fire a fused device-path failpoint (`fused.dispatch`) mid-run —
      time the in-place fused recovery (state rebuild + crash-window
      re-dispatch on AOT-cached executables), then the post-recovery
      steady-state eps."""
    import time as _t
    from risingwave_tpu.config import ROBUSTNESS
    from risingwave_tpu.sql import Database
    from risingwave_tpu.sql.database import _walk_executors
    from risingwave_tpu.utils import failpoint as fp
    ROBUSTNESS.respawn_backoff_s = 0.001
    out = {}
    # ---- half 1: supervised worker kill -> in-place respawn ----------
    db = Database()
    db.run("CREATE TABLE t (k BIGINT, v BIGINT)")
    db.run("SET streaming_parallelism = 2")
    db.run("SET streaming_placement = 'process'")
    db.run("SET streaming_supervision TO true")
    db.run("CREATE MATERIALIZED VIEW ra AS SELECT k, count(*) AS c,"
           " sum(v) AS s FROM t GROUP BY k")
    n_seed = 2000
    vals = ", ".join(f"({k % 97}, {k})" for k in range(n_seed))
    db.run(f"INSERT INTO t VALUES {vals}")
    for _ in range(4):
        db.tick()
    rset = None
    for e in _walk_executors(db.catalog.get("ra").runtime["shared"]
                             .upstream):
        rset = getattr(e, "_remote", None) or rset
    t0 = _t.perf_counter()
    rset.workers[0].proc.kill()
    while rset.supervisor.respawns == 0:
        db.tick()
    respawn_s = _t.perf_counter() - t0
    # post-recovery eps over fresh traffic
    vals = ", ".join(f"({k % 97}, {k})" for k in range(n_seed))
    t0 = _t.perf_counter()
    db.run(f"INSERT INTO t VALUES {vals}")
    post_dt = _t.perf_counter() - t0
    assert len(db.query("SELECT * FROM ra")) == 97
    rset.shutdown()
    out["worker_kill"] = {
        "respawn_mttr_s": round(respawn_s, 3),
        "post_recovery_eps": round(n_seed / post_dt),
        "respawns": rset.supervisor.respawns,
        "escalated": rset.supervisor._escalated is not None,
    }
    # ---- half 2: fused device-path fault -> in-place recovery --------
    # chunk sized for ~8 epochs: the fault must land MID-RUN, with real
    # committed history to rebuild and a real crash window to re-dispatch
    chunk = max(64, n_events // (64 * 8))
    db2 = Database(device=_device_cfg(True, 1 << 18))
    db2.run(BID_SRC.format(n=n_events, c=chunk))
    db2.run(Q4_MV)
    job = db2.catalog.get("q4").runtime["fused_job"]
    epochs = max(1, n_events // job.program.epoch_events)
    warm = max(1, epochs // 4)
    for _ in range(warm):
        db2.tick()
    fp.arm("fused.dispatch", 1.0, 0, 1)
    t0 = _t.perf_counter()
    db2.tick()                     # fires + recovers inside this barrier
    job.sync()
    mttr = _t.perf_counter() - t0
    fp.reset()
    assert job.recoveries == 1
    t0 = _t.perf_counter()
    for _ in range(epochs - warm + 2):
        db2.tick()
    job.sync()
    post_dt = max(1e-9, _t.perf_counter() - t0)
    post_events = job.counter - (warm + 1) * job.program.epoch_events
    out["fused_fault"] = {
        "recovery_mttr_s": round(mttr, 3),
        "recoveries": job.recoveries,
        "post_recovery_eps": round(max(0, post_events) / post_dt),
        "events": n_events,
        "zero_ddl_replay": True,
    }
    out["note"] = ("worker_kill: SIGKILL a supervised stateful-agg "
                   "worker, MTTR = kill->in-place respawn converged; "
                   "fused_fault: fused.dispatch failpoint fires once "
                   "mid-run, MTTR = barrier wall incl. state rebuild + "
                   "crash-window re-dispatch (AOT-cached, zero compiles)")
    return {"chaos_mttr": out}


INGEST_CHUNK = 4096    # epoch = 262144 events: the staged pipeline needs
                       # MANY windows per run for the double buffer to
                       # have anything to hide (one giant window = one
                       # synchronous stage, no overlap to measure)


def _ingest_arm(n_events, shards, warm_pass):
    """One host-ingest q4 arm: eps + freshness + the pack/h2d/dispatch
    split that proves (or disproves) the double-buffer overlap."""
    from risingwave_tpu.config import DeviceConfig
    from risingwave_tpu.sql import Database

    def one_pass():
        db = Database(device=DeviceConfig(capacity=1 << 18,
                                          host_ingest=True,
                                          mesh_shards=shards,
                                          mv_persist_every=MV_PERSIST_EVERY),
                      checkpoint_frequency=CKPT_EVERY)
        db.run(BID_SRC.format(n=n_events, c=INGEST_CHUNK))
        db.run(Q4_MV)
        dt = drive(db, n_events, chunk=INGEST_CHUNK)
        return db, dt

    if warm_pass:
        one_pass()
    db, dt = one_pass()
    job = db._fused["q4"]
    rows = db.query("SELECT * FROM q4")
    st = job.ingest.stats()
    ph = job.profiler.totals
    disp = ph.get("dispatch", 0.0)
    return {
        "device_eps": round(n_events / dt),
        "events": n_events,
        "effective_shards": job.mesh_shards,
        "groups": len(rows),
        "ingest": {k: (round(v, 4) if isinstance(v, float) else v)
                   for k, v in st.items()},
        # the overlap evidence: total H2D wall over total dispatch wall
        # (< 1.0 means the transfer hid under dispatch), plus the
        # dispatch-thread residual phases (pack/h2d ~ 0 when the double
        # buffer is warm)
        "h2d_over_dispatch": round(st["h2d_s"] / disp, 4) if disp else None,
        "prefetched_frac": round(
            st["prefetched"] / max(1, st["windows"]), 3),
        "phase_s": {k: round(v, 4) for k, v in ph.items()},
        "freshness": _freshness_stats(db),
    }, rows


def _copy_firehose(n_rows, producers):
    """COPY FROM STDIN firehose: `producers` concurrent pgwire
    connections stream text COPY batches into one table with a counting
    MV while the coordinator ticks — rows/s through the admission gate,
    with rw_mv_freshness as the SLO check."""
    import socket
    import struct
    import threading
    import time as _t
    from risingwave_tpu.pgwire import PgServer
    from risingwave_tpu.sql import Database
    db = Database()
    db.run("CREATE TABLE fh (v BIGINT)")
    db.run("CREATE MATERIALIZED VIEW fh_mv AS SELECT count(*) AS n,"
           " sum(v) AS sv FROM fh")
    srv = PgServer(db).start()
    per = n_rows // producers
    batch = 4096

    def produce(k):
        s = socket.create_connection((srv.host, srv.port), timeout=30)
        buf = b""

        def recv(n):
            nonlocal buf
            while len(buf) < n:
                got = s.recv(65536)
                if not got:
                    raise ConnectionError
                buf += got
            out, buf2 = buf[:n], buf[n:]
            buf = buf2
            return out

        def until(stop):
            while True:
                t = recv(1)
                (ln,) = struct.unpack(">I", recv(4))
                recv(ln - 4)
                if t == stop:
                    return

        body = struct.pack(">I", 196608) + b"user\0bench\0\0"
        s.sendall(struct.pack(">I", len(body) + 4) + body)
        until(b"Z")

        def send(tag, p=b""):
            s.sendall(tag + struct.pack(">I", len(p) + 4) + p)

        send(b"Q", b"COPY fh FROM STDIN\0")
        t = recv(1)
        (ln,) = struct.unpack(">I", recv(4))
        recv(ln - 4)
        assert t == b"G", t
        lo = k * per
        for off in range(0, per, batch):
            n = min(batch, per - off)
            data = b"".join(b"%d\n" % (lo + off + i) for i in range(n))
            send(b"d", data)
        send(b"c")
        until(b"Z")
        s.close()

    threads = [threading.Thread(target=produce, args=(k,), daemon=True)
               for k in range(producers)]
    t0 = _t.perf_counter()
    for t in threads:
        t.start()
    alive = True
    while alive:
        # the handler threads serialize on the server's session lock —
        # the tick loop must too (Database has no internal lock; an
        # unlocked tick would interleave barrier processing with
        # copy_rows' bucket read-modify-write)
        with srv.lock:
            db.tick()
        alive = any(t.is_alive() for t in threads)
    for t in threads:
        t.join()
    # drain: everything pushed must reach the MV
    for _ in range(200):
        with srv.lock:
            db.tick()
            got = db.query("SELECT n FROM fh_mv")
        if got and int(got[0][0] or 0) >= producers * per:
            break
    dt = max(1e-9, _t.perf_counter() - t0)
    srv.stop()
    total = producers * per
    n_mv, sv = db.query("SELECT n, sv FROM fh_mv")[0]
    bucket = db._overload.bucket("fh")
    assert int(n_mv) == total, (n_mv, total)
    assert int(sv) == total * (total - 1) // 2, "firehose sum mismatch"
    return {
        "producers": producers,
        "rows": total,
        "copy_eps": round(total / dt),
        "admitted_rows": bucket.admitted_rows,
        "lag_batches": bucket.lag,
        "mv_verified": True,
        "freshness": db._freshness.summary(),
    }


def stage_ingest(n_events, firehose_rows=200_000, producers=8):
    """Workload: line-rate host ingest (ISSUE 15) — q4 with HOST ingest
    in the measured path, before (host executor DAG, the BENCH_r05
    671k-eps architecture) vs after (zero-copy staged feed into the
    fused program), at 1 and 8 shards, plus the COPY firehose arm.
    Freshness p50/p99 rides every arm: ingest rate is only real if
    freshness holds under it."""
    out = {}
    # before: the old measured path — host chunks through the executor
    # stack (per-row Python; measured at its own smaller scale)
    before_n = min(n_events, HOST_SQL_EVENTS)
    eps_before, _rows, _c, _p, _w, fresh = _q4_db(False, before_n)
    out["before_host_executor"] = {
        "host_sql_eps": round(eps_before), "events": before_n,
        "freshness": fresh,
    }
    arm1, rows1 = _ingest_arm(n_events, 1, warm_pass=True)
    out["host_ingest_1shard"] = arm1
    # oracle verify (the host feed must change nothing)
    cols = nexmark_host_columns(n_events)["bid"]
    oracle = numpy_q4(cols[0].astype(np.int64), cols[2].astype(np.int64))
    assert len(rows1) == len(oracle)
    for a, c, s, m in rows1:
        assert oracle[int(a)] == (int(c), int(s), int(m)), a
    arm1["mv_verified"] = True
    # 8-shard arm at a quarter scale: on a CPU-only host the "8 chips"
    # are virtual devices over one CPU, so this arm proves per-shard
    # placement + bit-identity, not speedup (the 1-vs-8 speedup story
    # lives in shards_q4 on real chips)
    arm8, rows8 = _ingest_arm(max(64 * INGEST_CHUNK, n_events // 4), 8,
                              warm_pass=False)
    arm1q, rows1q = _ingest_arm(max(64 * INGEST_CHUNK, n_events // 4), 1,
                                warm_pass=False)
    assert rows8 == rows1q, "8-shard host-ingest MV diverged"
    arm8["mv_verified"] = True
    out["host_ingest_8shard"] = arm8
    out["ingest_speedup_vs_host_executor"] = round(
        arm1["device_eps"] / max(1, eps_before), 2)
    out["firehose_copy"] = _copy_firehose(firehose_rows, producers)
    out["note"] = (
        "before = host executor DAG with ingest in the measured path "
        "(the BENCH_r05 671k-eps q4_sql architecture, at its own "
        "scale); after = zero-copy staged host feed into the fused "
        "program (device/ingest.py), same host. h2d_over_dispatch < 1 "
        "= the double-buffered transfer hid under dispatch; "
        "prefetched_frac = windows staged off the dispatch thread. "
        "firehose_copy = concurrent pgwire COPY producers through the "
        "admission gate, MV count+sum verified exactly. On a CPU-only "
        "host the 'device' compute shares the same CPU as the ingest "
        "pipeline, so the before/after ratio understates what an "
        "accelerator sees (there, staging+H2D hide under real device "
        "dispatch and the executor-DAG baseline gains nothing).")
    return {"ingest": out}


def stage_overload(n_rows):
    """Workload: overload survival (ISSUE 14) — the same bounded datagen
    MV + file sink at 1x/2x/10x offered load (rows per poll scaled).
    Records freshness p50/p99 + eps + admission lag + shed counts per
    arm. The 10x arm additionally stalls the sink for a deterministic
    window (`overload.slow_sink`, RW_LOAD_SHED on) so the record shows
    the full ladder: escalation transitions, audited sheds, and the
    recovery back to `normal` once the stall clears."""
    from risingwave_tpu.config import ROBUSTNESS
    from risingwave_tpu.utils import failpoint as fp
    from risingwave_tpu.utils.overload import PRESSURE
    saved = {k: getattr(ROBUSTNESS, k)
             for k in ("overload_hold_s", "overload_window_s",
                       "load_shed")}
    ROBUSTNESS.overload_hold_s = 0.05
    ROBUSTNESS.overload_window_s = 2.0
    out = {}
    try:
        _overload_arms(n_rows, out)
    finally:
        fp.reset()
        PRESSURE.reset()
        for k, v in saved.items():
            setattr(ROBUSTNESS, k, v)
    out["note"] = ("offered load scaled by rows.per.poll; 10x arm runs "
                   "with RW_LOAD_SHED=true + a deterministic "
                   "overload.slow_sink stall window — shed_rows are "
                   "audited in rw_shed_log (accounted = MV rows + shed "
                   "rows cover every offered row); freshness blocks = "
                   "rw_mv_freshness p50/p99 per arm (the eps-vs-"
                   "freshness trade the cadence stretch makes)")
    return {"overload": out}


def _overload_arms(n_rows, out):
    import tempfile
    import time as _t
    from risingwave_tpu.config import ROBUSTNESS
    from risingwave_tpu.sql import Database
    from risingwave_tpu.utils import failpoint as fp
    from risingwave_tpu.utils.overload import PRESSURE
    for mult in (1, 2, 10):
        stress = mult == 10
        ROBUSTNESS.load_shed = stress
        PRESSURE.reset()
        fp.reset()
        db = Database()
        db.run("CREATE SOURCE s (v BIGINT) WITH (connector='datagen',"
               f" rows.per.poll='{64 * mult}',"
               f" datagen.max.rows='{n_rows}')")
        db.run("CREATE MATERIALIZED VIEW mo AS SELECT count(*) AS n,"
               " sum(v) AS sv FROM s")
        sink_path = os.path.join(tempfile.mkdtemp(prefix="rw_ovl_"),
                                 "out.jsonl")
        db.run(f"CREATE SINK so FROM mo WITH (connector='fs',"
               f" fs.path='{sink_path}', format='jsonl')")
        if stress:
            # stall the first ~30 delivery attempts: the ladder must
            # escalate under the stall and recover after it clears
            fp.arm("overload.slow_sink", 1.0, 0, 30)
        worst = 0
        t0 = _t.perf_counter()
        done = 0
        for tick in range(4000):
            db.tick()
            for c in db._overload.controllers.values():
                worst = max(worst, c.rung)
            if tick % 16 == 15:
                rows = db.query("SELECT n FROM mo")
                done = int(rows[0][0] or 0) if rows else 0
                bucket = db._overload.buckets["s"]
                if done + bucket.shed_rows >= n_rows and all(
                        c.rung == 0
                        for c in db._overload.controllers.values()):
                    break
        dt = max(1e-9, _t.perf_counter() - t0)
        bucket = db._overload.buckets["s"]
        shed_entries = db._shed_log.entries()
        transitions = sum(len(c.transitions)
                          for c in db._overload.controllers.values())
        fp.reset()
        out[f"x{mult}"] = {
            "offered_rows": n_rows,
            "rows_per_poll": 64 * mult,
            "admitted_rows": bucket.admitted_rows,
            "deferred_polls": bucket.deferred,
            "lag_polls": bucket.lag,
            "shed_rows": bucket.shed_rows,
            "shed_windows": len(shed_entries),
            "eps": round(done / dt),
            "wall_s": round(dt, 2),
            "ladder_transitions": transitions,
            "worst_state": ["normal", "throttled", "degraded",
                            "shedding"][worst],
            "recovered_to_normal": all(
                c.rung == 0 for c in db._overload.controllers.values()),
            "freshness": db._freshness.summary(),
            "accounted": done + bucket.shed_rows == n_rows,
        }


def stage_tiering(n_events):
    """Workload: tiered state beyond HBM (ISSUE 16) — a q8-style
    unbounded-key GROUP BY (nexmark auction ids keep arriving for the
    life of the stream) run at a device capacity clamped BELOW the
    final distinct-key count, tiering off vs on at the SAME clamp.

    The untiered arm has to grow (capacity-doubling replays); the
    tiered arm demotes cold groups to host memory off the commit phase
    and touch-promotes them back when their keys reappear (Xor8
    negative caches keep absent-key windows off the promotion path).
    Records eps for both arms, the demotion/promotion counters, the
    negative-cache hit rate, the HBM budget-utilization high-water and
    freshness p50/p99 — and asserts the MVs bit-identical."""
    import time as _t
    from risingwave_tpu.config import DeviceConfig
    from risingwave_tpu.sql import Database
    from risingwave_tpu.utils.metrics import REGISTRY
    # clamp ~half the run's distinct auctions (974 per 16384 bids)
    cap = 1 << max(10, int(0.03 * n_events).bit_length() - 1)
    chunk = max(512, n_events // (64 * 24))
    os.environ.setdefault("RW_TIER_HIGH_WATER", "0.35")
    os.environ.setdefault("RW_TIER_LOW_WATER", "0.15")
    # both demotion-inert-by-design shapes must stay out of this stage
    # (documented residuals): min/max fold through a minput multiset,
    # and a pre-combined agg's input lineage is the combiner, not an
    # ingest source — so q4 minus max(price), pre-combine off BOTH arms
    os.environ["RW_AGG_PRECOMBINE"] = "0"
    mv = ("CREATE MATERIALIZED VIEW qt AS SELECT auction,"
          " count(*) AS c, sum(price) AS s FROM bid GROUP BY auction")
    out = {"events": n_events, "capacity": cap}
    rows_by_arm = {}
    for arm, tier in (("untiered", "0"), ("tiered", "1")):
        os.environ["RW_STATE_TIERING"] = tier
        os.environ["RW_HOST_INGEST"] = tier
        db = Database(device=DeviceConfig(capacity=cap,
                                          hbm_budget_mb=256,
                                          mv_persist_every=
                                          MV_PERSIST_EVERY))
        db.run(BID_SRC.format(n=n_events, c=chunk))
        db.run(mv)
        dt = drive(db, n_events, chunk=chunk)
        db.tick()                       # harvest the last demote pull
        job = db._fused["qt"]
        rows_by_arm[arm] = db.query("SELECT * FROM qt")
        rec = {
            "eps": round(n_events / dt),
            "groups": len(rows_by_arm[arm]),
            "growth_replays": job.growth_replays,
            "capacity_final": job.cap_report(),
            "freshness": _freshness_stats(db),
        }
        if tier == "1":
            tm = job.tiering
            probes = tm.counters["filter_probes"]
            rec["tier"] = {
                "demotions": tm.counters["demotions"],
                "promotions": tm.counters["promotions"],
                "demote_events": tm.counters["demote_events"],
                "cold_rows": sum(len(s) for s in tm.stores.values()),
                "filter_probes": probes,
                "filter_hit_rate": round(
                    tm.counters["filter_hits"] / probes, 4)
                if probes else None,
                "filter_fallbacks": tm.counters["filter_fallbacks"],
            }
            util = [float(line.rsplit(" ", 1)[1])
                    for line in REGISTRY.expose().splitlines()
                    if line.startswith("rw_hbm_budget_utilization")]
            rec["hbm_budget_utilization_high_water"] = (
                round(max(util), 6) if util else None)
            rec["profile_tier_phase_s"] = {
                "demote_d2h": round(
                    job.profiler.totals.get("demote_d2h", 0.0), 3),
                "promote_h2d": round(
                    job.profiler.totals.get("promote_h2d", 0.0), 3),
            }
        out[arm] = rec
    assert rows_by_arm["tiered"] == rows_by_arm["untiered"], \
        "tiered MV must be bit-identical to untiered"
    out["mv_bit_identical"] = True
    out["note"] = ("same capacity clamp both arms; the untiered arm "
                   "pays growth replays, the tiered arm demotes cold "
                   "groups to host ColdStores (commit-phase async D2H) "
                   "and touch-promotes on reappearance — Xor8 negative "
                   "caches filter promotion probes; MVs asserted "
                   "bit-identical incl. row order")
    return {"tiering": out}


def stage_serving(n_events, window_s=1.0):
    """Workload: the read path at scale (ISSUE 19) — a fused q4 MV
    served to 1/8/64 concurrent readers, read cache off vs on, staleness
    bound 0 vs 2 epochs. Records read QPS, read p50/p99, device pulls
    per 1k SELECTs, and write-eps interference (ingest driven alone vs
    under a 64-reader cached storm). Asserts the acceptance invariants:
    a 64-reader cached storm between two checkpoints costs <= 1 device
    pull, and cached read QPS >= 5x uncached."""
    import threading as _th
    import time as _t
    from risingwave_tpu.config import DeviceConfig, ROBUSTNESS
    from risingwave_tpu.device import shard_exec
    from risingwave_tpu.sql import Database

    chunk = max(2048, n_events // (64 * 8))
    db = Database(device=DeviceConfig(capacity=1 << 16,
                                      mv_persist_every=MV_PERSIST_EVERY))
    db.run(BID_SRC.format(n=n_events, c=chunk))
    db.run(Q4_MV)
    job = db._fused["q4"]
    total_ticks = n_events // (64 * chunk) + 3
    quarter = max(1, total_ticks // 4)

    def ticks_eps(k):
        c0 = job.counter
        t0 = _t.perf_counter()
        for _ in range(k):
            db.tick()
        job.sync()
        dt = _t.perf_counter() - t0
        return round((job.counter - c0) / dt) if dt > 0 else None

    # write path alone: one warm quarter (absorbs the compiles), one
    # measured quarter
    ticks_eps(quarter)
    write_eps_alone = ticks_eps(quarter)

    # write path under a continuous 64-reader cached storm
    saved = (ROBUSTNESS.serving_cache, ROBUSTNESS.serving_staleness_epochs)
    ROBUSTNESS.serving_cache = True
    ROBUSTNESS.serving_staleness_epochs = 0
    stop_ev = _th.Event()

    def bg_reader():
        while not stop_ev.is_set():
            db._serve_mv_rows("q4", job)

    bg = [_th.Thread(target=bg_reader, daemon=True) for _ in range(64)]
    for t in bg:
        t.start()
    try:
        write_eps_storm = ticks_eps(total_ticks - 2 * quarter)
    finally:
        stop_ev.set()
        for t in bg:
            t.join(30.0)

    # read arms over the drained (stable) MV: readers x cache x staleness
    def read_storm(readers, seconds):
        lats = []
        lock = _th.Lock()
        deadline = _t.perf_counter() + seconds

        def worker():
            my = []
            while _t.perf_counter() < deadline:
                r0 = _t.perf_counter()
                db._serve_mv_rows("q4", job)
                my.append(_t.perf_counter() - r0)
            with lock:
                lats.extend(my)

        ts = [_th.Thread(target=worker) for _ in range(readers)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(seconds + 60.0)
        lats.sort()
        n = len(lats)
        return {"n_selects": n,
                "read_qps": round(n / seconds),
                "read_p50_ms": round(lats[n // 2] * 1e3, 3) if n else None,
                "read_p99_ms": round(lats[min(n - 1, int(n * 0.99))] * 1e3,
                                     3) if n else None}

    arms = {}
    try:
        for cache, stale in (("off", 0), ("on", 0), ("on", 2)):
            ROBUSTNESS.serving_cache = cache == "on"
            ROBUSTNESS.serving_staleness_epochs = stale
            for readers in (1, 8, 64):
                db.read_cache.invalidate()
                shard_exec.reset_pull_stats()
                rec = read_storm(readers, window_s)
                pulls = shard_exec.PULL_STATS["device_pulls"]
                rec["device_pulls"] = pulls
                rec["pulls_per_1k_selects"] = (
                    round(1e3 * pulls / rec["n_selects"], 3)
                    if rec["n_selects"] else None)
                arms[f"cache_{cache}_stale{stale}_r{readers}"] = rec
    finally:
        ROBUSTNESS.serving_cache, ROBUSTNESS.serving_staleness_epochs = saved

    # acceptance: one pull per (MV, epoch) under the cached 64-reader
    # storm (the stream is drained — exactly one commit window), and
    # cached QPS >= 5x uncached at the same reader count
    hot = arms["cache_on_stale0_r64"]
    cold = arms["cache_off_stale0_r64"]
    assert hot["device_pulls"] <= 1, \
        f"cached 64-reader storm pulled {hot['device_pulls']}x"
    assert hot["read_qps"] >= 5 * cold["read_qps"], \
        f"cached QPS {hot['read_qps']} < 5x uncached {cold['read_qps']}"
    out = {
        "events": n_events,
        "window_s": window_s,
        "write_eps_alone": write_eps_alone,
        "write_eps_under_64_reader_storm": write_eps_storm,
        "cache": db.read_cache.stats(),
        "speedup_cached_vs_uncached_64r":
            round(hot["read_qps"] / max(1, cold["read_qps"]), 1),
        "arms": arms,
        "note": ("read QPS over the drained fused q4 MV; cached arms "
                 "serve (epoch, rows) snapshots from host memory with "
                 "single-flight fills — pulls_per_1k_selects is the "
                 "device-pull amortization; interference compares ingest "
                 "eps alone vs under a continuous 64-reader cached "
                 "storm"),
    }
    return {"serving": out}


# ---------------------------------------------------------------------------
# the un-killable harness
# ---------------------------------------------------------------------------

_STAGES = {
    "fused": stage_fused,
    "q4_device": stage_q4_device,
    "q4_host": stage_q4_host,
    "qx_device": stage_qx_device,
    "qx_host": stage_qx_host,
    "shards_q4": stage_shards_q4,
    "shards_qx": stage_shards_qx,
    "skew_q4": stage_skew_q4,
    "skew_qx": stage_skew_qx,
    "chaos_mttr": stage_chaos_mttr,
    "overload": stage_overload,
    "ingest": stage_ingest,
    "tiering": stage_tiering,
    "serving": stage_serving,
}


def _stage_child(name, args, out_path):
    """Subprocess entry: run one stage, dump its dict to out_path.
    Write-then-rename so the parent can never read a half-written file."""
    try:
        # Kernel policy per workload (device/sorted_state.cheap_compile):
        # the fused ceiling and the join-dense q5/q7/q8 programs measure
        # FASTER with the compile-cheap kernel forms on the chip (r05)
        # (fused: 1.64B vs 984M ev/s, compile 30s vs 229s); q4's
        # 1M-capacity agg measures faster with the variadic-sort forms
        # (1.17M vs 350k ev/s warm). Must be set before jax imports.
        if name in ("fused", "qx_device", "shards_qx", "skew_qx"):
            os.environ["RW_TPU_CHEAP_COMPILE"] = "1"
        if name.startswith("shards") or name.startswith("skew") \
                or name == "ingest":
            # mesh fallback for CPU-only hosts: 8 virtual devices (the
            # flag is inert when the default platform has real chips);
            # must land before jax initializes in this child
            flags = os.environ.get("XLA_FLAGS", "")
            if "xla_force_host_platform_device_count" not in flags:
                os.environ["XLA_FLAGS"] = (
                    flags + " --xla_force_host_platform_device_count=8"
                ).strip()
        result = _STAGES[name](*args)
        payload = {"ok": True, "result": result}
    except BaseException as e:  # report, don't propagate — parent decides
        payload = {"ok": False, "error": f"{type(e).__name__}: {e}"}
    with open(out_path + ".part", "w") as f:
        json.dump(payload, f)
    os.replace(out_path + ".part", out_path)


class Harness:
    def __init__(self, total_budget, record=True):
        self.deadline = time.monotonic() + total_budget
        self.detail = {}
        self.log = []
        self._printed = False
        self._proc = None               # live stage subprocess, if any
        # write the round's BENCH record file only for full, uninterrupted
        # runs — a smoke run or a ctrl-C'd partial must never clobber the
        # canonical BENCH_rNN.json next to the committed history
        self.record = record
        signal.signal(signal.SIGTERM, self._on_term)
        signal.signal(signal.SIGINT, self._on_term)

    def _on_term(self, signum, frame):
        self.record = False
        self.log.append(f"signal {signum} — emitting partial results")
        if self._proc is not None and self._proc.is_alive():
            self._proc.kill()          # os._exit skips mp atexit cleanup
        self.emit()
        os._exit(1)

    def remaining(self):
        return self.deadline - time.monotonic()

    def run_stage(self, name, args, budget, note=""):
        """Run one stage subprocess under a wall budget; merge its result."""
        budget = min(budget, max(5.0, self.remaining() - 10.0))
        if budget <= 5.0:
            self.log.append(f"{name}{args}: skipped (total budget exhausted)")
            self._progress()
            return False
        out_path = f"{PROGRESS_PATH}.{name}.tmp"
        if os.path.exists(out_path):
            os.unlink(out_path)
        ctx = mp.get_context("spawn")
        t0 = time.monotonic()
        proc = ctx.Process(target=_stage_child, args=(name, args, out_path),
                           daemon=True)
        self._proc = proc
        proc.start()
        proc.join(budget)
        wall = time.monotonic() - t0
        if proc.is_alive():
            proc.kill()
            proc.join(10)
            self._proc = None
            self.log.append(f"{name}{args}: KILLED after {wall:.0f}s "
                            f"(budget {budget:.0f}s){note}")
            self._progress()
            return False
        self._proc = None
        ok = False
        payload = None
        if os.path.exists(out_path):
            try:
                with open(out_path) as f:
                    payload = json.load(f)
            except (OSError, ValueError) as e:   # truncated/unreadable
                payload = {"ok": False, "error": f"result unreadable: {e}"}
            os.unlink(out_path)
        if payload is not None:
            if payload.get("ok"):
                self.detail.update(payload["result"])
                self.log.append(f"{name}{args}: ok in {wall:.0f}s")
                ok = True
            else:
                self.log.append(f"{name}{args}: {payload['error']}")
        else:
            self.log.append(f"{name}{args}: died (rc={proc.exitcode}) "
                            f"after {wall:.0f}s")
        self._progress()
        return ok

    def _progress(self):
        tmp = PROGRESS_PATH + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"detail": self.detail, "log": self.log}, f, indent=1)
        os.replace(tmp, PROGRESS_PATH)

    def emit(self):
        if self._printed:
            return
        self._printed = True
        d = self.detail
        # fold the separately-staged host baselines into their workloads
        # (host runs at its own, smaller scale — keep that visible)
        if "q4_sql" in d and "q4_sql_host" in d:
            h = d.pop("q4_sql_host")
            d["q4_sql"]["host_sql_eps"] = h["host_sql_eps"]
            d["q4_sql"]["host_sql_events"] = h["events"]
        if "q5_q7_q8_sql" in d and "q5_q7_q8_sql_host" in d:
            h = d.pop("q5_q7_q8_sql_host")
            d["q5_q7_q8_sql"]["host_sql_eps"] = h["host_sql_eps"]
            d["q5_q7_q8_sql"]["host_sql_events"] = h["events"]
        d["stage_log"] = self.log
        fused = d.get("q4_fused", {})
        value = fused.get("device_eps", 0)
        base = fused.get("numpy_batch_eps")
        if not value:  # fused stage lost — fall back to the SQL headline
            value = d.get("q4_sql", {}).get("device_eps", 0)
            base = d.get("q4_sql", {}).get("host_sql_eps")
        result = {
            "metric": "nexmark_q4_agg_throughput",
            "value": value,
            "unit": "events/s",
            # honest denominator: the vectorized numpy batch baseline, not
            # the per-row Python loop BENCH_r01 used
            "vs_baseline": round(value / base, 3) if base else None,
            "detail": d,
        }
        # record the round's numbers (warmup_s + compile/retrace counts in
        # the per-stage `warmup` blocks) so regressions diff as files
        out_path = os.environ.get("RW_BENCH_OUT", "BENCH_r19.json")
        if out_path and self.record:
            try:
                with open(out_path + ".tmp", "w") as f:
                    json.dump(result, f, indent=1)
                os.replace(out_path + ".tmp", out_path)
            except OSError:
                pass
        print(json.dumps(result), flush=True)


def main():
    smoke = "--smoke" in sys.argv
    total = float(os.environ.get("RW_BENCH_BUDGET", "100" if smoke
                                 else "3400"))
    h = Harness(total, record=not smoke)
    if smoke:
        h.run_stage("fused", (10, 65_536), 60)
        h.run_stage("q4_device", (524_288,), 60)
        h.run_stage("q4_host", (32_768,), 30)
        h.run_stage("qx_device", (262_144,), 60)
        h.run_stage("qx_host", (8_192,), 30)
        h.run_stage("shards_q4", (262_144,), 90)
        h.run_stage("shards_qx", (65_536,), 90)
        h.run_stage("skew_q4", (131_072,), 120)
        h.run_stage("chaos_mttr", (262_144,), 90)
        h.run_stage("overload", (50_000,), 60)
        # >= 4 staged windows at INGEST_CHUNK so the double buffer has
        # something to overlap even at smoke scale
        h.run_stage("ingest", (1_048_576, 20_000, 4), 180)
        h.run_stage("tiering", (262_144,), 150)
        h.run_stage("serving", (131_072, 0.5), 120)
    else:
        # Budgets assume a possibly-cold persistent compile cache: one cold
        # compile of a fused epoch program set is ~200-400s on a TPU.
        # A killed attempt still wrote cache entries for
        # every program that finished, so the SAME-scale retry resumes from
        # there; only after two full-scale attempts do we shrink. Warm runs
        # finish each stage in well under 120s.
        if not h.run_stage("fused", (EPOCHS, ROWS), 300):
            h.run_stage("fused", (EPOCHS, ROWS), 150, " — retry (warmer)")
        # retries stay at the SAME scale: the traced programs embed the
        # event bound (SourceNode max_events / pack-plan ranges), so a
        # smaller fallback scale would start cold while same-scale
        # attempts resume from every cache entry the killed attempt wrote
        if not h.run_stage("q4_device", (Q4_SQL_EVENTS[0],), 600):
            if not h.run_stage("q4_device", (Q4_SQL_EVENTS[0],), 400,
                               " — retry (warmer)"):
                h.run_stage("q4_device", (Q4_SQL_EVENTS[0],), 300,
                            " — retry (warmer still)")
        h.run_stage("q4_host", (HOST_SQL_EVENTS,), 60)
        # mesh-shard sweep (ISSUE 7): the SAME fused q4 SQL at 1 vs 8
        # chips — warm + measured pass per shard count at a quarter of
        # the headline scale, MVs cross-verified bit-identical
        if not h.run_stage("shards_q4", (SHARDS_Q4_EVENTS,), 700):
            h.run_stage("shards_q4", (SHARDS_Q4_EVENTS,), 500,
                        " — retry (warmer)")
        # warmup + measured pass + three numpy oracles ≈ 650-850s warm
        if not h.run_stage("qx_device", (QX_SQL_EVENTS[0],), 1200):
            if not h.run_stage("qx_device", (QX_SQL_EVENTS[0],), 900,
                               " — retry (warmer)"):
                h.run_stage("qx_device", (QX_SQL_EVENTS[0],), 700,
                            " — retry (warmer still)")
        h.run_stage("qx_host", (HOST_QX_EVENTS,), 60)
        # q5/q7/q8 shard sweep: single pass per shard count (the qx
        # programs are compile-heavy; the cache from qx_device warms 1-
        # shard, the 8-shard pass pays its own compiles once)
        h.run_stage("shards_qx", (QX_SQL_EVENTS[0],), 900)
        # Zipfian skew sweep (ISSUE 13): the same fused SQL under a
        # power-law key distribution, defenses off vs on at 1 vs 8
        # shards — speedup_8v1 per arm is the straggler-proofing number
        if not h.run_stage("skew_q4", (SHARDS_Q4_EVENTS // 2,), 800):
            h.run_stage("skew_q4", (SHARDS_Q4_EVENTS // 2,), 500,
                        " — retry (warmer)")
        h.run_stage("skew_qx", (QX_SQL_EVENTS[0] // 4,), 700)
        # recovery MTTR under chaos (fault-tolerance v3): worker SIGKILL
        # respawn + fused device-fault in-place recovery, both timed
        h.run_stage("chaos_mttr", (Q4_SQL_EVENTS[0] // 4,), 300)
        # overload survival sweep (ISSUE 14): freshness p50/p99 + eps +
        # shed counts at 1x/2x/10x offered load, ladder + audit asserted
        h.run_stage("overload", (500_000,), 240)
        # line-rate host ingest (ISSUE 15): q4 with host ingest in the
        # measured path — before (executor DAG) vs after (staged feed)
        # at 1/8 shards + the concurrent-producer COPY firehose
        if not h.run_stage("ingest", (Q4_SQL_EVENTS[0] // 2,
                                      500_000, 16), 900):
            h.run_stage("ingest", (Q4_SQL_EVENTS[0] // 2,
                                   500_000, 16), 600, " — retry (warmer)")
        # tiered state beyond HBM (ISSUE 16): unbounded-key agg at a
        # clamped capacity, untiered (growth replays) vs tiered
        # (demote/promote), MVs asserted bit-identical
        if not h.run_stage("tiering", (Q4_SQL_EVENTS[0] // 4,), 600):
            h.run_stage("tiering", (Q4_SQL_EVENTS[0] // 4,), 400,
                        " — retry (warmer)")
        # serving read path (ISSUE 19): epoch-versioned MV read cache
        # off/on x staleness 0/2 x 1/8/64 readers — read QPS + p50/p99,
        # device pulls per 1k SELECTs, write-eps interference under a
        # 64-reader storm; coalescing + >=5x QPS asserted in-stage
        if not h.run_stage("serving", (Q4_SQL_EVENTS[0] // 4,), 400):
            h.run_stage("serving", (Q4_SQL_EVENTS[0] // 4,), 300,
                        " — retry (warmer)")
    h.emit()


if __name__ == "__main__":
    main()
