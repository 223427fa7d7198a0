"""The checkpoint's MV mirror over columns (device/mv_mirror.py, state/):
after EVERY checkpoint of a stream mirrored at every checkpoint, the MV
state table holds byte for byte the keys and value for value (Python type
included) the rows of the per-row oracle — `job._pull_rows()` keyed by
`StateTable.key_of`; the formatter, the diff and the bulk writes against
their per-row forms on hand-made columns and random batches."""
import random
from decimal import Decimal

import numpy as np
import pytest

import bench
from risingwave_tpu.config import DeviceConfig
from risingwave_tpu.core import dtypes as T
from risingwave_tpu.device import mv_mirror
from risingwave_tpu.device.mv_mirror import (MirrorImage, MVColumns,
                                             diff_images, mirror_batch)
from risingwave_tpu.sql import Database
from risingwave_tpu.state.hummock import SpillStateStore
from risingwave_tpu.state.state_table import StateTable
from risingwave_tpu.state.store import MemoryStateStore
from risingwave_tpu.utils.profile import SPANS

SRC = {
    "bid": ("CREATE SOURCE bid (auction BIGINT, bidder BIGINT, price BIGINT,"
            " channel VARCHAR, url VARCHAR, date_time TIMESTAMP,"
            " extra VARCHAR) WITH (connector='nexmark',"
            " nexmark.table='bid', nexmark.max.events='{n}',"
            " nexmark.chunk.size='{c}')"),
    "person": ("CREATE SOURCE person (id BIGINT, name VARCHAR,"
               " email_address VARCHAR, credit_card VARCHAR, city VARCHAR,"
               " state VARCHAR, date_time TIMESTAMP, extra VARCHAR)"
               " WITH (connector='nexmark', nexmark.table='person',"
               " nexmark.max.events='{n}', nexmark.chunk.size='{c}')"),
    "auction": ("CREATE SOURCE auction (id BIGINT, item_name VARCHAR,"
                " description VARCHAR, initial_bid BIGINT, reserve BIGINT,"
                " date_time TIMESTAMP, expires TIMESTAMP, seller BIGINT,"
                " category BIGINT, extra VARCHAR) WITH (connector='nexmark',"
                " nexmark.table='auction', nexmark.max.events='{n}',"
                " nexmark.chunk.size='{c}')"),
}
Q4 = ("CREATE MATERIALIZED VIEW mv AS SELECT auction, count(*) AS c,"
      " sum(price) AS s, max(price) AS m FROM bid GROUP BY auction")
# no count among the calls: every aggregate column carries a null mask
NULLABLE = ("CREATE MATERIALIZED VIEW mv AS SELECT bidder, min(price) AS lo,"
            " max(price) AS hi, sum(price) AS s FROM bid GROUP BY bidder")


def _named(sql, name):
    return sql.replace("nexmark_" + name, "mv")


# case -> (sources, MV, events, events a poll, DeviceConfig fields). An
# epoch is 64 polls; every case runs several checkpoints.
CASES = {
    "bid_groupby": (["bid"], Q4, 9_000, 24, {"capacity": 512}),
    "nullable_agg": (["bid"], NULLABLE, 9_000, 24, {"capacity": 512}),
    "q7_pair": (["bid"], _named(bench.Q7_MV, "q7"), 60_000, 160,
                {"capacity": 1 << 16}),
    "q5_retracted": (["bid"], _named(bench.Q5_MV, "q5"), 30_000, 80,
                     {"capacity": 1 << 15}),
    "q8_varchar_pk": (["person", "auction"], _named(bench.Q8_MV, "q8"),
                      120_000, 320, {"capacity": 1 << 13}),
    "mesh2": (["bid"], Q4, 9_000, 24, {"capacity": 512, "mesh_shards": 2}),
}


def _create(case, **db_args):
    sources, mv, events, chunk, device = CASES[case]
    db = Database(device=DeviceConfig(mv_persist_every=1, **device),
                  **db_args)
    for s in sources:
        db.run(SRC[s].format(n=events, c=chunk))
    db.run(mv)
    return db, db._fused["mv"]


def _typed(rows_by_key):
    return {k: r and tuple((type(v), v) for v in r)
            for k, r in rows_by_key.items()}


def _table(job):
    t = job.mv_state_table
    return dict(t.store.iter_range(t.table_id, None, None))


def _check_against_oracle(job):
    """The table after a checkpoint against the per-row oracle."""
    assert job.committed == job.counter
    table = job.mv_state_table
    oracle = {table.key_of(r): r for r in job._pull_rows()}
    assert _typed(_table(job)) == _typed(oracle)
    assert not table.mem
    return len(oracle)


def _run_checked(db, job, until=None):
    """Tick to the drain (or to `until` events), checking the table after
    every checkpoint; the mirrors' spans."""
    checked = 0
    until = job.max_events if until is None else until
    while job.counter < until or job.committed < job.counter:
        before = job.committed
        db.tick()
        if job.committed != before:
            _check_against_oracle(job)
            checked += 1
    assert checked >= 3, "several checkpoints, each with its mirror"
    return [s for s in SPANS if s["name"] == "rw:commit.mirror"
            and s.get("inst") == job.profiler.instance]


@pytest.mark.parametrize("case", sorted(CASES))
def test_table_equals_the_per_row_oracle_after_every_checkpoint(case):
    db, job = _create(case)
    if case == "mesh2":
        assert job.program.mesh is not None \
            and job.program.mesh.devices.size == 2
    mirrors = _run_checked(db, job)
    assert job.pull.kind == ("keyed" if case in (
        "bid_groupby", "nullable_agg", "mesh2") else "pair")
    assert len(_table(job)) == len(job._persisted) > 0
    for m in mirrors:
        assert m["inserted"] + m["updated"] <= m["rows"]
        assert m["keys_vectorised"] == (case != "q8_varchar_pk")
    total = {k: sum(m[k] for m in mirrors)
             for k in ("inserted", "updated", "deleted")}
    assert total["inserted"] - total["deleted"] == len(job._persisted)
    if case in ("bid_groupby", "nullable_agg", "mesh2"):
        # groups met again: rows put under the key they had
        assert total["updated"] > 0 and total["deleted"] == 0
    if case == "q5_retracted":
        # a window's hottest auction is overtaken: its row leaves the MV
        assert total["deleted"] > 0
    if case == "nullable_agg":
        assert all(m is not None for m in job._persisted.nulls[1:])


def test_a_recovered_job_leaves_a_fresh_jobs_table(tmp_path):
    """Half the stream, a restart (`recover()`: the image is the table's
    keys, its values unknown), the rest: every checkpoint against the
    oracle, and at the drain the table a job that never stopped leaves."""
    events = CASES["bid_groupby"][2]
    d = str(tmp_path / "data")
    db, job = _create("bid_groupby", data_dir=d)
    _run_checked(db, job, until=events // 2)
    assert 0 < job.committed < events
    before = _table(job)
    db.store.close()
    del db, job

    db = Database(data_dir=d, device=DeviceConfig(mv_persist_every=1,
                                                  capacity=512))
    job = db._fused["mv"]
    assert not job._persisted.known and len(job._persisted) == len(before)
    assert _table(job) == before
    mirrors = _run_checked(db, job)
    # the first mirror after the restart puts every row the table had
    assert mirrors[0]["updated"] == len(before)
    assert mirrors[0]["deleted"] == 0 and job._persisted.known
    assert all(m["updated"] < m["rows"] for m in mirrors[1:])

    fresh_db, fresh = _create("bid_groupby")
    _run_checked(fresh_db, fresh)
    assert _typed(_table(job)) == _typed(_table(fresh))


# ---- the formatter, the diff and the batch on hand-made columns ----------

def _format_per_element(dtype, vals, nulls):
    """What `_format_col` did before it worked by whole columns."""
    if dtype.kind == T.TypeKind.DECIMAL:
        out = [Decimal(int(v)) for v in vals]
    elif dtype.kind in (T.TypeKind.FLOAT32, T.TypeKind.FLOAT64):
        out = [float(v) for v in vals]
    elif dtype.kind == T.TypeKind.BOOLEAN:
        out = [bool(v) for v in vals]
    else:
        out = [int(v) for v in vals]
    if nulls is not None:
        out = [None if nulls[i] else out[i] for i in range(len(out))]
    return out


_POOL = np.array(["ann", "bob", "", "d\x00e"], dtype=object)
_DTYPES = [T.INT64, T.INT32, T.DECIMAL, T.FLOAT64, T.BOOLEAN, T.TIMESTAMP,
           T.VARCHAR]
_DECODERS = [("num",)] * 5 + [("ts",), ("pool", _POOL)]


def _random_columns(rng, n, keys=None):
    """`n` rows of every kind the device holds; column 0 a unique key."""
    keys = rng.choice(10 * n + 10, n, replace=False) - 5 if keys is None \
        else keys
    pulled = [
        (keys.astype(np.int64), None),
        (rng.integers(-2**31, 2**31, n), rng.random(n) < 0.3),
        (rng.integers(-10**12, 10**12, n), rng.random(n) < 0.3),
        (rng.normal(size=n) * 1e6, (rng.random(n) < 0.3).astype(np.int32)),
        (rng.integers(0, 2, n), None),
        (rng.integers(0, 2**50, n), np.zeros(n, bool)),
        (rng.integers(0, len(_POOL), n), rng.random(n) < 0.3),
    ]
    cols = MVColumns(_DTYPES, _DECODERS, pulled, n)
    cols.decode()
    return cols


def _rows_per_element(cols):
    out = []
    for k, dt in enumerate(cols.dtypes):
        if k in cols.strs:
            vals = list(_POOL[cols.vals[k]])
            out.append([None if cols.nulls[k][i] else vals[i]
                        for i in range(cols.n)])
        else:
            out.append(_format_per_element(dt, cols.vals[k], cols.nulls[k]))
    return [tuple(c[i] for c in out) for i in range(cols.n)]


@pytest.mark.parametrize("n", [0, 1, 257])
def test_whole_column_formatting_is_the_per_element_formatting(n):
    cols = _random_columns(np.random.default_rng(n), n)
    want = _rows_per_element(cols)
    assert _typed(dict(enumerate(cols.rows()))) \
        == _typed(dict(enumerate(want)))
    idx = np.random.default_rng(1).permutation(n)[: n // 2]
    assert cols.rows(idx) == [want[i] for i in idx.tolist()]
    if n > 1:
        assert {type(v) for r in want for v in r} == {
            int, Decimal, float, bool, str, type(None)}


def _pk_table(store, pk):
    return StateTable(store, 7, _DTYPES, pk)


@pytest.mark.parametrize("pk,vectorised", [
    ([0], True),            # one int64: the native vnode kernel's path
    ([0, 4, 5], True),      # int64, boolean, timestamp
    ([0, 3], False),        # a float column with NULLs in the pk
    ([6, 0], False),        # a VARCHAR in the pk
])
def test_mirror_batches_leave_what_per_row_writes_leave(pk, vectorised):
    """A sequence of mirrors of hand-made columns (rows come, change, turn
    NULL and go) against delete(old) / insert(new) of whole rows, the
    diff the mirror had before it worked over columns."""
    rng = np.random.default_rng(len(pk))
    new_t, old_t = (_pk_table(MemoryStateStore(), pk) for _ in range(2))
    image, persisted, prev_at = MirrorImage(), {}, {}
    keys = rng.choice(5_000, 400, replace=False)
    seen = set()
    for epoch in range(1, 7):
        keep = keys[rng.random(len(keys)) < 0.8]
        cols = _random_columns(rng, len(keep), keep)
        if epoch % 2 == 0:
            # the rows that stay keep their key, most all they held
            for i, key in enumerate(keep.tolist()):
                j = prev_at.get(key)
                if j is None:
                    continue
                as_it_was = rng.random() < 0.7
                for k in range(1, len(cols.vals)):
                    if not as_it_was and k not in pk:
                        continue
                    cols.vals[k][i] = prev.vals[k][j]
                    if cols.nulls[k] is not None:
                        cols.nulls[k][i] = prev.nulls[k][j]
            cols.decode()
        image, bkeys, brows, counts = mirror_batch(image, cols, new_t)
        assert counts["keys_vectorised"] == vectorised
        new_t.write_batch(bkeys, brows, ascending=not counts["deleted"])
        rows = {r: None for r in _rows_per_element(cols)}
        gone = [r for r in persisted if r not in rows]
        came = [r for r in rows if r not in persisted]
        for r in gone:
            old_t.delete(r)
        for r in came:
            old_t.insert(r)
        assert _typed(new_t.mem) == _typed(old_t.mem)
        assert list(new_t.iter_all()) == list(old_t.iter_all())
        came_keys = {old_t.key_of(r) for r in came}
        gone_keys = {old_t.key_of(r) for r in gone}
        assert counts == {
            "rows": len(rows), "keys_vectorised": vectorised,
            "inserted": len(came_keys - gone_keys),
            "updated": len(came_keys & gone_keys),
            "deleted": len(gone_keys - came_keys)}
        seen |= {k for k in ("inserted", "updated", "deleted") if counts[k]}
        new_t.commit(epoch)
        old_t.commit(epoch)
        assert _typed(dict(new_t.store.iter_range(7, None, None))) \
            == _typed(dict(old_t.store.iter_range(7, None, None)))
        persisted, prev = rows, cols
        prev_at = {key: i for i, key in enumerate(keep.tolist())}
    assert seen == {"inserted", "updated", "deleted"}


def test_images_of_two_key_forms_are_matched_as_bytes():
    """An image whose keys are one `S` array against one whose keys are
    objects (a NULL came into the pk, a table read back with keys of two
    widths): matched as the bytes they are, trailing NULs and all."""
    a = mv_mirror.key_array([b"\x00a\x00", b"\x00b\x00", b"\x01\x00\x00"])
    assert a.dtype == np.dtype("S3")
    assert mv_mirror.key_list(a, np.arange(3)) \
        == [b"\x00a\x00", b"\x00b\x00", b"\x01\x00\x00"]
    b = mv_mirror.key_array([b"\x00a", b"\x00a\x00", b"\x01\x00\x00"])
    assert b.dtype == object
    ins, upd, dels = diff_images(MirrorImage(a, known=False),
                                 MirrorImage(b))
    assert (ins.tolist(), upd.tolist(), dels.tolist()) == ([0], [1, 2], [1])
    ins, upd, dels = diff_images(MirrorImage(b, known=False),
                                 MirrorImage(a))
    assert (ins.tolist(), upd.tolist(), dels.tolist()) == ([1], [0, 2], [0])


# ---- the bulk write against per-row insert / delete ----------------------

def _stores(tmp_path):
    return {"memory": lambda tag: MemoryStateStore(),
            "spill": lambda tag: SpillStateStore(str(tmp_path / tag))}


@pytest.mark.parametrize("store", ["memory", "spill"])
@pytest.mark.parametrize("ascending", [True, False])
def test_write_batch_is_insert_and_delete_of_each_row(tmp_path, store,
                                                      ascending):
    """Random batches of puts and tombstones through `write_batch` and
    through per-row `insert` / `delete`: the same mem-table, the same
    reads before the commit, the same store after it."""
    rnd = random.Random(5)
    make = _stores(tmp_path)[store]
    dtypes = [T.INT64, T.VARCHAR, T.DECIMAL]
    bulk = StateTable(make("bulk"), 3, dtypes, [0, 1])
    per_row = StateTable(make("rows"), 3, dtypes, [0, 1])
    live = {}
    for epoch in range(1, 6):
        batch = []
        for _ in range(200):
            pk = (rnd.randrange(60), rnd.choice(["a", "b\x00", ""]))
            if pk in live and rnd.random() < 0.4:
                batch.append((live.pop(pk), False))
            else:
                live[pk] = pk + (Decimal(rnd.randrange(10**6)),)
                batch.append((live[pk], True))
        # one entry a key (the last stands), as a mirror's batch has
        last = {bulk.key_of(r): (r, put) for r, put in batch}
        items = sorted(last.items()) if ascending else list(last.items())
        bulk.write_batch([k for k, _ in items],
                         [r if put else None for _, (r, put) in items],
                         ascending=ascending)
        for r, put in batch:
            (per_row.insert if put else per_row.delete)(r)
        assert bulk.mem == per_row.mem
        # read-your-writes before the commit
        assert list(bulk.iter_all()) == list(per_row.iter_all())
        for pk in [(k, s) for k in range(0, 60, 7) for s in ("a", "")]:
            assert bulk.get_by_pk(pk) == per_row.get_by_pk(pk)
        if epoch == 3:
            # a per-row write after an ascending batch: sorted again
            extra = (-1, "z", Decimal(0))
            bulk.insert(extra)
            per_row.insert(extra)
        bulk.commit(epoch)
        per_row.commit(epoch)
        for t in (bulk, per_row):
            t.store.commit_epoch(epoch)
        assert list(bulk.store.iter_range(3, None, None)) \
            == list(per_row.store.iter_range(3, None, None))
        assert len(bulk) == len(per_row)
        assert list(bulk.iter_all()) == list(per_row.iter_all())


def test_the_store_takes_an_ascending_batch_as_it_stands(monkeypatch):
    """`commit` sorts only a mem-table that is not one batch its writer
    called ascending, and the memory store applies a batch with one
    update: the last pair of a key stands, as when each was applied in turn."""
    table = StateTable(MemoryStateStore(), 1, [T.INT64, T.INT64], [0])
    rows = [(i, i * i) for i in range(50)]
    keys = [table.key_of(r) for r in rows]
    assert keys != sorted(keys), "the vnode prefix shuffles them"
    handed = []
    ingest = table.store.ingest_batch
    monkeypatch.setattr(table.store, "ingest_batch",
                        lambda tid, batch, epoch:
                        (handed.append(batch), ingest(tid, batch, epoch)))
    for epoch, (ascending, then_a_row) in enumerate(
            [(True, False), (False, False), (True, True)]):
        # the writer's word is taken: nothing is sorted behind it
        table.write_batch(keys, rows, ascending=ascending)
        if then_a_row:
            table.insert(rows[0])
        table.commit(epoch)
    assert [k for k, _ in handed[0]] == keys
    assert [k for k, _ in handed[1]] == [k for k, _ in handed[2]] \
        == sorted(keys)
    # into a mem-table that holds something, a batch is one more write
    table.insert(rows[1])
    table.write_batch(keys, rows, ascending=True)
    table.commit(3)
    assert [k for k, _ in handed[3]] == sorted(keys)

    store = MemoryStateStore()
    store.ingest_batch(1, [(b"a", (1,)), (b"b", None), (b"b", (2,)),
                           (b"c", (3,)), (b"c", None), (b"d", None)], 1)
    assert list(store.iter_range(1, None, None)) \
        == [(b"a", (1,)), (b"b", (2,))]
    store.ingest_batch(1, [(b"a", None), (b"0", (0,))], 2)
    assert list(store.iter_range(1, None, None)) \
        == [(b"0", (0,)), (b"b", (2,))]
