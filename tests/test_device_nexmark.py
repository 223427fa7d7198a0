"""Device Nexmark generator == host connector, bit for bit.

The fused SQL pipeline's correctness story starts here: the oracle in
bench.py replays the HOST generator, so the device generator must produce
the identical stream (numeric columns exactly; strings via surrogate
decode)."""
import numpy as np
import pytest

from risingwave_tpu.connectors.nexmark import (BID_SCHEMA, AUCTION_SCHEMA,
                                               PERSON_SCHEMA,
                                               NexmarkConfig,
                                               NexmarkGenerator)
from risingwave_tpu.device.nexmark_gen import (GenCfg, SURROGATE,
                                               column_bounds, decode_column,
                                               gen_table, table_mask)

N = 5_000
SCHEMAS = {"person": PERSON_SCHEMA, "auction": AUCTION_SCHEMA,
           "bid": BID_SCHEMA}


@pytest.fixture(scope="module")
def streams():
    gen = NexmarkGenerator()
    return gen, gen.gen_range(0, N)


@pytest.mark.parametrize("table", ["person", "auction", "bid"])
def test_device_matches_host(streams, table):
    import jax.numpy as jnp
    gen, host_chunks = streams
    cfg = GenCfg.from_config(gen.cfg)
    ids = jnp.arange(N, dtype=jnp.int64)
    mask = np.asarray(table_mask(table, ids))
    cols = gen_table(cfg, table, ids)
    host = host_chunks[table]
    schema = SCHEMAS[table]
    for i, f in enumerate(schema.fields):
        dev = np.asarray(cols[f.name])[mask]
        want = host.columns[i].values
        got = decode_column(SURROGATE[table][f.name], dev)
        assert len(got) == len(want), f.name
        if want.dtype == object:
            assert all(a == b for a, b in zip(got, want)), f.name
        else:
            np.testing.assert_array_equal(got, want, err_msg=f.name)


@pytest.mark.parametrize("table", ["person", "auction", "bid"])
def test_column_bounds_hold(streams, table):
    import jax.numpy as jnp
    gen, _ = streams
    cfg = GenCfg.from_config(gen.cfg)
    ids = jnp.arange(N, dtype=jnp.int64)
    mask = np.asarray(table_mask(table, ids))
    cols = gen_table(cfg, table, ids)
    for name, arr in cols.items():
        lo, hi = column_bounds(cfg, table, name, max_events=N)
        v = np.asarray(arr)[mask]
        assert v.min() >= lo, (table, name, int(v.min()), lo)
        assert v.max() <= hi, (table, name, int(v.max()), hi)


def test_kind_proportions():
    import jax.numpy as jnp
    ids = jnp.arange(50_000, dtype=jnp.int64)
    assert int(table_mask("person", ids).sum()) == 1_000
    assert int(table_mask("auction", ids).sum()) == 3_000
    assert int(table_mask("bid", ids).sum()) == 46_000


# ---------------------------------------------------------------------------
# the fused source makes its own table's rows (`SourceNode`, PR 33)
# ---------------------------------------------------------------------------

# what `source_lanes` has to give: min(events, pow2 >= (events // 50 + 2) * p)
# (at 35,000, and its quarters of 8,750, bid's bucket is below the events)
LANES = {("person", 8_192): 256, ("auction", 8_192): 512,
         ("bid", 8_192): 8_192, ("person", 35_000): 1_024,
         ("auction", 35_000): 4_096, ("bid", 35_000): 32_768,
         ("person", 131_072): 4_096, ("auction", 131_072): 8_192,
         ("bid", 131_072): 131_072}
MAX_EVENTS = 3_000_017
# event_lo by case; "max_events" starts inside the horizon's last epoch
EVENT_LO = {"zero": lambda ee: 0,
            "not_a_multiple_of_50": lambda ee: 1_000_003,
            # the window's end falls on the second auction of a block
            "ends_inside_a_block": lambda ee: 2_000_002 - ee % 50 + 50,
            "max_events": lambda ee: MAX_EVENTS - ee // 2 - 3}
_STEPS = {}


def _source_node(table, max_events):
    """The fused source of every column of `table` plus the row id."""
    from risingwave_tpu.device.fused import SourceNode
    fields = SCHEMAS[table].fields
    names = [f.name for f in fields] + ["_row_id"]
    return SourceNode(table, GenCfg.from_config(NexmarkConfig()), names,
                      len(names) - 1, max_events,
                      [f.dtype for f in fields] + [None])


def _source_step(table, ee):
    """(node, jitted apply at this cadence), one per table and cadence:
    `event_lo` is traced, so every window of a case shares the program."""
    key = (table, ee)
    if key not in _STEPS:
        import jax
        node = _source_node(table, MAX_EVENTS)
        _STEPS[key] = node, jax.jit(
            lambda lo, _n=node, _e=ee: _n.apply(None, [], lo, _e)[1:3])
    return _STEPS[key]


def _live_rows(table, ee, lo):
    """The dense source's live rows for the window at `lo`:
    (lanes, [column...], pk), in the order the step made them."""
    import jax.numpy as jnp
    _node, step = _source_step(table, ee)
    d, (rows_out,) = step(jnp.int64(lo))
    mask = np.asarray(d.mask)
    assert int(rows_out) == mask.sum()
    assert (np.asarray(d.sign) == 1).all()
    return mask.size, [np.asarray(c)[mask] for c in d.cols], \
        np.asarray(d.pk)[mask]


@pytest.mark.parametrize("ee", [8_192, 35_000, 131_072])
@pytest.mark.parametrize("case", sorted(EVENT_LO))
@pytest.mark.parametrize("table", ["person", "auction", "bid"])
def test_source_makes_its_own_tables_rows(table, case, ee):
    """The source's live rows (every column, `pk`, order) are the live rows
    of the all-ids walk (`gen_table` over `arange`, masked by `table_mask`:
    the plain reference) and the host generator's; its lanes are the rule's;
    four quarter-windows (a shard's block each) concatenate to the window."""
    import jax.numpy as jnp
    from risingwave_tpu.device.nexmark_gen import source_lanes
    lo = EVENT_LO[case](ee)
    hi = min(lo + ee, MAX_EVENTS)
    assert (case == "max_events") == (hi < lo + ee)
    if case == "ends_inside_a_block":
        assert (lo + ee) % 50 == 2
    node, _ = _source_step(table, ee)
    lanes, cols, pk = _live_rows(table, ee, lo)
    assert lanes == source_lanes(table, ee) == LANES[table, ee]
    # the all-ids walk
    ids = jnp.arange(lo, hi, dtype=jnp.int64)
    keep = np.asarray(table_mask(table, ids))
    walk = gen_table(node.gencfg, table, ids)
    own = np.asarray(ids)[keep]
    assert len(own) > 0 and np.array_equal(pk, own)
    assert (np.diff(pk) > 0).all()
    for name, got in zip(node.col_names, cols):
        want = own if name == "_row_id" else np.asarray(walk[name])[keep]
        np.testing.assert_array_equal(got, want, err_msg=name)
    # the host generator
    host = NexmarkGenerator().gen_range(lo, hi)[table]
    for i, f in enumerate(SCHEMAS[table].fields):
        got = decode_column(SURROGATE[table][f.name], cols[i])
        want = host.columns[i].values
        assert len(got) == len(want), f.name
        if want.dtype == object:
            assert all(a == b for a, b in zip(got, want)), f.name
        else:
            np.testing.assert_array_equal(got, want, err_msg=f.name)
    # a shard makes the rows of its contiguous quarter
    quarters = [_live_rows(table, ee // 4, lo + s * (ee // 4))
                for s in range(4)]
    assert all(q[0] == source_lanes(table, ee // 4) for q in quarters)
    np.testing.assert_array_equal(np.concatenate([q[2] for q in quarters]),
                                  pk)
    for i, name in enumerate(node.col_names):
        np.testing.assert_array_equal(
            np.concatenate([q[1][i] for q in quarters]), cols[i],
            err_msg=name)


@pytest.mark.parametrize("ee", [131_072, 262_144, 1_048_576])
def test_bid_source_lanes_are_the_epochs_events_at_a_pow2_cadence(ee):
    """Bid fills 46 of 50 events: at the cells' pow2 cadences the bucket of
    its rows is the epoch's events (nothing to narrow), while person and
    auction make 1/32 and 1/16 of them; the traced delta has those lanes."""
    import jax
    import jax.numpy as jnp
    from risingwave_tpu.device.nexmark_gen import source_lanes
    assert source_lanes("bid", ee) == ee
    assert (source_lanes("person", ee), source_lanes("auction", ee)) \
        == (ee // 32, ee // 16)
    lo = jax.ShapeDtypeStruct((), jnp.int64)
    for table in ("person", "auction", "bid"):
        node = _source_node(table, 8 * ee)
        traced = jax.make_jaxpr(lambda e: node.apply(None, [], e, ee))(lo)
        assert traced.out_avals[0].shape == (source_lanes(table, ee),)
