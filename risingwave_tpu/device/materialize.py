"""Device-resident materialized-view table.

Analog of `MaterializeExecutor` + the MV StorageTable
(`src/stream/src/executor/mview/materialize.rs:166`): an upsert table keyed
by the MV primary key, living in HBM as a SortedState whose payload columns
use REPLACE semantics (newest write wins — ConflictBehavior::Overwrite).
Consuming an agg change set never leaves the device: upserts come from
`new_found` rows, deletes from `old_found & ~new_found`, so the steady-state
pipeline source -> agg -> MV does zero host round-trips; the host pulls the
MV only to serve a query (the batch-scan path).
"""
from __future__ import annotations

from functools import partial
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .sorted_state import (EMPTY_KEY, ReduceKind, SortedState, make_state,
                           merge)


def make_mv_state(capacity: int, col_dtypes: Sequence) -> SortedState:
    """Payload col 0 = liveness (REPLACE, int32 0/1); then the MV columns,
    each paired with a REPLACE null flag."""
    dtypes = [jnp.int32]
    for d in col_dtypes:
        dtypes += [d, jnp.bool_]
    kinds = [ReduceKind.REPLACE] * len(dtypes)
    return make_state(capacity, dtypes, kinds)


def mv_kinds(n_cols: int):
    return tuple([ReduceKind.REPLACE] * (1 + 2 * n_cols))


def mv_apply_changes(state: SortedState, keys: jax.Array,
                     upsert: jax.Array, delete: jax.Array,
                     cols: Sequence[jax.Array], nulls: Sequence[jax.Array]
                     ) -> Tuple[SortedState, jax.Array]:
    """Apply an (already unique-keyed) change set to the MV.

    upsert/delete are disjoint bool masks over keys; rows with neither are
    no-ops (key forced to EMPTY so they drop out of the merge).
    """
    kinds = mv_kinds(len(cols))
    with jax.named_scope("mv.apply"):
        touched = upsert | delete
        dkeys = jnp.where(touched, keys, EMPTY_KEY)
        live = upsert.astype(jnp.int32)  # delete -> 0 -> compacted away
        dvals = [live]
        for c, nl in zip(cols, nulls):
            dvals += [c.astype(state.vals[len(dvals)].dtype), nl]
        return merge(state, dkeys, dvals, kinds, drop_dead=True,
                     dead_col=0)


def mv_rows(state: SortedState, col_dtypes: Sequence) -> Tuple[np.ndarray, ...]:
    """Host pull of the MV (query serving): (keys, cols..., null masks...)."""
    n = int(state.count)
    keys = np.asarray(state.keys)[:n]
    cols, nulls = [], []
    for i in range(len(col_dtypes)):
        cols.append(np.asarray(state.vals[1 + 2 * i])[:n])
        nulls.append(np.asarray(state.vals[2 + 2 * i])[:n])
    return keys, cols, nulls
