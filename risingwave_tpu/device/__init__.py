"""Device (TPU) execution path.

State lives in HBM as *sorted runs* (`sorted_state.py`) — the TPU-idiomatic
re-design of the reference's hash-keyed state
(`src/stream/src/executor/join/hash_join.rs:181` JoinHashMap,
`src/stream/src/executor/aggregate/hash_agg.rs:52` AggGroup LRU over
StateTables): instead of pointer-chasing hash tables (scatter-conflict-hostile
on a vector machine), per-vnode-shard state is a sorted key/payload array and
every epoch's delta is applied as a sort + segment-reduce + merge + compact —
all XLA-native primitives that tile cleanly. This is an in-HBM LSM memtable:
the same shape as the reference's Hummock shared buffer
(`src/storage/src/hummock/shared_buffer/shared_buffer_batch.rs`), applied at
barrier granularity.

64-bit keys/accumulators need x64 — enabled here, before any array is made.
"""
import os

import jax

jax.config.update("jax_enable_x64", True)

# Persistent XLA compilation cache: epoch-program compiles are expensive
# (seconds to minutes per shape on a TPU) and fully deterministic, so they
# are cached on disk across processes — every per-bucket capacity re-trace
# after the first run of a query shape is a disk hit instead of a compile
# (the r05 q5/q7/q8 421.7s-warmup lever). The directory is part of the
# cache key, so it is a fixed path, never a temporary one.
_DEFAULT_CACHE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), ".jax_cache")


def compile_cache_dir():
    """The persistent-cache directory in force, or None when the cache is
    off. The one resolver: the compile manifest, `risectl compile-status
    --offline` and chip_smoke.py all ask here."""
    return jax.config.jax_compilation_cache_dir or None


def configure_compile_cache(cache_dir=None):
    """Place jax's persistent compilation cache; returns the directory in
    force (None = off).

    Where JAX_COMPILATION_CACHE_DIR is set in the environment the cache
    was placed from outside: jax reads that variable itself and nothing
    here writes the option (empty = cache off). Otherwise the explicit
    argument (DeviceConfig.compile_cache_dir), else <checkout>/.jax_cache.
    """
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir",
                          cache_dir or _DEFAULT_CACHE)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 2)
    return compile_cache_dir()


# Default policy at import: on, unless the process is pinned to the CPU.
# Tier-1 pins JAX_PLATFORMS=cpu before this package is imported, so tests
# run without a shared cache (several assert that fresh compiles happen,
# and an offline TPU compile written to the cache cannot be read back
# without a chip) unless they place one themselves.
if (jax.config.jax_platforms or "").split(",")[0] != "cpu":
    configure_compile_cache()

from .sorted_state import (  # noqa: E402,F401
    EMPTY_KEY,
    ReduceKind,
    SortedState,
    batch_reduce,
    grow_state,
    lookup,
    make_state,
    merge,
    merge_changes,
)

# the process has started: `rw:boot` (OS process start to here) and the
# listeners that put jax's own compile events on the program's spans
from .. import _T_IMPORT  # noqa: E402
from ..utils.profile import boot_done as _boot_done  # noqa: E402

_boot_done(_T_IMPORT)
