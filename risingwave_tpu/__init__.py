"""risingwave_tpu — a TPU-native streaming SQL engine.

A ground-up re-design of RisingWave's capabilities (reference at
/root/reference, see /root/repo/SURVEY.md) for JAX/XLA on TPU: Postgres-dialect
SQL in, incrementally-maintained materialized views out, with Chandy-Lamport
barrier checkpointing, vnode-hash data parallelism over a device mesh, and
epoch-versioned durable operator state.

Layer map (mirrors SURVEY.md §1, re-hosted):
  core/        L0 columnar kernel: chunks, types, vnode hash, epochs, encodings
  expr/        L4 vectorized expression & aggregate function layer
  ops/         L5 stream executors (generator protocol over Message streams)
  state/       L2/L3 state tables + storage backends + checkpoints
  device/      Pallas/XLA per-epoch kernels and HBM-resident operator state
  parallel/    vnode→mesh sharding, shard_map steps, exchange collectives
  runtime/     actors, barrier manager, dataflow assembly, recovery
  sql/         L9 parser/binder/planner (Postgres dialect subset)
  connectors/  L6 sources (nexmark, datagen) and sinks
  meta/        L8 control plane: catalog, DDL, checkpoint coordination
"""
import time as _time

# the package's first line on the spans' clock: where `rw:boot.import`
# starts (utils/profile.py `boot_done`, called where the import of
# `risingwave_tpu.device` ends)
_T_IMPORT = _time.perf_counter_ns()

__version__ = "0.1.0"


def _configure_jax() -> None:
    """SQL BIGINT/TIMESTAMP require 64-bit device integers; enable x64 before
    any array is created. Hot kernels still downcast to int32/bf16 where the
    value range allows (see risingwave_tpu/device/)."""
    try:
        import jax
        jax.config.update("jax_enable_x64", True)
    except ImportError:  # pragma: no cover - jax is a hard dep in practice
        pass


_configure_jax()
