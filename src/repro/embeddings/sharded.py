"""Model-parallel (row-sharded) embedding tables with explicit collectives.

TorchRec's sharded embedding + all-to-all pattern, translated to TPU/JAX:
table rows are sharded over the ``model`` mesh axis; a lookup computes a
local partial bag (ids outside the shard masked to zero) and ``psum``s over
``model``. Ids arrive batch-sharded over the (pod,) data axes and replicated
over ``model`` — the psum of (B_local, D) per table is the collective whose
bytes ROO reduces from B_NRO·D to B_RO·D for user-side tables (§2.2, Fig 3).

Variable-batch sharding: RO lookups (batch B_RO) and NRO lookups (batch
B_NRO) share the same table parameters — just two calls with different
leading dims, which is all the TorchRec "variable-length batch sharding"
machinery amounts to under SPMD.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.data.jagged import JaggedTensor
from repro.distributed import comms
from repro.embeddings.bag import bag_lookup, bag_lookup_dense
# table configs live with the collection (the embedding entry point);
# re-exported here because the sharding plan machinery predates it
from repro.embeddings.collection import (EmbeddingCollectionConfig,  # noqa: F401
                                         TableConfig, init_tables)


def table_partition_specs(cfg: EmbeddingCollectionConfig,
                          model_axis: str = "model") -> Dict[str, P]:
    """Row-shard every table over the model axis."""
    return {t.name: P(model_axis, None) for t in cfg.tables}


# ---------------------------------------------------------------------------
# Replicated-path lookups (single device / CPU tests): plain bags.
# ---------------------------------------------------------------------------

def lookup(table: jnp.ndarray, ids: JaggedTensor, pooling: str = "sum"):
    return bag_lookup(table, ids, pooling)


def lookup_dense(table: jnp.ndarray, ids: jnp.ndarray, lengths: jnp.ndarray,
                 pooling: str = "sum"):
    return bag_lookup_dense(table, ids, lengths, pooling)


# ---------------------------------------------------------------------------
# Explicit model-parallel lookup under shard_map.
# ---------------------------------------------------------------------------

def _local_partial_bag(tbl_shard: jnp.ndarray, ids: jnp.ndarray,
                       lengths: jnp.ndarray, vocab: int, n_shards: int,
                       shard_idx: jnp.ndarray, pooling: str) -> jnp.ndarray:
    """Partial bag over the rows this shard owns (padded-dense id layout)."""
    rows = tbl_shard.shape[0]                      # vocab // n_shards
    b, l = ids.shape
    local = ids - shard_idx * rows
    in_shard = (local >= 0) & (local < rows)
    valid = (jnp.arange(l)[None, :] < lengths[:, None]) & in_shard
    emb = jnp.take(tbl_shard, jnp.clip(local, 0, rows - 1).reshape(-1),
                   axis=0).reshape(b, l, -1)
    emb = emb * valid[..., None].astype(emb.dtype)
    out = jnp.sum(emb, axis=1)
    if pooling == "mean":
        out = out / jnp.maximum(lengths, 1).astype(out.dtype)[:, None]
    return out


def sharded_bag_lookup(table: jnp.ndarray, ids: jnp.ndarray,
                       lengths: jnp.ndarray, *, mesh: Mesh,
                       vocab: int, pooling: str = "sum",
                       model_axis: str = "model",
                       batch_axes: Tuple[str, ...] = ("data",)) -> jnp.ndarray:
    """Row-sharded lookup: local partial bag + psum(model).

    table: (V, D) sharded P(model, None); ids/lengths: (B, L)/(B,) sharded
    P(batch_axes). Output: (B, D) sharded P(batch_axes, None).
    Collective cost: one (B_local, D) psum over `model` per call — lookups for
    RO features therefore move B_RO·D bytes instead of B_NRO·D. The psum
    payload rides the wire compressed per the ``comms_compress`` knob.
    """
    n_shards = mesh.shape[model_axis]
    mode, block = comms.compress_mode(), comms.block_size()
    comms.STATS.record_exchange(
        f"lookup:bag:V{vocab}xB{ids.shape[0]}xD{table.shape[-1]}",
        (ids.shape[0], table.shape[-1]), mode=mode, block=block)

    def fn(tbl, i, ln):
        shard_idx = jax.lax.axis_index(model_axis)
        part = _local_partial_bag(tbl, i, ln, vocab, n_shards, shard_idx, pooling)
        part = comms.wire_transform(part, mode, block)
        return jax.lax.psum(part, model_axis)

    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(model_axis, None), P(batch_axes, None), P(batch_axes)),
        out_specs=P(batch_axes, None))(table, ids, lengths)


def sharded_seq_lookup(table: jnp.ndarray, ids: jnp.ndarray, *, mesh: Mesh,
                       vocab: int, model_axis: str = "model",
                       batch_axes: Tuple[str, ...] = ("data",),
                       stats_dedup: bool = False) -> jnp.ndarray:
    """Row-sharded per-position lookup: (B, L) ids -> (B, L, D) rows.

    The sequence-encoder analogue of ``sharded_bag_lookup`` (no pooling:
    HSTU consumes every position). Each shard gathers the rows it owns and
    zeros the rest; the psum over ``model`` reassembles exact ``jnp.take``
    semantics — ids are pre-clipped to [0, vocab), so every position
    contributes exactly one shard's row.
    Collective cost: one (B_local, L, D) psum over ``model`` per call,
    compressed on the wire per the ``comms_compress`` knob.
    """
    mode, block = comms.compress_mode(), comms.block_size()
    comms.STATS.record_exchange(
        f"lookup:seq:V{vocab}xB{ids.shape[0]}xL{ids.shape[1]}"
        f"xD{table.shape[-1]}",
        ids.shape + (table.shape[-1],), mode=mode, block=block,
        dedup=stats_dedup)

    def fn(tbl, i):
        rows = tbl.shape[0]
        shard_idx = jax.lax.axis_index(model_axis)
        local = jnp.clip(i, 0, vocab - 1) - shard_idx * rows
        in_shard = (local >= 0) & (local < rows)
        emb = jnp.take(tbl, jnp.clip(local, 0, rows - 1).reshape(-1),
                       axis=0).reshape(i.shape + (tbl.shape[-1],))
        emb = emb * in_shard[..., None].astype(emb.dtype)
        emb = comms.wire_transform(emb, mode, block)
        return jax.lax.psum(emb, model_axis)

    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(model_axis, None), P(batch_axes, None)),
        out_specs=P(batch_axes, None, None))(table, ids)


def sharded_jagged_bag_lookup(table: jnp.ndarray, ids: JaggedTensor, *,
                              mesh: Mesh, vocab: int, pooling: str = "sum",
                              model_axis: str = "model") -> jnp.ndarray:
    """Row-sharded bag lookup over a jagged id-list feature.

    The jagged ``values`` buffer is packed row-major with no per-row
    alignment, so it cannot shard over the data axis; it enters replicated
    and each model shard computes the partial bags of the rows it owns,
    psum'd over ``model``. Output: (B, D) replicated — this psum of B·D
    bytes per call is exactly the RO-side collective the paper's Fig. 3
    counts (B_RO·D instead of B_NRO·D for user tables). sum/mean only.
    """
    if pooling not in ("sum", "mean"):
        raise ValueError(f"sharded jagged bag supports sum/mean, not {pooling}")
    b = ids.batch_size
    mode, block = comms.compress_mode(), comms.block_size()
    comms.STATS.record_exchange(
        f"lookup:jagged:V{vocab}xB{b}xD{table.shape[-1]}",
        (b, table.shape[-1]), mode=mode, block=block)

    def fn(tbl, vals, lens):
        rows = tbl.shape[0]
        shard_idx = jax.lax.axis_index(model_axis)
        jt = JaggedTensor(vals, lens)
        seg = jt.segment_ids()                     # (capacity,), b == padding
        local = jnp.clip(vals, 0, vocab - 1) - shard_idx * rows
        valid = (seg < b) & (local >= 0) & (local < rows)
        emb = jnp.take(tbl, jnp.clip(local, 0, rows - 1), axis=0)
        emb = emb * valid[:, None].astype(emb.dtype)
        out = jax.ops.segment_sum(emb, seg, num_segments=b + 1)[:b]
        out = comms.wire_transform(out, mode, block)
        out = jax.lax.psum(out, model_axis)
        if pooling == "mean":
            out = out / jnp.maximum(lens, 1).astype(out.dtype)[:, None]
        return out

    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(model_axis, None), P(None), P(None)),
        out_specs=P(None, None))(table, ids.values, ids.lengths)


# NOTE: the plan-routed lookups (plan_seq_lookup & friends) moved into
# repro/embeddings/collection.py — the single embedding entry point — where
# the ShardingPlan decision additionally composes with request-level dedup
# and the GatheredTable sparse-training proxy. This module keeps only the
# explicit shard_map collectives the collection routes to.


def sharded_bag_lookup_rs(table: jnp.ndarray, ids: jnp.ndarray,
                          lengths: jnp.ndarray, *, mesh: Mesh,
                          vocab: int, pooling: str = "sum",
                          model_axis: str = "model",
                          batch_axes: Tuple[str, ...] = ("data",)) -> jnp.ndarray:
    """Reduce-scatter variant: output dim-sharded over `model`.

    Halves collective bytes vs psum when the consumer (e.g. the interaction
    arch) can take D/n_shards-sharded embeddings. ``collection.py`` routes
    here when the caller declares ``out_sharded=True`` (DLRM's dot
    interaction contracts over D, so it never needs the gather back).
    Composes with wire compression like the psum path.
    """
    n_shards = mesh.shape[model_axis]
    mode, block = comms.compress_mode(), comms.block_size()
    comms.STATS.record_exchange(
        f"lookup:bag_rs:V{vocab}xB{ids.shape[0]}xD{table.shape[-1]}",
        (ids.shape[0], table.shape[-1]), mode=mode, block=block,
        collective="psum_scatter")

    def fn(tbl, i, ln):
        shard_idx = jax.lax.axis_index(model_axis)
        part = _local_partial_bag(tbl, i, ln, vocab, n_shards, shard_idx, pooling)
        part = comms.wire_transform(part, mode, block)
        return jax.lax.psum_scatter(part, model_axis, scatter_dimension=1,
                                    tiled=True)

    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(model_axis, None), P(batch_axes, None), P(batch_axes)),
        out_specs=P(batch_axes, model_axis))(table, ids, lengths)
