"""EmbeddingCollection — the single embedding entry point for every model.

One named collection owns the tables (vocab/dim/pooling/RO-side metadata)
and the feature -> table routing, and every lookup mode the models need:

  * ``seq_lookup``        — (B, L) ids -> (B, L, D) rows (HSTU/GRU inputs)
  * ``row_lookup``        — (B,)  ids -> (B, D) single rows (item towers)
  * ``bag_lookup``        — JaggedTensor id-lists -> (B, D) pooled bags
  * ``bag_lookup_dense``  — padded (B, L) multi-hot -> (B, D) pooled bags

Every local lookup applies **request-level id dedup** first (RecD's
production observation, PAPERS.md): ``unique`` + inverse-index gather, so an
id repeated across the impressions/slots of a request batch is read from
HBM exactly once and duplicates expand from the small gathered buffer. The
expansion is index bookkeeping only — outputs are bit-identical to the
direct gather (tests/test_embeddings.py asserts exact equality).

The same functions accept four table representations:

  * a dense ``(V, D)`` array — the plain path;
  * a :class:`repro.embeddings.sparse.GatheredTable` proxy — touched-rows
    training (the train step's default): the batch's unique rows were
    gathered up front, lookups translate ids through its ``row_of`` map;
  * a :class:`repro.embeddings.sparse.TableReads` stand-in — the train
    step's recording pass: each lookup logs the ids it reads, then runs on
    the plain table;
  * a dense array under an SPMD ``plan`` that row-shards it — routed through
    the explicit psum lookups of ``embeddings/sharded.py``. Dedup composes:
    the unique-id set is gathered through the psum path and expanded
    locally, so per-shard HBM reads dedup exactly as in the local case.

Dedup policy: ``auto`` (default) applies dedup on TPU to dense tables with
at least ``DEDUP_MIN_VOCAB`` rows and ``DEDUP_MIN_IDS`` ids in the lookup.
Off-accelerator auto never dedups: host caches already absorb duplicate
reads, so the ``unique`` sort is pure overhead there (measured in
benchmarks/embedding_bench.py) — the CPU-side win lives in the sparse
gradient path, where the same unique-id set shrinks the backward and the
optimizer update. Override per call (``dedup=True/False``), per process
(:func:`set_dedup_policy`), or by env (``REPRO_EMB_DEDUP=always|never``).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.data.jagged import JaggedTensor, KeyedJagged
from repro.embeddings.bag import bag_pool, bag_pool_dense
from repro.embeddings.sparse import GatheredTable, TableReads
from repro.scenario.knobs import UNSET, Knob

# tables this tall with this many ids per lookup dedup by default
DEDUP_MIN_VOCAB = 4096
DEDUP_MIN_IDS = 64

# policy resolves through the shared ladder (arg > process default set by
# a CLI flag / scenario spec > REPRO_EMB_DEDUP env var > "auto")
DEDUP_KNOB = Knob("emb_dedup", "REPRO_EMB_DEDUP",
                  choices=("always", "never", "auto"), kind="policy",
                  auto=lambda: "auto")


def set_dedup_policy(policy: Optional[str]) -> None:
    """Process-wide dedup policy: "always" | "never" | "auto" | None."""
    DEDUP_KNOB.set_default(UNSET if policy is None else policy)


def _want_dedup(vocab: int, n_ids: int, dedup: Optional[bool]) -> bool:
    if dedup is not None:
        return dedup
    policy = DEDUP_KNOB.resolve()
    if policy == "always":
        return True
    if policy == "never":
        return False
    return (jax.default_backend() == "tpu"
            and vocab >= DEDUP_MIN_VOCAB and n_ids >= DEDUP_MIN_IDS)


def _dedup_forced(dedup: Optional[bool]) -> bool:
    """True when the caller (arg) or the process policy demands dedup —
    a forced dedup beats the fused-kernel route in bag_lookup_dense, which
    streams one DMA per slot and cannot honor it."""
    if dedup is not None:
        return dedup
    return DEDUP_KNOB.resolve() == "always"


# ---------------------------------------------------------------------------
# Table configs (shared with embeddings/sharded.py, which re-exports them)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TableConfig:
    name: str
    vocab: int
    dim: int
    pooling: str = "sum"
    side: str = "nro"          # "ro" (user/request) or "nro" (item) — decides
                               # which batch size the lookup runs at under ROO


@dataclasses.dataclass(frozen=True)
class EmbeddingCollectionConfig:
    tables: Tuple[TableConfig, ...]

    def table(self, name: str) -> TableConfig:
        for t in self.tables:
            if t.name == name:
                return t
        raise KeyError(name)


def init_tables(rng: jax.Array, cfg: EmbeddingCollectionConfig,
                dtype=jnp.float32, scale: float = 0.01) -> Dict[str, jnp.ndarray]:
    keys = jax.random.split(rng, len(cfg.tables))
    return {t.name: (jax.random.normal(k, (t.vocab, t.dim)) * scale).astype(dtype)
            for t, k in zip(cfg.tables, keys)}


@dataclasses.dataclass(frozen=True)
class FeatureSpec:
    """Feature -> table routing entry: which table a named feature reads,
    in which lookup mode, with which pooling."""
    name: str
    table: str
    kind: str = "bag"          # "jagged" | "bag" | "seq" | "row"
    pooling: str = "sum"


Table = Union[jnp.ndarray, GatheredTable]


# ---------------------------------------------------------------------------
# The dedup gather primitive.
# ---------------------------------------------------------------------------

def _vocab_of(table: Table, vocab: Optional[int]) -> int:
    return int(vocab) if vocab is not None else int(table.shape[0])


def dedup_gather(table: jnp.ndarray, ids: jnp.ndarray,
                 row_gather=None) -> jnp.ndarray:
    """``take(table, ids, axis=0)`` with each distinct id read once.

    ids must be pre-clipped to [0, vocab). Bit-identical to the direct
    gather: ``uids[inv] == ids`` by construction, so the expansion from the
    (n_ids, D) gathered buffer reproduces the exact same rows.
    ``row_gather(uids) -> (n_ids, D)`` overrides how the unique rows are
    fetched — the sharded seq path plugs its psum gather in here so both
    routes share one unique/expand implementation.
    """
    flat = ids.reshape(-1)
    uids, inv = jnp.unique(flat, size=flat.shape[0], fill_value=0,
                           return_inverse=True)
    rows = (row_gather(uids) if row_gather is not None
            else jnp.take(table, uids, axis=0))
    return jnp.take(rows, inv.reshape(-1), axis=0).reshape(
        ids.shape + rows.shape[1:])


def _gather(table: Table, ids: jnp.ndarray, vocab: int,
            dedup: Optional[bool]) -> jnp.ndarray:
    """Row gather with dedup + proxy handling; ids of any shape, unclipped."""
    ids = jnp.clip(ids, 0, vocab - 1)
    if isinstance(table, GatheredTable):
        return table.take(ids)      # already dedup'd at the batch level
    if _want_dedup(vocab, ids.size, dedup):
        return dedup_gather(table, ids)
    return jnp.take(table, ids, axis=0)


def _plan_shards(table: Table, vocab: int, plan) -> bool:
    if plan is None or isinstance(table, GatheredTable):
        return False
    from repro.distributed.spmd import table_is_sharded
    return table_is_sharded(plan, vocab)


def _read(table: Table, vocab: int, plan, read_ids) -> Table:
    """The table a lookup runs on: a :class:`TableReads` stand-in logs the
    ids the lookup reads (``read_ids()``, in [0, vocab]: ``vocab`` for a
    slot it skips) and hands its table on. A table the plan shards is read
    whole: its rows are not local, so it keeps the dense gradient."""
    if not isinstance(table, TableReads):
        return table
    if _plan_shards(table.table, vocab, plan):
        return table.whole()
    return table.read(read_ids())


def _compress_active() -> bool:
    from repro.distributed import comms
    return comms.compress_mode() != "none"


# ---------------------------------------------------------------------------
# Lookup modes.
# ---------------------------------------------------------------------------

def seq_lookup(table: Table, ids: jnp.ndarray, *, vocab: Optional[int] = None,
               plan=None, dedup: Optional[bool] = None) -> jnp.ndarray:
    """(B, L) ids -> (B, L, D); exact ``take(table, clip(ids))`` semantics."""
    v = _vocab_of(table, vocab)
    table = _read(table, v, plan, lambda: jnp.clip(ids, 0, v - 1))
    if _plan_shards(table, v, plan):
        from repro.embeddings.sharded import sharded_seq_lookup
        clipped = jnp.clip(ids, 0, v - 1)

        def psum_rows(uids):
            # dedup composes with the psum path: look the unique ids up
            # through the sharded gather (same (B, L) layout, so the data-
            # axis sharding contract holds), expand locally
            out = sharded_seq_lookup(
                table, uids.reshape(clipped.shape), mesh=plan.mesh, vocab=v,
                model_axis=plan.model_axis, batch_axes=plan.batch_axes,
                stats_dedup=True)
            return out.reshape(-1, out.shape[-1])

        # a compressed wire forces the dedup route: only the request's
        # unique rows ride the quantized psum, duplicates expand locally
        # from the reconstructed buffer (bit-identical expansion)
        if _want_dedup(v, clipped.size, dedup) or _compress_active():
            return dedup_gather(table, clipped, psum_rows)
        return sharded_seq_lookup(table, clipped, mesh=plan.mesh, vocab=v,
                                  model_axis=plan.model_axis,
                                  batch_axes=plan.batch_axes)
    return _gather(table, ids, v, dedup)


def row_lookup(table: Table, ids: jnp.ndarray, *, vocab: Optional[int] = None,
               plan=None, dedup: Optional[bool] = None) -> jnp.ndarray:
    """(B,) ids -> (B, D) single-row gather."""
    return seq_lookup(table, ids[:, None], vocab=vocab, plan=plan,
                      dedup=dedup)[:, 0, :]


def bag_lookup(table: Table, ids: JaggedTensor, pooling: str = "sum", *,
               plan=None, dedup: Optional[bool] = None) -> jnp.ndarray:
    """Jagged id-list bag -> (B, D). Sharded tables route through the psum
    bag (already reduction-before-communication — dedup would only grow the
    collective); local/proxy tables dedup-gather then pool."""
    v = _vocab_of(table, None)
    table = _read(table, v, plan, lambda: jnp.where(
        ids.segment_ids() < ids.batch_size, jnp.clip(ids.values, 0, v - 1), v))
    if not isinstance(table, GatheredTable) and pooling in ("sum", "mean") \
            and _plan_shards(table, v, plan):
        from repro.embeddings.sharded import sharded_jagged_bag_lookup
        return sharded_jagged_bag_lookup(table, ids, mesh=plan.mesh, vocab=v,
                                         pooling=pooling,
                                         model_axis=plan.model_axis)
    emb = _gather(table, ids.values, v, dedup)
    return bag_pool(emb, ids, pooling)


def bag_lookup_dense(table: Table, ids: jnp.ndarray, lengths: jnp.ndarray,
                     pooling: str = "sum", *, vocab: Optional[int] = None,
                     plan=None, dedup: Optional[bool] = None,
                     backend: Optional[str] = None,
                     out_sharded: Optional[bool] = None) -> jnp.ndarray:
    """Padded-layout bag: (B, L) ids + (B,) lengths -> (B, D).

    On TPU (or under an explicit ``backend``) unsharded tables route to the
    fused Pallas embedding-bag kernel (kernels/embedding_bag.py) — a
    :class:`GatheredTable` as the kernel over its gathered rows, with each
    id translated to its row there — unless dedup is forced (arg or
    "always" policy), which the per-slot DMA kernel cannot honor. The jnp
    path dedup-gathers then pools. ``max``
    pooling never routes to the psum bag (it cannot reassemble a max); on a
    plan-sharded table it falls back to the partitionable jnp gather.

    ``out_sharded=True`` declares that the consumer tolerates the output
    dim-sharded ``P(batch, model)`` — e.g. DLRM's dot interaction, which
    contracts over D — and routes a sharded table through the
    reduce-scatter lookup (``sharded_bag_lookup_rs``, half the collective
    bytes of the psum). Numerically the same bag; only the layout differs.
    """
    v = _vocab_of(table, vocab)
    table = _read(table, v, plan, lambda: jnp.where(
        jnp.arange(ids.shape[1])[None, :] < lengths[:, None],
        jnp.clip(ids, 0, v - 1), v))
    sharded = _plan_shards(table, v, plan)
    if pooling in ("sum", "mean") and sharded:
        from repro.embeddings.sharded import (sharded_bag_lookup,
                                              sharded_bag_lookup_rs)
        # clip first: the sharded partial-bag zeroes out-of-range ids while
        # the local path clips them — parity requires clip-then-shard
        clipped = jnp.clip(ids, 0, v - 1)
        n_model = plan.mesh.shape[plan.model_axis]
        d = int(table.shape[-1])
        if out_sharded and n_model > 1 and d % n_model == 0:
            return sharded_bag_lookup_rs(table, clipped, lengths,
                                         mesh=plan.mesh, vocab=v,
                                         pooling=pooling,
                                         model_axis=plan.model_axis,
                                         batch_axes=plan.batch_axes)
        return sharded_bag_lookup(table, clipped, lengths,
                                  mesh=plan.mesh, vocab=v, pooling=pooling,
                                  model_axis=plan.model_axis,
                                  batch_axes=plan.batch_axes)
    if not sharded and not _dedup_forced(dedup):
        from repro.kernels import dispatch
        be = dispatch.resolve_emb_backend(backend)
        if be != "jnp":
            from repro.kernels.embedding_bag import embedding_bag
            if isinstance(table, GatheredTable):
                return embedding_bag(table.rows, table.local(ids), lengths,
                                     pooling, backend=be)
            return embedding_bag(table, ids, lengths, pooling, backend=be)
    emb = _gather(table, ids, v, dedup)
    return bag_pool_dense(emb, lengths, pooling)


# ---------------------------------------------------------------------------
# The named collection: tables + feature routing in one object.
# ---------------------------------------------------------------------------

class EmbeddingCollection:
    """Named tables + feature -> table routing (the KJT-consuming entry
    point; DLRM's 26 fields are the canonical user)."""

    def __init__(self, cfg: EmbeddingCollectionConfig,
                 features: Tuple[FeatureSpec, ...]):
        self.cfg = cfg
        self.features = {f.name: f for f in features}
        for f in features:
            cfg.table(f.table)      # raises on a dangling route

    def init(self, rng: jax.Array, dtype=jnp.float32,
             scale: float = 0.01) -> Dict[str, jnp.ndarray]:
        return init_tables(rng, self.cfg, dtype, scale)

    def lookup(self, tables: Dict[str, Table], feature: str, ids,
               lengths: Optional[jnp.ndarray] = None, *, plan=None,
               dedup: Optional[bool] = None) -> jnp.ndarray:
        """One feature's lookup in its declared mode. ``ids`` is a
        JaggedTensor for "jagged", (B, L) [+ lengths] for "bag"/"seq",
        (B,) for "row"."""
        f = self.features[feature]
        t = self.cfg.table(f.table)
        tbl = tables[f.table]
        if f.kind == "jagged":
            return bag_lookup(tbl, ids, f.pooling, plan=plan, dedup=dedup)
        if f.kind == "bag":
            if lengths is None:
                lengths = jnp.full((ids.shape[0],), ids.shape[1], jnp.int32)
            return bag_lookup_dense(tbl, ids, lengths, f.pooling,
                                    vocab=t.vocab, plan=plan, dedup=dedup)
        if f.kind == "seq":
            return seq_lookup(tbl, ids, vocab=t.vocab, plan=plan, dedup=dedup)
        if f.kind == "row":
            return row_lookup(tbl, ids, vocab=t.vocab, plan=plan, dedup=dedup)
        raise ValueError(f"unknown lookup kind {f.kind!r}")

    def lookup_keyed(self, tables: Dict[str, Table], kj: KeyedJagged, *,
                     plan=None,
                     dedup: Optional[bool] = None) -> Dict[str, jnp.ndarray]:
        """Pooled bags for every jagged feature in a KeyedJagged bundle."""
        return {name: self.lookup(tables, name, kj[name], plan=plan,
                                  dedup=dedup)
                for name in kj.keys() if name in self.features}
