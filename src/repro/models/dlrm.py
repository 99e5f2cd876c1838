"""DLRM (Naumov et al. 2019) — MLPerf benchmark config, ROO-capable.

Assigned config (dlrm-mlperf): 13 dense features, 26 sparse fields,
embed_dim=128, bottom MLP 13-512-256-128 (ReLU after every layer, as in
the DLRM reference), dot interaction over 27 vectors
(128 + 351 = 479 features), top MLP 479-1024-1024-512-256-1, Criteo-1TB
vocabs capped at 40M rows (MLPerf Training v1).

ROO applicability (DESIGN.md §4): the 13 dense features and the user-side
subset of sparse fields are RO; item-side fields are NRO. Under ROO the
bottom MLP + RO lookups run at B_RO and fan out at the interaction.

A ``ROOBatch`` carries the fields in a fixed order: each request row of
``ro_sparse["user_ids"]`` holds its ``n_ro_fields * multi_hot`` ids, field
by field, and each impression slot of ``nro_sparse["item_cats"]`` the
item-side fields' ids the same way; padding rows hold none
(``dlrm_sample`` writes a sample in that layout, ``dlrm_batcher_config``
sizes the batcher for it).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.fanout import fanout
from repro.core.joiner import ROOSample
from repro.core.roo_batch import ROOBatch
from repro.data.batcher import BatcherConfig
from repro.embeddings.collection import (EmbeddingCollection,
                                         EmbeddingCollectionConfig,
                                         FeatureSpec, TableConfig,
                                         bag_lookup_dense)
from repro.models.interactions import dot_interaction
from repro.models.mlp import mlp_apply, mlp_init

# MLPerf Criteo-1TB row counts (capped at 40M rows, the v1 reference's
# max-ind-range; 187,767,399 rows in all, 96.1 GB in float32 at dim 128).
MLPERF_VOCABS = (
    39884406, 39043, 17289, 7420, 20263, 3, 7120, 1543, 63, 38532951,
    2953546, 403346, 10, 2208, 11938, 155, 4, 976, 14, 39979771,
    25641295, 39664984, 585935, 12972, 108, 36)


@dataclasses.dataclass(frozen=True)
class DLRMConfig:
    n_dense: int = 13
    embed_dim: int = 128
    bot_mlp: Tuple[int, ...] = (13, 512, 256, 128)   # input first
    top_mlp: Tuple[int, ...] = (1024, 1024, 512, 256, 1)  # after top_in_dim
    vocabs: Tuple[int, ...] = MLPERF_VOCABS
    n_ro_fields: int = 13       # first k sparse fields treated as user-side
    multi_hot: int = 1          # ids per field (MLPerf v1 is one-hot)

    @property
    def n_sparse(self) -> int:
        return len(self.vocabs)

    SHARD_MIN_ROWS = 65536      # tables below this are replicated
    ROW_PAD = 512               # sharded tables pad rows to this multiple

    def padded_vocab(self, v: int) -> int:
        if v < self.SHARD_MIN_ROWS:
            return v
        return ((v + self.ROW_PAD - 1) // self.ROW_PAD) * self.ROW_PAD

    def tables(self) -> EmbeddingCollectionConfig:
        return EmbeddingCollectionConfig(tuple(
            TableConfig(name=f"t{i}", vocab=self.padded_vocab(v),
                        dim=self.embed_dim,
                        side="ro" if i < self.n_ro_fields else "nro")
            for i, v in enumerate(self.vocabs)))

    def collection(self) -> EmbeddingCollection:
        """The named embedding entry point: one multi-hot bag feature per
        sparse field, routed to its table."""
        return EmbeddingCollection(self.tables(), tuple(
            FeatureSpec(name=f"f{i}", table=f"t{i}", kind="bag",
                        pooling="sum")
            for i in range(self.n_sparse)))

    def top_in_dim(self) -> int:
        f = self.n_sparse + 1
        return self.embed_dim + f * (f - 1) // 2


def _top_dims(cfg: DLRMConfig) -> Tuple[int, ...]:
    return (cfg.top_in_dim(),) + cfg.top_mlp


def dlrm_init(rng: jax.Array, cfg: DLRMConfig, dtype=jnp.float32) -> Dict:
    k1, k2, k3 = jax.random.split(rng, 3)
    return {
        "tables": cfg.collection().init(k1, dtype),
        "bot_mlp": mlp_init(k2, cfg.bot_mlp, dtype),
        "top_mlp": mlp_init(k3, _top_dims(cfg), dtype),
    }


def _field_lookup(params: Dict, cfg: DLRMConfig, ids: jnp.ndarray,
                  lengths: jnp.ndarray, fields, plan=None) -> jnp.ndarray:
    """ids: (B, n_fields, multi_hot) -> (B, n_fields, D).

    Routed through the embedding collection: dedup'd local gathers (or the
    Pallas bag kernel on TPU), and under an SPMD ``plan`` each row-sharded
    table's bag is an explicit collective over ``model`` — RO fields run at
    B_RO, so their collectives move B_RO·D instead of B_NRO·D bytes.
    ``out_sharded=True``: the only consumer is ``dot_interaction``, which
    contracts over D, so the field embeddings tolerate the dim-sharded
    layout and the collection routes sharded tables through the
    reduce-scatter lookup (half the bytes of the psum); GSPMD finishes the
    contraction with a small (B, F²) reduce instead of re-gathering
    (B, F, D)."""
    embs = []
    for j, i_field in enumerate(fields):
        tbl = params["tables"][f"t{i_field}"]
        embs.append(bag_lookup_dense(tbl, ids[:, j, :], lengths[:, j],
                                     plan=plan, out_sharded=True))
    return jnp.stack(embs, axis=1)


def dlrm_forward_from_embs(params: Dict, cfg: DLRMConfig,
                           ro_dense: jnp.ndarray,
                           ro_embs: jnp.ndarray, nro_embs: jnp.ndarray,
                           segment_ids: jnp.ndarray) -> jnp.ndarray:
    """Interaction + MLPs given already-gathered embeddings.

    ro_embs: (B_RO, n_ro_fields, D); nro_embs: (B_NRO, n_nro_fields, D).
    Split out so the sparse-update training path can differentiate wrt the
    gathered rows instead of the full tables.
    """
    with jax.named_scope("dlrm.bottom_mlp"):
        dense_out = mlp_apply(params["bot_mlp"], ro_dense,      # (B_RO, D)
                              final_activation=jax.nn.relu)
    with jax.named_scope("dlrm.fanout"):
        ro_pack = jnp.concatenate([dense_out[:, None, :], ro_embs], axis=1)
        ro_at_nro = fanout(ro_pack, segment_ids)                  # one fanout
    with jax.named_scope("dlrm.interaction"):
        sparse = jnp.concatenate([ro_at_nro[:, 1:, :], nro_embs], axis=1)
        z = dot_interaction(ro_at_nro[:, 0, :], sparse)
    with jax.named_scope("dlrm.top_mlp"):
        return mlp_apply(params["top_mlp"], z)[:, 0]


def dlrm_forward_roo(params: Dict, cfg: DLRMConfig,
                     ro_dense: jnp.ndarray,
                     ro_ids: jnp.ndarray, ro_lengths: jnp.ndarray,
                     nro_ids: jnp.ndarray, nro_lengths: jnp.ndarray,
                     segment_ids: jnp.ndarray, plan=None) -> jnp.ndarray:
    """ROO path: user side at B_RO, fanned out once.

    ro_dense: (B_RO, 13); ro_ids: (B_RO, n_ro_fields, mh);
    nro_ids: (B_NRO, n_nro_fields, mh). Returns (B_NRO,) logits.
    """
    with jax.named_scope("dlrm.lookup_ro"):
        ro_embs = _field_lookup(params, cfg, ro_ids, ro_lengths,
                                range(cfg.n_ro_fields), plan)
    with jax.named_scope("dlrm.lookup_nro"):
        nro_embs = _field_lookup(params, cfg, nro_ids, nro_lengths,
                                 range(cfg.n_ro_fields, cfg.n_sparse), plan)
    return dlrm_forward_from_embs(params, cfg, ro_dense, ro_embs, nro_embs,
                                  segment_ids)


def dlrm_forward_impression(params: Dict, cfg: DLRMConfig,
                            dense: jnp.ndarray, ids: jnp.ndarray,
                            lengths: jnp.ndarray, plan=None) -> jnp.ndarray:
    """Impression-level baseline: everything at B_NRO.

    dense: (B, 13); ids: (B, 26, mh). Returns (B,) logits.
    """
    n = cfg.n_ro_fields
    with jax.named_scope("dlrm.bottom_mlp"):
        dense_out = mlp_apply(params["bot_mlp"], dense,
                              final_activation=jax.nn.relu)
    with jax.named_scope("dlrm.lookup_ro"):
        ro_embs = _field_lookup(params, cfg, ids[:, :n], lengths[:, :n],
                                range(n), plan)
    with jax.named_scope("dlrm.lookup_nro"):
        nro_embs = _field_lookup(params, cfg, ids[:, n:], lengths[:, n:],
                                 range(n, cfg.n_sparse), plan)
    with jax.named_scope("dlrm.interaction"):
        z = dot_interaction(dense_out,
                            jnp.concatenate([ro_embs, nro_embs], axis=1))
    with jax.named_scope("dlrm.top_mlp"):
        return mlp_apply(params["top_mlp"], z)[:, 0]


# ---------------------------------------------------------------------------
# ROOBatch entry points
# ---------------------------------------------------------------------------

# the id-list keys ROOBatcher packs (data/batcher.py)
RO_KEY, NRO_KEY = "user_ids", "item_cats"


def _mix64(x: np.ndarray) -> np.ndarray:
    """splitmix64's finalizer: a fixed, well-spread map of uint64s."""
    with np.errstate(over="ignore"):
        x = x ^ (x >> np.uint64(30))
        x = x * np.uint64(0xBF58476D1CE4E5B9)
        x = x ^ (x >> np.uint64(27))
        x = x * np.uint64(0x94D049BB133111EB)
        return x ^ (x >> np.uint64(31))


def _hashed_ids(cfg: DLRMConfig, key: int, fields: range) -> List[int]:
    mh = cfg.multi_hot
    slots = np.arange(fields.start * mh, fields.stop * mh, dtype=np.uint64)
    vocab = np.repeat(np.asarray(cfg.vocabs[fields.start:fields.stop],
                                 np.uint64), mh)
    return (_mix64((np.uint64(key) << np.uint64(16)) | slots)
            % vocab).astype(np.int64).tolist()


def dlrm_sample(sample: ROOSample, cfg: DLRMConfig) -> ROOSample:
    """An event-stream sample in DLRM's field layout: its first ``n_dense``
    dense features (zero-padded), the user-side fields' ids hashed from its
    user id and each impression's item-side ids from its item id, so a user
    or an item keeps its rows across requests. The history is dropped:
    DLRM reads none."""
    dense = np.zeros((cfg.n_dense,), np.float32)
    ro = np.asarray(sample.ro_dense, np.float32).ravel()[:cfg.n_dense]
    dense[:ro.shape[0]] = ro
    nro = range(cfg.n_ro_fields, cfg.n_sparse)
    return dataclasses.replace(
        sample, ro_dense=dense,
        ro_idlist=_hashed_ids(cfg, sample.user_id, range(cfg.n_ro_fields)),
        history_ids=[], history_actions=[],
        item_idlist=[_hashed_ids(cfg, item, nro) for item in sample.item_ids])


def dlrm_batcher_config(cfg: DLRMConfig, base: BatcherConfig
                        ) -> BatcherConfig:
    """``base`` with id-list capacities that hold every field id of a full
    batch (smaller ones would truncate), and no history window."""
    mh = cfg.multi_hot
    return dataclasses.replace(
        base, hist_len=0,
        ro_idlist_capacity=base.b_ro * cfg.n_ro_fields * mh,
        item_idlist_capacity=base.b_nro * (cfg.n_sparse - cfg.n_ro_fields)
        * mh)


def dlrm_batch_fields(cfg: DLRMConfig, batch: ROOBatch):
    """(ro_ids (B_RO, n_ro_fields, mh), ro_lengths (B_RO, n_ro_fields),
    nro_ids (B_NRO, n_nro_fields, mh), nro_lengths) of a batch in DLRM's
    field layout; a padding row's bags are empty."""
    mh = cfg.multi_hot

    def fields(jt, n):
        ids, mask = jt.to_padded(n * mh)
        b = ids.shape[0]
        return (ids.reshape(b, n, mh),
                jnp.sum(mask.reshape(b, n, mh), axis=-1, dtype=jnp.int32))
    return (fields(batch.ro_sparse[RO_KEY], cfg.n_ro_fields)
            + fields(batch.nro_sparse[NRO_KEY],
                     cfg.n_sparse - cfg.n_ro_fields))


def dlrm_logits_roo(params: Dict, cfg: DLRMConfig, batch: ROOBatch,
                    plan=None) -> jnp.ndarray:
    """(B_NRO,) click logits of a ROOBatch (``dlrm_forward_roo``)."""
    ro_ids, ro_len, nro_ids, nro_len = dlrm_batch_fields(cfg, batch)
    return dlrm_forward_roo(params, cfg, batch.ro_dense, ro_ids, ro_len,
                            nro_ids, nro_len, batch.segment_ids, plan=plan)


def dlrm_loss(params: Dict, cfg: DLRMConfig, batch: ROOBatch,
              plan=None) -> jnp.ndarray:
    """Binary cross-entropy of the click logit (``labels[:, 0]``), the mean
    over the batch's real impressions."""
    logits = dlrm_logits_roo(params, cfg, batch, plan=plan)
    y = batch.labels[:, 0].astype(logits.dtype)
    w = batch.impression_mask().astype(logits.dtype)
    bce = (jnp.maximum(logits, 0) - logits * y
           + jnp.log1p(jnp.exp(-jnp.abs(logits))))
    return jnp.sum(bce * w) / jnp.maximum(jnp.sum(w), 1.0)


def dlrm_flops_per_example(cfg: DLRMConfig) -> int:
    """Analytic dense forward FLOPs per impression (impression-level)."""
    from repro.models.mlp import mlp_flops
    f = cfg.n_sparse + 1
    return (mlp_flops(cfg.bot_mlp, 1) + mlp_flops(_top_dims(cfg), 1)
            + 2 * f * f * cfg.embed_dim)
