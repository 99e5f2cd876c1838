"""Pallas TPU kernel: trainable embedding-bag (gather + pool) via scalar
prefetch, with a ``jax.custom_vjp`` backward that emits COO row gradients.

JAX has no native EmbeddingBag; the jnp path (take + masked reduce)
round-trips (B·L, D) gathered rows through HBM. The forward uses the
TPU-native pattern: the id matrix is *scalar-prefetched*, and the table row
for (b, l) is selected by the BlockSpec ``index_map`` itself — the DMA
engine streams exactly the needed (1, D) rows HBM->VMEM while the
accumulator for batch row b stays resident in VMEM across the L inner
steps. Grid: (B, L); out block (1, D) revisited over l with in-place
accumulation (sum/mean) or running max.

Backward: the gradient of a pooled bag w.r.t. the table is row-sparse —
slot (b, l) contributes ``w(b, l) * d_out[b]`` to row ``ids[b, l]`` and
nothing anywhere else. The backward kernel therefore materializes the
(B·L, D) COO *contribution rows* (weight: validity for sum, validity/len
for mean; recomputed argmax indicator for max, done on the jnp side since
it re-reads the gathered values), wraps them as
``embeddings.sparse.SparseRows`` with the slot ids as coordinates, and
densifies only at the very end because the custom_vjp cotangent contract
demands a (V, D) array for a (V, D) primal. (The train step's default
touched-rows path runs this kernel over a batch's gathered rows, with ids
translated to positions there, so that densify is (unique rows, D), never
the table: ``embeddings/collection.bag_lookup_dense``.)

Backend selection follows ``kernels/dispatch.py`` exactly like HSTU
(explicit arg > ``use_emb_backend`` > ``set_default_emb_backend`` >
``REPRO_EMB_BACKEND`` > auto: pallas on TPU, jnp elsewhere); there is no
hardcoded interpret default.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.embeddings.sparse import SparseRows

# statics = (pooling, interpret)


def _sum_kernel(ids_ref, len_ref, table_ref, o_ref):
    b = pl.program_id(0)
    l = pl.program_id(1)

    @pl.when(l == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(l < len_ref[b])
    def _acc():
        o_ref[...] += table_ref[...].astype(o_ref.dtype)


def _max_kernel(ids_ref, len_ref, table_ref, o_ref, *, neg: float):
    b = pl.program_id(0)
    l = pl.program_id(1)

    @pl.when(l == 0)
    def _init():
        o_ref[...] = jnp.full_like(o_ref, neg)

    @pl.when(l < len_ref[b])
    def _acc():
        o_ref[...] = jnp.maximum(o_ref[...], table_ref[...].astype(o_ref.dtype))


def _bwd_coo_kernel(ids_ref, len_ref, g_ref, o_ref, *, mean: bool):
    """COO contribution rows for sum/mean pooling: block (b, l) writes
    ``w * d_out[b]`` where w = [l < len_b] (sum) or [l < len_b]/len_b
    (mean). Each output block is written exactly once (no revisit)."""
    b = pl.program_id(0)
    l = pl.program_id(1)
    w = (l < len_ref[b]).astype(jnp.float32)
    if mean:
        w = w / jnp.maximum(len_ref[b], 1).astype(jnp.float32)
    o_ref[...] = (g_ref[...].astype(jnp.float32) * w).astype(o_ref.dtype)


def _fwd_call(statics, table, safe_ids, lengths):
    pooling, interpret = statics
    b, l = safe_ids.shape
    v, d = table.shape
    if pooling == "max":
        kernel = functools.partial(
            _max_kernel, neg=float(jnp.finfo(table.dtype).min))
    else:
        kernel = _sum_kernel
    # rows are viewed as (1, d) planes of 3-D arrays: a (1, d) block is
    # then the full extent of the last two dims, which the TPU's (8, 128)
    # tiling rule accepts for any d
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, l),
            in_specs=[
                # the scalar-prefetched id picks the table row block to DMA
                pl.BlockSpec((1, 1, d),
                             lambda bi, li, ids, lens: (ids[bi, li], 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, d),
                                   lambda bi, li, ids, lens: (bi, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((b, 1, d), table.dtype),
        interpret=interpret,
        name="embedding_bag_fwd",
    )(safe_ids, lengths, table.reshape(v, 1, d)).reshape(b, d)
    if pooling == "mean":
        out = out / jnp.maximum(lengths, 1).astype(out.dtype)[:, None]
    elif pooling == "max":
        out = jnp.where((lengths > 0)[:, None], out, jnp.zeros_like(out))
    return out


def _bwd_coo_rows(statics, table, safe_ids, lengths, out, g):
    """(B*L, D) COO contribution rows for d table, one per id slot."""
    pooling, interpret = statics
    b, l = safe_ids.shape
    d = table.shape[1]
    if pooling == "max":
        # argmax indicator needs the gathered values back; even tie-split
        # matches the oracle's max VJP
        emb = jnp.take(table, safe_ids.reshape(-1), axis=0).reshape(b, l, d)
        valid = jnp.arange(l)[None, :] < lengths[:, None]
        hit = (emb == out[:, None, :]) & valid[:, :, None]
        cnt = jnp.maximum(jnp.sum(hit, axis=1, keepdims=True), 1)
        rows = (hit / cnt).astype(jnp.float32) * g[:, None, :].astype(
            jnp.float32)
        return rows.reshape(b * l, d).astype(table.dtype)
    rows = pl.pallas_call(
        functools.partial(_bwd_coo_kernel, mean=(pooling == "mean")),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, l),
            in_specs=[
                pl.BlockSpec((1, 1, d), lambda bi, li, ids, lens: (bi, 0, 0)),
            ],
            out_specs=pl.BlockSpec(
                (1, 1, d),
                lambda bi, li, ids, lens, _l=l: (bi * _l + li, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((b * l, 1, d), table.dtype),
        interpret=interpret,
        name="embedding_bag_bwd_rows",
    )(safe_ids, lengths, g.reshape(b, 1, d))
    return rows.reshape(b * l, d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _bag_fused(statics, table, safe_ids, lengths):
    return _fwd_call(statics, table, safe_ids, lengths)


def _bag_fused_fwd(statics, table, safe_ids, lengths):
    out = _fwd_call(statics, table, safe_ids, lengths)
    return out, (table, safe_ids, lengths, out)


def _bag_fused_bwd(statics, res, g):
    table, safe_ids, lengths, out = res
    coo = embedding_bag_coo_grad(statics, table, safe_ids, lengths, out, g)
    zero_ids = np.zeros(safe_ids.shape, jax.dtypes.float0)
    zero_len = np.zeros(lengths.shape, jax.dtypes.float0)
    return coo.to_dense(), zero_ids, zero_len


_bag_fused.defvjp(_bag_fused_fwd, _bag_fused_bwd)


def embedding_bag_coo_grad(statics, table, safe_ids, lengths, out,
                           g) -> SparseRows:
    """The kernel backward in its native form: COO row gradients keyed by
    the slot ids (invalid slots padded to the ``vocab`` sentinel so every
    consumer drops them)."""
    b, l = safe_ids.shape
    v = table.shape[0]
    rows = _bwd_coo_rows(statics, table, safe_ids, lengths, out, g)
    valid = (jnp.arange(l)[None, :] < lengths[:, None]).reshape(-1)
    ids = jnp.where(valid, safe_ids.reshape(-1), v).astype(jnp.int32)
    return SparseRows(ids, rows, v)


def embedding_bag(table: jnp.ndarray, ids: jnp.ndarray, lengths: jnp.ndarray,
                  pooling: str = "sum",
                  backend: Optional[str] = None) -> jnp.ndarray:
    """table: (V, D); ids: (B, L) int; lengths: (B,). Returns (B, D) pooled
    embeddings (sum | mean | max); empty bags give zeros. Differentiable
    w.r.t. ``table``. ``backend`` resolves through kernels/dispatch.py when
    None (pallas on TPU, jnp elsewhere, REPRO_EMB_BACKEND honored)."""
    from repro.kernels import dispatch
    be = dispatch.resolve_emb_backend(backend)
    if pooling not in ("sum", "mean", "max"):
        raise ValueError(f"unknown pooling {pooling!r}")
    if be == "jnp":
        from repro.kernels.ref import embedding_bag_ref
        return embedding_bag_ref(table, ids, lengths, pooling)
    b, l = ids.shape
    v, _ = table.shape
    safe_ids = jnp.where(
        jnp.arange(l)[None, :] < lengths[:, None],
        jnp.clip(ids, 0, v - 1), 0).astype(jnp.int32)
    statics = (pooling, be == "pallas-interpret")
    return _bag_fused(statics, table, safe_ids, lengths.astype(jnp.int32))
