"""Sparse embedding gradients: COO row gradients + the gathered-rows proxy.

A dense gradient of a ``(V, D)`` table is a ``(V, D)`` array on every step,
and row-wise Adagrad then reads and writes all V rows even though a batch
touches a few thousand. This module keeps the sparse structure alive end to
end; it is the train step's default (``train/loop.make_train_step``):

  * :class:`SparseRows` — a registered pytree holding a COO row gradient
    ``(ids, rows)`` for a ``(vocab, D)`` table. It flows through
    ``value_and_grad`` output trees, the grad-accumulation scan in
    ``train/loop.py`` (stacked along the scan axis, then flattened), and the
    sparse apply path of ``train/optim.rowwise_adagrad``.
  * :class:`GatheredTable` — the request's unique rows of a table, gathered
    once from HBM. It quacks like the ``(V, D)`` array for every lookup in
    ``embeddings/collection.py``, so model code is identical in dense and
    sparse mode; differentiating w.r.t. its ``rows`` yields exactly the
    touched-row gradient.
  * :class:`TableReads` — a table in the step's recording pass: the
    collection's lookups log the ids they read, any other use marks the
    table as read densely.
  * :func:`touched_rows_value_and_grad` — the step's ``value_and_grad``:
    finds the tables and their ids by a recording pass, then differentiates
    through gathered rows. The returned grads tree carries
    :class:`SparseRows` at every such table and plain dense arrays
    everywhere else.

Why a proxy instead of a ``custom_vjp`` that returns ``SparseRows`` for a
dense table argument: JAX requires a cotangent structurally identical to the
primal, so a ``(V, D)`` input can only ever receive a ``(V, D)`` cotangent.
Gathering first and differentiating w.r.t. the gathered rows is the one
shape under which the sparsity legally survives the autodiff boundary —
the same reason TorchRec keeps its embedding backward fused.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.obs import metrics as obs_metrics


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class SparseRows:
    """COO row-sparse gradient of a ``(vocab, D)`` embedding table.

    ``ids[i]`` is the table row that ``rows[i]`` contributes to; ids may
    repeat (contributions add, matching dense scatter semantics) and entries
    with ``ids == vocab`` are padding (dropped by every consumer).
    ``unique=True`` (static) marks ids as already unique+sorted — the
    layout ``gather_table`` produces — letting :meth:`merged` skip its
    per-step sort; producers that concatenate or stack COO entries must
    leave it False.
    """

    ids: jnp.ndarray     # (N,) int32; vocab == padding sentinel
    rows: jnp.ndarray    # (N, D) float contributions
    vocab: int           # static table height
    unique: bool = False

    def tree_flatten(self):
        return (self.ids, self.rows), (self.vocab, self.unique)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], *aux)

    @property
    def shape(self) -> Tuple[int, ...]:
        return (self.vocab,) + tuple(self.rows.shape[1:])

    @property
    def dtype(self):
        return self.rows.dtype

    def merged(self) -> "SparseRows":
        """Duplicate-id merge: unique ids, contributions segment-summed.

        The result has the same static capacity (padded with the ``vocab``
        sentinel) so it stays jit-stable; padding rows are zero. A no-op
        for already-unique COO (the single-batch sparse training path).
        """
        if self.unique:
            return self
        n = self.ids.shape[0]
        uids, inv = jnp.unique(self.ids, size=n, fill_value=self.vocab,
                               return_inverse=True)
        rows = jnp.zeros_like(self.rows).at[inv.reshape(-1)].add(self.rows)
        return SparseRows(uids.astype(jnp.int32), rows, self.vocab,
                          unique=True)

    def to_dense(self) -> jnp.ndarray:
        """Densify to the ``(vocab, D)`` scatter-add — for parity tests and
        the dense cotangent of kernels/embedding_bag.py."""
        out = jnp.zeros(self.shape, self.rows.dtype)
        return out.at[self.ids].add(self.rows, mode="drop")


def is_sparse(x) -> bool:
    return isinstance(x, SparseRows)


def sq_sum(g) -> jnp.ndarray:
    """Sum of squared gradient entries for one grads leaf (SparseRows or
    dense) — the grad-norm term ``train/loop.py`` logs. SparseRows rows are
    squared as they stand, so the sum is exact once duplicate ids are
    merged (the train step merges before it asks)."""
    if is_sparse(g):
        return jnp.sum(jnp.square(g.rows.astype(jnp.float32)))
    return jnp.sum(jnp.square(g.astype(jnp.float32)))


# ---------------------------------------------------------------------------
# Grad-accumulation support: split a grads tree into its dense part (scan
# carry) and its SparseRows part (scan ys, stacked then flattened).
# ---------------------------------------------------------------------------

def split_sparse(grads):
    """-> (dense_tree, sparse_tree); each has None at the other's slots."""
    dense = jax.tree_util.tree_map(lambda g: None if is_sparse(g) else g,
                                   grads, is_leaf=is_sparse)
    sparse = jax.tree_util.tree_map(lambda g: g if is_sparse(g) else None,
                                    grads, is_leaf=is_sparse)
    return dense, sparse


def merge_sparse(dense, sparse):
    """Inverse of :func:`split_sparse` given congruent trees."""
    if sparse is None:
        return dense
    if dense is None:
        return sparse
    if isinstance(dense, dict):
        return {k: merge_sparse(dense.get(k), sparse.get(k))
                for k in set(dense) | set(sparse)}
    if isinstance(dense, (list, tuple)):
        return type(dense)(merge_sparse(d, s) for d, s in zip(dense, sparse))
    return dense


def flatten_stacked(sparse_stacked, scale: float = 1.0):
    """Collapse scan-stacked SparseRows — ids (M, N), rows (M, N, D) — back
    into flat COO, scaling rows (the 1/microbatches mean). Stacking
    reintroduces duplicate ids across microbatches, so the result is
    NOT marked unique (the optimizer's merge folds them)."""
    def leaf(g):
        if not is_sparse(g):
            return g
        d = g.rows.shape[2:]
        return SparseRows(g.ids.reshape(-1),
                          g.rows.reshape((-1,) + d) * scale, g.vocab)
    return jax.tree_util.tree_map(leaf, sparse_stacked, is_leaf=is_sparse)


def concat_sparse(sparse_parts, scale: float = 1.0):
    """Concatenate per-microbatch SparseRows trees into flat COO — the
    unrolled-loop analogue of :func:`flatten_stacked` for the overlapped
    accumulation path (``comms_overlap=on``): a COO sum IS concatenation.
    Entries land in microbatch order, the exact order scan-stack +
    flatten produces, so the two accumulation paths stay comparable.
    This deferred concatenation is what coalesces the SparseRows grad
    exchange to once per step. Not marked unique (the optimizer's merge
    folds cross-microbatch duplicates)."""
    def leaf(*gs):
        if not is_sparse(gs[0]):
            return gs[0]
        return SparseRows(jnp.concatenate([g.ids for g in gs]),
                          jnp.concatenate([g.rows for g in gs]) * scale,
                          gs[0].vocab)
    return jax.tree_util.tree_map(leaf, *sparse_parts, is_leaf=is_sparse)


# ---------------------------------------------------------------------------
# GatheredTable: the lookup-side proxy.
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class GatheredTable:
    """The unique rows of one table touched by the current batch.

    ``uids`` is sorted ascending with ``vocab`` sentinels padding the tail
    (the ``jnp.unique(..., size=, fill_value=vocab)`` layout); ``row_of``
    maps each id of the table to its row in ``rows`` (:meth:`local`), one
    gather per lookup. Through :meth:`take`, ids absent from ``uids`` read
    as zero rows — structurally impossible when the gathered ids cover the
    loss's lookups, which the recording pass guarantees.
    """

    uids: jnp.ndarray    # (N,) int32 sorted; vocab == padding
    rows: jnp.ndarray    # (N, D)
    row_of: jnp.ndarray  # (vocab,) int32; 0 for an absent id
    vocab: int

    def tree_flatten(self):
        return (self.uids, self.rows, self.row_of), self.vocab

    @classmethod
    def tree_unflatten(cls, vocab, children):
        return cls(*children, vocab)

    @property
    def shape(self) -> Tuple[int, ...]:
        return (self.vocab,) + tuple(self.rows.shape[1:])

    @property
    def dtype(self):
        return self.rows.dtype

    def local(self, ids: jnp.ndarray) -> jnp.ndarray:
        """Each id's row in ``rows`` (clipped into the table like a lookup;
        an absent id lands on row 0)."""
        return jnp.take(self.row_of, jnp.clip(ids, 0, self.vocab - 1))

    def take(self, ids: jnp.ndarray) -> jnp.ndarray:
        """``jnp.take(table, ids, axis=0)`` semantics for in-range ids."""
        ids = jnp.clip(ids, 0, self.vocab - 1).astype(jnp.int32)
        pos = self.local(ids)
        hit = jnp.take(self.uids, pos) == ids
        emb = jnp.take(self.rows, pos, axis=0)
        return emb * hit[..., None].astype(emb.dtype)


class TableReads:
    """One table in the step's recording pass (:func:`touched_rows_value_and_grad`).

    The lookups of ``embeddings/collection.py`` log the ids they read
    through :meth:`read`; any other use — arithmetic, a matmul, ``.T``,
    ``jnp.asarray`` — goes through :meth:`whole`, which marks the table as
    read densely. Either way the caller gets the table itself, so the pass
    computes what the loss computes.
    """

    def __init__(self, table: jnp.ndarray):
        self.table = table
        self.ids: List[jnp.ndarray] = []
        self.dense = False

    @property
    def shape(self) -> Tuple[int, ...]:
        return self.table.shape

    @property
    def dtype(self):
        return self.table.dtype

    @property
    def ndim(self) -> int:
        return self.table.ndim

    def read(self, ids: jnp.ndarray) -> jnp.ndarray:
        """Log one lookup's ids, in [0, vocab]: ``vocab`` marks a slot the
        lookup does not read."""
        self.ids.append(ids.reshape(-1).astype(jnp.int32))
        return self.table

    def whole(self) -> jnp.ndarray:
        self.dense = True
        return self.table

    def __jax_array__(self):
        return self.whole()

    def __getattr__(self, name):
        if name.startswith("_"):
            raise AttributeError(name)
        return getattr(self.whole(), name)


def _read_whole(op: str):
    return lambda self, *args: getattr(self.whole(), op)(*args)


for _op in ("__add__", "__radd__", "__sub__", "__rsub__", "__mul__",
            "__rmul__", "__truediv__", "__rtruediv__", "__matmul__",
            "__rmatmul__", "__pow__", "__neg__", "__getitem__", "__iter__",
            "__len__"):
    setattr(TableReads, _op, _read_whole(_op))


# ---------------------------------------------------------------------------
# The sparse training entry points.
# ---------------------------------------------------------------------------

def gather_table(table: jnp.ndarray, ids: jnp.ndarray) -> GatheredTable:
    """Dedup-gather the batch's rows of one table: unique ids (one HBM read
    per distinct id) -> :class:`GatheredTable`. ``ids`` lie in [0, vocab];
    ``vocab`` marks a slot no lookup reads."""
    vocab = table.shape[0]
    flat = ids.reshape(-1).astype(jnp.int32)
    uids = jnp.unique(flat, size=flat.shape[0],
                      fill_value=vocab).astype(jnp.int32)
    n = uids.shape[0]
    rows = jnp.take(table, jnp.minimum(uids, vocab - 1), axis=0, mode="clip")
    # padding slots get distinct indices past the table: the targets are
    # then sorted and distinct, and every padding slot drops
    slots = jnp.where(uids < vocab, uids,
                      vocab + jnp.arange(n, dtype=jnp.int32))
    row_of = jnp.zeros((vocab,), jnp.int32).at[slots].set(
        jnp.arange(n, dtype=jnp.int32), mode="drop", indices_are_sorted=True,
        unique_indices=True)
    return GatheredTable(uids, rows, row_of, vocab)


# tables below this stay dense: gathering + sorting a batch worth of COO
# rows to update a handful of table rows costs more than the dense apply it
# replaces (same reasoning as spmd.SHARD_MIN_ROWS)
SPARSE_MIN_VOCAB = 64

# a table takes the touched rows only while a step's id capacity (every id
# slot its lookups read, padding included: the shapes are static) is at most
# this share of its rows. Above it the sort, gathers and scatters over the
# capacity cost more than the dense passes over the table that they save
# (measured on a TPU v5e: PERF.md, PR 16).
TOUCHED_MAX_FILL = 1 / 16


def _value_and_grad_rows(loss_fn: Callable, params, batch, rng,
                         ids_at: Dict[int, jnp.ndarray]):
    """``value_and_grad`` of ``loss_fn`` with the tables at flat leaf
    positions ``ids_at`` ({position: ids}) differentiated through their
    gathered rows: their gradients are :class:`SparseRows`, every other
    leaf's is dense."""
    leaves, treedef = jax.tree.flatten(params)
    gathered = {i: gather_table(leaves[i], ids) for i, ids in ids_at.items()}
    # tables leave the differentiated tree entirely: a replaced-but-present
    # (V, D) leaf would come back as a dense zeros gradient, which is the
    # exact allocation this path exists to avoid
    rest = [None if i in gathered else l for i, l in enumerate(leaves)]

    def inner(rows, rest):
        full = [dataclasses.replace(gathered[i], rows=rows[i])
                if i in rows else l for i, l in enumerate(rest)]
        return loss_fn(jax.tree.unflatten(treedef, full), batch, rng)

    loss, (g_rows, g_rest) = jax.value_and_grad(inner, argnums=(0, 1))(
        {i: g.rows for i, g in gathered.items()}, rest)
    grads = [SparseRows(gathered[i].uids, g_rows[i], gathered[i].vocab,
                        unique=True) if i in gathered else g
             for i, g in enumerate(g_rest)]
    return loss, jax.tree.unflatten(treedef, grads)


def _record(loss_fn: Callable, leaves, treedef, picks, batch, rng
            ) -> Dict[int, jnp.ndarray]:
    """Run the loss once with the tables at ``picks`` behind
    :class:`TableReads`: {position: every id its lookups read} for each
    table the loss read only through lookups."""
    reads = {i: TableReads(leaves[i]) for i in picks}
    loss_fn(jax.tree.unflatten(
        treedef, [reads.get(i, l) for i, l in enumerate(leaves)]), batch, rng)
    return {i: jnp.concatenate(r.ids) for i, r in reads.items()
            if r.ids and not r.dense}


def touched_rows_value_and_grad(loss_fn: Callable,
                                rows_mask: Optional[Callable],
                                local: bool = True) -> Callable:
    """The train step's default ``value_and_grad``.

    A params leaf gets its gradient as the rows the batch touched
    (:class:`SparseRows`) when the optimizer takes it in that form
    (``rows_mask(params)``, ``train/optim.Optimizer.rows_mask``), it has at
    least ``SPARSE_MIN_VOCAB`` rows, the step is ``local`` (no enabled
    sharding plan), the loss reads it only through the collection's
    lookups, and those lookups read at most ``TOUCHED_MAX_FILL`` ids a row
    of the table. Every other leaf gets its dense gradient.

    The last two conditions and the ids are found at trace time: the loss
    runs once with those tables behind :class:`TableReads`. That pass's
    outputs are unused, so the compiler drops everything but the ids, which
    feed the dedup gather. A use the stand-in cannot follow (a JAX
    transform, a ``jnp`` function that refuses it) fails that pass; the
    tables are then recorded one at a time, and one that fails alone stays
    dense.

    Records the number of row-wise tables on each path in the
    ``repro.obs`` gauge ``train.tables`` (``path=touched_rows|dense``).
    """
    def vag(params, batch, rng):
        leaves, treedef = jax.tree.flatten(params)
        tables = (rows_mask(params) if rows_mask is not None
                  else [False] * len(leaves))
        picks = [i for i, (l, t) in enumerate(zip(leaves, tables))
                 if local and t and l.ndim == 2
                 and l.shape[0] >= SPARSE_MIN_VOCAB]
        try:
            ids_at = _record(loss_fn, leaves, treedef, picks, batch, rng)
        except (TypeError, ValueError):
            ids_at = {}
            for i in picks:
                try:
                    ids_at.update(_record(loss_fn, leaves, treedef, [i],
                                          batch, rng))
                except (TypeError, ValueError):
                    pass
        ids_at = {i: ids for i, ids in ids_at.items()
                  if ids.shape[0] <= TOUCHED_MAX_FILL * leaves[i].shape[0]}
        gauge = obs_metrics.gauge("train.tables")
        gauge.set(len(ids_at), path="touched_rows")
        gauge.set(sum(tables) - len(ids_at), path="dense")
        if not ids_at:
            return jax.value_and_grad(loss_fn)(params, batch, rng)
        return _value_and_grad_rows(loss_fn, params, batch, rng, ids_at)

    return vag

