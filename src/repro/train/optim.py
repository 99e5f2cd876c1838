"""In-repo optimizers (no external deps): Adam, row-wise Adagrad, SGD.

Row-wise Adagrad is the production embedding optimizer (one accumulator
scalar per table ROW instead of per element — 1/D the state, the TorchRec
default for huge tables); Adam handles the dense parameters. ``make_mixed``
routes by parameter path, which is exactly how DLRM deployments configure it.

Row-wise Adagrad additionally takes **sparse row gradients**: a grads leaf
may be a ``repro.embeddings.sparse.SparseRows`` (COO, from the train step's
touched-rows gradient), in which case duplicates are segment-sum merged and
only the touched rows of the accumulator and the table are read and
written — per-row arithmetic is bit-identical to the dense apply
(tests/test_embeddings.py asserts exact equality), untouched rows never
move through memory. ``Optimizer.rows_mask`` says which leaves those are.

Every ``update`` takes the train step's non-finite guard ``ok`` (a traced
bool, or None for no guard): on a skipped step each leaf and its state stay
as they were. Dense leaves select between the new and the old value; a
``SparseRows`` leaf drops its ids instead, so a skipped step costs no pass
over the table.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.embeddings.sparse import SparseRows, is_sparse


class Optimizer(NamedTuple):
    init: Callable
    # (grads, state, params, ok=None) -> (new_params, new_state)
    update: Callable
    # params -> one bool per leaf: True where ``update`` takes a SparseRows
    # gradient; None: at no leaf
    rows_mask: Optional[Callable] = None


def _keep(ok, new, old):
    """The non-finite guard on a dense leaf: ``new`` unless the step is
    skipped."""
    return new if ok is None else jnp.where(ok, new, old)


def adam(lr: float = 1e-3, b1: float = 0.9, b2: float = 0.999,
         eps: float = 1e-8, weight_decay: float = 0.0,
         grad_clip: float = 0.0) -> Optimizer:
    def init(params):
        zeros = jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params)
        return {"m": zeros,
                "v": jax.tree.map(jnp.zeros_like, zeros),
                "t": jnp.zeros((), jnp.int32)}

    def update(grads, state, params, ok=None):
        if grad_clip > 0:
            gn = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                              for g in jax.tree.leaves(grads)) + 1e-12)
            scale = jnp.minimum(1.0, grad_clip / gn)
            grads = jax.tree.map(lambda g: g * scale, grads)
        t = state["t"] + 1
        bc1 = 1 - b1 ** t.astype(jnp.float32)
        bc2 = 1 - b2 ** t.astype(jnp.float32)

        def upd(p, g, m0, v0):
            g32 = g.astype(jnp.float32)
            m = b1 * m0 + (1 - b1) * g32
            v = b2 * v0 + (1 - b2) * g32 * g32
            step = lr * (m / bc1) / (jnp.sqrt(v / bc2) + eps)
            if weight_decay:
                step = step + lr * weight_decay * p.astype(jnp.float32)
            new_p = (p.astype(jnp.float32) - step).astype(p.dtype)
            return _keep(ok, new_p, p), _keep(ok, m, m0), _keep(ok, v, v0)

        out = jax.tree.map(upd, params, grads, state["m"], state["v"])
        new_p = jax.tree.map(lambda o: o[0], out,
                             is_leaf=lambda x: isinstance(x, tuple))
        new_m = jax.tree.map(lambda o: o[1], out,
                             is_leaf=lambda x: isinstance(x, tuple))
        new_v = jax.tree.map(lambda o: o[2], out,
                             is_leaf=lambda x: isinstance(x, tuple))
        return new_p, {"m": new_m, "v": new_v,
                       "t": _keep(ok, t, state["t"])}

    return Optimizer(init, update)


def _rowwise_sparse_apply(p, g: SparseRows, a, lr: float, eps: float,
                          ok=None):
    """Touched-rows-only row-wise Adagrad step from a COO row gradient.

    Duplicate ids are merged first (dense scatter semantics: contributions
    add, THEN the row_sq/accumulator math runs — merging after would change
    the accumulator), then only the |touched| rows of ``a`` and ``p`` are
    gathered, stepped with the exact dense arithmetic, and scattered back.
    Padding entries (id == vocab) drop out of both scatters; on a skipped
    step (``ok`` false) every id does.
    """
    m = g.merged()
    ids = m.ids                                    # (N,) unique; vocab = pad
    if ok is not None:
        ids = jnp.where(ok, ids, g.vocab)
    g32 = m.rows.astype(jnp.float32)
    touched = ids < g.vocab
    safe = jnp.where(touched, ids, 0)
    row_sq = jnp.mean(g32 * g32, axis=tuple(range(1, g32.ndim)))
    a_rows = jnp.take(a, safe) + jnp.where(touched, row_sq, 0.0)
    scale = lr / (jnp.sqrt(a_rows) + eps)
    step = g32 * scale.reshape((-1,) + (1,) * (g32.ndim - 1))
    p_rows = (jnp.take(p, safe, axis=0).astype(jnp.float32)
              - step).astype(p.dtype)
    new_p = p.at[ids].set(p_rows, mode="drop")
    new_a = a.at[ids].set(a_rows, mode="drop")
    return new_p, new_a


def rowwise_adagrad(lr: float = 0.01, eps: float = 1e-8) -> Optimizer:
    """One accumulator per embedding row: state[p] has shape p.shape[:1].

    Dense grads update every row; :class:`SparseRows` grads scatter-update
    only the touched rows (identical per-row arithmetic)."""
    def init(params):
        return {"acc": jax.tree.map(
            lambda p: jnp.zeros(p.shape[:1], jnp.float32), params)}

    def update(grads, state, params, ok=None):
        def upd(p, g, a0):
            if is_sparse(g):
                return _rowwise_sparse_apply(p, g, a0, lr, eps, ok)
            g32 = g.astype(jnp.float32)
            row_sq = jnp.mean(g32 * g32, axis=tuple(range(1, g32.ndim)))
            a = a0 + row_sq
            scale = lr / (jnp.sqrt(a) + eps)
            step = g32 * scale.reshape((-1,) + (1,) * (g32.ndim - 1))
            new_p = (p.astype(jnp.float32) - step).astype(p.dtype)
            return _keep(ok, new_p, p), _keep(ok, a, a0)

        out = jax.tree.map(upd, params, grads, state["acc"])
        new_p = jax.tree.map(lambda o: o[0], out,
                             is_leaf=lambda x: isinstance(x, tuple))
        new_a = jax.tree.map(lambda o: o[1], out,
                             is_leaf=lambda x: isinstance(x, tuple))
        return new_p, {"acc": new_a}

    return Optimizer(init, update,
                     lambda params: [True] * len(jax.tree.leaves(params)))


def sgd(lr: float = 0.1, momentum: float = 0.0) -> Optimizer:
    def init(params):
        if momentum:
            return {"mom": jax.tree.map(
                lambda p: jnp.zeros_like(p, jnp.float32), params)}
        return {}

    def update(grads, state, params, ok=None):
        if momentum:
            new_mom = jax.tree.map(
                lambda m, g: momentum * m + g.astype(jnp.float32),
                state["mom"], grads)
            new_p = jax.tree.map(
                lambda p, m: (p.astype(jnp.float32) - lr * m).astype(p.dtype),
                params, new_mom)
            return (jax.tree.map(lambda n, o: _keep(ok, n, o), new_p, params),
                    {"mom": jax.tree.map(lambda n, o: _keep(ok, n, o),
                                         new_mom, state["mom"])})
        new_p = jax.tree.map(
            lambda p, g: _keep(ok, (p.astype(jnp.float32)
                                    - lr * g.astype(jnp.float32)
                                    ).astype(p.dtype), p),
            params, grads)
        return new_p, {}

    return Optimizer(init, update)


def make_mixed(dense_opt: Optimizer, embedding_opt: Optimizer,
               is_embedding: Callable[[Tuple], bool]) -> Optimizer:
    """Route params by tree path: embedding tables -> embedding_opt,
    everything else -> dense_opt (the standard DLRM setup). ``rows_mask``
    is each side's own over the leaves routed to it."""

    def _mask(params):
        """Static (trace-time) embedding mask from tree paths."""
        flat = jax.tree_util.tree_flatten_with_path(params)[0]
        return [is_embedding(tuple(str(k) for k in path)) for path, _ in flat]

    def init(params):
        emb_mask = _mask(params)
        leaves = jax.tree.leaves(params)
        emb_leaves = [l for l, m in zip(leaves, emb_mask) if m]
        dense_leaves = [l for l, m in zip(leaves, emb_mask) if not m]
        return {
            "emb": embedding_opt.init(emb_leaves),
            "dense": dense_opt.init(dense_leaves),
        }

    def update(grads, state, params, ok=None):
        emb_mask = _mask(params)
        # SparseRows grads are leaves here: they must stay whole and pair
        # up positionally with their table param
        g_leaves = jax.tree.leaves(grads, is_leaf=is_sparse)
        p_leaves = jax.tree.leaves(params)
        ge = [g for g, m in zip(g_leaves, emb_mask) if m]
        pe = [p for p, m in zip(p_leaves, emb_mask) if m]
        gd = [g for g, m in zip(g_leaves, emb_mask) if not m]
        pd = [p for p, m in zip(p_leaves, emb_mask) if not m]
        new_pe, new_se = embedding_opt.update(ge, state["emb"], pe, ok)
        new_pd, new_sd = dense_opt.update(gd, state["dense"], pd, ok)
        it_e, it_d = iter(new_pe), iter(new_pd)
        merged = [next(it_e) if m else next(it_d) for m in emb_mask]
        new_params = jax.tree.unflatten(jax.tree.structure(params), merged)
        return new_params, {"emb": new_se, "dense": new_sd}

    def rows_mask(params):
        emb_mask = _mask(params)
        leaves = jax.tree.leaves(params)

        def side(opt, m):
            sub = [l for l, e in zip(leaves, emb_mask) if e == m]
            return iter(opt.rows_mask(sub) if opt.rows_mask is not None
                        else [False] * len(sub))
        it_e, it_d = side(embedding_opt, True), side(dense_opt, False)
        return [next(it_e) if m else next(it_d) for m in emb_mask]

    return Optimizer(init, update, rows_mask)


def default_is_embedding(path: Tuple[str, ...]) -> bool:
    s = "/".join(path).lower()
    return any(k in s for k in ("emb", "table"))
