"""Pallas TPU kernels: fused HSTU pointwise attention with the ROO mask,
forward AND backward (trainable via ``jax.custom_vjp``).

The paper's flagship compute hot-spot: HSTU replaces softmax attention with
``SiLU(QK^T/sqrt(d) + rab) / S`` — no running-max/denominator bookkeeping, so
one pass over KV blocks with straight accumulation suffices (simpler than
flash attention, same O(S²) compute, O(blocks) VMEM).

TPU adaptation (DESIGN.md §3): GPU HSTU ships a Triton ragged kernel; here
q/k/v are tiled into 128-aligned VMEM blocks for the MXU, and the ROO
structural mask (history causal | target->history | target diagonal) plus
per-request validity lengths are generated *inside* the kernel from block
indices + scalar-prefetched lengths — the (S,S) mask never exists in HBM.

Block sparsity: only tiles in which the ROO mask can hold a true cell are
visited. A static table lists the tiles the mask's structure allows (the
history lower triangle of tiles, plus every target row block against the
history and the target diagonal: 2,145 of the 4,225 tiles at S 8,320 in
128 blocks); it is scalar-prefetched after the per-request lengths, and each
kernel's grid is (B*H, n_tiles). Per request, ``_tile_live`` tests each
tile's corners against ``hist_lengths`` / ``target_counts``, on the device
before the kernel, into one scalar-prefetched word per request and step
(``_step_info``): a dead step skips the body (``pl.when``) and the index
maps of its streamed operands point at the block of the live step before
it, so the pipeline copies nothing for it. Accumulator inits stay
unconditional, so rows no live tile reaches come out as zeros. Sums are
bit-for-bit those of the dense grid: a dead tile added exactly 0.

Forward: the table row-major, so each output block's tiles are visited
consecutively and it is accumulated in place.

Relative-position bias: a tile's bias depends only on ``row - col``, so it
is a Toeplitz matrix. The wrapper lays the compact (H, 2*max_rel+1) table
out as one lane-dense row per head, indexed by *descending* delta (with the
clipping to ``±max_rel`` baked in). In-kernel, a dynamic lane rotation
brings the tile's ``bq + bk - 1`` deltas to the front of the row, and one
strided rotation (row r shifted by r more lanes) expands that window into
the (bq, bk) tile. The TPU has no vector gather, so this replaces a
per-element ``take``.

Backward recomputes scores blockwise (no O(S²) residuals) in two passes:
  * dq + drab : the row-major table — dq accumulates over a row's tiles;
    the bias gradient is the transpose of the expansion above:
    the tile's rows are reversed (an exact permutation matmul), a strided
    rotation then lines each diagonal of dS up in one lane, a sublane sum
    gives the per-delta sums, and a dynamic rotation adds them into a
    per-(b,h) row in the same descending-delta layout, revisited across all
    of the (b,h)'s tiles. XLA folds that row back onto the compact table;
  * dk + dv   : the column-major table — both accumulate over a column's
    tiles.

Sequence lengths that do not divide the block size are handled by the
wrapper with pad-and-crop: padded positions read as out-of-range targets,
which the in-kernel validity mask zeroes out, and the 1/S score scale is
pinned to the *unpadded* length so numerics are invariant to padding.
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _rab_layout(bq: int, bk: int, max_rel: int):
    """Geometry of the lane-dense bias row: ``w`` lanes hold one tile's
    window of deltas, ``x0`` is the delta at lane 0, ``n`` the row length.
    Window starts stay in [0, 2*max_rel + w - 1] because bases are clamped
    to the range in which the clipped bias still varies."""
    w = _round_up(bq + bk - 1, 128)
    x0 = max_rel + w - 1
    n = _round_up(2 * max_rel + 2 * w - 1, 128)
    return w, x0, n


def _rab_row(rab: jnp.ndarray, bq: int, bk: int, max_rel: int) -> jnp.ndarray:
    """(H, 2*max_rel+1) table -> (H, 1, n) row, lane x holding the bias of
    delta ``x0 - x`` clipped to ``±max_rel``. Differentiable (a gather)."""
    _, x0, n = _rab_layout(bq, bk, max_rel)
    idx = jnp.clip(x0 - jnp.arange(n), -max_rel, max_rel) + max_rel
    return jnp.take(rab, idx, axis=1)[:, None, :]


def _window_start(base, *, bq: int, bk: int, max_rel: int):
    """Lane of the bias row where the window of a tile whose top-left cell
    has delta ``base`` begins."""
    w, x0, _ = _rab_layout(bq, bk, max_rel)
    base = jnp.clip(base, -max_rel - bq + 1, max_rel + w - bq)
    return x0 - base - (bq - 1)


def _toeplitz_bias(rab_row, base, *, bq: int, bk: int, max_rel: int):
    """bias[r, c] = rab[clip(base + r - c)] for one (bq, bk) tile, from the
    (1, n) row of :func:`_rab_row` (two lane rotations, no gather)."""
    w, _, n = _rab_layout(bq, bk, max_rel)
    start = _window_start(base, bq=bq, bk=bk, max_rel=max_rel)
    # win[j] = bias of delta base + bq - 1 - j
    win = pltpu.roll(rab_row, (n - start) % n, 1)[:, :w]
    # row r rotated by r - (bq - 1): tile[r, c] = win[c - r + bq - 1]
    tile = pltpu.roll(jnp.broadcast_to(win, (bq, w)), (w - bq + 1) % w, 1,
                      stride=1, stride_axis=0)
    return tile[:, :bk]


def _reverse_rows(x):
    """``x[::-1]`` for an f32 tile, as a permutation matmul (the chip's
    compiler lowers no ``rev``). x is split into three bf16 parts, each
    permuted exactly by a one-pass bf16 matmul and summed back in f32, so
    the result is exact. The one pass is pinned: an outer
    ``jax.default_matmul_precision("highest")`` would ask for an fp32
    contraction of bf16 operands, which the compiler refuses."""
    m = x.shape[0]
    i = jax.lax.broadcasted_iota(jnp.int32, (m, m), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (m, m), 1)
    perm = jnp.where(i + j == m - 1, 1.0, 0.0).astype(jnp.bfloat16)
    out = jnp.zeros_like(x)
    rest = x
    for _ in range(3):
        part = rest.astype(jnp.bfloat16)
        rest = rest - part.astype(jnp.float32)
        out = out + jax.lax.dot_general(
            perm, part, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32)
    return out


def _toeplitz_bias_grad(ds, base, *, bq: int, bk: int, max_rel: int):
    """Transpose of :func:`_toeplitz_bias`: the (1, n) row-gradient of one
    tile's dS. Rotating row r by (bq - 1 - r) lanes puts every diagonal of
    dS in one lane; the sublane sum then gives the per-delta sums. A strided
    rotation only grows with the row, so the rows are reversed first (their
    order does not matter to the sum)."""
    w, _, n = _rab_layout(bq, bk, max_rel)
    start = _window_start(base, bq=bq, bk=bk, max_rel=max_rel)
    if w > bk:
        ds = jnp.concatenate([ds, jnp.zeros((bq, w - bk), ds.dtype)], axis=1)
    # row r' of the reversed tile is row bq - 1 - r', rotated by r'
    diag = pltpu.roll(_reverse_rows(ds), 0, 1, stride=1, stride_axis=0)
    dwin = jnp.sum(diag, axis=0, keepdims=True)                  # (1, w)
    if n > w:
        dwin = jnp.concatenate([dwin, jnp.zeros((1, n - w), ds.dtype)],
                               axis=1)
    return pltpu.roll(dwin, start, 1)


def _roo_mask(rows, cols, hl, tc, n_hist: int):
    """The ROO structural mask and per-request validity of one tile, as
    one boolean (built from ``&``/``|`` only: the chip's compiler has no
    select between boolean vectors)."""
    is_hq = rows < n_hist
    is_hk = cols < n_hist
    struct = (is_hq & is_hk & (cols <= rows)) | ((~is_hq) & is_hk) | \
             ((~is_hq) & (~is_hk) & (rows == cols))
    valid_r = (is_hq & (rows < hl)) | ((~is_hq) & ((rows - n_hist) < tc))
    valid_c = (is_hk & (cols < hl)) | ((~is_hk) & ((cols - n_hist) < tc))
    return struct & valid_r & valid_c


def _tile_live(qi, ki, hl, tc, *, n_hist: int, bq: int, bk: int, xp=jnp):
    """True exactly when :func:`_roo_mask` holds a true cell in tile
    ``(qi, ki)`` (rows ``[qi*bq, qi*bq + bq)``, columns ``[ki*bk, ki*bk +
    bk)``) for a request of ``hl`` history events and ``tc`` targets.

    The mask's true cells are the union of three parts, each a test on the
    tile's corners: history rows and columns under ``min(hl, n_hist)`` with
    the column range starting at or below the last row (causal); target
    rows in ``[n_hist, n_hist + tc)`` against those history columns; the
    target diagonal. Arguments broadcast: the wrappers evaluate it on the
    device for every request and step of the table (``xp=jnp``), the host
    on numpy arrays (``xp=np``): one definition for both."""
    r0, c0 = qi * bq, ki * bk
    r1, c1 = r0 + (bq - 1), c0 + (bk - 1)
    hh = xp.minimum(xp.maximum(hl, 0), n_hist)     # valid history: [0, hh)
    te = n_hist + xp.maximum(tc, 0)                # valid targets: [n_hist, te)
    hist = (r0 < hh) & (c0 < hh) & (c0 <= r1)
    tgt = (r1 >= n_hist) & (xp.maximum(r0, n_hist) < te) & (c0 < hh)
    diag = (xp.maximum(xp.maximum(r0, c0), n_hist)
            < xp.minimum(xp.minimum(r1, c1) + 1, te))
    return hist | tgt | diag


@functools.lru_cache(maxsize=None)
def _tile_table(n_hist: int, s_pad: int, bq: int, bk: int, by_col: bool):
    """The tiles the mask's structure allows at all: those live for a full
    history and every padded slot a target. Row-major ``(qi, ki)`` pairs,
    or column-major with ``by_col``, as two int32 arrays."""
    qi, ki = np.meshgrid(np.arange(s_pad // bq), np.arange(s_pad // bk),
                         indexing="ij")
    if by_col:
        qi, ki = qi.T, ki.T
    qi, ki = qi.ravel(), ki.ravel()
    keep = _tile_live(qi, ki, n_hist, s_pad - n_hist, n_hist=n_hist, bq=bq,
                      bk=bk, xp=np)
    return qi[keep].astype(np.int32), ki[keep].astype(np.int32)


@dataclasses.dataclass(frozen=True)
class TileGrid:
    """Static geometry of one fused-kernel call, per (request, head): what
    the host needs to count the tiles the kernels compute."""
    n_heads: int
    n_hist: int
    s_pad: int
    bq: int
    bk: int

    @property
    def dense_tiles(self) -> int:
        """Tiles of the dense ``(S/bq, S/bk)`` grid."""
        return (self.s_pad // self.bq) * (self.s_pad // self.bk)

    def live_tiles(self, hist_lengths, target_counts) -> np.ndarray:
        """Live tiles of each request, per head: (B,) int."""
        qi, ki = _tile_table(self.n_hist, self.s_pad, self.bq, self.bk,
                             False)
        hl = np.asarray(hist_lengths, np.int32)[:, None]
        tc = np.asarray(target_counts, np.int32)[:, None]
        return _tile_live(qi, ki, hl, tc, n_hist=self.n_hist, bq=self.bq,
                          bk=self.bk, xp=np).sum(axis=1)


_TRACED_GRIDS: contextvars.ContextVar = contextvars.ContextVar(
    "hstu_traced_grids", default=None)


@contextlib.contextmanager
def record_tile_grids():
    """Collect the :class:`TileGrid` of every fused HSTU call traced inside
    (one per attention layer): the geometry is static, so a caller records
    it while jit traces and counts tiles on the host afterwards."""
    grids: list = []
    token = _TRACED_GRIDS.set(grids)
    try:
        yield grids
    finally:
        _TRACED_GRIDS.reset(token)


def _block_scores_and_mask(len_ref, cnt_ref, q, k, rab_ref, *,
                           b, qi, ki, n_hist: int,
                           bq: int, bk: int, max_rel: int, use_rab: bool):
    """Recompute the pre-activation scores (incl. bias) and the ROO mask for
    one (bq, bk) tile. q, k are f32 (bq, dqk)/(bk, dqk)."""
    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)              # (bq, bk)
    scores = scores * (1.0 / math.sqrt(q.shape[-1]))
    if use_rab:
        scores = scores + _toeplitz_bias(
            rab_ref[0], qi * bq - ki * bk, bq=bq, bk=bk, max_rel=max_rel)

    rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = _roo_mask(rows, cols, len_ref[b], cnt_ref[b], n_hist)
    return scores, mask


def _silu_grad(x):
    s = jax.nn.sigmoid(x)
    return s * (1.0 + x * (1.0 - s))


def _step(qi_ref, ki_ref, info_ref, *, n_heads: int, n_steps: int):
    """This grid step's batch row, tile, whether the tile is live, and
    whether it opens a run of tiles sharing its output block (see
    :func:`_step_info`)."""
    t = pl.program_id(1)
    b = pl.program_id(0) // n_heads
    info = info_ref[b * n_steps + t]
    return b, qi_ref[t], ki_ref[t], (info & 1) == 1, (info & 2) == 2


def _fwd_kernel(len_ref, cnt_ref, qi_ref, ki_ref, info_ref,  # prefetch
                q_ref, k_ref, v_ref, rab_ref,
                o_ref, *, n_hist: int, scale_len: int, n_heads: int,
                n_steps: int, bq: int, bk: int, max_rel: int, use_rab: bool):
    b, qi, ki, live, first = _step(qi_ref, ki_ref, info_ref,
                                   n_heads=n_heads, n_steps=n_steps)

    @pl.when(first)
    def _init():
        o_ref[0] = jnp.zeros_like(o_ref[0])

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                 # (bq, dqk)
        k = k_ref[0].astype(jnp.float32)                 # (bk, dqk)
        scores, mask = _block_scores_and_mask(
            len_ref, cnt_ref, q, k, rab_ref, b=b, qi=qi, ki=ki,
            n_hist=n_hist, bq=bq, bk=bk, max_rel=max_rel, use_rab=use_rab)
        a = jax.nn.silu(scores) * (1.0 / scale_len)
        a = jnp.where(mask, a, 0.0)
        v = v_ref[0].astype(jnp.float32)                 # (bk, dv)
        part = jax.lax.dot_general(a, v, (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)
        o_ref[0] += part.astype(o_ref.dtype)


def _bwd_dq_kernel(len_ref, cnt_ref, qi_ref, ki_ref, info_ref,
                   q_ref, k_ref, v_ref, rab_ref, do_ref,
                   dq_ref, drab_ref, *, n_hist: int, scale_len: int,
                   n_heads: int, n_steps: int, bq: int, bk: int,
                   max_rel: int, use_rab: bool):
    """dq (accumulated over a row's tiles) and the per-(b,h) gradient of
    the bias row (accumulated over every tile of the (b,h))."""
    b, qi, ki, live, first = _step(qi_ref, ki_ref, info_ref,
                                   n_heads=n_heads, n_steps=n_steps)

    @pl.when(first)
    def _init_dq():
        dq_ref[0] = jnp.zeros_like(dq_ref[0])

    @pl.when(pl.program_id(1) == 0)
    def _init_drab():
        drab_ref[0] = jnp.zeros_like(drab_ref[0])

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        scores, mask = _block_scores_and_mask(
            len_ref, cnt_ref, q, k, rab_ref, b=b, qi=qi, ki=ki,
            n_hist=n_hist, bq=bq, bk=bk, max_rel=max_rel, use_rab=use_rab)
        do = do_ref[0].astype(jnp.float32)               # (bq, dv)
        v = v_ref[0].astype(jnp.float32)                 # (bk, dv)
        da = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = da * (1.0 / scale_len) * _silu_grad(scores)
        ds = jnp.where(mask, ds, 0.0)                    # dL/d(scores+bias)
        dq_part = jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        dq_part = dq_part * (1.0 / math.sqrt(q.shape[-1]))
        dq_ref[0] += dq_part.astype(dq_ref.dtype)
        if use_rab:
            drab_ref[0] += _toeplitz_bias_grad(ds, qi * bq - ki * bk, bq=bq,
                                               bk=bk, max_rel=max_rel)


def _bwd_dkv_kernel(len_ref, cnt_ref, qi_ref, ki_ref, info_ref,
                    q_ref, k_ref, v_ref, rab_ref, do_ref,
                    dk_ref, dv_ref, *, n_hist: int, scale_len: int,
                    n_heads: int, n_steps: int, bq: int, bk: int,
                    max_rel: int, use_rab: bool):
    """dk and dv, both accumulated over a column's tiles (column-major
    table)."""
    b, qi, ki, live, first = _step(qi_ref, ki_ref, info_ref,
                                   n_heads=n_heads, n_steps=n_steps)

    @pl.when(first)
    def _init():
        dk_ref[0] = jnp.zeros_like(dk_ref[0])
        dv_ref[0] = jnp.zeros_like(dv_ref[0])

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        scores, mask = _block_scores_and_mask(
            len_ref, cnt_ref, q, k, rab_ref, b=b, qi=qi, ki=ki,
            n_hist=n_hist, bq=bq, bk=bk, max_rel=max_rel, use_rab=use_rab)
        do = do_ref[0].astype(jnp.float32)               # (bq, dv)
        v = v_ref[0].astype(jnp.float32)                 # (bk, dv)
        a = jax.nn.silu(scores) * (1.0 / scale_len)
        a = jnp.where(mask, a, 0.0)
        dv_part = jax.lax.dot_general(a, do, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        da = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = da * (1.0 / scale_len) * _silu_grad(scores)
        ds = jnp.where(mask, ds, 0.0)
        dk_part = jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        dk_part = dk_part * (1.0 / math.sqrt(q.shape[-1]))
        dk_ref[0] += dk_part.astype(dk_ref.dtype)
        dv_ref[0] += dv_part.astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call plumbing on block-aligned shapes (wrapped in custom_vjp)
# ---------------------------------------------------------------------------

# statics = (n_hist, scale_len, max_rel, bq, bk, use_rab, interpret)


def _flatten(q, k, v):
    b, h, s, dqk = q.shape
    dv = v.shape[-1]
    return (q.reshape(b * h, s, dqk), k.reshape(b * h, s, dqk),
            v.reshape(b * h, s, dv))


def _rab_spec(rab_row, n_heads: int):
    """BlockSpec of the (H, 1, n) bias row: one head's whole row per step."""
    return pl.BlockSpec((1, 1, rab_row.shape[-1]),
                        lambda bh, *rest: (bh % n_heads, 0, 0))


def _tile_row(bh, t, len_ref, cnt_ref, qi_ref, ki_ref, info_ref):
    """Index map of a block that follows the tile's row block."""
    return bh, qi_ref[t], 0


def _tile_col(bh, t, len_ref, cnt_ref, qi_ref, ki_ref, info_ref):
    """Index map of a block that follows the tile's column block."""
    return bh, ki_ref[t], 0


def _streamed(n_heads: int, n_steps: int):
    """Index map of the operands that change within a run of the table
    (k and v along a row, q and dO along a column): the block
    :func:`_step_info` chose for the step."""
    def index(bh, t, len_ref, cnt_ref, qi_ref, ki_ref, info_ref):
        return bh, info_ref[(bh // n_heads) * n_steps + t] >> 2, 0
    return index


def _step_info(hist_lengths, target_counts, *, n_hist: int, s_pad: int,
               bq: int, bk: int, by_col: bool):
    """The structural table (row-major, or column-major with ``by_col``)
    and, per request and step, ``4 * block + 2 * first + live``, flattened:
    ``live`` whether the tile is live for the request's lengths, ``first``
    whether the step opens a run of tiles sharing its output block (a row,
    or a column), ``block`` the block of the streamed operands (k, v along
    a row; q, dO along a column). A dead step takes the block of the last
    live step before it, or before any, of the first live one: the
    pipeline copies a block only when its index changes, so a dead step
    moves no data."""
    qi, ki = _tile_table(n_hist, s_pad, bq, bk, by_col)
    run, streamed = (ki, qi) if by_col else (qi, ki)
    first = np.concatenate([[True], run[1:] != run[:-1]])
    live = _tile_live(qi, ki, hist_lengths[:, None], target_counts[:, None],
                      n_hist=n_hist, bq=bq, bk=bk)            # (B, n_steps)
    steps = jnp.arange(len(qi))
    last = jax.lax.cummax(jnp.where(live, steps, -1), axis=1)
    src = jnp.where(last >= 0, last, jnp.argmax(live, axis=1)[:, None])
    info = 4 * jnp.asarray(streamed)[src] + 2 * first + live
    return (jnp.asarray(qi), jnp.asarray(ki),
            info.astype(jnp.int32).reshape(-1))


def _fwd_call(statics, hist_lengths, target_counts, q, k, v, rab_row):
    n_hist, scale_len, max_rel, bq, bk, use_rab, interpret = statics
    b, h, s, dqk = q.shape
    dv = v.shape[-1]
    qf, kf, vf = _flatten(q, k, v)
    tables = _step_info(hist_lengths, target_counts, n_hist=n_hist, s_pad=s,
                        bq=bq, bk=bk, by_col=False)
    n_steps = tables[0].shape[0]
    kv = _streamed(h, n_steps)
    kernel = functools.partial(
        _fwd_kernel, n_hist=n_hist, scale_len=scale_len, n_heads=h,
        n_steps=n_steps, bq=bq, bk=bk, max_rel=max_rel, use_rab=use_rab)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(b * h, n_steps),
            in_specs=[
                pl.BlockSpec((1, bq, dqk), _tile_row),
                pl.BlockSpec((1, bk, dqk), kv),
                pl.BlockSpec((1, bk, dv), kv),
                _rab_spec(rab_row, h),
            ],
            out_specs=pl.BlockSpec((1, bq, dv), _tile_row),
        ),
        out_shape=jax.ShapeDtypeStruct((b * h, s, dv), v.dtype),
        interpret=interpret,
        name="hstu_attn_fwd",
    )(hist_lengths, target_counts, *tables, qf, kf, vf, rab_row)
    return out.reshape(b, h, s, dv)


def _bwd_call(statics, hist_lengths, target_counts, q, k, v, rab_row, g):
    n_hist, scale_len, max_rel, bq, bk, use_rab, interpret = statics
    b, h, s, dqk = q.shape
    dv = v.shape[-1]
    qf, kf, vf = _flatten(q, k, v)
    dof = g.reshape(b * h, s, dv)
    n_row = rab_row.shape[-1]
    tables = functools.partial(_step_info, hist_lengths, target_counts,
                               n_hist=n_hist, s_pad=s, bq=bq, bk=bk)
    kw = dict(n_hist=n_hist, scale_len=scale_len, n_heads=h, bq=bq, bk=bk,
              max_rel=max_rel, use_rab=use_rab)

    rows = tables(by_col=False)
    n_steps = rows[0].shape[0]
    kv = _streamed(h, n_steps)
    dq_f, drab_f = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, n_steps=n_steps, **kw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(b * h, n_steps),
            in_specs=[
                pl.BlockSpec((1, bq, dqk), _tile_row),
                pl.BlockSpec((1, bk, dqk), kv),
                pl.BlockSpec((1, bk, dv), kv),
                _rab_spec(rab_row, h),
                pl.BlockSpec((1, bq, dv), _tile_row),
            ],
            out_specs=[
                pl.BlockSpec((1, bq, dqk), _tile_row),
                pl.BlockSpec((1, 1, n_row), lambda bh, *rest: (bh, 0, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, dqk), q.dtype),
            jax.ShapeDtypeStruct((b * h, 1, n_row), jnp.float32),
        ],
        interpret=interpret,
        name="hstu_attn_bwd_dq",
    )(hist_lengths, target_counts, *rows, qf, kf, vf, rab_row, dof)

    cols = tables(by_col=True)
    qdo = _streamed(h, n_steps)
    dk_f, dv_f = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, n_steps=n_steps, **kw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(b * h, n_steps),
            in_specs=[
                pl.BlockSpec((1, bq, dqk), qdo),
                pl.BlockSpec((1, bk, dqk), _tile_col),
                pl.BlockSpec((1, bk, dv), _tile_col),
                _rab_spec(rab_row, h),
                pl.BlockSpec((1, bq, dv), qdo),
            ],
            out_specs=[pl.BlockSpec((1, bk, dqk), _tile_col),
                       pl.BlockSpec((1, bk, dv), _tile_col)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, dqk), k.dtype),
            jax.ShapeDtypeStruct((b * h, s, dv), v.dtype),
        ],
        interpret=interpret,
        name="hstu_attn_bwd_dkv",
    )(hist_lengths, target_counts, *cols, qf, kf, vf, rab_row, dof)

    dq = dq_f.reshape(b, h, s, dqk)
    dk = dk_f.reshape(b, h, s, dqk)
    dvv = dv_f.reshape(b, h, s, dv)
    # the bias row is shared across the batch: reduce the per-(b,h) partials
    drab = drab_f.reshape(b, h, 1, n_row).sum(0).astype(rab_row.dtype)
    return dq, dk, dvv, drab


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _hstu_fused(statics, hist_lengths, target_counts, q, k, v, rab_row):
    return _fwd_call(statics, hist_lengths, target_counts, q, k, v, rab_row)


def _hstu_fused_fwd(statics, hist_lengths, target_counts, q, k, v, rab_row):
    out = _fwd_call(statics, hist_lengths, target_counts, q, k, v, rab_row)
    return out, (hist_lengths, target_counts, q, k, v, rab_row)


def _hstu_fused_bwd(statics, res, g):
    hist_lengths, target_counts, q, k, v, rab_row = res
    dq, dk, dv, drab = _bwd_call(statics, hist_lengths, target_counts,
                                 q, k, v, rab_row, g)
    zero_hl = np.zeros(hist_lengths.shape, jax.dtypes.float0)
    zero_tc = np.zeros(target_counts.shape, jax.dtypes.float0)
    return zero_hl, zero_tc, dq, dk, dv, drab


_hstu_fused.defvjp(_hstu_fused_fwd, _hstu_fused_bwd)


def hstu_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   rab: Optional[jnp.ndarray],
                   n_hist: int,
                   hist_lengths: jnp.ndarray,
                   target_counts: jnp.ndarray,
                   max_rel_pos: int = 128,
                   block_q: int = 128, block_k: int = 128,
                   interpret: bool = True) -> jnp.ndarray:
    """q,k: (B,H,S,Dqk); v: (B,H,S,Dv); rab: (H, 2*max_rel_pos+1) or None.

    Returns (B,H,S,Dv). Differentiable w.r.t. q, k, v, and rab via the fused
    backward kernels (``jax.custom_vjp``); scores are recomputed blockwise so
    no O(S²) residual is stored. S need not divide the block size: the
    wrapper pads to the block lattice and crops, with the 1/S scale pinned to
    the unpadded length. ``interpret=True`` executes on CPU (validation); on
    TPU pass interpret=False.
    """
    b, h, s, dqk = q.shape
    # blocks cover whole sublane tiles (8 rows): the chip's tiling rule
    bq = min(block_q, _round_up(s, 8))
    bk = min(block_k, _round_up(s, 8))
    lcm = bq * bk // math.gcd(bq, bk)
    s_pad = -(-s // lcm) * lcm
    grids = _TRACED_GRIDS.get()
    if grids is not None:
        grids.append(TileGrid(h, n_hist, s_pad, bq, bk))
    use_rab = rab is not None
    if rab is None:
        rab = jnp.zeros((h, 2 * max_rel_pos + 1), q.dtype)
    if s_pad != s:
        # padded positions are out-of-range targets -> masked out in-kernel
        pad = ((0, 0), (0, 0), (0, s_pad - s), (0, 0))
        q, k, v = jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad)
    statics = (n_hist, s, max_rel_pos, bq, bk, use_rab, bool(interpret))
    out = _hstu_fused(statics, hist_lengths.astype(jnp.int32),
                      target_counts.astype(jnp.int32), q, k, v,
                      _rab_row(rab, bq, bk, max_rel_pos))
    return out[:, :, :s, :] if s_pad != s else out


# ---------------------------------------------------------------------------
# Cached-prefix (incremental serving) forward kernel
# ---------------------------------------------------------------------------


def _prefix_fwd_kernel(pfx_ref, nc_ref, tc_ref,      # scalar prefetch: (B,)x3
                       q_ref, k_ref, v_ref, rab_ref,
                       o_ref, *, n_hist: int, n_new: int, scale_len: int,
                       n_heads: int, bq: int, bk: int, max_rel: int,
                       use_rab: bool):
    """One (bq, bk) tile of cached-prefix attention. Rows are
    [new events | targets]; columns the full K/V buffer [history cache |
    targets]. New event r sits at absolute position ``prefix + r`` — the
    mask and rab deltas are generated in-kernel from that mapping, so the
    asymmetric row/column indexing never materializes in HBM."""
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    b = bh // n_heads

    q = q_ref[0].astype(jnp.float32)                     # (bq, dqk)
    k = k_ref[0].astype(jnp.float32)                     # (bk, dqk)
    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)              # (bq, bk)
    scores = scores * (1.0 / math.sqrt(q.shape[-1]))

    rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    cols = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    pfx = pfx_ref[b]
    nc = nc_ref[b]
    tc = tc_ref[b]
    is_new = rows < n_new
    row_pos = jnp.where(is_new, pfx + rows, rows + (n_hist - n_new))
    if use_rab:
        # new rows and target rows sit at different offsets: one Toeplitz
        # tile each, picked per row
        tile = functools.partial(_toeplitz_bias, rab_ref[0], bq=bq, bk=bk,
                                 max_rel=max_rel)
        base = qi * bq - ki * bk
        scores = scores + jnp.where(is_new, tile(base + pfx),
                                    tile(base + (n_hist - n_new)))

    is_hk = cols < n_hist
    struct = ((is_new & is_hk & (cols <= row_pos))
              | ((~is_new) & is_hk)
              | ((~is_new) & (~is_hk) & ((rows - n_new) == (cols - n_hist))))
    valid_r = (is_new & (rows < nc)) | ((~is_new) & ((rows - n_new) < tc))
    valid_c = (is_hk & (cols < pfx + nc)) | ((~is_hk) & ((cols - n_hist) < tc))
    mask = struct & valid_r & valid_c

    a = jax.nn.silu(scores) * (1.0 / scale_len)
    a = jnp.where(mask, a, 0.0)
    v = v_ref[0].astype(jnp.float32)                     # (bk, dv)
    part = jax.lax.dot_general(a, v, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)

    @pl.when(ki == 0)
    def _init():
        o_ref[0] = jnp.zeros_like(o_ref[0])

    o_ref[0] += part.astype(o_ref.dtype)


def hstu_attention_prefix(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                          rab: Optional[jnp.ndarray],
                          n_hist: int, n_new: int,
                          prefix_lengths: jnp.ndarray,
                          new_counts: jnp.ndarray,
                          target_counts: jnp.ndarray,
                          scale_len: int,
                          max_rel_pos: int = 128,
                          block_q: int = 128, block_k: int = 128,
                          interpret: bool = True) -> jnp.ndarray:
    """Cached-prefix HSTU attention (forward only — a serving path).

    q: (B, H, n_new + m, Dqk) — new history events then target slots;
    k, v: (B, H, n_hist + m, ·) — the per-user K/V cache (new events already
    scattered at ``prefix_lengths + r``) then the target slots. ``scale_len``
    pins the 1/n normalizer to the equivalent full-sequence length
    (n_hist + m_targets), so extend-only calls (m == 0 rows) normalize
    identically to extend-and-score. Rows and columns are padded to their
    block lattices independently and cropped; padded slots read as
    out-of-range targets, which the validity mask zeroes.
    Returns (B, H, n_new + m, Dv).
    """
    b, h, n_rows, dqk = q.shape
    n_cols = k.shape[2]
    dv = v.shape[-1]
    # blocks cover whole sublane tiles (8 rows): the chip's tiling rule
    bq = min(block_q, _round_up(n_rows, 8))
    bk = min(block_k, _round_up(n_cols, 8))
    r_pad = _round_up(n_rows, bq)
    c_pad = _round_up(n_cols, bk)
    use_rab = rab is not None
    if rab is None:
        rab = jnp.zeros((h, 2 * max_rel_pos + 1), q.dtype)
    if r_pad != n_rows:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, r_pad - n_rows), (0, 0)))
    if c_pad != n_cols:
        pad = ((0, 0), (0, 0), (0, c_pad - n_cols), (0, 0))
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    qf = q.reshape(b * h, r_pad, dqk)
    kf = k.reshape(b * h, c_pad, dqk)
    vf = v.reshape(b * h, c_pad, dv)
    rab_row = _rab_row(rab, bq, bk, max_rel_pos)

    kernel = functools.partial(
        _prefix_fwd_kernel, n_hist=n_hist, n_new=n_new, scale_len=scale_len,
        n_heads=h, bq=bq, bk=bk, max_rel=max_rel_pos, use_rab=use_rab)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b * h, r_pad // bq, c_pad // bk),
            in_specs=[
                pl.BlockSpec((1, bq, dqk), lambda bh, qi, ki, *s: (bh, qi, 0)),
                pl.BlockSpec((1, bk, dqk), lambda bh, qi, ki, *s: (bh, ki, 0)),
                pl.BlockSpec((1, bk, dv), lambda bh, qi, ki, *s: (bh, ki, 0)),
                _rab_spec(rab_row, h),
            ],
            out_specs=pl.BlockSpec((1, bq, dv),
                                   lambda bh, qi, ki, *s: (bh, qi, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((b * h, r_pad, dv), v.dtype),
        interpret=interpret,
        name="hstu_attn_prefix_fwd",
    )(prefix_lengths.astype(jnp.int32), new_counts.astype(jnp.int32),
      target_counts.astype(jnp.int32), qf, kf, vf, rab_row)
    out = out.reshape(b, h, r_pad, dv)
    return out[:, :, :n_rows, :] if r_pad != n_rows else out
