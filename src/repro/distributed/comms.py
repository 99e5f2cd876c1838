"""Overlapped, compressed sparse embedding exchange.

PR 4 made the sharded-table collectives real — explicit per-table psums in
``embeddings/sharded.py`` — but synchronous and full-precision f32. At
multi-host scale the embedding exchange is the binding constraint (the
Facebook scale-up/scale-out finding), and the Intel CPU-cluster recipe is
quantized collectives with error compensation. This module is that layer:

  * **Wire compression** (``none | bf16 | int8``): :func:`wire_transform`
    fake-quantizes the per-shard partial *before* the psum, so the bytes
    that cross the wire are the compressed representation (the psum itself
    still runs in the compute dtype — on-wire cost is what
    :func:`wire_bytes` accounts). int8 uses per-block max-abs scaling
    (:data:`BLOCK_KNOB` values per scale) — much tighter than the seed's
    per-tensor scale in ``train/compression.py``. The transform is a
    straight-through estimator: quantized forward, identity backward, so
    autodiff through a compressed lookup still produces exact table grads
    (the gradient's own exchange is compressed separately, with error
    feedback, below).
  * **Error-feedback residual** (Karimireddy et al. 2019) for the gradient
    exchange: :func:`ef_init` builds an optimizer-adjacent residual tree
    (``state["comms_ef"]``) holding one f32 ``(V, D)`` buffer per
    compressed table; :func:`ef_compress_step` sends ``q(g + e)`` and
    carries ``e' = (g + e) - q(g + e)``. The telescoping sum bounds the
    accumulated error by a single quantization step independent of the
    step count, which is what keeps int8 training loss-parity-bounded
    (tests/test_comms.py, tests/test_distributed_train.py).
    ``SparseRows`` COO grads compress row-wise: only the batch's unique
    rows (PR 5's dedup) ship through the quantizer, and the residual is
    gathered/scattered at exactly those rows.
  * **Overlap**: with ``comms_overlap=on`` the grad-accum scan in
    ``train/loop.py`` unrolls, removing the sequential-loop barrier so
    XLA's latency-hiding scheduler can issue microbatch k+1's lookup
    psums while microbatch k's dense compute runs; the SparseRows grad
    exchange is deferred and coalesced to once per step symmetrically.
  * **Accounting**: :data:`STATS` (a :class:`CommsStats`) records every
    exchange site at trace time — f32-equivalent vs on-wire bytes,
    compression ratio, overlap occupancy — and mirrors into
    ``repro.obs`` so ``obs.snapshot()`` covers the exchange layer.

Knobs (shared precedence ladder, see docs/CONFIG.md):
``comms_compress`` (none|bf16|int8), ``comms_overlap`` (on|off),
``comms_block`` (int8 scale-block width, default 128).
"""
from __future__ import annotations

import math
import threading
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from repro.embeddings import sparse as _sp
from repro.obs import metrics as obs_metrics
from repro.scenario.knobs import UNSET, Knob

COMPRESS_MODES = ("none", "bf16", "int8")

COMPRESS_KNOB = Knob("comms_compress", "REPRO_COMMS_COMPRESS",
                     choices=COMPRESS_MODES, auto=lambda: "none")
OVERLAP_KNOB = Knob("comms_overlap", "REPRO_COMMS_OVERLAP",
                    choices=("on", "off"), auto=lambda: "off")
BLOCK_KNOB = Knob("comms_block", "REPRO_COMMS_BLOCK", parse=int,
                  auto=lambda: 128)

# bytes per element on the wire, excluding int8's per-block scales
_WIRE_BYTES_PER_ELT = {"none": 4, "bf16": 2, "int8": 1}
_SCALE_BYTES = 4   # one f32 scale per block


def compress_mode(arg=UNSET) -> str:
    return COMPRESS_KNOB.resolve(arg)


def overlap_enabled(arg=UNSET) -> bool:
    return OVERLAP_KNOB.resolve(arg) == "on"


def block_size(arg=UNSET) -> int:
    return int(BLOCK_KNOB.resolve(arg))


# ---------------------------------------------------------------------------
# Per-block quantization
# ---------------------------------------------------------------------------

def _effective_block(last_dim: int, block: int) -> int:
    """Scale-block width actually used for a tensor whose last dim is
    ``last_dim``: the configured width when it divides evenly, else the
    whole row (one scale per last-dim vector) — static shapes rule out
    ragged blocks, and padding would bill phantom bytes."""
    if block > 0 and last_dim % block == 0:
        return min(block, last_dim)
    return last_dim


def quantize_int8(x: jnp.ndarray, block: int) -> Tuple[jnp.ndarray,
                                                       jnp.ndarray]:
    """Per-block symmetric int8: ``(q, scale)`` with blocks along the last
    dim. ``scale`` has shape ``x.shape[:-1] + (n_blocks, 1)``."""
    d = x.shape[-1]
    b = _effective_block(d, block)
    xb = x.reshape(x.shape[:-1] + (d // b, b)).astype(jnp.float32)
    scale = jnp.max(jnp.abs(xb), axis=-1, keepdims=True) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(xb / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jnp.ndarray, scale: jnp.ndarray,
                    shape: Tuple[int, ...]) -> jnp.ndarray:
    return (q.astype(jnp.float32) * scale).reshape(shape)


def fake_quant(x: jnp.ndarray, mode: str, block: int) -> jnp.ndarray:
    """Round-trip ``x`` through the wire representation (same dtype out).
    This is the value the receiving shards reconstruct — inserting it
    before a psum makes the collective's payload the compressed bytes."""
    if mode == "none":
        return x
    if mode == "bf16":
        return x.astype(jnp.bfloat16).astype(x.dtype)
    if mode == "int8":
        q, s = quantize_int8(x, block)
        return dequantize_int8(q, s, x.shape).astype(x.dtype)
    raise ValueError(f"unknown comms compress mode {mode!r}")


def wire_transform(x: jnp.ndarray, mode: str, block: int) -> jnp.ndarray:
    """Forward-path wire compression as a straight-through estimator.

    Forward: the quantized value (what actually crosses the wire).
    Backward: identity — round/clip have zero gradient a.e., which would
    kill the table gradient; the backward exchange is compressed on its
    own terms (with error feedback) by :func:`ef_compress_step`.
    """
    if mode == "none":
        return x
    return x + jax.lax.stop_gradient(fake_quant(x, mode, block) - x)


def wire_bytes(shape: Tuple[int, ...], mode: str, block: int = 0) -> int:
    """On-wire payload bytes for one exchange of a tensor of ``shape``."""
    n = int(math.prod(shape))
    if n == 0:
        return 0
    per = _WIRE_BYTES_PER_ELT[mode]
    total = n * per
    if mode == "int8":
        b = _effective_block(int(shape[-1]), block)
        total += (n // b) * _SCALE_BYTES
    return total


# ---------------------------------------------------------------------------
# CommsStats: trace-time accounting, mirrored into repro.obs
# ---------------------------------------------------------------------------

class CommsStats:
    """Per-site exchange ledger, recorded when a collective is traced.

    Sites are keyed (overwrite-by-key) so retracing never double-counts;
    the snapshot reports per-step totals assuming each recorded site fires
    once per step (grad sites fire once regardless of microbatch count —
    the accumulation scan coalesces them, which ``overlap`` records).
    """

    def __init__(self):
        self._lock = threading.Lock()
        self.reset()

    def reset(self) -> None:
        with self._lock:
            self._sites: Dict[str, dict] = {}
            self._overlap: Dict[str, Any] = {
                "enabled": False, "microbatches": 1, "occupancy": 0.0,
                "deferred_grad_exchanges_per_step": 0}

    def record_exchange(self, site: str, shape: Tuple[int, ...], *,
                        mode: str, block: int = 0, kind: str = "lookup",
                        collective: str = "psum",
                        dedup: bool = False) -> None:
        f32 = int(math.prod(shape)) * 4
        wire = wire_bytes(tuple(shape), mode, block)
        if collective == "psum_scatter":
            # reduce-scatter moves each element once instead of log/ring
            # all-reduce's ~2x; account the halving the RS path buys
            f32 //= 2
            wire //= 2
        with self._lock:
            self._sites[site] = {
                "shape": tuple(int(s) for s in shape), "mode": mode,
                "kind": kind, "collective": collective, "dedup": bool(dedup),
                "f32_bytes": f32, "wire_bytes": wire}
        _ensure_registered()

    def record_overlap(self, microbatches: int, enabled: bool) -> None:
        m = max(int(microbatches), 1)
        with self._lock:
            self._overlap = {
                "enabled": bool(enabled and m > 1),
                "microbatches": m,
                # fraction of microbatches whose lookup exchange can hide
                # behind the previous microbatch's dense compute
                "occupancy": (m - 1) / m if (enabled and m > 1) else 0.0,
                "deferred_grad_exchanges_per_step": m - 1}
        _ensure_registered()

    def snapshot(self) -> dict:
        with self._lock:
            sites = {k: dict(v) for k, v in self._sites.items()}
            overlap = dict(self._overlap)
        f32 = sum(s["f32_bytes"] for s in sites.values())
        wire = sum(s["wire_bytes"] for s in sites.values())
        return {
            "sites": sites,
            "exchanges": len(sites),
            "dedup_exchanges": sum(1 for s in sites.values() if s["dedup"]),
            "f32_bytes_per_step": f32,
            "wire_bytes_per_step": wire,
            "compression_ratio": (f32 / wire) if wire else 1.0,
            "overlap": overlap,
        }


STATS = CommsStats()


def _ensure_registered() -> None:
    # re-register on every record: obs_metrics.reset() (tests, benchmarks)
    # clears mirrors, and a dict write under the registry lock is cheap
    obs_metrics.register_stats("distributed.comms", STATS)


# ---------------------------------------------------------------------------
# Error-feedback residual for the gradient exchange
# ---------------------------------------------------------------------------

def _leaf_name(key) -> str:
    return str(getattr(key, "key", getattr(key, "name", key)))


def ef_paths(params: Any, plan=None) -> List[Tuple[str, ...]]:
    """Paths (tuples of dict keys) of the table leaves whose gradient
    exchange is compressed: 2-D leaves the optimizer's embedding predicate
    matches, restricted to tables that actually shard under ``plan`` (or,
    with no plan, tables big enough that they *would* shard — the
    single-process simulation of the multi-host exchange)."""
    from repro.distributed import spmd
    from repro.train.optim import default_is_embedding
    out: List[Tuple[str, ...]] = []
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    for key_path, leaf in flat:
        shape = jnp.shape(leaf)
        if len(shape) != 2:
            continue
        path = tuple(str(k) for k in key_path)
        if not default_is_embedding(path):
            continue
        if plan is not None and plan.enabled:
            if not spmd.table_is_sharded(plan, shape[0]):
                continue
        elif shape[0] < spmd.SHARD_MIN_ROWS:
            continue
        out.append(tuple(_leaf_name(k) for k in key_path))
    return out


def _get_nested(tree, path):
    for k in path:
        tree = tree[k]
    return tree


def _replace_nested(tree: dict, path, value) -> dict:
    """A copy of ``tree`` with the leaf at ``path`` replaced."""
    out = dict(tree)
    out[path[0]] = (value if len(path) == 1
                    else _replace_nested(tree[path[0]], path[1:], value))
    return out


def _set_nested(tree: dict, path, value) -> None:
    for k in path[:-1]:
        tree = tree.setdefault(k, {})
    tree[path[-1]] = value


def ef_init(params: Any, plan=None) -> Dict[str, Any]:
    """Residual tree for ``state["comms_ef"]``: zeros_like(f32) at each
    compressed-table path, nested like ``params`` (so ``spmd.param_spec``
    shards each residual exactly like its table)."""
    out: Dict[str, Any] = {}
    for path in ef_paths(params, plan):
        leaf = _get_nested(params, path)
        _set_nested(out, path, jnp.zeros(jnp.shape(leaf), jnp.float32))
    return out


def ef_compress_step(grads: Any, residual: Any, mode: str,
                     block: int) -> Tuple[Any, Any]:
    """One EF step over the grads tree: returns ``(sent_grads,
    new_residual)`` where every leaf of ``residual`` had its matching grad
    replaced by ``q(g + e)`` and the residual advanced to
    ``(g + e) - q(g + e)``. Dense ``(V, D)`` grads compress whole;
    :class:`SparseRows` grads are duplicate-merged first and only the
    unique touched rows ride the quantizer — untouched rows keep their
    residual until next touched (standard sparse EF)."""
    if mode == "none" or residual is None:
        return grads, residual
    flat, _ = jax.tree_util.tree_flatten_with_path(residual)
    new_grads, new_res = grads, residual
    for key_path, e in flat:
        path = tuple(_leaf_name(k) for k in key_path)
        g = _get_nested(grads, path)
        if _sp.is_sparse(g):
            m = g.merged()
            touched = (m.ids < m.vocab)[:, None].astype(jnp.float32)
            e_rows = jnp.take(e, jnp.minimum(m.ids, m.vocab - 1),
                              axis=0) * touched
            g32 = m.rows.astype(jnp.float32) + e_rows
            sent_rows = fake_quant(g32, mode, block)
            e2 = e.at[m.ids].set(g32 - sent_rows, mode="drop")
            sent = _sp.SparseRows(m.ids, sent_rows.astype(m.rows.dtype),
                                  m.vocab, unique=True)
            STATS.record_exchange(
                "grad:" + "/".join(path), m.rows.shape, mode=mode,
                block=block, kind="grad", collective="coo", dedup=True)
        else:
            g32 = g.astype(jnp.float32) + e
            sent32 = fake_quant(g32, mode, block)
            e2 = g32 - sent32
            sent = sent32.astype(g.dtype)
            STATS.record_exchange(
                "grad:" + "/".join(path), g.shape, mode=mode, block=block,
                kind="grad", collective="psum")
        new_grads = _replace_nested(new_grads, path, sent)
        new_res = _replace_nested(new_res, path, e2)
    return new_grads, new_res
