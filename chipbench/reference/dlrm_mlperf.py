"""Plain reference of DLRM (Naumov et al., arXiv:1906.00091) at the widths of
the MLPerf Training v1 recommendation benchmark: the forward pass, the loss,
the gradients and the optimizer steps, in straightforward ``jax.numpy``. It
imports nothing of the program under test.

It works at impression level: every impression carries its own copy of its
request's dense features and user-side ids, so the program's request-level
batch and its one fanout are checked too. Computed in float32 under
``jax.default_matmul_precision("highest")``, in blocks of impressions.
``precision`` selects a lower-precision twin of the same arithmetic, the
control of the correctness check:

* ``"f32"``  float32 everywhere, every dot at full precision;
* ``"bf16"`` every array and every op in bfloat16 (loss and sums included);
* ``"fp8"``  dot operands rounded to float8 e4m3, float32 everywhere else.

Forward, for one impression with dense features ``x`` (13) and one id list
per sparse field (26 fields, ``multi_hot`` ids each):
    b   = bottom MLP (ReLU after every layer) of x                  (128)
    e_f = sum of table f's rows at the field's ids                  (128)
    v   = (b, e_0, ..., e_25), 27 vectors
    z   = [b, v_i . v_j for i > j]   (row-major strict lower triangle,
          MLPerf's order: 128 + 351 = 479 features)
    logit = top MLP (ReLU after every layer but the last) of z
The loss is the binary cross-entropy of the logit against the click label,
summed over the impressions.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import common
from chipbench.reference.common import Arith

# tables at least this tall are laid out padded to a multiple of PAD rows,
# as the program holds them; the padding rows are never read
PAD_FROM, PAD = 65536, 512


def table_rows(v: int) -> int:
    return v if v < PAD_FROM else -(-v // PAD) * PAD


# ---------------------------------------------------------------------------
# weights: the one law both the program and the reference are seeded with
# ---------------------------------------------------------------------------

def _mlp(key, dims):
    """The DLRM reference's law: weights normal with variance 2 / (fan_in +
    fan_out), biases normal with variance 1 / fan_out."""
    layers = []
    for i, k in enumerate(jax.random.split(key, len(dims) - 1)):
        kw, kb = jax.random.split(k)
        m, n = dims[i], dims[i + 1]
        layers.append({
            "w": jax.random.normal(kw, (m, n), jnp.float32) * (2.0 / (m + n)) ** 0.5,
            "b": jax.random.normal(kb, (n,), jnp.float32) * (1.0 / n) ** 0.5})
    return {"layers": layers}


def top_in(c: Dict) -> int:
    f = len(c["vocabs"]) + 1
    return c["embed_dim"] + f * (f - 1) // 2


def init_params(c: Dict, key) -> Dict:
    """Weights from a key, in the program's tree layout (float32): each
    table uniform in +-sqrt(1 / rows held), as the DLRM reference
    initialises a table of that many rows."""
    kt, kb, kp = jax.random.split(key, 3)
    tables = {}
    for i, (v, k) in enumerate(zip(c["vocabs"],
                                   jax.random.split(kt, len(c["vocabs"])))):
        s = (1.0 / v) ** 0.5
        tables[f"t{i}"] = jax.random.uniform(
            k, (table_rows(v), c["embed_dim"]), jnp.float32, -s, s)
    return {"tables": tables,
            "bot_mlp": _mlp(kb, c["bot_mlp"]),
            "top_mlp": _mlp(kp, [top_in(c)] + list(c["top_mlp"]))}


# ---------------------------------------------------------------------------
# inputs: plain arrays, impression-major
# ---------------------------------------------------------------------------

def inputs(requests, c: Dict) -> Dict[str, np.ndarray]:
    """Impression-major arrays from requests that carry ``ro_dense``,
    ``ro_idlist`` (the user-side fields' ids, field by field),
    ``item_idlist`` (each impression's item-side ids) and ``labels``
    (click): every impression gets its request's dense features and
    user-side ids."""
    n_fields, mh = len(c["vocabs"]), c["multi_hot"]
    dense, ids, labels = [], [], []
    for r in requests:
        ro = np.asarray(r.ro_idlist, np.int32)
        for k in range(r.num_impressions):
            dense.append(np.asarray(r.ro_dense, np.float32))
            ids.append(np.concatenate(
                [ro, np.asarray(r.item_idlist[k], np.int32)]))
            labels.append(r.labels[k].get("click", 0.0))
    return {"dense": np.stack(dense),
            "ids": np.stack(ids).reshape(-1, n_fields, mh),
            "labels": np.asarray(labels, np.float32)}


# ---------------------------------------------------------------------------
# arithmetic at a chosen precision
# ---------------------------------------------------------------------------

def _mlp_apply(ar: Arith, layers, x, relu_last: bool):
    for i, lyr in enumerate(layers):
        x = ar.dot("nd,de->ne", x, lyr["w"]) + lyr["b"]
        if relu_last or i < len(layers) - 1:
            x = jax.nn.relu(x)
    return x


def logits(params: Dict, x: Dict, c: Dict, precision: str = "f32"):
    """(N,) click logits of the impressions."""
    ar = Arith(precision)
    p = jax.tree.map(ar.cast, params)
    b = _mlp_apply(ar, p["bot_mlp"]["layers"], ar.cast(x["dense"]), True)
    # ids lie in their tables: "clip" gathers as "fill" would, and compiles
    # in a fifth of the time on the TPU
    embs = [jnp.sum(jnp.take(p["tables"][f"t{f}"], x["ids"][:, f], axis=0,
                             mode="clip"), axis=1)
            for f in range(len(c["vocabs"]))]
    v = jnp.stack([b] + embs, axis=1)                          # (N, 27, D)
    dots = ar.dot("nfd,ngd->nfg", v, v)
    i, j = np.tril_indices(v.shape[1], k=-1)
    z = jnp.concatenate([b, dots[:, i, j]], axis=1)            # (N, 479)
    return _mlp_apply(ar, p["top_mlp"]["layers"], z, False)[:, 0]


def loss_sum(params: Dict, x: Dict, c: Dict, precision: str = "f32"):
    """Summed binary cross-entropy of the click logits."""
    lg = logits(params, x, c, precision)
    y = x["labels"].astype(lg.dtype)
    return jnp.sum(jnp.maximum(lg, 0) - lg * y
                   + jnp.log1p(jnp.exp(-jnp.abs(lg))))


def n_terms(x: Dict, c: Dict) -> int:
    return int(x["labels"].shape[0])


def train_steps(params: Dict, batches: List[Dict], c: Dict, opt: Dict,
                precision: str = "f32", block: int = 4096):
    """The reference trainer's steps on these batches, ``block``
    impressions at a time (see ``common.train_steps``). A block's
    activations are small (4,096 impressions of a 1,024-wide layer are
    17 MB), and each block's gradient is as large as the tables, so a batch
    of the cell is one block."""
    def blocks(x):
        n = x["labels"].shape[0]
        return [{k: v[lo:lo + block] for k, v in x.items()}
                for lo in range(0, n, block)]
    return common.train_steps(
        params, batches, lambda p, x: loss_sum(p, x, c, precision),
        lambda x: n_terms(x, c), blocks, opt)
