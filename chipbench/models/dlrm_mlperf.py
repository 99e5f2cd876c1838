"""DLRM at the MLPerf Training v1 widths wired from the program's own pieces,
as ``scenario/build.py`` wires ``dlrm-mlperf``: ``DLRMConfig`` ->
``dlrm_loss`` on ``ROOBatch``es packed by ``ROOBatcher``
(``dlrm_batcher_config``), ``make_mixed(adam, rowwise_adagrad)``,
``Trainer``. The chip's row share is the ``vocabs`` the configuration file
holds.

Requests are the program's ``ROOSample`` in DLRM's field layout: the 13
dense features and the user-side fields' ids (field by field) on the
request, the item-side fields' ids and the click on each impression. The
weights come from the reference's seeded law
(``chipbench/reference/dlrm_mlperf.py``), made on the device in one call.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import numpy as np

from chipbench import gen
from chipbench.drivers.common import Pool
from chipbench.models import hstu_gr
from chipbench.reference import dlrm_mlperf as ref

F32 = 4


class Model:
    reference = ref

    def __init__(self, c: Dict):
        # a program without the ROOBatch loss stops here, before any work
        from repro.models.dlrm import DLRMConfig, dlrm_loss
        self.c = c
        self._loss = dlrm_loss
        self.cfg = DLRMConfig(
            n_dense=c["n_dense"], embed_dim=c["embed_dim"],
            bot_mlp=tuple(c["bot_mlp"]), top_mlp=tuple(c["top_mlp"]),
            vocabs=tuple(c["vocabs"]), n_ro_fields=c["n_ro_fields"],
            multi_hot=c["multi_hot"])

    # ---- weights ------------------------------------------------------------
    def make_params(self, seed: int):
        return jax.jit(lambda k: ref.init_params(self.c, k))(gen.jax_key(seed))

    # ---- program entry points ----------------------------------------------
    def loss_fn(self):
        cfg, loss = self.cfg, self._loss
        return lambda p, b, r: loss(p, cfg, b)

    # the test fault that leaves half of the requests out, as for hstu-gr
    half_batch = staticmethod(hstu_gr.Model.half_batch)

    def pack(self, requests, b_ro: int, b_nro: int):
        """One program batch (``ROOBatcher``) holding all ``requests``."""
        from repro.data.batcher import BatcherConfig, ROOBatcher
        from repro.models.dlrm import dlrm_batcher_config
        out = list(ROOBatcher(dlrm_batcher_config(
            self.cfg, BatcherConfig(b_ro=b_ro, b_nro=b_nro))).batches(
                requests))
        if len(out) != 1:
            raise RuntimeError(f"{len(requests)} requests packed into "
                               f"{len(out)} batches, expected one")
        return out[0]

    # ---- traffic ------------------------------------------------------------
    def requests(self, traffic: Dict, seed: int, hist, imps: np.ndarray,
                 stream: str) -> List:
        """ROOSamples with the given impression counts (DLRM requests carry
        no history, so ``hist`` is not read); dense features, ids and
        clicks drawn from the seed, each field's ids by a Zipf law over the
        rows its table holds, the hot rows placed by the seed."""
        from repro.core.joiner import ROOSample
        c, mh, n_ro = self.c, self.c["multi_hot"], self.c["n_ro_fields"]
        law = traffic["dense"]
        if law["law"] != "normal":
            raise ValueError(f"unknown dense law {law['law']!r}")
        rows = gen.rng_for(seed, "rows")
        tables = [gen.Zipf(v, traffic["ids"]["exponent"], rows)
                  for v in c["vocabs"]]
        rng = gen.rng_for(seed, stream)
        imps = np.asarray(imps).ravel()
        n, total = len(imps), int(imps.sum())
        dense = (law["mean"] + law["sigma"] * rng.standard_normal(
            (n, c["n_dense"]))).astype(np.float32)
        ro = np.concatenate([t.draw(rng, (n, mh)) for t in tables[:n_ro]], 1)
        nro = np.concatenate([t.draw(rng, (total, mh))
                              for t in tables[n_ro:]], 1)
        clicks = gen.labels(rng, traffic["label_rates"], total)[:, 0]
        out, at = [], 0
        for i, m in enumerate(int(x) for x in imps):
            out.append(ROOSample(
                request_id=i, user_id=i, ro_dense=dense[i],
                ro_idlist=ro[i].tolist(), history_ids=[], history_actions=[],
                # Criteo rows carry no item key: the item is its fields
                item_ids=[0] * m,
                item_dense=[np.zeros((1,), np.float32)] * m,
                item_idlist=[x.tolist() for x in nro[at:at + m]],
                labels=[{"click": float(y)} for y in clicks[at:at + m]]))
            at += m
        return out

    def train_pool(self, traffic: Dict, seed: int) -> "Pool":
        """The window's pool of pre-built batches (the first three are the
        checked steps'), with the reference's view of each."""
        b_ro, b_nro = self.c["train"]["b_ro"], self.c["train"]["b_nro"]
        n = traffic["pool_batches"]
        _, imps = gen.train_sizes(traffic, seed, n, b_ro)
        reqs = self.requests(traffic, seed, None, imps, "train")
        groups = [reqs[i * b_ro:(i + 1) * b_ro] for i in range(n)]
        return Pool(batches=[self.pack(g, b_ro, b_nro) for g in groups],
                    reference=[ref.inputs(g, self.c) for g in groups],
                    impressions=[sum(r.num_impressions for r in g)
                                 for g in groups],
                    requests=groups)

    # ---- work counts --------------------------------------------------------
    def _fields(self):
        c = self.c
        return c["n_ro_fields"], len(c["vocabs"]) - c["n_ro_fields"]

    def flops_fwd(self, requests) -> float:
        """Useful forward FLOPs: the bottom MLP at the real requests, the
        interaction's 351 pairwise dots and the top MLP at the real
        impressions."""
        c = self.c
        imps = sum(r.num_impressions for r in requests)
        f = len(c["vocabs"]) + 1
        top = [ref.top_in(c)] + list(c["top_mlp"])

        def mlp(dims):
            return 2.0 * sum(a * b for a, b in zip(dims[:-1], dims[1:]))
        pairs = 2.0 * (f * (f - 1) // 2) * c["embed_dim"]
        return len(requests) * mlp(c["bot_mlp"]) + imps * (pairs + mlp(top))

    def bag_bytes_fwd(self, requests) -> float:
        """Least HBM traffic of the forward bag kernels of one step: one
        table row read per valid id, one pooled row written per bag (B_RO
        bags a user-side field, B_NRO an item-side one), float32."""
        c, (n_ro, n_nro) = self.c, self._fields()
        row = F32 * c["embed_dim"]
        imps = sum(r.num_impressions for r in requests)
        ids = c["multi_hot"] * (len(requests) * n_ro + imps * n_nro)
        bags = c["train"]["b_ro"] * n_ro + c["train"]["b_nro"] * n_nro
        return row * (ids + bags)

    def bag_bytes_bwd(self, requests) -> float:
        """Least HBM traffic of the backward bag kernels of one step: each
        bag's cotangent row read, one gradient row written per valid id."""
        return self.bag_bytes_fwd(requests)
