"""The recsys archs the scenario surface covers: one table, one entry each.

An :class:`Arch` entry holds everything the construction path knows about
an arch: the spec defaults its registered scenario starts from
(``configs/registry.scenario``), the builder that turns a spec into a
:class:`ModelBundle`, the field layout of a model that reads the joined
samples in a layout of its own, and whether its loss routes embedding
lookups through a sharding plan (the only archs that may train under
``train.mesh``). Adding a recsys arch is one entry in :data:`ARCHS` plus
its model module.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Mapping, NamedTuple, Optional

from repro.scenario.spec import ScenarioSpec, ScenarioValidationError
from repro.serve.adapter import ServeAdapter


class ModelBundle(NamedTuple):
    """Everything a trainer/server needs for one arch, built from a spec."""
    arch: str
    cfg: Any
    params: Any
    loss_fn: Callable
    metrics_fn: Optional[Callable]
    serve: Optional[ServeAdapter]            # None: arch is not ROO-servable


@dataclasses.dataclass(frozen=True)
class Arch:
    # (spec, rng, plan) -> ModelBundle
    build: Callable[..., ModelBundle]
    # dotted overrides the registered scenario applies to a bare spec
    defaults: Mapping[str, Any] = dataclasses.field(default_factory=dict)
    # spec -> (batcher config from the spec's sizes, sample mapping), for a
    # model that reads the joined samples in a field layout of its own
    layout: Optional[Callable] = None
    # the loss threads the plan into sharded lookups (may train on a mesh)
    plan_routed: bool = False


def _ne_metrics(logits_fn):
    from repro.train.metrics import make_ne_metrics
    return make_ne_metrics(logits_fn)


def _lsr(spec: ScenarioSpec, rng, plan) -> ModelBundle:
    from repro.configs import roo_models as rm
    from repro.models.lsr import (lsr_init, lsr_logits_from_user,
                                  lsr_logits_roo, lsr_loss, lsr_user_repr)
    m = spec.model
    cfg = dataclasses.replace(rm.lsr_config(m.variant or "userarch_hstu"),
                              n_items=m.n_items)
    return ModelBundle(
        m.arch, cfg, lsr_init(rng, cfg),
        lambda p, b, r: lsr_loss(p, cfg, b, plan=plan),
        _ne_metrics(lambda p, b: (lsr_logits_roo(p, cfg, b, plan=plan)[:, 0],
                                  b.labels[:, 0], b.impression_mask())),
        ServeAdapter(
            score=lambda p, b: lsr_logits_roo(p, cfg, b),
            user_repr=lambda p, b: lsr_user_repr(p, cfg, b),
            score_from_user=lambda p, b, u: lsr_logits_from_user(
                p, cfg, b, u)))


def _esr(spec: ScenarioSpec, rng, plan) -> ModelBundle:
    from repro.configs import roo_models as rm
    from repro.models.two_tower import (esr_logits_from_user, esr_logits_roo,
                                        esr_loss_roo, two_tower_init,
                                        user_tower)
    m = spec.model
    cfg = dataclasses.replace(rm.esr_config(), n_items=m.n_items)
    return ModelBundle(
        m.arch, cfg, two_tower_init(rng, cfg),
        lambda p, b, r: esr_loss_roo(p, cfg, b),
        _ne_metrics(lambda p, b: (esr_logits_roo(p, cfg, b),
                                  b.labels[:, 0], b.impression_mask())),
        ServeAdapter(
            score=lambda p, b: esr_logits_roo(p, cfg, b),
            user_repr=lambda p, b: user_tower(p, cfg, b),
            score_from_user=lambda p, b, u: esr_logits_from_user(
                p, cfg, b, u)))


def _retrieval(spec: ScenarioSpec, rng, plan) -> ModelBundle:
    import jax.numpy as jnp

    from repro.configs import roo_models as rm
    from repro.models.two_tower import (item_tower, retrieval_loss_roo,
                                        two_tower_init, user_tower)
    m = spec.model
    cfg = dataclasses.replace(rm.retrieval_config(), n_items=m.n_items)

    def _fanout_scores(p, b, u):
        v = item_tower(p, cfg, b.item_ids, b.nro_dense)
        seg = jnp.minimum(b.segment_ids, b.b_ro - 1)
        return jnp.sum(u[seg] * v, axis=-1)

    return ModelBundle(
        m.arch, cfg, two_tower_init(rng, cfg),
        lambda p, b, r: retrieval_loss_roo(p, cfg, b), None,
        ServeAdapter(
            score=lambda p, b: _fanout_scores(p, b, user_tower(p, cfg, b)),
            user_repr=lambda p, b: user_tower(p, cfg, b),
            score_from_user=_fanout_scores))


def _gr(spec: ScenarioSpec, rng, plan) -> ModelBundle:
    from repro.configs import roo_models as rm
    from repro.models.gr import (gr_extend_user_state, gr_history_repr,
                                 gr_init, gr_ranking_logits,
                                 gr_ranking_logits_from_history,
                                 gr_ranking_loss, gr_score_from_state,
                                 gr_state_init)
    m = spec.model
    cfg = dataclasses.replace(
        rm.gr_config(hist_len=m.hist_len, m_targets=m.m_targets),
        n_items=m.n_items)
    return ModelBundle(
        m.arch, cfg, gr_init(rng, cfg),
        lambda p, b, r: gr_ranking_loss(p, cfg, b, plan=plan),
        _ne_metrics(lambda p, b: (
            gr_ranking_logits(p, cfg, b, plan=plan)[:, 0],
            b.labels[:, 0], b.impression_mask())),
        ServeAdapter(
            score=lambda p, b: gr_ranking_logits(p, cfg, b),
            user_repr=lambda p, b: gr_history_repr(p, cfg, b),
            score_from_user=lambda p, b, h:
                gr_ranking_logits_from_history(p, cfg, b, h),
            init_user_state=lambda: gr_state_init(cfg),
            extend_user_state=lambda p, b, s, *, n_new:
                gr_extend_user_state(p, cfg, b, s, n_new=n_new),
            score_from_state=lambda p, b, s, *, n_new:
                gr_score_from_state(p, cfg, b, s, n_new=n_new),
            state_hist_len=cfg.hist_len))


def _dien(spec: ScenarioSpec, rng, plan) -> ModelBundle:
    from repro.models.din_dien import (DIENConfig, dien_init,
                                       dien_logits_roo, dien_loss)
    m = spec.model
    cfg = DIENConfig(n_items=m.n_items, seq_len=m.seq_len or 64)
    return ModelBundle(
        m.arch, cfg, dien_init(rng, cfg),
        lambda p, b, r: dien_loss(p, cfg, b),
        _ne_metrics(lambda p, b: (dien_logits_roo(p, cfg, b),
                                  b.labels[:, 0], b.impression_mask())),
        ServeAdapter(score=lambda p, b: dien_logits_roo(p, cfg, b)))


def _mind(spec: ScenarioSpec, rng, plan) -> ModelBundle:
    from repro.models.mind import (MINDConfig, mind_init, mind_loss,
                                   score_candidates_roo)
    m = spec.model
    cfg = MINDConfig(n_items=m.n_items)
    return ModelBundle(
        m.arch, cfg, mind_init(rng, cfg),
        lambda p, b, r: mind_loss(p, cfg, b), None,
        ServeAdapter(score=lambda p, b: score_candidates_roo(p, cfg, b)))


def _bert4rec(spec: ScenarioSpec, rng, plan) -> ModelBundle:
    from repro.models.bert4rec import (BERT4RecConfig, bert4rec_init,
                                       bert4rec_loss, score_candidates_roo)
    m = spec.model
    cfg = BERT4RecConfig(n_items=m.n_items, seq_len=m.seq_len or 65)
    return ModelBundle(
        m.arch, cfg, bert4rec_init(rng, cfg),
        lambda p, b, r: bert4rec_loss(p, cfg, b, r), None,
        ServeAdapter(score=lambda p, b: score_candidates_roo(p, cfg, b)))


def dlrm_config(spec: ScenarioSpec):
    """dlrm-mlperf at ``DLRMConfig``'s published widths, each table capped
    at ``model.n_items`` rows."""
    from repro.models.dlrm import DLRMConfig
    return DLRMConfig(vocabs=tuple(min(v, spec.model.n_items)
                                   for v in DLRMConfig.vocabs))


def _dlrm(spec: ScenarioSpec, rng, plan) -> ModelBundle:
    from repro.models.dlrm import dlrm_init, dlrm_logits_roo, dlrm_loss
    cfg = dlrm_config(spec)
    return ModelBundle(
        spec.model.arch, cfg, dlrm_init(rng, cfg),
        lambda p, b, r: dlrm_loss(p, cfg, b, plan=plan),
        _ne_metrics(lambda p, b: (dlrm_logits_roo(p, cfg, b, plan=plan),
                                  b.labels[:, 0], b.impression_mask())),
        None)


def _dlrm_layout(spec: ScenarioSpec):
    from repro.models.dlrm import dlrm_batcher_config, dlrm_sample
    cfg = dlrm_config(spec)
    return (lambda base: dlrm_batcher_config(cfg, base),
            lambda s: dlrm_sample(s, cfg))


ARCHS: Dict[str, Arch] = {
    "roo-lsr": Arch(_lsr, plan_routed=True),
    "roo-esr": Arch(_esr),
    "roo-retrieval": Arch(_retrieval),
    "hstu-gr": Arch(_gr, plan_routed=True),
    "dien": Arch(_dien, {"model.seq_len": 64}),
    "mind": Arch(_mind),
    "bert4rec": Arch(_bert4rec, {"model.seq_len": 65}),
    # DLRMConfig's published widths; model.n_items caps every table's rows
    # (and sizes the stream), so the CPU smoke run holds the tables
    "dlrm-mlperf": Arch(_dlrm, {"model.n_items": 1000, "batcher.b_ro": 8,
                                "batcher.b_nro": 32},
                        layout=_dlrm_layout),
}


def arch_of(spec: ScenarioSpec) -> Arch:
    """The table entry for ``spec.model.arch``."""
    try:
        return ARCHS[spec.model.arch]
    except KeyError:
        raise ScenarioValidationError(
            f"scenario {spec.name!r}: model.arch {spec.model.arch!r} is not "
            f"a recsys scenario arch; expected one of {tuple(ARCHS)}"
        ) from None
