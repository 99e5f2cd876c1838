"""ScenarioSpec -> running objects: the one construction path.

Every consumer — ``launch/train.py``, ``ScoringEngine.from_scenario``,
``repro.scenario.smoke`` (CI), benchmarks — builds stream/batcher/model/
trainer/engine through THESE functions, so a spec-driven run and a
flag-driven run are bit-identical by construction (the flags merely edit
the spec; tests/test_scenario.py proves the parity end to end).

Also home of the provenance plumbing the spec hash rides:

  * :func:`shard_provenance` — what a shard writer stamps into its
    manifest; reuse of a shard directory is gated on the spec's
    ``data_hash`` (stream+batcher sections only), so bumping
    ``train.steps`` never forces a rebuild;
  * :func:`cursor_fingerprint` — (data_hash, manifest shard index):
    what resume cursors are keyed on;
  * checkpoint ``meta.json`` carries ``scenario``/``scenario_hash`` via
    ``TrainLoopConfig.ckpt_meta``.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
from typing import Any, Callable, Dict, List, NamedTuple, Optional

from repro.scenario.spec import ScenarioSpec, ScenarioValidationError
# ServeAdapter moved to repro.serve.adapter in PR 9 (first-class serving
# interface); re-exported here because PRs 2-8 imported it from this module
from repro.serve.adapter import ServeAdapter  # noqa: F401 (re-export)

# archs the recsys scenario surface covers (dry-run-only archs excluded)
RECSYS_ARCHS = ("roo-lsr", "roo-esr", "roo-retrieval", "hstu-gr",
                "dien", "mind", "bert4rec", "dlrm-mlperf")

# archs whose losses route embedding lookups through a sharding plan —
# the only ones that may train under --mesh / train.mesh
PLAN_ARCHS = ("roo-lsr", "hstu-gr")


class ModelBundle(NamedTuple):
    """Everything a trainer/server needs for one arch, built from a spec."""
    arch: str
    cfg: Any
    params: Any
    loss_fn: Callable
    vag_fn: Optional[Callable]               # sparse value_and_grad (or None)
    metrics_fn: Optional[Callable]
    serve: Optional[ServeAdapter]            # None: arch is not ROO-servable


# ---------------------------------------------------------------------------
# Data + batcher sections
# ---------------------------------------------------------------------------

def build_stream_cfg(spec: ScenarioSpec):
    from repro.data.events import EventStreamConfig
    d = spec.data
    return EventStreamConfig(
        n_users=d.n_users, n_items=spec.stream_n_items(),
        n_requests=d.n_requests, product=d.product,
        hist_init_max=d.hist_init_max, seed=d.seed,
        late_fraction=d.late_fraction,
        # a window longer than the stream's default cap must be fillable
        hist_len_max=max(EventStreamConfig.hist_len_max,
                         spec.batcher.hist_len))


def build_batcher_cfg(spec: ScenarioSpec, n_shards: int = 1):
    from repro.data.batcher import BatcherConfig
    return BatcherConfig(b_ro=spec.batcher.b_ro, b_nro=spec.batcher.b_nro,
                         hist_len=spec.batcher.hist_len, n_shards=n_shards)


def build_samples(spec: ScenarioSpec) -> List:
    """Deterministic in-memory ROO samples for the spec's event stream."""
    from repro.core.joiner import RequestLevelJoiner
    from repro.data.events import EventSimulator
    return RequestLevelJoiner().join(
        list(EventSimulator(build_stream_cfg(spec)).stream()))


# ---------------------------------------------------------------------------
# Provenance
# ---------------------------------------------------------------------------

def shard_provenance(spec: ScenarioSpec) -> dict:
    """Manifest provenance for shards built from ``spec``. ``data_hash``
    is the reuse gate; the rest is for humans debugging a directory."""
    return {"scenario": spec.name,
            "scenario_hash": spec.content_hash(),
            "data_hash": spec.data_hash(),
            "stream": dataclasses.asdict(build_stream_cfg(spec)),
            "label_wait_s": spec.data.label_wait_s,
            "requests_per_shard": spec.data.requests_per_shard}


def provenance_matches(stored: dict, spec: ScenarioSpec) -> bool:
    """Whether an existing shard directory holds this spec's data. New
    manifests compare by ``data_hash``; pre-scenario manifests (no hash)
    compare the legacy provenance fields."""
    if "data_hash" in stored:
        return stored["data_hash"] == spec.data_hash()
    want = shard_provenance(spec)
    legacy = {k: want[k] for k in ("stream", "label_wait_s",
                                   "requests_per_shard")}
    return stored == legacy


def cursor_fingerprint(spec: ScenarioSpec, manifest) -> str:
    """What a resume cursor is valid against: the spec's data/batcher
    sections plus the manifest's shard index. Train-section edits (more
    steps, different ckpt cadence) keep the fingerprint stable."""
    shards = [[s.filename, s.n_bytes, s.n_requests, s.n_impressions]
              for s in manifest.shards]
    blob = json.dumps([spec.data_hash(), shards], sort_keys=True)
    return hashlib.sha1(blob.encode("utf-8")).hexdigest()[:16]


def ckpt_meta(spec: ScenarioSpec) -> dict:
    return {"scenario": spec.name, "scenario_hash": spec.content_hash()}


# ---------------------------------------------------------------------------
# Models (params + loss + sparse vag + metrics + serving halves)
# ---------------------------------------------------------------------------

def _ne_metrics(logits_fn):
    from repro.train.metrics import make_ne_metrics
    return make_ne_metrics(logits_fn)


def build_model(spec: ScenarioSpec, rng, plan=None,
                sparse: bool = False) -> ModelBundle:
    """Params, loss and serving halves for ``spec.model`` — the spec-driven
    successor of launch/train.py's per-arch dispatch table."""
    import jax.numpy as jnp

    from repro.configs import roo_models as rm
    from repro.embeddings.sparse import make_sparse_value_and_grad

    arch, m = spec.model.arch, spec.model
    if arch not in RECSYS_ARCHS:
        raise ScenarioValidationError(
            f"scenario {spec.name!r}: model.arch {arch!r} is not a recsys "
            f"scenario arch; expected one of {RECSYS_ARCHS}")

    def sparse_vag(loss, table_ids_fn):
        return (make_sparse_value_and_grad(loss, table_ids_fn)
                if sparse else None)

    if arch == "roo-lsr":
        from repro.models.lsr import (lsr_init, lsr_logits_from_user,
                                      lsr_logits_roo, lsr_loss, lsr_table_ids,
                                      lsr_user_repr)
        cfg = dataclasses.replace(rm.lsr_config(m.variant or "userarch_hstu"),
                                  n_items=m.n_items)
        loss = lambda p, b, r: lsr_loss(p, cfg, b, plan=plan)
        return ModelBundle(
            arch, cfg, lsr_init(rng, cfg), loss,
            sparse_vag(loss, lambda b: lsr_table_ids(cfg, b)),
            _ne_metrics(lambda p, b: (lsr_logits_roo(p, cfg, b, plan=plan)[:, 0],
                                      b.labels[:, 0], b.impression_mask())),
            ServeAdapter(
                score=lambda p, b: lsr_logits_roo(p, cfg, b),
                user_repr=lambda p, b: lsr_user_repr(p, cfg, b),
                score_from_user=lambda p, b, u: lsr_logits_from_user(
                    p, cfg, b, u)))
    if arch == "roo-esr":
        from repro.models.two_tower import (esr_logits_from_user,
                                            esr_logits_roo, esr_loss_roo,
                                            two_tower_init,
                                            two_tower_table_ids, user_tower)
        cfg = dataclasses.replace(rm.esr_config(), n_items=m.n_items)
        loss = lambda p, b, r: esr_loss_roo(p, cfg, b)
        return ModelBundle(
            arch, cfg, two_tower_init(rng, cfg), loss,
            sparse_vag(loss, lambda b: two_tower_table_ids(cfg, b)),
            _ne_metrics(lambda p, b: (esr_logits_roo(p, cfg, b),
                                      b.labels[:, 0], b.impression_mask())),
            ServeAdapter(
                score=lambda p, b: esr_logits_roo(p, cfg, b),
                user_repr=lambda p, b: user_tower(p, cfg, b),
                score_from_user=lambda p, b, u: esr_logits_from_user(
                    p, cfg, b, u)))
    if arch == "roo-retrieval":
        from repro.models.two_tower import (item_tower, retrieval_loss_roo,
                                            two_tower_init,
                                            two_tower_table_ids, user_tower)
        cfg = dataclasses.replace(rm.retrieval_config(), n_items=m.n_items)
        loss = lambda p, b, r: retrieval_loss_roo(p, cfg, b)

        def _fanout_scores(p, b, u):
            v = item_tower(p, cfg, b.item_ids, b.nro_dense)
            seg = jnp.minimum(b.segment_ids, b.b_ro - 1)
            return jnp.sum(u[seg] * v, axis=-1)

        return ModelBundle(
            arch, cfg, two_tower_init(rng, cfg), loss,
            sparse_vag(loss, lambda b: two_tower_table_ids(cfg, b)), None,
            ServeAdapter(
                score=lambda p, b: _fanout_scores(p, b,
                                                  user_tower(p, cfg, b)),
                user_repr=lambda p, b: user_tower(p, cfg, b),
                score_from_user=_fanout_scores))
    if arch == "hstu-gr":
        from repro.models.gr import (gr_extend_user_state, gr_history_repr,
                                     gr_init, gr_ranking_logits,
                                     gr_ranking_logits_from_history,
                                     gr_ranking_loss, gr_score_from_state,
                                     gr_state_init, gr_table_ids)
        cfg = dataclasses.replace(
            rm.gr_config(hist_len=m.hist_len, m_targets=m.m_targets),
            n_items=m.n_items)
        loss = lambda p, b, r: gr_ranking_loss(p, cfg, b, plan=plan)
        return ModelBundle(
            arch, cfg, gr_init(rng, cfg), loss,
            sparse_vag(loss, lambda b: gr_table_ids(cfg, b)),
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
    if arch == "mind":
        from repro.models.mind import (MINDConfig, mind_init, mind_loss,
                                       mind_table_ids, score_candidates_roo)
        cfg = MINDConfig(n_items=m.n_items)
        loss = lambda p, b, r: mind_loss(p, cfg, b)
        return ModelBundle(
            arch, cfg, mind_init(rng, cfg), loss,
            sparse_vag(loss, lambda b: mind_table_ids(cfg, b)), None,
            ServeAdapter(score=lambda p, b: score_candidates_roo(p, cfg, b)))
    if arch == "bert4rec":
        from repro.models.bert4rec import (BERT4RecConfig, bert4rec_init,
                                           bert4rec_loss,
                                           score_candidates_roo)
        if sparse:
            raise ScenarioValidationError(
                "bert4rec's cloze head is a full softmax over item_emb — "
                "dense by construction; drop train.sparse_emb")
        cfg = BERT4RecConfig(n_items=m.n_items, seq_len=m.seq_len or 65)
        return ModelBundle(
            arch, cfg, bert4rec_init(rng, cfg),
            lambda p, b, r: bert4rec_loss(p, cfg, b, r), None, None,
            ServeAdapter(score=lambda p, b: score_candidates_roo(p, cfg, b)))
    if arch == "dien":
        from repro.models.din_dien import (DIENConfig, dien_init,
                                           dien_logits_roo, dien_loss,
                                           dien_table_ids)
        cfg = DIENConfig(n_items=m.n_items, seq_len=m.seq_len or 64)
        loss = lambda p, b, r: dien_loss(p, cfg, b)
        return ModelBundle(
            arch, cfg, dien_init(rng, cfg), loss,
            sparse_vag(loss, lambda b: dien_table_ids(cfg, b)),
            _ne_metrics(lambda p, b: (dien_logits_roo(p, cfg, b),
                                      b.labels[:, 0], b.impression_mask())),
            ServeAdapter(score=lambda p, b: dien_logits_roo(p, cfg, b)))
    # dlrm-mlperf: MLPerf-shaped at reduced scale (the full vocabs are
    # hundreds of millions of rows — dry-run cells only). Field-dict
    # batches, not ROOBatch, so it is synthetic-data-only + not servable
    # through the ROO engine.
    from repro.models.dlrm import (DLRMConfig, dlrm_forward_roo, dlrm_init,
                                   dlrm_table_ids)
    ed = m.embed_dim or 16
    cfg = DLRMConfig(n_dense=4, embed_dim=ed, bot_mlp=(4, 32, ed),
                     top_mlp=(64, 32, 1), vocabs=(512, 256, 64, 32),
                     n_ro_fields=2, multi_hot=2)

    def loss(p, b, r):
        logits = dlrm_forward_roo(p, cfg, b["ro_dense"], b["ro_ids"],
                                  b["ro_len"], b["nro_ids"], b["nro_len"],
                                  b["seg"], plan=plan)
        y = b["y"]
        bce = (jnp.maximum(logits, 0) - logits * y
               + jnp.log1p(jnp.exp(-jnp.abs(logits))))
        return jnp.mean(bce)

    return ModelBundle(
        arch, cfg, dlrm_init(rng, cfg), loss,
        sparse_vag(loss, lambda b: dlrm_table_ids(cfg, b["ro_ids"],
                                                  b["nro_ids"])),
        None, None)


def synthetic_dlrm_batches(spec: ScenarioSpec, cfg, n_batches: int = 4
                           ) -> List[Dict]:
    """Deterministic field-dict batches for dlrm-mlperf (its MLPerf input
    format predates the ROO schema; the stream simulator doesn't emit it)."""
    import jax.numpy as jnp
    import numpy as np

    r = np.random.RandomState(spec.data.seed)
    b_ro, b_nro = spec.batcher.b_ro, spec.batcher.b_nro
    if b_nro % b_ro:
        raise ScenarioValidationError(
            f"scenario {spec.name!r}: dlrm synthetic batches need "
            f"batcher.b_nro divisible by batcher.b_ro")
    mh, n_ro = cfg.multi_hot, cfg.n_ro_fields
    n_nro = cfg.n_sparse - n_ro
    out = []
    for _ in range(n_batches):
        out.append({
            "ro_dense": jnp.asarray(
                r.normal(size=(b_ro, cfg.n_dense)).astype(np.float32)),
            "ro_ids": jnp.asarray(np.stack(
                [r.randint(0, cfg.vocabs[f], (b_ro, mh))
                 for f in range(n_ro)], axis=1).astype(np.int32)),
            "ro_len": jnp.full((b_ro, n_ro), mh, jnp.int32),
            "nro_ids": jnp.asarray(np.stack(
                [r.randint(0, cfg.vocabs[n_ro + f], (b_nro, mh))
                 for f in range(n_nro)], axis=1).astype(np.int32)),
            "nro_len": jnp.full((b_nro, n_nro), mh, jnp.int32),
            "seg": jnp.repeat(jnp.arange(b_ro, dtype=jnp.int32),
                              b_nro // b_ro),
            "y": jnp.asarray(
                (r.uniform(size=(b_nro,)) < 0.3).astype(np.float32))})
    return out


# ---------------------------------------------------------------------------
# Training: the whole recsys path, spec in -> (trainer, final state) out
# ---------------------------------------------------------------------------

def train_from_scenario(spec: ScenarioSpec, *, ckpt_dir: Optional[str] = None,
                        shard_dir: Optional[str] = None, rng_seed: int = 0,
                        prints: bool = True,
                        telemetry_path: Optional[str] = None):
    """Run the spec's training end to end; returns ``(trainer, state)``.

    ``ckpt_dir``/``shard_dir``/``telemetry_path`` are runtime locations,
    deliberately NOT part of the spec (a spec hash must be machine-
    portable). ``telemetry_path`` (or ``obs.export`` in the spec, which
    defaults the file to ``<ckpt_dir>/telemetry.jsonl``) installs a JSONL
    telemetry emitter for the duration of the run. Raises
    :class:`ScenarioValidationError` on config conflicts (the CLI turns
    those into exit messages).
    """
    spec.validate().apply()
    emitter = _install_emitter(spec, telemetry_path, ckpt_dir)
    try:
        return _train_from_scenario(spec, ckpt_dir=ckpt_dir,
                                    shard_dir=shard_dir, rng_seed=rng_seed,
                                    prints=prints)
    finally:
        if emitter is not None:
            from repro.obs import export as obs_export
            obs_export.install(None)
            emitter.close(final_source="train.final")


def _install_emitter(spec: ScenarioSpec, telemetry_path: Optional[str],
                     ckpt_dir: Optional[str]):
    if not (spec.obs.export or telemetry_path):
        return None
    from repro.obs import export as obs_export
    if telemetry_path is None:
        if not ckpt_dir:
            raise ScenarioValidationError(
                "obs.export needs somewhere to write: pass --obs-export "
                "PATH or a --ckpt-dir (defaults to "
                "<ckpt_dir>/telemetry.jsonl)")
        os.makedirs(ckpt_dir, exist_ok=True)
        telemetry_path = os.path.join(ckpt_dir, "telemetry.jsonl")
    emitter = obs_export.TelemetryEmitter(
        telemetry_path, every_s=spec.obs.export_every_s,
        scenario_hash=spec.content_hash())
    obs_export.install(emitter)
    return emitter


def _train_from_scenario(spec: ScenarioSpec, *, ckpt_dir, shard_dir,
                         rng_seed, prints):
    import jax

    from repro.obs.log import get_logger
    log = get_logger("scenario", enabled=prints)

    from repro.reliability import faults as _faults
    _plan = _faults.active_plan()
    if _plan is not None:
        # fault injection is never silent: a chaos run announces itself
        log.info("fault-injection-active", plan=_plan.to_env())

    rng = jax.random.PRNGKey(rng_seed)
    arch, tr = spec.model.arch, spec.train

    plan = None
    if tr.mesh:
        # only archs whose loss threads the plan into sharded lookups may
        # run under a mesh: sharding the state of a plan-blind loss would
        # silently re-gather every row-sharded table each step
        if arch not in PLAN_ARCHS:
            raise ScenarioValidationError(
                f"train.mesh supports {', '.join(PLAN_ARCHS)} (their losses "
                f"route lookups through the sharding plan); {arch} would "
                f"train slower sharded than replicated")
        from repro.distributed.sharding import plan_for_mesh
        from repro.launch.mesh import make_mesh_from_spec
        mesh = make_mesh_from_spec(tr.mesh)
        plan = plan_for_mesh(mesh)
        log.info("mesh",
                 axes=dict(zip(mesh.axis_names, mesh.devices.shape)),
                 devices=mesh.devices.size)
    if tr.sparse_emb and plan is not None:
        # the GatheredTable proxy gathers rows locally, bypassing the psum
        # lookups a row-sharded table needs — pick one regime per run
        raise ScenarioValidationError(
            "train.sparse_emb and train.mesh are mutually exclusive: sparse "
            "row grads assume locally-addressable tables (see "
            "docs/EMBEDDINGS.md)")

    bundle = build_model(spec, rng, plan=plan, sparse=tr.sparse_emb)
    if tr.sparse_emb and bundle.vag_fn is None:
        raise ScenarioValidationError(
            f"{arch} has no table_ids declaration; train.sparse_emb "
            f"unsupported")

    n_data_shards = 1
    if plan is not None:
        from repro.distributed.spmd import data_shard_count
        n_data_shards = data_shard_count(plan)
        if spec.batcher.b_ro % n_data_shards or \
                spec.batcher.b_nro % n_data_shards:
            raise ScenarioValidationError(
                f"batcher.b_ro/b_nro must be divisible by the mesh's "
                f"{n_data_shards} data shard(s)")
    batcher_cfg = build_batcher_cfg(spec, n_shards=n_data_shards)

    from repro.train.loop import Trainer, TrainLoopConfig
    from repro.train.optim import (adam, default_is_embedding, make_mixed,
                                   rowwise_adagrad)
    opt = make_mixed(adam(tr.lr_dense), rowwise_adagrad(tr.lr_emb),
                     default_is_embedding)
    trainer = Trainer(
        bundle.loss_fn, opt,
        TrainLoopConfig(total_steps=tr.steps, log_every=tr.log_every,
                        ckpt_dir=ckpt_dir, ckpt_every=tr.ckpt_every,
                        keep_last=tr.keep_last, microbatches=tr.microbatches,
                        halt_after_skips=tr.halt_after_skips,
                        ckpt_meta=ckpt_meta(spec)),
        lambda: bundle.params, plan=plan,
        value_and_grad_fn=bundle.vag_fn, metrics_fn=bundle.metrics_fn)

    if spec.data.source == "synthetic" or arch == "dlrm-mlperf":
        if arch != "dlrm-mlperf":
            raise ScenarioValidationError(
                f"data.source='synthetic' is the dlrm-mlperf field-batch "
                f"path; {arch} trains from the event stream "
                f"(data.source memory|disk)")
        if spec.data.source != "synthetic":
            raise ScenarioValidationError(
                "dlrm-mlperf consumes MLPerf field-dict batches, not ROO "
                "samples — set data.source='synthetic'")
        batches = synthetic_dlrm_batches(spec, bundle.cfg)
        state = trainer.run(_cycling_iter_fn(batches), rng)
    elif spec.data.source == "disk":
        state = _train_disk(spec, trainer, batcher_cfg, rng, plan,
                            shard_dir=shard_dir, ckpt_dir=ckpt_dir, log=log)
    else:
        from repro.data.batcher import ROOBatcher
        batches = list(ROOBatcher(batcher_cfg).batches(build_samples(spec)))
        state = trainer.run(_cycling_iter_fn(batches), rng)
    return trainer, state


def _cycling_iter_fn(batches):
    def batch_iter(start):
        def gen():
            i = start
            while True:
                yield batches[i % len(batches)]
                i += 1
        return gen()
    return batch_iter


def _train_disk(spec, trainer, batcher_cfg, rng, plan, *, shard_dir,
                ckpt_dir, log):
    """Disk pipeline: (re)build shards, wire cursor resume, run."""
    from repro.distributed.spmd import make_batch_sharding_fn
    from repro.pipeline import (OnlineJoinConfig, WatermarkJoiner,
                                load_manifest, make_data_source,
                                write_samples)
    if not shard_dir:
        raise ScenarioValidationError(
            "data.source='disk' needs a shard_dir (--shard-dir)")
    provenance = shard_provenance(spec)
    try:
        manifest = load_manifest(shard_dir)
        if not provenance_matches(manifest.provenance, spec):
            raise ScenarioValidationError(
                f"[pipeline] {shard_dir} holds shards built with different "
                f"settings:\n  stored:    {manifest.provenance}\n"
                f"  requested: {provenance}\n"
                f"Pick another --shard-dir or delete the old one.")
        log.info("shards-reused", n=len(manifest.shards), dir=shard_dir)
    except FileNotFoundError:
        from repro.data.events import EventSimulator
        joiner = WatermarkJoiner(OnlineJoinConfig(
            label_wait_s=spec.data.label_wait_s))
        samples = joiner.join(
            EventSimulator(build_stream_cfg(spec)).stream())
        manifest = write_samples(
            shard_dir, samples,
            requests_per_shard=spec.data.requests_per_shard,
            provenance=provenance)
        st = joiner.stats
        log.info("shards-built", requests=st.requests_emitted,
                 label_completeness=round(st.label_completeness, 3),
                 mean_close_lag_s=round(st.mean_close_lag_s, 1),
                 shards=len(manifest.shards),
                 mb=round(manifest.n_bytes / 1e6, 2))
    cursor_dir = os.path.join(ckpt_dir or shard_dir, "cursors")
    source = make_data_source(shard_dir, batcher_cfg, cursor_dir,
                              prefetch=spec.data.prefetch,
                              sharding=make_batch_sharding_fn(plan),
                              strict=spec.data.strict_shards,
                              fingerprint=cursor_fingerprint(spec, manifest))
    with source:                       # join producer threads on exit
        state = trainer.run(source.batch_iter_fn, rng,
                            on_checkpoint=source.on_checkpoint)
    ds_stats = source.loader.dataset.stats
    if ds_stats.shards_quarantined:
        log.info("shards-quarantined", n=ds_stats.shards_quarantined,
                 files=ds_stats.quarantined_files)
    if trainer.skipped_steps:
        log.info("steps-skipped", n=trainer.skipped_steps)
    return state


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------

def engine_from_scenario(spec: ScenarioSpec, params=None, rng_seed: int = 0,
                         clock=None):
    """ScoringEngine for the spec's model (the ``from_scenario`` core).

    ``params=None`` initializes fresh parameters from ``rng_seed`` —
    handy for benchmarks; production passes trained params.
    """
    import jax
    import time as _time

    from repro.serve.bucketing import BucketLadder
    from repro.serve.engine import EnginePolicy, ScoringEngine
    from repro.serve.user_cache import UserStateStore, UserTowerCache

    spec.validate().apply()
    bundle = build_model(spec, jax.random.PRNGKey(rng_seed))
    if bundle.serve is None:
        raise ScenarioValidationError(
            f"scenario {spec.name!r}: {spec.model.arch} is not servable "
            f"through the ROO engine (field-dict batches, no ROO forward)")
    sv = spec.serve
    policy = EnginePolicy(max_requests=sv.max_requests,
                          max_impressions=sv.max_impressions,
                          max_delay_ms=sv.max_delay_ms,
                          hist_len=spec.batcher.hist_len,
                          breaker_threshold=sv.breaker_threshold,
                          breaker_cooldown_s=sv.breaker_cooldown_s)
    ladder = (BucketLadder.geometric(
                  min_b_ro=min(4, sv.max_requests),
                  min_b_nro=min(32, sv.max_impressions),
                  max_b_ro=sv.max_requests, max_b_nro=sv.max_impressions)
              if sv.bucketed else
              BucketLadder.fixed(sv.max_requests, sv.max_impressions))
    adapter = bundle.serve
    cache = None
    state_store = None
    if sv.cache_user_tower:
        if not adapter.supports_user_cache:
            raise ScenarioValidationError(
                f"scenario {spec.name!r}: serve.cache_user_tower needs "
                f"split user/score entry points; {spec.model.arch} has a "
                f"fused forward only")
        cache = UserTowerCache(sv.cache_capacity)
    if sv.incremental:
        if not adapter.supports_incremental:
            raise ScenarioValidationError(
                f"scenario {spec.name!r}: serve.incremental needs the "
                f"stateful adapter hooks (init_user_state/score_from_state);"
                f" {spec.model.arch} serves statelessly")
        if adapter.state_hist_len != spec.batcher.hist_len:
            raise ScenarioValidationError(
                f"scenario {spec.name!r}: serve.incremental needs the "
                f"model's state window to equal the batcher window "
                f"(model.hist_len {adapter.state_hist_len} != "
                f"batcher.hist_len {spec.batcher.hist_len}); otherwise "
                f"'prefix of the served history' is ill-defined")
        state_store = UserStateStore(sv.state_capacity)
    return ScoringEngine(
        params if params is not None else bundle.params,
        policy=policy, ladder=ladder, adapter=adapter,
        user_fn=adapter.user_repr if cache is not None else None,
        score_from_user=(adapter.score_from_user
                         if cache is not None else None),
        cache=cache, state_store=state_store,
        attn_backend=spec.knobs.attn_backend,
        clock=clock if clock is not None else _time.monotonic)
