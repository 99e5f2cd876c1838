"""Training launcher: ``--arch <id>`` selects any registered architecture.

Recsys archs (roo-lsr / roo-esr / roo-retrieval / hstu-gr / dien / mind /
bert4rec / dlrm-mlperf) are **scenario-driven**: the registry's
ScenarioSpec factory (configs/registry.py) supplies the declarative
config, ``--config spec.json`` replaces it with a serialized spec,
``--set section.field=value`` applies dotted overrides, and every legacy
flag (--steps, --b-ro, --data, ...) still works — flags are translated
into the same overrides, so existing invocations and CI commands behave
identically. Construction happens in ``repro.scenario.build``, the SAME
code path tests and CI smoke runs use, which is what makes a spec-driven
run bit-identical to its flag-driven equivalent
(tests/test_scenario.py). See docs/CONFIG.md.

LM/GNN archs train their reduced smoke config — the full configs are
exercised via launch/dryrun.py (ShapeDtypeStruct only).

SPMD: ``--mesh DATAxMODEL`` (or ``--set train.mesh=2x4``) runs the recsys
archs under a real device mesh. On CPU, simulate devices with
XLA_FORCE_HOST_PLATFORM_DEVICE_COUNT (read below, before jax
initializes). See docs/DISTRIBUTED.md.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch roo-lsr --steps 200
  PYTHONPATH=src python -m repro.launch.train --arch roo-lsr \
      --config myrun.json --set train.steps=500 --set knobs.emb_dedup=always
  PYTHONPATH=src python -m repro.launch.train --arch roo-lsr --steps 200 \
      --data disk --shard-dir /tmp/roo_shards --ckpt-dir /tmp/roo_ckpt
  PYTHONPATH=src XLA_FORCE_HOST_PLATFORM_DEVICE_COUNT=8 \
      python -m repro.launch.train --arch roo-lsr --steps 50 --mesh 2x4
  PYTHONPATH=src python -m repro.launch.train --arch dien --steps 50
  PYTHONPATH=src python -m repro.launch.train --arch starcoder2-15b --steps 20
"""
from __future__ import annotations

import argparse
import time
from typing import Optional

# must run before jax touches the backend: the CI/test convention for CPU
# device simulation is the env var; translate it into the XLA flag
from repro.launch.hostdevices import apply_host_device_env

apply_host_device_env()

import jax
import jax.numpy as jnp

from repro.obs.log import get_logger

LM_ARCHS = ("starcoder2-15b", "deepseek-coder-33b", "phi3-medium-14b",
            "qwen3-moe-235b-a22b", "granite-moe-3b-a800m")

log = get_logger("launch")


def _parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="registered arch id; optional when --config "
                         "supplies the scenario")
    # scenario surface
    ap.add_argument("--config", default=None, metavar="SPEC.json",
                    help="load a serialized ScenarioSpec instead of the "
                         "registry factory for --arch")
    ap.add_argument("--set", action="append", default=[], metavar="K=V",
                    dest="sets",
                    help="dotted spec override, e.g. train.steps=500 or "
                         "knobs.attn_backend=jnp-chunked (repeatable)")
    ap.add_argument("--dump-config", default=None, metavar="OUT.json",
                    help="write the resolved spec as JSON and exit "
                         "(the artifact --config replays)")
    # legacy flags — kept working as spec overrides (None = not passed)
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--b-ro", type=int, default=None)
    ap.add_argument("--b-nro", type=int, default=None)
    ap.add_argument("--attn-backend", default=None,
                    choices=("pallas", "pallas-interpret", "jnp-chunked",
                             "jnp-dense"),
                    help="HSTU attention backend (default: auto — fused "
                         "Pallas kernel on TPU, chunked jnp elsewhere)")
    ap.add_argument("--emb-backend", default=None,
                    choices=("pallas", "pallas-interpret", "jnp"),
                    help="embedding-bag backend (default: auto — fused "
                         "Pallas kernel on TPU, jnp elsewhere)")
    ap.add_argument("--emb-dedup", default=None,
                    choices=("auto", "always", "never"),
                    help="request-level id dedup before embedding lookups "
                         "(default auto: tables >= 4096 rows)")
    ap.add_argument("--comms-compress", default=None,
                    choices=("none", "bf16", "int8"),
                    help="wire compression for the sharded-embedding "
                         "exchange (int8 = per-block scales + error-"
                         "feedback residual; see docs/DISTRIBUTED.md)")
    ap.add_argument("--comms-overlap", default=None, choices=("on", "off"),
                    help="overlap embedding-lookup collectives with dense "
                         "compute across grad-accum microbatches (unrolls "
                         "the accumulation scan)")
    ap.add_argument("--comms-block", type=int, default=None,
                    help="int8 scale-block width for --comms-compress "
                         "(default 128)")
    ap.add_argument("--data", default=None, choices=("memory", "disk"),
                    help="recsys data path: in-memory batches (default) or "
                         "the disk-backed shard pipeline with prefetch + "
                         "cursor resume")
    ap.add_argument("--shard-dir", default="/tmp/roo_shards",
                    help="shard directory for --data disk (reused if a "
                         "manifest already exists)")
    ap.add_argument("--requests-per-shard", type=int, default=None)
    ap.add_argument("--strict-shards", action="store_true",
                    help="raise on corrupt shards instead of quarantining "
                         "them (data-validation runs)")
    ap.add_argument("--halt-after-skips", type=int, default=None,
                    help="halt after N consecutive non-finite training "
                         "steps (0 = keep skipping silently)")
    ap.add_argument("--no-prefetch", action="store_true",
                    help="disable the background prefetch thread "
                         "(synchronous shard reads; benchmarking aid)")
    ap.add_argument("--label-wait", type=float, default=None,
                    help="online-join label wait window (seconds)")
    ap.add_argument("--late-fraction", type=float, default=None,
                    help="fraction of conversions given a heavy-tail delay")
    ap.add_argument("--mesh", default=None, metavar="DATAxMODEL",
                    help="run SPMD over a device mesh, e.g. 2x4 (or "
                         "PODxDATAxMODEL). On CPU set "
                         "XLA_FORCE_HOST_PLATFORM_DEVICE_COUNT to the "
                         "device product. roo-lsr / hstu-gr only (plan-"
                         "routed losses).")
    # observability (docs/OBSERVABILITY.md)
    ap.add_argument("--obs", default=None,
                    choices=("off", "metrics", "trace"),
                    help="observability mode (spec obs.mode / env "
                         "REPRO_OBS): metrics = registry counters/"
                         "histograms, trace = metrics + span tracing")
    ap.add_argument("--obs-export", default=None, metavar="OUT.jsonl",
                    help="append periodic metrics snapshots to this JSONL "
                         "file (cadence obs.export_every_s; read with "
                         "python -m repro.obs.report)")
    ap.add_argument("--trace-out", default=None, metavar="OUT.json",
                    help="save the run's span trace as Chrome trace-event "
                         "JSON (open in Perfetto; implies --obs trace)")
    return ap


def _flag_overrides(args) -> dict:
    """Legacy flags -> dotted spec overrides (only flags actually passed)."""
    mapping = {
        "train.steps": args.steps,
        "batcher.b_ro": args.b_ro,
        "batcher.b_nro": args.b_nro,
        "knobs.attn_backend": args.attn_backend,
        "knobs.emb_backend": args.emb_backend,
        "knobs.emb_dedup": args.emb_dedup,
        "knobs.comms_compress": args.comms_compress,
        "knobs.comms_overlap": args.comms_overlap,
        "knobs.comms_block": args.comms_block,
        "data.source": args.data,
        "data.requests_per_shard": args.requests_per_shard,
        "data.label_wait_s": args.label_wait,
        "data.late_fraction": args.late_fraction,
        "train.halt_after_skips": args.halt_after_skips,
        "train.mesh": args.mesh,
        "obs.mode": (args.obs if args.obs is not None
                     else "trace" if args.trace_out else None),
    }
    out = {k: v for k, v in mapping.items() if v is not None}
    if args.obs_export:
        out["obs.export"] = True
    if args.strict_shards:
        out["data.strict_shards"] = True
    if args.no_prefetch:
        out["data.prefetch"] = False
    return out


def resolve_spec(args):
    """--config / registry factory + --set + legacy flags -> ScenarioSpec."""
    from repro.configs.registry import scenario
    from repro.scenario.spec import ScenarioSpec, parse_set_args
    if args.config:
        spec = ScenarioSpec.load(args.config)
        if args.arch and args.arch != spec.model.arch:
            raise SystemExit(f"--arch {args.arch} contradicts --config "
                             f"(model.arch={spec.model.arch}); drop one")
    else:
        spec = scenario(args.arch)
    overrides = _flag_overrides(args)
    overrides.update(parse_set_args(args.sets))   # --set beats legacy flags
    return spec.with_overrides(overrides) if overrides else spec


def _train_lm(arch: str, steps: int, ckpt_dir: Optional[str], rng) -> None:
    from repro.configs.registry import get_arch
    from repro.models.lm.transformer import lm_init, lm_loss
    from repro.train.loop import Trainer, TrainLoopConfig
    from repro.train.optim import adam
    cfg = get_arch(arch).smoke_config()
    params = lm_init(rng, cfg)

    def batch_iter(start):
        def gen():
            i = start
            while True:
                r = jax.random.fold_in(rng, i)
                toks = jax.random.randint(r, (4, 64), 0, cfg.vocab)
                yield {"tokens": toks}
                i += 1
        return gen()

    trainer = Trainer(
        lambda p, b, r: lm_loss(p, cfg, b["tokens"], b["tokens"]),
        adam(3e-4),
        TrainLoopConfig(total_steps=steps, log_every=10,
                        ckpt_dir=ckpt_dir, ckpt_every=50),
        lambda: params)
    state = trainer.run(batch_iter, rng)
    log.info("lm-smoke-done", arch=arch,
             loss=round(trainer.history[-1]["loss"], 4),
             step=int(state["step"]))


def _train_mace(steps: int, ckpt_dir: Optional[str], rng) -> None:
    import numpy as np
    from repro.models.gnn.mace import MACEConfig, mace_forward, mace_init
    from repro.train.loop import Trainer, TrainLoopConfig
    from repro.train.optim import adam
    cfg = MACEConfig(channels=32, n_feat_in=8)
    params = mace_init(rng, cfg)
    r = np.random.RandomState(0)
    n, e, g = 64, 256, 8
    batch = dict(
        node_feat=jnp.asarray(r.normal(size=(n, 8)).astype(np.float32)),
        positions=jnp.asarray(r.normal(size=(n, 3)).astype(np.float32)),
        edge_index=jnp.asarray(r.randint(0, n, (e, 2)).astype(np.int32)),
        edge_mask=jnp.ones((e,), bool),
        graph_ids=jnp.asarray(np.sort(r.randint(0, g, n)).astype(np.int32)))
    targets = jnp.asarray(r.normal(size=(g,)).astype(np.float32))

    def loss_fn(p, b, _):
        out = mace_forward(p, cfg, **b, n_graphs=g)
        return jnp.mean((out["energy"][:, 0] - targets) ** 2)

    trainer = Trainer(loss_fn, adam(1e-3),
                      TrainLoopConfig(total_steps=steps, log_every=10,
                                      ckpt_dir=ckpt_dir),
                      lambda: params)
    trainer.run(lambda s: iter(lambda: batch, None), rng)
    log.info("mace-smoke-done", loss=round(trainer.history[-1]["loss"], 5))


def main(argv=None):
    args = _parser().parse_args(argv)
    if not args.arch and not args.config:
        raise SystemExit("pass --arch <id> or --config spec.json")
    from repro.launch.compile_cache import enable_compilation_cache
    enable_compilation_cache()

    # LM/GNN smoke paths predate the scenario surface and keep their
    # direct construction (they are not recsys scenarios)
    if args.arch in LM_ARCHS:
        _train_lm(args.arch, args.steps or 100, args.ckpt_dir,
                  jax.random.PRNGKey(0))
        return None
    if args.arch == "mace":
        _train_mace(args.steps or 100, args.ckpt_dir, jax.random.PRNGKey(0))
        return None

    from repro.scenario.build import train_from_scenario
    from repro.scenario.spec import ScenarioValidationError
    try:
        spec = resolve_spec(args)
        if args.dump_config:
            spec.save(args.dump_config)
            log.info("config-dumped", scenario=spec.name,
                     hash=spec.content_hash(), path=args.dump_config)
            return None
        t0 = time.time()
        trainer, state = train_from_scenario(
            spec, ckpt_dir=args.ckpt_dir, shard_dir=args.shard_dir,
            telemetry_path=args.obs_export)
    except ScenarioValidationError as e:
        raise SystemExit(str(e))
    dt = time.time() - t0
    # history only fills every log_every steps; short runs end with none
    last = trainer.history[-1] if trainer.history else {}
    kv = {k: round(last[k], 4) for k in ("loss", "ne") if k in last}
    log.info("train-done", arch=spec.model.arch, steps=int(state["step"]),
             seconds=round(dt, 1), scenario=spec.name,
             hash=spec.content_hash(), **kv)
    if args.trace_out:
        from repro.obs import trace as obs_trace
        n = obs_trace.get_tracer().save(args.trace_out)
        log.info("trace-saved", path=args.trace_out, events=n)
    return trainer, state


if __name__ == "__main__":
    main()
