#!/usr/bin/env python3
"""Chip smoke test: hstu-gr training and serving on one TPU through the
compiled Pallas kernels, the way a user drives them.

    python chip_smoke.py               # one chip: phases (a)-(d)
    python chip_smoke.py --four-chips  # 2x2 mesh training vs one device

Phases, one line each (phase, compile seconds, steady seconds, checks):

  (a) kernels  HSTU fused forward + grads and the cached-prefix kernel at
               hstu-gr width (B 32, H 2, S 1,040 = 1,024 history events + 16
               targets, d 32, bias on), the embedding bag forward + grads at
               d 16 and d 128, each against ``kernels/ref.py`` run under
               ``jax.default_matmul_precision("highest")``.
  (b) train    hstu-gr through ``train_from_scenario``, 10 steps at
               1,024-event histories; every loss finite, no skipped step.
      parity   the same 10 steps on pallas and on ``jnp-chunked``, both with
               f32 dots at full precision, agree step by step.
  (c) serve    ``engine_from_scenario`` with the trained params, stateless
               and then incremental on repeat users (the prefix kernel);
               every request scored, no ``ScoreError``, incremental scores
               equal to stateless ones within the kernel tolerance.
  (d) dlrm     dlrm-mlperf as registered (published widths, tables capped
               at ``model.n_items`` rows), 5 steps on ``ROOBatch``es (the
               bag kernel trains).

The last line of stdout is one JSON object naming the device. The script
exits non-zero, printing no result, unless JAX's first device is a TPU and
both kernel dispatchers resolve to compiled ``pallas``. Everything runs in
this one process: a child would find the chip held.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# weights are random (seeded), so tolerances are relative to each output's
# largest magnitude. The kernels' f32 dots and the reference's
# ``highest``-precision dots may differ by bf16-pass rounding (2**-8 per
# product); accumulated over ~1e3 terms of mixed sign that stays well below:
KERNEL_TOL = 5e-3
# two 10-step loss trajectories at full f32 matmul precision (pallas vs
# jnp-chunked, 2x2 mesh vs one device): each step's loss within
# tests/test_distributed_train.py's parity bound
LOSS_RTOL = 2e-4

# the backend phase (a) drives; a CPU rehearsal swaps in pallas-interpret
PALLAS = "pallas"

HSTU_GR = {"model.hist_len": 1024, "batcher.hist_len": 1024,
           "data.hist_init_max": 1024, "train.log_every": 1,
           "train.steps": 10, "train.halt_after_skips": 1}


class SmokeFailure(RuntimeError):
    pass


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _rel_err(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    _check(got.shape == want.shape, f"shape {got.shape} != {want.shape}")
    _check(bool(np.all(np.isfinite(got))), "non-finite output")
    scale = max(float(np.max(np.abs(want))), 1e-30)
    return float(np.max(np.abs(got - want))) / scale


def _timed(fn, *args):
    """(first-call seconds incl. compile, repeat-call seconds, result)."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    t1 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    t2 = time.perf_counter()
    return t1 - t0, t2 - t1, out


def _report(phase: str, compile_s: float, steady_s: float, checks: dict):
    body = " ".join(f"{k}={v:.3g}" if isinstance(v, float) else f"{k}={v}"
                    for k, v in checks.items())
    print(f"phase={phase} compile_s={compile_s:.2f} steady_s={steady_s:.3f} "
          f"{body}", flush=True)


# ---------------------------------------------------------------------------
# (a) kernel parity
# ---------------------------------------------------------------------------

def phase_kernels(b: int = 32, h: int = 2, n_hist: int = 1024, m: int = 16,
                  d: int = 32, max_rel: int = 1024) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core.masks import MaskSpec, PrefixMaskSpec
    from repro.kernels import dispatch, ref
    from repro.kernels.embedding_bag import embedding_bag

    s = n_hist + m
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    q, k, v = (jax.random.normal(ks[i], (b, h, s, d)) for i in range(3))
    rab = jax.random.normal(ks[3], (h, 2 * max_rel + 1)) * 0.1
    hl = jax.random.randint(ks[4], (b,), n_hist // 2, n_hist + 1)
    tc = jax.random.randint(ks[5], (b,), 1, m + 1)
    w = jax.random.normal(ks[6], (b, h, s, d))
    spec = MaskSpec(n_hist, hl, tc)

    def loss(be):
        def f(q, k, v, rab):
            out = dispatch.hstu_attention(q, k, v, rab, spec, backend=be,
                                          max_rel_pos=max_rel)
            return jnp.sum(out * w), out
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2, 3),
                                          has_aux=True))

    checks = {}
    compile_s = steady_s = 0.0
    c, st, ((_, out), grads) = _timed(loss(PALLAS), q, k, v, rab)
    compile_s += c
    steady_s += st
    with jax.default_matmul_precision("highest"):
        (_, want), want_g = loss("jnp-dense")(q, k, v, rab)
        checks["hstu_fwd"] = _rel_err(out, want)
        for name, g, wg in zip(("dq", "dk", "dv", "drab"), grads, want_g):
            checks[f"hstu_{name}"] = _rel_err(g, wg)
        # the same kernels with their dots at full precision, as the
        # trajectory comparisons of phase_parity and phase_mesh run them
        (_, hi), hi_g = loss(PALLAS)(q, k, v, rab)
        checks["hstu_highest"] = max(
            _rel_err(x, y) for x, y in zip((hi, *hi_g), (want, *want_g)))

    # cached prefix: 16 new events after a ragged cached prefix
    n_new = m
    pfx = jax.random.randint(ks[7], (b,), 0, n_hist - n_new + 1)
    nc = jnp.full((b,), n_new, jnp.int32)
    pspec = PrefixMaskSpec(n_hist, n_new, pfx, nc, tc)
    qn = q[:, :, n_hist - n_new:]

    def prefix(be):
        return jax.jit(lambda q, k, v, rab: dispatch.hstu_attention_prefix(
            q, k, v, rab, pspec, backend=be, scale_len=s,
            max_rel_pos=max_rel))
    c, st, got = _timed(prefix(PALLAS), qn, k, v, rab)
    compile_s += c
    steady_s += st
    with jax.default_matmul_precision("highest"):
        checks["prefix_fwd"] = _rel_err(
            got, prefix("jnp-dense")(qn, k, v, rab))

    for dim in (16, 128):
        vocab, nb, nl = 4096, 256, 8
        tbl = jax.random.normal(jax.random.fold_in(ks[0], dim), (vocab, dim))
        ids = jax.random.randint(ks[1], (nb, nl), 0, vocab)
        lens = jax.random.randint(ks[2], (nb,), 0, nl + 1)
        gw = jax.random.normal(ks[3], (nb, dim))

        def bag(fn):
            def f(t):
                out = fn(t)
                return jnp.sum(gw * out), out
            return jax.jit(jax.value_and_grad(f, has_aux=True))
        kernel = bag(lambda t: embedding_bag(t, ids, lens, "sum",
                                             backend=PALLAS))
        c, st, ((_, out), g) = _timed(kernel, tbl)
        compile_s += c
        steady_s += st
        with jax.default_matmul_precision("highest"):
            (_, want), wg = bag(lambda t: ref.embedding_bag_ref(
                t, ids, lens, "sum"))(tbl)
            checks[f"bag{dim}_fwd"] = _rel_err(out, want)
            checks[f"bag{dim}_grad"] = _rel_err(g, wg)

    worst = max(checks.values())
    checks["tol"] = KERNEL_TOL
    _report("a_kernels", compile_s, steady_s, checks)
    _check(worst <= KERNEL_TOL,
           f"kernel parity {worst:.3g} > {KERNEL_TOL} ({checks})")


# ---------------------------------------------------------------------------
# (b) training, (c) serving, (d) dlrm
# ---------------------------------------------------------------------------

def _train(spec):
    """Train through the user's entry point; returns (trainer, state, losses,
    first-step seconds, seconds of the remaining steps)."""
    import numpy as np

    from repro.scenario.build import train_from_scenario
    trainer, state = train_from_scenario(spec, prints=False)
    rows = trainer.history
    _check(len(rows) == spec.train.steps,
           f"{len(rows)} logged steps, expected {spec.train.steps}")
    losses = np.asarray([r["loss"] for r in rows])
    _check(bool(np.all(np.isfinite(losses))), f"non-finite loss {losses}")
    _check(trainer.skipped_steps == 0 and
           all(r.get("skipped", 0.0) == 0.0 for r in rows),
           f"skipped steps: {trainer.skipped_steps}")
    # cumulative steps/s per logged step -> wall clock at each step
    at = [r["step"] / r["steps_per_s"] for r in rows]
    return trainer, state, losses, at[0], at[-1] - at[0]


def _step_has_kernel(trainer, state, batch) -> bool:
    import jax
    lowered = trainer.step_fn.lower(state, batch, jax.random.PRNGKey(0))
    return "tpu_custom_call" in lowered.as_text()


def _highest_losses(*specs):
    """Each spec's (losses, first-step s, later-steps s) with every f32 dot
    at full precision.

    Trajectory comparisons run there. At the default precision (one bf16
    pass) two equally correct programs round differently, Adam's first
    steps turn that noise in near-zero gradients into full-size updates,
    and the trajectories part by ~3% in 10 steps (pallas vs jnp-chunked on
    a v5e); at full precision only the summation order differs."""
    import jax

    from repro.kernels import dispatch
    default = dispatch.get_default_backend()
    with jax.default_matmul_precision("highest"):
        out = [_train(spec)[2:] for spec in specs]
    dispatch.set_default_backend(default)   # a spec's knobs are process-wide
    return out


def _max_rel(got, want) -> float:
    import numpy as np
    return float(np.max(np.abs(got - want) / np.abs(want)))


def phase_train(overrides=HSTU_GR):
    from repro.configs.registry import scenario
    from repro.data.batcher import ROOBatcher
    from repro.scenario.build import build_batcher_cfg, build_samples

    spec = scenario("hstu-gr", overrides)
    trainer, state, losses, c, st = _train(spec)
    batch = next(iter(ROOBatcher(build_batcher_cfg(spec)).batches(
        build_samples(spec))))
    _check(_step_has_kernel(trainer, state, batch),
           "no Pallas kernel in the hstu-gr train step")
    _report("b_train", c, st, {
        "steps": len(losses), "loss_first": float(losses[0]),
        "loss_last": float(losses[-1]), "skipped": trainer.skipped_steps})
    return spec, state["params"]


def phase_parity(spec) -> None:
    """The same 10 steps on pallas and on jnp-chunked."""
    (pallas, c1, s1), (ref, c2, s2) = _highest_losses(
        spec, spec.with_overrides({"knobs.attn_backend": "jnp-chunked"}))
    rel = _max_rel(pallas, ref)
    _report("b_parity", c1 + c2, s1 + s2, {
        "loss_last": float(pallas[-1]), "pallas_vs_jnp_chunked_rel": rel,
        "rtol": LOSS_RTOL})
    _check(rel <= LOSS_RTOL,
           f"pallas vs jnp-chunked losses differ by {rel:.3g}: {pallas} vs "
           f"{ref}")


def _serve(engine, requests):
    import numpy as np

    from repro.serve.engine import ScoreError
    t0 = time.perf_counter()
    scores = engine.score_requests(requests)
    dt = time.perf_counter() - t0
    _check(len(scores) == len(requests), "a request got no score array")
    for r, sc in zip(requests, scores):
        _check(not isinstance(sc, ScoreError), f"ScoreError: {sc}")
        _check(sc.shape[0] == r.num_impressions,
               f"{sc.shape[0]} scores for {r.num_impressions} impressions")
        _check(bool(np.all(np.isfinite(sc))), "non-finite score")
    _check(engine.stats.n_failed_batches == 0, "a scoring batch failed")
    return scores, dt


def phase_serve(spec, params, n_requests: int = 16) -> None:
    import numpy as np

    from repro.scenario.build import build_samples, engine_from_scenario
    requests = [r for r in build_samples(spec) if r.num_impressions][
        :n_requests]

    stateless = engine_from_scenario(spec, params=params)
    base, c0 = _serve(stateless, requests)
    _, s0 = _serve(stateless, requests)

    inc_spec = spec.with_overrides({"serve.incremental": True})
    incremental = engine_from_scenario(inc_spec, params=params)
    cold, c1 = _serve(incremental, requests)      # prefix 0: full extend
    warm, c2 = _serve(incremental, requests)      # repeat users: all hits
    _, s1 = _serve(incremental, requests)
    _check(incremental.stats.n_incremental_batches > 0,
           "no batch went through the incremental path")
    _check(incremental.state_store.stats.hits > 0, "no state-store hit")
    flat = np.concatenate(base)
    errs = {"cold_vs_stateless": _rel_err(np.concatenate(cold), flat),
            "warm_vs_stateless": _rel_err(np.concatenate(warm), flat)}
    _report("c_serve", c0 + c1 + c2, s0 + s1, {
        "requests": len(requests), "impressions": int(flat.shape[0]),
        "incremental_batches": incremental.stats.n_incremental_batches,
        "failed_batches": (stateless.stats.n_failed_batches
                           + incremental.stats.n_failed_batches),
        **errs, "tol": KERNEL_TOL})
    _check(max(errs.values()) <= KERNEL_TOL,
           f"incremental vs stateless scores: {errs}")


def phase_dlrm() -> None:
    from repro.configs.registry import scenario
    from repro.data.batcher import ROOBatcher
    from repro.scenario.build import build_batcher_cfg, build_samples
    spec = scenario("dlrm-mlperf", {"train.steps": 5, "train.log_every": 1,
                                    "train.halt_after_skips": 1})
    trainer, state, losses, c, st = _train(spec)
    batch = next(ROOBatcher(build_batcher_cfg(spec)).batches(
        build_samples(spec)))
    _check(_step_has_kernel(trainer, state, batch),
           "no Pallas kernel in the dlrm-mlperf train step")
    _report("d_dlrm", c, st, {"steps": len(losses),
                              "loss_first": float(losses[0]),
                              "loss_last": float(losses[-1]),
                              "skipped": trainer.skipped_steps})


# ---------------------------------------------------------------------------
# --four-chips: sharded training against one device
# ---------------------------------------------------------------------------

def phase_mesh() -> None:
    """hstu-gr on a 2x2 mesh: the sharded run a user starts, then its
    trajectory against one device's (both at full precision, as in
    phase_parity)."""
    import jax

    from repro.configs.registry import scenario
    _check(len(jax.devices()) >= 4,
           f"--four-chips needs 4 devices, found {len(jax.devices())}")
    spec = scenario("hstu-gr", HSTU_GR)
    mesh_spec = spec.with_overrides({"train.mesh": "2x2"})
    trainer, _, losses, c, st = _train(mesh_spec)
    _report("mesh_2x2", c, st, {
        "steps": len(losses), "loss_first": float(losses[0]),
        "loss_last": float(losses[-1]), "skipped": trainer.skipped_steps})
    (one, c1, s1), (four, c4, s4) = _highest_losses(spec, mesh_spec)
    rel = _max_rel(four, one)
    _report("mesh_parity", c1 + c4, s1 + s4, {
        "loss_last": float(four[-1]), "mesh_vs_one_rel": rel,
        "rtol": LOSS_RTOL})
    _check(rel <= LOSS_RTOL,
           f"2x2 mesh vs one device losses differ by {rel:.3g}: {four} vs "
           f"{one}")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only hstu-gr training on a 2x2 mesh against "
                         "the same run on one device")
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no repro package under {src}: run from a "
              f"checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    from repro.launch.compile_cache import enable_compilation_cache
    cache = enable_compilation_cache()

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX's first device is "
              f"{dev.platform} ({dev.device_kind}); no fallback",
              file=sys.stderr)
        return 3
    from repro.kernels import dispatch
    backends = {"attention": dispatch.resolve_backend(),
                "embedding": dispatch.resolve_emb_backend()}
    if any(b != "pallas" for b in backends.values()):
        print(f"chip_smoke: kernels must resolve to compiled pallas, got "
              f"{backends} (unset REPRO_HSTU_BACKEND / REPRO_EMB_BACKEND)",
              file=sys.stderr)
        return 4
    print(f"device={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} jax={jax.__version__} cache={cache}",
          flush=True)

    t0 = time.perf_counter()
    failed = []

    def run(phase, *a):
        """A phase's result, or None once it failed; later phases go on
        where they can, so one call shows every fault."""
        try:
            return phase(*a)
        except SmokeFailure as e:
            print(f"chip_smoke: {phase.__name__} FAILED: {e}",
                  file=sys.stderr, flush=True)
            failed.append(phase.__name__)
            return None

    if args.four_chips:
        run(phase_mesh)
    else:
        run(phase_kernels)
        trained = run(phase_train)
        if trained is not None:
            run(phase_parity, trained[0])
            run(phase_serve, *trained)
        else:
            failed += ["phase_parity", "phase_serve"]
        run(phase_dlrm)
    if failed:
        print(f"chip_smoke: failed: {' '.join(failed)}", file=sys.stderr)
        return 1
    print(f"total_s={time.perf_counter() - t0:.1f}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
