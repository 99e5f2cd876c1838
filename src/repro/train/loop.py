"""Generic training loop: jit'd step, grad accumulation, checkpoint/resume,
straggler-aware deterministic data skipping.

The loop is model-agnostic: it takes ``loss_fn(params, batch, rng)`` and an
Optimizer. Fault tolerance contract:
  * state = {params, opt, step, rng} checkpointed every ``ckpt_every`` steps
    (async, atomic). ``rng`` is the run's base key: the per-step key is
    ``fold_in(rng, step)``, and because the base key is part of the
    checkpointed state a resumed run continues bit-identically even if the
    caller passes a different ``rng`` argument to ``run()``;
  * on (re)start, ``run()`` restores the newest committed step and fast-
    forwards the data iterator deterministically (iterator seeded by step),
    so a preempted-and-restarted run continues exactly. Disk-backed loaders
    hook ``on_checkpoint(step)`` to persist their (shard, offset) cursor at
    exactly the committed steps (repro/pipeline/resume.py);
  * simulated-failure tests: TestPreemptionResume (tests/test_train.py)
    and the pipeline kill-and-restart test (tests/test_pipeline.py) kill
    the loop mid-run and verify bit-continuation.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Iterator, Optional

import jax
import jax.numpy as jnp

from repro.distributed import comms as _comms
from repro.embeddings import sparse as _sp
from repro.obs import export as obs_export
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.reliability import faults
from repro.train.checkpoint import CheckpointManager
from repro.train.optim import Optimizer


class NonFiniteLossError(RuntimeError):
    """Raised when ``halt_after_skips`` consecutive steps produced a
    non-finite loss/gradient — the run is diverging, not glitching."""


def _poison_batch(batch):
    """Replace the first float leaf with NaNs (``train.batch`` nan fault)."""
    flat, tree = jax.tree_util.tree_flatten(batch)
    for i, leaf in enumerate(flat):
        if hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype,
                                                     jnp.floating):
            flat[i] = jnp.full_like(leaf, jnp.nan)
            break
    return jax.tree_util.tree_unflatten(tree, flat)


@dataclasses.dataclass
class TrainLoopConfig:
    total_steps: int = 100
    ckpt_every: int = 50
    log_every: int = 10
    microbatches: int = 1          # grad accumulation factor
    ckpt_dir: Optional[str] = None
    keep_last: int = 3
    # halt after this many CONSECUTIVE non-finite (skipped) steps; 0 keeps
    # the guard passive (skips counted in metrics, loop never halts).
    # Enabling it polls the skip flag every step (one small host sync).
    halt_after_skips: int = 0
    # extra provenance merged into every checkpoint's meta.json (e.g. the
    # scenario name + content hash, so a checkpoint can prove which spec
    # produced it)
    ckpt_meta: Optional[Dict[str, Any]] = None


def make_train_step(loss_fn: Callable, opt: Optimizer,
                    microbatches: int = 1, plan=None, state_shardings=None,
                    value_and_grad_fn: Optional[Callable] = None):
    """Returns jit'd step(state, batch) -> (state, metrics).

    With microbatches > 1, `batch` must be a pytree whose leaves have a
    leading microbatch axis; grads are accumulated (comm/compute overlap:
    the all-reduce happens once per step, not per microbatch).

    The default gradient is ``embeddings.sparse.touched_rows_value_and_grad``:
    each table the optimizer updates row-wise (``opt.rows_mask``), of at
    least ``SPARSE_MIN_VOCAB`` rows, that the loss reads only through the
    embedding collection's lookups, at most ``TOUCHED_MAX_FILL`` ids a row
    a step, gets its gradient as the rows the batch touched
    (``SparseRows``), and the optimizer reads and writes only those rows;
    every other leaf, and every leaf under an enabled ``plan``, gets its
    dense gradient. ``value_and_grad_fn(params, batch, rng) -> (loss,
    grads)`` replaces that default; it is the hook through which tests pass
    the all-dense oracle ``jax.value_and_grad(loss_fn)``. ``SparseRows``
    leaves flow through accumulation and into the optimizer: the dense part
    rides the scan carry as before, the COO part is emitted per-microbatch
    and stacked by the scan (a COO sum IS concatenation; the step's segment
    merge folds duplicates). The metrics count the distinct rows touched
    (``touched_rows``).

    With an enabled ``plan`` (distributed/sharding.py) and the matching
    ``state_shardings`` pytree (distributed/spmd.py), the step runs SPMD:
    inputs keep their committed shardings (params/opt FSDP+TP, batch over
    the data axes) and ``out_shardings`` pins the updated state to the same
    layout, so parameters never silently de-shard between steps.

    Comms knobs (distributed/comms.py) resolve HERE, at step-construction
    time — the step's structure depends on them. ``comms_overlap=on`` with
    microbatches > 1 unrolls the accumulation scan: ``lax.scan``'s
    sequential loop is a scheduling barrier between iterations, while the
    unrolled graph lets XLA's latency-hiding scheduler issue microbatch
    k+1's embedding-lookup psums while microbatch k's dense compute runs.
    Accumulation order is identical, so overlap with ``comms_compress=none``
    is bit-comparable to the scan. With compression on and a
    ``state["comms_ef"]`` residual present, the coalesced gradient exchange
    runs through error feedback (``ef_compress_step``) before the optimizer.
    """
    if value_and_grad_fn is None:
        value_and_grad_fn = _sp.touched_rows_value_and_grad(
            loss_fn, opt.rows_mask, local=plan is None or not plan.enabled)
    vag = value_and_grad_fn
    comms_mode = _comms.compress_mode()
    comms_block = _comms.block_size()
    overlap = _comms.overlap_enabled() and microbatches > 1
    _comms.STATS.record_overlap(microbatches, overlap)

    def step(state, batch, rng):
        params = state["params"]

        if microbatches > 1 and overlap:
            # unrolled accumulation (see docstring); the SparseRows grad
            # exchange stays deferred: COO parts concatenate after the
            # loop, one coalesced exchange per step
            acc = None
            losses = []
            sp_parts = []
            for i in range(microbatches):
                mb = jax.tree.map(lambda x, i=i: x[i], batch)
                l, g = vag(params, mb, jax.random.fold_in(rng, i))
                dense_g, sparse_g = _sp.split_sparse(g)
                dense_g = jax.tree.map(
                    lambda x: x.astype(jnp.float32), dense_g)
                acc = (dense_g if acc is None
                       else jax.tree.map(jnp.add, acc, dense_g))
                losses.append(l)
                sp_parts.append(sparse_g)
            grads = _sp.merge_sparse(
                jax.tree.map(lambda g: g / microbatches, acc),
                _sp.concat_sparse(sp_parts, 1.0 / microbatches))
            loss = jnp.mean(jnp.stack(losses))
        elif microbatches > 1:
            # which grads leaves are sparse is structural (trace-time):
            # read it off the abstract grads tree so the scan carry holds
            # only the dense part
            g_aval = jax.eval_shape(vag, params,
                                    jax.tree.map(lambda x: x[0], batch),
                                    rng)[1]
            dense_aval, _ = _sp.split_sparse(g_aval)
            zero = jax.tree.map(lambda s: jnp.zeros(s.shape, jnp.float32),
                                dense_aval)

            def micro(carry, xs):
                mb, i = xs
                acc, = carry
                # distinct rng per microbatch — otherwise dropout/sampling
                # repeat across the accumulation scan
                l, g = vag(params, mb, jax.random.fold_in(rng, i))
                dense_g, sparse_g = _sp.split_sparse(g)
                return (jax.tree.map(jnp.add, acc, dense_g),), (l, sparse_g)
            (gsum,), (losses, sp_stacked) = jax.lax.scan(
                micro, (zero,), (batch, jnp.arange(microbatches)))
            grads = _sp.merge_sparse(
                jax.tree.map(lambda g: g / microbatches, gsum),
                _sp.flatten_stacked(sp_stacked, 1.0 / microbatches))
            loss = jnp.mean(losses)
        else:
            loss, grads = vag(params, batch, rng)

        # compressed gradient exchange with error feedback: send
        # q(g + e), carry e' = (g + e) - q(g + e) in optimizer-adjacent
        # state (checkpointed + sharded like the tables it compensates)
        new_ef = None
        if comms_mode != "none" and "comms_ef" in state:
            grads, new_ef = _comms.ef_compress_step(
                grads, state["comms_ef"], comms_mode, comms_block)

        # one merge per table: unique ids, so the norm below is exact and
        # the optimizer's own merge is a no-op
        grads = jax.tree.map(
            lambda g: g.merged() if _sp.is_sparse(g) else g, grads,
            is_leaf=_sp.is_sparse)
        g_leaves = jax.tree.leaves(grads, is_leaf=_sp.is_sparse)
        gnorm = jnp.sqrt(sum(_sp.sq_sum(g) for g in g_leaves) + 1e-20)
        touched = sum((jnp.sum(g.ids < g.vocab, dtype=jnp.int32)
                       for g in g_leaves if _sp.is_sparse(g)),
                      jnp.zeros((), jnp.int32))
        # non-finite guard: a NaN/Inf loss or gradient must not poison the
        # parameters — the optimizer keeps the old params/opt for this step
        # (the step counter still advances so data alignment is unchanged)
        # and the skip surfaces in metrics
        ok = jnp.isfinite(loss) & jnp.isfinite(gnorm)
        new_params, new_opt = opt.update(grads, state["opt"], params, ok)
        # {**state, ...} carries pass-through keys (e.g. the base "rng")
        new_state = {**state, "params": new_params, "opt": new_opt,
                     "step": state["step"] + 1}
        if new_ef is not None:
            # the residual reverts with params on a skipped step — a
            # non-finite gradient must not poison the error accumulator
            new_state["comms_ef"] = jax.tree.map(
                lambda new, old: jnp.where(ok, new, old), new_ef,
                state["comms_ef"])
        return new_state, {"loss": loss, "grad_norm": gnorm,
                           "skipped": (~ok).astype(jnp.int32),
                           "touched_rows": touched}

    if plan is not None and plan.enabled and state_shardings is not None:
        # metrics sharding left to the compiler (None = unconstrained)
        return jax.jit(step, out_shardings=(state_shardings, None))
    return jax.jit(step)


class Trainer:
    def __init__(self, loss_fn: Callable, opt: Optimizer,
                 cfg: TrainLoopConfig,
                 init_params_fn: Callable[[], Any], *, plan=None,
                 value_and_grad_fn: Optional[Callable] = None,
                 metrics_fn: Optional[Callable] = None):
        self.loss_fn = loss_fn
        self.opt = opt
        self.cfg = cfg
        self.init_params_fn = init_params_fn
        self.plan = plan
        self.value_and_grad_fn = value_and_grad_fn
        # extra metrics (e.g. NE) run OUTSIDE the train step, only at
        # logging steps — a quality metric consumed 1-in-log_every times
        # must not cost a second model forward on every step
        self.metrics_fn = metrics_fn
        self._metrics_jit = (jax.jit(metrics_fn)
                             if metrics_fn is not None else None)
        self._spmd = plan is not None and plan.enabled
        # under a mesh the step's out_shardings need the concrete state
        # pytree, so compilation is deferred to the first run()
        self.step_fn = (None if self._spmd
                        else make_train_step(loss_fn, opt, cfg.microbatches,
                                             value_and_grad_fn=value_and_grad_fn))
        self.ckpt = (CheckpointManager(cfg.ckpt_dir, cfg.keep_last,
                                       meta=cfg.ckpt_meta)
                     if cfg.ckpt_dir else None)
        self.history: list = []
        self.skipped_steps = 0   # non-finite steps the guard neutralized
        self._last_step = 0
        obs_metrics.register_stats("train", self)

    def snapshot(self) -> dict:
        """Trainer view for ``obs.snapshot()``: progress + the guard's
        skip count + the latest logged metrics row."""
        return {"last_step": self._last_step,
                "total_steps": self.cfg.total_steps,
                "skipped_steps": self.skipped_steps,
                "last_log": dict(self.history[-1]) if self.history else None}

    def init_state(self, rng: Optional[jax.Array] = None) -> Dict:
        params = self.init_params_fn()
        state = {"params": params, "opt": self.opt.init(params),
                 "step": jnp.zeros((), jnp.int32)}
        if rng is not None:
            state["rng"] = rng
        self._ensure_comms_ef(state)
        return state

    def _ensure_comms_ef(self, state: Dict) -> None:
        """Back-fill the comms error-feedback residual when the compressed
        exchange is on and the state (fresh or restored from a
        pre-compression checkpoint) doesn't carry one yet."""
        if _comms.compress_mode() == "none" or "comms_ef" in state:
            return
        ef = _comms.ef_init(state["params"], self.plan)
        if ef:
            state["comms_ef"] = ef

    def _prepare(self, state: Dict) -> Dict:
        """Place state per plan and build the (possibly SPMD) step fn."""
        if not self._spmd:
            return state
        from repro.distributed import spmd
        shardings = spmd.state_shardings(state, self.plan)
        state = jax.device_put(state, shardings)
        if self.step_fn is None:
            self.step_fn = make_train_step(self.loss_fn, self.opt,
                                           self.cfg.microbatches,
                                           plan=self.plan,
                                           state_shardings=shardings,
                                           value_and_grad_fn=self.value_and_grad_fn)
        # with grad accumulation dim 0 is the scan axis — shard dim 1
        self._place_batch = spmd.make_batch_placer(
            self.plan, batch_dim=1 if self.cfg.microbatches > 1 else 0)
        return state

    def run(self, batch_iter_fn: Callable[[int], Iterator],
            rng: jax.Array, stop_after: Optional[int] = None,
            on_checkpoint: Optional[Callable[[int], None]] = None) -> Dict:
        """batch_iter_fn(start_step) must yield batches from that step on
        (the deterministic-skip contract). ``on_checkpoint(step)`` fires at
        every committed checkpoint so data sources can persist their resume
        cursor for exactly that step."""
        state = None
        start = 0
        if self.ckpt is not None and self.ckpt.latest_step() is not None:
            state = self.ckpt.restore()
            start = int(state["step"])
            # pre-rng checkpoints: adopt the caller's key (old behavior)
            state.setdefault("rng", rng)
            self._ensure_comms_ef(state)
        if state is None:
            state = self.init_state(rng)
        state = self._prepare(state)
        base_rng = jnp.asarray(state["rng"])   # checkpointed base key wins
        it = batch_iter_fn(start)
        t0 = time.monotonic()
        consecutive_skips = 0
        for step in range(start, self.cfg.total_steps):
            with obs_trace.span("train.step", step=step + 1):
                with obs_trace.span("train.data", step=step + 1):
                    batch = next(it)
                    spec = faults.fire("train.batch")
                    if spec is not None and spec.kind == "nan":
                        batch = _poison_batch(batch)
                    if self._spmd:
                        # cached shardings; no-op for loader-placed batches
                        batch = self._place_batch(batch)
                # dispatch only — the device work overlaps the next data span
                # and is drained by the sync inside the train.log span
                with obs_trace.span("train.compute", step=step + 1):
                    state, metrics = self.step_fn(
                        state, batch, jax.random.fold_in(base_rng, step))
                self._last_step = step + 1
                if self.cfg.halt_after_skips > 0:
                    if int(metrics["skipped"]):
                        consecutive_skips += 1
                        self.skipped_steps += 1
                        if consecutive_skips >= self.cfg.halt_after_skips:
                            raise NonFiniteLossError(
                                f"{consecutive_skips} consecutive non-finite "
                                f"steps ending at step {step + 1} — halting "
                                f"instead of spinning on a diverged run")
                    else:
                        consecutive_skips = 0
                if (step + 1) % self.cfg.log_every == 0:
                    with obs_trace.span("train.log", step=step + 1):
                        rate = ((step + 1 - start)
                                / max(time.monotonic() - t0, 1e-9))
                        row = {"step": step + 1, "loss": float(metrics["loss"]),
                               "steps_per_s": rate}
                        row.update({k: float(v) for k, v in metrics.items()
                                    if k not in row})
                        if self._metrics_jit is not None:
                            mb = (jax.tree.map(lambda x: x[0], batch)
                                  if self.cfg.microbatches > 1 else batch)
                            extra = self._metrics_jit(
                                state["params"], mb,
                                jax.random.fold_in(base_rng, step))
                            row.update({k: float(v) for k, v in extra.items()})
                        self.history.append(row)
                    obs_export.maybe_emit("train.log")
                if self.ckpt is not None and (step + 1) % self.cfg.ckpt_every == 0:
                    with obs_trace.span("train.checkpoint", step=step + 1):
                        self.ckpt.save(int(state["step"]), state, blocking=False)
                        if on_checkpoint is not None:
                            on_checkpoint(int(state["step"]))
                if stop_after is not None and (step + 1 - start) >= stop_after:
                    break   # simulated preemption (tests)
        if self.ckpt is not None:
            self.ckpt.wait()
        return state
