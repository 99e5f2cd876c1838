"""Compile every Pallas kernel of the main path for a TPU v5e, without one.

The TPU compiler is installed with JAX and compiles for a topology that is
described, not attached. That catches what interpret mode cannot: block
shapes off the (8, 128) tiling, primitives the chip's compiler does not
lower, VMEM overruns. Shapes are those ``chip_smoke.py`` runs: the HSTU
kernels at ``hstu-gr`` width (B 32, H 2, S 1,040 = 1,024 history events +
16 targets, d 32, bias on, max_rel 1,024), the cached prefix at the serving
shapes (1 and 1,024 new events), the embedding bag at d 16 and d 128.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import embedding_bag as bag_kernel
from repro.kernels import hstu_attention as hstu_kernel

B, H, N_HIST, M, D = 32, 2, 1024, 16, 32
S = N_HIST + M
MAX_REL = 1024


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one: keep it out of the cache
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _hstu_args(sh, s=S):
    return (_spec(sh, (B, H, s, D)), _spec(sh, (B, H, s, D)),
            _spec(sh, (B, H, s, D)), _spec(sh, (H, 2 * MAX_REL + 1)),
            _spec(sh, (B,), jnp.int32), _spec(sh, (B,), jnp.int32))


def _hstu(q, k, v, rab, hl, tc):
    return hstu_kernel.hstu_attention(q, k, v, rab, N_HIST, hl, tc, MAX_REL,
                                      interpret=False)


def _hstu_fwd_bwd(q, k, v, rab, hl, tc):
    return jax.value_and_grad(
        lambda q, k, v, rab: _hstu(q, k, v, rab, hl, tc).sum(),
        argnums=(0, 1, 2, 3))(q, k, v, rab)


def test_hstu_forward(one_chip):
    _compile(_hstu, *_hstu_args(one_chip))


@pytest.mark.parametrize("precision", ["default", "highest"])
def test_hstu_forward_backward(one_chip, precision):
    """Also under ``highest``, where chip_smoke.py compares trajectories."""
    with jax.default_matmul_precision(precision):
        _compile(_hstu_fwd_bwd, *_hstu_args(one_chip))


def test_kernel_names_reach_the_compiled_hlo(one_chip):
    """``pallas_call(name=...)`` names the kernel's HLO instruction, which
    is the event name on a profiler trace's ``XLA Ops`` line; under
    ``grad`` JAX's transform names wrap it (``transpose_jvp_<name>__.1``)."""
    args = _hstu_args(one_chip)
    assert "%hstu_attn_fwd." in _compile(_hstu, *args).as_text()
    text = _compile(_hstu_fwd_bwd, *args).as_text()
    for name in ("hstu_attn_fwd", "hstu_attn_bwd_dq", "hstu_attn_bwd_dkv"):
        assert re.search(rf"%\w*{name}_*\.\d+ = ", text), name


def _roofline_kernels_regex(cell: str) -> str:
    """``KERNELS`` of the benchmark's roofline reader for ``cell``."""
    path = os.path.join(os.path.dirname(__file__), os.pardir, "chipbench",
                        "metrics", f"hstu_attn_roofline.{cell}.py")
    spec = importlib.util.spec_from_file_location(f"roofline_{cell}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.KERNELS


def _kernel_lines(compiled):
    """The compiled module's Pallas kernel instructions, printed with their
    operands' shapes, as a profiler trace names its ``XLA Ops`` events."""
    from jax._src.lib import xla_client
    opts = xla_client._xla.HloPrintOptions()
    opts.print_operand_shape = True
    opts.print_metadata = False
    text = "\n".join(m.to_string(opts)
                     for m in compiled.runtime_executable().hlo_modules())
    return [ln.strip() for ln in text.splitlines() if "tpu_custom_call" in ln]


@pytest.mark.parametrize("cell", ["train", "serve"])
def test_kernels_match_the_roofline_readers(one_chip, cell):
    """The roofline readers find the HSTU kernels by their first two
    operands, the (B,) int32 history lengths and target counts; the tile
    tables come after them. Every fused kernel, forward and backward, still
    matches, under its own name."""
    kernels = _roofline_kernels_regex(cell)
    args = _hstu_args(one_chip)
    fwd = _kernel_lines(_compile(_hstu, *args))
    assert len(fwd) == 1 and fwd[0].startswith("%hstu_attn_fwd.")
    both = _kernel_lines(_compile(_hstu_fwd_bwd, *args))
    assert len(both) == 3
    for name in ("hstu_attn_fwd", "hstu_attn_bwd_dq", "hstu_attn_bwd_dkv"):
        line, = [ln for ln in both if re.match(rf"%\w*{name}_*\.\d+ = ", ln)]
        assert re.search(kernels, line), line[:300]
    lengths_first = re.compile(
        rf"custom-call\(s32\[{B}\]\{{[^}}]*\}} %[\w.-]+, s32\[{B}\]\{{")
    for line in fwd + both:
        assert re.search(kernels, line), line[:300]
        assert lengths_first.search(line), line[:300]


@pytest.mark.parametrize("n_new", [1, 1024])
def test_hstu_cached_prefix(one_chip, n_new):
    def prefix(q, k, v, rab, pfx, nc, tc):
        return hstu_kernel.hstu_attention_prefix(
            q, k, v, rab, N_HIST, n_new, pfx, nc, tc, S, MAX_REL,
            interpret=False)
    sh = one_chip
    _compile(prefix, _spec(sh, (B, H, n_new + M, D)), _spec(sh, (B, H, S, D)),
             _spec(sh, (B, H, S, D)), _spec(sh, (H, 2 * MAX_REL + 1)),
             *[_spec(sh, (B,), jnp.int32)] * 3)


@pytest.mark.parametrize("pooling", ["sum", "mean", "max"])
@pytest.mark.parametrize("d", [16, 128])
def test_embedding_bag_forward_backward(one_chip, d, pooling):
    def fwd_bwd(table, ids, lengths):
        return jax.value_and_grad(lambda t: bag_kernel.embedding_bag(
            t, ids, lengths, pooling, backend="pallas").sum())(table)
    sh = one_chip
    _compile(fwd_bwd, _spec(sh, (4096, d)), _spec(sh, (256, 8), jnp.int32),
             _spec(sh, (256,), jnp.int32))


def _dlrm_shapes(sharding, cfg, b_ro=8, per=5):
    """A dlrm-mlperf training state and ROOBatch as shapes on ``sharding``:
    ``b_ro`` requests of ``per`` impressions, one id a field."""
    import numpy as np

    from repro.core.joiner import ROOSample
    from repro.data.batcher import BatcherConfig, ROOBatcher
    from repro.models.dlrm import dlrm_batcher_config, dlrm_init
    rng = np.random.default_rng(0)
    reqs = [ROOSample(
        request_id=i, user_id=i, ro_dense=rng.standard_normal(13),
        ro_idlist=rng.integers(0, 3, 13).tolist(), history_ids=[],
        history_actions=[], item_ids=[0] * per,
        item_dense=[np.zeros((1,), np.float32)] * per,
        item_idlist=[rng.integers(0, 3, 13).tolist() for _ in range(per)],
        labels=[{"click": 1.0}] * per) for i in range(b_ro)]
    batch = next(ROOBatcher(dlrm_batcher_config(cfg, BatcherConfig(
        b_ro=b_ro, b_nro=b_ro * per))).batches(reqs))
    params = jax.eval_shape(lambda: dlrm_init(jax.random.PRNGKey(0), cfg))
    return jax.tree_util.tree_map(
        lambda a: _spec(sharding, a.shape, a.dtype), (params, batch))


def _train_state(sharding, params, opt):
    return {"params": params,
            "opt": jax.tree_util.tree_map(
                lambda a: _spec(sharding, a.shape, a.dtype),
                jax.eval_shape(opt.init, params)),
            "step": _spec(sharding, (), jnp.int32)}


def test_dlrm_step_runs_the_bag_kernels(one_chip):
    """The dlrm-mlperf training step at the published widths (tables cut to
    4,096 rows) runs one forward and one backward bag kernel per sparse
    field, and ``emb_bag_roofline.train``'s pattern finds each of them and
    nothing else."""
    from repro.kernels import dispatch
    from repro.models.dlrm import MLPERF_VOCABS, DLRMConfig, dlrm_loss
    from repro.train.loop import make_train_step
    from repro.train.optim import (adam, default_is_embedding, make_mixed,
                                   rowwise_adagrad)
    path = os.path.join(os.path.dirname(__file__), os.pardir, "chipbench",
                        "metrics", "emb_bag_roofline.train.py")
    spec = importlib.util.spec_from_file_location("emb_bag_roofline", path)
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    cfg = DLRMConfig(vocabs=tuple(min(v, 4096) for v in MLPERF_VOCABS))
    params, batch = _dlrm_shapes(one_chip, cfg)
    opt = make_mixed(adam(1e-3), rowwise_adagrad(0.05), default_is_embedding)
    state = _train_state(one_chip, params, opt)
    key = _spec(one_chip, (2,), jnp.uint32)
    step = make_train_step(lambda p, b, r: dlrm_loss(p, cfg, b), opt)
    with dispatch.use_emb_backend("pallas"):
        lines = _kernel_lines(step.lower(state, batch, key).compile())
    assert len(lines) == 2 * cfg.n_sparse
    assert all(re.search(reader.KERNELS, ln) for ln in lines), lines[0][:300]
    for name in ("embedding_bag_fwd", "embedding_bag_bwd_rows"):
        assert sum(f"%{name}." in ln for ln in lines) == cfg.n_sparse, name


def test_dlrm_step_has_no_dense_table_gradient(one_chip):
    """The default dlrm-mlperf step (tables cut to a distinctive 4,099 rows)
    updates its tables through the rows a step touches: no broadcast to a
    table's shape is left, the zero fill of a dense table gradient. The
    same step given an explicit dense gradient has them."""
    from repro.models.dlrm import MLPERF_VOCABS, DLRMConfig, dlrm_loss
    from repro.train.loop import make_train_step
    from repro.train.optim import (adam, default_is_embedding, make_mixed,
                                   rowwise_adagrad)
    cfg = DLRMConfig(vocabs=tuple(min(v, 4099) for v in MLPERF_VOCABS))
    params, batch = _dlrm_shapes(one_chip, cfg)
    opt = make_mixed(adam(1e-3), rowwise_adagrad(0.05), default_is_embedding)
    state = _train_state(one_chip, params, opt)
    key = _spec(one_chip, (2,), jnp.uint32)

    def loss(p, b, r):
        return dlrm_loss(p, cfg, b)

    def table_broadcasts(vag):
        step = make_train_step(loss, opt, value_and_grad_fn=vag)
        text = step.lower(state, batch, key).compile().as_text()
        return len(re.findall(r"f32\[4099,128\]\{[^}]*\} broadcast\(", text))
    assert table_broadcasts(None) == 0
    assert table_broadcasts(jax.value_and_grad(loss)) > 0


def test_gr_step_runs_each_hstu_kernel_once(one_chip, roo_samples):
    """The default step's recording pass (a second, unused forward that
    finds the tables' ids) is compiled away: the hstu-gr step runs the
    forward kernel and the two backward kernels once each."""
    from repro.core.hstu import HSTUConfig
    from repro.data.batcher import BatcherConfig, ROOBatcher
    from repro.kernels import dispatch
    from repro.models.gr import GRConfig, gr_init, gr_ranking_loss
    from repro.train.loop import make_train_step
    from repro.train.optim import (adam, default_is_embedding, make_mixed,
                                   rowwise_adagrad)
    cfg = GRConfig(n_items=4096, hist_len=256, m_targets=8,
                   hstu=HSTUConfig(d_model=128, n_heads=2, d_qk=64, d_v=64,
                                   n_layers=1, max_rel_pos=64))
    batch = next(ROOBatcher(BatcherConfig(
        b_ro=4, b_nro=16, hist_len=cfg.hist_len)).batches(roo_samples))
    params = jax.eval_shape(lambda: gr_init(jax.random.PRNGKey(0), cfg))
    params, batch = jax.tree_util.tree_map(
        lambda a: _spec(one_chip, a.shape, a.dtype), (params, batch))
    opt = make_mixed(adam(1e-3), rowwise_adagrad(0.05), default_is_embedding)
    step = make_train_step(lambda p, b, r: gr_ranking_loss(p, cfg, b), opt)
    with dispatch.use_backend("pallas"):
        lines = _kernel_lines(step.lower(
            _train_state(one_chip, params, opt), batch,
            _spec(one_chip, (2,), jnp.uint32)).compile())
    assert len(lines) == 3
    for name in ("hstu_attn_fwd", "hstu_attn_bwd_dq", "hstu_attn_bwd_dkv"):
        assert sum(bool(re.match(rf"%\w*{name}_*\.\d+ = ", ln))
                   for ln in lines) == 1, name
