"""The dlrm-train-zipf cell end to end at a CPU size (the benchmark's own
files, model wiring, driver and limits, with the widths and tables cut
here): a sound run is correct, and the check turns false for a frozen
state and for the fp8 control. Also the cell's work counts and its
roofline reader."""
import importlib.util
import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import bench, check, trace  # noqa: E402
from chipbench.drivers import train  # noqa: E402
from chipbench.drivers.common import RunInput  # noqa: E402
from chipbench.run import TRACE_DIR, run_cell  # noqa: E402

CELL = "dlrm-train-zipf"
SEED = 2 ** 40 + 11


def cell():
    c = bench.find_cell(CELL)
    c.config.update(embed_dim=16, bot_mlp=[13, 32, 16], top_mlp=[64, 32, 1],
                    vocabs=[min(v, 1000) for v in c.config["vocabs"]],
                    train={"b_ro": 8, "b_nro": 48})
    c.traffic["pool_batches"] = 4
    return c


def run(fault=None):
    return run_cell(cell(), SEED, 1.0, False, jax.devices(), {}, fault=fault)


def test_sound_run_is_correct():
    r = run()
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r["checks"]) == ["loss_rel", "grad_norm_gap", "update_norm_gap"]
    assert all(v["value"] < 1e-4 for v in r["checks"].values())


def test_frozen_state_is_caught():
    r = run("frozen_state")
    assert not r["correct"]
    assert r["checks"]["update_norm_gap"]["value"] == 1.0


def test_fp8_control_fails():
    c = cell()
    x = RunInput(cell=c.name, config=c.config, traffic=c.traffic,
                 model=bench.model_for(c.config), seed=SEED, seconds=1.0,
                 trace=False, t_start=0.0, trace_dir=TRACE_DIR)
    pool = x.model.train_pool(x.traffic, x.seed)
    numbers = train.compare(train.reference_steps(x, pool, "fp8"),
                            train.reference_steps(x, pool))
    assert not check.judge(numbers, c.limits)[0], numbers


@pytest.fixture(scope="module")
def small_pool():
    c = cell()
    m = bench.model_for(c.config)
    return c, m, m.train_pool(c.traffic, 7)


def test_work_counts_match_the_packed_batch(small_pool):
    from repro.models.dlrm import dlrm_batch_fields
    c, m, pool = small_pool
    for reqs, batch in zip(pool.requests, pool.batches):
        ro_ids, ro_len, nro_ids, nro_len = dlrm_batch_fields(m.cfg, batch)
        ids = int(np.sum(ro_len) + np.sum(nro_len))
        bags = ro_len.size + nro_len.size
        assert m.bag_bytes_fwd(reqs) == 4 * 16 * (ids + bags)
        assert m.bag_bytes_bwd(reqs) == m.bag_bytes_fwd(reqs)
        imps = int(np.sum(np.asarray(batch.impression_mask())))
        bottom = 2 * (13 * 32 + 32 * 16)
        top = 2 * ((16 + 351) * 64 + 64 * 32 + 32)
        assert m.flops_fwd(reqs) == (len(reqs) * bottom
                                     + imps * (top + 2 * 351 * 16))


def _reader():
    path = os.path.join(ROOT, "chipbench", "metrics",
                        "emb_bag_roofline.train.py")
    spec = importlib.util.spec_from_file_location("emb_bag_roofline", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_roofline_reader_times_the_bag_kernels_only(small_pool):
    c, m, pool = small_pool
    target = 'custom_call_target="tpu_custom_call"'
    ops = [trace.Op(f"%embedding_bag_fwd.3 = f32[48,1,16]{{2,1,0}} "
                    f"custom-call(...), {target}", 0.0, 2e6),
           trace.Op(f"%embedding_bag_bwd_rows.7 = f32[48,1,16]{{2,1,0}} "
                    f"custom-call(...), {target}", 2e6, 5e6),
           trace.Op(f"%hstu_attn_fwd.2 = f32[8]{{0}} custom-call(...), "
                    f"{target}", 5e6, 9e6)]
    s = trace.Summary(window_s=10e-3, busy_s=9e-3, n_devices=1, op_s={},
                      kernel_ops=ops, gaps=[])

    class Out:
        counts = {"steps": pool.requests[:2]}
    ctx = {"trace": s, "outcome": Out(), "model": m,
           "peaks": {"hbm_bytes_per_s": 819e9}}
    nbytes = sum(m.bag_bytes_fwd(r) + m.bag_bytes_bwd(r)
                 for r in pool.requests[:2])
    assert _reader().read(ctx) == pytest.approx(
        100 * nbytes / 819e9 / 5e-3)
    ctx["trace"].kernel_ops = ops[2:]
    assert _reader().read(ctx) is None
