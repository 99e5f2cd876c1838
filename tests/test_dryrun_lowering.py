"""Launch-path integration: representative cells lower+compile on a small
SPMD mesh (subprocess with its own device-count flag), and the roofline
extraction pipeline produces sane numbers."""
import json
import os
import subprocess
import sys

import pytest

_CODE = '''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, json
from repro.configs.registry import get_arch
from repro.distributed.sharding import plan_for_mesh
from repro.launch.hlo_analysis import analyze

from repro.launch.mesh import auto_mesh
mesh = auto_mesh((2, 4), ("data", "model"))
plan = plan_for_mesh(mesh)
out = {}
cells = [("dlrm-mlperf", "train_batch"), ("mind", "serve_p99"),
         ("starcoder2-15b", "decode_32k"), ("granite-moe-3b-a800m", "train_4k"),
         ("mace", "molecule")]
for arch, shape in cells:
    cell = get_arch(arch).build_cell(shape, plan)
    st_sh, in_sh = cell.shardings(plan)
    with mesh:
        c = jax.jit(cell.step, in_shardings=(st_sh, in_sh)).lower(
            cell.abstract_state(), cell.input_specs()).compile()
    a = analyze(c.as_text())
    m = c.memory_analysis()
    peak = m.peak_memory_in_bytes
    out[f"{arch}/{shape}"] = {
        "flops": a["flops"], "coll": a["collective_bytes"],
        "mem": a["memory_bytes"], "peak": peak}
print("RESULT=" + json.dumps(out))
'''


@pytest.fixture(scope="module")
def lowered():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"     # the child must never claim a chip
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", _CODE], capture_output=True,
                       text=True, env=env, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT=")][0]
    return json.loads(line[len("RESULT="):])


class TestDryrunLowering:
    def test_all_representative_cells_compile(self, lowered):
        assert len(lowered) == 5

    def test_flops_positive_and_sane(self, lowered):
        for k, v in lowered.items():
            assert v["flops"] > 0, k
            assert v["mem"] > 0, k

    def test_sharded_training_has_collectives(self, lowered):
        # training steps across 8 devices MUST communicate
        assert lowered["dlrm-mlperf/train_batch"]["coll"] > 0
        assert lowered["granite-moe-3b-a800m/train_4k"]["coll"] > 0

    def test_moe_train_flops_scale(self, lowered):
        # granite train: >= 6 * active params * tokens / devices (order check)
        from repro.configs.registry import get_arch
        cfg = get_arch("granite-moe-3b-a800m").CONFIG
        toks = 256 * 4096
        lower_bound = 2.0 * cfg.n_active_params() * toks / 8
        assert lowered["granite-moe-3b-a800m/train_4k"]["flops"] > lower_bound
