"""Architecture registry: --arch <id> resolution for launch/dryrun/train,
plus the declarative ScenarioSpec factory for every recsys arch
(:func:`scenario` / :func:`all_scenarios` — see docs/CONFIG.md)."""
from __future__ import annotations

import importlib
from typing import List, Mapping, Optional

from repro.scenario.archs import ARCHS

_MODULES = {
    "starcoder2-15b": "repro.configs.starcoder2_15b",
    "deepseek-coder-33b": "repro.configs.deepseek_coder_33b",
    "phi3-medium-14b": "repro.configs.phi3_medium_14b",
    "qwen3-moe-235b-a22b": "repro.configs.qwen3_moe_235b_a22b",
    "granite-moe-3b-a800m": "repro.configs.granite_moe_3b_a800m",
    "mace": "repro.configs.mace",
    "mind": "repro.configs.mind",
    "bert4rec": "repro.configs.bert4rec",
    "dlrm-mlperf": "repro.configs.dlrm_mlperf",
    "dien": "repro.configs.dien",
    # the paper's own ROO models (selectable for train/bench, not dry-run cells)
    "roo-lsr": "repro.configs.roo_models",
    "roo-esr": "repro.configs.roo_models",
    "roo-retrieval": "repro.configs.roo_models",
    "hstu-gr": "repro.configs.roo_models",
}

ASSIGNED = ["starcoder2-15b", "deepseek-coder-33b", "phi3-medium-14b",
            "qwen3-moe-235b-a22b", "granite-moe-3b-a800m", "mace",
            "mind", "bert4rec", "dlrm-mlperf", "dien"]


def get_arch(arch_id: str):
    return importlib.import_module(_MODULES[arch_id])


def all_cells() -> List[tuple]:
    """All 40 (arch, shape) dry-run cells."""
    out = []
    for a in ASSIGNED:
        mod = get_arch(a)
        for s in mod.SHAPES:
            out.append((a, s))
    return out


# ---------------------------------------------------------------------------
# Declarative scenarios (the recsys zoo as ScenarioSpecs)
# ---------------------------------------------------------------------------

# every trainable recsys arch: the keys of scenario/archs.py's table
SCENARIO_ARCHS = tuple(ARCHS)


def scenario(arch_id: str, overrides: Optional[Mapping] = None):
    """The registered ScenarioSpec for ``arch_id`` (its table entry's
    defaults), optionally with dotted ``--set``-style overrides (e.g.
    ``{"train.steps": 20}``) applied."""
    from repro.scenario.spec import ModelSpec, ScenarioSpec
    if arch_id not in ARCHS:
        raise KeyError(f"no registered scenario {arch_id!r}; "
                       f"known: {SCENARIO_ARCHS}")
    spec = ScenarioSpec(name=arch_id, model=ModelSpec(arch=arch_id))
    return spec.with_overrides({**ARCHS[arch_id].defaults,
                                **(overrides or {})})


def all_scenarios() -> List:
    """Every registered recsys scenario (CI validates + smoke-trains each)."""
    return [scenario(a) for a in SCENARIO_ARCHS]
