"""Scenario surface: JSON round-trip (property-based), strict validation,
the knob precedence ladder, provenance hashing, and the flag-driven vs
spec-driven bit-identity guarantee (ISSUE: one config surface)."""
import dataclasses
import json
import os

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs.registry import SCENARIO_ARCHS, all_scenarios, scenario
from repro.kernels import dispatch
from repro.scenario import (ScenarioSpec, ScenarioValidationError,
                            parse_set_args, resolve_knob)
from repro.scenario.build import (build_stream_cfg, cursor_fingerprint,
                                  provenance_matches, shard_provenance)


@pytest.fixture
def knob_state():
    """Snapshot/restore every knob a test may touch, so precedence tests
    cannot leak process defaults into the rest of the suite."""
    saved = [(k, k.snapshot()) for k in (dispatch.ATTN_KNOB,
                                         dispatch.EMB_KNOB)]
    yield
    for knob, state in saved:
        knob.restore(state)


# ---------------------------------------------------------------------------
# round-trip
# ---------------------------------------------------------------------------

class TestRoundTrip:
    def test_every_registered_scenario_roundtrips(self):
        for spec in all_scenarios():
            wire = spec.to_json_str()
            back = ScenarioSpec.from_json(json.loads(wire))
            assert back == spec
            assert back.content_hash() == spec.content_hash()
            assert back.data_hash() == spec.data_hash()

    def test_save_load_file_roundtrip(self, tmp_path):
        spec = scenario("roo-lsr")
        path = str(tmp_path / "spec.json")
        spec.save(path)
        assert ScenarioSpec.load(path) == spec

    @settings(max_examples=40, deadline=None)
    @given(arch=st.sampled_from(SCENARIO_ARCHS),
           steps=st.integers(min_value=1, max_value=100_000),
           b_ro=st.integers(min_value=1, max_value=256),
           seed=st.integers(min_value=0, max_value=2**31 - 1),
           late=st.floats(min_value=0.0, max_value=1.0,
                          allow_nan=False, width=32),
           # bounds of a width-32 strategy must be float32 values
           lr=st.floats(min_value=float(np.float32(1e-6)), max_value=1.0,
                        allow_nan=False, width=32),
           prefetch=st.booleans())
    def test_roundtrip_is_identity_under_overrides(
            self, arch, steps, b_ro, seed, late, lr, prefetch):
        spec = scenario(arch, {"train.steps": steps,
                               "batcher.b_ro": b_ro,
                               "data.seed": seed,
                               "data.late_fraction": float(late),
                               "train.lr_dense": float(lr),
                               "data.prefetch": prefetch})
        back = ScenarioSpec.from_json(json.loads(spec.to_json_str()))
        assert back == spec
        assert back.content_hash() == spec.content_hash()
        # string-typed overrides (the --set path) coerce to the same spec
        again = scenario(arch, {"train.steps": str(steps),
                                "batcher.b_ro": str(b_ro),
                                "data.seed": str(seed),
                                "data.late_fraction": repr(float(late)),
                                "train.lr_dense": repr(float(lr)),
                                "data.prefetch": str(prefetch)})
        assert again == spec

    def test_set_args_coerce_types(self):
        overrides = parse_set_args(["train.steps=50", "data.prefetch=false",
                                    "knobs.attn_backend=none",
                                    "train.lr_dense=0.01"])
        spec = scenario("roo-lsr", overrides)
        assert spec.train.steps == 50
        assert spec.data.prefetch is False
        assert spec.knobs.attn_backend is None
        assert spec.train.lr_dense == 0.01


# ---------------------------------------------------------------------------
# strict validation — a config that lies must fail loudly
# ---------------------------------------------------------------------------

class TestValidation:
    def _wire(self, **edits):
        wire = scenario("roo-lsr").to_json()
        for key, value in edits.items():
            wire[key] = value
        return wire

    def test_unknown_section_rejected(self):
        with pytest.raises(ScenarioValidationError):
            ScenarioSpec.from_json(self._wire(extra={}))

    def test_unknown_field_rejected(self):
        wire = self._wire()
        wire["train"]["warmup"] = 5
        with pytest.raises(ScenarioValidationError):
            ScenarioSpec.from_json(wire)

    def test_mistyped_int_rejected(self):
        wire = self._wire()
        wire["train"]["steps"] = "50"        # strings never silently parse
        with pytest.raises(ScenarioValidationError):
            ScenarioSpec.from_json(wire)

    def test_bool_is_not_int(self):
        wire = self._wire()
        wire["data"]["prefetch"] = 1
        with pytest.raises(ScenarioValidationError):
            ScenarioSpec.from_json(wire)

    def test_future_schema_rejected(self):
        with pytest.raises(ScenarioValidationError):
            ScenarioSpec.from_json(self._wire(schema_version=99))

    def test_missing_arch_rejected(self):
        with pytest.raises(ScenarioValidationError):
            scenario("roo-lsr", {"model.arch": ""})

    def test_bad_source_rejected(self):
        with pytest.raises(ScenarioValidationError):
            scenario("roo-lsr", {"data.source": "s3"})

    def test_bad_knob_value_rejected(self):
        with pytest.raises(ScenarioValidationError):
            scenario("roo-lsr", {"knobs.attn_backend": "bogus"})

    def test_bad_override_key_rejected(self):
        with pytest.raises(ScenarioValidationError):
            scenario("roo-lsr", {"train.nope": 1})
        with pytest.raises(ScenarioValidationError):
            scenario("roo-lsr", {"notasection.x": 1})

    def test_bad_mesh_rejected(self):
        with pytest.raises(ScenarioValidationError):
            scenario("roo-lsr", {"train.mesh": "abc"})


# ---------------------------------------------------------------------------
# the one precedence ladder: explicit > scoped > default > env > auto
# ---------------------------------------------------------------------------

class TestKnobLadder:
    def test_auto_rung(self, knob_state):
        # no explicit/scope/default/env: hardware-aware auto (CPU CI)
        assert dispatch.resolve_backend() in dispatch.BACKENDS

    def test_env_beats_auto(self, knob_state, monkeypatch):
        monkeypatch.setenv(dispatch.ENV_VAR, "jnp-dense")
        assert dispatch.resolve_backend() == "jnp-dense"

    def test_default_beats_env(self, knob_state, monkeypatch):
        monkeypatch.setenv(dispatch.ENV_VAR, "jnp-dense")
        dispatch.set_default_backend("pallas-interpret")
        assert dispatch.resolve_backend() == "pallas-interpret"
        dispatch.set_default_backend(None)          # cleared: env wins again
        assert dispatch.resolve_backend() == "jnp-dense"

    def test_scope_beats_default(self, knob_state):
        dispatch.set_default_backend("pallas-interpret")
        with dispatch.use_backend("jnp-dense"):
            assert dispatch.resolve_backend() == "jnp-dense"
        assert dispatch.resolve_backend() == "pallas-interpret"

    def test_explicit_beats_everything(self, knob_state, monkeypatch):
        monkeypatch.setenv(dispatch.ENV_VAR, "jnp-dense")
        dispatch.set_default_backend("pallas-interpret")
        with dispatch.use_backend("jnp-dense"):
            assert dispatch.resolve_backend("jnp-chunked") == "jnp-chunked"

    def test_invalid_env_fails_loudly(self, knob_state, monkeypatch):
        monkeypatch.setenv(dispatch.ENV_VAR, "bogus")
        with pytest.raises(ValueError):
            dispatch.resolve_backend()

    def test_resolve_by_name(self, knob_state):
        dispatch.set_default_emb_backend("jnp")
        assert resolve_knob("emb_backend") == "jnp"
        assert resolve_knob("emb_backend", "pallas-interpret") == \
            "pallas-interpret"

    def test_spec_apply_installs_defaults(self, knob_state):
        spec = scenario("roo-lsr", {"knobs.attn_backend": "jnp-dense",
                                    "knobs.emb_backend": "jnp"})
        spec.apply()
        assert dispatch.resolve_backend() == "jnp-dense"
        assert dispatch.resolve_emb_backend() == "jnp"


# ---------------------------------------------------------------------------
# provenance: what each hash covers
# ---------------------------------------------------------------------------

class TestProvenance:
    def test_content_hash_covers_everything(self):
        base = scenario("roo-lsr")
        assert base.content_hash() != \
            scenario("roo-lsr", {"train.steps": 7}).content_hash()
        assert base.content_hash() != \
            scenario("roo-lsr", {"serve.max_delay_ms": 9.0}).content_hash()

    def test_data_hash_ignores_train_and_runtime_knobs(self):
        base = scenario("roo-lsr")
        # continuing a run (more steps) or toggling prefetch must not
        # invalidate shard reuse / resume cursors ...
        assert base.data_hash() == \
            scenario("roo-lsr", {"train.steps": 9999}).data_hash()
        assert base.data_hash() == \
            scenario("roo-lsr", {"data.prefetch": False}).data_hash()
        # ... but a different stream or batch shape is different data
        assert base.data_hash() != \
            scenario("roo-lsr", {"data.seed": 1}).data_hash()
        assert base.data_hash() != \
            scenario("roo-lsr", {"batcher.b_nro": 64}).data_hash()

    def test_data_hash_resolves_n_items_indirection(self):
        # data.n_items=0 follows model.n_items; the hash must see through it
        a = scenario("roo-lsr", {"model.n_items": 4096})
        b = scenario("roo-lsr", {"model.n_items": 4096,
                                 "data.n_items": 4096})
        assert a.data_hash() == b.data_hash()

    def test_provenance_matches_spec_and_legacy(self):
        spec = scenario("roo-lsr", {"data.source": "disk"})
        assert provenance_matches(shard_provenance(spec), spec)
        other = scenario("roo-lsr", {"data.source": "disk", "data.seed": 3})
        assert not provenance_matches(shard_provenance(other), spec)
        # pre-scenario manifests carried only the stream/join fields
        legacy = {"stream": dataclasses.asdict(build_stream_cfg(spec)),
                  "label_wait_s": spec.data.label_wait_s,
                  "requests_per_shard": spec.data.requests_per_shard}
        assert provenance_matches(legacy, spec)

    def test_cursor_fingerprint_survives_more_steps(self, tmp_path):
        from repro.pipeline import OnlineJoinConfig, WatermarkJoiner, \
            write_samples
        from repro.data.events import EventSimulator
        spec = scenario("roo-lsr", {"data.source": "disk",
                                    "data.n_requests": 40})
        samples = WatermarkJoiner(OnlineJoinConfig()).join(
            EventSimulator(build_stream_cfg(spec)).stream())
        manifest = write_samples(str(tmp_path / "shards"), samples,
                                 requests_per_shard=16,
                                 provenance=shard_provenance(spec))
        fp = cursor_fingerprint(spec, manifest)
        more = spec.with_overrides({"train.steps": 500})
        assert cursor_fingerprint(more, manifest) == fp
        other = spec.with_overrides({"data.seed": 3})
        assert cursor_fingerprint(other, manifest) != fp


# ---------------------------------------------------------------------------
# the tentpole guarantee: flags and specs are the SAME run
# ---------------------------------------------------------------------------

def _npz_payload(path):
    """arrays.npz entries as raw bytes (zip headers carry timestamps, so
    whole-file compare would flake; the array payloads are what matters)."""
    with np.load(path) as data:
        return {k: (data[k].dtype.str, data[k].shape, data[k].tobytes())
                for k in data.files}


class TestFlagSpecParity:
    @pytest.mark.parametrize("arch", ["roo-lsr", "hstu-gr"])
    def test_flag_vs_config_bit_identical(self, arch, tmp_path):
        from repro.launch.train import main
        steps = 20
        tweaks = {"train.steps": steps, "train.ckpt_every": steps,
                  "train.log_every": 5, "data.n_requests": 200}
        # flag-driven: legacy CLI surface
        ckpt_a = str(tmp_path / "flag_ckpt")
        argv_a = ["--arch", arch, "--steps", str(steps),
                  "--ckpt-dir", ckpt_a,
                  "--set", "train.ckpt_every=%d" % steps,
                  "--set", "train.log_every=5",
                  "--set", "data.n_requests=200"]
        tr_a, st_a = main(argv_a)
        # spec-driven: serialized config replay
        spec = scenario(arch, tweaks)
        cfg_path = str(tmp_path / "spec.json")
        spec.save(cfg_path)
        ckpt_b = str(tmp_path / "spec_ckpt")
        tr_b, st_b = main(["--config", cfg_path, "--ckpt-dir", ckpt_b])

        assert int(st_a["step"]) == int(st_b["step"]) == steps
        losses_a = [h["loss"] for h in tr_a.history]
        losses_b = [h["loss"] for h in tr_b.history]
        assert losses_a == losses_b and losses_a   # bit-identical trajectory

        step_dir = "step_%012d" % steps
        with open(os.path.join(ckpt_a, step_dir, "treedef.pkl"), "rb") as f:
            tree_a = f.read()
        with open(os.path.join(ckpt_b, step_dir, "treedef.pkl"), "rb") as f:
            tree_b = f.read()
        assert tree_a == tree_b
        pay_a = _npz_payload(os.path.join(ckpt_a, step_dir, "arrays.npz"))
        pay_b = _npz_payload(os.path.join(ckpt_b, step_dir, "arrays.npz"))
        assert pay_a == pay_b                      # bit-identical checkpoint

        # both runs stamp the SAME provenance hash into meta.json
        metas = []
        for d in (ckpt_a, ckpt_b):
            with open(os.path.join(d, step_dir, "meta.json")) as f:
                metas.append(json.load(f))
        assert all(m["scenario"] == spec.name for m in metas)
        assert all(m["scenario_hash"] == spec.content_hash() for m in metas)
        assert metas[0]["digests"] == metas[1]["digests"]


class TestEngineFromScenario:
    def test_served_scores_align_with_requests(self):
        from repro.scenario.build import build_samples
        from repro.serve.engine import ScoringEngine
        spec = scenario("roo-esr", {"data.n_requests": 24,
                                    "serve.cache_user_tower": True})
        engine = ScoringEngine.from_scenario(spec)
        requests = build_samples(spec)[:10]
        scores = engine.score_requests(requests)
        assert len(scores) == len(requests)
        assert all(s.shape[0] == r.num_impressions
                   for r, s in zip(requests, scores))
        # repeat traffic hits the user-tower cache
        engine.score_requests(requests)
        assert engine.cache.stats.hits > 0
