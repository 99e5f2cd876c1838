"""ScenarioSpec — one declarative, serializable config surface per scenario.

The paper's claim is a *co-design* of data, infrastructure and model around
the request; this module is where that co-design becomes one object. A
``ScenarioSpec`` names everything a run needs — model, batcher, data
source, training, serving, and the kernel/runtime knobs — and every
consumer (``launch/train.py``, ``ScoringEngine.from_scenario``, the
benchmarks, the CI smoke runner, the future tuner) builds itself from the
same spec, so two runs with equal specs are bit-identical by construction
(tests/test_scenario.py proves it for the flag-driven vs --config paths).

Design rules:

  * **Serializable, strictly validated.** ``to_json``/``from_json`` round-
    trip bit-identically; the decoder rejects unknown fields, wrong types
    and future schema versions loudly (a silently-dropped knob is a
    config that lies).
  * **No paths inside the spec.** Shard/checkpoint directories are runtime
    arguments, so a spec (and its hash) is portable across machines.
  * **Content-addressed provenance.** :meth:`ScenarioSpec.content_hash`
    fingerprints the whole spec; it is stamped into checkpoint
    ``meta.json``, shard manifests and benchmark artifacts, so an
    artifact can prove which scenario produced it.
    :meth:`ScenarioSpec.data_hash` covers only the stream/batcher-
    deciding sections — the resume-cursor fingerprint — so bumping
    ``train.steps`` to continue a run never invalidates its cursors.
  * **One precedence ladder.** Runtime knobs resolve through
    ``scenario.knobs`` (explicit arg > spec/CLI default > env > auto);
    :meth:`ScenarioSpec.apply` installs the spec's knob section as the
    process defaults.

See docs/CONFIG.md for the schema and the tuner handoff.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import typing
from typing import Any, Dict, Mapping, Optional, Tuple

SCHEMA_VERSION = 1


class ScenarioValidationError(ValueError):
    """A spec failed validation (unknown field, bad type, bad value)."""


# ---------------------------------------------------------------------------
# Sections
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ModelSpec:
    """What to train/serve. ``arch`` keys the registry; the few shared
    shape knobs cover the recsys zoo (0/"" = the arch's default)."""
    arch: str = ""
    n_items: int = 50000
    hist_len: int = 64
    seq_len: int = 0          # sequence models (dien/bert4rec); 0 = default
    m_targets: int = 16       # GR ranking targets
    variant: str = ""         # lsr mode / two-tower user-tower mode


@dataclasses.dataclass(frozen=True)
class BatcherSpec:
    b_ro: int = 32            # requests per batch
    b_nro: int = 192          # impression slots per batch
    hist_len: int = 64


@dataclasses.dataclass(frozen=True)
class DataSpec:
    """Event stream + (for ``source="disk"``) the shard pipeline knobs.
    ``n_items=0`` follows ``model.n_items`` so the stream can never emit
    ids the model's tables don't cover."""
    source: str = "memory"    # memory | disk
    n_requests: int = 800
    n_users: int = 200
    n_items: int = 0
    hist_init_max: int = 48
    product: str = "product_a"
    seed: int = 0
    late_fraction: float = 0.0
    label_wait_s: float = 600.0
    requests_per_shard: int = 256
    prefetch: bool = True
    strict_shards: bool = False


@dataclasses.dataclass(frozen=True)
class TrainSpec:
    steps: int = 100
    log_every: int = 10
    ckpt_every: int = 100
    keep_last: int = 3
    microbatches: int = 1
    lr_dense: float = 1e-3    # Adam on dense weights
    lr_emb: float = 0.05      # row-wise Adagrad on embedding tables
    halt_after_skips: int = 0
    mesh: str = ""            # "" = single device; else "DATAxMODEL" e.g. 2x4


@dataclasses.dataclass(frozen=True)
class ServeSpec:
    max_requests: int = 64
    max_impressions: int = 512
    max_delay_ms: float = 2.0
    bucketed: bool = True
    cache_user_tower: bool = False
    cache_capacity: int = 4096
    incremental: bool = False     # per-user K/V state, O(new events)/request
    state_capacity: int = 256     # users resident in the state store
    breaker_threshold: int = 5
    breaker_cooldown_s: float = 1.0


@dataclasses.dataclass(frozen=True)
class KnobsSpec:
    """Runtime knobs installed as process defaults by ``apply()`` — each
    resolves through the shared ladder in ``scenario.knobs``; ``None``
    leaves the rung unset (env var / auto decide)."""
    attn_backend: Optional[str] = None
    emb_backend: Optional[str] = None
    emb_dedup: Optional[str] = None     # always | never | auto
    faults: Optional[str] = None        # REPRO_FAULTS grammar
    # the comms group (distributed/comms.py): wire compression for the
    # sharded-embedding exchange, overlap of lookup collectives with dense
    # compute across the grad-accum microbatches, int8 scale-block width
    comms_compress: Optional[str] = None   # none | bf16 | int8
    comms_overlap: Optional[str] = None    # on | off
    comms_block: Optional[int] = None      # int8 scale-block width


@dataclasses.dataclass(frozen=True)
class ObsSpec:
    """Observability (repro.obs). ``mode`` rides the knob ladder like any
    other knob (``None`` leaves REPRO_OBS / auto in charge); ``export``
    asks the run's build path to install a JSONL telemetry emitter (the
    file path stays a runtime argument — specs never carry paths)."""
    mode: Optional[str] = None          # off | metrics | trace
    export: bool = False
    export_every_s: float = 0.0         # min seconds between JSONL lines
    verbosity: Optional[int] = None     # 0=errors 1=progress 2=debug


_SECTIONS = {"model": ModelSpec, "batcher": BatcherSpec, "data": DataSpec,
             "train": TrainSpec, "serve": ServeSpec, "knobs": KnobsSpec,
             "obs": ObsSpec}


# ---------------------------------------------------------------------------
# Strict decoding helpers
# ---------------------------------------------------------------------------

def _decode_field(value, ftype, path: str):
    """JSON value -> field value, strictly typed (bool is not an int)."""
    origin = typing.get_origin(ftype)
    if origin is typing.Union:                      # Optional[str]
        args = [a for a in typing.get_args(ftype) if a is not type(None)]
        if value is None:
            return None
        return _decode_field(value, args[0], path)
    if ftype is bool:
        if not isinstance(value, bool):
            raise ScenarioValidationError(f"{path}: expected bool, got "
                                          f"{value!r}")
        return value
    if ftype is int:
        if isinstance(value, bool) or not isinstance(value, int):
            raise ScenarioValidationError(f"{path}: expected int, got "
                                          f"{value!r}")
        return value
    if ftype is float:
        if isinstance(value, bool) or not isinstance(value, (int, float)):
            raise ScenarioValidationError(f"{path}: expected float, got "
                                          f"{value!r}")
        return float(value)
    if ftype is str:
        if not isinstance(value, str):
            raise ScenarioValidationError(f"{path}: expected str, got "
                                          f"{value!r}")
        return value
    raise ScenarioValidationError(f"{path}: unsupported field type {ftype}")


def _decode_section(cls, obj, path: str):
    if not isinstance(obj, Mapping):
        raise ScenarioValidationError(f"{path}: expected an object, got "
                                      f"{obj!r}")
    hints = typing.get_type_hints(cls)
    fields = {f.name: f for f in dataclasses.fields(cls)}
    unknown = set(obj) - set(fields)
    if unknown:
        raise ScenarioValidationError(
            f"{path}: unknown field(s) {sorted(unknown)}; "
            f"valid: {sorted(fields)}")
    kwargs = {name: _decode_field(obj[name], hints[name], f"{path}.{name}")
              for name in obj}
    return cls(**kwargs)


def _coerce(text: Any, ftype):
    """--set string -> typed value (typed values pass through checked)."""
    if not isinstance(text, str):
        return text
    origin = typing.get_origin(ftype)
    if origin is typing.Union:
        if text.lower() in ("none", "null", ""):
            return None
        args = [a for a in typing.get_args(ftype) if a is not type(None)]
        return _coerce(text, args[0])
    if ftype is bool:
        if text.lower() in ("1", "true", "yes", "on"):
            return True
        if text.lower() in ("0", "false", "no", "off"):
            return False
        raise ScenarioValidationError(f"can't parse bool from {text!r}")
    if ftype is int:
        return int(text)
    if ftype is float:
        return float(text)
    return text


# ---------------------------------------------------------------------------
# The spec
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ScenarioSpec:
    name: str
    model: ModelSpec
    batcher: BatcherSpec = BatcherSpec()
    data: DataSpec = DataSpec()
    train: TrainSpec = TrainSpec()
    serve: ServeSpec = ServeSpec()
    knobs: KnobsSpec = KnobsSpec()
    obs: ObsSpec = ObsSpec()

    # -- serialization ----------------------------------------------------------
    def to_json(self) -> dict:
        out: Dict[str, Any] = {"schema_version": SCHEMA_VERSION,
                               "name": self.name}
        for sec in _SECTIONS:
            out[sec] = dataclasses.asdict(getattr(self, sec))
        return out

    def to_json_str(self, indent: Optional[int] = 1) -> str:
        return json.dumps(self.to_json(), indent=indent, sort_keys=True)

    @classmethod
    def from_json(cls, obj) -> "ScenarioSpec":
        if isinstance(obj, str):
            obj = json.loads(obj)
        if not isinstance(obj, Mapping):
            raise ScenarioValidationError(f"spec: expected an object, got "
                                          f"{type(obj).__name__}")
        version = obj.get("schema_version")
        if not isinstance(version, int) or isinstance(version, bool):
            raise ScenarioValidationError(
                "spec: missing/invalid schema_version (int required)")
        if version > SCHEMA_VERSION:
            raise ScenarioValidationError(
                f"spec: schema_version {version} is newer than supported "
                f"{SCHEMA_VERSION} — upgrade the code, don't guess")
        unknown = set(obj) - set(_SECTIONS) - {"schema_version", "name"}
        if unknown:
            raise ScenarioValidationError(
                f"spec: unknown section(s) {sorted(unknown)}; "
                f"valid: {sorted(_SECTIONS)}")
        name = obj.get("name")
        if not isinstance(name, str) or not name:
            raise ScenarioValidationError("spec: 'name' (non-empty str) "
                                          "required")
        sections = {sec: _decode_section(scls, obj.get(sec, {}), sec)
                    for sec, scls in _SECTIONS.items()}
        spec = cls(name=name, **sections)
        spec.validate()
        return spec

    @classmethod
    def load(cls, path: str) -> "ScenarioSpec":
        with open(path) as f:
            return cls.from_json(json.load(f))

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            f.write(self.to_json_str() + "\n")

    # -- validation -------------------------------------------------------------
    def validate(self) -> "ScenarioSpec":
        """Value-level checks (types were enforced at decode). Raises
        :class:`ScenarioValidationError`; returns self for chaining."""
        def bad(msg):
            raise ScenarioValidationError(f"scenario {self.name!r}: {msg}")

        if not self.model.arch:
            bad("model.arch is required")
        if self.data.source not in ("memory", "disk"):
            bad(f"data.source {self.data.source!r} not in memory|disk")
        for field, val in (("train.steps", self.train.steps),
                           ("train.log_every", self.train.log_every),
                           ("train.ckpt_every", self.train.ckpt_every),
                           ("train.microbatches", self.train.microbatches),
                           ("batcher.b_ro", self.batcher.b_ro),
                           ("batcher.b_nro", self.batcher.b_nro),
                           ("data.n_requests", self.data.n_requests),
                           ("data.requests_per_shard",
                            self.data.requests_per_shard)):
            if val <= 0:
                bad(f"{field} must be positive, got {val}")
        if self.train.mesh:
            parts = self.train.mesh.lower().split("x")
            if not (2 <= len(parts) <= 3 and
                    all(p.isdigit() and int(p) > 0 for p in parts)):
                bad(f"train.mesh {self.train.mesh!r} is not DATAxMODEL "
                    f"(e.g. 2x4)")
        # knob values validate against the same registry the ladder uses;
        # the registering modules are imported lazily (and only when a knob
        # is actually set) so a bare spec round-trip stays stdlib-light
        knob_names = ("attn_backend", "emb_backend", "emb_dedup",
                      "comms_compress", "comms_overlap", "comms_block")
        if any(getattr(self.knobs, k) is not None for k in knob_names):
            import repro.distributed.comms      # noqa: F401 (registers knobs)
            import repro.embeddings.collection  # noqa: F401 (registers knob)
            import repro.kernels.dispatch       # noqa: F401 (registers knobs)
            from repro.scenario.knobs import REGISTRY
            for kname in knob_names:
                val = getattr(self.knobs, kname)
                if val is not None:
                    try:
                        REGISTRY[kname].check(val)
                    except ValueError as e:
                        bad(str(e))
        if self.knobs.comms_block is not None and self.knobs.comms_block <= 0:
            bad(f"knobs.comms_block must be positive, "
                f"got {self.knobs.comms_block}")
        if self.knobs.faults is not None:
            from repro.reliability.faults import FaultPlan
            try:
                FaultPlan.parse(self.knobs.faults)
            except ValueError as e:
                bad(f"knobs.faults: {e}")
        if self.serve.incremental and self.serve.cache_user_tower:
            bad("serve.incremental and serve.cache_user_tower are mutually "
                "exclusive: the state store already subsumes the user-tower "
                "memoization for stateful archs — pick one")
        if self.serve.state_capacity <= 0:
            bad(f"serve.state_capacity must be positive, got "
                f"{self.serve.state_capacity}")
        if self.obs.mode is not None:
            from repro.obs.metrics import OBS_MODES
            if self.obs.mode not in OBS_MODES:
                bad(f"obs.mode {self.obs.mode!r} not in "
                    + "|".join(OBS_MODES))
        if self.obs.verbosity is not None and self.obs.verbosity < 0:
            bad(f"obs.verbosity must be >= 0, got {self.obs.verbosity}")
        if self.obs.export_every_s < 0:
            bad(f"obs.export_every_s must be >= 0, got "
                f"{self.obs.export_every_s}")
        return self

    # -- provenance hashes ------------------------------------------------------
    def content_hash(self) -> str:
        """Content address of the WHOLE spec — the provenance fingerprint
        stamped into checkpoint meta, manifests and bench artifacts."""
        blob = json.dumps(self.to_json(), sort_keys=True,
                          separators=(",", ":"))
        return hashlib.sha256(blob.encode("utf-8")).hexdigest()[:16]

    def data_hash(self) -> str:
        """Hash of only the stream/batcher-deciding sections: two specs
        with equal data_hash produce bit-identical batch streams, so this
        (plus the shard manifest) is what resume cursors key on."""
        obj = {"data": dataclasses.asdict(
                   dataclasses.replace(self.data,
                                       n_items=self.stream_n_items(),
                                       prefetch=True, strict_shards=False)),
               "batcher": dataclasses.asdict(self.batcher)}
        blob = json.dumps(obj, sort_keys=True, separators=(",", ":"))
        return hashlib.sha256(blob.encode("utf-8")).hexdigest()[:16]

    def stream_n_items(self) -> int:
        return self.data.n_items or self.model.n_items

    # -- overrides (--set key=value) -------------------------------------------
    def with_overrides(self, overrides: Mapping[str, Any]) -> "ScenarioSpec":
        """New spec with dotted-path overrides applied; values may be
        typed or ``--set``-style strings (coerced by field type)."""
        spec = self
        for key, raw in overrides.items():
            if key == "name":
                spec = dataclasses.replace(spec, name=str(raw))
                continue
            try:
                sec_name, field = key.split(".", 1)
            except ValueError:
                raise ScenarioValidationError(
                    f"override {key!r}: expected section.field "
                    f"(e.g. train.steps)") from None
            if sec_name not in _SECTIONS:
                raise ScenarioValidationError(
                    f"override {key!r}: unknown section {sec_name!r}; "
                    f"valid: {sorted(_SECTIONS)}")
            scls = _SECTIONS[sec_name]
            hints = typing.get_type_hints(scls)
            if field not in hints:
                raise ScenarioValidationError(
                    f"override {key!r}: {scls.__name__} has no field "
                    f"{field!r}; valid: {sorted(hints)}")
            value = _coerce(raw, hints[field])
            value = _decode_field(value, hints[field], key)
            section = dataclasses.replace(getattr(spec, sec_name),
                                          **{field: value})
            spec = dataclasses.replace(spec, **{sec_name: section})
        return spec.validate()

    # -- runtime knob installation ---------------------------------------------
    def apply(self) -> "ScenarioSpec":
        """Install the spec's knob section as the process defaults on the
        shared ladder (spec beats env, per-call args beat the spec), and
        install the fault plan when one is named. Returns self."""
        knob_names = ("attn_backend", "emb_backend", "emb_dedup",
                      "comms_compress", "comms_overlap", "comms_block")
        if any(getattr(self.knobs, k) is not None for k in knob_names):
            import repro.distributed.comms      # noqa: F401 (registers knobs)
            import repro.embeddings.collection  # noqa: F401 (registers knob)
            import repro.kernels.dispatch       # noqa: F401 (registers knobs)
            from repro.scenario.knobs import REGISTRY
            for kname in knob_names:
                val = getattr(self.knobs, kname)
                if val is not None:
                    REGISTRY[kname].set_default(val)
        if self.knobs.faults is not None:
            from repro.reliability import faults
            faults.install(faults.FaultPlan.parse(self.knobs.faults))
        if self.obs.mode is not None:
            from repro.obs.metrics import OBS_KNOB
            OBS_KNOB.set_default(self.obs.mode)
        if self.obs.verbosity is not None:
            from repro.obs.log import VERBOSITY_KNOB
            VERBOSITY_KNOB.set_default(self.obs.verbosity)
        return self


def parse_set_args(pairs) -> Dict[str, str]:
    """``--set key=value`` argv fragments -> overrides dict."""
    out: Dict[str, str] = {}
    for pair in pairs or ():
        if "=" not in pair:
            raise ScenarioValidationError(
                f"--set {pair!r}: expected key=value")
        key, value = pair.split("=", 1)
        out[key.strip()] = value.strip()
    return out


def scenario_sections() -> Tuple[str, ...]:
    return tuple(_SECTIONS)
