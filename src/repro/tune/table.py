"""The on-disk tuning table — versioned, fingerprint-keyed, schema-checked.

A :class:`TuningTable` maps ``(topology fingerprint, backend, dtype)``
to the :class:`TunedConfig` the sweep (``repro.tune.sweep``) selected
for that stack, so tuning happens once per topology — offline or in a
warmup pass — and every later plan build is a dictionary lookup.

Deliberately a leaf module: it imports nothing above ``repro.sparse`` /
``repro.kernels``, so ``repro.plan`` and ``repro.serve`` can consume
:class:`TunedConfig` objects without an import cycle. The plan layer
duck-types the config (it only reads the knob attributes and
``token()``), which keeps ``repro.plan`` free of any ``repro.tune``
import.

File format (JSON, human-diffable, committed next to benchmarks)::

    {
      "schema_version": 1,
      "entries": {
        "<fingerprint>:<backend>:<dtype>": {
          "config": {"block_n": 128, "layout": "bcsr", ...},
          "grid_steps": 1234, "block_work": 315904, ...
        }
      }
    }

``load`` refuses anything it cannot trust: wrong/missing
``schema_version``, non-object entries, unknown config knobs (including
knobs an older tuner wrote that the plan no longer reads) — all raise
:class:`TuningTableError` rather than silently steering kernels with
garbage.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Mapping

SCHEMA_VERSION = 1

# The only knobs a table entry may carry — anything else in a loaded
# config dict is a schema violation, not a forward-compat freebie.
_KNOBS = ("block_size", "block_n", "layout")
_LAYOUTS = ("ell", "bcsr")


class TuningTableError(ValueError):
    """A tuning-table file failed schema validation on load."""


@dataclasses.dataclass(frozen=True)
class TunedConfig:
    """One tuned kernel configuration — every knob optional.

    ``None`` means "keep the default": a config of all-``None`` is
    byte-for-byte the untuned plan. The plan builder
    (``repro.plan.stack_plan.build_plan``) reads these attributes
    directly; ``token()`` is the stable string that lands in the
    :class:`~repro.plan.PlanKey` so tuned and untuned plans never share
    a cache slot.
    """

    block_size: int | None = None  # re-block sparse weights to (b, b)
    block_n: int | None = None  # column-tile width of the kernel grids
    layout: str | None = None  # force "ell" or "bcsr" (layered route)

    def __post_init__(self):
        if self.layout is not None and self.layout not in _LAYOUTS:
            raise ValueError(
                f"layout must be one of {_LAYOUTS}, got {self.layout!r}"
            )

    @property
    def is_default(self) -> bool:
        return all(getattr(self, k) is None for k in _KNOBS)

    def token(self) -> str:
        """Deterministic cache-key fragment for this config."""
        parts = [
            f"{k}={getattr(self, k)}"
            for k in _KNOBS
            if getattr(self, k) is not None
        ]
        return ",".join(parts) if parts else "default"

    def to_dict(self) -> dict:
        return {
            k: getattr(self, k)
            for k in _KNOBS
            if getattr(self, k) is not None
        }

    @classmethod
    def from_dict(cls, d: Mapping[str, Any]) -> "TunedConfig":
        unknown = set(d) - set(_KNOBS)
        if unknown:
            raise TuningTableError(
                f"unknown tuning knobs {sorted(unknown)}; "
                f"known: {list(_KNOBS)}"
            )
        return cls(**dict(d))


def entry_key(fingerprint: str, backend: str, dtype: str) -> str:
    return f"{fingerprint}:{backend}:{dtype}"


class TuningTable:
    """In-memory view of one tuning-table file.

    ``entries`` maps :func:`entry_key` strings to records: each record
    holds the selected ``config`` plus the sweep's evidence (grid-step /
    block-work bills for tuned and default, measured wall-clock, probe
    width, route, max-abs error against the default plan). The evidence
    rides along so a committed table is auditable — the bench gates
    re-derive the grid-step claims from it.
    """

    def __init__(self, entries: dict[str, dict] | None = None):
        self.entries: dict[str, dict] = dict(entries or {})

    def __len__(self) -> int:
        return len(self.entries)

    def put(
        self,
        fingerprint: str,
        backend: str,
        dtype: str,
        config: TunedConfig,
        evidence: Mapping[str, Any] | None = None,
    ) -> None:
        record = {"config": config.to_dict()}
        if evidence:
            record.update(evidence)
        self.entries[entry_key(fingerprint, backend, dtype)] = record

    def lookup(
        self,
        fingerprint: str,
        *,
        backend: str | None = None,
        dtype: str = "float32",
    ) -> TunedConfig | None:
        """The tuned config for this stack, or ``None`` on a miss.

        ``backend=None`` resolves to the running JAX backend, so a table
        tuned on one backend never silently steers another.
        """
        if backend is None:
            import jax

            backend = jax.default_backend()
        record = self.entries.get(entry_key(fingerprint, backend, dtype))
        if record is None:
            return None
        return TunedConfig.from_dict(record["config"])

    def record(
        self,
        fingerprint: str,
        *,
        backend: str | None = None,
        dtype: str = "float32",
    ) -> dict | None:
        """The full evidence record for this stack, or ``None``."""
        if backend is None:
            import jax

            backend = jax.default_backend()
        return self.entries.get(entry_key(fingerprint, backend, dtype))

    def to_json(self) -> dict:
        return {"schema_version": SCHEMA_VERSION, "entries": self.entries}

    def save(self, path: str | os.PathLike) -> None:
        tmp = f"{path}.tmp"
        with open(tmp, "w") as f:
            json.dump(self.to_json(), f, indent=2, sort_keys=True)
            f.write("\n")
        os.replace(tmp, path)

    @classmethod
    def load(cls, path: str | os.PathLike) -> "TuningTable":
        try:
            with open(path) as f:
                raw = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            raise TuningTableError(f"cannot read tuning table {path}: {e}")
        if not isinstance(raw, dict):
            raise TuningTableError("tuning table root must be an object")
        version = raw.get("schema_version")
        if version != SCHEMA_VERSION:
            raise TuningTableError(
                f"tuning table schema_version {version!r} != "
                f"{SCHEMA_VERSION}; re-run the tuner"
            )
        entries = raw.get("entries")
        if not isinstance(entries, dict):
            raise TuningTableError("tuning table 'entries' must be an object")
        for key, record in entries.items():
            if not isinstance(record, dict) or "config" not in record:
                raise TuningTableError(
                    f"tuning table entry {key!r} missing 'config'"
                )
            TunedConfig.from_dict(record["config"])  # validates knobs
        return cls(entries)
