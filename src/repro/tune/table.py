"""Persistent tuning table: measured kernel-routing decisions, keyed by
device kind and shape bucket.

The table is a flat ``{key: value}`` JSON cache.  Keys are strings built
by :func:`shape_key` from a decision kind plus the power-of-two shape
bucket and format the decision applies to, e.g.::

    decode_m_max/K1024/R1024/1:4:8/gr64/float32   -> 24
    spmm_block_elems                              -> 4194304
    gemv_pallas/K1024/R1024/1:4:8/gr64/float32    -> {"tm": 128,
                                                      "target_depth": 256}
    convert_cost/CsrTensor->DenseTensor           -> 13.7   (us)

Values are *decisions* (thresholds, block sizes, tile configs, measured
conversion costs), never kernels themselves: a table can only change
*which* registered path runs and with which tiles, so a stale or wrong
table degrades performance, not correctness.  The differential suite pins
the routes to bitwise-identical outputs; the one exception is a Pallas
window depth (``target_depth``), which reassociates the f32 sum and is
pinned within tolerance.

A table file carries one device section per device kind, so a single
cache file can serve a heterogeneous fleet; :meth:`TuningTable.load`
selects the section for the running device and falls back to shipped
defaults (see :mod:`repro.tune.routing`) for every key the section does
not cover.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Optional

import jax

from repro.ioutil import atomic_write_json

__all__ = [
    "SCHEMA_VERSION",
    "TuningTable",
    "bucket",
    "device_kind",
    "shape_key",
]

SCHEMA_VERSION = 1


def device_kind() -> str:
    """Normalized device identity the table sections are keyed by, e.g.
    ``cpu:cpu`` or ``tpu:tpu_v5_lite`` (a TPU v5e reports ``device_kind``
    "TPU v5 lite")."""
    dev = jax.devices()[0]
    kind = dev.device_kind.lower().replace(" ", "_")
    return f"{jax.default_backend()}:{kind}"


def bucket(x: int) -> int:
    """Shape bucket: the next power of two >= x (minimum 1).  Measured
    decisions generalize across the bucket, so the table stays small and a
    lookup for an unmeasured-but-nearby shape still hits."""
    x = max(1, int(x))
    return 1 << (x - 1).bit_length()


def shape_key(kind: str, *, K: int, R: int, fmt: tuple, gr: int,
              dtype) -> str:
    """Build the table key for decision ``kind`` at a (bucketed) shape.

    ``K`` is the contraction extent, ``R`` the sparse operand's output
    extent, ``fmt`` the (n, m, g) sparsity format, ``gr`` the row-sharing
    width and ``dtype`` the activation dtype.
    """
    import jax.numpy as jnp

    n, m, g = fmt
    return (f"{kind}/K{bucket(K)}/R{bucket(R)}/{n}:{m}:{g}/gr{gr}/"
            f"{jnp.dtype(dtype).name}")


@dataclasses.dataclass
class TuningTable:
    """In-memory view of one device section of the JSON cache."""

    device: str
    entries: dict[str, Any] = dataclasses.field(default_factory=dict)
    meta: dict[str, Any] = dataclasses.field(default_factory=dict)

    # -- lookups ----------------------------------------------------------
    def get(self, key: str, default: Any = None) -> Any:
        return self.entries.get(key, default)

    def put(self, key: str, value: Any) -> None:
        self.entries[key] = value

    def __len__(self) -> int:
        return len(self.entries)

    def __contains__(self, key: str) -> bool:
        return key in self.entries

    # -- persistence ------------------------------------------------------
    @classmethod
    def for_device(cls, device: Optional[str] = None) -> "TuningTable":
        return cls(device=device or device_kind())

    @classmethod
    def load(cls, path: str, *, device: Optional[str] = None
             ) -> "TuningTable":
        """Load the section for ``device`` (default: the running device).
        A file without a matching section yields an *empty* table — every
        lookup then falls back to the shipped defaults."""
        with open(path) as f:
            doc = json.load(f)
        if doc.get("schema") != SCHEMA_VERSION:
            raise ValueError(
                f"tuning table {path!r} has schema {doc.get('schema')!r}; "
                f"this build reads schema {SCHEMA_VERSION} "
                f"(regenerate with `python -m repro.tune`)"
            )
        device = device or device_kind()
        section = doc.get("devices", {}).get(device, {})
        return cls(device=device,
                   entries=dict(section.get("entries", {})),
                   meta=dict(section.get("meta", {})))

    def save(self, path: str) -> None:
        """Write this device's section into ``path``, preserving sections
        other devices recorded (read-modify-write).

        The temp file is pid-unique and atomically renamed, so readers
        never see a torn file and concurrent savers cannot interleave
        writes; the read-modify-write itself is last-writer-wins (no
        cross-process lock) — concurrent tuners racing on one cache file
        can drop each other's *section update*, so fleet-shared caches
        should be written by one tuner per device kind at a time."""
        doc = {"schema": SCHEMA_VERSION, "devices": {}}
        if os.path.exists(path):
            try:
                with open(path) as f:
                    old = json.load(f)
                if old.get("schema") == SCHEMA_VERSION:
                    doc["devices"].update(old.get("devices", {}))
            except (OSError, ValueError):
                pass  # unreadable/corrupt cache: rewrite from scratch
        doc["devices"][self.device] = {
            "meta": self.meta,
            "entries": self.entries,
        }
        atomic_write_json(path, doc, sort_keys=True)

    def merge(self, other: "TuningTable") -> None:
        """Adopt ``other``'s entries (other wins on conflicts)."""
        self.entries.update(other.entries)
        self.meta.update(other.meta)
