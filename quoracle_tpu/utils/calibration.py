"""Measured gates for the paged-attention fast paths.

The ragged paged kernels trade per-launch overhead for not materializing
the [B, maxp·page] contiguous working cache. WHERE that trade wins has
not been measured on the chip (ROADMAP S3), so the gates are DATA:

  * ``tools/calibrate_paged.py`` measures the gather/direct crossover on
    the current host and writes it here;
  * ``load_paged_gates()`` reads that file (env override
    ``QUORACLE_PAGED_CALIB``; explicit constructor args beat both);
  * absent a calibration file the direct paths stay off — the
    conservative default, now a *documented absence of data* rather than
    a magic constant.

File format (JSON): {"decode_min_resident": int|null,
"prefill_min_resident": int|null, "prefill_max_chunk": int,
"measured_on": str, "device_kind": str} — null disables that path.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Optional

_OFF = 1 << 30


@dataclasses.dataclass(frozen=True)
class PagedGates:
    """Resident-token thresholds enabling the direct (ragged-kernel) paged
    paths; ``_OFF`` (2**30) disables. ``prefill_max_chunk`` bounds the
    dense intra-chunk O(T²) piece of the direct prefill — longer chunks
    take the standard path (they're mostly-fresh prefills, which never
    gather a prefix anyway).

    ``unified_min_resident`` gates the UNIFIED ragged kernel (ISSUE 8 —
    one mixed prefill+decode launch, KV written straight to pages). Its
    default differs from the direct gates: ``None`` means AUTO — ON
    (threshold 0) on TPU, off elsewhere — because the unified kernel is
    the intended default serving path on TPU and needs no calibration
    file to engage; gather is the measured FALLBACK a calibration run
    can reinstate per geometry (tools/calibrate_paged.py measures
    unified-vs-gather and writes an explicit threshold or ``"off"``).
    Old calibration files without the key keep their direct/decode gates
    and get AUTO for unified (backward compatible)."""

    decode_min_resident: int = _OFF
    prefill_min_resident: int = _OFF
    prefill_max_chunk: int = 1024
    unified_min_resident: Optional[int] = None   # None = AUTO (TPU: on)
    source: str = "default (no calibration file)"


def default_calib_path() -> str:
    base = os.environ.get("XDG_CACHE_HOME",
                          os.path.join(os.path.expanduser("~"), ".cache"))
    return os.path.join(base, "quoracle_tpu", "paged_gates.json")


def load_paged_gates(path: Optional[str] = None) -> PagedGates:
    p = (path or os.environ.get("QUORACLE_PAGED_CALIB")
         or default_calib_path())
    try:
        with open(p) as f:
            raw = json.load(f)
    except (OSError, json.JSONDecodeError):
        return PagedGates()

    # The crossover is a property of the device it was measured on: a
    # recorded device_kind that doesn't match the current device
    # invalidates the file.
    recorded = raw.get("device_kind") or ""
    if recorded:
        try:
            import jax
            current = getattr(jax.devices()[0], "device_kind", "")
        except Exception:
            current = ""
        if current and recorded != current:
            import logging
            logging.getLogger(__name__).warning(
                "ignoring paged-gate calibration %s: measured on %r, "
                "running on %r — recalibrate with tools/calibrate_paged",
                p, recorded, current)
            return PagedGates(
                source=f"default (calibration {p} is for {recorded!r}, "
                       f"not {current!r})")

    def gate(key: str) -> int:
        v = raw.get(key)
        return _OFF if v is None else int(v)

    # unified gate (ISSUE 8): ABSENT key (old files) = AUTO; explicit
    # JSON null = measured off (gather wins on this geometry)
    _absent = object()
    u = raw.get("unified_min_resident", _absent)
    unified = None if u is _absent else (_OFF if u is None else int(u))

    return PagedGates(
        decode_min_resident=gate("decode_min_resident"),
        prefill_min_resident=gate("prefill_min_resident"),
        prefill_max_chunk=int(raw.get("prefill_max_chunk", 1024)),
        unified_min_resident=unified,
        source=p,
    )


def resolve_unified_gate(gates: PagedGates) -> int:
    """The unified ragged kernel's effective threshold: an explicit
    calibrated value wins; AUTO (no file / old file) resolves to ON
    (threshold 0) on TPU — the flip the kernel exists for — and off on
    other backends, where the fused gather programs stay the measured
    default and tests opt in explicitly."""
    if gates.unified_min_resident is not None:
        return int(gates.unified_min_resident)
    try:
        import jax
        on_tpu = jax.devices()[0].platform == "tpu"
    except Exception:     # noqa: BLE001 — no backend = no kernel
        on_tpu = False
    return 0 if on_tpu else _OFF


_UNSET = object()


def save_paged_gates(path: Optional[str], *, decode_min_resident,
                     prefill_min_resident, prefill_max_chunk: int = 1024,
                     unified_min_resident=_UNSET,
                     device_kind: str = "", note: str = "") -> str:
    """Write a calibration file (tools/calibrate_paged.py's output).
    ``unified_min_resident`` omitted = the key is left out of the file
    (AUTO on load); explicit None = measured off (JSON null)."""
    import datetime
    p = path or default_calib_path()
    os.makedirs(os.path.dirname(p), exist_ok=True)
    payload = {
        "decode_min_resident": decode_min_resident,
        "prefill_min_resident": prefill_min_resident,
        "prefill_max_chunk": prefill_max_chunk,
        "device_kind": device_kind,
        "note": note,
        "measured_on": datetime.datetime.now(
            datetime.timezone.utc).isoformat(),
    }
    if unified_min_resident is not _UNSET:
        payload["unified_min_resident"] = unified_min_resident
    with open(p, "w") as f:
        json.dump(payload, f, indent=1)
    return p
