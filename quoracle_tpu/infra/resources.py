"""Live resource accounting for the serving path (ISSUE 3 tentpole):
device-memory sampling, per-component HBM attribution, and the
scrape-time collector that feeds the gauges in infra/telemetry.py.

Until now HBM existed in the codebase only as a *plan* — the static
budget arithmetic of ``parallel/mesh.pool_sizing`` (weights + page pool
vs. ``POOL_TAIL_RESERVE``). This module is the *actual*: what the
devices report in use right now (``device.memory_stats()``, with a
``jax.live_arrays()`` fallback for backends that expose no allocator
stats — the CPU path CI runs on), attributed per engine to the
components an operator can act on — params are fixed cost, the KV page
pool is sized at boot, prefix-cache pages are reclaimable by eviction.

Nothing here touches RNG or device *state*: sampling reads allocator
counters and host-side bookkeeping only, so scrapes are safe on the
serving hot path and temp-0 outputs are bit-identical with the collector
registered or not (the ISSUE 2 invariant extends to resources).
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Optional

logger = logging.getLogger(__name__)

_PROC_T0 = time.monotonic()


def device_memory_stats() -> list[dict]:
    """One dict per local device: bytes in use / limit / peak and where
    the numbers came from. TPU/GPU backends answer ``memory_stats()``;
    the CPU backend reports none, so the fallback sums ``live_arrays``
    buffer bytes per device (sharded arrays split evenly across their
    devices) — an under-count of allocator overhead but an honest view
    of what serving actually holds."""
    import jax

    live_share: Optional[dict] = None

    def live_bytes(dev) -> int:
        nonlocal live_share
        if live_share is None:
            live_share = {}
            for arr in jax.live_arrays():
                try:
                    devs = list(arr.devices())
                except Exception:         # noqa: BLE001 — deleted buffer
                    continue
                share = arr.nbytes / max(1, len(devs))
                for dv in devs:
                    live_share[dv.id] = live_share.get(dv.id, 0.0) + share
        return int(live_share.get(dev.id, 0))

    from quoracle_tpu.parallel.mesh import device_hbm_limit

    out = []
    for d in jax.local_devices():
        try:
            stats = d.memory_stats()
        except Exception:                 # noqa: BLE001 — optional API
            stats = None
        if stats and stats.get("bytes_in_use") is not None:
            out.append({
                "device": d.id,
                "platform": d.platform,
                "kind": getattr(d, "device_kind", "unknown"),
                "bytes_in_use": int(stats["bytes_in_use"]),
                "bytes_limit": device_hbm_limit(d),
                "peak_bytes_in_use": int(
                    stats.get("peak_bytes_in_use") or 0),
                "source": "memory_stats",
            })
        else:
            out.append({
                "device": d.id,
                "platform": d.platform,
                "kind": getattr(d, "device_kind", "unknown"),
                "bytes_in_use": live_bytes(d),
                "bytes_limit": device_hbm_limit(d),
                "peak_bytes_in_use": 0,
                "source": "live_arrays",
            })
    return out


def headroom_fraction(devices: Optional[list[dict]] = None) -> Optional[float]:
    """min over limit-reporting devices of (limit - used) / limit, or
    None when no device reports a limit (CPU)."""
    devices = devices if devices is not None else device_memory_stats()
    fracs = [(d["bytes_limit"] - d["bytes_in_use"]) / d["bytes_limit"]
             for d in devices if d.get("bytes_limit")]
    return min(fracs) if fracs else None


def _kv_page_bytes(engine) -> int:
    # per-token pool byte rate is the engine's own (int8 payload +
    # scales for quantized members, plain cache bytes otherwise —
    # ISSUE 13), so demotable/headroom math matches what demote
    # actually moves
    return engine.kv_token_pool_bytes() * engine.sessions.page


def reclaimable_kv_bytes(backend) -> int:
    """HBM bytes the tier ladder could free RIGHT NOW without losing
    state (ISSUE 7): allocated pool pages of tier-attached engines,
    bounded by each tier's remaining host budget. Zero without tiering —
    evicting untiered pages destroys state, which is not headroom."""
    total = 0
    for e in (getattr(backend, "engines", None) or {}).values():
        tier = getattr(getattr(e, "sessions", None), "tier", None)
        if tier is None:
            continue
        try:
            total += tier.demotable_bytes(_kv_page_bytes(e))
        except Exception:                 # noqa: BLE001 — telemetry only
            pass
    return total


def effective_headroom_fraction(backend) -> Optional[float]:
    """The QoS admission controller's HBM signal under tiering
    (serving/admission.py): raw device headroom PLUS the demotable-page
    margin, capped at 1. Without a limit-reporting device (CPU) the
    signal stays None, exactly like the raw fraction."""
    devices = device_memory_stats()
    frac = headroom_fraction(devices)
    if frac is None:
        return None
    reclaim = reclaimable_kv_bytes(backend)
    if reclaim:
        limit = min(d["bytes_limit"] for d in devices
                    if d.get("bytes_limit"))
        frac = min(1.0, frac + reclaim / limit)
    return frac


def process_stats() -> dict:
    """Self-observation block for /api/resources: uptime, threads, open
    fds, current RSS (same /proc sources as the /api/metrics vm block)."""
    import os

    from quoracle_tpu.infra.telemetry import open_fd_count

    rss_mb = None
    try:
        with open("/proc/self/statm") as f:
            rss_mb = round(int(f.read().split()[1])
                           * os.sysconf("SC_PAGE_SIZE") / (1024 * 1024), 1)
    except (OSError, IndexError, ValueError):
        pass
    return {
        "pid": os.getpid(),
        "uptime_s": round(time.monotonic() - _PROC_T0, 1),
        "threads": threading.active_count(),
        "open_fds": open_fd_count(),
        "rss_mb": rss_mb,
    }


def hbm_attribution(backend) -> dict:
    """Per-engine HBM attribution: params bytes, KV page-pool bytes
    (split into session-held, prefix-cache-held, and free pages), set
    against the static ``POOL_TAIL_RESERVE`` budget from
    parallel/mesh.py. Backends without engines (MockBackend) attribute
    nothing — the empty dict IS the answer."""
    import jax

    from quoracle_tpu.parallel.mesh import POOL_TAIL_RESERVE

    members = {}
    engines = getattr(backend, "engines", None) or {}
    pool = set(getattr(backend, "pool", None) or ())
    draft_map = dict(getattr(backend, "draft_map", None) or {})
    draft_for = {d: t for t, d in draft_map.items()}
    for spec, e in engines.items():
        try:
            params_b = sum(
                int(getattr(p, "nbytes", 0) or 0)
                for p in jax.tree.leaves(e.params))
            st = e.sessions
            cfg = e.cfg
            page_b = _kv_page_bytes(e)
            pool_b = 0
            if st.k is not None:
                # a latent pool has no v; conv layers: state; a window
                # group or an ssm model's records: tuples of pools
                pool_b = sum(int(a.nbytes) for a in jax.tree.leaves(
                    (st.k, st.v, st.state)))
                if st.k_scale is not None:
                    pool_b += (int(st.k_scale.nbytes)
                               + int(st.v_scale.nbytes))
            with st.lock:
                free = len(st._free)
                n_sessions = len(st._sessions)
                occ = st.prefix_cache.occupancy()
            # page 0 is scratch; used = allocated (non-free, non-scratch)
            used_pages = st.n_pages - 1 - free
            # role (ISSUE 6): pool member, speculative draft (never
            # serves directly — its weights exist to accelerate
            # ``draft_for``), or aux (e.g. a dedicated embed model)
            # cluster engines key as "<replica>@<spec>" (serving/
            # cluster.py): the bare spec decides pool membership
            role = ("member"
                    if not pool or spec in pool
                    or spec.rsplit("@", 1)[-1] in pool
                    else "draft" if spec in draft_for else "aux")
            members[spec] = {
                "role": role,
                **({"draft_for": draft_for[spec]}
                   if spec in draft_for else {}),
                "params_bytes": params_b,
                "kv_pool_bytes": pool_b,
                "kv_pool_pages": st.n_pages,
                "kv_page_bytes": page_b,
                "kv_used_pages": used_pages,
                "kv_used_bytes": used_pages * page_b,
                "kv_free_pages": free,
                "prefix_cache_pages": occ["resident_pages"],
                "prefix_cache_bytes": occ["resident_pages"] * page_b,
                "prefix_cache": occ,
                "sessions": n_sessions,
            }
            # tiered KV (ISSUE 7): host/disk tier rows beside the HBM
            # attribution, so the operator sees the WHOLE ladder —
            # resident pages, parked host bytes, durable disk entries
            tier = getattr(st, "tier", None)
            if tier is not None:
                ts = tier.stats()
                members[spec]["kv_host_bytes"] = ts["host"]["bytes"]
                members[spec]["kv_host_budget_bytes"] = \
                    ts["host"]["budget_bytes"]
                members[spec]["kv_host_sessions"] = ts["host"]["sessions"]
                members[spec]["kv_host_prefix_blocks"] = \
                    ts["host"]["prefix_blocks"]
                if ts["disk"] is not None:
                    members[spec]["kv_disk_bytes"] = ts["disk"]["bytes"]
                    members[spec]["kv_disk_entries"] = \
                        ts["disk"]["entries"]
                members[spec]["kv_demotable_bytes"] = \
                    tier.demotable_bytes(page_b)
        except Exception:                 # noqa: BLE001 — partial is fine
            logger.exception("hbm attribution failed for %s", spec)
    totals = {
        "params_bytes": sum(m["params_bytes"] for m in members.values()),
        "kv_pool_bytes": sum(m["kv_pool_bytes"] for m in members.values()),
        "prefix_cache_bytes": sum(m["prefix_cache_bytes"]
                                  for m in members.values()),
        "draft_params_bytes": sum(
            m["params_bytes"] for m in members.values()
            if m.get("role") == "draft"),
        "kv_host_bytes": sum(m.get("kv_host_bytes", 0)
                             for m in members.values()),
        "kv_disk_bytes": sum(m.get("kv_disk_bytes", 0)
                             for m in members.values()),
        "kv_demotable_bytes": sum(m.get("kv_demotable_bytes", 0)
                                  for m in members.values()),
        "tail_reserve_bytes": int(POOL_TAIL_RESERVE),
    }
    return {"members": members, "totals": totals}


class ResourceCollector:
    """The scrape-time sampler a Runtime registers on METRICS
    (``METRICS.register_collector``): refreshes the HBM, prefix-cache,
    scheduler, and compile-storm gauges from live state, and drops a
    rate-limited ``resource_sample`` event into the flight recorder so a
    later dump shows the memory trajectory, not just the final frame."""

    def __init__(self, runtime, min_sample_gap_s: float = 1.0):
        self.runtime = runtime
        self.min_sample_gap_s = min_sample_gap_s
        self._last_sample = 0.0

    def __call__(self) -> None:
        from quoracle_tpu.infra.flightrec import FLIGHT
        from quoracle_tpu.infra.telemetry import (
            HBM_COMPONENT_BYTES, HBM_HEADROOM_RATIO, HBM_LIMIT_BYTES,
            HBM_USED_BYTES, KV_TIER_BYTES, KV_TIER_ENTRIES,
            PREFIX_CACHE_PAGES,
        )

        devices = device_memory_stats()
        for d in devices:
            HBM_USED_BYTES.set(d["bytes_in_use"], device=d["device"])
            if d["bytes_limit"]:
                HBM_LIMIT_BYTES.set(d["bytes_limit"], device=d["device"])
        frac = headroom_fraction(devices)
        HBM_HEADROOM_RATIO.set(frac if frac is not None else -1.0)

        attribution = hbm_attribution(self.runtime.backend)
        for spec, m in attribution["members"].items():
            HBM_COMPONENT_BYTES.set(m["params_bytes"], model=spec,
                                    component="params")
            HBM_COMPONENT_BYTES.set(m["kv_pool_bytes"], model=spec,
                                    component="kv_pool")
            HBM_COMPONENT_BYTES.set(m["prefix_cache_bytes"], model=spec,
                                    component="prefix_cache")
            occ = m["prefix_cache"]
            PREFIX_CACHE_PAGES.set(occ["resident_pages"], model=spec,
                                   kind="resident")
            PREFIX_CACHE_PAGES.set(occ["referenced_pages"], model=spec,
                                   kind="referenced")
            PREFIX_CACHE_PAGES.set(occ["evictable_leaf_pages"],
                                   model=spec, kind="evictable")
            # tiered KV occupancy (ISSUE 7): one gauge series per tier
            if "kv_host_bytes" in m:
                KV_TIER_BYTES.set(m["kv_used_bytes"], model=spec,
                                  tier="hbm")
                KV_TIER_BYTES.set(m["kv_host_bytes"], model=spec,
                                  tier="host")
                KV_TIER_BYTES.set(m.get("kv_disk_bytes", 0), model=spec,
                                  tier="disk")
                KV_TIER_ENTRIES.set(m["sessions"], model=spec,
                                    tier="hbm", kind="session")
                KV_TIER_ENTRIES.set(m["kv_host_sessions"], model=spec,
                                    tier="host", kind="session")
                KV_TIER_ENTRIES.set(m["kv_host_prefix_blocks"],
                                    model=spec, tier="host",
                                    kind="prefix")
                KV_TIER_ENTRIES.set(m.get("kv_disk_entries", 0),
                                    model=spec, tier="disk",
                                    kind="prefix")
        # storm gauges decay with time, not with traffic — refresh so a
        # storm that ended shows 0 at the next scrape even with no new
        # generate() calls
        for e in (getattr(self.runtime.backend, "engines", None)
                  or {}).values():
            compiles = getattr(e, "compiles", None)
            if compiles is not None:
                compiles.refresh()

        now = time.monotonic()
        if now - self._last_sample >= self.min_sample_gap_s:
            self._last_sample = now
            FLIGHT.record(
                "resource_sample",
                headroom_frac=frac,
                bytes_in_use=sum(d["bytes_in_use"] for d in devices),
                devices=len(devices),
                members={spec: {"kv_free_pages": m["kv_free_pages"],
                                "prefix_cache_pages":
                                    m["prefix_cache_pages"],
                                "sessions": m["sessions"]}
                         for spec, m in attribution["members"].items()})
