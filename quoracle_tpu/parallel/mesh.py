"""Mesh construction + PartitionSpecs for the model runtime.

Sharding philosophy (scaling-book recipe): pick a mesh, annotate params and
activations with NamedSharding, let XLA/GSPMD insert the collectives, which
ride ICI. Axes:

  dp — data parallel: consensus batch rows ([model-pool member x agent] rows)
  tp — tensor parallel: attention heads / ffn columns within one pool member
  sp — sequence parallel: long-context ring attention (ops/ring_attention.py)

A 3-model pool on a v5e-8 is three sub-meshes (static chip partition, host
scheduler launches the three generates concurrently) OR one mesh where the
pool rides the dp axis; both are expressible here because specs only name
axes, never device counts.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from quoracle_tpu.models.config import ModelConfig


def make_mesh(
    n_devices: Optional[int] = None,
    tp: Optional[int] = None,
    axis_names: Optional[Sequence[str]] = None,
    devices: Optional[Sequence] = None,
    sp: int = 1,
) -> Mesh:
    """Build a dp×tp mesh — or dp×sp×tp when sp > 1 (sequence-parallel
    ring attention over the middle axis: ppermute hops ride neighboring
    ICI links).

    tp defaults to all remaining devices (dp=1): latency-optimal for a
    single agent's consensus round; callers raise dp when many agents
    decode concurrently.
    """
    devs = list(devices) if devices is not None else jax.devices()
    n = n_devices or len(devs)
    devs = devs[:n]
    assert n % sp == 0, f"{n} devices not divisible by sp={sp}"
    tp = tp or n // sp
    assert n % (sp * tp) == 0, \
        f"{n} devices not divisible by sp*tp={sp * tp}"
    if axis_names is None:
        axis_names = ("dp", "sp", "tp") if sp > 1 else ("dp", "tp")
    if sp > 1:
        arr = np.array(devs).reshape(n // (sp * tp), sp, tp)
    else:
        arr = np.array(devs).reshape(n // tp, tp)
    return Mesh(arr, axis_names=tuple(axis_names))


def pool_submeshes(
    n_members: int,
    devices: Optional[Sequence] = None,
    tp: Optional[int] = None,
) -> list[Mesh]:
    """Static partition of the slice into one sub-mesh per pool member —
    the SURVEY §7 hard-part-1 design: each member's generate runs on its own
    chips and the host scheduler overlaps members (models/runtime.py).

    Contiguous device ranges keep each member's tp collectives on
    neighboring ICI links. With fewer devices than members, members share
    meshes round-robin (degenerates to the single-chip case at n=1).
    """
    devs = list(devices) if devices is not None else jax.devices()
    per = max(1, len(devs) // n_members)
    meshes = []
    for i in range(n_members):
        lo = (i * per) % len(devs)
        sub = devs[lo:lo + per] or devs[:per]
        t = tp or len(sub)
        t = _largest_tp_divisor(len(sub), t)
        arr = np.array(sub).reshape(len(sub) // t, t)
        meshes.append(Mesh(arr, axis_names=("dp", "tp")))
    return meshes


def replica_device_groups(
    n_replicas: int,
    devices: Optional[Sequence] = None,
) -> list[list]:
    """Static partition of the slice into one contiguous device group
    per REPLICA (ISSUE 10, serving/cluster.py): each group is then
    sub-partitioned per pool member by :func:`pool_submeshes`, so a
    2-replica 3-member pool on 8 chips is 2 × (4 chips → 3 sub-meshes).
    Contiguity keeps every replica's intra-member tp collectives on
    neighboring ICI links and replicas fully independent (no cross-
    replica collective exists — the router is the only coupling). With
    fewer devices than replicas, replicas share devices round-robin
    (degenerates to everyone-on-one-chip at n=1 — the CPU test case)."""
    devs = list(devices) if devices is not None else jax.devices()
    per = max(1, len(devs) // n_replicas)
    groups = []
    for i in range(n_replicas):
        lo = (i * per) % len(devs)
        sub = devs[lo:lo + per] or devs[:per]
        groups.append(sub)
    return groups


def host_layout(n_hosts: int, chips_per_host: int,
                tp: Optional[int] = None,
                fsdp: Optional[int] = None) -> dict:
    """Canonical dp/fsdp/tp sizing for an ``n_hosts x chips_per_host``
    deployment (ISSUE 12; SNIPPETS.md [2]/[3], PAPERS.md "Scalable
    Training of Language Models using JAX pjit and TPUv4"): tp stays
    INSIDE a host (its collectives ride ICI every step), fsdp spans the
    hosts (its all-gathers amortize over a layer, so DCN-class links
    carry them), and dp takes whatever remains. Returns
    ``{"dp", "fsdp", "tp", "n_hosts", "chips_per_host", "total"}``
    with ``dp * fsdp * tp == n_hosts * chips_per_host``."""
    n_hosts = max(1, int(n_hosts))
    chips_per_host = max(1, int(chips_per_host))
    total = n_hosts * chips_per_host
    tp = min(chips_per_host, tp or chips_per_host)
    while chips_per_host % tp:
        tp -= 1
    fsdp = fsdp if fsdp is not None else n_hosts
    fsdp = max(1, min(fsdp, total // tp))
    while (total // tp) % fsdp:
        fsdp -= 1
    dp = total // (tp * fsdp)
    return {"dp": dp, "fsdp": fsdp, "tp": tp, "n_hosts": n_hosts,
            "chips_per_host": chips_per_host, "total": total}


def make_host_mesh(n_hosts: int, chips_per_host: int,
                   tp: Optional[int] = None,
                   fsdp: Optional[int] = None,
                   devices: Optional[Sequence] = None) -> Mesh:
    """A ("dp", "fsdp", "tp") mesh laid out HOST-MAJOR per
    :func:`host_layout`: the fastest-varying axis (tp) walks one host's
    chips, so device i*chips_per_host..(i+1)*chips_per_host-1 — host
    i's local devices in a multi-process jax.devices() ordering — hold
    whole tp groups, and dp/fsdp boundaries land on host boundaries
    wherever the layout allows. SPMD jobs (training, dryruns) shard
    over it; the serving plane stays host-local by design
    (runtime.py) and sizes itself with :func:`pool_sizing`'s ``hosts``
    dimension instead."""
    lay = host_layout(n_hosts, chips_per_host, tp=tp, fsdp=fsdp)
    devs = list(devices) if devices is not None else jax.devices()
    if len(devs) < lay["total"]:
        raise ValueError(
            f"host mesh needs {lay['total']} devices "
            f"({n_hosts} hosts x {chips_per_host}); only "
            f"{len(devs)} visible")
    arr = np.array(devs[:lay["total"]]).reshape(
        lay["dp"], lay["fsdp"], lay["tp"])
    return Mesh(arr, axis_names=("dp", "fsdp", "tp"))


V5E_HBM_BYTES = 16 * 1024 ** 3          # 16 GiB per v5e chip (public spec)
POOL_TAIL_RESERVE = 1.25 * 1024 ** 3    # activations + compiled programs +
                                        # grammar tables + fragmentation


def device_hbm_limit(device) -> int:
    """Memory capacity of one jax device, in bytes: the live runtime's
    ``memory_stats()`` limit when the backend exposes it (TPU and GPU
    do), the public spec of a v5e ("TPU v5 lite") that reports none, 0
    for hosts that report nothing (CPU) — callers treat 0 as "no budget
    known" rather than inventing one (infra/resources.py headroom
    gauges). Any other TPU that reports no limit is an error."""
    try:
        stats = device.memory_stats()
    except Exception:                     # noqa: BLE001 — optional API
        stats = None
    if stats:
        limit = int(stats.get("bytes_limit")
                    or stats.get("bytes_reservable_limit") or 0)
        if limit > 0:
            return limit
    if getattr(device, "platform", "") != "tpu":
        return 0
    kind = getattr(device, "device_kind", "")
    if "v5 lite" in kind:
        return V5E_HBM_BYTES
    raise ValueError(f"device kind {kind!r} reports no memory limit and "
                     f"has no HBM size on file (parallel/mesh.py)")


def pool_sizing(pool: Sequence[str], n_devices: int = 8,
                hbm_per_chip: int = V5E_HBM_BYTES,
                dtype_bytes: int = 2,
                host_kv_mb: int = 0,
                disk_kv_gb: float = 0.0,
                page: int = 128,
                replicas: int = 1,
                disaggregate: bool = False,
                hosts: int = 1,
                quantize_weights: bool = False,
                quantize_kv: bool = False,
                fleet_min: int = 1,
                fleet_max: int = 0,
                trainer_chips: int = 0,
                capture_events_per_s: float = 0.0,
                capture_mb: float = 256.0) -> dict:
    """Explicit HBM budget for a model pool on a v5e sub-mesh partition
    (VERDICT r4 item 4): per member — chips (= recommended_tp), bf16
    weight bytes per chip, the page-pool bytes left after the tail
    reserve, and how many resident KV tokens that pool holds. The
    placement is the SURVEY §7 hard-part-1 design: a static partition of
    the slice, one contiguous tp sub-mesh per member.

    With tiered KV (ISSUE 7, serving/kvtier.py) the HBM figure stops
    being the capacity ceiling: ``host_kv_mb`` (per member, the
    ``--host-kv-mb`` flag) and ``disk_kv_gb`` (the ``--disk-kv-dir``
    store's budget; 0 = unbounded when enabled elsewhere) extend each
    member with host/disk tier rows — the ``tiers`` block reports
    resident HBM pages beside hibernation and durable-prefix capacity in
    tokens, so ``--plan`` output matches what the serving path actually
    holds. Host/disk copies are UNSHARDED (full KV bytes per token),
    hence the tp=1 byte rate in those rows.

    With ``replicas`` > 1 (ISSUE 10, serving/cluster.py) the plan grows
    a ``replica_tiers`` section matching the disaggregated topology:
    the slice splits into ``replicas`` contiguous device groups
    (``replica_device_groups``), each holding the WHOLE pool, and —
    under ``disaggregate`` — the first ``max(1, replicas // 2)`` groups
    form the prefill tier, the rest the decode tier (the cluster
    builder's split). Per role: replica count, device count, HBM
    budget, and resident-session capacity (sessions of one context
    window each, summed over the role's replicas; prefill replicas hold
    sessions only transiently — pages hibernate out at handoff — so
    steady-state resident capacity is attributed to the decode tier).

    With ``hosts`` > 1 (ISSUE 12, serving/fabric/) the plan answers
    "N hosts x M chips" instead of assuming one device set:
    ``n_devices`` becomes PER-HOST chips, replicas stay HOST-LOCAL
    (serving never spans a collective across hosts — the fabric wire is
    the only cross-host coupling), and a ``hosts`` block reports
    replicas-per-host packing, the host count the topology needs, and
    the canonical dp/fsdp/tp layout (:func:`host_layout`) an SPMD job
    of the same footprint would shard over.

    Returns {"members": [...], "chips_used", "fits", "hbm_per_chip"};
    ``fits`` is False when the pool needs more chips than the slice has
    or any member's weights alone exceed its chips' HBM.
    """
    from quoracle_tpu.models.config import get_model_config
    members, used, fits = [], 0, True
    # Quantized serving (ISSUE 13): plan at the byte rates the ladder
    # actually pays — int8 weights are 1 byte/param; int8 KV is 1
    # byte/elem plus 8 bytes per (token, kv-head) of fp32 K+V scales
    # (models/quant.py). Host/disk tier token rates quantize too: the
    # scales travel WITH the pages through every tier.
    w_byte = 1 if quantize_weights else dtype_bytes
    for spec in pool:
        cfg = get_model_config(spec)
        tp = _largest_tp_divisor(cfg.n_kv_heads,
                                 max(1, cfg.recommended_tp))
        weights = cfg.n_params * w_byte
        w_per_chip = weights / tp
        page_pool = hbm_per_chip - w_per_chip - POOL_TAIL_RESERVE
        if quantize_kv:
            kv_tok = (cfg.kv_bytes_per_token(tp, 1)
                      + cfg.n_layers * max(1, cfg.n_kv_heads // tp) * 8)
        else:
            kv_tok = cfg.kv_bytes_per_token(tp, dtype_bytes)
        resident = int(page_pool // kv_tok) if page_pool > 0 else 0
        window_resident = 0
        if len(cfg.kv_groups) > 1 and not quantize_kv:
            # window and full attention layers mixed: the engine gives
            # each retention group half of the pool at the group's own
            # rate (generate.py GenerateEngine.__init__)
            kv_tok = cfg.kv_bytes_per_token(tp, dtype_bytes, group=0)
            resident, window_resident = (
                int(max(0.0, page_pool) / 2
                    // cfg.kv_bytes_per_token(tp, dtype_bytes, group=g))
                for g in (0, 1))
        m_fits = page_pool > 0
        fits = fits and m_fits
        used += tp
        # host/disk tiers hold full (unsharded) KV bytes per token
        if quantize_kv:
            kv_tok_host = (cfg.kv_bytes_per_token(1, 1)
                           + cfg.n_layers * cfg.n_kv_heads * 8)
        else:
            kv_tok_host = cfg.kv_bytes_per_token(1, dtype_bytes)
        host_tokens = int(host_kv_mb * (1 << 20) // kv_tok_host) \
            if host_kv_mb else 0
        disk_tokens = int(disk_kv_gb * (1 << 30) // kv_tok_host) \
            if disk_kv_gb else 0
        members.append({
            "model": cfg.name, "tp": tp, "chips": tp,
            "params_b": round(cfg.n_params / 1e9, 2),
            "weights_gb_per_chip": round(w_per_chip / 1024 ** 3, 2),
            "page_pool_gb_per_chip": round(max(0.0, page_pool) / 1024 ** 3,
                                           2),
            "kv_bytes_per_token_per_chip": kv_tok,
            "weights_dtype": "int8" if quantize_weights else "bf16",
            "kv_dtype": "int8+scales" if quantize_kv else "bf16",
            "resident_kv_tokens": resident,
            # tokens a WINDOW group's pools hold (0: the model has none;
            # the figure above is then the full group's)
            "resident_window_kv_tokens": window_resident,
            "tiers": {
                "hbm_pages": resident // page,
                "hbm_tokens": resident,
                "host_kv_mb": host_kv_mb,
                "host_kv_tokens": host_tokens,
                # disk store has no built-in budget: 0 here means
                # "no explicit cap given", not "no disk tier"
                "disk_kv_tokens": disk_tokens,
            },
            "fits": m_fits,
        })
    hosts = max(1, int(hosts))
    total_devices = hosts * n_devices
    fits = fits and used * max(1, replicas) <= total_devices
    out = {"members": members, "chips_used": used * max(1, replicas),
           "n_devices": n_devices, "fits": fits,
           "hbm_per_chip_gb": round(hbm_per_chip / 1024 ** 3, 2),
           "tail_reserve_gb": round(POOL_TAIL_RESERVE / 1024 ** 3, 2),
           "host_kv_mb_per_member": host_kv_mb}
    if hosts > 1:
        # replicas are host-local: a replica's engines never issue a
        # cross-host collective, so packing is per-host chips / chips
        # per replica, and the host count the topology needs follows
        per_host = n_devices // used if used else 0
        hosts_needed = (-(-max(1, replicas) // per_host) if per_host
                        else hosts + 1)
        fits = fits and per_host >= 1 and hosts_needed <= hosts
        out["fits"] = fits
        out["hosts"] = {
            "hosts": hosts,
            "chips_per_host": n_devices,
            "total_chips": total_devices,
            "replicas_per_host": per_host,
            "hosts_needed": hosts_needed,
            "fits": per_host >= 1 and hosts_needed <= hosts,
            "layout": host_layout(hosts, n_devices,
                                  tp=max((m["tp"] for m in members),
                                         default=1)),
        }
    if replicas > 1:
        out["replica_tiers"] = _replica_tiers(
            list(pool), members, used, total_devices, replicas,
            disaggregate, hbm_per_chip, host_kv_mb,
            quantize_kv=quantize_kv)
        if fleet_max:
            # Elastic fleet (ISSUE 14, serving/fleet.py): the capacity
            # ENVELOPE the autoscaler moves within — serving-tier
            # resident sessions at the min and max bounds, and whether
            # the slice can even hold the max (a fleet that cannot
            # reach --fleet-max is a misconfiguration the plan should
            # say out loud). New replicas share the default device set
            # until the next reboot repartitions, so devices_at_max is
            # the honest post-reboot figure.
            rt = out["replica_tiers"]
            serving = rt.get("decode") or rt.get("unified")
            n_reps = max(1, serving["replicas"])
            per_sessions = serving["resident_sessions"] // n_reps
            per_host_s = serving["host_tier_sessions"] // n_reps
            n_prefill = rt.get("prefill", {}).get("replicas", 0)
            devices_at_max = (n_prefill + fleet_max) * used
            out["fleet"] = {
                "min_replicas": fleet_min,
                "max_replicas": fleet_max,
                "serving_role": serving["role"],
                "resident_sessions_min": per_sessions * fleet_min,
                "resident_sessions_max": per_sessions * fleet_max,
                "host_tier_sessions_min": per_host_s * fleet_min,
                "host_tier_sessions_max": per_host_s * fleet_max,
                "devices_at_max": devices_at_max,
                "fits_at_max": devices_at_max <= total_devices,
            }
    if trainer_chips:
        out["trainer"] = _trainer_sizing(list(pool), trainer_chips,
                                         capture_events_per_s,
                                         capture_mb)
    return out


# Nominal crc-framed JSON bytes per captured spec round: CTX_TAIL token
# ids (~6 chars each serialized) plus proposal/verified arrays and the
# fixed fields — measured ~3.5 KiB on the CPU smoke corpus, planned at
# 4 KiB so the retention figure errs conservative.
CAPTURE_RECORD_BYTES = 4096


def _trainer_sizing(pool: list, trainer_chips: int,
                    capture_events_per_s: float,
                    capture_mb: float) -> dict:
    """The serving-flywheel block of a --plan (ISSUE 19): the
    distillation job's submesh (pure data-parallel over the draft — the
    draft is small enough that tp=1 always fits, which is why it IS the
    draft), the capture store's ingest rate vs its disk budget (how
    many days of traffic the ``--capture-mb`` budget retains before
    oldest-first eviction), and the checkpoint footprint (fp32 params
    plus the two adamw moment trees)."""
    from quoracle_tpu.models.config import get_model_config
    # the flywheel trains the DRAFT: size against the pool's smallest
    # member, which is the one a speculator would propose with
    cfgs = [get_model_config(s) for s in pool]
    draft = min(cfgs, key=lambda c: c.n_params)
    layout = host_layout(1, trainer_chips, tp=1)
    ckpt_bytes = draft.n_params * 4 * 3
    daily_bytes = capture_events_per_s * CAPTURE_RECORD_BYTES * 86400
    budget_bytes = capture_mb * (1 << 20)
    return {
        "draft_model": draft.name,
        "chips": trainer_chips,
        "layout": layout,
        "batch_rows_per_step_min": layout["dp"],
        "checkpoint_gb": round(ckpt_bytes / 1024 ** 3, 3),
        "capture": {
            "events_per_s": capture_events_per_s,
            "record_bytes_nominal": CAPTURE_RECORD_BYTES,
            "mb_per_day": round(daily_bytes / (1 << 20), 1),
            "budget_mb": capture_mb,
            "retention_days": (round(budget_bytes / daily_bytes, 2)
                               if daily_bytes else None),
        },
    }


def _replica_tiers(pool: list, members: list, chips_per_replica: int,
                   n_devices: int, replicas: int, disaggregate: bool,
                   hbm_per_chip: int, host_kv_mb: int,
                   quantize_kv: bool = False) -> dict:
    """The per-role capacity block of a multi-replica --plan (ISSUE 10
    satellite). Session capacity is denominated in resident sessions of
    ONE full context window per member (the conservative agent-serving
    unit); the host tier extends the decode tier's figure exactly as in
    the single-replica tiers rows."""
    n_prefill = max(1, replicas // 2) if disaggregate else 0
    n_decode = replicas - n_prefill

    def _tier(name: str, n_reps: int, resident: bool) -> dict:
        from quoracle_tpu.models.config import get_model_config
        sessions = 0
        host_sessions = 0
        for spec, m in zip(pool, members):
            cfg = get_model_config(spec)
            window = max(1, cfg.context_window)
            sessions += m["resident_kv_tokens"] // window
            if host_kv_mb:
                kv_tok_host = (
                    cfg.kv_bytes_per_token(1, 1)
                    + cfg.n_layers * cfg.n_kv_heads * 8
                    if quantize_kv else cfg.kv_bytes_per_token(1, 2))
                host_sessions += int(host_kv_mb * (1 << 20)
                                     // kv_tok_host) // window
        return {
            "role": name,
            "replicas": n_reps,
            "devices": n_reps * chips_per_replica,
            "hbm_budget_gb": round(
                n_reps * chips_per_replica * hbm_per_chip / 1024 ** 3,
                2),
            # prefill replicas park nothing: sessions hibernate out at
            # handoff, so steady-state residency is a decode-tier number
            "resident_sessions": (sessions * n_reps if resident else 0),
            "host_tier_sessions": (host_sessions * n_reps
                                   if resident else 0),
        }

    tiers = {}
    if disaggregate:
        tiers["prefill"] = _tier("prefill", n_prefill, resident=False)
        tiers["decode"] = _tier("decode", n_decode, resident=True)
    else:
        tiers["unified"] = _tier("unified", replicas, resident=True)
    tiers["total_devices_needed"] = replicas * chips_per_replica
    tiers["fits"] = replicas * chips_per_replica <= n_devices
    tiers["disaggregate"] = disaggregate
    return tiers


def _largest_tp_divisor(n_kv_heads: int, tp_size: int) -> int:
    d = min(n_kv_heads, tp_size)
    while n_kv_heads % d or tp_size % d:
        d -= 1
    return d


def param_specs(cfg: ModelConfig) -> dict:
    """PartitionSpec pytree matching transformer.init_params' structure.

    Megatron-style: qkv/gate/up shard the OUTPUT feature dim (heads / ffn
    columns), wo/down shard the INPUT dim — the pre-matmul activations stay
    replicated-by-row and GSPMD inserts one psum per block. Embedding shards
    the vocab axis (the gather and the logit matmul both parallelize).
    """
    specs = {
        "embed": P("tp", None),
        "layers": {
            "attn_norm": P(None, None),
            "wq": P(None, None, "tp"),
            "wk": P(None, None, "tp"),
            "wv": P(None, None, "tp"),
            "wo": P(None, "tp", None),
            "mlp_norm": P(None, None),
            "w_gate": P(None, None, "tp"),
            "w_up": P(None, None, "tp"),
            "w_down": P(None, "tp", None),
        },
        "final_norm": P(None),
    }
    if cfg.attn_bias:
        # biases follow their projection's output sharding
        specs["layers"]["bq"] = P(None, "tp")
        specs["layers"]["bk"] = P(None, "tp")
        specs["layers"]["bv"] = P(None, "tp")
    if not cfg.tie_embeddings:
        specs["lm_head"] = P(None, "tp")
    return specs


def cache_spec(cfg: ModelConfig, mesh: Mesh) -> P:
    """KV cache [L, B, S, n_kv, hd]: batch on dp, kv heads on tp (when they
    divide; MQA/MHA mismatches fall back to replicated kv heads). On an
    sp-capable mesh the SEQUENCE axis shards over sp — ring-prefilled
    prompts never materialize whole on one chip, and decode's attention
    contraction over S becomes a GSPMD psum across the ring."""
    tp_size = mesh.shape.get("tp", 1)
    kv_axis = "tp" if cfg.n_kv_heads % tp_size == 0 else None
    sp_axis = "sp" if mesh.shape.get("sp", 1) > 1 else None
    return P(None, "dp", sp_axis, kv_axis, None)


def data_spec() -> P:
    """Token batches [B, T]: rows ride dp."""
    return P("dp", None)


def shard_params(params: dict, mesh: Mesh, cfg: ModelConfig) -> dict:
    """Place a params pytree onto the mesh per param_specs."""
    specs = param_specs(cfg)
    return jax.tree.map(
        lambda p, s: jax.device_put(p, NamedSharding(mesh, s)), params, specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def _main(argv=None) -> int:
    """``python -m quoracle_tpu.parallel.mesh --plan``: print the pool's
    HBM/capacity plan as JSON — including the replica-tier section when
    ``--replicas`` > 1, so capacity planning matches the disaggregated
    topology (ISSUE 10 satellite)."""
    import argparse
    import json

    ap = argparse.ArgumentParser(prog="quoracle_tpu.parallel.mesh")
    ap.add_argument("--plan", action="store_true",
                    help="print the pool_sizing plan as JSON")
    ap.add_argument("--pool", default=None,
                    help="comma-separated model specs (default: the "
                         "bench pool)")
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--host-kv-mb", dest="host_kv_mb", type=int,
                    default=0)
    ap.add_argument("--disk-kv-gb", dest="disk_kv_gb", type=float,
                    default=0.0)
    ap.add_argument("--replicas", type=int, default=1,
                    help="data-parallel replicas of the whole pool "
                         "(serving/cluster.py)")
    ap.add_argument("--disaggregate", action="store_true",
                    help="split replicas into prefill/decode tiers")
    ap.add_argument("--hosts", type=int, default=1,
                    help="cross-host fabric topology (ISSUE 12): plan "
                         "over N hosts x --devices chips each; "
                         "replicas stay host-local, the wire is the "
                         "only cross-host coupling")
    ap.add_argument("--fleet-min", dest="fleet_min", type=int,
                    default=1,
                    help="elastic fleet (ISSUE 14): autoscaler lower "
                         "bound for the serving tier")
    ap.add_argument("--fleet-max", dest="fleet_max", type=int,
                    default=0,
                    help="elastic fleet: plan the capacity envelope "
                         "the autoscaler moves within (0 = static)")
    ap.add_argument("--trainer-chips", dest="trainer_chips", type=int,
                    default=0,
                    help="serving flywheel (ISSUE 19): size the draft "
                         "distillation job's data-parallel submesh "
                         "(0 = no trainer section)")
    ap.add_argument("--capture-events-per-s", dest="capture_events_per_s",
                    type=float, default=0.0,
                    help="flywheel capture ingest rate for the "
                         "retention estimate")
    ap.add_argument("--capture-mb", dest="capture_mb", type=float,
                    default=256.0,
                    help="flywheel capture store disk budget "
                         "(training/capture.py oldest-first eviction)")
    ap.add_argument("--quantize-weights", dest="quantize_weights",
                    action="store_true",
                    help="plan at the int8 weight byte rate (ISSUE 13)")
    ap.add_argument("--quantize-kv", dest="quantize_kv",
                    action="store_true",
                    help="plan at the int8+scales KV byte rate — "
                         "resident/host/disk token figures ~double")
    args = ap.parse_args(argv)
    if args.pool:
        pool = args.pool.split(",")
    else:
        from quoracle_tpu.models.config import BENCH_POOL
        pool = list(BENCH_POOL)
    plan = pool_sizing(pool, args.devices, host_kv_mb=args.host_kv_mb,
                       disk_kv_gb=args.disk_kv_gb,
                       replicas=args.replicas,
                       disaggregate=args.disaggregate,
                       hosts=args.hosts,
                       quantize_weights=args.quantize_weights,
                       quantize_kv=args.quantize_kv,
                       fleet_min=args.fleet_min,
                       fleet_max=args.fleet_max,
                       trainer_chips=args.trainer_chips,
                       capture_events_per_s=args.capture_events_per_s,
                       capture_mb=args.capture_mb)
    print(json.dumps(plan, indent=2))
    return 0 if plan["fits"] else 1


if __name__ == "__main__":
    raise SystemExit(_main())
