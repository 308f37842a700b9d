"""CLI entry: run tasks from the terminal.

The reference is a Phoenix server driven from a browser; the TPU-native
build adds a first-class CLI (the minimum end-to-end slice of SURVEY.md §7:
"CLI task entry"). The web dashboard consumes the same Runtime.

Usage:
    python -m quoracle_tpu.cli run "describe the task" \
        [--backend mock|tpu] [--pool xla:llama-1b,...] [--db path.db] \
        [--budget 5.00] [--profile name] [--watch-seconds 30]
    python -m quoracle_tpu.cli resume --db path.db      # boot revival
    python -m quoracle_tpu.cli status --db path.db
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys

from quoracle_tpu.infra.bus import TOPIC_ACTIONS, TOPIC_LIFECYCLE
from quoracle_tpu.runtime import Runtime, RuntimeConfig


def _print_event(topic: str, event: dict) -> None:
    kind = event.get("event")
    agent = event.get("agent_id", "")
    if kind == "agent_spawned":
        line = f"+ {agent} spawned (parent={event.get('parent_id')})"
    elif kind in ("agent_terminated", "agent_dismissed"):
        line = f"- {agent} {kind.split('_')[1]}"
    elif kind == "action_started":
        line = f"  {agent} → {event.get('action')}"
    elif kind == "action_completed":
        line = f"  {agent} ✓ {event.get('action')} [{event.get('status')}]"
    elif kind == "decision":
        d = event.get("decision", {})
        line = (f"  {agent} decided {d.get('action')} "
                f"(confidence {d.get('confidence')}, rounds {d.get('rounds')})")
    elif kind == "task_message":
        m = event.get("message", {})
        line = f"  ✉ {m.get('from')} → {m.get('targets')}: {m.get('content')}"
    else:
        return
    print(line, flush=True)


def _attach_printer(rt: Runtime) -> None:
    rt.bus.subscribe(TOPIC_LIFECYCLE, _print_event)
    rt.bus.subscribe(TOPIC_ACTIONS, _print_event)




def _parse_drafts(drafts) -> dict:
    """--draft TARGET=DRAFT (repeatable) -> draft_map dict."""
    out = {}
    for item in drafts or []:
        target, sep, draft = item.partition("=")
        if not sep or not target or not draft:
            raise SystemExit(f"--draft expects TARGET=DRAFT, got {item!r}")
        out[target] = draft
    return out

def runtime_from_args(args: argparse.Namespace) -> Runtime:
    """The Runtime a run/resume/serve invocation describes (one mapping
    from the shared flags to RuntimeConfig)."""
    pool = getattr(args, "pool", None)
    return Runtime(RuntimeConfig(
        db_path=args.db, backend=args.backend,
        model_pool=pool.split(",") if pool else None,
        checkpoints=args.checkpoints, tp=args.tp,
        image_backend=args.image_backend,
        coordinator_address=args.coordinator,
        num_processes=args.num_processes,
        process_id=args.process_id,
        draft_map=_parse_drafts(args.drafts) or None,
        draft_k=args.draft_k,
        qos=args.qos or None,
        host_kv_mb=args.host_kv_mb, disk_kv_dir=args.disk_kv_dir,
        disk_kv_gb=args.disk_kv_gb,
        replicas=args.replicas, disaggregate=args.disaggregate,
        fabric_listen=args.fabric_listen,
        fabric_peers=(args.fabric_peers.split(",")
                      if args.fabric_peers else None),
        prefixd=args.prefixd,
        chaos_plan=args.chaos_plan,
        quantize_weights=args.quantize_weights,
        quantize_kv=args.quantize_kv,
        fleet_min=args.fleet_min, fleet_max=args.fleet_max,
        fleet_tick_s=args.fleet_tick_s,
        sim_trace=args.sim_trace, sim_seed=args.sim_seed,
        capture_dir=args.capture_dir, capture_mb=args.capture_mb))


async def cmd_run(args: argparse.Namespace) -> int:
    pool = args.pool.split(",") if args.pool else None
    rt = runtime_from_args(args)
    _attach_printer(rt)
    if pool is None and args.profile is None:
        pool = rt.default_pool()
    task_id, root = await rt.tasks.create_task(
        args.description, model_pool=pool, profile=args.profile,
        budget=args.budget, grove=args.grove)
    rt.bus.subscribe(f"agents:{root.agent_id}:logs", _print_event)
    rt.bus.subscribe(f"tasks:{task_id}:messages", _print_event)
    print(f"task {task_id} started, root agent {root.agent_id}", flush=True)
    try:
        await asyncio.sleep(args.watch_seconds)
    finally:
        await rt.tasks.pause_task(task_id)
        print(json.dumps(rt.status()), flush=True)
        rt.close()
    return 0


async def cmd_resume(args: argparse.Namespace) -> int:
    rt = runtime_from_args(args)
    _attach_printer(rt)
    result = await rt.boot()
    print(json.dumps(result), flush=True)
    try:
        await asyncio.sleep(args.watch_seconds)
    finally:
        for task_id in result.get("revived", []):
            await rt.tasks.pause_task(task_id)
        rt.close()
    return 0


async def start_server(args: argparse.Namespace):
    """Everything ``serve`` does before it idles: build the runtime, bind
    the dashboard, revive persisted tasks. Returns (runtime, server), or
    (None, None) after printing why the bind was refused. chip_smoke.py
    starts the server through this, so that server and client can share
    the one process that may hold the chip."""
    from quoracle_tpu.web import DashboardServer
    rt = runtime_from_args(args)
    # Validate host/token BEFORE boot so a refused bind exits with a clean
    # message instead of a traceback over a half-started runtime.
    try:
        server = DashboardServer(rt, host=args.host, port=args.port,
                                 auth_token=args.token)
    except ValueError as e:
        print(f"error: {e}", flush=True)
        rt.close()
        return None, None
    _attach_printer(rt)
    result = await rt.boot()
    if result["revived"]:
        print(f"revived tasks: {result['revived']}", flush=True)
    server = await server.start()
    print(f"dashboard at {server.url}", flush=True)
    return rt, server


async def cmd_serve(args: argparse.Namespace) -> int:
    rt, server = await start_server(args)
    if rt is None:
        return 2
    try:
        while True:
            await asyncio.sleep(3600)
    except (KeyboardInterrupt, asyncio.CancelledError):
        pass
    finally:
        await server.stop()
        await rt.shutdown()
    return 0


async def cmd_status(args: argparse.Namespace) -> int:
    rt = Runtime(RuntimeConfig(db_path=args.db))
    print(json.dumps(rt.status(), indent=2))
    rt.close()
    return 0


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="quoracle_tpu")
    sub = p.add_subparsers(dest="cmd", required=True)

    def common(sp):
        sp.add_argument("--db", default=":memory:")
        sp.add_argument("--backend", choices=["mock", "tpu"], default="mock")
        sp.add_argument("--watch-seconds", type=float, default=30.0)
        sp.add_argument("--checkpoint", action="append", dest="checkpoints",
                        metavar="DIR",
                        help="HF checkpoint dir to register + serve "
                             "(repeatable; implies the pool when --pool "
                             "is unset)")
        sp.add_argument("--tp", type=int, default=None,
                        help="tensor-parallel size per pool member on "
                             "multi-chip slices")
        sp.add_argument("--image-backend", dest="image_backend",
                        choices=["procedural", "diffusion"],
                        default="procedural",
                        help="generate_images backend: placeholder PNGs or "
                             "the on-device diffusion model")
        sp.add_argument("--draft", action="append", dest="drafts",
                        metavar="TARGET=DRAFT",
                        help="speculative serving: draft model spec for a "
                             "pool member, e.g. xla:llama-1b=xla:draft "
                             "(repeatable; models/speculative.py)")
        sp.add_argument("--draft-k", dest="draft_k", type=int, default=6,
                        help="speculative serving: initial draft length K "
                             "per round (adaptive: shrinks on low "
                             "acceptance, falls back to vanilla below "
                             "the floor and re-probes)")
        sp.add_argument("--coordinator", dest="coordinator", default=None,
                        help="multi-host: coordinator address "
                             "(host:port) to join the JAX distributed "
                             "system; auto-detected on TPU pods")
        sp.add_argument("--num-processes", dest="num_processes", type=int,
                        default=None)
        sp.add_argument("--process-id", dest="process_id", type=int,
                        default=None)
        sp.add_argument("--continuous", action="store_true",
                        help="accepted and read nowhere: every member "
                             "serves through the one batcher "
                             "(models/scheduler.py); kept until a "
                             "`benchmark` PR drops it from the argv "
                             "benchmark/run.py:405 builds")
        sp.add_argument("--host-kv-mb", dest="host_kv_mb", type=int,
                        default=0,
                        help="tiered KV (serving/kvtier.py): host-RAM "
                             "budget per pool member for hibernated "
                             "sessions and stripped prefix blocks; "
                             "0 disables the host tier unless "
                             "--disk-kv-dir is set (then 256 MB)")
        sp.add_argument("--disk-kv-dir", dest="disk_kv_dir", default=None,
                        help="tiered KV: directory of the checksummed "
                             "disk prefix store — a restarted process "
                             "warm-starts from its predecessor's "
                             "prefixes; corrupt entries are skipped")
        sp.add_argument("--disk-kv-gb", dest="disk_kv_gb", type=float,
                        default=8.0,
                        help="byte budget of the disk prefix store per "
                             "pool member (GiB): oldest-LRU entries "
                             "prune when a write overflows it; 0 = "
                             "unbounded")
        sp.add_argument("--quantize-weights", dest="quantize_weights",
                        action="store_true",
                        help="quantized serving (models/quant.py): "
                             "per-channel symmetric int8 weights with "
                             "on-the-fly dequant in the matmuls — ~2x "
                             "more/larger pool members at fixed HBM")
        sp.add_argument("--quantize-kv", dest="quantize_kv",
                        action="store_true",
                        help="quantized serving: int8 KV pages with "
                             "per-(token, kv-head) scales beside them "
                             "— resident_kv_tokens ~doubles and every "
                             "demote/spill/handoff ships ~half the "
                             "bytes; the quant format is part of "
                             "kv_signature (mixed-precision peers "
                             "reject handoff and re-prefill)")
        sp.add_argument("--replicas", type=int, default=1,
                        help="disaggregated serving plane "
                             "(serving/cluster.py): run N full replicas "
                             "of the pool, each on its own slice of the "
                             "local devices, behind a QoS-aware router; "
                             "scale = raise this number")
        sp.add_argument("--disaggregate", action="store_true",
                        help="role-tag the replicas into prefill "
                             "(MFU-optimized, first token + KV) and "
                             "decode (long decode loops + "
                             "speculation) tiers with KV handoff "
                             "between them; implies --replicas 2 when "
                             "unset")
        sp.add_argument("--fleet-min", dest="fleet_min", type=int,
                        default=1,
                        help="elastic fleet (serving/fleet.py): "
                             "serving-tier replica lower bound for the "
                             "autoscaler")
        sp.add_argument("--fleet-max", dest="fleet_max", type=int,
                        default=0,
                        help="elastic fleet: arm the FleetController "
                             "over the cluster — scale the serving "
                             "tier within [--fleet-min, this], re-tier "
                             "prefill/decode when the traffic mix "
                             "shifts, and drain replicas by live "
                             "session migration; 0 (default) keeps the "
                             "static boot topology; requires "
                             "--replicas/--disaggregate")
        sp.add_argument("--fleet-tick-s", dest="fleet_tick_s",
                        type=float, default=5.0,
                        help="elastic fleet: seconds between policy "
                             "ticks (paces the ticker thread only — "
                             "decisions consume signals, never the "
                             "clock)")
        sp.add_argument("--fabric-listen", dest="fabric_listen",
                        default=None, metavar="[ROLE@]HOST:PORT",
                        help="cluster fabric (serving/fabric/): serve "
                             "this node's backend as a network replica "
                             "peer at this address (role: prefill | "
                             "decode | unified, default unified); the "
                             "front door process places work here over "
                             "the wire")
        sp.add_argument("--fabric-peers", dest="fabric_peers",
                        default=None, metavar="[ROLE@]HOST:PORT,...",
                        help="cluster fabric: run this node as the "
                             "standalone router front door over these "
                             "remote peers (no local engines; "
                             "SignalSnapshot poll protocol, aggregate "
                             "admission, wire KV handoff)")
        sp.add_argument("--prefixd", default=None, metavar="HOST:PORT",
                        help="cluster fabric: fleet prefix service "
                             "address — every engine tier reads "
                             "through it, so this replica warm-starts "
                             "from the fleet's prefixes (serve one "
                             "with python -m quoracle_tpu.serving."
                             "fabric.prefixd)")
        sp.add_argument("--chaos-plan", dest="chaos_plan", default=None,
                        metavar="PLAN.json",
                        help="chaos plane (quoracle_tpu/chaos): arm this "
                             "JSON fault plan ({'seed': N, 'faults': "
                             "[{'point', 'kind', ...}]}) at boot — "
                             "deterministic game-day fault injection "
                             "against a canary; see ARCHITECTURE.md §14")
        sp.add_argument("--sim-trace", dest="sim_trace", default=None,
                        metavar="TRACE.json",
                        help="fleet simulator (quoracle_tpu/sim): "
                             "replay this serialized workload trace at "
                             "boot on a shadow thread — compressed "
                             "virtual time, capacity sized from the "
                             "live router, forecast priors to the "
                             "fleet policy's dry-run seam; results on "
                             "GET /api/sim; see ARCHITECTURE.md §19")
        sp.add_argument("--sim-seed", dest="sim_seed", default=None,
                        type=int, metavar="N",
                        help="fleet simulator: with no --sim-trace, "
                             "generate and replay the canonical "
                             "diurnal-mix trace from this seed")
        sp.add_argument("--capture-dir", dest="capture_dir", default=None,
                        metavar="DIR",
                        help="serving flywheel (ISSUE 19): install the "
                             "replay capture store here — speculative "
                             "rounds + consensus audits append as "
                             "crc-framed training examples for the "
                             "offline draft-distillation trainer; "
                             "env-killable via QUORACLE_TRAIN_CAPTURE=0")
        sp.add_argument("--capture-mb", dest="capture_mb", type=float,
                        default=256.0,
                        help="capture store disk budget; oldest "
                             "segments evict first (default 256)")
        sp.add_argument("--qos", action="store_true",
                        help="serving QoS (ISSUE 4): weighted-fair "
                             "admission + overload shedding + SLO "
                             "demotion with default thresholds; tenants "
                             "via the qos_tenants setting + "
                             "serving/qos.QoSConfig")

    runp = sub.add_parser("run", help="create a task and watch it")
    runp.add_argument("description")
    runp.add_argument("--pool", help="comma-separated model specs")
    runp.add_argument("--profile")
    runp.add_argument("--budget")
    runp.add_argument("--grove", help="grove directory (topology + "
                                      "governance manifest)")
    common(runp)

    resp = sub.add_parser("resume", help="boot revival of persisted tasks")
    common(resp)

    servep = sub.add_parser("serve", help="run the web dashboard")
    servep.add_argument("--host", default="127.0.0.1")
    servep.add_argument("--port", type=int, default=8400)
    servep.add_argument("--pool", help="comma-separated model specs")
    servep.add_argument("--token", default=None,
                        help="dashboard auth token (default: env "
                             "QUORACLE_DASHBOARD_TOKEN); required for "
                             "non-loopback --host")
    common(servep)

    statp = sub.add_parser("status", help="show tasks + agents")
    statp.add_argument("--db", default=":memory:")

    showp = sub.add_parser(
        "show-prompts",
        help="dump verbatim LLM prompts for a named scenario (the "
             "reference's mix quoracle.show_llm_prompts)")
    showp.add_argument("scenario", nargs="?", default=None)
    showp.add_argument("--write-golden", metavar="DIR", default=None)
    return p


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)
    if args.cmd == "show-prompts":
        from quoracle_tpu.tools.show_prompts import main as show_main
        if args.write_golden:
            return show_main(["--write-golden", args.write_golden])
        return show_main([args.scenario] if args.scenario else [])
    handler = {"run": cmd_run, "resume": cmd_resume,
               "serve": cmd_serve, "status": cmd_status}[args.cmd]
    return asyncio.run(handler(args))


if __name__ == "__main__":
    sys.exit(main())
