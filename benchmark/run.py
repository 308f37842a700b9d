#!/usr/bin/env python3
"""One cell of the benchmark, once:

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, because a chip belongs to one process: it registers the cell's
configuration with the program, starts the server the way a user does
(`quoracle_tpu.cli serve --backend tpu --continuous --pool xla:<config>`,
through `cli.start_server`), warms the cell's programs, and drives
`rt.backend.query` — the call `ConsensusEngine` makes once per member per
round — from one thread per client, closed loop. Phases: set-up (weights on
the device from the seed, warm-up, a lead-in that spreads the clients out),
the measured window of `--seconds`, then — the window closed and the
program's memory freed — the comparison of a seeded sample of the window's
own greedy rows with the plain reference of the configuration's family
(`benchmark/families/<family>.py`; this file names no architecture).

The last line of stdout is one JSON object: `correct`, `attempted`,
`failed`, `metrics`, `device`, with `--trace 1` `breakdown`, and last
`checks`: every number of the output check beside its limit. Realised
lengths, the generator's lateness, compile counters, the time clients spent
dropping sessions (`[drops]`), with `--trace 1` what the profiler session
cost the batcher's worker (`[tracing]`), and the same checks one to a
`[check]` line go on earlier lines; the `[check]` lines are also the last
lines of stderr.

Exits non-zero, printing no result line, when the cell is one of
`BENCHMARK.json` and JAX finds no TPU or another number of chips than the
cell asks for, and when the program is not beside it. Off the TPU it runs
only the `tiny` cells of `benchmark/cells_rehearsal.json`, and says
`"platform": "cpu"`.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse          # noqa: E402
import asyncio           # noqa: E402
import functools         # noqa: E402
import importlib         # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import shutil            # noqa: E402
import sys               # noqa: E402
import threading         # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:                  # `python3 benchmark/run.py` too
    sys.path.insert(0, ROOT)

from benchmark import configs, stats, traffic      # noqa: E402

OUT_DIR = os.path.join(ROOT, ".bench_out")
# The traced part of a `--trace 1` window: long enough for some hundreds of
# decode steps, short enough that the trace stays tens of megabytes.
TRACE_SECONDS = 5.0
TRACE_LEAD_SECONDS = 1.0


def say(tag: str, obj) -> None:
    print(f"[{tag}] " + (obj if isinstance(obj, str)
                         else json.dumps(obj, default=str)), flush=True)


def load_cells(root: str = HERE) -> tuple[dict, dict, dict]:
    """(cells of BENCHMARK.json, rehearsal cells), each by name, and the
    end-to-end metrics as BENCHMARK.json lists them."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    tiny = {w["name"]: w for w in configs.load_json(
        root, "cells_rehearsal.json")["workloads"]}
    return ({w["name"]: w for w in manifest["workloads"]}, tiny,
            manifest["end_to_end"])


def per_layer_names(mix: dict, raw_cfg: dict) -> list:
    """The per-layer metrics a cell reports: its mix's list and then its
    configuration's own, each name once."""
    return list(dict.fromkeys(mix["per_layer"]
                              + raw_cfg.get("per_layer", [])))


class CompileLog:
    """JAX's own compile events, with the time of each: what compiled, what
    the persistent cache served, and what fell inside the window."""

    def __init__(self):
        self.requests: list[float] = []       # every program asked for
        self.misses: list[float] = []         # compiled by the backend
        self.hits = 0
        self.compile_s = 0.0

    def install(self) -> None:
        import jax

        def on_event(event, **_):
            if event == "/jax/compilation_cache/compile_requests_use_cache":
                self.requests.append(time.monotonic())
            elif event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses.append(time.monotonic())

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compile_s += secs

        jax.monitoring.register_event_listener(on_event)
        jax.monitoring.register_event_duration_secs_listener(on_duration)

    def between(self, t0: float, t1: float) -> dict:
        return {"requests": sum(t0 <= t <= t1 for t in self.requests),
                "misses": sum(t0 <= t <= t1 for t in self.misses)}


def counters(backend, spec: str) -> dict:
    """The program's own counters, read at the window's two edges."""
    from quoracle_tpu.infra.telemetry import SCHED_ADMIT_WAIT_MS
    engine = backend.engines[spec]
    cb = backend.scheduler_stats()[spec]
    _, wait_sum, _ = SCHED_ADMIT_WAIT_MS.counts()
    reg = engine.compiles.snapshot(max_shapes=4096)
    return {"steps": cb["steps"], "chunk": cb["chunk"],
            "real_tokens": cb["padding"]["real_tokens"],
            "padded_tokens": cb["padding"]["padded_tokens"],
            "ticks": cb["padding"]["ticks"],
            "admit_wait_ms_sum": wait_sum,
            "registry_misses": reg["misses"],
            "shapes": {e["shape"]: 1 + e["hits"] for e in reg["shapes"]}}


def client_loop(client, backend, spec: str, engine, log: list, lock,
                stop: threading.Event, t_open: list, drops: list) -> None:
    """One closed-loop client: think, send, wait, note, again. What it
    does BETWEEN turns no latency sees: before its next turn it releases
    the sessions that ended (a traffic mix's `drop`), and the engine's drop
    waits for the lock the batcher holds through a whole tick; `drops`
    takes the seconds of each such call."""
    from quoracle_tpu.models.runtime import QueryRequest
    prev = None
    while not stop.is_set():
        try:
            turn = client.next(prev)
        except IndexError:
            with lock:
                log.append({"ok": False, "error": "script exhausted",
                            "t_done": time.monotonic(), "client": client.name,
                            "completion_tokens": 0, "latency_ms": 0.0})
            return
        for sid in turn.drop:
            t = time.monotonic()
            backend.drop_session(sid)
            drops.append(time.monotonic() - t)
        due = time.monotonic() + turn.think_s
        if stop.wait(max(0.0, due - time.monotonic())):
            return
        t0 = time.monotonic()
        res = backend.query([QueryRequest(
            spec, turn.messages, temperature=turn.temperature, top_p=1.0,
            max_tokens=turn.max_tokens, session_id=turn.session_id,
            constrain_json=False)])[0]
        t1 = time.monotonic()
        row = {"client": client.name, "sid": turn.session_id,
               "t_submit": t0, "t_done": t1, "late_ms": (t0 - due) * 1000,
               "latency_ms": (t1 - t0) * 1000, "ok": res.ok,
               "error": res.error, "max_tokens": turn.max_tokens,
               "temperature": turn.temperature,
               "new_session": turn.new_session,
               "prompt_tokens": res.usage.prompt_tokens,
               "completion_tokens": res.usage.completion_tokens,
               "cached_tokens": res.cached_tokens}
        if res.ok and turn.temperature == 0.0 and t_open and t0 >= t_open[0]:
            # the served token ids, as the engine holds them: the prompt
            # and all but the last of the tokens it generated
            row["ids"] = engine.session_tokens(turn.session_id)
            # and what the caller got must be those tokens' text
            held = engine.tokenizer.decode(
                row["ids"][res.usage.prompt_tokens:]).rstrip("\ufffd")
            row["text_ok"] = (res.text or "").startswith(held)
        with lock:
            log.append(row)
        prev = res


def tick_phase_ms(model: str) -> dict:
    """The batcher worker's time by phase so far, from the program's own
    counter (`quoracle_tick_phase_ms_total`), and when it was read."""
    from quoracle_tpu.infra.telemetry import TICK_PHASE_MS_TOTAL, TICK_PHASES
    return {"t": time.monotonic(),
            "ms": {p: TICK_PHASE_MS_TOTAL.value(model=model, phase=p)
                   for p in TICK_PHASES}}


def worker_time(a: dict, b: dict) -> dict:
    """Between two readings of `tick_phase_ms`: the worker's time waiting
    for the device, idle, and in host work, by kind of phase."""
    d = {p: b["ms"][p] - a["ms"][p] for p in a["ms"]}
    device = d["wait_prefill"] + d["wait_decode"]
    return {"seconds": round(b["t"] - a["t"], 3),
            "device_wait_ms": round(device, 1),
            "idle_ms": round(d["idle"], 1),
            "host_ms": round(sum(d.values()) - device - d["idle"], 1),
            "host_ms_by_phase": {p: round(v, 2) for p, v in d.items()
                                 if v and not p.startswith("wait_")
                                 and p != "idle"}}


def drive(rt, spec: str, cell: dict, mix: dict, args, clog: CompileLog,
          warm: dict) -> dict:
    """Warm-up, lead-in, window; returns what the window saw."""
    import jax
    backend = rt.backend
    engine = backend.engines[spec]
    gen = traffic.load_generator(mix["kind"])
    text = traffic.SeededText(engine.tokenizer, args.seed)
    t_gen = time.monotonic()
    lead_s = float(mix.get("lead_s", 0.0))
    n_turns = int((args.seconds + lead_s + 5.0)
                  * float(mix["max_turns_per_client_per_s"])) + 4
    clients = gen.build(mix["params"], args.seed, n_turns, text)
    say("traffic", {"kind": mix["kind"], "clients": len(clients),
                    "turns_generated_per_client": n_turns,
                    "generate_s": round(time.monotonic() - t_gen, 2)})

    from benchmark.warmup import Warmer
    t_w = time.monotonic()
    report = Warmer(engine, args.seed, int(warm["budget"]),
                    backend.scheduler_stats()[spec]["max_slots"]).warm(
        [tuple(k) for k in warm["keys"]])
    say("warm", {**report, "seconds": round(time.monotonic() - t_w, 1),
                 "compile_s": round(clog.compile_s, 1),
                 "cache_hits": clog.hits, "cache_misses": len(clog.misses)})

    log: list = []
    lock = threading.Lock()
    stop = threading.Event()
    t_open: list = []
    drops: list = []
    threads = [threading.Thread(
        target=client_loop, name=c.name, daemon=True,
        args=(c, backend, spec, engine, log, lock, stop, t_open, drops))
        for c in clients]
    for th in threads:
        th.start()
    time.sleep(lead_s)

    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(OUT_DIR, f"trace-{cell['name']}")
        shutil.rmtree(trace_dir, ignore_errors=True)
    before = counters(backend, spec)
    t0 = time.monotonic()
    t_open.append(t0)
    say("window", {"opens_after_s": round(t0 - T_START, 2)})
    if trace_dir:
        time.sleep(min(TRACE_LEAD_SECONDS, args.seconds / 4))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0       # the device and the runtime's
        opts.host_tracer_level = 1         # own threads, not every frame
        # what the session costs: the worker's time by phase while it is
        # open, and over as long a stretch of the same window right after
        marks = [tick_phase_ms(engine.cfg.name)]
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        time.sleep(min(TRACE_SECONDS, args.seconds / 2))
        jax.profiler.stop_trace()
        marks.append(tick_phase_ms(engine.cfg.name))
        time.sleep(max(0.0, min(marks[1]["t"] - marks[0]["t"],
                                t0 + args.seconds - time.monotonic())))
        marks.append(tick_phase_ms(engine.cfg.name))
        say("tracing", {"session_open": worker_time(marks[0], marks[1]),
                        "right_after": worker_time(marks[1], marks[2])})
    time.sleep(max(0.0, t0 + args.seconds - time.monotonic()))
    t1 = time.monotonic()
    after = counters(backend, spec)
    quant = engine.quant_stats()
    stop.set()
    for th in threads:
        th.join(timeout=120)
    alive = [th.name for th in threads if th.is_alive()]
    mem = [d.memory_stats() or {} for d in jax.devices()]
    say("drops", {"calls": len(drops), "total_s": round(sum(drops), 3),
                  "max_ms": round(1000 * max(drops, default=0.0), 1),
                  "over_100_ms": sum(t > 0.1 for t in drops),
                  "note": "whole run, lead-in included"})
    return {"log": log, "t0": t0, "t1": t1, "before": before, "after": after,
            "trace_dir": trace_dir, "alive": alive,
            "warm_missed": report["missed"], "quant": quant,
            "peak_bytes": max((m.get("peak_bytes_in_use", 0) for m in mem),
                              default=0),
            "compiles": clog.between(t0, t1)}


def check_window(family, raw_cfg: dict, mix: dict, seen: dict) -> list:
    """The conditions on the window's rows that are part of `correct`:
    (name, value, limit, passed)."""
    rows = stats.in_window(seen["log"], seen["t0"], seen["t1"])
    ok = [r for r in rows if r["ok"]]
    checks = [("rows_failed", len(rows) - len(ok), 0,
               len(rows) == len(ok) and bool(rows)),
              ("clients_still_running", len(seen["alive"]), 0,
               not seen["alive"])]
    full = sum(r["completion_tokens"] == r["max_tokens"] for r in ok)
    share = full / len(ok) if ok else 0.0
    limit = float(mix["checks"]["full_length_share_min"])
    checks.append(("full_length_share", share, limit, share >= limit))
    n = sum(r.get("text_ok") is False for r in ok)
    checks.append(("rows_whose_text_is_not_their_tokens", n, 0, n == 0))
    if mix["checks"].get("cached_tokens_zero"):
        n = sum(r["cached_tokens"] != 0 for r in ok)
        checks.append(("rows_with_cached_tokens", n, 0, n == 0))
    # the precision the configuration states is the precision served: the
    # engine's own account of what a resident token costs, key by key
    # against what the family reckons from the configuration's shapes at
    # its stated type, and no quantized weights. (A path as quiet as int8
    # KV pages is beneath what the comparison of served tokens below can
    # tell from bfloat16's own rounding: PERF.md.)
    for key, stated in family.stated_precision(raw_cfg).items():
        n = seen["quant"][key]
        checks.append((key, n, stated, n == stated))
    n = int(bool(seen["quant"]["quantize_weights"]))
    checks.append(("weights_quantized", n, 0, n == 0))
    # nothing compiles inside the window: a program key that warm-up did
    # not land on, or any program JAX was asked for while it was open,
    # would be timed as if it were serving
    n = len(seen["warm_missed"])
    checks.append(("warm_keys_missed", n, 0, n == 0))
    n = seen["compiles"]["requests"]
    checks.append(("programs_asked_for_in_window", n, 0, n == 0))
    return checks


def check_reference(family, raw_cfg: dict, limits: dict, seen: dict,
                    seed: int, detail: dict | None = None) -> list:
    """A seeded sample of the window's own greedy rows, the longest among
    them, through the family's plain reference: the widest and the mean gap
    by which a served token's logit lies below the reference's best.
    `detail`, the builder's (benchmark/control.py), is filled with the
    reference, its logits and each row's gaps, for the readings a limit is
    set from."""
    import numpy as np
    from benchmark import draws
    from benchmark.reference import gaps_of, served_logits
    rows = [r for r in stats.in_window(seen["log"], seen["t0"], seen["t1"])
            if r.get("ids") and len(r["ids"]) > r["prompt_tokens"]]
    want = int(limits["reference_rows"])
    if not rows:
        return [("reference_rows_compared", 0, 1, False)]
    rows.sort(key=lambda r: (len(r["ids"]), r["t_done"]))
    longest = rows.pop()
    order = draws.permutation(seed, "reference-sample", len(rows))
    sample = [longest] + [rows[i] for i in order[:want - 1]]
    # one padded length for the cell, so that the reference compiles once
    pad_to = max(int(limits["reference_pad_to"]),
                 -(-max(len(r["ids"]) for r in sample) // 512) * 512)
    t = time.monotonic()
    ref = family.Reference(raw_cfg, seed)
    logits = [served_logits(ref, r["ids"], r["prompt_tokens"], pad_to)
              for r in sample]
    gaps = [gaps_of(lg, np.asarray(r["ids"][r["prompt_tokens"]:]))
            for lg, r in zip(logits, sample)]
    for r, g in zip(sample, gaps):
        say("reference", {"sid": r["sid"], "context": len(r["ids"]),
                          "served_tokens": len(g), "gap": float(g.max()),
                          "tokens_off_the_best": int((g > 0).sum())})
    if detail is not None:
        detail.update(ref=ref, logits=logits, sample=sample, pad_to=pad_to,
                      gaps=gaps)
    gaps = np.concatenate(gaps)
    say("reference", {"rows": len(sample), "served_tokens": len(gaps),
                      "tokens_off_the_best": int((gaps > 0).sum()),
                      "padded_to": pad_to,
                      "seconds": round(time.monotonic() - t, 1)})
    out = [("reference_rows_compared", len(sample), 1, True)]
    for name, value in (("reference_gap", float(gaps.max())),
                        ("reference_gap_mean", float(gaps.mean()))):
        limit = float(limits[f"{name}_max"])
        out.append((name, value, limit, value <= limit))
    return out


def free_program(rt) -> None:
    """The program is done: delete what it holds on the devices, so that
    the reference has the chip and the peak read before stays the
    program's."""
    import gc
    import jax
    rt.backend.engines.clear()
    gc.collect()
    for a in jax.live_arrays():
        a.delete()


async def serve_and_drive(family, cell, mix, raw_cfg, args, clog, warm,
                          more_serve_args=()) -> dict:
    from quoracle_tpu import cli
    spec = family.register(raw_cfg)
    # `cli serve` has no --seed: the weights and the sampler take the
    # Runtime's seed, which the flags never set. Bind it here, through
    # RuntimeConfig's own field, so that --seed makes the weights too.
    cli.RuntimeConfig = functools.partial(cli.RuntimeConfig, seed=args.seed)
    argv = (["serve", "--backend", "tpu", "--continuous", "--pool", spec,
             "--port", "0"] + list(raw_cfg.get("serve_args", []))
            + list(more_serve_args))
    say("start", "python -m quoracle_tpu.cli " + " ".join(argv))
    rt, server = await cli.start_server(cli.build_parser().parse_args(argv))
    if rt is None:
        raise RuntimeError("the dashboard refused to bind")
    say("start", {"server_up_after_s": round(time.monotonic() - T_START, 2)})
    try:
        seen = await asyncio.to_thread(drive, rt, spec, cell, mix, args,
                                       clog, warm)
    finally:
        await server.stop()
        await rt.shutdown()
    free_program(rt)
    return seen


def run(args, more_serve_args=(), detail: dict | None = None,
        root: str = HERE) -> int:
    """One run of one cell. `more_serve_args` and `detail` are the
    builder's (benchmark/control.py): the program's lower-precision flag,
    and a place for what the output check read. `root` is a test's: where
    the benchmark's data files and families are looked for first
    (`configs.find`)."""
    real, tiny, end_to_end = load_cells(root)
    cell = real.get(args.workload) or tiny.get(args.workload)
    if cell is None:
        print(f"benchmark: unknown workload {args.workload!r}; cells: "
              f"{sorted(real)}; rehearsal: {sorted(tiny)}", file=sys.stderr)
        return 2
    raw_cfg = configs.load_config(cell["config"], root)
    for key, value in raw_cfg.get("env", {}).items():
        # rehearsal configurations only: a file of the benchmark's own
        # that the program reads through its environment
        os.environ[key] = os.path.join(ROOT, value)
    try:
        import jax
        from quoracle_tpu.utils.compile_cache import enable_compilation_cache
    except ImportError as e:
        print(f"benchmark: the program is not beside it: {e}",
              file=sys.stderr)
        return 2
    cache_dir = enable_compilation_cache()
    clog = CompileLog()
    clog.install()
    devs = jax.devices()
    on_tpu = devs[0].platform == "tpu"
    if args.workload in real and not on_tpu:
        print(f"benchmark: no accelerator — jax found platform "
              f"{devs[0].platform!r}; off the TPU only the rehearsal cells "
              f"run: {sorted(tiny)}", file=sys.stderr)
        return 2
    if on_tpu and len(devs) != int(cell["chips"]):
        print(f"benchmark: cell {cell['name']} asks for {cell['chips']} "
              f"chip(s), jax found {len(devs)}", file=sys.stderr)
        return 2
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)["devices"]
    if on_tpu and devs[0].device_kind not in peaks:
        print(f"benchmark: no peaks for device kind "
              f"{devs[0].device_kind!r} in benchmark/peaks.json",
              file=sys.stderr)
        return 2
    family = configs.family(raw_cfg, root)
    mix = traffic.load_traffic(cell["traffic"], root)
    # what belongs to the cell and not to its mix: the program keys to warm
    # and, where the cell's readings differ from the mix's, its own limits
    warm = configs.load_json(root, "warm", f"{cell['name']}.json")
    mix["checks"] = {**mix["checks"], **warm.get("checks", {})}
    say("cell", {"name": cell["name"], "seed": args.seed,
                 "seconds": args.seconds, "trace": args.trace,
                 "compile_cache": cache_dir,
                 "device": [devs[0].platform, devs[0].device_kind,
                            len(devs)]})
    os.makedirs(OUT_DIR, exist_ok=True)

    seen = asyncio.run(serve_and_drive(family, cell, mix, raw_cfg, args,
                                       clog, warm, more_serve_args))
    t0, t1 = seen["t0"], seen["t1"]
    e2e = stats.end_to_end(seen["log"], t0, t1)
    rows = stats.in_window(seen["log"], t0, t1)
    ok = [r for r in rows if r["ok"]]
    say("window", {
        "seconds": round(t1 - t0, 3), **{k: e2e[k] for k in (
            "attempted", "failed", "samples")},
        "highest_supported_percentile":
            e2e.get("highest_supported_percentile"),
        "latency_at_it_ms": e2e.get("latency_at_highest_supported_ms"),
        "turn_latency_p95_ms": e2e.get("turn_latency_p95_ms"),
        "latency_max_ms": max((r["latency_ms"] for r in ok), default=None),
        "new_sessions": sum(r.get("new_session", False) for r in ok),
        "greedy_rows": sum(r.get("temperature") == 0.0 for r in ok),
        "prompt_tokens": [min((r["prompt_tokens"] for r in ok), default=0),
                          max((r["prompt_tokens"] for r in ok), default=0)],
        "prompt_tokens_sum": sum(r["prompt_tokens"] for r in ok),
        "cached_tokens_sum": sum(r["cached_tokens"] for r in ok),
        "completion_tokens_sum": sum(r["completion_tokens"] for r in ok),
        "finished_turns_tokens_per_s": e2e["finished_turns_tokens_per_s"],
        "stopped_early": sum(r["completion_tokens"] < r["max_tokens"]
                             for r in ok),
        "generator_late_ms_max": max((r["late_ms"] for r in ok), default=0),
        "compiles_in_window": seen["compiles"],
        "errors": sorted({str(r.get("error")) for r in rows
                          if not r["ok"]})[:5]})
    new_shapes = sorted(set(seen["after"]["shapes"])
                        - set(seen["before"]["shapes"]))
    say("shapes", {"in_window": {
        k: v - seen["before"]["shapes"].get(k, 0)
        for k, v in seen["after"]["shapes"].items()
        if v != seen["before"]["shapes"].get(k, 0)},
        "first_seen_in_window": new_shapes})

    checks = check_window(family, raw_cfg, mix, seen)
    checks += check_reference(family, raw_cfg, mix["checks"], seen,
                              args.seed, detail)
    said = [{"name": name, "value": value, "limit": limit, "passed": passed}
            for name, value, limit, passed in checks]
    for c in said:
        say("check", c)
    correct = all(c[3] for c in checks)
    if detail is not None:
        detail["checks"] = checks
        detail["window"] = {"seconds": t1 - t0, "rows": [
            {"t_submit": r["t_submit"] - t0, "t_done": r["t_done"] - t0,
             "completion_tokens": r["completion_tokens"], "ok": r["ok"]}
            for r in seen["log"] if "t_submit" in r]}

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": seen["peak_bytes"]}
    metrics: dict = {}
    breakdown = None
    if not args.trace:
        values = {"setup_s": t0 - T_START,
                  "peak_hbm_gib": seen["peak_bytes"] / 2 ** 30,
                  **{k: v for k, v in e2e.items() if k in (
                      "turn_latency_p50_ms", "output_tokens_per_s")}}
        # every end-to-end metric of BENCHMARK.json but those the cell's
        # traffic file leaves out, with the reason beside them there
        omit = set(mix.get("end_to_end_omit", {}))
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in end_to_end
                   if m["name"] in values and m["name"] not in omit}
    else:
        from benchmark import trace_reduce
        reduced = trace_reduce.reduce(trace_reduce.load(
            trace_reduce.find_xplane(seen["trace_dir"])), len(devs))
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        breakdown = trace_reduce.breakdown(reduced)
        ctx = {"rows": rows, "ok": ok, "before": seen["before"],
               "after": seen["after"], "compiles": seen["compiles"],
               "trace": reduced, "config": raw_cfg, "family": family,
               "mix": mix,
               "peaks": peaks.get(devs[0].device_kind),
               "seconds": t1 - t0}
        for name in per_layer_names(mix, raw_cfg):
            m = configs.load_json(root, "metrics", f"{name}.json")
            reader = importlib.import_module(
                f"benchmark.readers.{m['reader']}")
            value = reader.read(ctx, m)
            if value is not None:
                metrics[name] = {"value": value, "unit": m["unit"]}
    line = {"correct": correct, "attempted": e2e["attempted"],
            "failed": e2e["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    # each number compared beside its limit: last in the result line, and
    # the last lines of stderr
    line["checks"] = {name: {"value": value, "limit": limit}
                      for name, value, limit, _ in checks}
    print(json.dumps(line, default=float), flush=True)
    for c in said:
        print("[check] " + json.dumps(c, default=str), file=sys.stderr,
              flush=True)
    return 0


def parser(description: str) -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=description)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap


def main(argv=None, root: str = HERE) -> int:
    return run(parser(__doc__.split("\n\n")[0]).parse_args(argv), root=root)


if __name__ == "__main__":
    sys.exit(main())
