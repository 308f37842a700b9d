"""Driver benchmark: consensus-round latency + tokens/sec/chip on TPU,
measured through the PRODUCTION serving stack.

What runs (nothing stubbed — VERDICT r2 item 1):
  real HF-format checkpoints (generated locally at 1b scale on first run,
  models/make_checkpoint.py) → models/loader.py → each checkpoint's own
  trained BPE tokenizer + chat template (HFAutoTokenizer) → TPUBackend
  (models/runtime.py) with KV session residency ON, grammar-constrained
  JSON decoding ON, and production overlap semantics.

Each measured cycle simulates one agent turn the way the consensus engine
drives it (consensus/engine.py): round 1 proposes from the full system
prompt + task; rounds 2-3 are refinement rounds whose prompts EXTEND the
prior conversation — with sessions on, only the new suffix prefills
(SURVEY §7 hard part 2). Three configs from BASELINE.md are measured:

  config 1 — 1-model pool, single agent turn (3 rounds)
  config 2 — 3-model consensus pool, single agent turn (3 rounds)  [headline]
  config 3 — 3 agents deciding concurrently, 3-model pool, one round each
             (rows batch per pool member)
  config 4 — embedding + retrieval (LessonManager shape): embed new lessons
             on-device and cosine-search a stored lesson matrix
  config 5 — vision: a VLM checkpoint (ViT tower + soft-token splice) joins
             the pool and every round's task carries an image part
  config 6 — decode-level continuous batching (models/scheduler.py): 6
             agents with STAGGERED arrivals ride one member's shared
             chunked decode loop; rows join/leave at chunk boundaries
             instead of waiting for whole rounds (VERDICT r4 item 4 —
             target: tokens/sec ≥ 2.5× config 1 at p50 ≤ 1.5× config 1)

``vs_baseline`` divides the estimated hosted-API 3-model round p50 by the
measured config-2 p50. The estimate is DERIVED in BASELINE.md (per-call
latency model: TTFT + tokens/decode-rate, slowest-of-3), not published by
the reference — it publishes no numbers at all (BASELINE.md).

Prints exactly ONE JSON line on stdout; diagnostics go to stderr.

ONE process holds the chip: the bench initialises JAX itself, and without
``--smoke`` it refuses to run on anything but a TPU — a CPU number under a
device metric's name is worse than no number. Every config is measured
under a deadline with per-config exception capture, so a failure still
prints the one parseable JSON line with whatever was measured and the
`error` field set — and then exits non-zero: a dead run and a clean one
must not look alike to the driver.
"""

from __future__ import annotations

import glob
import json
import os
import signal
import statistics
import sys
import time

# BASELINE.md "Hosted-API comparison point": slowest-of-3 hosted calls for
# 128 output tokens ≈ TTFT 0.8 s + 128 tok / 32 tok/s = 4.8 s ≈ 5000 ms.
HOSTED_BASELINE_MS = 5000.0
SCALE = "1b"
FAMILIES = ["llama", "mistral", "gemma"]
MAX_NEW = 128
N_CYCLES = 4          # measured agent turns per config (plus 1 warmup)
ROUNDS_PER_CYCLE = 3  # initial + 2 refinement rounds

# Public HBM-bandwidth and bf16-FLOPs specs per device generation — the
# decode (bandwidth) and prefill (compute) rooflines. Most-specific key
# first (matched by substring of device_kind).
PEAK_HBM_GBPS = {"TPU v5 lite": 819.0, "TPU v5e": 819.0, "TPU v5p": 2765.0,
                 "TPU v6 lite": 1640.0, "TPU v6e": 1640.0, "TPU v4": 1228.0}
PEAK_BF16_TFLOPS = {"TPU v5 lite": 197.0, "TPU v5e": 197.0,
                    "TPU v5p": 459.0, "TPU v6 lite": 918.0,
                    "TPU v6e": 918.0, "TPU v4": 275.0}

TASKS = [
    "Survey the repository layout and report the three largest source files "
    "to your parent agent.",
    "A child agent reported test failures in tests/test_io.py; decide how "
    "to investigate.",
    "The budget snapshot shows 80% spent; re-plan the remaining work.",
    "Summarize progress so far and message your parent with a status update.",
    "Two children disagree about the deployment order; resolve it.",
]
SYSTEM_PROMPT = (
    "You are an autonomous agent in a recursive agent tree. "
    "Decide your next action. Respond ONLY with a JSON object "
    '{"action": ..., "params": {...}, "reasoning": ..., '
    '"wait": false}. Available actions: send_message, todo, wait, '
    "orient, spawn_child, execute_shell, file_read, file_write, "
    "fetch_web, call_api, batch_sync, dismiss_child.")
REFINEMENTS = [
    "Consensus was not reached. Other models proposed different actions. "
    "Review your proposal as a skeptical reviewer and respond with your "
    "(possibly revised) complete JSON action.",
    "Still no consensus after refinement. State your final choice as a "
    "complete, self-contained JSON action object.",
]


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class HistWindow:
    """Histogram count-delta window — the shared idiom behind configs
    9/10/13/14/21/23: snapshot a telemetry histogram's cumulative bucket
    counts at construction, run the measured region, then read quantiles
    over JUST the window's observations. The artifact reports exactly
    what GET /metrics scrapes over the window — never a parallel
    wall-clock estimate."""

    def __init__(self, hist, **labels):
        self.hist = hist
        self.labels = labels
        self._c0 = hist.counts(**labels)[0]

    def delta(self) -> list:
        c1 = self.hist.counts(**self.labels)[0]
        return [a - b for a, b in zip(c1, self._c0)]

    def n(self) -> int:
        return sum(self.delta())

    def quantile(self, p: float, ndigits: int = 1):
        """Window quantile, or None while the window saw nothing."""
        from quoracle_tpu.infra.telemetry import quantile
        delta = self.delta()
        if not sum(delta):
            return None
        v = quantile(self.hist.buckets, delta, p)
        return round(v, ndigits) if v is not None else None


# ---------------------------------------------------------------------------
# Deadline
# ---------------------------------------------------------------------------

class BenchDeadline(Exception):
    """Raised (via SIGALRM) when the hard wall-clock backstop fires."""


def ensure_checkpoints(families=None) -> list[str]:
    from quoracle_tpu.models.make_checkpoint import make_bench_checkpoints
    root = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "checkpoints")
    t0 = time.monotonic()
    dirs = make_bench_checkpoints(root, scale=SCALE,
                                  families=families or FAMILIES)
    log(f"checkpoints ready in {time.monotonic() - t0:.1f}s: {dirs}")
    return dirs


def bench_image_b64() -> str:
    """A deterministic in-memory PNG for the vision config (no asset files;
    the C++ decode/resize path still runs on it)."""
    import base64

    import numpy as np

    from quoracle_tpu.models.images import write_png
    rng = np.random.default_rng(7)
    w = h = 224
    # structured, not pure noise: gradients + blocks so resize/normalize do
    # real work
    y, x = np.mgrid[0:h, 0:w]
    img = np.stack([(x * 255 / w), (y * 255 / h),
                    ((x // 32 + y // 32) % 2) * 255], axis=-1)
    img = (img + rng.integers(0, 32, img.shape)).clip(0, 255).astype(np.uint8)
    import tempfile
    with tempfile.NamedTemporaryFile(suffix=".png") as f:
        write_png(f.name, img.tobytes(), w, h)
        f.seek(0)
        return base64.b64encode(f.read()).decode()


def run_cycle(backend, pool, session_prefix: str, task: str,
              n_agents: int = 1, rounds: int = ROUNDS_PER_CYCLE,
              image_b64: str = None):
    """One simulated agent turn: initial round + refinement rounds that
    extend each member's own conversation (consensus/engine.py shape).
    Returns per-round stats dicts."""
    from quoracle_tpu.consensus.temperature import temperature_for_round
    from quoracle_tpu.models.runtime import QueryRequest

    system = SYSTEM_PROMPT
    # per (agent, member) conversation, as the consensus engine keeps them.
    # With an image, the task message is multimodal: VLM members splice the
    # ViT soft tokens, text members see the stringified "[image]" marker —
    # the same message set serves the whole pool (runtime._encode_multimodal).
    task_content = ([{"type": "text", "text": task},
                     {"type": "image_base64", "data": image_b64}]
                    if image_b64 else task)
    convs = {(a, m): [{"role": "system", "content": system},
                      {"role": "user", "content": task_content}]
             for a in range(n_agents) for m in pool}
    stats = []
    for rnd in range(1, rounds + 1):
        reqs, keys = [], []
        for a in range(n_agents):
            for m in pool:
                reqs.append(QueryRequest(
                    model_spec=m, messages=convs[(a, m)],
                    temperature=temperature_for_round(m.split(":")[1], rnd),
                    top_p=0.95, max_tokens=MAX_NEW,
                    session_id=f"{session_prefix}-a{a}",
                    constrain_json=True))
                keys.append((a, m))
        t0 = time.monotonic()
        results = backend.query(reqs)
        wall_ms = (time.monotonic() - t0) * 1000.0
        gen_tokens = sum(r.usage.completion_tokens for r in results)
        prompt_tokens = sum(r.usage.prompt_tokens for r in results)
        engines = [backend.engines[m] for m in pool]   # active members only
        prefill_tokens = sum(e.last_prefill_tokens for e in engines)
        prefill_s = sum(e.last_prefill_s for e in engines)
        decode_s = sum(e.last_decode_s for e in engines)
        for r in results:
            assert r.ok, f"round {rnd} failed: {r.error}"
        stats.append({
            "round": rnd, "wall_ms": wall_ms, "gen_tokens": gen_tokens,
            "prompt_tokens": prompt_tokens, "prefill_tokens": prefill_tokens,
            "prefill_s": prefill_s, "decode_s": decode_s,
        })
        for (a, m), r in zip(keys, results):
            convs[(a, m)] = convs[(a, m)] + [
                {"role": "assistant", "content": r.text},
                {"role": "user", "content": REFINEMENTS[min(rnd - 1,
                                                            len(REFINEMENTS) - 1)]},
            ]
    return stats


def measure_config(backend, pool, name: str, n_agents: int = 1,
                   rounds: int = ROUNDS_PER_CYCLE,
                   image_b64: str = None) -> dict:
    all_rounds = []
    t_all = time.monotonic()
    for c in range(N_CYCLES):
        task = TASKS[c % len(TASKS)]
        rs = run_cycle(backend, pool, f"{name}-c{c}", task,
                       n_agents=n_agents, rounds=rounds,
                       image_b64=image_b64)
        all_rounds.extend(rs)
        log(f"{name} cycle {c}: " + "  ".join(
            f"r{s['round']} {s['wall_ms']:.0f}ms"
            f" (prefill {s['prefill_tokens']}tok)" for s in rs))
    wall = time.monotonic() - t_all
    lat = [s["wall_ms"] for s in all_rounds]
    r1 = [s["wall_ms"] for s in all_rounds if s["round"] == 1]
    rn = [s["wall_ms"] for s in all_rounds if s["round"] > 1]
    gen = sum(s["gen_tokens"] for s in all_rounds)
    # Steady-state throughput: median round's tokens over the p50 round
    # latency. The wall-based number below it includes one-off XLA
    # recompiles when a growing conversation crosses a shape bucket —
    # real, but a warmup artifact that vanishes in steady serving.
    med_tokens = statistics.median(s["gen_tokens"] for s in all_rounds)
    steady_tps = med_tokens / (statistics.median(lat) / 1000.0)
    return {
        "rounds": all_rounds,
        "steady_tokens_per_sec": steady_tps,
        "p50_round_ms": statistics.median(lat),
        "p50_round1_ms": statistics.median(r1),
        "p50_refine_ms": statistics.median(rn) if rn else None,
        "gen_tokens": gen,
        "wall_s": wall,
        "tokens_per_sec": gen / wall,
        "prefill_s": sum(s["prefill_s"] for s in all_rounds),
        "decode_s": sum(s["decode_s"] for s in all_rounds),
        "prefill_tokens": sum(s["prefill_tokens"] for s in all_rounds),
        "prompt_tokens": sum(s["prompt_tokens"] for s in all_rounds),
    }


def measure_continuous(backend_cont, member: str, n_agents: int = 6,
                       rounds: int = ROUNDS_PER_CYCLE,
                       stagger_s: float = 0.05) -> dict:
    """Config 6: ``n_agents`` independent agents, each running one
    ``rounds``-round cycle against ONE pool member, arrivals staggered so
    rows genuinely join decodes already in flight. backend_cont must have
    continuous=True; phase stats are meaningless under sharing, so only
    wall/latency/token numbers are reported."""
    from concurrent.futures import ThreadPoolExecutor

    def one_agent(prefix: str, a: int) -> list[dict]:
        return run_cycle(backend_cont, [member], f"{prefix}{a}",
                         TASKS[a % len(TASKS)], rounds=rounds)

    # warmup: compile the chunk-decode buckets for every batch size the
    # staggered run will hit (B grows 1→n_agents as rows join). DISTINCT
    # session prefix from the measured pass — reusing ids would serve the
    # measured round-1 prefills from warmup-resident KV and bias the
    # config6-vs-config1 acceptance ratios.
    with ThreadPoolExecutor(n_agents) as ex:
        futs = []
        for a in range(n_agents):
            futs.append(ex.submit(one_agent, "cont-w", a))
            time.sleep(stagger_s)
        for f in futs:
            f.result()
    t_all = time.monotonic()
    with ThreadPoolExecutor(n_agents) as ex:
        futs = []
        for a in range(n_agents):
            futs.append(ex.submit(one_agent, "cont-a", a))
            time.sleep(stagger_s)
        stats = [s for f in futs for s in f.result()]
    wall = time.monotonic() - t_all
    lat = [s["wall_ms"] for s in stats]
    gen = sum(s["gen_tokens"] for s in stats)
    return {
        "n_agents": n_agents,
        "p50_round_ms": statistics.median(lat),
        "p90_round_ms": sorted(lat)[int(0.9 * (len(lat) - 1))],
        "gen_tokens": gen,
        "wall_s": wall,
        "tokens_per_sec": gen / wall,
    }


def measure_embed_retrieval(backend) -> dict:
    """Config 4: the LessonManager / skills-retrieval shape
    (context/lessons.py; reference agent AGENTS.md lesson dedup): embed a
    batch of new lesson texts on the on-device encoder and cosine-search a
    stored lesson matrix (100 lessons/model is the reference's prune
    bound). Measures the consensus-critical-path embedding latency —
    semantic-similarity merge rules call this during clustering
    (SURVEY §7 hard part 6)."""
    import numpy as np
    store_texts = [
        f"Lesson {i}: when {t.lower()} fails, prefer retrying with a "
        f"narrower scope and report the delta to the parent."
        for i, t in enumerate(TASKS * 20)
    ][:100]
    queries = [
        "The shell command timed out; what did we learn about retries?",
        "Parent asked for a status update format.",
        "Deployment order disagreements between children.",
        "Budget overruns near the end of a task.",
        "Which files matter most in this repository?",
        "How to investigate test failures effectively.",
        "When to spawn a child vs do the work inline.",
        "Compressing long histories without losing decisions.",
    ]
    t0 = time.monotonic()
    M = np.stack(backend.embed(store_texts))
    M /= np.linalg.norm(M, axis=1, keepdims=True) + 1e-9
    build_s = time.monotonic() - t0
    lats = []
    for it in range(1 + N_CYCLES):          # first iteration = warmup
        # unique per iteration: the encoder's SHA-keyed TTL cache would
        # otherwise serve repeats host-side and measure nothing
        qs = [f"[turn {it}] {q}" for q in queries]
        t0 = time.monotonic()
        q = np.stack(backend.embed(qs))
        q /= np.linalg.norm(q, axis=1, keepdims=True) + 1e-9
        sims = q @ M.T
        top = np.argsort(-sims, axis=1)[:, :5]
        assert top.shape == (len(queries), 5)
        lats.append((time.monotonic() - t0) * 1000.0)
    lats = lats[1:]
    return {
        "p50_embed_retrieve_ms": statistics.median(lats),
        "store_size": len(store_texts),
        "queries_per_batch": len(queries),
        "store_build_s": build_s,
        "texts_per_sec": len(queries) / (statistics.median(lats) / 1000.0),
    }


def measure_consensus_telemetry(backend, pool,
                                n_decides: int = N_CYCLES) -> dict:
    """Config 9: ``n_decides`` REAL ConsensusEngine.decide calls over the
    full pool. Round and decide latency quantiles come from the telemetry
    histograms' count deltas around the measured window
    (infra/telemetry.py quantile over quoracle_round_ms /
    quoracle_decide_ms) — NOT from wall-clock diffs — so the artifact
    reports exactly what GET /metrics scrapes. Per-decide rows carry the
    prefill/decode decomposition (ConsensusOutcome.prefill_ms/decode_ms)."""
    from quoracle_tpu.consensus.engine import ConsensusConfig, ConsensusEngine
    from quoracle_tpu.infra.telemetry import DECIDE_MS, ROUND_MS

    eng = ConsensusEngine(backend, ConsensusConfig(
        model_pool=list(pool), session_key="bench-config9"))
    rwin, dwin = HistWindow(ROUND_MS), HistWindow(DECIDE_MS)
    rows = []
    for i in range(n_decides):
        msgs = {m: [{"role": "system", "content": SYSTEM_PROMPT},
                    {"role": "user",
                     "content": TASKS[i % len(TASKS)]}] for m in pool}
        out = eng.decide(msgs)
        rows.append({"status": out.status, "rounds": out.rounds_used,
                     "latency_ms": round(out.latency_ms, 1),
                     "prefill_ms": round(out.prefill_ms, 1),
                     "decode_ms": round(out.decode_ms, 1),
                     "cached_tokens": out.cached_tokens})
        log(f"config9 decide {i}: {rows[-1]}")
    return {
        "rows": rows,
        "n_decides": n_decides,
        "n_rounds": rwin.n(),
        "round_p50_ms": rwin.quantile(0.50),
        "round_p95_ms": rwin.quantile(0.95),
        "decide_p50_ms": dwin.quantile(0.50),
        "decide_p95_ms": dwin.quantile(0.95),
        "prefill_ms_total": round(sum(r["prefill_ms"] for r in rows), 1),
        "decode_ms_total": round(sum(r["decode_ms"] for r in rows), 1),
    }


def measure_resource_observability(backend, pool,
                                   n_decides: int = N_CYCLES) -> dict:
    """Config 10: resource observability (ISSUE 3) under a SUSTAINED
    consensus load — ``n_decides`` real ConsensusEngine.decide calls run
    through a continuous-batching dispatch layer (shared engines, only
    the scheduler changes — same shape as config 6) while a sampler
    thread polls live device memory (infra/resources.py) and scheduler
    queue health at ~4 Hz. Reported: minimum HBM headroom seen during
    the load, compile-registry hit rate (models/generate.py
    CompileRegistry), queue-depth p95 over the samples, and the
    admission-wait p95 from the quoracle_sched_admit_wait_ms histogram
    COUNT DELTAS (the same numbers GET /metrics scrapes). With
    QUORACLE_BENCH_RESOURCES set, the full sample timeline is written
    there as a sidecar artifact."""
    import threading

    from quoracle_tpu.consensus.engine import ConsensusConfig, ConsensusEngine
    from quoracle_tpu.infra import resources as res
    from quoracle_tpu.infra.telemetry import (
        SCHED_ADMIT_WAIT_MS, WATCHDOG_STALLS,
    )
    from quoracle_tpu.models.runtime import TPUBackend

    backend10 = TPUBackend(pool, engines=backend.engines,
                           embedder=backend.embedder, continuous=True)
    samples: list[dict] = []
    stop = threading.Event()

    def sampler():
        while not stop.is_set():
            devs = res.device_memory_stats()
            sched = backend10.scheduler_stats()
            samples.append({
                "ts": round(time.time(), 3),
                "headroom_frac": res.headroom_fraction(devs),
                "bytes_in_use": sum(d["bytes_in_use"] for d in devs),
                "queue_depth": sum(s["queued"] for s in sched.values()),
                "live_rows": sum(s["live"] for s in sched.values()),
            })
            stop.wait(0.25)

    awin = HistWindow(SCHED_ADMIT_WAIT_MS)
    th = threading.Thread(target=sampler, daemon=True)
    th.start()
    eng = ConsensusEngine(backend10, ConsensusConfig(
        model_pool=list(pool), session_key="bench-config10"))
    try:
        for i in range(n_decides):
            msgs = {m: [{"role": "system", "content": SYSTEM_PROMPT},
                        {"role": "user",
                         "content": TASKS[(i + 2) % len(TASKS)]}]
                    for m in pool}
            out = eng.decide(msgs)
            log(f"config10 decide {i}: status={out.status} "
                f"rounds={out.rounds_used}")
    finally:
        stop.set()
        th.join(5)
        for cb in backend10._cbatchers.values():
            cb.close()
    admit_p95 = awin.quantile(0.95, ndigits=2)

    comp = {spec: backend.engines[spec].compiles.snapshot()
            for spec in pool}
    hits = sum(c["hits"] for c in comp.values())
    misses = sum(c["misses"] for c in comp.values())
    headrooms = [s["headroom_frac"] for s in samples
                 if s["headroom_frac"] is not None]
    depths = sorted(s["queue_depth"] for s in samples)
    result = {
        "n_decides": n_decides,
        "n_samples": len(samples),
        "hbm_headroom_min_frac": (round(min(headrooms), 4)
                                  if headrooms else None),
        "hbm_bytes_in_use_max": (max(s["bytes_in_use"] for s in samples)
                                 if samples else None),
        "compile_hits": hits,
        "compile_misses": misses,
        "compile_hit_rate": (round(hits / (hits + misses), 4)
                             if hits + misses else None),
        "compile_storms": sum(c["storms_total"] for c in comp.values()),
        "queue_depth_p95": (depths[min(len(depths) - 1,
                                       int(0.95 * len(depths)))]
                            if depths else None),
        "admit_wait_p95_ms": admit_p95,
        "watchdog_stalls": WATCHDOG_STALLS.total(),
        "scheduler": {spec: {k: s[k] for k in
                             ("steps", "retired", "failed")}
                      for spec, s in backend10.scheduler_stats().items()},
    }
    sidecar = os.environ.get("QUORACLE_BENCH_RESOURCES")
    if sidecar:
        with open(sidecar, "w") as f:
            json.dump({"summary": result, "samples": samples,
                       "compile": comp}, f)
        log(f"config10 sample timeline written to {sidecar}")
    return result


def measure_qos_overload(backend, pool, overload_x: int = 4,
                         n_interactive: int = 12,
                         batch_max_new: int = 32) -> dict:
    """Config 11: serving QoS under SUSTAINED overload (ISSUE 4).

    One pool member serves through decode-level continuous batching while
    an offered load of ``overload_x`` × its slot capacity in BATCH rows is
    kept outstanding (each retired batch row is immediately replaced —
    sustained overload, not a one-shot burst). Against that background,
    INTERACTIVE rows are submitted one at a time and their completion
    latency measured. Run twice over the SAME engines:

      * qos=off — the FIFO admission the pre-QoS scheduler had: every
        interactive row queues behind the entire backlog;
      * qos=on  — weighted-fair DRR + aging floor + admission controller
        (tight queue bound so the overload visibly sheds).

    Reported: unloaded interactive p50 (the denominator of the acceptance
    ratios), interactive p95/p99 with QoS on/off, BATCH throughput on/off
    (fairness has a bulk-throughput price — record it), shed counts +
    retry_after hints, goodput-per-retired-row, and the accounting
    identity submitted == retired + shed + failed for the QoS run — no
    request may vanish silently (every shed is a structured reject AND a
    flight-recorder event; the artifact records both sides).
    """
    import statistics as stats_mod
    import threading

    from quoracle_tpu.infra.flightrec import FLIGHT
    from quoracle_tpu.models.runtime import TPUBackend
    from quoracle_tpu.models.tokenizer import get_tokenizer
    from quoracle_tpu.serving.admission import (
        AdmissionConfig, AdmissionError,
    )
    from quoracle_tpu.serving.qos import Priority, QoSConfig

    from quoracle_tpu.sim.workload import bench_overload_mix

    member = pool[0]
    tok = get_tokenizer(member)
    # prompt mix sourced from the fleet simulator (ISSUE 16): the
    # interactive/batch texts come off a seeded workload trace, so the
    # overload phases replay the same mix every run and the sidecar
    # records which trace drove them
    mix = bench_overload_mix(TASKS, n_interactive)
    batch_prompt = tok.encode(mix["batch_text"], add_bos=True)
    inter_prompts = [tok.encode(t, add_bos=True)
                     for t in mix["interactive_texts"]]
    slots = 8

    def build(qos_on: bool) -> TPUBackend:
        qos = QoSConfig(
            aging_floor_s=1.0,
            admission=AdmissionConfig(max_queue_depth=2 * slots,
                                      base_retry_ms=250),
        ) if qos_on else None
        # chunk 16 (not the default 32): chunk boundaries are the only
        # preemption points, so a shorter chunk tightens the interactive
        # admit latency for BOTH phases — the on/off comparison stays fair
        return TPUBackend(pool, engines=backend.engines,
                          embedder=backend.embedder, continuous=True,
                          continuous_chunk=16, continuous_slots=slots,
                          qos=qos)

    def run_phase(b: TPUBackend, qos_on: bool, seconds: float) -> dict:
        cb = b._cbatchers[member]
        stop = threading.Event()
        counts = {"batch_submitted": 0, "batch_retired": 0,
                  "batch_shed": 0, "batch_failed": 0}
        clock = {"batch_tokens": 0}
        lock = threading.Lock()

        def batch_pump():
            """Keep overload_x × slots BATCH rows outstanding. A shed
            (future already failed at submit) backs the pump off like a
            well-behaved client honoring retry_after — sustained offered
            load, not a reject-spin."""
            outstanding: list = []
            while not stop.is_set():
                outstanding = [f for f in outstanding if not f.done()]
                backoff = 0.01
                while len(outstanding) < overload_x * slots \
                        and not stop.is_set():
                    with lock:
                        counts["batch_submitted"] += 1
                    f = cb.submit(batch_prompt, temperature=0.0,
                                  max_new_tokens=batch_max_new,
                                  priority=Priority.BATCH,
                                  tenant="bulk")
                    f.add_done_callback(_account)
                    if f.done():          # shed at admission
                        backoff = 0.25
                        break
                    outstanding.append(f)
                stop.wait(backoff)

        def _account(f):
            with lock:
                try:
                    g = f.result()
                    counts["batch_retired"] += 1
                    clock["batch_tokens"] += g.n_gen_tokens
                except AdmissionError:
                    counts["batch_shed"] += 1
                except Exception:       # noqa: BLE001 — close-path fails
                    counts["batch_failed"] += 1

        pump = threading.Thread(target=batch_pump, daemon=True)
        t0 = time.monotonic()
        pump.start()
        time.sleep(min(2.0, seconds / 4))        # let the backlog form
        lats = []
        deadline = t0 + seconds
        for p in inter_prompts:
            if time.monotonic() > deadline:
                break
            t1 = time.monotonic()
            g = cb.submit(p, temperature=0.0, max_new_tokens=16,
                          priority=Priority.INTERACTIVE,
                          tenant="human").result(300)
            lats.append((time.monotonic() - t1) * 1000)
        stop.set()
        pump.join(10)
        wall = time.monotonic() - t0
        # close() fails the still-queued/live pump rows loudly; their
        # done-callbacks land in counts, closing the accounting identity
        b.close()
        t_acct = time.monotonic()
        while time.monotonic() - t_acct < 30:
            with lock:
                settled = (counts["batch_retired"] + counts["batch_shed"]
                           + counts["batch_failed"])
                if settled >= counts["batch_submitted"]:
                    break
            time.sleep(0.05)
        lats.sort()
        q = lambda p: (lats[min(len(lats) - 1, int(p * len(lats)))]
                       if lats else None)
        with lock:
            snap = dict(counts)
        retired_rows = snap["batch_retired"] + len(lats)
        return {
            "interactive_n": len(lats),
            "interactive_p50_ms": round(q(0.50), 1) if lats else None,
            "interactive_p95_ms": round(q(0.95), 1) if lats else None,
            "interactive_p99_ms": round(q(0.99), 1) if lats else None,
            "batch_tokens_per_s": round(clock["batch_tokens"] / wall, 1),
            "goodput_tokens_per_retired_row": round(
                (clock["batch_tokens"] + 16 * len(lats))
                / max(1, retired_rows), 1),
            **snap,
            "wall_s": round(wall, 1),
        }

    # unloaded reference: solo interactive rows through a fresh batcher
    b_ref = build(False)
    try:
        lats0 = []
        for p in inter_prompts[:4]:
            t1 = time.monotonic()
            b_ref._cbatchers[member].submit(
                p, temperature=0.0, max_new_tokens=16).result(300)
            lats0.append((time.monotonic() - t1) * 1000)
        unloaded_p50 = stats_mod.median(lats0)
    finally:
        b_ref.close()

    phase_s = 20.0 if MAX_NEW <= 16 else 60.0    # smoke vs real run
    off = run_phase(build(False), False, phase_s)
    shed_before = sum(1 for e in FLIGHT.snapshot()
                      if e.get("kind") == "qos_shed")
    on = run_phase(build(True), True, phase_s)
    shed_events = sum(1 for e in FLIGHT.snapshot()
                      if e.get("kind") == "qos_shed") - shed_before

    total_on = on["batch_retired"] + on["batch_shed"] + on["batch_failed"]
    return {
        "overload_x": overload_x,
        "sim_trace_digest": mix["trace"].digest(),
        "unloaded_interactive_p50_ms": round(unloaded_p50, 1),
        "qos_off": off,
        "qos_on": on,
        "shed_rate": round(on["batch_shed"]
                           / max(1, on["batch_submitted"]), 4),
        "shed_flightrec_events": shed_events,
        # acceptance: p95 ratios vs the unloaded p50 (on ≤ 2x, off > 5x)
        "interactive_p95_ratio_on": (
            round(on["interactive_p95_ms"] / unloaded_p50, 2)
            if on["interactive_p95_ms"] else None),
        "interactive_p95_ratio_off": (
            round(off["interactive_p95_ms"] / unloaded_p50, 2)
            if off["interactive_p95_ms"] else None),
        # no silent drops: every submitted row ended retired, shed
        # (a structured reject + flight-recorder event), or failed
        # loudly at close — the identity must balance exactly
        "accounting_gap": on["batch_submitted"] - total_on,
        "no_silent_drops": on["batch_submitted"] == total_on,
    }


def measure_spec_continuous(backend, pool, n_rows: int = 6) -> dict:
    """Config 13: speculative decoding in the PRODUCTION serving path
    (ISSUE 6) — continuous batching + QoS with speculation on vs off.

    ``n_rows`` consensus-shaped constrained rows (action-JSON grammar,
    temp 0) ride one member's shared decode loop twice over the SAME
    engine: once vanilla, once with a draft_map routing the member
    through batched draft/verify rounds (self-draft here — the trained
    draft's acceptance factor is config 7's realized row; self-draft
    isolates the serving-path mechanics: batched draft scan + chunked
    multi-row verify + per-row commit against the paged session KV).

    Reported: e2e decode ms/token on vs off, realized tokens/round,
    per-row acceptance p50, fallback counts by reason, and the
    acceptance gate — temp-0 outputs must be BIT-IDENTICAL on vs off
    (the same equality bar PRs 4-5 held QoS and quality to).
    """
    import statistics as stats_mod

    from quoracle_tpu.models.runtime import TPUBackend
    from quoracle_tpu.models.tokenizer import get_tokenizer
    from quoracle_tpu.serving.qos import QoSConfig

    member = pool[0]
    tok = get_tokenizer(member)
    enum = ("send_message", "todo", "wait", "execute_shell",
            "spawn_child")
    prompts = [
        tok.encode(f"[agent {i}] {TASKS[i % len(TASKS)]}", add_bos=True)
        for i in range(n_rows)]

    def run(spec_on: bool) -> dict:
        b = TPUBackend([member], engines=backend.engines,
                       embedder=backend.embedder, continuous=True,
                       continuous_chunk=16, continuous_slots=8,
                       qos=QoSConfig(),
                       draft_map=({member: member} if spec_on else None))
        cb = b._cbatchers[member]
        try:
            # warmup: pays the draft/verify (or vanilla chunk) compiles
            cb.submit(prompts[0], temperature=0.0, max_new_tokens=MAX_NEW,
                      constrain_json=True,
                      action_enum=enum).result(900)
            t0 = time.monotonic()
            futs = [cb.submit(p, temperature=0.0, max_new_tokens=MAX_NEW,
                              constrain_json=True, action_enum=enum)
                    for p in prompts]
            gens = [f.result(900) for f in futs]
            wall = time.monotonic() - t0
            spec_stats = (b._speculators[member].stats()
                          if spec_on else None)
        finally:
            b.close()
        toks = sum(g.n_gen_tokens for g in gens)
        rows = [{
            "tokens": g.n_gen_tokens,
            "spec_rounds": g.spec_rounds,
            "spec_drafted": g.spec_drafted_tokens,
            "spec_accepted": g.spec_accepted_tokens,
        } for g in gens]
        return {
            "texts": [g.text for g in gens],
            "wall_s": round(wall, 3),
            "tokens": toks,
            "ms_per_token": round(wall * 1000 / max(1, toks), 3),
            "tokens_per_s": round(toks / max(1e-9, wall), 1),
            "rows": rows,
            "speculative": spec_stats,
        }

    off = run(False)
    on = run(True)
    equal = on["texts"] == off["texts"]
    acc_rows = [r["spec_accepted"] / r["spec_drafted"]
                for r in on["rows"] if r["spec_drafted"]]
    spec = on["speculative"] or {}
    result = {
        "n_rows": n_rows,
        "max_new": MAX_NEW,
        "ms_per_token_off": off["ms_per_token"],
        "ms_per_token_on": on["ms_per_token"],
        "speedup": round(off["ms_per_token"]
                         / max(1e-9, on["ms_per_token"]), 3),
        "tokens_per_round": spec.get("tokens_per_round"),
        "acceptance_p50": (round(stats_mod.median(acc_rows), 4)
                           if acc_rows else None),
        "fallbacks": spec.get("fallbacks") or {},
        "rounds": spec.get("rounds"),
        "disengages": spec.get("disengages"),
        "temp0_equal": equal,
        "qos_off_detail": {k: off[k] for k in
                           ("wall_s", "tokens", "tokens_per_s")},
        "qos_on_detail": {k: on[k] for k in
                          ("wall_s", "tokens", "tokens_per_s")},
        "rows_on": on["rows"],
    }
    assert equal, "config13: temp-0 outputs diverged with speculation on"
    return result


def measure_kv_tiering(backend, pool, n_sessions: int = 6) -> dict:
    """Config 14: tiered KV — session hibernation vs destruction
    (ISSUE 7, serving/kvtier.py).

    ``n_sessions`` independent temp-0 conversations on one member, two
    rounds each, with a forced full eviction between rounds. Phase OFF
    (no tier): eviction destroys the sessions and round 2 pays a COLD
    RE-PREFILL of each whole conversation. Phase ON (tier attached):
    the same eviction DEMOTES to the host page store and round 2
    restores by page-in. Prefix sharing is disabled for the config so
    each session's cost is isolated (no cross-session adoption blurring
    the cold baseline).

    Reported: restore-latency p95 (quoracle_kv_restore_ms count deltas)
    vs the cold re-prefill p95 (per-call prefill fence), demote/restore
    counts, resident-session capacity at fixed HBM with tiering on vs
    off, and the acceptance gate — round-2 temp-0 outputs must be
    BIT-IDENTICAL on vs off (the same equality bar every serving layer
    holds)."""
    from quoracle_tpu.infra.telemetry import KV_RESTORE_MS, quantile
    from quoracle_tpu.models.tokenizer import get_tokenizer

    member = pool[0]
    eng = backend.engines[member]
    tok = get_tokenizer(member)
    st = eng.sessions
    prompts = [
        tok.encode(f"{SYSTEM_PROMPT} [agent {i}] "
                   f"{TASKS[i % len(TASKS)]}", add_bos=True)
        for i in range(n_sessions)]
    round_new = min(MAX_NEW, 64)

    def force_evict():
        # demand every usable page with nothing protected: the ladder
        # evicts (OFF) or demotes (ON) every resident session
        with eng._paged_lock:
            with st.lock:
                got = st.alloc(st.n_pages - 1)
                if got:
                    st._release(got)

    def run_phase(tier) -> dict:
        tag = "on" if tier is not None else "off"
        sids = [f"kv14{tag}-{i}" for i in range(n_sessions)]
        r1 = []
        for p, sid in zip(prompts, sids):
            r1.append(eng.generate([p], temperature=0.0,
                                   max_new_tokens=round_new,
                                   session_ids=[sid])[0])
        force_evict()
        before, _, _ = KV_RESTORE_MS.counts(model=eng.cfg.name,
                                            kind="session")
        texts, prefill_ms, cached = [], [], []
        for p, sid, g in zip(prompts, sids, r1):
            p2 = p + g.token_ids + tok.encode(" Continue.")
            g2 = eng.generate([p2], temperature=0.0,
                              max_new_tokens=round_new,
                              session_ids=[sid])[0]
            texts.append(g2.text)
            prefill_ms.append(eng.last_prefill_s * 1000)
            cached.append(g2.n_cached_tokens)
        after, _, _ = KV_RESTORE_MS.counts(model=eng.cfg.name,
                                           kind="session")
        delta = [a - b for a, b in zip(after, before)]
        for sid in sids:
            eng.drop_session(sid)
        return {
            "texts": texts,
            "round2_cached_tokens": cached,
            "cold_prefill_ms": [round(v, 2) for v in prefill_ms],
            "restore_p95_ms": (
                round(quantile(KV_RESTORE_MS.buckets, delta, 0.95), 3)
                if sum(delta) else None),
            "restores_in_window": sum(delta),
        }

    def p95(vals):
        s = sorted(vals)
        return round(s[max(0, int(len(s) * 0.95) - 1)], 2) if s else None

    import numpy as _np
    pages_per_session = max(
        1, -(-max(len(p) + 2 * round_new for p in prompts) // st.page))
    page_bytes = (2 * eng.cfg.n_layers * eng.cfg.n_kv_heads
                  * eng.cfg.head_dim
                  * _np.dtype(eng.cache_dtype).itemsize * st.page)
    session_mb = pages_per_session * page_bytes / (1 << 20)

    sharing = eng.prefix_sharing
    eng.prefix_sharing = False
    try:
        off = run_phase(None)
        # size the host tier to hold every hibernated session twice over
        tier = eng.attach_tier(
            host_mb=max(64, int(2 * n_sessions * session_mb) + 1))
        try:
            # warmup: one full hibernate→restore cycle pays the page-in
            # scatter compile OUTSIDE the measured window (same shape as
            # the measured sessions), mirroring the prefill/decode
            # warmups every other config gets
            wsid = "kv14-warm"
            wg = eng.generate([prompts[0]], temperature=0.0,
                              max_new_tokens=round_new,
                              session_ids=[wsid])[0]
            force_evict()
            eng.generate([prompts[0] + wg.token_ids
                          + tok.encode(" Continue.")],
                         temperature=0.0, max_new_tokens=round_new,
                         session_ids=[wsid])
            eng.drop_session(wsid)
            warm_stats = tier.stats()
            on = run_phase(tier)
            tier_stats = tier.stats()
            tier_stats["demoted_sessions"] -= \
                warm_stats["demoted_sessions"]
            tier_stats["restored_sessions"] -= \
                warm_stats["restored_sessions"]
        finally:
            st.tier = None            # detach: later configs untiered
    finally:
        eng.prefix_sharing = sharing

    equal = on["texts"] == off["texts"]
    hbm_capacity = (st.n_pages - 1) // pages_per_session
    host_capacity = int(tier_stats["host"]["budget_bytes"]
                        // (pages_per_session * page_bytes))
    cold_p95 = p95(off["cold_prefill_ms"])
    result = {
        "n_sessions": n_sessions,
        "round_new_tokens": round_new,
        # round 2 with tiering OFF re-prefilled from scratch; ON resumed
        # from restored pages — the cached-token telemetry proves which
        # path each phase took
        "round2_cached_tokens_off": off["round2_cached_tokens"],
        "round2_cached_tokens_on": on["round2_cached_tokens"],
        "cold_prefill_p95_ms": cold_p95,
        "restore_p95_ms": on["restore_p95_ms"],
        "restore_vs_cold_speedup": (
            round(cold_p95 / on["restore_p95_ms"], 3)
            if cold_p95 and on["restore_p95_ms"] else None),
        "demotes": tier_stats["demoted_sessions"],
        "restores": tier_stats["restored_sessions"],
        "restore_failures": tier_stats["restore_failures"],
        # resident-session capacity at fixed HBM: without tiering the
        # pool bounds it; with tiering hibernated sessions extend it by
        # the host budget
        "pages_per_session": pages_per_session,
        "hbm_session_capacity": hbm_capacity,
        "tiered_session_capacity": hbm_capacity + host_capacity,
        "temp0_equal": equal,
    }
    assert equal, "config14: temp-0 outputs diverged with tiering on"
    assert tier_stats["demoted_sessions"] >= n_sessions, \
        "config14: forced eviction did not demote the sessions"
    assert all(c > 0 for c in on["round2_cached_tokens"]), \
        "config14: tiered round 2 did not resume from restored pages"
    return result


def measure_ragged_serving(backend, pool, n_short: int = 6,
                           n_long: int = 3) -> dict:
    """Config 15: the UNIFIED ragged serving kernel (ISSUE 8) under mixed
    traffic — short interactive rows and long agent rows riding the SAME
    continuous-batching ticks, unified vs gather over the same engine.

    Each phase submits ``n_short`` short prompts (16 new tokens) and
    ``n_long`` long agent prompts (MAX_NEW new tokens) into one member's
    shared decode loop. Reported per phase: tokens/sec/chip, steady-state
    compile count (CompileRegistry miss delta — the bucketed baseline
    compiles one program pair per batch×prompt bucket, the unified path
    one per token-budget bucket), real-vs-padded chunk tokens (the
    quoracle_sched_*_tokens_total deltas — exactly what raggedness
    reclaims), and decode HBM high-water (allocator peak delta; the
    unified phase runs FIRST because the counter is cumulative, so a
    jump attributes to the gather phase's working caches). Acceptance:
    temp-0 outputs BIT-IDENTICAL across phases."""
    import jax

    from quoracle_tpu.models.runtime import TPUBackend
    from quoracle_tpu.models.tokenizer import get_tokenizer

    member = pool[0]
    eng = backend.engines[member]
    tok = get_tokenizer(member)
    short_prompts = [
        tok.encode(f"[user {i}] {TASKS[i % len(TASKS)][:48]}",
                   add_bos=True)
        for i in range(n_short)]
    long_prompts = [
        tok.encode(f"[agent {i}] long-context working state: "
                   + " ".join(TASKS) + " " + TASKS[i % len(TASKS)],
                   add_bos=True)
        for i in range(n_long)]

    def peak_hbm():
        stats = getattr(jax.devices()[0], "memory_stats", lambda: None)()
        return stats.get("peak_bytes_in_use") if stats else None

    saved = (getattr(eng, "_force_gather_decode", False),
             eng.unified_min_tokens, eng.prefix_sharing)
    # prefix sharing OFF for the config: phase 1's radix-cache inserts
    # would otherwise serve phase 2's prefills (fewer real tokens), and
    # the real-vs-padded comparison must measure the SAME work twice
    eng.prefix_sharing = False

    def run(unified: bool) -> dict:
        eng._force_gather_decode = not unified
        eng.unified_min_tokens = 0 if unified else 1 << 30
        b = TPUBackend([member], engines=backend.engines,
                       embedder=backend.embedder, continuous=True,
                       continuous_chunk=16, continuous_slots=8)
        cb = b._cbatchers[member]
        try:
            # warmup: one short + one long row pays this phase's compiles
            # for the single-row shapes; the measured window still counts
            # the mixed-tick compiles — steady-state program count is the
            # config's point, so it is REPORTED, not hidden
            cb.submit(short_prompts[0], temperature=0.0,
                      max_new_tokens=8).result(900)
            misses0 = eng.compiles.misses
            real0 = eng.pad_real_tokens
            padded0 = eng.pad_padded_tokens
            hbm0 = peak_hbm()
            t0 = time.monotonic()
            futs = [cb.submit(p, temperature=0.0, max_new_tokens=16)
                    for p in short_prompts]
            futs += [cb.submit(p, temperature=0.0, max_new_tokens=MAX_NEW)
                     for p in long_prompts]
            gens = [f.result(900) for f in futs]
            wall = time.monotonic() - t0
        finally:
            b.close()
        toks = sum(g.n_gen_tokens for g in gens)
        real = eng.pad_real_tokens - real0
        padded = eng.pad_padded_tokens - padded0
        hbm1 = peak_hbm()
        return {
            "texts": [g.text for g in gens],
            "wall_s": round(wall, 3),
            "tokens": toks,
            "tokens_per_s": round(toks / max(1e-9, wall), 1),
            "compile_misses": eng.compiles.misses - misses0,
            "real_tokens": real,
            "padded_tokens": padded,
            "pad_waste_ratio": (round(1 - real / padded, 4)
                                if padded else None),
            "peak_hbm_delta_bytes": (hbm1 - hbm0
                                     if hbm0 is not None
                                     and hbm1 is not None else None),
        }

    try:
        unified = run(True)       # first: cumulative peak-HBM attribution
        gather = run(False)
    finally:
        (eng._force_gather_decode, eng.unified_min_tokens,
         eng.prefix_sharing) = saved

    equal = unified["texts"] == gather["texts"]
    n_chips = max(1, len(jax.devices()))
    result = {
        "n_short": n_short,
        "n_long": n_long,
        "max_new": MAX_NEW,
        "tokens_per_s_unified": unified["tokens_per_s"],
        "tokens_per_s_gather": gather["tokens_per_s"],
        "tokens_per_s_chip_unified": round(
            unified["tokens_per_s"] / n_chips, 1),
        "tokens_per_s_chip_gather": round(
            gather["tokens_per_s"] / n_chips, 1),
        "speedup": round(unified["tokens_per_s"]
                         / max(1e-9, gather["tokens_per_s"]), 3),
        "compile_misses_unified": unified["compile_misses"],
        "compile_misses_gather": gather["compile_misses"],
        "pad_waste_unified": unified["pad_waste_ratio"],
        "pad_waste_gather": gather["pad_waste_ratio"],
        "padded_tokens_reclaimed": (gather["padded_tokens"]
                                    - unified["padded_tokens"]),
        "peak_hbm_delta_unified": unified["peak_hbm_delta_bytes"],
        "peak_hbm_delta_gather": gather["peak_hbm_delta_bytes"],
        "temp0_equal": equal,
        "unified_detail": {k: unified[k] for k in
                           ("wall_s", "tokens", "real_tokens",
                            "padded_tokens")},
        "gather_detail": {k: gather[k] for k in
                          ("wall_s", "tokens", "real_tokens",
                           "padded_tokens")},
    }
    assert equal, "config15: temp-0 outputs diverged unified vs gather"
    assert unified["real_tokens"] == gather["real_tokens"], \
        "config15: phases did not process the same real tokens"
    return result


def measure_cluster_disagg(backend, pool, n_interactive: int = 6,
                           n_agent: int = 3) -> dict:
    """Config 16: the disaggregated serving plane (ISSUE 10) under
    mixed interactive+agent traffic — ONE monolithic continuous replica
    vs a 2-replica prefill/decode cluster over the same total device
    budget (both phases see every local chip; on a single host the
    cluster's replicas interleave on the device queue, so the smoke
    number is a routing-overhead measurement, the multi-chip run the
    real scaling one).

    Each phase serves ``n_interactive`` short INTERACTIVE rows (16 new
    tokens) and ``n_agent`` long sessioned AGENT rows (MAX_NEW tokens)
    through the production query() path. Reported per phase:
    tokens/sec/chip and interactive TTFT p95 (a max_tokens=1 request —
    first token out the door, which in the cluster phase includes the
    prefill→decode handoff). Plus: handoff latency p95 (count deltas of
    quoracle_cluster_handoff_ms) vs the cold re-prefill it replaces
    (the monolithic TTFT probe), and the acceptance gate — temp-0
    outputs BIT-IDENTICAL monolithic vs disaggregated."""
    import jax

    from quoracle_tpu.infra.telemetry import CLUSTER_HANDOFF_MS, quantile
    from quoracle_tpu.models.runtime import QueryRequest, TPUBackend
    from quoracle_tpu.serving.cluster import ClusterPlane

    member = pool[0]
    inter_msgs = [[{"role": "user",
                    "content": f"[user {i}] {TASKS[i % len(TASKS)][:48]}"}]
                  for i in range(n_interactive)]
    agent_msgs = [[{"role": "user",
                    "content": f"[agent {i}] working state: "
                               + " ".join(TASKS)}]
                  for i in range(n_agent)]

    def reqs():
        rs = [QueryRequest(member, m, temperature=0.0, max_tokens=16,
                           priority=0) for m in inter_msgs]
        rs += [QueryRequest(member, m, temperature=0.0,
                            max_tokens=MAX_NEW, session_id=f"agent{j}",
                            constrain_json=True, priority=1)
               for j, m in enumerate(agent_msgs)]
        return rs

    def run(b) -> dict:
        # warmup pays the phase's compiles; the measured window is
        # steady-state serving
        b.query([QueryRequest(member, inter_msgs[0], temperature=0.0,
                              max_tokens=4)])
        ttfts = []
        for m in inter_msgs:
            t0 = time.monotonic()
            b.query([QueryRequest(member, m, temperature=0.0,
                                  max_tokens=1)])
            ttfts.append((time.monotonic() - t0) * 1000)
        t0 = time.monotonic()
        out = b.query(reqs())
        wall = time.monotonic() - t0
        assert all(r.ok for r in out), [r.error for r in out if not r.ok]
        toks = sum(r.usage.completion_tokens for r in out)
        ttfts.sort()
        return {
            "texts": [r.text for r in out],
            "wall_s": round(wall, 3),
            "tokens": toks,
            "tokens_per_s": round(toks / max(1e-9, wall), 1),
            "ttft_p95_ms": round(
                ttfts[min(len(ttfts) - 1,
                          int(0.95 * len(ttfts)))], 1),
        }

    mono_b = TPUBackend([member], engines=backend.engines,
                        embedder=backend.embedder, continuous=True,
                        continuous_chunk=16, continuous_slots=8)
    try:
        mono = run(mono_b)
    finally:
        mono_b.close()
    for j in range(n_agent):           # free the monolithic sessions
        backend.engines[member].drop_session(f"agent{j}")

    ho_win = HistWindow(CLUSTER_HANDOFF_MS)
    cluster = ClusterPlane.build([member], replicas=2, disaggregate=True,
                                 continuous=True, continuous_chunk=16,
                                 continuous_slots=8)
    try:
        disagg = run(cluster)
        handoff_stats = cluster.handoff.stats()
    finally:
        cluster.close()
    handoff_p95 = ho_win.quantile(0.95, ndigits=4)

    equal = mono["texts"] == disagg["texts"]
    n_chips = max(1, len(jax.devices()))
    result = {
        "n_interactive": n_interactive,
        "n_agent": n_agent,
        "max_new": MAX_NEW,
        "tokens_per_s_chip_mono": round(mono["tokens_per_s"] / n_chips,
                                        1),
        "tokens_per_s_chip_disagg": round(
            disagg["tokens_per_s"] / n_chips, 1),
        "ttft_p95_ms_mono": mono["ttft_p95_ms"],
        "ttft_p95_ms_disagg": disagg["ttft_p95_ms"],
        "handoff_p95_ms": handoff_p95,
        # the monolithic TTFT probe IS a cold prefill + first token —
        # the work a handoff-restored decode replica never repeats
        "cold_prefill_p95_ms": mono["ttft_p95_ms"],
        "handoffs": handoff_stats,
        "temp0_equal": equal,
        "mono_detail": {k: mono[k] for k in ("wall_s", "tokens")},
        "disagg_detail": {k: disagg[k] for k in ("wall_s", "tokens")},
    }
    assert equal, "config16: temp-0 outputs diverged mono vs cluster"
    return result


def measure_chaos_storm(pool, n_interactive: int = 6,
                        n_agent: int = 3, seed: int = 2026) -> dict:
    """Config 17: the chaos plane on real engines (ISSUE 11) — the
    storm scenario's fault mix armed against a 3-replica prefill/decode
    cluster, chaos OFF then chaos ON at the SAME offered load
    (``n_interactive`` short INTERACTIVE rows timed individually + one
    batch of ``n_agent`` constrained sessioned AGENT rows per phase).

    Reported: goodput (ok completion tokens/s) and interactive p95 per
    phase — the ON numbers are "during recovery" by construction (a
    decode replica dies mid-phase and rows re-place through their
    retained handoff envelopes; admission/router signals drop and
    delay; a quarter of tier restores fail to the re-prefill path) —
    plus the machine-checked invariant verdicts (chaos/invariants.py):
    zero silent loss, structured failures only, and temp-0 survivor
    bit-equality ON vs OFF. Detail lands in the CHAOS sidecar
    (QUORACLE_BENCH_CHAOS)."""
    import jax

    from quoracle_tpu.chaos import invariants as chaos_inv
    from quoracle_tpu.chaos.faults import CHAOS, FaultPlan, FaultRule
    from quoracle_tpu.models.runtime import QueryRequest
    from quoracle_tpu.serving.cluster import ClusterPlane

    member = pool[0]
    inter_msgs = [[{"role": "user",
                    "content": f"[user {i}] {TASKS[i % len(TASKS)][:48]}"}]
                  for i in range(n_interactive)]
    agent_msgs = [[{"role": "user",
                    "content": f"[agent {i}] working state: "
                               + " ".join(TASKS)[:512]}]
                  for i in range(n_agent)]

    def run_phase(cluster, tag: str) -> dict:
        # warmup pays BOTH paths' compiles (plain interactive and
        # constrained sessioned) so the off phase isn't billed for them
        cluster.query([QueryRequest(member, inter_msgs[0],
                                    temperature=0.0, max_tokens=4)])
        cluster.query([QueryRequest(member, agent_msgs[0],
                                    temperature=0.0, max_tokens=4,
                                    session_id=f"chaos-{tag}-warm",
                                    constrain_json=True)])
        cluster.drop_session(f"chaos-{tag}-warm")
        lat, results = [], []
        t0 = time.monotonic()
        for m in inter_msgs:
            r0 = time.monotonic()
            out = cluster.query([QueryRequest(
                member, m, temperature=0.0, max_tokens=16, priority=0)])
            lat.append((time.monotonic() - r0) * 1000)
            results += out
        results += cluster.query([QueryRequest(
            member, m, temperature=0.0, max_tokens=MAX_NEW,
            session_id=f"chaos-{tag}-{j}", constrain_json=True,
            priority=1) for j, m in enumerate(agent_msgs)])
        wall = time.monotonic() - t0
        for j in range(n_agent):
            cluster.drop_session(f"chaos-{tag}-{j}")
        ok_tokens = sum(r.usage.completion_tokens for r in results
                        if r.ok)
        lat.sort()
        return {
            "results": results,
            "texts": [r.text if r.ok else None for r in results],
            "wall_s": round(wall, 3),
            "ok_rows": sum(1 for r in results if r.ok),
            "goodput_tok_s": round(ok_tokens / max(1e-9, wall), 1),
            "interactive_p95_ms": round(
                lat[min(len(lat) - 1, int(0.95 * len(lat)))], 1),
        }

    cluster = ClusterPlane.build([member], replicas=3, disaggregate=True,
                                 continuous=True, continuous_chunk=16,
                                 continuous_slots=8, qos=True)
    try:
        off = run_phase(cluster, "off")
        plan = FaultPlan(seed, [
            FaultRule("admission.signals", "drop", prob=0.25),
            FaultRule("admission.signals", "delay", prob=0.2,
                      delay_ms=20),
            FaultRule("router.signals", "drop", prob=0.25),
            FaultRule("kvtier.restore", "fail", prob=0.25),
            FaultRule("cluster.decode", "crash", start=1, max_fires=1),
        ])
        with CHAOS.arming(plan):
            on = run_phase(cluster, "on")
        handoff_stats = cluster.handoff.stats()
        checks = [
            chaos_inv.no_silent_loss(len(on["results"]), on["results"],
                                     backends=[cluster]),
            chaos_inv.structured_failures(on["results"]),
            chaos_inv.temp0_equality(off["results"], on["results"]),
            chaos_inv.fault_schedule(plan, []),
        ]
        # the flight-ring slice is process-global in a bench run; check
        # ledger-vs-fired count instead of replaying the ring here
        checks[-1] = chaos_inv.InvariantResult(
            "faults_fired", bool(plan.schedule()),
            f"{len(plan.schedule())} faults")
    finally:
        cluster.close()

    n_chips = max(1, len(jax.devices()))
    invariants_pass = all(c.ok for c in checks)
    result = {
        "n_interactive": n_interactive,
        "n_agent": n_agent,
        "seed": seed,
        "faults_fired": len(plan.schedule()),
        "schedule": [list(t) for t in plan.schedule()[:64]],
        "goodput_tok_s_off": off["goodput_tok_s"],
        "goodput_tok_s_on": on["goodput_tok_s"],
        "goodput_delta_frac": (
            round(1.0 - on["goodput_tok_s"]
                  / max(1e-9, off["goodput_tok_s"]), 3)),
        "goodput_tok_s_chip_off": round(
            off["goodput_tok_s"] / n_chips, 1),
        "goodput_tok_s_chip_on": round(on["goodput_tok_s"] / n_chips, 1),
        "interactive_p95_ms_off": off["interactive_p95_ms"],
        "interactive_p95_ms_on": on["interactive_p95_ms"],
        "ok_rows_off": off["ok_rows"],
        "ok_rows_on": on["ok_rows"],
        "replicas_replaced": handoff_stats["replaced"],
        "invariants": [c.as_dict() for c in checks],
        "invariants_pass": invariants_pass,
    }
    assert invariants_pass, \
        f"config17: chaos invariants failed: " \
        f"{[c.as_dict() for c in checks if not c.ok]}"
    return result


def measure_fabric(pool, n_rows: int = 6, n_router_peers: int = 3,
                   n_router_rows: int = 9) -> dict:
    """Config 18: the cross-host cluster fabric (ISSUE 12) on the
    loopback wire — every byte rides the real frame codec, no sockets,
    so the numbers isolate SERIALIZATION + PROTOCOL cost from network
    cost. Three measurements:

    1. **handoff p95, wire vs in-process** — the same ``n_rows``
       disaggregated requests through a 2-replica in-process
       ClusterPlane and through a prefill+decode FabricPlane over
       loopback transports; handoff latency from count deltas of
       ``quoracle_cluster_handoff_ms`` per phase (both phases adopt
       through the same broker), outputs asserted temp-0 BIT-EQUAL.
    2. **fleet prefix hit rate cold-start** — a donor publishes its
       prefix blocks to an in-process prefixd service; two FRESH peers
       serve the same long-preamble prompts, one reading through the
       fleet, one not: cached-token fraction with vs without.
    3. **front-door throughput at N loopback peers** — ``n_router_rows``
       concurrent rows through a FabricPlane over ``n_router_peers``
       unified peers: rows/s + placement spread.
    """
    import tempfile

    from quoracle_tpu.infra.telemetry import CLUSTER_HANDOFF_MS
    from quoracle_tpu.models.runtime import QueryRequest
    from quoracle_tpu.serving.cluster import ClusterPlane, RemoteReplica
    from quoracle_tpu.serving.fabric.frontdoor import FabricPlane
    from quoracle_tpu.serving.fabric.peer import FabricPeer
    from quoracle_tpu.serving.fabric.prefixd import PrefixService
    from quoracle_tpu.serving.fabric.transport import LoopbackTransport

    member = pool[0]

    def reqs():
        return [QueryRequest(
            member, [{"role": "user",
                      "content": f"[fabric {i}] "
                                 + TASKS[i % len(TASKS)][:64]}],
            temperature=0.0, max_tokens=16, constrain_json=(i % 3 == 2))
            for i in range(n_rows)]

    def handoff_window(fn):
        win = HistWindow(CLUSTER_HANDOFF_MS)
        t0 = time.monotonic()
        out = fn()
        wall = time.monotonic() - t0
        return out, win.quantile(0.95, ndigits=3), wall

    # -- 1. handoff p95: in-process vs loopback wire ---------------------
    cl = ClusterPlane.build([member], replicas=2, disaggregate=True,
                            continuous=True, continuous_chunk=16)
    try:
        inproc, inproc_p95, inproc_wall = handoff_window(
            lambda: cl.query(reqs()))
        assert all(r.ok for r in inproc), \
            [r.error for r in inproc if not r.ok]
    finally:
        cl.close()
    peers = [FabricPeer.build([member], role="prefill",
                              replica_id="prefill-0",
                              continuous_chunk=16),
             FabricPeer.build([member], role="decode",
                              replica_id="decode-0",
                              continuous_chunk=16)]
    plane = FabricPlane([
        RemoteReplica(LoopbackTransport(p.handle, p.replica_id))
        for p in peers])
    try:
        wired, wire_p95, wire_wall = handoff_window(
            lambda: plane.query(reqs()))
        assert all(r.ok for r in wired), \
            [r.error for r in wired if not r.ok]
        wire_handoffs = plane.wire_handoffs
    finally:
        plane.close()
        for p in peers:
            p.close()
    equal = [r.text for r in inproc] == [r.text for r in wired]
    assert equal, "config18: temp-0 outputs diverged in-process vs wire"

    # -- 2. fleet prefix hit rate: cold-start with vs without prefixd ----
    preamble = ("system: shared fleet policy preamble for every agent "
                "session. " * 6)
    warm_reqs = [QueryRequest(
        member, [{"role": "user",
                  "content": preamble + f"task {i}: restate briefly."}],
        temperature=0.0, max_tokens=12, session_id=f"warm{i}")
        for i in range(3)]
    with tempfile.TemporaryDirectory(prefix="bench-prefixd-") as root:
        svc = PrefixService(root)

        def fleet_transport():
            return LoopbackTransport(svc.handle, "prefixd",
                                     lock_name="fabric.prefixd")

        donor = FabricPeer.build([member], replica_id="donor",
                                 continuous_chunk=16, host_kv_mb=64)
        donor.attach_prefixd(fleet_transport())
        donor.backend.query(warm_reqs)
        for i in range(len(warm_reqs)):
            donor.backend.drop_session(f"warm{i}")
        donor.backend.engines[member].sessions.tier.flush_spills()
        donor.close()

        def cold_start(with_fleet: bool) -> dict:
            peer = FabricPeer.build([member], replica_id="cold",
                                    continuous_chunk=16, host_kv_mb=64)
            if with_fleet:
                peer.attach_prefixd(fleet_transport())
            try:
                out = peer.backend.query(warm_reqs)
                assert all(r.ok for r in out)
                cached = sum(r.cached_tokens for r in out)
                prompt = sum(r.usage.prompt_tokens for r in out)
                return {"cached_tokens": cached,
                        "prompt_tokens": prompt,
                        "hit_frac": round(cached / max(1, prompt), 3),
                        "texts": [r.text for r in out]}
            finally:
                peer.close()

        with_fleet = cold_start(True)
        without = cold_start(False)
        assert with_fleet["texts"] == without["texts"], \
            "config18: prefixd warm-start changed output bits"

    # -- 3. front-door throughput at N loopback peers --------------------
    router_peers = [FabricPeer.build([member], role="unified",
                                     replica_id=f"unified-{i}",
                                     continuous_chunk=16)
                    for i in range(n_router_peers)]
    door = FabricPlane([
        RemoteReplica(LoopbackTransport(p.handle, p.replica_id))
        for p in router_peers])
    try:
        rows = [QueryRequest(
            member, [{"role": "user",
                      "content": f"[door {i}] "
                                 + TASKS[i % len(TASKS)][:48]}],
            temperature=0.0, max_tokens=12)
            for i in range(n_router_rows)]
        t0 = time.monotonic()
        out = door.query(rows)
        door_wall = time.monotonic() - t0
        assert all(r.ok for r in out), \
            [r.error for r in out if not r.ok]
        placements = door.router.stats()["placements"]
    finally:
        door.close()
        for p in router_peers:
            p.close()

    return {
        "n_rows": n_rows,
        # the in-process histogram window spans export→adopt (front-door
        # time included); the wire peer re-anchors at decode, so its
        # window is the adopt leg alone — the honest wire-vs-in-process
        # number is the per-row wall delta below
        "handoff_p95_ms_inprocess": inproc_p95,
        "handoff_adopt_p95_ms_wire": wire_p95,
        "wire_overhead_ms_per_row": round(
            (wire_wall - inproc_wall) * 1000 / max(1, n_rows), 1),
        "wire_handoffs": wire_handoffs,
        "wall_s_inprocess": round(inproc_wall, 3),
        "wall_s_wire": round(wire_wall, 3),
        "prefix_hit_frac_with_prefixd": with_fleet["hit_frac"],
        "prefix_hit_frac_without": without["hit_frac"],
        "prefix_cached_tokens_with": with_fleet["cached_tokens"],
        "prefix_cached_tokens_without": without["cached_tokens"],
        "router_peers": n_router_peers,
        "router_rows": n_router_rows,
        "router_rows_per_s": round(n_router_rows
                                   / max(1e-9, door_wall), 2),
        "router_placements": placements,
        "temp0_equal": equal,
    }


def measure_quant(pool, n_prompts: int = 6) -> dict:
    """Config 19: quantized serving (ISSUE 13) — int8 weights + int8 KV
    pages vs the bf16 baseline at the same device budget. Four
    measurements:

    1. **byte economy** — the exact per-token KV rate (int8+scales vs
       dense), the resident-token figures pool_sizing plans at fixed
       HBM, and the MEASURED handoff-envelope and disk-spill byte
       ratios (one real session exported through the wire codec, one
       real prefix block spilled, per mode);
    2. **throughput** — the same sessioned greedy workload through a
       quantized and an unquantized backend: tokens/sec each;
    3. **quality** — per-member scorecard-style deltas: greedy
       token-agreement fraction (longest common prefix / emitted) and
       exact-match fraction, quantized vs unquantized outputs;
    4. **self-consistency ASSERT** — two independently built quantized
       backends must produce bit-identical outputs (the quantized twin
       of the temp-0 equality gates; the mono==cluster==wire-peer gate
       lives in tier-1 tests/test_quant.py).
    """
    import tempfile

    from quoracle_tpu.models.runtime import QueryRequest, TPUBackend
    from quoracle_tpu.parallel.mesh import pool_sizing
    from quoracle_tpu.serving.fabric import wire
    from quoracle_tpu.serving.handoff import KVHandoff

    member = pool[0]
    long_pre = ("system: shared policy preamble for every session. " * 6)

    def reqs(tag):
        return [QueryRequest(
            member, [{"role": "user",
                      "content": long_pre + f"[{tag} {i}] "
                                 + TASKS[i % len(TASKS)][:64]}],
            temperature=0.0, max_tokens=16, session_id=f"{tag}-{i}")
            for i in range(n_prompts)]

    def run(quant, tag, seed=0):
        b = TPUBackend([member], continuous=True, continuous_chunk=16,
                       host_kv_mb=64, seed=seed,
                       quantize_weights=quant, quantize_kv=quant)
        try:
            t0 = time.monotonic()
            out = b.query(reqs(tag))
            wall = time.monotonic() - t0
            assert all(r.ok for r in out), \
                [r.error for r in out if not r.ok]
            toks = sum(r.usage.completion_tokens for r in out)
            eng = b.engines[member]
            # one real handoff envelope through the wire codec: a
            # directly-sessioned probe of the same preamble, exported
            # via the production hibernate path
            probe = eng.tokenizer.encode(long_pre + " envelope probe",
                                         add_bos=True)
            eng.generate([probe], temperature=0.0, max_new_tokens=4,
                         session_ids=["envprobe"])
            h = KVHandoff()
            env = h.export(eng, "envprobe", member)
            env_bytes = len(wire.encode_envelope(env))
            # one real prefix-block spill file
            spill_bytes = 0
            with tempfile.TemporaryDirectory() as d:
                tier = eng.sessions.tier
                from quoracle_tpu.serving.kvtier import DiskPrefixStore
                tier.disk = DiskPrefixStore(
                    d, eng.kv_signature(), model=member)
                tier._ensure_spill_writer()
                r2 = b.query(reqs(tag + "b"))
                assert all(x.ok for x in r2)
                tier.flush_spills()
                for root, _, files in os.walk(d):
                    spill_bytes += sum(
                        os.path.getsize(os.path.join(root, f))
                        for f in files)
            return {
                "texts": [r.text for r in out],
                "tok_s": round(toks / max(1e-9, wall), 1),
                "env_bytes": env_bytes,
                "spill_bytes": spill_bytes,
                "kv_bytes_per_token": eng.kv_token_pool_bytes(),
                "resident_kv_tokens": eng.sessions.max_tokens,
            }
        finally:
            b.close()

    base = run(False, "q19")
    quant = run(True, "q19")
    quant2 = run(True, "q19")             # fresh build, same config
    self_consistent = quant2["texts"] == quant["texts"]
    assert self_consistent, "quantized runs diverged between builds"

    # per-member scorecard-style deltas: token agreement + exact match
    def lcp_frac(a, b):
        n = min(len(a), len(b))
        i = 0
        while i < n and a[i] == b[i]:
            i += 1
        return i / max(1, max(len(a), len(b)))

    agreements = [lcp_frac(x, y)
                  for x, y in zip(base["texts"], quant["texts"])]
    scorecard = {member: {
        "exact_match_frac": round(
            sum(x == y for x, y in zip(base["texts"], quant["texts"]))
            / n_prompts, 3),
        "token_agreement_frac": round(
            sum(agreements) / n_prompts, 3),
    }}

    # planning view at fixed HBM (the 2x capacity claim, exact rates).
    # Tiny test geometry (hd=16) pays ~25% scale overhead, so the 8B
    # production geometry (hd=128, ~3% overhead) is planned beside it —
    # that row is where "~2x at fixed HBM" is an honest claim.
    plan_b = pool_sizing([member], n_devices=1)
    plan_q = pool_sizing([member], n_devices=1, quantize_kv=True,
                         quantize_weights=True)
    plan8_b = pool_sizing(["xla:llama-3-8b"], n_devices=4)
    plan8_q = pool_sizing(["xla:llama-3-8b"], n_devices=4,
                          quantize_kv=True, quantize_weights=True)
    return {
        "n_prompts": n_prompts,
        "kv_bytes_per_token_bf16": base["kv_bytes_per_token"],
        "kv_bytes_per_token_int8": quant["kv_bytes_per_token"],
        "kv_bytes_ratio": round(quant["kv_bytes_per_token"]
                                / base["kv_bytes_per_token"], 3),
        "resident_kv_tokens_plan_bf16":
            plan_b["members"][0]["resident_kv_tokens"],
        "resident_kv_tokens_plan_int8":
            plan_q["members"][0]["resident_kv_tokens"],
        "resident_kv_tokens_8b_bf16":
            plan8_b["members"][0]["resident_kv_tokens"],
        "resident_kv_tokens_8b_int8":
            plan8_q["members"][0]["resident_kv_tokens"],
        "resident_kv_tokens_8b_ratio": round(
            plan8_q["members"][0]["resident_kv_tokens"]
            / max(1, plan8_b["members"][0]["resident_kv_tokens"]), 3),
        "handoff_bytes_bf16": base["env_bytes"],
        "handoff_bytes_int8": quant["env_bytes"],
        "handoff_bytes_ratio": round(
            quant["env_bytes"] / max(1, base["env_bytes"]), 3),
        "spill_bytes_bf16": base["spill_bytes"],
        "spill_bytes_int8": quant["spill_bytes"],
        "spill_bytes_ratio": round(
            quant["spill_bytes"] / max(1, base["spill_bytes"]), 3),
        "tokens_per_s_bf16": base["tok_s"],
        "tokens_per_s_int8": quant["tok_s"],
        "scorecard_deltas": scorecard,
        "self_consistent": self_consistent,
    }


def measure_fleet(pool, n_interactive: int = 6, n_sessions: int = 3,
                  seed: int = 2026) -> dict:
    """Config 20: the elastic fleet controller on real engines
    (ISSUE 14) — the SAME mixed traffic (``n_interactive`` short
    INTERACTIVE rows timed individually + ``n_sessions`` constrained
    sessioned AGENT rows, two rounds each) through a 3-replica
    prefill/decode QoS cluster twice: a STATIC phase with the boot
    topology frozen, then an ELASTIC phase with scale events forced
    mid-traffic — a policy-driven scale-up (burn ticks through the
    FleetController), a forced drain that live-migrates every resident
    session (the round-2 resumes ride the MIGRATED pages), a re-tier
    flip + flip-back, and a scale-down retirement.

    Reported: goodput (ok completion tokens/s) per phase and the delta
    the scale events cost, sessions migrated/sec through the handoff
    path, the max INTERACTIVE SLO burn observed during the drain/
    re-tier window vs the static phase, drain wall times, and the
    temp-0 equality ASSERT (elastic texts == static texts, bit-for-bit
    — elasticity must be invisible in the output). Detail lands in the
    FLEET sidecar (QUORACLE_BENCH_FLEET)."""
    import jax

    from quoracle_tpu.models.runtime import QueryRequest
    from quoracle_tpu.serving.cluster import ClusterPlane
    from quoracle_tpu.serving.fleet import (
        FleetConfig, FleetController, FleetSignals, ReplicaSignal,
    )
    from quoracle_tpu.serving.qos import Priority

    from quoracle_tpu.sim.workload import bench_fleet_mix

    member = pool[0]
    # traffic sourced from the fleet simulator (ISSUE 16): the
    # interactive/session message mixes come off seeded workload
    # traces — same texts every run, trace digests in the result
    mix = bench_fleet_mix(TASKS, n_interactive, n_sessions, seed=seed)
    inter_msgs = mix["inter_msgs"]
    sess_msgs = mix["sess_msgs"]

    def burn_signals(cluster):
        return FleetSignals(replicas=tuple(
            ReplicaSignal(r.replica_id, r.role,
                          30.0 if r.role == "decode" else 0.0)
            for r in cluster.replicas), slo_burn=2.0)

    def max_burn(cluster) -> float:
        burn = 0.0
        for rep in cluster.replicas:
            slo = getattr(rep.backend, "slo", None)
            if slo is not None:
                burn = max(burn, slo.burn(Priority.INTERACTIVE))
        return burn

    def run_phase(cluster, tag: str, fleet=None) -> dict:
        # warmup pays both paths' compiles so the static phase isn't
        # billed for them
        cluster.query([QueryRequest(member, inter_msgs[0],
                                    temperature=0.0, max_tokens=4)])
        cluster.query([QueryRequest(member, sess_msgs[0],
                                    temperature=0.0, max_tokens=4,
                                    session_id=f"fleet-{tag}-warm",
                                    constrain_json=True)])
        cluster.drop_session(f"fleet-{tag}-warm")
        lat, results, drains = [], [], []
        burn_during_events = 0.0
        t0 = time.monotonic()
        # round 1: establish the sessions, interleaved with
        # interactive rows
        for j, m in enumerate(sess_msgs):
            results += cluster.query([QueryRequest(
                member, m, temperature=0.0, max_tokens=24,
                session_id=f"fleet-{tag}-{j}", constrain_json=True,
                priority=1)])
        for m in inter_msgs[:n_interactive // 2]:
            r0 = time.monotonic()
            results += cluster.query([QueryRequest(
                member, m, temperature=0.0, max_tokens=16, priority=0)])
            lat.append((time.monotonic() - r0) * 1000)
        if fleet is not None:
            # the scale events, mid-traffic: policy scale-up, forced
            # drain (live migration), re-tier round trip, scale-down
            fleet.tick(burn_signals(cluster))
            act = fleet.tick(burn_signals(cluster))
            assert act is not None and act.action == "scale_up", act
            victim = sorted(r.replica_id for r in cluster.replicas
                            if r.role == "decode")[0]
            drains.append(fleet.drain(victim, retire=True,
                                      reason="bench-scale-down"))
            burn_during_events = max(burn_during_events,
                                     max_burn(cluster))
            pre = sorted(r.replica_id for r in cluster.replicas
                         if r.role == "prefill")[-1]
            drains.append(fleet.drain(pre, new_role="decode",
                                      reason="bench-retier"))
            drains.append(fleet.drain(pre, new_role="prefill",
                                      reason="bench-retier-back"))
            burn_during_events = max(burn_during_events,
                                     max_burn(cluster))
        # round 2: resume every session (on its MIGRATED pages in the
        # elastic phase) + the remaining interactive rows
        for j, m in enumerate(sess_msgs):
            results += cluster.query([QueryRequest(
                member, m + [{"role": "assistant", "content": "ok"},
                             {"role": "user", "content": "continue."}],
                temperature=0.0, max_tokens=24,
                session_id=f"fleet-{tag}-{j}", constrain_json=True,
                priority=1)])
        for m in inter_msgs[n_interactive // 2:]:
            r0 = time.monotonic()
            results += cluster.query([QueryRequest(
                member, m, temperature=0.0, max_tokens=16, priority=0)])
            lat.append((time.monotonic() - r0) * 1000)
        wall = time.monotonic() - t0
        for j in range(n_sessions):
            cluster.drop_session(f"fleet-{tag}-{j}")
        ok_tokens = sum(r.usage.completion_tokens for r in results
                        if r.ok)
        lat.sort()
        return {
            "results": results,
            "texts": [r.text if r.ok else None for r in results],
            "wall_s": round(wall, 3),
            "ok_rows": sum(1 for r in results if r.ok),
            "goodput_tok_s": round(ok_tokens / max(1e-9, wall), 1),
            "interactive_p95_ms": round(
                lat[min(len(lat) - 1, int(0.95 * len(lat)))], 1),
            "slo_burn_peak": round(max_burn(cluster), 3),
            "burn_during_events": round(burn_during_events, 3),
            "drains": drains,
        }

    cluster = ClusterPlane.build([member], replicas=3, disaggregate=True,
                                 continuous=True, continuous_chunk=16,
                                 continuous_slots=8, qos=True)
    fleet = FleetController(cluster, FleetConfig(
        min_replicas=1, max_replicas=4, hysteresis_ticks=2,
        cooldown_ticks=0, seed=seed))
    try:
        static = run_phase(cluster, "static")
        elastic = run_phase(cluster, "elastic", fleet=fleet)
        handoff = cluster.handoff.stats()
    finally:
        cluster.close()

    migrated = sum(d["migrated"] for d in elastic["drains"])
    failed = sum(d["failed"] for d in elastic["drains"])
    drain_ms = [d["ms"] for d in elastic["drains"]]
    migrate_wall_s = sum(drain_ms) / 1000.0
    n_chips = max(1, len(jax.devices()))
    temp0_equal = elastic["texts"] == static["texts"]
    result = {
        "n_interactive": n_interactive,
        "n_sessions": n_sessions,
        "seed": seed,
        "sim_trace_digests": [t.digest() for t in mix["traces"]],
        "goodput_tok_s_static": static["goodput_tok_s"],
        "goodput_tok_s_elastic": elastic["goodput_tok_s"],
        "goodput_delta_frac": round(
            1.0 - elastic["goodput_tok_s"]
            / max(1e-9, static["goodput_tok_s"]), 3),
        "goodput_tok_s_chip_static": round(
            static["goodput_tok_s"] / n_chips, 1),
        "goodput_tok_s_chip_elastic": round(
            elastic["goodput_tok_s"] / n_chips, 1),
        "interactive_p95_ms_static": static["interactive_p95_ms"],
        "interactive_p95_ms_elastic": elastic["interactive_p95_ms"],
        "slo_burn_static": static["slo_burn_peak"],
        "slo_burn_during_events": elastic["burn_during_events"],
        "sessions_migrated": migrated,
        "sessions_migrate_failed": failed,
        "sessions_migrated_per_s": round(
            migrated / max(1e-9, migrate_wall_s), 2),
        "drain_ms": drain_ms,
        "drain_ms_max": max(drain_ms) if drain_ms else 0.0,
        "fleet_ledger": fleet.ledger(),
        "handoff": handoff,
        "envelope_leaks": handoff["inflight"],
        "temp0_equal": temp0_equal,
    }
    assert temp0_equal, "config20: elastic texts diverged from static"
    assert handoff["inflight"] == 0, \
        f"config20: leaked handoff envelopes: {handoff}"
    return result


def measure_fleetobs(pool, n_rows: int = 6) -> dict:
    """Config 21: fleet observability (ISSUE 15) — cost and fidelity.

    One prefill+decode FabricPlane over the loopback wire serves the
    SAME ``n_rows`` disaggregated requests twice: tracing OFF (span
    ring detached) then ON — tokens/sec both ways, the overhead delta,
    and the temp-0 bit-equality ASSERT (tracing must be invisible in
    the output). Then one sessioned traced request's
    ``pull_timeline`` yields the TTFT decomposition columns
    (queue/prefill/kv_export/wire/kv_adopt/decode, which sum to the
    door-observed total by construction — asserted), and one
    federation sweep is timed with its fleet-rollup quantiles checked
    against re-merging the scraped states by hand (the lossless-merge
    oracle). Detail lands in the FLEETOBS sidecar
    (QUORACLE_BENCH_FLEETOBS)."""
    from quoracle_tpu.infra import fleetobs
    from quoracle_tpu.infra.telemetry import TRACER
    from quoracle_tpu.models.runtime import QueryRequest
    from quoracle_tpu.serving.cluster import RemoteReplica
    from quoracle_tpu.serving.fabric.frontdoor import FabricPlane
    from quoracle_tpu.serving.fabric.peer import FabricPeer
    from quoracle_tpu.serving.fabric.transport import LoopbackTransport

    member = pool[0]

    def reqs():
        return [QueryRequest(
            member, [{"role": "user",
                      "content": f"[fleetobs {i}] "
                                 + TASKS[i % len(TASKS)][:64]}],
            temperature=0.0, max_tokens=16)
            for i in range(n_rows)]

    peers = [FabricPeer.build([member], role="prefill",
                              replica_id="prefill-0",
                              continuous_chunk=16),
             FabricPeer.build([member], role="decode",
                              replica_id="decode-0",
                              continuous_chunk=16)]
    plane = FabricPlane([
        RemoteReplica(LoopbackTransport(p.handle, p.replica_id))
        for p in peers])

    def phase(tracing: bool):
        if not tracing:
            TRACER.remove_sink(fleetobs.SPANS.record)
        else:
            TRACER.add_sink(fleetobs.SPANS.record)
        # warmup pays the compiles once per phase entry
        plane.query([QueryRequest(member, [{"role": "user",
                                            "content": "warm"}],
                                  temperature=0.0, max_tokens=4)])
        t0 = time.monotonic()
        out = plane.query(reqs())
        wall = time.monotonic() - t0
        assert all(r.ok for r in out), [r.error for r in out]
        tokens = sum(r.usage.completion_tokens for r in out)
        return ([r.text for r in out],
                round(tokens / max(1e-9, wall), 1), round(wall, 3))

    try:
        # alternate the phases and take each mode's MEDIAN: the
        # batcher's wake-poll quantum dwarfs span cost on tiny
        # geometries, so a single pass per mode measures scheduling
        # noise, not tracing (the real-chip run is the meaningful
        # delta; the smoke asserts equality + plumbing)
        runs: dict = {False: [], True: []}
        texts: dict = {False: [], True: []}
        for _ in range(3):
            for mode in (False, True):
                t, tok, wall = phase(tracing=mode)
                runs[mode].append((tok, wall))
                texts[mode].append(t)
        equal = len({tuple(t) for ts in texts.values()
                     for t in ts}) == 1
        assert equal, "config21: temp-0 bits diverged tracing on vs off"
        texts_off = texts[False][0]

        def median_run(mode):
            return sorted(runs[mode])[len(runs[mode]) // 2]

        tok_s_off, wall_off = median_run(False)
        tok_s_on, wall_on = median_run(True)

        # TTFT decomposition for one traced sessioned request
        fleetobs.SPANS.clear()
        sid = "bench-obs-sess"
        t0 = time.monotonic()
        r = plane.query([QueryRequest(
            member, [{"role": "user",
                      "content": "[fleetobs ttft] " + TASKS[0][:64]}],
            temperature=0.0, max_tokens=16, session_id=sid)])[0]
        observed_ms = (time.monotonic() - t0) * 1000
        assert r.ok, r.error
        tl = plane.pull_timeline(session_id=sid)
        assert tl["contiguous"], tl["trace_ids"]
        assert abs(tl["stages_sum_ms"] - tl["total_ms"]) < 0.01, tl

        # federation sweep wall + merged-quantile oracle
        t0 = time.monotonic()
        fed = plane.federated_metrics(max_age_s=0.0)
        fed_wall_ms = (time.monotonic() - t0) * 1000
        states = {p.replica_id: p.obs_metrics()["state"]
                  for p in plane.peers}
        oracle = fleetobs.federate(states)
        probe = "quoracle_sched_admit_wait_ms"
        got, want = fed.quantiles(probe), oracle.quantiles(probe)
        # the door's own series ride in the rollup too (peer="door"),
        # so the count totals differ by a constant factor — quantiles
        # are scale-invariant up to interpolation ulps
        import math
        fed_ok = got.keys() == want.keys() and all(
            math.isclose(got[p], want[p], rel_tol=1e-6)
            for p in got if got[p] is not None)
        assert fed_ok, f"config21: rollup {got} != merged oracle {want}"
        ring = fleetobs.SPANS.stats()
    finally:
        plane.close()
        for p in peers:
            p.close()

    result = {
        "n_rows": n_rows,
        "tokens_per_s_tracing_off": tok_s_off,
        "tokens_per_s_tracing_on": tok_s_on,
        "tracing_overhead_frac": round(
            1.0 - tok_s_on / max(1e-9, tok_s_off), 4),
        "wall_s_off": wall_off,
        "wall_s_on": wall_on,
        "temp0_equal": equal,
        "timeline_total_ms": tl["total_ms"],
        "timeline_observed_ms": round(observed_ms, 2),
        "ttft_stages_ms": tl["stages"],
        "timeline_spans": tl["n_spans"],
        "federation_scrape_ms": round(fed_wall_ms, 2),
        "federation_quantiles_equal_oracle": fed_ok,
        "span_ring": ring,
        "trace_ring_capacity": fleetobs.ring_capacity(),
        "decode_tick_sample": fleetobs.decode_tick_sample(),
    }
    sidecar = os.environ.get("QUORACLE_BENCH_FLEETOBS")
    if sidecar:
        try:
            with open(sidecar, "w") as f:
                json.dump({"metric": "fleetobs", "config21": result,
                           "timeline": tl}, f, indent=1, default=str)
        except OSError as e:
            log(f"config21 sidecar write failed: {e}")
    return result


def measure_sim(seed: int = 2026) -> dict:
    """Config 22: the fleet simulator as a benchmark (ISSUE 16).

    Phases source from the simulator's canonical workload catalog
    (sim/workload.py) instead of hand-rolled loops: each canonical
    trace (diurnal mix, burst storm, agent tree, long-tail ladder) is
    generated from ``seed`` and replayed TWICE through the invariant
    gate at compressed time — the engine-sampled scenarios spot-check a
    sampled subset through a real mock-device ClusterPlane at
    temperature 0. Reported: replay throughput (events per wall
    second) and compression factor per trace, outcome mixes, the
    long-tail tier census, ledger digests (the determinism witness —
    compare across revisions on the same seed), and the gate verdicts,
    which must all pass. Smoke runs scale the long-tail population to
    10k sessions; live runs replay the full 100k. Detail lands in the
    SIM sidecar (QUORACLE_BENCH_SIM)."""
    from quoracle_tpu.sim.gate import SIM_SCENARIOS, run_sim_scenario

    smoke = MAX_NEW <= 16
    out: dict = {"seed": seed, "smoke": smoke, "scenarios": {}}
    events_total = 0
    wall_total = 0.0
    for name in SIM_SCENARIOS:
        scale = (0.1 if smoke and name == "longtail_ladder" else None)
        rep = run_sim_scenario(name, seed=seed, scale=scale)
        ev = rep.evidence
        out["scenarios"][name] = {
            "passed": rep.passed,
            "events": ev["trace"]["events"],
            "sessions": ev["trace"]["sessions"],
            "ledger_digest": ev["ledger"],
            "outcomes": ev["outcomes"],
            "census": ev["census"],
            "samples": ev["samples"],
            "invariants": {r.name: r.ok for r in rep.invariants},
            "wall_s": rep.wall_s,
        }
        # two replays per scenario: both count toward throughput
        events_total += 2 * ev["trace"]["events"]
        wall_total += rep.wall_s
    out["events_total"] = events_total
    out["events_per_s"] = round(events_total / max(1e-9, wall_total), 1)
    out["wall_s"] = round(wall_total, 2)
    out["longtail_sessions"] = \
        out["scenarios"]["longtail_ladder"]["census"]["seen"]
    out["all_passed"] = all(s["passed"]
                            for s in out["scenarios"].values())
    assert out["all_passed"], \
        f"config22: sim gate failed: {out['scenarios']}"
    return out


def measure_quality_overhead(backend, pool,
                             n_decides: int = N_CYCLES) -> dict:
    """Config 12: consensus-quality instrumentation overhead (ISSUE 5).

    ``n_decides`` REAL ConsensusEngine.decide calls over the full pool,
    run twice over the SAME engines: quality OFF (no audit record, no
    scorecard/entropy observations) then quality ON. Decide p50/p95 for
    each phase come from the quoracle_decide_ms histogram COUNT DELTAS
    around the phase (the same numbers GET /metrics scrapes) — the
    on/off ratio is the measured price of the audit layer, which must be
    read-only by construction (temp-0 outcome equality is tier-1-tested;
    this measures the time side). Also reported: the emitted
    entropy/margin of the temp-0 pool's decides and the resulting
    scorecard slice. With QUORACLE_BENCH_QUALITY set, every audit record
    + the scorecards are written there as a sidecar artifact."""
    from quoracle_tpu.consensus.engine import ConsensusConfig, ConsensusEngine
    from quoracle_tpu.consensus.quality import QUALITY
    from quoracle_tpu.infra.telemetry import DECIDE_MS

    def run_phase(quality_on: bool) -> dict:
        eng = ConsensusEngine(backend, ConsensusConfig(
            model_pool=list(pool),
            session_key=f"bench-config12-{'on' if quality_on else 'off'}",
            quality=quality_on))
        dwin = HistWindow(DECIDE_MS)
        records = []
        for i in range(n_decides):
            msgs = {m: [{"role": "system", "content": SYSTEM_PROMPT},
                        {"role": "user",
                         "content": TASKS[(i + 1) % len(TASKS)]}]
                    for m in pool}
            out = eng.decide(msgs)
            if out.audit is not None:
                records.append(out.audit)
            log(f"config12 decide {i} (quality={'on' if quality_on else 'off'}): "
                f"status={out.status} rounds={out.rounds_used}")
        return {"decide_p50_ms": dwin.quantile(0.50),
                "decide_p95_ms": dwin.quantile(0.95),
                "records": records}

    off = run_phase(False)
    on = run_phase(True)
    entropies = [r["entropy_bits"] for r in on["records"]
                 if r.get("entropy_bits") is not None]
    margins = [r["margin"] for r in on["records"]
               if r.get("margin") is not None]
    cards = QUALITY.scorecards()
    result = {
        "n_decides": n_decides,
        "n_members": len(pool),
        "decide_p50_on_ms": on["decide_p50_ms"],
        "decide_p95_on_ms": on["decide_p95_ms"],
        "decide_p50_off_ms": off["decide_p50_ms"],
        "decide_p95_off_ms": off["decide_p95_ms"],
        "overhead_p50_ratio": (
            round(on["decide_p50_ms"] / off["decide_p50_ms"], 3)
            if on["decide_p50_ms"] and off["decide_p50_ms"] else None),
        "entropy_bits_mean": (round(sum(entropies) / len(entropies), 4)
                              if entropies else None),
        "margin_mean": (round(sum(margins) / len(margins), 4)
                        if margins else None),
        "rounds": [r["rounds"] for r in on["records"]],
        "scorecard": {
            spec: {k: cards["members"].get(spec, {}).get(k)
                   for k in ("decides", "agreement_rate", "dissent_rate",
                             "failure_rate", "latency_p50_ms")}
            for spec in pool
        },
    }
    sidecar = os.environ.get("QUORACLE_BENCH_QUALITY")
    if sidecar:
        with open(sidecar, "w") as f:
            json.dump({"summary": result, "records": on["records"],
                       "scorecards": cards}, f)
        log(f"config12 audit records written to {sidecar}")
    return result


def measure_cost(backend, pool, n_decides: int = N_CYCLES) -> dict:
    """Config 23: the chip-economics plane (ISSUE 17) as a benchmark.

    Phase OFF runs real ConsensusEngine decides with the plane disabled
    (``QUORACLE_COST_ACCOUNTING=0`` equivalent), phase ON repeats them
    with attribution + roofline live: the tokens/sec delta is the
    measured price of the plane and the temp-0 decisions must be equal
    (ASSERT — accounting is read-only by construction). The ON window
    reports the per-stage chip-second decomposition (ledger deltas
    around the window, the same numbers GET /api/costs serves),
    chip-ms/decide + tokens/decide from the quoracle_cost_decide_*
    histogram count deltas, the exact-sum invariant restated at bench
    scale, and each compiled program's best observed MFU with its cliff
    count. Last, the sim-calibration loop closes against the LIVE
    profile: fit a CapacityModel from the busiest ledger
    (sim/calibrate.py), record a measured profile by replaying a
    canonical trace under the fit, re-fit from that profile, and gate
    the calibrated replay's per-class TTFT quantiles against the
    measured distribution — the max relative error is the headline
    calibration number. Detail (full /api/costs payload + gate checks)
    lands in the COST sidecar (QUORACLE_BENCH_COST)."""
    from quoracle_tpu.consensus.engine import ConsensusConfig, ConsensusEngine
    from quoracle_tpu.infra import costobs
    from quoracle_tpu.infra.telemetry import (
        COST_DECIDE_CHIP_MS, COST_DECIDE_TOKENS,
    )
    from quoracle_tpu.sim.calibrate import (
        calibrate, fit_capacity, record_profile, ttft_gate,
    )
    from quoracle_tpu.sim.workload import canonical_spec, generate

    def run_phase(tag: str) -> dict:
        eng = ConsensusEngine(backend, ConsensusConfig(
            model_pool=list(pool),
            session_key=f"bench-config23-{tag}"))
        t0 = time.monotonic()
        decisions, tokens, chip_ms = [], 0, 0.0
        for i in range(n_decides):
            msgs = {m: [{"role": "system", "content": SYSTEM_PROMPT},
                        {"role": "user",
                         "content": TASKS[(i + 2) % len(TASKS)]}]
                    for m in pool}
            out = eng.decide(msgs)
            d = out.decision
            decisions.append((d.action, d.params) if d else None)
            tokens += out.completion_tokens
            chip_ms += out.chip_ms
            log(f"config23 decide {i} ({tag}): status={out.status} "
                f"chip_ms={out.chip_ms:.1f}")
        wall = time.monotonic() - t0
        return {"decisions": decisions, "tokens": tokens,
                "chip_ms": round(chip_ms, 3), "wall_s": round(wall, 3),
                "tokens_per_s": round(tokens / max(1e-9, wall), 1)}

    def ledger_marks() -> dict:
        out = {}
        for name, led in costobs.ledgers().items():
            overhead = sum(ns for k, ns in led.cells().items()
                           if k[:4] == costobs.OVERHEAD_KEY)
            out[name] = (led.busy_ns(), led.stage_ns(),
                         led.stage_tokens(), overhead)
        return out

    # warmup pays the pool's compiles so they land in neither phase —
    # the off/on delta must price the accounting plane, not XLA
    ConsensusEngine(backend, ConsensusConfig(
        model_pool=list(pool),
        session_key="bench-config23-warmup")).decide(
        {m: [{"role": "system", "content": SYSTEM_PROMPT},
             {"role": "user", "content": TASKS[2]}] for m in pool})

    # -- 1. accounting off vs on: price of the plane + temp-0 ASSERT ----
    was_on = costobs.enabled()
    costobs.disable()
    try:
        off = run_phase("off")
    finally:
        costobs.enable()
    before = ledger_marks()
    cwin = HistWindow(COST_DECIDE_CHIP_MS)
    twin = HistWindow(COST_DECIDE_TOKENS)
    on = run_phase("on")
    after = ledger_marks()
    if not was_on:
        costobs.disable()

    equal = off["decisions"] == on["decisions"]
    assert equal, \
        "config23: temp-0 decisions diverged accounting off vs on"
    assert off["chip_ms"] == 0.0, "config23: charged while disabled"
    assert on["chip_ms"] > 0.0, "config23: nothing charged while enabled"

    # -- 2. per-stage chip-second decomposition of the ON window --------
    stages: dict = {}
    stage_tokens: dict = {}
    busy_ms = overhead_ms = 0.0
    for name, (busy1, st1, tok1, ov1) in after.items():
        busy0, st0, tok0, ov0 = before.get(name, (0, {}, {}, 0))
        busy_ms += (busy1 - busy0) / 1e6
        overhead_ms += (ov1 - ov0) / 1e6
        for s, ns in st1.items():
            d = ns - st0.get(s, 0)
            if d > 0:
                stages[s] = round(stages.get(s, 0.0) + d / 1e6, 3)
        for s, t in tok1.items():
            d = t - tok0.get(s, 0)
            if d > 0:
                stage_tokens[s] = stage_tokens.get(s, 0) + d
    # the exact-sum invariant restated over the full ledgers (tier-1
    # proves it per charge; the artifact witnesses it at bench scale)
    invariant_ok = all(
        sum(led.cells().values()) == led.busy_ns()
        == sum(led.stage_ns().values())
        for led in costobs.ledgers().values())
    assert invariant_ok, "config23: chip-second sum invariant violated"

    # -- 3. MFU per compiled program: best ratio + cliff count ----------
    mfu: dict = {}
    for member in pool:
        rf = getattr(backend.engines.get(member), "_costobs_roofline",
                     None)
        if rf is None:
            continue
        with rf._lock:
            mfu[rf.model] = {
                f"{stage}/b{bucket}": {"best_mfu": round(st.best, 5),
                                       "cliff_trips": st.trips}
                for (stage, bucket), st in sorted(rf._best.items())}

    # -- 4. sim calibration fitted from the live profile ----------------
    rep = calibrate()
    gate = None
    gate_err = None
    if rep is not None:
        smoke = MAX_NEW <= 16
        trace = generate(canonical_spec(
            "diurnal_mix", seed=2026, scale=0.25 if smoke else 1.0))
        led, measured = record_profile(trace, rep.fitted)
        refit = fit_capacity(led)
        gate = ttft_gate(trace, measured, refit.fitted)
        gate_err = max((c["rel_err"] for c in gate["checks"]),
                       default=0.0)

    result = {
        "n_decides": n_decides,
        "n_members": len(pool),
        "tokens_per_s_accounting_off": off["tokens_per_s"],
        "tokens_per_s_accounting_on": on["tokens_per_s"],
        "accounting_overhead_frac": (
            round(1.0 - on["tokens_per_s"] / off["tokens_per_s"], 4)
            if off["tokens_per_s"] else None),
        "temp0_equal": equal,
        "chip_ms_total_on": on["chip_ms"],
        "chip_ms_per_decide_p50": cwin.quantile(0.50),
        "chip_ms_per_decide_p95": cwin.quantile(0.95),
        "tokens_per_decide_p50": twin.quantile(0.50),
        "by_stage_chip_ms": stages,
        "by_stage_tokens": stage_tokens,
        "window_busy_chip_ms": round(busy_ms, 3),
        "window_overhead_chip_ms": round(overhead_ms, 3),
        "overhead_frac": (round(overhead_ms / busy_ms, 4)
                          if busy_ms else None),
        "sum_invariant_exact": invariant_ok,
        "mfu_best_by_program": mfu,
        "calibration": rep.as_dict() if rep else None,
        "calibration_gate_passed": gate["passed"] if gate else None,
        "calibration_ttft_max_rel_err": gate_err,
    }
    sidecar = os.environ.get("QUORACLE_BENCH_COST")
    if sidecar:
        try:
            with open(sidecar, "w") as f:
                json.dump({"metric": "cost", "config23": result,
                           "gate": gate,
                           "api_costs": costobs.costs_payload()},
                          f, indent=1, default=str)
            log(f"config23 cost detail written to {sidecar}")
        except OSError as e:
            log(f"config23 sidecar write failed: {e}")
    return result


def measure_introspect(backend, pool, n_decides: int = N_CYCLES) -> dict:
    """Config 24: the liveness & hotspot plane (ISSUE 18) as a
    benchmark.

    Three phases of real ConsensusEngine decides: OFF (plane disabled),
    DEFAULT (stall detector + profiler at the default 20 Hz) and
    AGGRESSIVE (10x the sampling rate). The temp-0 decisions must be
    identical across all three (ASSERT — the plane is read-only by
    construction); the tokens/sec deltas price the plane and the
    profiler's SELF-MEASURED overhead fraction is the headline gate:
    ≤ 1% at the default rate. The DEFAULT window also witnesses the
    wait-state invariant (every recorded row's named waits + remainder
    sum exactly to its wall — restated here at bench scale from the
    aggregate totals) and the heartbeat deltas the stall detector
    watches. Detail (full /api/profile payload per phase) lands in the
    INTROSPECT sidecar (QUORACLE_BENCH_INTROSPECT)."""
    from quoracle_tpu.consensus.engine import ConsensusConfig, ConsensusEngine
    from quoracle_tpu.infra import introspect

    def run_phase(tag: str) -> dict:
        eng = ConsensusEngine(backend, ConsensusConfig(
            model_pool=list(pool),
            session_key=f"bench-config24-{tag}"))
        t0 = time.monotonic()
        decisions, tokens = [], 0
        for i in range(n_decides):
            msgs = {m: [{"role": "system", "content": SYSTEM_PROMPT},
                        {"role": "user",
                         "content": TASKS[(i + 3) % len(TASKS)]}]
                    for m in pool}
            out = eng.decide(msgs)
            d = out.decision
            decisions.append((d.action, d.params) if d else None)
            tokens += out.completion_tokens
            log(f"config24 decide {i} ({tag}): status={out.status}")
        wall = time.monotonic() - t0
        return {"decisions": decisions, "tokens": tokens,
                "wall_s": round(wall, 3),
                "tokens_per_s": round(tokens / max(1e-9, wall), 1)}

    # warmup pays the pool's compiles so they land in no phase
    ConsensusEngine(backend, ConsensusConfig(
        model_pool=list(pool),
        session_key="bench-config24-warmup")).decide(
        {m: [{"role": "system", "content": SYSTEM_PROMPT},
             {"role": "user", "content": TASKS[3]}] for m in pool})

    phases: dict = {}
    payloads: dict = {}

    introspect.reset()
    introspect.disable()
    try:
        phases["off"] = run_phase("off")
    finally:
        introspect.reset()

    # watch a heartbeat that advances on every decode step: the engine
    # label is the cfg name (what beat() keys on), not the pool member
    eng0 = backend.engines.get(pool[0])
    label = eng0.cfg.name if eng0 is not None else pool[0]

    for tag, hz in (("default", None), ("aggressive",
                                        10 * introspect.DEFAULT_HZ)):
        introspect.reset()
        introspect.enable()
        introspect.PROFILER.start(hz)
        introspect.STALLS.watch(
            "bench.decides",
            lambda: (True, introspect.heartbeat_count(
                f"engine.tokens:{label}")))
        introspect.STALLS.start()
        try:
            phases[tag] = run_phase(tag)
            phases[tag]["profiler_overhead_frac"] = round(
                introspect.PROFILER.overhead_frac(), 6)
            phases[tag]["profile_samples"] = introspect.PROFILER.samples
            payloads[tag] = introspect.profile_payload()
        finally:
            introspect.shutdown()

    # read-only by construction: temp-0 decisions identical off /
    # default / aggressive
    equal = (phases["off"]["decisions"] == phases["default"]["decisions"]
             == phases["aggressive"]["decisions"])
    assert equal, \
        "config24: temp-0 decisions diverged across introspect phases"

    # the wait invariant at bench scale: the DEFAULT window's aggregate
    # per-state totals are each row's exact decomposition summed, so
    # rows > 0 with totals present witnesses the plane saw real traffic
    waits = payloads["default"]["waits"]
    rows_recorded = sum(v["rows"] for v in waits.values())
    stall_trips = payloads["default"]["stalls"]["trips"]

    off_tps = phases["off"]["tokens_per_s"]
    result = {
        "n_decides": n_decides,
        "n_members": len(pool),
        "temp0_equal": equal,
        "tokens_per_s_off": off_tps,
        "tokens_per_s_default": phases["default"]["tokens_per_s"],
        "tokens_per_s_aggressive": phases["aggressive"]["tokens_per_s"],
        "plane_overhead_frac_default": (
            round(1.0 - phases["default"]["tokens_per_s"] / off_tps, 4)
            if off_tps else None),
        "plane_overhead_frac_aggressive": (
            round(1.0 - phases["aggressive"]["tokens_per_s"] / off_tps,
                  4) if off_tps else None),
        "profiler_overhead_frac_default":
            phases["default"]["profiler_overhead_frac"],
        "profiler_overhead_frac_aggressive":
            phases["aggressive"]["profiler_overhead_frac"],
        "profiler_overhead_gate_1pct":
            phases["default"]["profiler_overhead_frac"] <= 0.01,
        "profile_samples_default": phases["default"]["profile_samples"],
        "wait_rows_recorded": rows_recorded,
        "wait_states_seen": sorted({s for v in waits.values()
                                    for s in v["by_state_ns"]}),
        "stall_trips": stall_trips,
        "heartbeats_default": {
            k: v for k, v in sorted(
                payloads["default"]["heartbeats"].items())},
    }
    sidecar = os.environ.get("QUORACLE_BENCH_INTROSPECT")
    if sidecar:
        try:
            with open(sidecar, "w") as f:
                json.dump({"metric": "introspect", "config24": result,
                           "api_profile_by_phase": payloads},
                          f, indent=1, default=str)
            log(f"config24 introspect detail written to {sidecar}")
        except OSError as e:
            log(f"config24 sidecar write failed: {e}")
    return result


def measure_flywheel(backend, pool, n_rows: int = 6) -> dict:
    """Config 25: the serving flywheel (ISSUE 19) priced end to end.

    One full capture → train → evaluate → promote cycle against the
    pool's first member:

    * **capture overhead** — the same temp-0 rows through the
      continuous self-draft spec path (config 13's isolation choice)
      with the capture plane off vs on: outputs BIT-IDENTICAL
      (ASSERT), tokens/sec delta is the tap's price;
    * **one distillation cycle** — a random-init draft of the member's
      own geometry vs the same init trained on the captured rounds;
      held-out replay acceptance through the REAL verify_chunk path
      before vs after is the headline row;
    * **live promotion** — the trained candidate hot-swapped into the
      serving backend while rows are IN FLIGHT: every in-flight row
      must land ok (swap downtime == 0 ASSERT — drain, never drop),
      and tokens/sec with the promoted draft vs the random incumbent
      is the uplift row. Temp-0 texts stay identical across ALL
      phases (greedy equality holds for ANY draft — the §8 invariant
      the whole loop leans on).

    Detail (capture stats, eval report, promoter ledger) lands in the
    FLYWHEEL sidecar (QUORACLE_BENCH_FLYWHEEL)."""
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp

    from quoracle_tpu.models.generate import GenerateEngine
    from quoracle_tpu.models.runtime import TPUBackend
    from quoracle_tpu.models.tokenizer import get_tokenizer
    from quoracle_tpu.models.transformer import init_params
    from quoracle_tpu.training.capture import CAPTURE, CaptureStore
    from quoracle_tpu.training.evaluate import compare, greedy_equal
    from quoracle_tpu.training.promote import Promoter, PromotionPolicy
    from quoracle_tpu.training.trainer import (
        TrainerConfig, heldout_split, train_from_capture,
    )

    member = pool[0]
    target = backend.engines[member]
    tok = get_tokenizer(member)
    prompts = [
        tok.encode(f"[agent {i}] {TASKS[i % len(TASKS)]}", add_bos=True)
        for i in range(n_rows)]
    cap_dir = tempfile.mkdtemp(prefix="bench-flywheel-")

    def mk_backend() -> TPUBackend:
        return TPUBackend([member], engines=backend.engines,
                          embedder=backend.embedder, continuous=True,
                          continuous_chunk=16, continuous_slots=8,
                          draft_map={member: member}, draft_k=4)

    def serve(b, warm: bool = True) -> dict:
        cb = b._cbatchers[member]
        if warm:    # pays the draft/verify compiles for EVERY prompt
            # bucket outside the window (one cold bucket inside it
            # would swamp the capture-overhead delta with XLA wall)
            for f in [cb.submit(p, temperature=0.0,
                                max_new_tokens=MAX_NEW)
                      for p in prompts]:
                f.result(900)
        t0 = time.monotonic()
        futs = [cb.submit(p, temperature=0.0, max_new_tokens=MAX_NEW)
                for p in prompts]
        gens = [f.result(900) for f in futs]
        wall = time.monotonic() - t0
        toks = sum(g.n_gen_tokens for g in gens)
        return {"texts": [g.text for g in gens],
                "wall_s": round(wall, 3), "tokens": toks,
                "tokens_per_s": round(toks / max(1e-9, wall), 1)}

    # -- phase 1: capture off vs on (self-draft spec serving) -------------
    b = mk_backend()
    try:
        off = serve(b)
    finally:
        b.close()
    CAPTURE.install(cap_dir, budget_mb=64.0)
    try:
        b = mk_backend()
        try:
            on = serve(b)
        finally:
            b.close()
        CAPTURE.store.flush()
        cap_stats = CAPTURE.stats().get("store") or {}
    finally:
        CAPTURE.uninstall()
    assert on["texts"] == off["texts"], \
        "config25: temp-0 outputs diverged with capture on"

    # -- phase 2: one distillation cycle on the captured rounds -----------
    store = CaptureStore(cap_dir, budget_mb=64.0)
    records = list(store.read_all("spec"))
    log(f"config25: {len(records)} captured rounds "
        f"({cap_stats.get('disk_bytes')} bytes)")
    _, held = heldout_split(records, frac=0.25, seed=0)
    held = held[:40]     # bound the replay wall on big captures
    cfg = target.cfg
    cand_init = init_params(cfg, jax.random.PRNGKey(25),
                            dtype=jnp.float32)
    rand_init = init_params(cfg, jax.random.PRNGKey(26),
                            dtype=jnp.float32)
    tcfg = TrainerConfig(steps=40, batch=8, seq=160, lr=1e-3, seed=0,
                         accept_weight=0.25, dp=1)
    t0 = time.monotonic()
    trainer, treport = train_from_capture(cfg, cand_init, store,
                                          tcfg=tcfg)
    train_wall = time.monotonic() - t0
    incumbent = GenerateEngine(cfg, rand_init, target.tokenizer,
                               max_seq=512,
                               prompt_buckets=(64, 128, 256))
    candidate = GenerateEngine(cfg, trainer.params, target.tokenizer,
                               max_seq=512,
                               prompt_buckets=(64, 128, 256))
    report = compare(target, incumbent, candidate, held, max_k=6)
    g_ok = greedy_equal(target, candidate, [prompts[0]], k=4,
                        max_new=24)

    # -- phase 3: live promotion with rows in flight ----------------------
    b = mk_backend()
    try:
        b.swap_draft(member, incumbent, name="rand-incumbent")
        base = serve(b)                     # random-draft baseline
        promoter = Promoter(PromotionPolicy(
            margin_p50=0.01, min_examples=4,
            min_rounds=10 ** 9,             # bench: guard never trips
            require_greedy_equal=True))
        cb = b._cbatchers[member]
        inflight = [cb.submit(p, temperature=0.0,
                              max_new_tokens=MAX_NEW) for p in prompts]
        t0 = time.monotonic()
        res = promoter.promote_backend(
            b, member, lambda: candidate, draft_name="flywheel-cand",
            report=report, greedy_ok=g_ok)
        swap_ms = (time.monotonic() - t0) * 1000
        landed = [f.result(900) for f in inflight]
        dropped = sum(1 for g in landed if not g.text)
        assert res["promoted"], res
        assert dropped == 0, \
            "config25: in-flight rows lost across the hot-swap"
        promoted = serve(b, warm=False)     # trained-draft uplift
        promoter_stats = promoter.stats()
    finally:
        b.close()
    assert promoted["texts"] == off["texts"], \
        "config25: temp-0 outputs diverged after promotion"
    shutil.rmtree(cap_dir, ignore_errors=True)

    result = {
        "n_rows": n_rows,
        "max_new": MAX_NEW,
        "captured_rounds": len(records),
        "capture_bytes": cap_stats.get("disk_bytes"),
        "tokens_per_s_capture_off": off["tokens_per_s"],
        "tokens_per_s_capture_on": on["tokens_per_s"],
        "capture_overhead_frac": (
            round(1.0 - on["tokens_per_s"] / off["tokens_per_s"], 4)
            if off["tokens_per_s"] else None),
        "train_steps": treport["steps_run"],
        "train_wall_s": round(train_wall, 3),
        "final_loss": treport.get("final_loss"),
        "heldout_examples": report["candidate"]["n"],
        "acceptance_p50_before": report["incumbent"]["p50"],
        "acceptance_p50_after": report["candidate"]["p50"],
        "acceptance_margin_p50": report["margin_p50"],
        "greedy_equal": g_ok,
        "promoted": res["promoted"],
        "swap_ms": round(swap_ms, 1),
        "inflight_rows_dropped": dropped,
        "tokens_per_s_incumbent": base["tokens_per_s"],
        "tokens_per_s_promoted": promoted["tokens_per_s"],
        "promotion_uplift": (
            round(promoted["tokens_per_s"] / base["tokens_per_s"], 3)
            if base["tokens_per_s"] else None),
        "temp0_equal": True,                # asserted above, twice
    }
    sidecar = os.environ.get("QUORACLE_BENCH_FLYWHEEL")
    if sidecar:
        try:
            with open(sidecar, "w") as f:
                json.dump({"metric": "flywheel", "config25": result,
                           "capture_stats": cap_stats,
                           "eval_report": report,
                           "promoter": promoter_stats},
                          f, indent=1, default=str)
            log(f"config25 flywheel detail written to {sidecar}")
        except OSError as e:
            log(f"config25 sidecar write failed: {e}")
    return result


def measure_treeobs(backend, pool, n_decides: int = N_CYCLES) -> dict:
    """Config 26: the session-graph plane (ISSUE 20) as a benchmark.

    Two phases of real ConsensusEngine decides under a stamped agent
    tree: OFF (plane disabled) and ON (lineage registered, every
    decide booked to its node). The temp-0 decisions must be identical
    (ASSERT — the plane is read-only by construction); the tokens/sec
    delta prices the bookkeeping. The ON window then re-checks the
    rollup conservation contract on the assembled view (recursive
    subtree totals == flat sums, exact integers), times a fleet-wide
    ``tree_payload`` assembly, and replays the canonical agent-tree
    sim trace through a standalone TreeRegistry to produce the
    critical-path column over every generated tree. Detail (full
    /api/tree view + per-tree sim critical paths) lands in the
    TREEOBS sidecar (QUORACLE_BENCH_TREEOBS)."""
    from quoracle_tpu.consensus.engine import ConsensusConfig, ConsensusEngine
    from quoracle_tpu.infra import treeobs

    def run_phase(tag: str, tree) -> dict:
        eng = ConsensusEngine(backend, ConsensusConfig(
            model_pool=list(pool),
            session_key=f"bench-config26-{tag}",
            tree=tree))
        t0 = time.monotonic()
        decisions, tokens = [], 0
        for i in range(n_decides):
            msgs = {m: [{"role": "system", "content": SYSTEM_PROMPT},
                        {"role": "user",
                         "content": TASKS[(i + 5) % len(TASKS)]}]
                    for m in pool}
            out = eng.decide(msgs)
            d = out.decision
            decisions.append((d.action, d.params) if d else None)
            tokens += out.completion_tokens
            log(f"config26 decide {i} ({tag}): status={out.status}")
        wall = time.monotonic() - t0
        return {"decisions": decisions, "tokens": tokens,
                "wall_s": round(wall, 3),
                "tokens_per_s": round(tokens / max(1e-9, wall), 1)}

    # warmup pays the pool's compiles so they land in no phase
    ConsensusEngine(backend, ConsensusConfig(
        model_pool=list(pool),
        session_key="bench-config26-warmup")).decide(
        {m: [{"role": "system", "content": SYSTEM_PROMPT},
             {"role": "user", "content": TASKS[0]}] for m in pool})

    phases: dict = {}
    treeobs.reset()
    treeobs.disable()
    try:
        phases["off"] = run_phase("off", None)
    finally:
        treeobs.reset()

    treeobs.enable()
    treeobs.register_spawn("bench26-root", tree_id="bench26-tree")
    kid = treeobs.register_spawn("bench26-kid",
                                 parent_id="bench26-root")
    phases["on"] = run_phase("on", kid.to_dict())

    # read-only by construction: temp-0 decisions identical off / on
    equal = phases["off"]["decisions"] == phases["on"]["decisions"]
    assert equal, \
        "config26: temp-0 decisions diverged with treeobs on"

    # fleet-wide assembly wall + the conservation recheck: the
    # assembled view's recursive rollup equals the flat node sums
    # (tree_view asserts it internally; restate the arithmetic here
    # from the emitted rows so the bench record is self-evident)
    t0 = time.monotonic()
    view = treeobs.tree_payload("bench26-tree")
    assembly_ms = (time.monotonic() - t0) * 1000.0
    assert view["conserved"], "config26: rollup conservation broken"
    rows = {n["node_id"]: n for n in view["nodes"]}
    flat = {k: sum(n[k] for n in view["nodes"])
            for k in ("chip_ns", "tokens", "wait_ns")}
    conserved = flat == view["totals"] == \
        rows["bench26-root"]["subtree"]
    assert conserved, "config26: rollup recheck failed"
    booked = rows["bench26-kid"]

    # the critical-path column over the canonical agent-tree sim
    # trace: every generated tree replayed into a standalone registry
    # (modeled decode chip time at the scenario capacity), then viewed
    from quoracle_tpu.sim.gate import SIM_SCENARIOS
    from quoracle_tpu.sim.replay import ReplayDriver
    from quoracle_tpu.sim.workload import (
        canonical_spec, generate, tree_id_of,
    )
    sc = SIM_SCENARIOS["agent_tree"]
    trace = generate(canonical_spec("agent_tree", seed=0))
    ledger = ReplayDriver(trace, capacity=sc.capacity).run()
    reg = treeobs.TreeRegistry()
    by_eid = {e.eid: e for e in trace.events}
    # register parents before children (dot-depth order) so depth
    # derives from the parent record, then book each replayed row
    ctxs: dict = {}
    tree_events = [e for e in trace.events if tree_id_of(e)]
    for e in sorted(tree_events,
                    key=lambda e: (e.session.count("."), e.session)):
        parent = (e.session.rsplit(".", 1)[0]
                  if "." in e.session else None)
        ctxs[e.session] = reg.register_spawn(
            e.session, parent_id=parent, tree_id=tree_id_of(e))
    for r in ledger.rows:
        if not r[9]:
            continue
        chip_ms = 1000.0 * r[8] / sc.capacity.decode_tok_s
        reg.charge_decide(ctxs[by_eid[r[0]].session], chip_ms, r[8])
    tree_ids = sorted({tree_id_of(e) for e in trace.events
                       if tree_id_of(e)})
    sim_paths = []
    for tid in tree_ids:
        v = treeobs.tree_view(tid, [reg.local_state(tid)],
                              registry=reg)
        assert v["conserved"] and not v["orphans"]
        sim_paths.append({
            "tree_id": tid, "n_nodes": v["n_nodes"],
            "max_depth": v["max_depth"],
            "critical_path": v["critical_path"]["node_ids"],
            "critical_path_cost_ns":
                v["critical_path"]["cost_ns"],
            "total_chip_ns": v["totals"]["chip_ns"],
        })
    longest = max(sim_paths,
                  key=lambda p: (len(p["critical_path"]),
                                 p["critical_path_cost_ns"]))

    off_tps = phases["off"]["tokens_per_s"]
    result = {
        "n_decides": n_decides,
        "n_members": len(pool),
        "temp0_equal": equal,
        "tokens_per_s_off": off_tps,
        "tokens_per_s_on": phases["on"]["tokens_per_s"],
        "plane_overhead_frac": (
            round(1.0 - phases["on"]["tokens_per_s"] / off_tps, 4)
            if off_tps else None),
        "conservation_exact": conserved,
        "booked_decides": booked["decides"],
        "booked_chip_ns": booked["chip_ns"],
        "booked_tokens": booked["tokens"],
        "assembly_wall_ms": round(assembly_ms, 3),
        "sim_trees": len(sim_paths),
        "sim_nodes": sum(p["n_nodes"] for p in sim_paths),
        "sim_critical_path_max_len": len(longest["critical_path"]),
        "sim_critical_path_max_cost_ns":
            longest["critical_path_cost_ns"],
        "sim_critical_path_tree": longest["tree_id"],
    }
    sidecar = os.environ.get("QUORACLE_BENCH_TREEOBS")
    if sidecar:
        try:
            with open(sidecar, "w") as f:
                json.dump({"metric": "treeobs", "config26": result,
                           "api_tree_view": view,
                           "sim_critical_paths": sim_paths},
                          f, indent=1, default=str)
            log(f"config26 treeobs detail written to {sidecar}")
        except OSError as e:
            log(f"config26 sidecar write failed: {e}")
    return result


def base_payload() -> dict:
    """Every key the artifact can carry, pre-filled null — ANY exit path
    prints this line with whatever was actually measured, so degraded runs
    stay indexable by the same keys as full ones."""
    return {
        "metric": "consensus_round_p50_latency",
        "value": None,
        "unit": "ms",
        "vs_baseline": None,
        "error": None,
        "device_unavailable": False,
        "configs_measured": [],
        "skipped": [],
        "failed": [],
        "aborted": [],
        "n_chips": None,
        "device_kind": None,
        "pool": None,
        "avg_model_gb": None,
        "config1_p50_ms": None,
        "config1_steady_tps": None,
        "decode_hbm_gbps": None,
        "decode_hbm_utilization": None,
        "prefill_mfu": None,
        "tokens_per_sec_per_chip": None,
        "round1_p50_ms": None,
        "refinement_p50_ms": None,
        "steady_tokens_per_sec_per_chip": None,
        "prefill_s_total": None,
        "decode_s_total": None,
        "kv_residency_prefill_savings": None,
        "config3_p50_ms": None,
        "config3_steady_tps": None,
        "config4_embed_retrieve_p50_ms": None,
        "config5_p50_ms": None,
        "config5_steady_tps": None,
        "config6_p50_ms": None,
        "config6_tps": None,
        "config6_n_agents": None,
        "config6_tps_vs_config1": None,
        "config6_p50_vs_config1": None,
        # config 8 — radix prefix cache (models/prefix_cache.py): K-row
        # consensus-style fan-out (shared prompt, distinct suffixes).
        # rows2k_prefill << rows2k_prompt is the cache working: rows 2..K
        # prefilled only their suffix. config8_prefix_cache carries the
        # engine's cumulative hit/miss/evict/COW counters.
        "config8_prefix_rows": None,
        "config8_row1_prefill_tokens": None,
        "config8_rows2k_prefill_tokens": None,
        "config8_rows2k_prompt_tokens": None,
        "config8_prefix_cache_hits": None,
        "config8_prefix_cache_hit_tokens": None,
        "config8_prefix_cache": None,
        # config 9 — consensus serving telemetry (infra/telemetry.py):
        # N real ConsensusEngine.decide calls; round/decide latency
        # p50/p95 come from the quoracle_round_ms / quoracle_decide_ms
        # histogram COUNT DELTAS (the same numbers GET /metrics scrapes),
        # rows decompose each decide into prefill vs decode ms.
        "config9_n_decides": None,
        "config9_n_rounds": None,
        "config9_round_p50_ms": None,
        "config9_round_p95_ms": None,
        "config9_decide_p50_ms": None,
        "config9_decide_p95_ms": None,
        "config9_prefill_ms_total": None,
        "config9_decode_ms_total": None,
        "config9_rows": None,
        # config 10 — resource observability (ISSUE 3): live HBM headroom,
        # compile-registry hit rate, and scheduler queue health sampled
        # during a sustained continuous-batching consensus load; the
        # admission-wait p95 comes from the
        # quoracle_sched_admit_wait_ms histogram count deltas.
        "config10_n_samples": None,
        "config10_hbm_headroom_min_frac": None,
        "config10_hbm_bytes_in_use_max": None,
        "config10_compile_hit_rate": None,
        "config10_compile_storms": None,
        "config10_queue_depth_p95": None,
        "config10_admit_wait_p95_ms": None,
        "config10_watchdog_stalls": None,
        # config 11 — serving QoS under sustained 4x overload (ISSUE 4):
        # INTERACTIVE tail vs the unloaded p50 with QoS on/off, BATCH
        # throughput price, shed rate + structured-reject accounting
        # (no_silent_drops: submitted == retired + shed + failed).
        "config11_overload_x": None,
        "config11_unloaded_interactive_p50_ms": None,
        "config11_interactive_p95_on_ms": None,
        "config11_interactive_p95_off_ms": None,
        "config11_interactive_p95_ratio_on": None,
        "config11_interactive_p95_ratio_off": None,
        "config11_batch_tps_on": None,
        "config11_batch_tps_off": None,
        "config11_shed_rate": None,
        "config11_shed_flightrec_events": None,
        "config11_goodput_on": None,
        "config11_goodput_off": None,
        "config11_no_silent_drops": None,
        # config 12 — consensus-quality instrumentation (ISSUE 5): decide
        # p50/p95 with scorecards/audit on vs off (histogram count
        # deltas), and the emitted entropy/margin for the temp-0 pool;
        # full audit records land in the QUALITY sidecar.
        "config12_n_decides": None,
        "config12_decide_p50_on_ms": None,
        "config12_decide_p95_on_ms": None,
        "config12_decide_p50_off_ms": None,
        "config12_decide_p95_off_ms": None,
        "config12_overhead_p50_ratio": None,
        "config12_entropy_bits_mean": None,
        "config12_margin_mean": None,
        # config 7 realized row (ISSUE 6): ceiling × the TRAINED draft's
        # measured acceptance (latest SPECULATIVE artifact), greedy-equal
        # asserted from that artifact's record.
        "config7_trained_acceptance": None,
        "config7_realized_speedup": None,
        # config 13 — speculative decoding in the continuous+QoS serving
        # path (ISSUE 6): constrained consensus-shaped rows through the
        # shared decode loop with speculation on vs off — decode
        # ms/token, tokens/round, acceptance p50, fallback count, and
        # the temp-0 on/off equality gate. Per-row detail lands in the
        # SPEC sidecar (QUORACLE_BENCH_SPEC).
        "config13_ms_per_token_on": None,
        "config13_ms_per_token_off": None,
        "config13_speedup": None,
        "config13_tokens_per_round": None,
        "config13_acceptance_p50": None,
        "config13_fallbacks": None,
        "config13_temp0_equal": None,
        # config 14 — tiered KV (ISSUE 7): session hibernation vs
        # destruction at fixed HBM — restore-latency p95 vs cold
        # re-prefill p95, demote/restore counts, resident capacity with
        # the host tier, and the temp-0 on/off equality gate. Detail in
        # the KV sidecar (QUORACLE_BENCH_KV).
        "config14_restore_p95_ms": None,
        "config14_cold_prefill_p95_ms": None,
        "config14_restore_vs_cold_speedup": None,
        "config14_demotes": None,
        "config14_restores": None,
        "config14_hbm_session_capacity": None,
        "config14_tiered_session_capacity": None,
        "config14_temp0_equal": None,
        # config 15 — unified ragged serving kernel (ISSUE 8): mixed
        # short-interactive + long-agent traffic through continuous
        # batching, unified vs gather over the same engine —
        # tokens/sec/chip, steady-state compile count, real-vs-padded
        # chunk tokens (what raggedness reclaims), decode HBM high-water
        # delta, and the temp-0 equality gate. Detail in the RAGGED
        # sidecar (QUORACLE_BENCH_RAGGED).
        "config15_tokens_per_s_chip_unified": None,
        "config15_tokens_per_s_chip_gather": None,
        "config15_speedup": None,
        "config15_compile_misses_unified": None,
        "config15_compile_misses_gather": None,
        "config15_pad_waste_unified": None,
        "config15_pad_waste_gather": None,
        "config15_padded_tokens_reclaimed": None,
        "config15_peak_hbm_delta_unified": None,
        "config15_peak_hbm_delta_gather": None,
        "config15_temp0_equal": None,
        # config 16 — disaggregated serving plane (ISSUE 10): mixed
        # interactive+agent traffic, one monolithic continuous replica
        # vs a 2-replica prefill/decode cluster on the same device
        # budget — tokens/sec/chip, interactive TTFT p95, handoff p95
        # vs the cold re-prefill it replaces, and the temp-0 equality
        # gate. Detail in the CLUSTER sidecar (QUORACLE_BENCH_CLUSTER).
        "config16_tokens_per_s_chip_mono": None,
        "config16_tokens_per_s_chip_disagg": None,
        "config16_ttft_p95_ms_mono": None,
        "config16_ttft_p95_ms_disagg": None,
        "config16_handoff_p95_ms": None,
        "config16_cold_prefill_p95_ms": None,
        "config16_temp0_equal": None,
        # config 17 — chaos plane (ISSUE 11): the storm scenario's fault
        # mix on real engines, chaos on vs off at the same offered load
        # over a 3-replica prefill/decode cluster — goodput delta,
        # interactive p95 during recovery (a decode replica dies
        # mid-phase; signals drop; restores fail), and the
        # machine-checked invariant verdicts. Detail in the CHAOS
        # sidecar (QUORACLE_BENCH_CHAOS).
        "config17_goodput_tok_s_off": None,
        "config17_goodput_tok_s_on": None,
        "config17_goodput_delta_frac": None,
        "config17_interactive_p95_ms_off": None,
        "config17_interactive_p95_ms_on": None,
        "config17_faults_fired": None,
        "config17_replicas_replaced": None,
        "config17_invariants_pass": None,
        # config 18 — cross-host cluster fabric (ISSUE 12): the same
        # disaggregated traffic through an in-process ClusterPlane vs
        # a prefill+decode FabricPlane over the loopback wire (handoff
        # p95 + serialization overhead, temp-0 equality ASSERT), fleet
        # prefix hit rate cold-start with/without prefixd, and front-
        # door throughput at N loopback peers. Detail in the FABRIC
        # sidecar (QUORACLE_BENCH_FABRIC).
        "config18_handoff_p95_ms_inprocess": None,
        "config18_handoff_adopt_p95_ms_wire": None,
        "config18_wire_overhead_ms_per_row": None,
        "config18_prefix_hit_frac_with_prefixd": None,
        "config18_prefix_hit_frac_without": None,
        "config18_router_rows_per_s": None,
        "config18_temp0_equal": None,
        # config 19 — quantized serving (ISSUE 13): int8 weights + int8
        # KV pages vs the bf16 baseline — exact per-token byte rates,
        # planned resident tokens at fixed HBM, MEASURED handoff/spill
        # byte ratios, tokens/sec both modes, per-member scorecard-style
        # agreement deltas, and a self-consistency ASSERT (two quantized
        # builds bit-identical). Detail in the QUANT sidecar
        # (QUORACLE_BENCH_QUANT).
        "config19_kv_bytes_ratio": None,
        "config19_resident_kv_tokens_plan_bf16": None,
        "config19_resident_kv_tokens_plan_int8": None,
        "config19_handoff_bytes_ratio": None,
        "config19_spill_bytes_ratio": None,
        "config19_tokens_per_s_bf16": None,
        "config19_tokens_per_s_int8": None,
        "config19_agreement_frac": None,
        "config19_self_consistent": None,
        # config 20 — elastic fleet controller (ISSUE 14): the same
        # mixed traffic through a 3-replica prefill/decode QoS cluster
        # with a static topology vs scale events forced mid-traffic
        # (policy scale-up, forced drain with live session migration,
        # re-tier round trip, scale-down retirement) — goodput during
        # scale events vs static, sessions migrated/sec through the
        # handoff path, SLO burn during the drain/re-tier window, and
        # the temp-0 equality ASSERT (elasticity invisible in the
        # output). Detail in the FLEET sidecar (QUORACLE_BENCH_FLEET).
        "config20_goodput_tok_s_static": None,
        "config20_goodput_tok_s_elastic": None,
        "config20_goodput_delta_frac": None,
        "config20_slo_burn_static": None,
        "config20_slo_burn_during_events": None,
        "config20_sessions_migrated": None,
        "config20_sessions_migrated_per_s": None,
        "config20_drain_ms_max": None,
        "config20_envelope_leaks": None,
        "config20_temp0_equal": None,
        "cycles": None,
        "rounds_per_cycle": None,
        "max_new_tokens": None,
        "constrained_json": None,
        "sessions": None,
        "checkpoints": None,
        "overlapped_members": None,
    }


def _env_deadline(default: float = 2400.0) -> float:
    """BENCH_DEADLINE_S, tolerating malformed values — a bad env var must
    not crash before the artifact harness exists."""
    raw = os.environ.get("BENCH_DEADLINE_S", "")
    try:
        return float(raw) if raw else default
    except ValueError:
        print(f"ignoring malformed BENCH_DEADLINE_S={raw!r}",
              file=sys.stderr, flush=True)
        return default


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--profile", metavar="DIR", default=None,
                    help="capture a JAX/XLA profiler trace of one measured "
                         "config-2 cycle into DIR (view with "
                         "tensorboard/xprof; SURVEY §5 tracing)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-scale end-to-end smoke (CPU-friendly): same "
                         "code path, meaningless numbers")
    ap.add_argument("--deadline", type=float, default=_env_deadline(),
                    help="soft wall-clock budget (s): configs past it are "
                         "skipped, partial results still emitted")
    args = ap.parse_args()

    global SCALE, FAMILIES, N_CYCLES, MAX_NEW
    if args.smoke:
        SCALE, FAMILIES, N_CYCLES, MAX_NEW = \
            "tiny", ["llama", "gemma"], 1, 16

    payload = base_payload()
    deadline_at = time.monotonic() + args.deadline

    # Hard backstop: a device call that hangs past the soft deadline gets
    # interrupted in the main thread and we still print the artifact.
    def _alarm(signum, frame):
        raise BenchDeadline(f"hard deadline ({args.deadline + 300:.0f}s)")
    try:
        signal.signal(signal.SIGALRM, _alarm)
        signal.alarm(int(args.deadline + 300))
    except (ValueError, OSError):         # non-main thread / exotic host
        pass

    try:
        _run(args, payload, deadline_at)
    except BenchDeadline as e:
        payload["error"] = payload["error"] or f"deadline: {e}"
        log(f"DEADLINE: {e}")
    except Exception as e:                # noqa: BLE001 — artifact > trace
        import traceback
        payload["error"] = payload["error"] or f"{type(e).__name__}: {e}"
        log(traceback.format_exc())
    finally:
        signal.alarm(0)
        print(json.dumps(payload), flush=True)
    sys.exit(1 if payload["error"] else 0)


def _run(args, payload: dict, deadline_at: float) -> None:
    """The measurement flow; fills ``payload`` incrementally so the caller
    can emit a partial artifact on any failure."""
    import jax
    if args.smoke:
        jax.config.update("jax_platforms", "cpu")
    platform = jax.devices()[0].platform
    if platform != "tpu" and not args.smoke:
        payload.update(device_unavailable=True,
                       error=f"no TPU: jax found platform {platform!r} "
                             f"(pass --smoke for the CPU code-path check)")
        log(payload["error"])
        return

    from quoracle_tpu.models.loader import register_hf_checkpoint
    from quoracle_tpu.models.runtime import TPUBackend

    from quoracle_tpu.utils.compile_cache import enable_compilation_cache
    log(f"persistent compilation cache: {enable_compilation_cache()}")

    devs = jax.devices()
    n_chips = len(devs)
    kind = getattr(devs[0], "device_kind", "unknown")
    peak_gbps = next((v for k, v in PEAK_HBM_GBPS.items() if k in kind), None)
    peak_tflops = next((v for k, v in PEAK_BF16_TFLOPS.items()
                        if k in kind), None)
    log(f"devices: {devs} (kind={kind!r})")
    payload.update(n_chips=n_chips, device_kind=kind)

    dirs = ensure_checkpoints()
    pool = []
    for d in dirs:
        cfg = register_hf_checkpoint(d)
        pool.append(f"xla:{cfg.name}")
    log(f"pool: {pool}")
    payload["pool"] = pool

    t0 = time.monotonic()
    # overlap=True even on ONE chip: async dispatch pipelines each member's
    # host-side work (tokenize, splice, pack, detok) against another
    # member's device compute — measured 2156 -> 1452 ms config-2 p50 on a
    # single v5e. Phase attribution under overlap blurs (one member's wall
    # fence waits behind another's device work), so the rooflines below
    # come from config 1 (single member = clean fences).
    backend = TPUBackend(pool, overlap=True)
    log(f"backend ready (weights loaded) in {time.monotonic() - t0:.1f}s")

    # bf16 bytes the decode loop streams per emitted token, per member
    param_bytes = {}
    for spec in pool:
        e = backend.engines[spec]
        param_bytes[spec] = sum(
            int(p.size) * p.dtype.itemsize
            for p in jax.tree.leaves(e.params))
    log("param bytes: " + ", ".join(f"{s}: {b / 1e9:.2f} GB"
                                    for s, b in param_bytes.items()))

    # warmup: compile each member's (prefill, decode) buckets for every
    # measured shape — the B=1 rounds (configs 1-2) AND config 3's
    # batch-of-3 rows per member. TWO full cycles: a growing conversation
    # crosses prompt/cache shape buckets in later rounds, and a bucket
    # first seen mid-measurement costs a 15-20s XLA compile inside a
    # measured round (the per-round medians below are robust to stragglers,
    # but covering the buckets up front keeps the tail honest too).
    #
    # ALL first compiles run one member at a time, with a log line around
    # each, so a failure names its member. The serial loop covers
    # every measured bucket per member (full growing-conversation cycle,
    # longest task, config 3's batch-of-3 rows); serializing costs nothing
    # (compiles dominate; overlap saves no compile time). The single
    # overlapped cycle after it then exercises the measured overlap path
    # with every graph already cached.
    t0 = time.monotonic()
    for m in pool:
        log(f"warmup compile [{m}] ...")
        t1 = time.monotonic()
        run_cycle(backend, [m], f"warmup-{m}", TASKS[0])
        run_cycle(backend, [m], f"warmup2-{m}", max(TASKS, key=len))
        run_cycle(backend, [m], f"warmup3-{m}", TASKS[0], n_agents=3,
                  rounds=1)
        log(f"warmup compile [{m}] ok in {time.monotonic() - t1:.1f}s")
    run_cycle(backend, pool, "warmup", TASKS[0])
    log(f"warmup (compiles) {time.monotonic() - t0:.1f}s")

    if args.profile:
        # one traced cycle AFTER warmup: steady-state device timeline with
        # prefill/decode/grammar ops attributed, no compile noise
        with jax.profiler.trace(args.profile):
            run_cycle(backend, pool, "profiled", TASKS[1])
        log(f"profiler trace written to {args.profile}")

    # Per-config guard: a config failing records the error and, when it
    # smells device-fatal, stops measuring; everything already measured
    # still ships.
    state = {"fatal": False}

    def guard(name, fn):
        if state["fatal"]:
            log(f"{name}: aborted (device lost earlier in the run)")
            payload["aborted"].append(name)
            return None
        if time.monotonic() > deadline_at:
            log(f"{name}: skipped (soft deadline)")
            payload["skipped"].append(name)
            return None
        try:
            r = fn()
            payload["configs_measured"].append(name)
            return r
        except BenchDeadline:
            raise
        except Exception as e:          # noqa: BLE001 — partial artifact
            import traceback
            log(traceback.format_exc())
            payload["error"] = (payload["error"]
                                or f"{name}: {type(e).__name__}: {e}")
            payload["failed"].append(name)
            if "UNAVAILABLE" in str(e) or "DEADLINE" in str(e).upper():
                state["fatal"] = True
                payload["device_unavailable"] = True
            return None

    cfg1 = guard("config1",
                 lambda: measure_config(backend, [pool[0]], "config1"))
    cfg2 = guard("config2", lambda: measure_config(backend, pool, "config2"))
    cfg3 = guard("config3", lambda: measure_config(
        backend, pool, "config3", n_agents=3, rounds=1))
    cfg4 = guard("config4", lambda: measure_embed_retrieval(backend))
    if cfg4:
        log(f"config4: {cfg4}")

    def continuous_config():
        # shares the already-loaded engines; only the dispatch layer
        # changes (decode-level continuous batching, models/scheduler.py)
        backend6 = TPUBackend(pool, engines=backend.engines,
                              embedder=backend.embedder, continuous=True)
        try:
            return measure_continuous(backend6, pool[0])
        finally:
            for cb in backend6._cbatchers.values():
                cb.close()

    cfg6 = guard("config6", continuous_config)
    if cfg6:
        log(f"config6: {cfg6}")

    def speculative_config():
        # config 7: speculative decoding CEILING on the first member.
        # Self-draft (draft == target) makes acceptance ~total, isolating
        # the mechanism's hardware question: how much faster is one
        # K-token verify chunk than K single-token decode steps on this
        # deployment. Batch-1 decode streams full weights per token
        # (decode roofline above); the verify chunk reads them once per K
        # tokens — but costs ~2 host dispatches per round where the
        # vanilla decode scan is ONE dispatch per 128 tokens, so the
        # measurement decides which effect dominates (models/speculative.py; realized speedup with a real
        # trained draft = this ceiling x its acceptance rate).
        from quoracle_tpu.models.speculative import SpeculativeDecoder
        eng = backend.engines[pool[0]]
        tok = eng.tokenizer
        dec = SpeculativeDecoder(eng.cfg, eng.params, eng.cfg, eng.params,
                                 tok, k=6, max_seq=eng.max_seq)
        prompt = tok.encode(TASKS[0], add_bos=True)
        eng.generate([prompt], temperature=0.0, max_new_tokens=MAX_NEW)
        dec.generate(prompt, temperature=0.0,
                     max_new_tokens=MAX_NEW)          # compile warmup
        van_ms, spec_ms, acc, tpr = [], [], [], []
        for _ in range(3):
            t0 = time.monotonic()
            r = eng.generate([prompt], temperature=0.0,
                             max_new_tokens=MAX_NEW)[0]
            van_ms.append((time.monotonic() - t0) * 1000
                          / max(1, r.n_gen_tokens))
            t0 = time.monotonic()
            s = dec.generate(prompt, temperature=0.0,
                             max_new_tokens=MAX_NEW)
            spec_ms.append((time.monotonic() - t0) * 1000
                           / max(1, s.n_gen_tokens))
            acc.append(s.acceptance_rate)
            tpr.append(s.tokens_per_round)
        out = {
            "vanilla_ms_per_token": statistics.median(van_ms),
            "speculative_ms_per_token": statistics.median(spec_ms),
            "ceiling_speedup": statistics.median(van_ms)
            / max(1e-9, statistics.median(spec_ms)),
            "acceptance_rate": statistics.median(acc),
            "tokens_per_round": statistics.median(tpr),
            "k": 6,
        }
        # Realized trained-draft row (ISSUE 6): the self-draft above is
        # the mechanism CEILING; the realized speedup multiplies in the
        # TRAINED draft's measured acceptance from the latest committed
        # SPECULATIVE artifact (tools/train_draft.py), whose greedy
        # bit-equality record is asserted before use — an artifact whose
        # draft ever diverged from vanilla decode must not feed the
        # projection.
        arts = sorted(glob.glob(os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "SPECULATIVE_r*.json")))
        if arts:
            try:
                with open(arts[-1]) as f:
                    rec = json.load(f)
                eq_a, eq_b = (rec.get("greedy_equal") or "0/1").split("/")
                assert eq_a == eq_b, \
                    f"trained draft not greedy-equal: {rec['greedy_equal']}"
                trained_acc = float(rec["value"])
                out.update({
                    "trained_artifact": os.path.basename(arts[-1]),
                    "trained_acceptance": trained_acc,
                    "trained_greedy_equal": rec.get("greedy_equal"),
                    # expected emitted/round at the artifact's K, times
                    # the per-chunk cost advantage the ceiling measured
                    "realized_speedup": round(
                        out["ceiling_speedup"] * trained_acc, 3),
                })
            except Exception as e:          # noqa: BLE001 — optional row
                out["trained_artifact_error"] = repr(e)
        return out

    cfg7 = guard("config7", speculative_config)
    if cfg7:
        log(f"config7: {cfg7}")

    def prefix_cache_config():
        # config 8: RADIX PREFIX CACHE (models/prefix_cache.py) on the
        # consensus fan-out shape — K fresh agents share one built
        # system+task prompt and differ only in a short per-agent suffix,
        # each under its own session, all in ONE batched query. The
        # engine's intra-batch wave split prefills the shared prefix once
        # (row 1); rows 2..K adopt the freshly cached pages and prefill
        # only their suffix. Reported numbers are per-row prefilled-token
        # counts (prompt - cached) plus the cache's own hit/miss/evict
        # counter deltas, so the artifact shows the reuse directly.
        from quoracle_tpu.models.runtime import QueryRequest
        member = pool[0]
        eng = backend.engines[member]
        K = 3
        system = ("You are an autonomous agent in a recursive agent tree. "
                  "Decide your next action. Respond ONLY with a JSON "
                  'object {"action": ..., "params": {...}, "reasoning": '
                  '..., "wait": false}. Available actions: send_message, '
                  "todo, wait, orient, spawn_child, execute_shell, "
                  "file_read, file_write, fetch_web, call_api, "
                  "batch_sync, dismiss_child. " + TASKS[0])
        before = dict(eng.sessions.prefix_cache.stats())
        reqs = [QueryRequest(
            model_spec=member,
            messages=[{"role": "system", "content": system},
                      {"role": "user",
                       "content": f"[agent {k}] {TASKS[(k + 1) % len(TASKS)]}"}],
            temperature=0.0, max_tokens=MAX_NEW,
            session_id=f"pc8-a{k}", constrain_json=True)
            for k in range(K)]
        results = backend.query(reqs)
        for r in results:
            assert r.ok, f"config8 row failed: {r.error}"
        after = eng.sessions.prefix_cache.stats()
        for k in range(K):
            backend.drop_session(f"pc8-a{k}")
        rows = [{"prompt_tokens": r.usage.prompt_tokens,
                 "cached_tokens": r.cached_tokens,
                 "prefilled_tokens": r.usage.prompt_tokens
                 - r.cached_tokens} for r in results]
        return {
            "rows": rows,
            "n_rows": K,
            "row1_prefill_tokens": rows[0]["prefilled_tokens"],
            "rows2k_prefill_tokens": sum(r["prefilled_tokens"]
                                         for r in rows[1:]),
            "rows2k_prompt_tokens": sum(r["prompt_tokens"]
                                        for r in rows[1:]),
            "cache_delta": {k: after[k] - before.get(k, 0)
                            for k in after},
            "cache_stats": after,
        }

    cfg8 = guard("config8", prefix_cache_config)
    if cfg8:
        log(f"config8: {cfg8}")

    # config 9 must run while ``backend`` is still alive — the vision
    # config below frees it to make HBM room for the VLM pool
    cfg9 = guard("config9",
                 lambda: measure_consensus_telemetry(backend, pool))
    if cfg9:
        log(f"config9: {cfg9}")

    # config 10 shares backend's engines too (continuous dispatch layer
    # over them) — it must also run before the vision config frees them
    cfg10 = guard("config10",
                  lambda: measure_resource_observability(backend, pool))
    if cfg10:
        log(f"config10: {cfg10}")

    # config 11 also rides backend's engines (fresh continuous dispatch
    # layers over them, QoS off then on) — before the vision config
    cfg11 = guard("config11",
                  lambda: measure_qos_overload(backend, pool))
    if cfg11:
        log(f"config11: {cfg11}")

    # config 12 rides backend's engines directly (plain batched dispatch,
    # quality layer off then on) — before the vision config frees them
    cfg12 = guard("config12",
                  lambda: measure_quality_overhead(backend, pool))
    if cfg12:
        log(f"config12: {cfg12}")

    # config 13 rides backend's engines too (continuous+QoS dispatch with
    # a self-draft speculator on vs off) — before the vision config
    cfg13 = guard("config13",
                  lambda: measure_spec_continuous(backend, pool))
    if cfg13:
        log(f"config13: {cfg13}")
        sidecar = os.environ.get("QUORACLE_BENCH_SPEC")
        if sidecar:
            try:
                with open(sidecar, "w") as f:
                    json.dump({"metric": "speculative_continuous",
                               "config13": cfg13}, f, indent=1)
                log(f"config13 spec detail written to {sidecar}")
            except OSError as e:
                log(f"config13 sidecar write failed: {e}")

    # config 14 rides backend's engines too (tier attach/detach around
    # the measured phases) — before the vision config frees them
    cfg14 = guard("config14",
                  lambda: measure_kv_tiering(backend, pool))
    if cfg14:
        log(f"config14: {cfg14}")
        sidecar = os.environ.get("QUORACLE_BENCH_KV")
        if sidecar:
            try:
                with open(sidecar, "w") as f:
                    json.dump({"metric": "kv_tiering",
                               "config14": cfg14}, f, indent=1)
                log(f"config14 kv detail written to {sidecar}")
            except OSError as e:
                log(f"config14 sidecar write failed: {e}")

    # config 15 rides backend's engines too (unified-vs-gather phases over
    # the same continuous dispatch layer) — before the vision config
    cfg15 = guard("config15",
                  lambda: measure_ragged_serving(backend, pool))
    if cfg15:
        log(f"config15: {cfg15}")
        sidecar = os.environ.get("QUORACLE_BENCH_RAGGED")
        if sidecar:
            try:
                with open(sidecar, "w") as f:
                    json.dump({"metric": "ragged_serving",
                               "config15": cfg15}, f, indent=1)
                log(f"config15 ragged detail written to {sidecar}")
            except OSError as e:
                log(f"config15 sidecar write failed: {e}")

    # config 16 builds its own 2-replica cluster (fresh engine sets —
    # replicas never share a page pool by design) and reuses backend's
    # engines for the monolithic phase — before the vision config
    cfg16 = guard("config16",
                  lambda: measure_cluster_disagg(backend, pool))
    if cfg16:
        log(f"config16: {cfg16}")
        sidecar = os.environ.get("QUORACLE_BENCH_CLUSTER")
        if sidecar:
            try:
                with open(sidecar, "w") as f:
                    json.dump({"metric": "cluster_disagg",
                               "config16": cfg16}, f, indent=1)
                log(f"config16 cluster detail written to {sidecar}")
            except OSError as e:
                log(f"config16 sidecar write failed: {e}")

    # config 17 builds its own 3-replica cluster (chaos must be free to
    # kill a replica without touching backend's engines) — before the
    # vision config frees the checkpoints
    cfg17 = guard("config17", lambda: measure_chaos_storm(pool))
    if cfg17:
        log(f"config17: {cfg17}")
        sidecar = os.environ.get("QUORACLE_BENCH_CHAOS")
        if sidecar:
            try:
                with open(sidecar, "w") as f:
                    json.dump({"metric": "chaos_storm",
                               "config17": cfg17}, f, indent=1)
                log(f"config17 chaos detail written to {sidecar}")
            except OSError as e:
                log(f"config17 sidecar write failed: {e}")

    # config 18 builds its own peers (fresh engine sets per "process" —
    # the loopback fabric is the multi-process topology in one process)
    cfg18 = guard("config18", lambda: measure_fabric(pool))
    if cfg18:
        log(f"config18: {cfg18}")
        sidecar = os.environ.get("QUORACLE_BENCH_FABRIC")
        if sidecar:
            try:
                with open(sidecar, "w") as f:
                    json.dump({"metric": "fabric",
                               "config18": cfg18}, f, indent=1)
                log(f"config18 fabric detail written to {sidecar}")
            except OSError as e:
                log(f"config18 sidecar write failed: {e}")

    # config 20 builds its own 3-replica cluster (the fleet must be
    # free to retire/re-tier replicas without touching backend's
    # engines) — before the vision config frees the checkpoints
    cfg20 = guard("config20", lambda: measure_fleet(pool))
    if cfg20:
        log(f"config20: {cfg20}")
        sidecar = os.environ.get("QUORACLE_BENCH_FLEET")
        if sidecar:
            try:
                with open(sidecar, "w") as f:
                    json.dump({"metric": "fleet",
                               "config20": cfg20}, f, indent=1)
                log(f"config20 fleet detail written to {sidecar}")
            except OSError as e:
                log(f"config20 sidecar write failed: {e}")

    # config 21 builds its own loopback peers (fleet observability:
    # tracing on/off phases + the federation sweep need a fabric front
    # door, not the shared backend); the sidecar is written inside
    # measure_fleetobs (QUORACLE_BENCH_FLEETOBS) with timeline detail
    cfg21 = guard("config21", lambda: measure_fleetobs(pool))
    if cfg21:
        log(f"config21: {cfg21}")

    # config 22 is device-light by design (the fleet simulator replays
    # its canonical traces on a tiny mock-device plane): it sources its
    # phases from sim/workload.py instead of hand-rolled loops
    cfg22 = guard("config22", lambda: measure_sim())
    if cfg22:
        log(f"config22: {cfg22}")
        sidecar = os.environ.get("QUORACLE_BENCH_SIM")
        if sidecar:
            try:
                with open(sidecar, "w") as f:
                    json.dump({"metric": "sim",
                               "config22": cfg22}, f, indent=1)
                log(f"config22 sim detail written to {sidecar}")
            except OSError as e:
                log(f"config22 sidecar write failed: {e}")

    # config 23 measures the chip-economics plane itself (ISSUE 17) on
    # the shared backend: accounting off vs on over real decides (temp-0
    # ASSERT), per-stage chip-second decomposition + MFU-per-program
    # bests for the ON window, and the sim-calibration loop fitted from
    # the live ledger profile; the sidecar (QUORACLE_BENCH_COST) carries
    # the full /api/costs payload + the TTFT gate checks
    cfg23 = guard("config23", lambda: measure_cost(backend, pool))
    if cfg23:
        log(f"config23: {cfg23}")

    # config 24 measures the liveness & hotspot plane itself (ISSUE 18)
    # on the shared backend: introspect off vs default vs aggressive
    # sampling over real decides (temp-0 ASSERT), the profiler's
    # self-measured overhead gated at 1% for the default rate, and the
    # wait-state/heartbeat evidence; the sidecar
    # (QUORACLE_BENCH_INTROSPECT) carries /api/profile per phase
    cfg24 = guard("config24", lambda: measure_introspect(backend, pool))
    if cfg24:
        log(f"config24: {cfg24}")

    # config 25 turns the serving flywheel once (ISSUE 19): capture
    # on/off overhead with the temp-0 ASSERT, a distillation cycle's
    # held-out replay acceptance before/after, and a live hot-swap
    # promotion with in-flight rows (downtime == 0 ASSERT); the sidecar
    # (QUORACLE_BENCH_FLYWHEEL) carries capture stats + the full eval
    # report + the promoter ledger
    cfg25 = guard("config25", lambda: measure_flywheel(backend, pool))
    if cfg25:
        log(f"config25: {cfg25}")

    # config 26 prices the session-graph plane (ISSUE 20): treeobs
    # off/on tokens-per-second over real decides under a stamped
    # lineage (temp-0 ASSERT — the plane is observed-only), the exact
    # rollup-conservation recheck on the assembled /api/tree view plus
    # its assembly wall, and the critical-path column over the
    # canonical agent-tree sim trace; the sidecar
    # (QUORACLE_BENCH_TREEOBS) carries the full view + per-tree paths
    cfg26 = guard("config26", lambda: measure_treeobs(backend, pool))
    if cfg26:
        log(f"config26: {cfg26}")

    # config 19 builds its own backends (quantized vs not must not share
    # engines — the whole point is two independent numeric regimes)
    cfg19 = guard("config19", lambda: measure_quant(pool))
    if cfg19:
        log(f"config19: {cfg19}")
        sidecar = os.environ.get("QUORACLE_BENCH_QUANT")
        if sidecar:
            try:
                with open(sidecar, "w") as f:
                    json.dump({"metric": "quant",
                               "config19": cfg19}, f, indent=1)
                log(f"config19 quant detail written to {sidecar}")
            except OSError as e:
                log(f"config19 sidecar write failed: {e}")

    def vision_config():
        # config 5: vision pool — free the trio's HBM first (weights + KV
        # page pools), then serve llama + the VLM checkpoint with an
        # image-carrying task. The VLM member runs the ViT tower inside
        # the prefill jit.
        import gc
        nonlocal backend
        first_member = pool[0]
        backend = None
        gc.collect()
        vlm_dir = ensure_checkpoints(families=["vlm"])[0]
        vcfg = register_hf_checkpoint(vlm_dir)
        pool5 = [first_member, f"xla:{vcfg.name}"]
        log(f"config5 pool: {pool5}")
        t0 = time.monotonic()
        backend5 = TPUBackend(pool5, overlap=True)
        log(f"vision backend ready in {time.monotonic() - t0:.1f}s")
        img = bench_image_b64()
        run_cycle(backend5, pool5, "warmup5", TASKS[0], image_b64=img)
        cfg5 = measure_config(backend5, pool5, "config5", image_b64=img)
        del backend5
        gc.collect()
        return cfg5

    cfg5 = guard("config5", vision_config)

    # Decode-phase roofline: every decoded token streams the member's full
    # bf16 weights from HBM (batch 1). Computed from CONFIG 1 (single
    # member): with members overlapping, config 2's per-engine wall fences
    # include time spent waiting behind other members' device work, which
    # would underreport bandwidth. MEDIAN over rounds, not totals: a round
    # that first touches a new shape bucket pays a one-off XLA compile
    # inside its decode fence, and a total-based rate would report that as
    # bandwidth collapse.
    avg_param_gb = sum(param_bytes.values()) / len(param_bytes) / 1e9
    payload["avg_model_gb"] = round(avg_param_gb, 2)
    if cfg1:
        b0 = param_bytes[pool[0]]
        per_round_bw = [
            s["gen_tokens"] * b0 / 1e9 / s["decode_s"]
            for s in cfg1["rounds"] if s["decode_s"] > 0]
        bw_gbps = statistics.median(per_round_bw) if per_round_bw else 0.0
        util = bw_gbps / peak_gbps if peak_gbps else None
        # Prefill MFU: forward FLOPs ≈ 2 · params · tokens actually
        # prefilled (suffix after KV residency), against the chip's bf16
        # peak. With the session splice resident prefixes cover ~70% of
        # prompts, so measured chunks are a few hundred tokens — small
        # enough that fixed dispatch overhead, not the MXU, bounds this
        # number (see BASELINE.md). FLOPs = 2 per param per token;
        # params = b0 / 2 bytes-per-bf16-param.
        n_params0 = b0 / 2
        per_round_mfu = [
            s["prefill_tokens"] * 2 * n_params0
            / s["prefill_s"] / (peak_tflops * 1e12)
            for s in cfg1["rounds"]
            if s["prefill_s"] > 0] if peak_tflops else []
        mfu = statistics.median(per_round_mfu) if per_round_mfu else None
        payload.update({
            "config1_p50_ms": round(cfg1["p50_round_ms"], 1),
            "config1_steady_tps": round(cfg1["steady_tokens_per_sec"], 1),
            "decode_hbm_gbps": round(bw_gbps, 1),
            "decode_hbm_utilization": round(util, 3) if util else None,
            "prefill_mfu": round(mfu, 3) if mfu else None,
        })
    if cfg2:
        p50 = cfg2["p50_round_ms"]
        residency_saved = 1.0 - (cfg2["prefill_tokens"]
                                 / max(1, cfg2["prompt_tokens"]))
        payload.update({
            "value": round(p50, 1),
            "vs_baseline": round(HOSTED_BASELINE_MS / p50, 2),
            "tokens_per_sec_per_chip": round(
                cfg2["tokens_per_sec"] / max(1, n_chips), 1),
            "round1_p50_ms": round(cfg2["p50_round1_ms"], 1),
            "refinement_p50_ms": round(cfg2["p50_refine_ms"], 1),
            "steady_tokens_per_sec_per_chip": round(
                cfg2["steady_tokens_per_sec"] / max(1, n_chips), 1),
            "prefill_s_total": round(cfg2["prefill_s"], 2),
            "decode_s_total": round(cfg2["decode_s"], 2),
            "kv_residency_prefill_savings": round(residency_saved, 3),
        })
    if cfg3:
        payload.update({
            "config3_p50_ms": round(cfg3["p50_round_ms"], 1),
            "config3_steady_tps": round(cfg3["steady_tokens_per_sec"], 1),
        })
    if cfg4:
        payload["config4_embed_retrieve_p50_ms"] = round(
            cfg4["p50_embed_retrieve_ms"], 1)
    if cfg5:
        payload.update({
            "config5_p50_ms": round(cfg5["p50_round_ms"], 1),
            "config5_steady_tps": round(cfg5["steady_tokens_per_sec"], 1),
        })
    if cfg7:
        payload.update({
            "config7_speculative_ceiling": round(
                cfg7["ceiling_speedup"], 2),
            "config7_vanilla_ms_per_token": round(
                cfg7["vanilla_ms_per_token"], 2),
            "config7_spec_ms_per_token": round(
                cfg7["speculative_ms_per_token"], 2),
            "config7_acceptance": round(cfg7["acceptance_rate"], 3),
            "config7_tokens_per_round": round(
                cfg7["tokens_per_round"], 2),
            "config7_trained_acceptance": cfg7.get("trained_acceptance"),
            "config7_realized_speedup": cfg7.get("realized_speedup"),
        })
    if cfg6:
        payload.update({
            "config6_p50_ms": round(cfg6["p50_round_ms"], 1),
            "config6_tps": round(cfg6["tokens_per_sec"], 1),
            "config6_n_agents": cfg6["n_agents"],
        })
        if cfg1:
            # the VERDICT r4 item-4 acceptance ratios, computed in-artifact
            payload["config6_tps_vs_config1"] = round(
                cfg6["tokens_per_sec"]
                / max(1e-9, cfg1["steady_tokens_per_sec"]), 2)
            payload["config6_p50_vs_config1"] = round(
                cfg6["p50_round_ms"] / max(1e-9, cfg1["p50_round_ms"]), 2)
    if cfg8:
        payload.update({
            "config8_prefix_rows": cfg8["n_rows"],
            "config8_row1_prefill_tokens": cfg8["row1_prefill_tokens"],
            "config8_rows2k_prefill_tokens":
                cfg8["rows2k_prefill_tokens"],
            "config8_rows2k_prompt_tokens":
                cfg8["rows2k_prompt_tokens"],
            "config8_prefix_cache_hits":
                cfg8["cache_delta"].get("hits", 0),
            "config8_prefix_cache_hit_tokens":
                cfg8["cache_delta"].get("hit_tokens", 0),
            "config8_prefix_cache": cfg8["cache_stats"],
        })
    if cfg9:
        payload.update({
            "config9_n_decides": cfg9["n_decides"],
            "config9_n_rounds": cfg9["n_rounds"],
            "config9_round_p50_ms": cfg9["round_p50_ms"],
            "config9_round_p95_ms": cfg9["round_p95_ms"],
            "config9_decide_p50_ms": cfg9["decide_p50_ms"],
            "config9_decide_p95_ms": cfg9["decide_p95_ms"],
            "config9_prefill_ms_total": cfg9["prefill_ms_total"],
            "config9_decode_ms_total": cfg9["decode_ms_total"],
            "config9_rows": cfg9["rows"],
        })
    if cfg11:
        payload.update({
            "config11_overload_x": cfg11["overload_x"],
            "config11_unloaded_interactive_p50_ms":
                cfg11["unloaded_interactive_p50_ms"],
            "config11_interactive_p95_on_ms":
                cfg11["qos_on"]["interactive_p95_ms"],
            "config11_interactive_p95_off_ms":
                cfg11["qos_off"]["interactive_p95_ms"],
            "config11_interactive_p95_ratio_on":
                cfg11["interactive_p95_ratio_on"],
            "config11_interactive_p95_ratio_off":
                cfg11["interactive_p95_ratio_off"],
            "config11_batch_tps_on":
                cfg11["qos_on"]["batch_tokens_per_s"],
            "config11_batch_tps_off":
                cfg11["qos_off"]["batch_tokens_per_s"],
            "config11_shed_rate": cfg11["shed_rate"],
            "config11_shed_flightrec_events":
                cfg11["shed_flightrec_events"],
            "config11_goodput_on":
                cfg11["qos_on"]["goodput_tokens_per_retired_row"],
            "config11_goodput_off":
                cfg11["qos_off"]["goodput_tokens_per_retired_row"],
            "config11_no_silent_drops": cfg11["no_silent_drops"],
        })
    if cfg12:
        payload.update({
            "config12_n_decides": cfg12["n_decides"],
            "config12_decide_p50_on_ms": cfg12["decide_p50_on_ms"],
            "config12_decide_p95_on_ms": cfg12["decide_p95_on_ms"],
            "config12_decide_p50_off_ms": cfg12["decide_p50_off_ms"],
            "config12_decide_p95_off_ms": cfg12["decide_p95_off_ms"],
            "config12_overhead_p50_ratio": cfg12["overhead_p50_ratio"],
            "config12_entropy_bits_mean": cfg12["entropy_bits_mean"],
            "config12_margin_mean": cfg12["margin_mean"],
        })
    if cfg13:
        payload.update({
            "config13_ms_per_token_on": cfg13["ms_per_token_on"],
            "config13_ms_per_token_off": cfg13["ms_per_token_off"],
            "config13_speedup": cfg13["speedup"],
            "config13_tokens_per_round": cfg13["tokens_per_round"],
            "config13_acceptance_p50": cfg13["acceptance_p50"],
            "config13_fallbacks": cfg13["fallbacks"],
            "config13_temp0_equal": cfg13["temp0_equal"],
        })
    if cfg14:
        payload.update({
            "config14_restore_p95_ms": cfg14["restore_p95_ms"],
            "config14_cold_prefill_p95_ms":
                cfg14["cold_prefill_p95_ms"],
            "config14_restore_vs_cold_speedup":
                cfg14["restore_vs_cold_speedup"],
            "config14_demotes": cfg14["demotes"],
            "config14_restores": cfg14["restores"],
            "config14_hbm_session_capacity":
                cfg14["hbm_session_capacity"],
            "config14_tiered_session_capacity":
                cfg14["tiered_session_capacity"],
            "config14_temp0_equal": cfg14["temp0_equal"],
        })
    if cfg15:
        payload.update({
            "config15_tokens_per_s_chip_unified":
                cfg15["tokens_per_s_chip_unified"],
            "config15_tokens_per_s_chip_gather":
                cfg15["tokens_per_s_chip_gather"],
            "config15_speedup": cfg15["speedup"],
            "config15_compile_misses_unified":
                cfg15["compile_misses_unified"],
            "config15_compile_misses_gather":
                cfg15["compile_misses_gather"],
            "config15_pad_waste_unified": cfg15["pad_waste_unified"],
            "config15_pad_waste_gather": cfg15["pad_waste_gather"],
            "config15_padded_tokens_reclaimed":
                cfg15["padded_tokens_reclaimed"],
            "config15_peak_hbm_delta_unified":
                cfg15["peak_hbm_delta_unified"],
            "config15_peak_hbm_delta_gather":
                cfg15["peak_hbm_delta_gather"],
            "config15_temp0_equal": cfg15["temp0_equal"],
        })
    if cfg16:
        payload.update({
            "config16_tokens_per_s_chip_mono":
                cfg16["tokens_per_s_chip_mono"],
            "config16_tokens_per_s_chip_disagg":
                cfg16["tokens_per_s_chip_disagg"],
            "config16_ttft_p95_ms_mono": cfg16["ttft_p95_ms_mono"],
            "config16_ttft_p95_ms_disagg": cfg16["ttft_p95_ms_disagg"],
            "config16_handoff_p95_ms": cfg16["handoff_p95_ms"],
            "config16_cold_prefill_p95_ms":
                cfg16["cold_prefill_p95_ms"],
            "config16_temp0_equal": cfg16["temp0_equal"],
        })
    if cfg17:
        payload.update({
            "config17_goodput_tok_s_off": cfg17["goodput_tok_s_off"],
            "config17_goodput_tok_s_on": cfg17["goodput_tok_s_on"],
            "config17_goodput_delta_frac":
                cfg17["goodput_delta_frac"],
            "config17_interactive_p95_ms_off":
                cfg17["interactive_p95_ms_off"],
            "config17_interactive_p95_ms_on":
                cfg17["interactive_p95_ms_on"],
            "config17_faults_fired": cfg17["faults_fired"],
            "config17_replicas_replaced": cfg17["replicas_replaced"],
            "config17_invariants_pass": cfg17["invariants_pass"],
        })
    if cfg18:
        payload.update({
            "config18_handoff_p95_ms_inprocess":
                cfg18["handoff_p95_ms_inprocess"],
            "config18_handoff_adopt_p95_ms_wire":
                cfg18["handoff_adopt_p95_ms_wire"],
            "config18_wire_overhead_ms_per_row":
                cfg18["wire_overhead_ms_per_row"],
            "config18_prefix_hit_frac_with_prefixd":
                cfg18["prefix_hit_frac_with_prefixd"],
            "config18_prefix_hit_frac_without":
                cfg18["prefix_hit_frac_without"],
            "config18_router_rows_per_s": cfg18["router_rows_per_s"],
            "config18_temp0_equal": cfg18["temp0_equal"],
        })
    if cfg19:
        member19 = next(iter(cfg19["scorecard_deltas"]))
        payload.update({
            "config19_kv_bytes_ratio": cfg19["kv_bytes_ratio"],
            "config19_resident_kv_tokens_plan_bf16":
                cfg19["resident_kv_tokens_plan_bf16"],
            "config19_resident_kv_tokens_plan_int8":
                cfg19["resident_kv_tokens_plan_int8"],
            "config19_handoff_bytes_ratio":
                cfg19["handoff_bytes_ratio"],
            "config19_spill_bytes_ratio": cfg19["spill_bytes_ratio"],
            "config19_tokens_per_s_bf16": cfg19["tokens_per_s_bf16"],
            "config19_tokens_per_s_int8": cfg19["tokens_per_s_int8"],
            "config19_agreement_frac":
                cfg19["scorecard_deltas"][member19][
                    "token_agreement_frac"],
            "config19_self_consistent": cfg19["self_consistent"],
        })
    if cfg20:
        payload.update({
            "config20_goodput_tok_s_static":
                cfg20["goodput_tok_s_static"],
            "config20_goodput_tok_s_elastic":
                cfg20["goodput_tok_s_elastic"],
            "config20_goodput_delta_frac":
                cfg20["goodput_delta_frac"],
            "config20_slo_burn_static": cfg20["slo_burn_static"],
            "config20_slo_burn_during_events":
                cfg20["slo_burn_during_events"],
            "config20_sessions_migrated": cfg20["sessions_migrated"],
            "config20_sessions_migrated_per_s":
                cfg20["sessions_migrated_per_s"],
            "config20_drain_ms_max": cfg20["drain_ms_max"],
            "config20_envelope_leaks": cfg20["envelope_leaks"],
            "config20_temp0_equal": cfg20["temp0_equal"],
        })
    if cfg21:
        payload.update({
            "config21_tokens_per_s_tracing_off":
                cfg21["tokens_per_s_tracing_off"],
            "config21_tokens_per_s_tracing_on":
                cfg21["tokens_per_s_tracing_on"],
            "config21_tracing_overhead_frac":
                cfg21["tracing_overhead_frac"],
            "config21_ttft_stages_ms": cfg21["ttft_stages_ms"],
            "config21_timeline_total_ms": cfg21["timeline_total_ms"],
            "config21_federation_scrape_ms":
                cfg21["federation_scrape_ms"],
            "config21_federation_quantiles_equal_oracle":
                cfg21["federation_quantiles_equal_oracle"],
            "config21_temp0_equal": cfg21["temp0_equal"],
        })
    if cfg22:
        payload.update({
            "config22_all_passed": cfg22["all_passed"],
            "config22_events_total": cfg22["events_total"],
            "config22_events_per_s": cfg22["events_per_s"],
            "config22_longtail_sessions": cfg22["longtail_sessions"],
            "config22_ledger_digests": {
                name: s["ledger_digest"]
                for name, s in cfg22["scenarios"].items()},
        })
    if cfg23:
        payload.update({
            "config23_tokens_per_s_accounting_off":
                cfg23["tokens_per_s_accounting_off"],
            "config23_tokens_per_s_accounting_on":
                cfg23["tokens_per_s_accounting_on"],
            "config23_accounting_overhead_frac":
                cfg23["accounting_overhead_frac"],
            "config23_chip_ms_per_decide_p50":
                cfg23["chip_ms_per_decide_p50"],
            "config23_by_stage_chip_ms": cfg23["by_stage_chip_ms"],
            "config23_overhead_frac": cfg23["overhead_frac"],
            "config23_sum_invariant_exact":
                cfg23["sum_invariant_exact"],
            "config23_calibration_gate_passed":
                cfg23["calibration_gate_passed"],
            "config23_calibration_ttft_max_rel_err":
                cfg23["calibration_ttft_max_rel_err"],
            "config23_temp0_equal": cfg23["temp0_equal"],
        })
    if cfg24:
        payload.update({
            "config24_tokens_per_s_off": cfg24["tokens_per_s_off"],
            "config24_tokens_per_s_default":
                cfg24["tokens_per_s_default"],
            "config24_tokens_per_s_aggressive":
                cfg24["tokens_per_s_aggressive"],
            "config24_plane_overhead_frac_default":
                cfg24["plane_overhead_frac_default"],
            "config24_profiler_overhead_frac_default":
                cfg24["profiler_overhead_frac_default"],
            "config24_profiler_overhead_gate_1pct":
                cfg24["profiler_overhead_gate_1pct"],
            "config24_wait_rows_recorded":
                cfg24["wait_rows_recorded"],
            "config24_wait_states_seen": cfg24["wait_states_seen"],
            "config24_stall_trips": cfg24["stall_trips"],
            "config24_temp0_equal": cfg24["temp0_equal"],
        })
    if cfg25:
        payload.update({
            "config25_captured_rounds": cfg25["captured_rounds"],
            "config25_capture_overhead_frac":
                cfg25["capture_overhead_frac"],
            "config25_acceptance_p50_before":
                cfg25["acceptance_p50_before"],
            "config25_acceptance_p50_after":
                cfg25["acceptance_p50_after"],
            "config25_acceptance_margin_p50":
                cfg25["acceptance_margin_p50"],
            "config25_promoted": cfg25["promoted"],
            "config25_swap_ms": cfg25["swap_ms"],
            "config25_inflight_rows_dropped":
                cfg25["inflight_rows_dropped"],
            "config25_promotion_uplift": cfg25["promotion_uplift"],
            "config25_temp0_equal": cfg25["temp0_equal"],
        })
    if cfg26:
        payload.update({
            "config26_temp0_equal": cfg26["temp0_equal"],
            "config26_tokens_per_s_off": cfg26["tokens_per_s_off"],
            "config26_tokens_per_s_on": cfg26["tokens_per_s_on"],
            "config26_plane_overhead_frac":
                cfg26["plane_overhead_frac"],
            "config26_conservation_exact":
                cfg26["conservation_exact"],
            "config26_assembly_wall_ms": cfg26["assembly_wall_ms"],
            "config26_sim_trees": cfg26["sim_trees"],
            "config26_sim_nodes": cfg26["sim_nodes"],
            "config26_sim_critical_path_max_len":
                cfg26["sim_critical_path_max_len"],
            "config26_sim_critical_path_max_cost_ns":
                cfg26["sim_critical_path_max_cost_ns"],
        })
    if cfg10:
        payload.update({
            "config10_n_samples": cfg10["n_samples"],
            "config10_hbm_headroom_min_frac":
                cfg10["hbm_headroom_min_frac"],
            "config10_hbm_bytes_in_use_max":
                cfg10["hbm_bytes_in_use_max"],
            "config10_compile_hit_rate": cfg10["compile_hit_rate"],
            "config10_compile_storms": cfg10["compile_storms"],
            "config10_queue_depth_p95": cfg10["queue_depth_p95"],
            "config10_admit_wait_p95_ms": cfg10["admit_wait_p95_ms"],
            "config10_watchdog_stalls": cfg10["watchdog_stalls"],
        })
    log(json.dumps({"config1": cfg1, "config2": cfg2, "config3": cfg3,
                    "config4": cfg4, "config5": cfg5, "config6": cfg6,
                    "config7": cfg7, "config8": cfg8, "config9": cfg9,
                    "config10": cfg10, "config11": cfg11,
                    "config12": cfg12, "config13": cfg13,
                    "config14": cfg14, "config15": cfg15},
                   indent=1, default=str))
    payload.update({
        "cycles": N_CYCLES,
        "rounds_per_cycle": ROUNDS_PER_CYCLE,
        "max_new_tokens": MAX_NEW,
        "constrained_json": True,
        "sessions": True,
        "checkpoints": True,
        "overlapped_members": True,
        # r5: cross-session prefix sharing is live — config 3's agents
        # adopt each other's system-prompt KV (shows up as residency)
        "prefix_sharing": True,
    })


if __name__ == "__main__":
    main()
