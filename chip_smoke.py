#!/usr/bin/env python3
"""The quickest proof that the serving path still starts on the chip.

One process — server and client together, because a chip belongs to one
process at a time — starts the server the way a user does
(``quoracle_tpu.cli serve --backend tpu --pool …``, through
``cli.start_server``), serving ONE member at Mistral-7B's published widths
with seeded random weights:

  default     ``xla:mistral-7b-l16`` — depth cut to 16 layers, what one
              16 GB v5e holds beside its page pool (models/config.py);
  --chips 4   ``xla:mistral-7b`` at full depth, ``--tp 4`` on a four-chip
              host through ``pool_submeshes``.

It then sends the kinds of request the system really makes and checks what
comes back by the repo's own means:

  agent    a profile (``POST /api/profiles``) whose capability groups admit
           no shell, file, API or spawn action, a grove whose hard rule
           blocks every remaining action but ``wait``, and one task
           (``POST /api/tasks``) whose root agent must complete three
           consensus decides under the real system prompt — the grammar
           makes random weights propose valid actions — each woken by
           ``POST /api/messages``;
  direct   ``backend.query`` rows at temperature 0: a greedy row (twice —
           equal), a grammar-constrained row, a K-row fan-out over one
           prompt (all equal), and a second round on one session id that
           must report ``cached_tokens > 0``;
  kernels  outside any timing, each Pallas kernel the run dispatched
           against its ``*_ref`` twin on the chip, at the run's geometry.

Exits non-zero — and prints no result line — when JAX finds no TPU, when the
package is not beside it, or when any phase failed. Otherwise the last line
of stdout is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import tempfile
import time
import urllib.request

T_START = time.monotonic()
SPECS = {1: "xla:mistral-7b-l16", 4: "xla:mistral-7b"}
N_DECIDES = 3
FANOUT_K = 3
DIRECT_MAX_TOKENS = 48
# The whole run must end inside the driver's 1200 s, compilation included.
AGENT_PHASE_TIMEOUT_S = 700.0
# The grove blocks every action the empty capability-group set still admits
# but `wait`: the only action whose schema has no required parameter, so
# the only one random weights can propose validly — and it touches nothing.
GROVE_MD = """---
name: chip-smoke
description: chip_smoke.py — the root agent may only wait
version: "1.0"
topology:
  root: root
  edges: []
governance:
  hard_rules:
    - type: action_block
      actions: [{blocked}]
      message: "chip smoke: random weights decide nothing but wait"
      scope: [root]
---
"""


class Phase:
    """One phase's requests (HTTP calls, backend rows, kernel comparisons)
    sent / succeeded / failed, and every failed request or expectation."""

    def __init__(self, name: str):
        self.name = name
        self.sent = self.succeeded = 0
        self.errors: list[str] = []

    def expect(self, ok: bool, what: str) -> bool:
        if not ok:
            self.errors.append(what)
            print(f"[{self.name}] FAILED: {what}", file=sys.stderr,
                  flush=True)
        return bool(ok)

    def request(self, ok: bool, what: str) -> bool:
        self.sent += 1
        self.succeeded += bool(ok)
        return self.expect(ok, what)

    def http(self, url: str, path: str, body: dict | None = None,
             want: int = 200) -> tuple[bool, dict]:
        data = None if body is None else json.dumps(body).encode()
        req = urllib.request.Request(
            url + path, data=data,
            headers={"Content-Type": "application/json"} if data else {})
        try:
            with urllib.request.urlopen(req, timeout=90) as resp:
                code, payload = resp.status, json.loads(resp.read() or b"{}")
        except urllib.error.HTTPError as e:
            code, payload = e.code, {"error": e.read().decode(
                errors="replace")}
        ok = self.request(code == want,
                          f"{'POST' if data else 'GET'} {path} -> {code} "
                          f"{payload if code != want else ''}")
        return ok, payload

    def summary(self) -> dict:
        return {"sent": self.sent, "succeeded": self.succeeded,
                "failed": self.sent - self.succeeded, "errors": self.errors}


def agent_phase(url: str, spec: str, grove_dir: str) -> tuple[Phase, dict]:
    """One agent task over HTTP; N_DECIDES completed consensus decides."""
    ph = Phase("agent")
    ok, _ = ph.http(url, "/api/profiles", {
        "name": "chip-smoke", "model_pool": [spec],
        "capability_groups": [],
        "description": "no shell, file, API or spawn action"}, want=201)
    if not ok:
        return ph, {}
    t0 = time.monotonic()
    ok, task = ph.http(url, "/api/tasks", {
        "description": "Chip smoke. Decide your next action.",
        "profile": "chip-smoke", "grove": grove_dir}, want=201)
    if not ok:
        return ph, {}
    task_id, agent_id = task["task_id"], task["root_agent"]
    decided, first_s, seen = [], None, 0
    deadline = time.monotonic() + AGENT_PHASE_TIMEOUT_S
    while len(decided) < N_DECIDES and time.monotonic() < deadline:
        time.sleep(0.5)
        _, payload = ph.http(url, f"/api/consensus?task_id={task_id}")
        for rec in payload.get("records", [])[seen:]:
            seen += 1
            d = rec.get("decision")
            if not d:
                # an invalid round is the retry machinery's business
                # (correction feedback), not a failed request
                print(f"[agent] decide without a decision: "
                      f"{rec.get('failure_counts')}", flush=True)
                continue
            if first_s is None:
                first_s = time.monotonic() - t0
            decided.append(d.get("action"))
            print(f"[agent] decide {len(decided)}: {d.get('action')} "
                  f"(+{time.monotonic() - t0:.1f}s)", flush=True)
            ph.expect(d.get("action") == "wait",
                      f"decided {d.get('action')!r}, not 'wait'")
            if len(decided) < N_DECIDES:
                # a decided `wait` sleeps until the next message: the
                # short user turn of an agent loop
                _, r = ph.http(url, "/api/messages", {
                    "agent_id": agent_id,
                    "content": f"Turn {len(decided) + 1}: decide again."})
                ph.expect(r.get("delivered"), f"message not delivered: {r}")
    ph.expect(len(decided) >= N_DECIDES,
              f"{len(decided)} of {N_DECIDES} consensus decides completed "
              f"in {AGENT_PHASE_TIMEOUT_S:.0f}s")
    ph.http(url, f"/api/tasks/{task_id}/pause", {})
    return ph, {"decides": decided, "first_request_s": first_s,
                "audit_records": seen}


def make_reference(engine):
    """The plain forward pass as one jitted function, logits [1, T, V] for
    tokens [1, T]: ``transformer.forward_hidden`` + ``project_logits`` —
    one dense pass, no pages, no kernel of the serving path (past 256
    tokens its attention is the flash kernel, which the kernels phase
    compares with dense attention)."""
    import jax
    import jax.numpy as jnp
    from quoracle_tpu.models.transformer import (
        forward_hidden, init_cache, project_logits,
    )
    cfg = engine.cfg
    # one row: never split over dp; heads still split over tp
    shard = engine.attn_shard and (*engine.attn_shard[:2], None)

    @jax.jit
    def reference(params, tokens, n):
        T = tokens.shape[1]
        positions = jnp.arange(T, dtype=jnp.int32)[None]
        cache = init_cache(cfg, 1, T, dtype=engine.cache_dtype)
        hidden, _ = forward_hidden(params, cfg, tokens, positions, cache,
                                   jnp.zeros((1,), jnp.int32), n,
                                   shard=shard)
        return project_logits(params, cfg, hidden)
    return reference


def reference_gap(reference, params, ids: list, n_prompt: int) -> float:
    """How far the served tokens are from what the plain forward pass
    picks: over the generated positions of ``ids``, the largest (max logit
    − logit of the served token). Logits, not tokens: with random weights
    the largest logit changes on rounding."""
    import jax.numpy as jnp
    import numpy as np
    tokens = np.zeros((1, -(-len(ids) // 128) * 128), np.int32)
    tokens[0, :len(ids)] = ids
    logits = np.asarray(reference(params, jnp.asarray(tokens),
                                  jnp.asarray([len(ids)], jnp.int32)))
    gen = np.asarray(ids[n_prompt:])
    rows = logits[0, n_prompt - 1:len(ids) - 1]     # row p predicts ids[p+1]
    return float(np.max(rows.max(-1) - rows[np.arange(len(gen)), gen]))


# Served token vs the plain forward's best, in logit units. The two paths
# share bf16 weights and a bf16 cache but round differently (paged ragged
# kernel vs dense XLA attention, token-major vs [B, T] matmuls), which
# moves a logit by hundredths at most — 0.006 was the largest gap on the
# chip (PR 21). The top two of 32,000 unit-scale logits lie about 0.2
# apart, so 0.1 admits a rounding flip between near-ties and nothing else:
# a wrong mask, page or position lands whole units below the maximum.
REFERENCE_GAP_TOL = 0.1


def direct_phase(backend, spec: str) -> tuple[Phase, dict]:
    """backend.query rows at temperature 0."""
    from quoracle_tpu.consensus.prompt_builder import build_system_prompt
    from quoracle_tpu.models.runtime import QueryRequest
    ph = Phase("direct")
    engine = backend.engines[spec]
    reference = make_reference(engine)
    info: dict = {}

    def ask(messages, n=1, **kw):
        reqs = [QueryRequest(spec, messages, temperature=0.0,
                             max_tokens=DIRECT_MAX_TOKENS,
                             **{k: (v[i] if isinstance(v, list) else v)
                                for k, v in kw.items()})
                for i in range(n)]
        out = backend.query(reqs)
        for r in out:
            ph.request(r.ok, f"row failed: {r.error}")
        return out

    def check_reference(name, sid, row):
        ids = engine.session_tokens(sid) or []
        n_prompt = row.usage.prompt_tokens
        if not ph.expect(row.ok and len(ids) > n_prompt,
                         f"{name}: no served tokens resident to compare"):
            return
        gap = reference_gap(reference, engine.params, ids, n_prompt)
        info.setdefault("reference_gap", {})[name] = round(gap, 4)
        ph.expect(gap <= REFERENCE_GAP_TOL,
                  f"{name}: a served token lies {gap:.3f} logits below the "
                  f"plain forward's best (tolerance {REFERENCE_GAP_TOL})")

    user = [{"role": "user", "content": "Name the three primary colours."}]
    a, b = ask(user)[0], ask(user)[0]
    ph.expect(a.text == b.text and a.usage.completion_tokens > 0,
              "greedy row differs between two identical calls")

    c = ask(user, constrain_json=True, action_enum=("wait",))[0]
    compact = (c.text or "").replace(" ", "").replace("\n", "")
    ph.expect(compact.startswith('{"action":"wait"'),
              f"constrained row does not open with the enum action: "
              f"{(c.text or '')[:60]!r}")

    # the fan-out a consensus round makes: K rows over the builder's own
    # ungoverned system prompt, one tick of K x ~1.8k tokens
    shared = [{"role": "system", "content": build_system_prompt()}] + user
    sids = [f"smoke-fan-{i}" for i in range(FANOUT_K)]
    fan = ask(shared, n=FANOUT_K, session_id=sids)
    info["fanout_prompt_tokens"] = fan[0].usage.prompt_tokens
    info["fanout_rows_identical"] = all(r.text == fan[0].text for r in fan)
    for sid, r in zip(sids, fan):
        check_reference(sid, sid, r)

    s1 = ask(user, session_id="smoke-session")[0]
    check_reference("session-round-1", "smoke-session", s1)
    round2 = user + [{"role": "assistant", "content": s1.text or ""},
                     {"role": "user", "content": "And the secondary ones?"}]
    s2 = ask(round2, session_id="smoke-session")[0]
    ph.expect(s2.cached_tokens > 0,
              f"second round on one session id reports cached_tokens="
              f"{s2.cached_tokens}")
    check_reference("session-round-2", "smoke-session", s2)
    info["second_round_cached_tokens"] = s2.cached_tokens
    info["second_round_prompt_tokens"] = s2.usage.prompt_tokens
    return ph, info


def tick_paths(engine) -> tuple[list, dict]:
    """The CompileRegistry's shape keys (one per compiled program pair) and,
    per attention path, programs and ticks: unified keys start "ragged",
    anything else is a [B, T, …] rectangle of the gather programs."""
    shapes = engine.compiles.snapshot(max_shapes=1024)["shapes"]
    paths: dict = {}
    for e in shapes:
        kind = str(e["shape"]).split("x")[0]
        p = paths.setdefault(kind if kind.startswith("ragged") else "gather",
                             {"programs": 0, "ticks": 0})
        p["programs"] += 1
        p["ticks"] += 1 + e["hits"]
    return shapes, paths


def kernels_phase(engine) -> tuple[Phase, dict]:
    """Each Pallas kernel the run dispatched against its reference twin, on
    the chip, at the engine's geometry (under tp, one shard's heads — what
    each device's kernel instance sees) and pool dtype, on seeded random
    K/V. The unified ragged kernel (every serving tick): the last 128
    query positions of one row against its whole 8k context (tq=8), and
    one decode step of a full slot set at ragged lengths (tq=1), alone and
    with a quarter of each row's pages walked once for all. The flash
    kernel (the plain forward of ``reference_gap`` above 256 tokens, and
    the embedder): a 256-token chunk against a 2k cache.

    Tolerance: inputs are bf16, the kernels accumulate in f32 on the MXU at
    its default precision and the reference runs at "highest"; outputs are
    softmax-weighted means of unit-scale values. On the chip the largest
    difference was 2.1e-3 (flash; 5e-4 ragged — PR 21), so 1e-2 absolute
    leaves room for another device's rounding while an int8 cache (about
    1.6e-2 per value at 127 levels over four sigma) or a wrong mask
    exceeds it; the measured error is printed."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from quoracle_tpu.models.generate import RAGGED_TQ
    from quoracle_tpu.ops import paged_attention as pa
    from quoracle_tpu.ops.attention import attend
    from quoracle_tpu.ops.flash_attention import flash_attend
    tol = 1e-2
    ph = Phase("kernels")
    used = tick_paths(engine)[1]
    report: dict = {"dispatched": sorted(used)}
    cfg, st = engine.cfg, engine.sessions
    tp = 1 if engine.mesh is None else int(engine.mesh.shape["tp"])
    H, KV, hd, page = cfg.n_heads // tp, cfg.n_kv_heads // tp, \
        cfg.head_dim, st.page
    rng = np.random.default_rng(0)

    def compare(name, out, ref):
        err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref)))
        finite = bool(jnp.all(jnp.isfinite(out)))
        report[name] = {"max_abs_err": round(err, 5), "tol": tol,
                        "ref_abs_max": round(float(jnp.max(jnp.abs(ref))),
                                             3),
                        "shape": list(out.shape)}
        ph.request(finite and err <= tol,
                   f"{name}: max |kernel - ref| = {err:.4g} "
                   f"(finite={finite})")

    if "ragged" in used:
        n_pages, maxp = 129, 64               # page 0 is the scratch page
        # a 2-layer pool in the stored layout; the kernel reads layer 1
        kp, vp = (jnp.asarray(
            rng.standard_normal((2, n_pages, page, KV * hd)), st.k.dtype)
            for _ in range(2))
        kv_len = maxp * page - 5
        tables = np.stack([rng.permutation(n_pages - 1)[:maxp] + 1
                           for _ in range(8)]).astype(np.int32)
        nb = 16
        lens = kv_len - np.arange(8) * 517        # ragged decode rows
        chunk = np.stack([
            np.full(nb, kv_len), kv_len - (nb - np.arange(nb)) * RAGGED_TQ,
            np.full(nb, RAGGED_TQ), np.zeros(nb)]).astype(np.int32)
        tile = engine._ragged_tile
        decode = np.stack([lens, lens - 1, np.ones(8), np.arange(8)])
        # a shared prompt: every row's first 32 pages are row 0's
        common = tables.copy()
        common[:, :32] = tables[0, :32]
        window = cfg.sliding_window
        cases = {
            "ragged_tq8": (RAGGED_TQ, chunk, tables, window, {}),
            # the chunk forward's call: the same blocks, one walk a tile
            "ragged_tile": (RAGGED_TQ, chunk, tables, window, dict(
                tile=tile, tiles=jnp.asarray(pa.ragged_tiles(
                    chunk, RAGGED_TQ, tile)))),
            "ragged_tq1": (1, decode, tables, window, {}),
            # the decode program's call on those rows: the common pages
            # walked once for all eight (without the window, under which
            # nothing is shared)
            "ragged_tq1_shared": (1, decode, common, None, dict(
                shared=jnp.asarray(pa.shared_walks(common, lens - 1,
                                                   page)))),
        }
        for name, (tq, meta, tabs, window, walk) in cases.items():
            q = jnp.asarray(rng.standard_normal((meta.shape[1] * tq, H,
                                                 hd)), jnp.bfloat16)
            args = (q, kp, vp, jnp.asarray(tabs),
                    jnp.asarray(meta.astype(np.int32)), 1)
            out = pa.ragged_attend(*args, tq=tq, **walk,
                                   sliding_window=window)
            with jax.default_matmul_precision("highest"):
                ref = pa.ragged_attend_ref(
                    *args, tq=tq, sliding_window=window)
            compare(name, out, ref)

    B, T, S = 2, 256, 2048
    q = jnp.asarray(rng.standard_normal((B, T, H, hd)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((B, S, KV, hd)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((B, S, KV, hd)), jnp.bfloat16)
    q_pos = jnp.asarray(np.stack([S - T + np.arange(T),
                                  S - 300 - T + np.arange(T)]), jnp.int32)
    kv_len = jnp.asarray([S, S - 300], jnp.int32)
    out = flash_attend(q, k, v, q_pos, kv_len, sliding_window=1024)
    with jax.default_matmul_precision("highest"):
        ref = attend(q.astype(jnp.float32), k.astype(jnp.float32),
                     v.astype(jnp.float32), q_pos, kv_len,
                     sliding_window=1024)
    compare("flash", out, ref)
    return ph, report


def client(rt, url: str, spec: str, grove_dir: str) -> dict:
    """Everything the smoke asks of the running server (worker thread: the
    event loop stays free to serve the HTTP requests)."""
    engine = rt.backend.engines[spec]
    phases, extra = [], {}
    for fn, args in ((agent_phase, (url, spec, grove_dir)),
                     (direct_phase, (rt.backend, spec)),
                     (kernels_phase, (engine,))):
        t0 = time.monotonic()
        try:
            ph, info = fn(*args)
        except Exception as e:          # noqa: BLE001 — a phase that dies
            import traceback            # is a failed phase, reported
            traceback.print_exc()
            ph, info = Phase(fn.__name__.removesuffix("_phase")), {}
            ph.expect(False, f"{type(e).__name__}: {e}")
        info["seconds"] = round(time.monotonic() - t0, 1)
        phases.append(ph)
        extra[ph.name] = info
    return {"phases": {p.name: p.summary() for p in phases}, **extra}


async def run(chips: int, compile_log: dict) -> dict:
    import jax
    from quoracle_tpu import cli
    from quoracle_tpu.governance.capabilities import ALWAYS_ALLOWED
    from quoracle_tpu.infra.resources import device_memory_stats
    from quoracle_tpu.models.config import get_model_config
    from quoracle_tpu.native.tokenizer import native_available
    spec = SPECS[chips]
    argv = ["serve", "--backend", "tpu", "--pool", spec,
            "--port", "0"] + (["--tp", str(chips)] if chips > 1 else [])
    print("starting: python -m quoracle_tpu.cli " + " ".join(argv),
          flush=True)
    rt, server = await cli.start_server(cli.build_parser().parse_args(argv))
    if rt is None:
        raise RuntimeError("the dashboard refused to bind")
    startup_s = time.monotonic() - T_START
    engine = rt.backend.engines[spec]
    after_load = device_memory_stats()
    cfg = engine.cfg
    params_bytes = sum(int(x.size) * x.dtype.itemsize
                       for x in jax.tree.leaves(engine.params))
    failures = []
    if not native_available():
        failures.append("native tokenizer: g++ build of native/bpe.cpp "
                        "failed; the Python fallback would have served")
    with tempfile.TemporaryDirectory(prefix="chip-smoke-grove-") as grove:
        with open(os.path.join(grove, "GROVE.md"), "w") as f:
            f.write(GROVE_MD.format(
                blocked=", ".join(sorted(ALWAYS_ALLOWED - {"wait"}))))
        try:
            result = await asyncio.to_thread(client, rt, server.url, spec,
                                             grove)
        finally:
            await server.stop()
            await rt.shutdown()
    for name, ph in result["phases"].items():
        failures += [f"{name}: {e}" for e in ph["errors"]]
    shapes, paths = tick_paths(engine)
    if not any(k.startswith("ragged") for k in paths):
        failures.append(f"no tick took the unified ragged path: {paths}")
    if chips > 1:
        # no chip may hold the whole model: its tp share of the weights and
        # of the page pool, plus the replicated norms and grammar tables
        limit = params_bytes / chips * 1.1
        for d in after_load:
            if d["bytes_in_use"] > limit:
                failures.append(
                    f"device {d['device']} holds {d['bytes_in_use']} bytes "
                    f"after load; its share of the weights is "
                    f"{params_bytes // chips}")
    dev = jax.devices()[0]
    import importlib.metadata as md
    return {
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "versions": {"jax": jax.__version__, "jaxlib": md.version("jaxlib"),
                     "libtpu": md.version("libtpu")},
        "model": {"spec": spec, "layers_kept": cfg.n_layers,
                  "layers_published": get_model_config("mistral-7b").n_layers,
                  "dim": cfg.dim,
                  "heads": [cfg.n_heads, cfg.n_kv_heads],
                  "head_dim": cfg.head_dim, "ffn_dim": cfg.ffn_dim,
                  "vocab": cfg.vocab_size, "window": cfg.sliding_window,
                  "tp": chips, "params_bytes": params_bytes,
                  "pool_tokens": engine.sessions.max_tokens},
        "compile": {"cache_dir": compile_log["cache_dir"],
                    "backend_compile_s": round(compile_log["seconds"], 1),
                    "persistent_cache_hits": compile_log["hits"],
                    "persistent_cache_misses": compile_log["misses"]},
        "cold_startup_s": round(startup_s, 1),
        "first_request_s": result["agent"].get("first_request_s"),
        "attention_paths": paths,
        "compiled_shapes": [{"shape": e["shape"],
                             "first_call_ms": e["compile_ms"],
                             "later_ticks": e["hits"]} for e in shapes],
        "tokenizer": (f"{type(engine.tokenizer).__name__} "
                      f"(native C++: {native_available()})"),
        "memory_after_load": after_load,
        "memory_at_end": device_memory_stats(),
        **result,
        "wall_s": round(time.monotonic() - T_START, 1),
        "failures": failures,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=sorted(SPECS), default=1,
                    help="4: full-depth mistral-7b, tp=4 (builder-run)")
    args = ap.parse_args(argv)
    import jax
    from quoracle_tpu.utils.compile_cache import enable_compilation_cache
    compile_log = {"cache_dir": enable_compilation_cache(), "seconds": 0.0,
                   "hits": 0, "misses": 0}

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compile_log["seconds"] += secs

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            compile_log["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            compile_log["misses"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no accelerator — jax found platform "
              f"{devs[0].platform!r} ({devs[0].device_kind})",
              file=sys.stderr)
        return 2
    if len(devs) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but jax found "
              f"{len(devs)} device(s)", file=sys.stderr)
        return 2
    summary = asyncio.run(run(args.chips, compile_log))
    print(json.dumps(summary, indent=1), flush=True)
    if summary["failures"]:
        print("chip_smoke: FAILED\n  " + "\n  ".join(summary["failures"]),
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": summary["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
