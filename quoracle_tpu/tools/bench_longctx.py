"""Long-context resumed-round micro-benchmark: paged kernels vs gather.

The main bench (bench.py) measures consensus rounds at ~1-2k resident
tokens, where the fused gather decode wins on hosts with expensive kernel
launches (models/generate.py paged gates; utils/calibration.py). This
tool measures the regime the paged kernels exist for: a LONG resident
session resumed for short rounds, where the gather path materializes a
[B, maxp·page] working cache and attends over the padded length while the
kernels read only the row's real pages. Three paths:

  gather          — working-cache gather prefill + gather decode
  direct_decode   — gather prefill, ragged-kernel decode (r3 path)
  direct_full     — paged prefill (suffix chunk vs pages in place,
                    VERDICT r4 item 2) + ragged-kernel decode: no
                    [B, maxp·page] materialization anywhere in the call

Per path it reports p50 resumed-round latency and the allocator's peak
HBM. The peak counter is cumulative per process, so paths run in
ascending expected-footprint order (direct_full first) — each row's
reported peak is the high-water AFTER that path; a jump attributes to it.

Run on the TPU host (ONE python process holds the chip):

    python -m quoracle_tpu.tools.bench_longctx --resident 16384 --rounds 4

tools/calibrate_paged.py reuses measure_paths() to find each path's
crossover on the current host and writes the engine's gate file.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import statistics
import sys
import time


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def peak_hbm_gb() -> float | None:
    import jax
    stats = getattr(jax.devices()[0], "memory_stats", lambda: None)()
    if not stats:
        return None
    peak = stats.get("peak_bytes_in_use")
    return round(peak / 1e9, 3) if peak else None


def build_engine(resident: int, rounds: int, new_tokens: int, scale: str,
                 session_max_bytes: int = 8 << 30):
    from quoracle_tpu.models.config import register_model
    from quoracle_tpu.models.generate import GenerateEngine
    from quoracle_tpu.models.loader import (
        load_params, register_hf_checkpoint, to_device,
    )
    from quoracle_tpu.models.make_checkpoint import make_checkpoint
    from quoracle_tpu.models.tokenizer import get_tokenizer

    root = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "checkpoints")
    ckpt = make_checkpoint(os.path.join(root, f"llama-{scale}"),
                           family="llama", scale=scale)
    base = register_hf_checkpoint(ckpt, name="longctx-base")
    max_seq = resident + 4 * new_tokens * (rounds + 2) + 1024
    cfg = register_model(dataclasses.replace(
        base, name="longctx", context_window=max_seq))
    tok = get_tokenizer("xla:longctx")
    params = to_device(load_params(ckpt, cfg))
    eng = GenerateEngine(
        cfg, params, tok, max_seq=max_seq,
        prompt_buckets=(256, 1024, resident, max_seq),
        session_max_bytes=session_max_bytes)
    return eng, tok


# Ascending expected-footprint order (the peak-HBM counter is cumulative):
# unified holds only the pool (KV written straight to pages — no working
# cache, no tail buffer), direct_full adds the tail, direct_decode adds
# the working cache at prefill, gather keeps it through decode.
PATHS = ("unified", "direct_full", "direct_decode", "gather")


def _set_path(eng, path: str) -> None:
    eng._force_gather_decode = path == "gather"
    eng.unified_min_tokens = 0 if path == "unified" else 1 << 30
    eng.direct_decode_min_tokens = 0 if path.startswith("direct") else 1 << 30
    eng.direct_prefill_min_tokens = 0 if path == "direct_full" else 1 << 30


def measure_paths(eng, tok, resident: int, rounds: int, new_tokens: int,
                  paths=PATHS) -> dict:
    """Build one resident session, then time resumed refinement-shaped
    rounds under each path. Returns {path: {p50_round_ms, peak_hbm_gb}}.

    Comparability contracts (these feed calibrate_paged's gate decisions):
      * the session is built INCREMENTALLY in ≤1024-token suffix chunks
        under the FIRST path's gates — so when direct_full runs first, the
        cumulative peak-HBM counter never includes a full-resident gather
        working cache that isn't that path's own doing;
      * every path replays rounds from the SAME base conversation (conv
        resets per path) — each path is timed at the same resident size,
        not at whatever the previous path grew the session to.
    """
    filler = ("The quick brown fox jumps over the lazy dog. "
              "Numbers: 0123456789. ")
    ids = tok.encode(filler)
    prompt = [tok.bos_id] + (ids * (resident // len(ids) + 1))[:resident - 1]
    _set_path(eng, paths[0])
    eng.sessions.drop("s")
    t0 = time.monotonic()
    step = 1024
    for end in range(step, len(prompt), step):
        eng.generate([prompt[:end]], temperature=0.0, max_new_tokens=1,
                     session_ids=["s"])
    r = eng.generate([prompt], temperature=0.0,
                     max_new_tokens=new_tokens, session_ids=["s"])[0]
    log(f"incremental prefill of {len(prompt)} tokens: "
        f"{time.monotonic() - t0:.1f}s (path {paths[0]})")

    results = {}
    base_conv = list(prompt) + r.token_ids
    for path in paths:
        _set_path(eng, path)
        conv = list(base_conv)
        lats = []
        for i in range(rounds + 1):            # first = warmup/compile
            nxt = conv + tok.encode(f" continue {path} {i}.")
            t0 = time.monotonic()
            rr = eng.generate([nxt], temperature=0.0,
                              max_new_tokens=new_tokens,
                              session_ids=["s"])[0]
            lats.append((time.monotonic() - t0) * 1000)
            conv = nxt + rr.token_ids
            log(f"{path} round {i}: {lats[-1]:.0f}ms "
                f"(reused {rr.n_cached_tokens} tokens)")
        results[path] = {
            "p50_round_ms": statistics.median(lats[1:]),
            "peak_hbm_gb": peak_hbm_gb(),
            "rounds": rounds,
        }
    return results


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--resident", type=int, default=16384,
                    help="target resident session size in tokens")
    ap.add_argument("--rounds", type=int, default=4,
                    help="timed resumed rounds per path")
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--scale", default="1b", choices=["1b", "tiny"])
    args = ap.parse_args()

    import jax

    from quoracle_tpu.utils.compile_cache import enable_compilation_cache
    enable_compilation_cache()

    eng, tok = build_engine(args.resident, args.rounds, args.new_tokens,
                            args.scale)
    log(f"engine ready; resident target {args.resident} tokens")
    results = measure_paths(eng, tok, args.resident, args.rounds,
                            args.new_tokens)

    print(json.dumps({
        "metric": "longctx_resumed_round_p50",
        "resident_tokens": args.resident,
        "new_tokens_per_round": args.new_tokens,
        **{f"{k}_p50_ms": round(v["p50_round_ms"], 1)
           for k, v in results.items()},
        **{f"{k}_peak_hbm_gb": v["peak_hbm_gb"] for k, v in results.items()},
        "device": str(jax.devices()[0]),
    }))


if __name__ == "__main__":
    main()
