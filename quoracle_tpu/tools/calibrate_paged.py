"""Measure the gather/direct paged-path crossover ON THIS HOST and write
the engine's gate file (utils/calibration.py; VERDICT r3 weak #2 — the
gate must be a measurement, not a hardcoded constant).

For each resident size in the sweep, times resumed rounds under the
unified (ISSUE 8 ragged kernel), gather, direct_decode, and direct_full
paths (tools/bench_longctx.py harness). The smallest resident size where
a direct path's p50 beats gather becomes its ``*_min_resident`` gate; a
path that never wins stays null (off). The UNIFIED gate works the other
way around — the kernel is the TPU default without a file, so the sweep
records where gather is the better fallback: unified winning at the
smallest size writes 0 (explicit always-on), losing everywhere writes
null (gather is the measured default on this host). Writes the file the
engine loads at startup (~/.cache/quoracle_tpu/paged_gates.json, or
--out / QUORACLE_PAGED_CALIB).

Run on the serving host (ONE python process on TPU deployments):

    python -m quoracle_tpu.tools.calibrate_paged --sweep 1024 4096 16384

``--prefer-memory`` enables a direct path at its smallest MEASURED size
even when it loses on latency (within --latency-slack), for deployments
where peak HBM matters more than p50.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sweep", type=int, nargs="+",
                    default=[1024, 4096, 16384])
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--scale", default="1b", choices=["1b", "tiny"])
    ap.add_argument("--out", default=None,
                    help="gate file path (default: the engine's load path)")
    ap.add_argument("--prefer-memory", action="store_true",
                    help="enable direct paths for peak-HBM reasons even "
                         "when they lose on latency within --latency-slack")
    ap.add_argument("--latency-slack", type=float, default=1.25,
                    help="with --prefer-memory: max direct/gather p50 "
                         "ratio still considered acceptable")
    args = ap.parse_args()

    import jax

    from quoracle_tpu.tools.bench_longctx import build_engine, measure_paths
    from quoracle_tpu.utils.calibration import save_paged_gates
    from quoracle_tpu.utils.compile_cache import enable_compilation_cache
    enable_compilation_cache()

    sweep = sorted(args.sweep)
    device_kind = getattr(jax.devices()[0], "device_kind", "unknown")
    log(f"calibrating on {device_kind}; sweep {sweep}")

    by_size = {}
    for resident in sweep:
        log(f"--- resident {resident} ---")
        # fresh engine PER size: one engine sized for sweep[-1] would
        # bucket-pad mid-sweep gather rounds to the largest size,
        # inflating gather ~sweep[-1]/resident× and writing gates that
        # enable the direct paths where properly-bucketed gather wins.
        # Pool budget 2 GiB (not the serving default 8 GiB): the session
        # only ever holds ~resident+rounds·new tokens, and two engines
        # briefly coexist between sweep sizes — 1b weights + a 32·max_seq
        # token pool each OOMed a 16 GB v5e at the 4096 step.
        eng, tok = build_engine(resident, args.rounds, args.new_tokens,
                                args.scale, session_max_bytes=2 << 30)
        by_size[resident] = measure_paths(
            eng, tok, resident, args.rounds, args.new_tokens)
        # Free this size's weights + pool BEFORE the next build: the jit
        # caches keep executables (and through them donated-buffer aliases)
        # alive past `del`, and GC alone is too lazy to beat the next
        # engine's allocation to the HBM.
        del eng, tok
        gc.collect()
        jax.clear_caches()
        gc.collect()

    def crossover(path: str):
        for resident in sweep:
            r = by_size[resident]
            ratio = (r[path]["p50_round_ms"]
                     / max(1e-9, r["gather"]["p50_round_ms"]))
            if ratio <= 1.0:
                return resident
            if args.prefer_memory and ratio <= args.latency_slack:
                return resident
        return None

    decode_gate = crossover("direct_decode")
    full_gate = crossover("direct_full")
    # UNIFIED ragged kernel (ISSUE 8): measured unified-vs-gather per
    # geometry. The engine's default is ON (threshold 0) on TPU without a
    # file, so the calibration's job here is the REVERSE of the direct
    # gates': record where gather is the better fallback. Unified winning
    # at the smallest sweep size → gate 0 (always on, making the measured
    # default explicit); winning only above some size → that size;
    # losing everywhere → explicit off (JSON null — gather is the
    # measured default on this host).
    unified_gate = crossover("unified")
    if unified_gate == sweep[0]:
        unified_gate = 0
    # The engine's use_direct_pre requires use_direct (the gather decode
    # cannot read what the direct prefill wrote without a working cache),
    # so a winning direct_full must PULL THE DECODE GATE DOWN to its own
    # crossover — otherwise the measured-as-winning path is unreachable.
    prefill_gate = full_gate
    if full_gate is not None and (decode_gate is None
                                  or decode_gate > full_gate):
        decode_gate = full_gate

    note = "; ".join(
        f"resident {r}: " + ", ".join(
            f"{p}={v['p50_round_ms']:.0f}ms" for p, v in res.items())
        for r, res in by_size.items())
    path = save_paged_gates(
        args.out, decode_min_resident=decode_gate,
        prefill_min_resident=prefill_gate,
        unified_min_resident=unified_gate, device_kind=device_kind,
        note=note)
    summary = {
        "metric": "paged_gate_calibration",
        "decode_min_resident": decode_gate,
        "prefill_min_resident": prefill_gate,
        "unified_min_resident": unified_gate,
        "gate_file": path,
        "device_kind": device_kind,
        "measurements": {str(k): {p: v["p50_round_ms"]
                                  for p, v in r.items()}
                         for k, r in by_size.items()},
    }
    print(json.dumps(summary))


if __name__ == "__main__":
    main()
