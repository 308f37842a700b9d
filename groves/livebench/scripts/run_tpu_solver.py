#!/usr/bin/env python
"""Model-only LiveBench runner: free-form answers through the TPU
backend, graded by score_run.py's MECHANICAL graders (exact / numeric /
checks — no LLM judges), with continuous batching driving concurrency.

The agent-level grove run (GROVE.md topology) is CI-covered on mock;
this runner gives the 1,152-task workload-scale set
(data/questions_full.jsonl) a direct serving consumer, symmetric to
groves/mmlu-pro/scripts/run_tpu_throughput.py: wall-clock per task,
tokens/s, and per-category accuracy in one JSON line.

    python groves/livebench/scripts/run_tpu_solver.py \
        [--pool xla:llama-1b] [--checkpoint DIR ...] [--limit 200] \
        [--concurrency 8] [--data ../data/questions_full.jsonl]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _HERE)
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(_HERE))))

from score_run import grade  # noqa: E402  (same scripts dir)

SYSTEM = ("Answer the task exactly as instructed. Follow the required "
          "answer format precisely; output ONLY the answer.")


def load(path: str) -> list[dict]:
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def solve_one(backend, spec, q) -> tuple[bool, float, int]:
    from quoracle_tpu.models.runtime import QueryRequest
    t0 = time.monotonic()
    r = backend.query([QueryRequest(
        spec, [{"role": "system", "content": SYSTEM},
               {"role": "user", "content": q["task"]}],
        temperature=0.2, max_tokens=96)])[0]
    wall = time.monotonic() - t0
    text = (r.text or "").strip() if r.ok else ""
    gen = r.usage.completion_tokens if (r.ok and r.usage) else 0
    return grade(q, text), wall, gen


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--pool", default=None)
    ap.add_argument("--checkpoint", action="append", default=[])
    ap.add_argument("--limit", type=int, default=200)
    ap.add_argument("--concurrency", type=int, default=8)
    ap.add_argument("--data", default=os.path.join(
        _HERE, "..", "data", "questions_full.jsonl"))
    ap.add_argument("--out-artifact", default=None)
    args = ap.parse_args()

    from quoracle_tpu.models.loader import register_hf_checkpoint
    from quoracle_tpu.models.runtime import TPUBackend
    pool = args.pool.split(",") if args.pool else []
    for d in args.checkpoint:
        cfg = register_hf_checkpoint(d)
        pool.append(f"xla:{cfg.name}")
    if not pool:
        from quoracle_tpu.models.config import BENCH_POOL
        pool = [BENCH_POOL[0]]
    spec = pool[0]
    backend = TPUBackend(
        [spec], continuous_slots=max(8, args.concurrency))

    tasks = load(args.data)[: args.limit]
    per_cat: dict[str, list[int]] = {}
    walls: list[float] = []
    correct = tot_gen = 0
    t_start = time.monotonic()
    with ThreadPoolExecutor(max_workers=args.concurrency) as ex:
        futs = {ex.submit(solve_one, backend, spec, q): q for q in tasks}
        for fut in futs:
            q = futs[fut]
            ok, wall, gen = fut.result()
            walls.append(wall)
            tot_gen += gen
            correct += int(ok)
            per_cat.setdefault(q["category"], []).append(int(ok))
    t_total = time.monotonic() - t_start
    backend.close()

    walls.sort()
    payload = {
        "metric": "livebench_throughput",
        "value": round(len(tasks) / t_total, 3),
        "unit": "tasks/s",
        "tasks": len(tasks),
        "accuracy": round(correct / max(1, len(tasks)), 4),
        "wall_total_s": round(t_total, 2),
        "wall_per_task_p50_s": round(
            walls[len(walls) // 2] if walls else 0.0, 3),
        "gen_tokens_per_s": round(tot_gen / t_total, 1),
        "concurrency": args.concurrency,
        "pool": [spec],
        "per_category_accuracy": {c: round(sum(v) / len(v), 3)
                                  for c, v in sorted(per_cat.items())},
    }
    line = json.dumps(payload)
    print(line)
    if args.out_artifact:
        with open(args.out_artifact, "w") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
