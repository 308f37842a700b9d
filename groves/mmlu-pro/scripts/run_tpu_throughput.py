#!/usr/bin/env python
"""Throughput-mode grove runner (VERDICT r4 item 3): drive the
workload-scale question set through DECODE-LEVEL CONTINUOUS BATCHING.

Where run_tpu_accuracy.py steps question-by-question (one batched pool
query per question, waiting for each round), this runner submits
``--concurrency`` questions' worth of rows AT ONCE from a thread pool —
the shape of a coordinator fanning out answerer agents — and the
ContinuousBatcher (models/scheduler.py) admits/retires rows at 32-token
chunk boundaries: many agents' forced-choice decodes riding one member's
shared decode loop.

Records, per the VERDICT contract: wall-clock per question, aggregate
tokens/s, and accuracy, in one JSON line.

    python groves/mmlu-pro/scripts/run_tpu_throughput.py \
        [--pool xla:llama-1b] [--checkpoint DIR ...] [--limit 200] \
        [--concurrency 8] [--data ../data/questions_full.jsonl]

Reference counterpart: the 12,032-question MMLU-Pro grove
(/root/reference/priv/groves/mmlu-pro/GROVE.md:4-8) driven by parallel
answerer agents; the reference fans out to hosted APIs, this fans into
one chip's batcher.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import re
import sys
import time
from concurrent.futures import ThreadPoolExecutor

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _HERE)
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(_HERE))))

LETTER = re.compile(r'"action"\s*:\s*"([A-J])"')
LETTERS = tuple("ABCDEFGHIJ")


def load(path: str) -> list[dict]:
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def ask_one(backend, pool, q) -> tuple[dict, float, int, int]:
    """One question = one pool-wide query; returns (votes, wall_s,
    prompt_tokens, gen_tokens). Runs on a worker thread — many questions
    in flight land their rows in the same continuous decode chunks."""
    from quoracle_tpu.models.runtime import QueryRequest
    opts = "\n".join(f"{k}. {v}" for k, v in q["options"].items())
    msgs = [
        {"role": "system",
         "content": "Answer the multiple-choice question. Respond ONLY "
                    'with JSON: {"action": "<LETTER A-J>"}.'},
        {"role": "user", "content": f"{q['question']}\n{opts}"},
    ]
    reqs = [QueryRequest(model_spec=m, messages=msgs, temperature=0.2,
                         max_tokens=96, constrain_json=True,
                         action_enum=LETTERS) for m in pool]
    t0 = time.monotonic()
    results = backend.query(reqs)
    wall = time.monotonic() - t0
    votes, p_tok, g_tok = {}, 0, 0
    for m, r in zip(pool, results):
        match = LETTER.search(r.text or "")
        votes[m] = match.group(1) if (r.ok and match) else None
        if r.usage:
            p_tok += r.usage.prompt_tokens
            g_tok += r.usage.completion_tokens
    return votes, wall, p_tok, g_tok


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
        pool = list(BENCH_POOL)
    backend = TPUBackend(
        pool, continuous_slots=max(8, args.concurrency))

    questions = load(args.data)[: args.limit]
    per_subject: dict[str, list[int]] = {}
    walls: list[float] = []
    correct = answered = tot_p = tot_g = 0
    t_start = time.monotonic()
    with ThreadPoolExecutor(max_workers=args.concurrency) as ex:
        futs = {ex.submit(ask_one, backend, pool, q): q for q in questions}
        for fut in futs:
            q = futs[fut]
            votes, wall, p_tok, g_tok = fut.result()
            walls.append(wall)
            tot_p += p_tok
            tot_g += g_tok
            counts = collections.Counter(v for v in votes.values() if v)
            if counts:
                answered += 1
                winner, _ = counts.most_common(1)[0]
                hit = int(winner == q["answer"])
            else:
                hit = 0
            correct += hit
            per_subject.setdefault(q["subject"], []).append(hit)
    t_total = time.monotonic() - t_start
    backend.close()

    walls.sort()
    payload = {
        "metric": "mmlu_pro_throughput",
        "value": round(len(questions) / t_total, 3),
        "unit": "questions/s",
        "questions": len(questions),
        "answered": answered,
        "accuracy": round(correct / max(1, len(questions)), 4),
        "wall_total_s": round(t_total, 2),
        "wall_per_question_p50_s": round(
            walls[len(walls) // 2] if walls else 0.0, 3),
        "wall_per_question_p90_s": round(
            walls[int(len(walls) * 0.9)] if walls else 0.0, 3),
        "gen_tokens_per_s": round(tot_g / t_total, 1),
        "prompt_tokens": tot_p,
        "gen_tokens": tot_g,
        "concurrency": args.concurrency,
        "pool": pool,
        "per_subject_accuracy": {s: round(sum(v) / len(v), 3)
                                 for s, v in sorted(per_subject.items())},
    }
    line = json.dumps(payload)
    print(line)
    if args.out_artifact:
        with open(args.out_artifact, "w") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
