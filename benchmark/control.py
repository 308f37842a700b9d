#!/usr/bin/env python3
"""The control of the output check, read on the chip. The builder's tool
for setting a limit: no run of the benchmark calls it.

    python3 -m benchmark.control --workload <cell> --seed <n> --seconds <s>
                                 [--sound] [--out FILE]

One run of the cell as `benchmark.run` makes it, but with the program's own
lower-precision path switched on: the flags under `control.serve_args` in
the configuration's file (`--quantize-kv`: int8 KV pages where the
configuration states bfloat16). The program is in the loop: the same
server, warm-up, traffic and window, the same sample of the window's greedy
rows through the same reference, against the same limits. Such a run has
to end with `correct` false. With `--sound` no flag is added: the other end
of the reading, with the same detail.

A second reading comes with both, and costs no second server: the plain
reference of the configuration's family lowered to int8 weights
(`Reference.lower_to_int8`) and put in the program's place, on the same
rows — at each position the gap of the token IT puts first, held to the
cell's own limits.

A family whose program has no lower-precision path of its own puts
`"serve_args": []` under `control`. That lowered reference is then the
control: the run is the sound program, which has to end `correct`, and the
lowered reference in its place has to fail one of the two limits.

`--out FILE` keeps, for every row compared, each served token's gap and the
reference's margin between its best and second-best token there, so that a
statistic can be tried on the readings without another run; and every
request's times, lead-in included, so that the end-to-end metrics can be
taken over shorter windows of the same run.

Exit code 0 when the run came out as it should (the control not correct, a
`--sound` run correct; with no path of the program's, the run correct and
the lowered reference over a limit), 1 when not.
"""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import configs, run      # noqa: E402


def lowered_reference_gaps(detail: dict) -> list:
    """The reference lowered to int8 weights, put in the program's place:
    per row, the float32 reference's gaps of the tokens it puts first."""
    from benchmark.reference import gaps_of, served_logits
    ref = detail["ref"]
    ref.lower_to_int8()
    return [gaps_of(lg, served_logits(ref, r["ids"], r["prompt_tokens"],
                                      detail["pad_to"]).argmax(-1))
            for lg, r in zip(detail["logits"], detail["sample"])]


def as_it_should(sound: bool, serve_args: list, correct: bool,
                 lowered_passes: bool | None) -> bool:
    """Did the run come out as it should? `lowered_passes`: whether the
    reference lowered to int8, in the program's place, stayed within both
    limits (None where no row was compared)."""
    if sound:
        return correct
    if serve_args:                    # the program's own path is the control
        return not correct
    return correct and lowered_passes is False


def main(argv=None, root: str = run.HERE) -> int:
    import numpy as np
    ap = run.parser(__doc__.split("\n\n")[0])
    ap.add_argument("--sound", action="store_true")
    ap.add_argument("--out", default=None, metavar="FILE")
    ap.add_argument("--flags", default=None, metavar="FLAGS",
                    help="try another path of the program than the one the "
                         "configuration names, as `--flags='--x --y'`")
    args = ap.parse_args(argv)
    real, tiny, _ = run.load_cells(root)
    cell = real.get(args.workload) or tiny.get(args.workload)
    if cell is None:
        print(f"control: unknown workload {args.workload!r}", file=sys.stderr)
        return 2
    control = configs.load_config(cell["config"], root).get("control")
    if args.flags:
        control = {"precision": args.flags, "serve_args": args.flags.split()}
    if control is None and not args.sound:
        print(f"control: configuration {cell['config']} says nothing of "
              f"its control (`control`; `\"serve_args\": []` where the "
              f"program has no lower-precision path)", file=sys.stderr)
        return 2
    more = [] if args.sound else list(control["serve_args"])
    detail: dict = {}
    rc = run.run(args, more, detail, root)
    if rc or "checks" not in detail:
        return rc or 2
    correct = all(c[3] for c in detail["checks"])
    out = {"cell": cell["name"], "seed": args.seed,
           "program": "sound" if args.sound else control["precision"],
           "serve_args": more, "correct": correct,
           "checks": {c[0]: {"value": c[1], "limit": c[2], "passed": c[3]}
                      for c in detail["checks"]}}
    lowered_passes = None
    if "logits" in detail:
        low = lowered_reference_gaps(detail)
        flat = np.concatenate(low)
        limits = {c[0]: c[2] for c in detail["checks"]}
        lowered_passes = bool(
            flat.max() <= limits["reference_gap"]
            and flat.mean() <= limits["reference_gap_mean"])
        out["reference_lowered_to_int8"] = {
            "tokens": len(flat), "tokens_off_the_best": int((flat > 0).sum()),
            "gap": float(flat.max()), "gap_mean": float(flat.mean()),
            "within_both_limits": lowered_passes}
        if args.out:
            rows = []
            for r, lg, g, lo in zip(detail["sample"], detail["logits"],
                                    detail["gaps"], low):
                top2 = np.partition(lg, -2, axis=-1)[:, -2:]
                rows.append({"sid": r["sid"], "context": len(r["ids"]),
                             "prompt_tokens": r["prompt_tokens"],
                             "gaps": [float(x) for x in g],
                             "margins": [float(x) for x in
                                         top2[:, 1] - top2[:, 0]],
                             "lowered_reference_gaps":
                                 [float(x) for x in lo]})
            os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                        exist_ok=True)
            with open(args.out, "w") as f:
                json.dump({**out, "rows": rows,
                           "window": detail["window"]}, f)
    run.say("control", out)
    return 0 if as_it_should(args.sound, more, correct,
                             lowered_passes) else 1


if __name__ == "__main__":
    sys.exit(main())
