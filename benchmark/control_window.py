#!/usr/bin/env python3
"""The second control of a cell whose model mixes WINDOW and full attention
layers: can the output check see the window? The builder's tool, as
`benchmark.control` is: no run of the benchmark calls it.

    python3 -m benchmark.control_window --workload <cell> --seed <n> --seconds <s>

One sound run of the cell as `benchmark.run` makes it, which has to end
`correct`, and then, on the same rows through the same reference, two
models put in the program's place, each read as `benchmark.control` reads
its lowered reference (at each position the float32 reference's gap of the
token the stand-in puts first) and held to the cell's own limits:

* the family's reference with the window LIFTED in the sliding layers
  (`Reference.lift_window`: every layer attends to the whole context). A
  program whose window layers walked pages they should have let go, took
  the full layers' mask or tables, or adopted a prefix's window pages at
  the wrong boundary would say such tokens. It has to FAIL a limit, or the
  limits cannot tell the mechanism from its absence and are set again;
* the reference lowered to int8 (`benchmark.control`'s reading, here so
  that one run gives all three), which has to fail one too.

Exit code 0 when the run is correct and both stand-ins fail a limit.
"""

from __future__ import annotations

import sys

from benchmark import control, run


def main(argv=None, root: str = run.HERE) -> int:
    import numpy as np
    from benchmark.reference import gaps_of, served_logits
    args = run.parser(__doc__.split("\n\n")[0]).parse_args(argv)
    detail: dict = {}
    rc = run.run(args, (), detail, root)
    if rc or "logits" not in detail:
        return rc or 2
    limits = {c[0]: c[2] for c in detail["checks"]}
    out = {"cell": args.workload, "seed": args.seed,
           "correct": all(c[3] for c in detail["checks"]),
           "sound": {c[0]: c[1] for c in detail["checks"]
                     if c[0].startswith("reference_gap")}}

    def held(name: str, gaps: list) -> bool:
        flat = np.concatenate(gaps)
        passes = bool(flat.max() <= limits["reference_gap"]
                      and flat.mean() <= limits["reference_gap_mean"])
        out[name] = {"tokens": len(flat), "gap": float(flat.max()),
                     "tokens_off_the_best": int((flat > 0).sum()),
                     "gap_mean": float(flat.mean()),
                     "within_both_limits": passes}
        return passes

    ref = detail["ref"]
    ref.lift_window = True
    lifted_passes = held("reference_with_the_window_lifted", [
        gaps_of(lg, served_logits(ref, r["ids"], r["prompt_tokens"],
                                  detail["pad_to"]).argmax(-1))
        for lg, r in zip(detail["logits"], detail["sample"])])
    ref.lift_window = False
    int8_passes = held("reference_lowered_to_int8",
                       control.lowered_reference_gaps(detail))
    run.say("control", out)
    return 0 if out["correct"] and not lifted_passes and not int8_passes \
        else 1


if __name__ == "__main__":
    sys.exit(main())
