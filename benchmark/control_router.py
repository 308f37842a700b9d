#!/usr/bin/env python3
"""The control of a cell whose router scores its experts by a SOFTMAX over
all of them: can the output check see the score function? The builder's
tool, as `benchmark.control` is: no run of the benchmark calls it.

    python3 -m benchmark.control_router --workload <cell> --seed <n> --seconds <s>

One sound run of the cell as `benchmark.run` makes it, which has to end
`correct`, and then, on the same rows through the same reference, one model
put in the program's place, read as `benchmark.control` reads its lowered
reference (at each position the float32 reference's gap of the token the
stand-in puts first) and held to the cell's own limits:

* the family's reference with SIGMOID scores in softmax's place
  (`Reference.sigmoid_router`): each expert scored by its own logit alone,
  the same experts chosen (both rise with the logit), the gates their
  sigmoids over the chosen sigmoids' sum. Every other expert configuration
  of the benchmark routes so, and a program that dropped the score function
  on its way from the configuration to `moe_select` would serve such
  tokens. It has to FAIL a limit, or the limits cannot tell the mechanism
  from its absence and are set again.

The window's and the precision's controls are `benchmark.control_window`'s,
which runs on such a cell as it is. Exit code 0 when the run is correct and
the stand-in fails a limit.
"""

from __future__ import annotations

import sys

from benchmark import run


def main(argv=None, root: str = run.HERE) -> int:
    import numpy as np
    from benchmark.reference import gaps_of, served_logits
    args = run.parser(__doc__.split("\n\n")[0]).parse_args(argv)
    detail: dict = {}
    rc = run.run(args, (), detail, root)
    if rc or "logits" not in detail:
        return rc or 2
    limits = {c[0]: c[2] for c in detail["checks"]}
    ref = detail["ref"]
    ref.sigmoid_router = True
    flat = np.concatenate([
        gaps_of(lg, served_logits(ref, r["ids"], r["prompt_tokens"],
                                  detail["pad_to"]).argmax(-1))
        for lg, r in zip(detail["logits"], detail["sample"])])
    ref.sigmoid_router = False
    passes = bool(flat.max() <= limits["reference_gap"]
                  and flat.mean() <= limits["reference_gap_mean"])
    out = {"cell": args.workload, "seed": args.seed,
           "correct": all(c[3] for c in detail["checks"]),
           "sound": {c[0]: c[1] for c in detail["checks"]
                     if c[0].startswith("reference_gap")},
           "reference_with_sigmoid_scores": {
               "tokens": len(flat), "gap": float(flat.max()),
               "tokens_off_the_best": int((flat > 0).sum()),
               "gap_mean": float(flat.mean()),
               "within_both_limits": passes}}
    run.say("control", out)
    return 0 if out["correct"] and not passes else 1


if __name__ == "__main__":
    sys.exit(main())
