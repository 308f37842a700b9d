#!/usr/bin/env python3
"""One cell of the benchmark with the STAGED per-layer metrics of
`host_op_metrics.json` read as well (PR 37; the builder's, no run of the
driver calls it):

    python3 -m benchmark.with_host_ops --workload <cell> --seed <n> \
        --seconds <s> --trace 1

A cell reports the per-layer names its traffic file and its configuration's
file list, and an accepted file may be edited by a `benchmark` PR alone. So
this writes the cell's traffic file (and, where the list names its
configuration, the configuration's file) with the staged names appended
into a temporary root and runs `benchmark.run` with that `root`: every
other file of the benchmark is found beside `run.py` as always. It goes when
a `benchmark` PR has admitted the list."""

from __future__ import annotations

import json
import os
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import configs, run      # noqa: E402


def staged(kind: str, name: str) -> list:
    """The staged metric names for a traffic mix (`kind` "traffic") or a
    configuration ("configs") of this name."""
    listed = configs.load_json(HERE, "host_op_metrics.json")["metrics"]
    return [m["name"] for m in listed if name in m.get(kind, [])]


def write_with(root: str, folder: str, name: str, more: list) -> None:
    """`<folder>/<name>.json` into `root`, `more` appended to its
    `per_layer` list (each name once)."""
    data = configs.load_json(HERE, folder, f"{name}.json")
    data["per_layer"] = list(dict.fromkeys(data.get("per_layer", []) + more))
    os.makedirs(os.path.join(root, folder), exist_ok=True)
    with open(os.path.join(root, folder, f"{name}.json"), "w") as f:
        json.dump(data, f)


def main(argv=None) -> int:
    args = run.parser(__doc__.split("\n\n")[0]).parse_args(argv)
    real, tiny, _ = run.load_cells()
    cell = real.get(args.workload) or tiny.get(args.workload)
    if cell is None:
        return run.run(args)            # says which cells there are
    with tempfile.TemporaryDirectory(prefix="host-ops-") as root:
        write_with(root, "traffic", cell["traffic"],
                   staged("traffic", cell["traffic"]))
        more = staged("configs", cell["config"])
        if more:
            write_with(root, "configs", cell["config"], more)
        return run.run(args, root=root)


if __name__ == "__main__":
    sys.exit(main())
