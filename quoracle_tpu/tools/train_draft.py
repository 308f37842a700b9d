"""Compat shim (ISSUE 19): the draft trainer grew into the serving
flywheel's training plane and lives at
:mod:`quoracle_tpu.training.draft_check` — the pjit data-parallel step
itself is :mod:`quoracle_tpu.training.trainer`. This module keeps the
historical entry point stable:

    python -m quoracle_tpu.tools.train_draft --check

and ``run_check``/``main`` importable from here (the tier-1 contract in
tests/test_train_draft_check.py uses this path).
"""

from __future__ import annotations

from quoracle_tpu.training.draft_check import main, run_check

__all__ = ["main", "run_check"]

if __name__ == "__main__":
    main()
