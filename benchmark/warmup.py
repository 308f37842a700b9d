"""Warm exactly the programs a cell's traffic uses, and no others.

The unified ragged path compiles one prefill program per (token budget TB,
row slots R, page-table width W) and one decode program per (R, W, decode
bound); `benchmark/warm/<cell>.json` lists the cell's (TB, W) pairs. For
each pair this module crafts ONE engine tick that lands on it, from the
program's bucket arithmetic (models/generate.py: `_generate_impl`,
`_run_unified`), with the engine's own `prompt_buckets` and the batcher's
own `max_slots`, read from the program at run time:

    T      = the largest suffix in the tick, rounded up to a prompt bucket
    Bk     = (largest resident prefix + T) rounded up to a prompt bucket
    W      = pow2ceil(Bk / 128 + 1)  = Bk / 64 for the power-of-two buckets
    TB     = sum of the suffixes, each rounded up to 8, rounded up to a
             token bucket

so TB >= Bk is n = TB / (2 Bk) + 1 new sessions of Bk tokens each, and TB < Bk is
one row of TB new tokens on a session that already holds Bk - T tokens
(made one tick earlier by a new session, whose own key is the diagonal
(Bk', Bk' / 64)). Every crafted tick is checked against the key the
engine's CompileRegistry recorded for it. A tick that misses its key, and
any program JAX is asked for while the window is open, make the run's
`correct` false (run.py `check_window`): if the program changes its
buckets, the cell's `warm/<cell>.json` no longer lands and every run says
so, rather than timing compilation as if it were serving.
"""

from __future__ import annotations

import numpy as np

def _round_up(n: int, buckets) -> int:
    for b in buckets:
        if n <= b:
            return b
    return n


def plan(tb: int, w: int, prompt_buckets, max_slots: int) -> list:
    """[(prefix tokens wanted on the row's session, new tokens)] for the
    tick that lands on (tb, w). prefix 0 = a new session."""
    bk = 64 * w
    if tb >= bk:
        # the fewest new sessions of bk tokens whose sum rounds up to tb
        # (more would only fill the page pool: eight of 4,096 do not fit
        # Mistral's 32,768 tokens)
        n = tb // (2 * bk) + 1
        if n > max_slots:
            raise ValueError(f"key ({tb}, {w}) needs {n} rows; the batcher "
                             f"has {max_slots} slots")
        return [(0, bk)] * n
    t = _round_up(tb, prompt_buckets)
    return [(bk - t, tb)]


class Warmer:
    def __init__(self, engine, seed: int, budget: int, max_slots: int):
        self.engine = engine
        self.budget = budget
        self.max_slots = max_slots
        self.rng = np.random.default_rng(seed % (2 ** 32))
        self.n = 0
        self.vocab = min(engine.tokenizer.vocab_size, engine.cfg.vocab_size)

    def _tokens(self, n: int) -> list:
        return [int(x) for x in self.rng.integers(3, self.vocab, n)]

    def _tick(self, rows: list) -> None:
        """rows: [(session id, prompt ids)] -> one engine.generate, as the
        batcher's `_plain_step` calls it."""
        n = len(rows)
        self.engine.generate(
            [p for _, p in rows], temperature=[1.0] * n, top_p=[1.0] * n,
            max_new_tokens=[self.budget] * n,
            session_ids=[s for s, _ in rows],
            constrain_json=[False] * n, action_enums=[None] * n,
            initial_json_state=[None] * n)

    def _seen(self) -> set:
        out = set()
        for e in self.engine.compiles.snapshot(max_shapes=4096)["shapes"]:
            parts = str(e["shape"]).split("x")
            if parts[0] == "ragged":
                out.add((int(parts[1]), int(parts[3])))
        return out

    def warm(self, keys: list) -> dict:
        """Touch every (TB, W) in `keys`; returns what was wanted, what the
        registry shows, and the ticks that missed their key."""
        missed = []
        for tb, w in keys:
            rows, made = [], []
            for prefix, new in plan(tb, w, self.engine.prompt_buckets,
                                    self.max_slots):
                sid = f"warm-{self.n}"
                self.n += 1
                made.append(sid)
                prompt = self._tokens(new)
                if prefix:
                    # the session holds its prompt and all but the last
                    # of the tokens it then generated
                    self._tick([(sid, self._tokens(
                        prefix - self.budget + 1))])
                    have = self.engine.session_tokens(sid) or []
                    prompt = list(have) + prompt
                rows.append((sid, prompt))
            self._tick(rows)
            if (tb, w) not in self._seen():
                missed.append([tb, w])
            for sid in made:
                self.engine.drop_session(sid)
        seen = self._seen()
        return {"wanted": [list(k) for k in keys],
                "extra": sorted(list(k) for k in seen
                                - {tuple(k) for k in keys}),
                "missed": missed}
