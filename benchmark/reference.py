"""What the output check shares across families: how the reference of a
configuration's family (`families/<family>.py`, `Reference(raw, seed)`) is
asked for the logits of a served row, and the gap that is compared. Nothing
here knows an architecture; the forward pass and its weights are the
family's.
"""

from __future__ import annotations

import numpy as np


def served_logits(ref, ids: list, n_prompt: int, pad_to: int,
                  rows_pad: int = 128) -> np.ndarray:
    """The reference's logits at the generated positions of `ids`: row i
    predicts ids[n_prompt + i]."""
    n = len(ids)
    tokens = np.zeros((pad_to,), np.int32)
    tokens[:n] = ids
    rows = np.arange(n_prompt - 1, n - 1)
    padded = np.zeros((-(-len(rows) // rows_pad) * rows_pad,), np.int32)
    padded[:len(rows)] = rows
    return ref.logits(tokens, padded)[:len(rows)]


def gaps_of(logits: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """Per position: the best logit minus the logit of `tokens` there."""
    return logits.max(-1) - logits[np.arange(len(tokens)), tokens]
