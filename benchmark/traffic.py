"""What every traffic generator shares: the turn a client sends, seeded
text of a stated length in tokens, and the loader that finds a generator by
the `kind` in a traffic file.

A generator is `benchmark/generators/<kind>.py` with one function

    build(params, seed, n_turns, text) -> list[Client]

and a `Client` has `next(prev) -> Turn`: the next request of a closed loop,
given the result of the last one (None at the start). Everything a client
will send is drawn from the seed inside `build`, before the window opens;
`next` only appends what the model answered. The program sees messages and
nothing else.
"""

from __future__ import annotations

import dataclasses
import importlib
import os
import re

from benchmark import configs, draws

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass
class Turn:
    """One request of a closed-loop client."""
    messages: list
    session_id: str
    max_tokens: int
    temperature: float
    think_s: float                      # wait before sending it
    drop: tuple = ()                    # sessions that ended: drop first
    new_session: bool = False


class SeededText:
    """Text of a stated number of tokens, drawn from the seed: words of the
    frozen system prompt in an order from sha256, cut to length under the
    served model's own tokenizer (lengths are stated in ITS tokens) and
    opened with a tag of its own, so that no two texts share a prefix."""

    def __init__(self, tokenizer, seed: int):
        self.tok = tokenizer
        self.seed = seed
        with open(os.path.join(HERE, "system_prompt.txt")) as f:
            words = sorted(set(re.findall(r"[A-Za-z]{3,12}", f.read())))
        self.words = words

    def make(self, stream: str, n_tokens: int) -> str:
        n_words = int(n_tokens * 1.3) + 8
        idx = [draws.draw_int(self.seed, stream, i, 0, len(self.words) - 1)
               for i in range(n_words)]
        tag = f"[{stream} {draws.draw_int(self.seed, stream, -1, 0, 10**9)}]"
        ids = self.tok.encode(tag + " " + " ".join(self.words[i]
                                                   for i in idx))
        return self.tok.decode(ids[:max(1, n_tokens)])

    def count(self, text: str) -> int:
        return len(self.tok.encode(text))

    def count_chat_glue(self) -> int:
        """Tokens the chat template adds around one user message."""
        return len(self.tok.encode_chat([{"role": "user", "content": ""}]))


def load_traffic(name: str, root: str = HERE) -> dict:
    return configs.load_json(root, "traffic", f"{name}.json")


def load_generator(kind: str):
    return importlib.import_module(f"benchmark.generators.{kind}")
