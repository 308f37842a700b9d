"""Seeded draws for the traffic generators: no `random`, no clock.

`draw`, `draw_exp` and `draw_int` are copied from
`quoracle_tpu/sim/workload.py` (sha256 of "seed:stream:n"), so that the
yardstick does not move when the program's simulator does. `permutation`
and `stratified` are the benchmark's own: every seed gets the SAME set of
values in another order, so that the seed changes the order of the work and
not its amount.
"""

from __future__ import annotations

import hashlib
import math

_U64 = float(1 << 64)


def draw(seed: int, stream: str, n: int) -> float:
    """Uniform [0, 1) from sha256(seed:stream:n)."""
    digest = hashlib.sha256(f"{seed}:{stream}:{n}".encode()).digest()
    return int.from_bytes(digest[:8], "big") / _U64


def draw_exp(seed: int, stream: str, n: int, mean: float) -> float:
    """Exponential with the given mean (inverse transform)."""
    u = draw(seed, stream, n)
    return -mean * math.log(1.0 - u)


def draw_int(seed: int, stream: str, n: int, lo: int, hi: int) -> int:
    """Integer in [lo, hi] inclusive."""
    if hi <= lo:
        return lo
    return lo + int(draw(seed, stream, n) * (hi - lo + 1))


def permutation(seed: int, stream: str, n: int) -> list[int]:
    """A permutation of range(n) (Fisher-Yates over `draw`)."""
    out = list(range(n))
    for i in range(n - 1, 0, -1):
        j = draw_int(seed, stream, i, 0, i)
        out[i], out[j] = out[j], out[i]
    return out


def stratified(seed: int, stream: str, count: int, block: int,
               phase: float = 0.5) -> list[float]:
    """`count` numbers in (0, 1): block after block of the `block` quantile
    points (i + phase) / block, each block in an order drawn from the seed.
    Fed through an inverse CDF, every stretch of `block` values holds the
    whole distribution, whatever the seed; clients that take different
    phases fill in each other's gaps."""
    out: list[float] = []
    b = 0
    while len(out) < count:
        out += [(i + phase) / block
                for i in permutation(seed, f"{stream}:{b}", block)]
        b += 1
    return out[:count]


def log_uniform(u: float, lo: float, hi: float) -> float:
    """Inverse CDF of the log-uniform distribution on [lo, hi]."""
    return lo * (hi / lo) ** u


def exponential(u: float, mean: float) -> float:
    """Inverse CDF of the exponential distribution."""
    return -mean * math.log(1.0 - u)


def weighted(u: float, values: list, weights: list) -> object:
    """Inverse CDF of a discrete distribution."""
    total = float(sum(weights))
    acc = 0.0
    for v, w in zip(values, weights):
        acc += w / total
        if u < acc:
            return v
    return values[-1]
