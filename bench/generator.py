"""The one traffic generator: reads a mix's parameters, draws from the seed.

A mix (``bench/traffic/<name>.json``) names its input ``sizes``, a ``pool``
count for each size, and one or more ``streams`` of requests that share the
engine's queue.  Each stream gives ``mix``, a weight per size (0 for a size
it never sends), an optional ``priority`` (the engine serves higher first;
0 if absent) and how its requests arrive:

* ``"loop": "closed"`` — one client that keeps ``queued`` requests waiting
  behind the one in service, so the engine never waits on it.  Its
  requests carry no due time, and it sends them for as long as it is asked.
* ``"loop": "open"`` — independent users at a mean ``rate_per_s``.  A run
  of ``seconds`` gets ``round(rate_per_s · seconds)`` requests: sizes in
  the mix's exact proportions and gaps at the quantiles of the exponential
  distribution, shuffled once into one arrival trace that every seed
  shares.  The seed rotates the trace, so each seed meets the same bursts
  and lulls from another starting point: the seed changes the order, never
  the amount or the burstiness of the work, and a tail over a window of a
  few hundred requests reads alike from seed to seed.  With ``"bursts":
  {"on_s": a, "off_s": b}`` the users send only in the first ``a`` seconds
  of every ``a + b``, at the same mean rate over the window.

Inputs are float32 noise.  Each size has a pool of ``pool[i]`` distinct
volumes, made once at set-up and cycled, so a run's inputs cost no time in
the window.  ``warmup`` lists request sequences, by size index, that set-up
serves before the window so that every program the mix reaches is compiled.
``sweeps_open``, where given, is how many sweeps of the largest size the
engine's device budget leaves room for.
"""

from __future__ import annotations

import itertools
import math
from dataclasses import dataclass
from typing import Iterator, List, Optional, Sequence

import numpy as np

SEED_MOD = 2**64


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """A numpy generator for ``stream`` of ``seed``; any whole number works."""
    return np.random.default_rng([int(seed) % SEED_MOD, *stream])


@dataclass(frozen=True)
class Arrival:
    size: int  # index into the mix's ``sizes``
    pool: int  # which volume of that size's pool
    due: Optional[float]  # seconds after the window opens; None: closed loop
    stream: int = 0  # index into the mix's ``streams``


@dataclass(frozen=True)
class Stream:
    index: int
    spec: dict  # the stream's entry in the mix
    arrivals: Iterator[Arrival]  # closed: endless; open: the window's, by due


def _exact_counts(n: int, mix: Sequence[float]) -> List[int]:
    """Split ``n`` by the weights in ``mix``, largest remainders first."""
    total = float(sum(mix))
    raw = [n * w / total for w in mix]
    counts = [math.floor(r) for r in raw]
    order = sorted(range(len(mix)), key=lambda i: raw[i] - counts[i], reverse=True)
    for i in order[: n - sum(counts)]:
        counts[i] += 1
    return counts


def make_pools(traffic: dict, seed: int, in_channels: int) -> List[List[np.ndarray]]:
    """The input volumes, ``pools[size][i]`` of shape (in_channels, X, Y, Z)."""
    rng = rng_for(seed, 1)
    return [
        [
            rng.standard_normal((in_channels,) + tuple(shape), dtype=np.float32)
            for _ in range(n)
        ]
        for shape, n in zip(traffic["sizes"], traffic["pool"])
    ]


def _key(k: int) -> tuple:
    # stream 0 keeps the key the first cells were measured with
    return (2,) if k == 0 else (2, k)


def _closed_kinds(mix, rng) -> Iterator[int]:
    """Whole periods of the mix's integer weights, each period shuffled."""
    period = np.repeat(np.arange(len(mix)), [int(w) for w in mix])
    while True:
        rng.shuffle(period)
        yield from (int(s) for s in period)


def _open_trace(spec: dict, k: int, rng, seconds: float):
    """(size indices, due times) of an open stream's window."""
    mix = spec["mix"]
    n = max(1, round(spec["rate_per_s"] * seconds))
    trace = rng_for(0, *_key(k))  # the one arrival trace every seed shares
    kinds = np.repeat(np.arange(len(mix)), _exact_counts(n, mix))
    trace.shuffle(kinds)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)  # exponential quantiles
    trace.shuffle(gaps)
    turn = int(rng.integers(n))
    kinds, gaps = np.roll(kinds, turn), np.roll(gaps, turn)
    bursts = spec.get("bursts")
    on = seconds * bursts["on_s"] / (bursts["on_s"] + bursts["off_s"]) if bursts else seconds
    # the sending time holds the n gaps exactly: the mean rate is n / seconds
    due = (np.cumsum(gaps) - gaps[0]) * (on / gaps.sum())
    if bursts:
        # lay the sending time out in on-periods, each followed by its lull
        a, b = bursts["on_s"], bursts["off_s"]
        due = np.floor(due / a) * (a + b) + np.mod(due, a)
    return kinds, [float(t) for t in due]


def streams(traffic: dict, seed: int, seconds: float) -> List[Stream]:
    """The mix's streams for one run of ``seconds`` from ``seed``."""
    out = []
    for k, spec in enumerate(traffic["streams"]):
        rng = rng_for(seed, *_key(k))
        if spec["loop"] == "closed":
            kinds = _closed_kinds(spec["mix"], rng)
            due = itertools.repeat(None)
        elif spec["loop"] == "open":
            kinds, due = _open_trace(spec, k, rng, seconds)
        else:
            raise ValueError(f"unknown loop {spec['loop']!r} in stream {k}")
        out.append(Stream(k, spec, _numbered(kinds, due, traffic["pool"], k)))
    return out


def _numbered(kinds, due, pools, k: int) -> Iterator[Arrival]:
    seen = [0] * len(pools)
    for s, t in zip(kinds, due):
        s = int(s)
        yield Arrival(s, seen[s] % pools[s], t, k)
        seen[s] += 1
