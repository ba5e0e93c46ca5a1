"""The one traffic generator: reads a mix from ``bench/traffic/<name>.json``.

Every seed gets the same multiset of prompt lengths, output lengths and
inter-arrival gaps, drawn as evenly spaced quantiles of the mix's
distributions; the seed only orders them and fills the prompts with
tokens. So two seeds ask the same work of the system, in another order.

A mix is ``"loop": "open"`` (Poisson arrivals at ``rate_per_s``, each
request due at a fixed time whatever the server does) or ``"closed"``
(``clients`` callers, each sending its next request when the last one
finished, with no think time). A closed loop's first ``clients``
requests, the batch its window opens with, are a quantile set of their
own, so every seed starts the same batch; later requests follow in
streams of ``CLOSED_STREAM``.

A mix's ``engine`` block builds the ``Engine`` in one of three layouts
(``bench.lib.harness.layout``). Every layout states ``slots``,
``max_len``, ``hot_cap``, ``sync_every``, ``prefill_chunk``, ``paged``
and ``prefix_sharing``:

* paged: ``"paged": true`` with ``prefill_chunk`` > 0 and the pool's
  size, ``pool_pages``; ``prefix_sharing`` as the cell needs. Only a
  full-attention model can page.
* contiguous: ``"paged": false``, ``"prefix_sharing": false``,
  ``prefill_chunk`` > 0, and no ``pool_pages``. Models whose cache
  chunked prefill can stream (full or windowed attention).
* whole-prompt: as contiguous, with ``"prefill_chunk": 0``. Any model.
  Admission compiles once per group of equal prompt lengths, so a
  whole-prompt mix must be a fixed batch: ``"loop": "closed"`` with
  ``"first_wave_in_setup": true``, at most ``slots`` clients, and
  outputs that outlast the window, so that nothing is admitted in it.
  The harness refuses any other whole-prompt mix before it makes the
  weights. Each prompt length of the batch compiles its own prefill in
  set-up: few distinct lengths keep set-up short.
"""

from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from statistics import NormalDist
from typing import List, Optional

import numpy as np

# lengths in the closed loop's stream, before it repeats in a new order
CLOSED_STREAM = 256


@dataclasses.dataclass
class Planned:
    """One request as the generator plans it."""

    index: int
    prompt: np.ndarray  # int32 tokens
    max_new: int
    due: Optional[float] = None  # open loop: seconds after the window opens


def load(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def quantile(dist: dict, u: float) -> float:
    kind = dist["dist"]
    lo, hi = dist["min"], dist["max"]
    if kind == "lognormal":
        x = dist["median"] * math.exp(dist["sigma"] * NormalDist().inv_cdf(u))
    elif kind == "uniform":
        x = lo + u * (hi - lo)
    elif kind == "loguniform":
        x = math.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return min(max(x, lo), hi)


def lengths(dist: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """n lengths at the quantiles (i + 1/2)/n, in the seed's order."""
    xs = [int(round(quantile(dist, (i + 0.5) / n))) for i in range(n)]
    return rng.permutation(np.asarray(xs, np.int64))


def arrivals(rate: float, seconds: float, rng) -> np.ndarray:
    """Due times of a Poisson stream at ``rate`` over ``seconds``: the
    exponential gaps at evenly spaced quantiles, in the seed's order."""
    n = max(int(round(rate * seconds)), 1)
    gaps = np.asarray([-math.log(1.0 - (i + 0.5) / n) / rate
                       for i in range(n)])
    due = np.cumsum(rng.permutation(gaps))
    return due[due < seconds]


class Mix:
    """The requests of one run, planned from the mix and the seed."""

    def __init__(self, mix: dict, seed: int, seconds: float, vocab: int):
        self.mix = mix
        self.vocab = vocab
        self.rng = np.random.default_rng(int(seed))
        self.open = mix["loop"] == "open"
        self._planned: List[Planned] = []
        self._next = 0
        if self.open:
            due = arrivals(mix["rate_per_s"], seconds, self.rng)
            self._extend(len(due), due)
        else:
            self._extend(mix["clients"])

    def _extend(self, n: int, due=None) -> None:
        """Plan ``n`` more requests at the mix's quantiles, in the seed's
        order."""
        prompts = lengths(self.mix["prompt"], n, self.rng)
        outputs = lengths(self.mix["output"], n, self.rng)
        base = len(self._planned)
        self._planned += [
            Planned(index=base + i,
                    prompt=self.rng.integers(0, self.vocab, size=int(p),
                                             dtype=np.int32),
                    max_new=int(o),
                    due=None if due is None else float(due[i]))
            for i, (p, o) in enumerate(zip(prompts, outputs))
        ]

    def scheduled(self) -> List[Planned]:
        """Open loop: every request of the window, by due time."""
        return list(self._planned)

    def next_request(self) -> Planned:
        """Closed loop: the next request of the stream."""
        if self._next == len(self._planned):
            self._extend(CLOSED_STREAM)
        p = self._planned[self._next]
        self._next += 1
        return p
