"""Whether what the timed path served is correct.

After the window closes, a sample is drawn from the seed of the requests
the window finished and of those it was still serving at its close (with
the tokens served to them so far), the longest always among them, until
it holds some hundreds of served tokens. The configuration's plain
reference runs once over each sampled prompt with its served tokens, and
the number compared is the widest gap by which a served (greedy) token's reference logit lies
below the reference's best logit at that position:

    logit_gap = max over served tokens t at position p of
                max_v ref[p, v] - ref[p, t]

A correct program serves the reference's argmax up to rounding, so the
gap stays near the rounding of the logits. Its limit, and the readings
it was set from, are in ``bench/cells/<cell>.json``.
"""

from __future__ import annotations

import importlib
from typing import Dict, List

import numpy as np

SAMPLE_TOKENS = 512  # served tokens the sample aims at
SAMPLE_MAX = 8  # requests at most


def sample(records, seed: int) -> List:
    """The longest served request, then others in the seed's order, until
    the sample holds ``SAMPLE_TOKENS`` served tokens."""
    done = [r for r in records if r.served is not None and len(r.served)
            and r.outcome in (None, "finished")]
    if not done:
        return []
    done.sort(key=lambda r: r.index)
    longest = max(done, key=lambda r: (r.prompt_len + len(r.served), r.index))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng(int(seed) + 1).permutation(len(rest))
    picked = [longest]
    served = len(longest.served)
    for i in order:
        if served >= SAMPLE_TOKENS or len(picked) >= SAMPLE_MAX:
            break
        picked.append(rest[i])
        served += len(rest[i].served)
    return picked


def logit_gap(conf: dict, seed: int, picked) -> Dict[str, float]:
    """The widest reference-logit gap of the served tokens in ``picked``."""
    ref = importlib.import_module(f"bench.references.{conf['reference']}")
    seqs = []
    for r in picked:
        full = np.concatenate([r.prompt, r.served[:-1]]).astype(np.int32)
        rows = np.arange(r.prompt_len - 1, r.prompt_len - 1 + len(r.served))
        seqs.append((full, rows))
    length = max(len(full) for full, _ in seqs)
    widest = 0.0
    for (_, rows), r, lg in zip(seqs, picked,
                                ref.logits(conf, seed, seqs, length)):
        best = lg.max(axis=-1)
        got = lg[np.arange(len(rows)), r.served]
        widest = max(widest, float((best - got).max()))
    return {"logit_gap": widest,
            "tokens": float(sum(len(r.served) for r in picked)),
            "requests": float(len(picked))}
