"""The one traffic generator: a traffic file of parameters plus ``--seed``
gives the requests of a run.

Every seed gets the same multiset of sizes and of arrival gaps, in another
order: lengths are the ``levels`` mid-quantiles of the file's clipped
log-normal, gaps the ``gap_levels`` mid-quantiles of the exponential at the
file's rate, each dealt out epoch by epoch in an order drawn from the seed.
So two seeds differ in order and in token ids, never in the amount of work,
and the distinct prompt lengths of a run are known before it starts (the
warm-up covers each).
"""

from __future__ import annotations

import json
import math
import random
from dataclasses import dataclass
from statistics import NormalDist


@dataclass(frozen=True)
class Request:
    index: int
    due_s: float  # open loop: offset from the window's start; closed loop: 0
    prompt: tuple[int, ...]
    max_new_tokens: int


def load(path: str) -> dict:
    with open(path) as f:
        spec = json.load(f)
    if spec["loop"] not in ("open", "closed", "steps"):
        raise ValueError(f"{path}: loop must be open, closed or steps")
    if spec["loop"] == "open" and not spec.get("rate_rps", 0) > 0:
        raise ValueError(f"{path}: an open loop needs rate_rps > 0")
    if spec["loop"] == "closed" and not spec.get("clients", 0) > 0:
        raise ValueError(f"{path}: a closed loop needs clients > 0")
    return spec


def length_levels(dist: dict) -> list[int]:
    """The ``levels`` mid-quantiles of a log-normal clipped to [min, max]."""
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    normal = NormalDist(math.log(dist["median"]), dist["sigma"])
    n = dist["levels"]
    return [
        int(min(max(round(math.exp(normal.inv_cdf((i + 0.5) / n))), dist["min"]), dist["max"]))
        for i in range(n)
    ]


def gap_levels(rate_rps: float, n: int) -> list[float]:
    """The ``n`` mid-quantiles of the exponential gap, rescaled so that
    their mean is exactly 1/rate (mid-quantiles cut the tail's mass)."""
    raw = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = (1.0 / rate_rps) / (sum(raw) / n)
    return [g * scale for g in raw]


def _dealer(rng: random.Random, levels: list):
    """Deal the levels out epoch by epoch, each epoch in a fresh order."""
    while True:
        epoch = list(levels)
        rng.shuffle(epoch)
        yield from epoch


def stream(spec: dict, seed: int, vocab: int):
    """The mix's requests without end, in the seed's order.  An open loop's
    ``due_s`` grows by the dealt gaps; a closed loop's is 0."""
    rng = random.Random(f"chipbench-traffic-{seed}")
    prompts = _dealer(rng, length_levels(spec["prompt_tokens"]))
    outputs = _dealer(rng, length_levels(spec["output_tokens"]))
    gaps, due = None, 0.0
    if spec["loop"] == "open":
        levels = gap_levels(spec["rate_rps"], spec.get("gap_levels", 64))
        gaps = _dealer(rng, levels)
        # Whole epochs end on a multiple of the mean gap, where a window may
        # end too: half the smallest gap earlier, every seed's window holds
        # the same number of arrivals whatever the rounding.
        due = -min(levels) / 2.0
    ids = random.Random(f"chipbench-ids-{seed}")
    shared = spec.get("shared_prefix_tokens", 0)
    prefix = tuple(ids.randrange(vocab) for _ in range(shared))
    index = 0
    while True:
        if gaps is not None:
            due += next(gaps)
        plen = next(prompts)
        body = tuple(ids.randrange(vocab) for _ in range(max(plen - shared, 1)))
        yield Request(index, due, (prefix + body)[: max(plen, 1)], next(outputs))
        index += 1


def generate(spec: dict, seed: int, seconds: float, vocab: int) -> list[Request]:
    """An open loop's requests for a window of ``seconds``: every arrival
    due inside it."""
    out = []
    for req in stream(spec, seed, vocab):
        if req.due_s >= seconds:
            return out
        out.append(req)


def warmup_lengths(spec: dict) -> list[int]:
    """Every distinct prompt length the mix can send, longest first."""
    return sorted(set(length_levels(spec["prompt_tokens"])), reverse=True)
