"""Seeded request traffic for the chip benchmark, read from a mix's data file.

One general generator serves every mix in ``traffic/<name>.json``; a new mix
is a new data file. The arrival shapes are copied from the server's own
``serving/workload.py`` (poisson and bursty gaps at a mean rate) so that a
later change to the program cannot move the yardstick; the lengths add a
clipped log-normal to the uniform draw.

Every seed gets the same work in another order. The sizes and gaps are
stratified quantiles of their distributions, jittered from the mix's own
``shape_seed``. The sizes are dealt out in rounds of ``strata`` consecutive
requests, each round holding one value from each stratum: an open loop's
window deals its whole set, a batch draws round after round. The gaps of an
open loop are the same set for every seed, in an order that ``--seed``
permutes over the whole window: the arrivals clump as a Poisson stream does
at every time scale shorter than the window, and only the number of
requests in the window is fixed. ``--seed`` also deals the sizes to rounds,
orders each round and draws the token ids.

A mix file holds::

    loop        "open" (requests due on a schedule) or "batch" (backlogged)
    rate_per_s  open loop: mean arrival rate (the cell's fixed offered load)
    arrivals    open loop: "poisson" or "bursty" (with "burst", requests per
                burst, 20x tighter gaps inside a burst as in workload.py)
    queue_depth batch: requests kept waiting beyond the busy slots
    prompt, output  {"dist": "uniform", "min", "max"} or
                {"dist": "lognormal", "median", "sigma", "min", "max"}
    strata      requests per round
    shape_seed  fixes the set of sizes and gaps
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist

import numpy as np

LOOPS = ("open", "batch")
DISTS = ("uniform", "lognormal")
ARRIVALS = ("poisson", "bursty")
_STD_NORMAL = NormalDist()


@dataclass(frozen=True)
class Item:
    """One request as the generator draws it; ``due`` is seconds after the
    window opens (0 for a batch, whose requests are due when submitted)."""
    index: int
    prompt: np.ndarray
    max_new_tokens: int
    due: float


def load_mix(path: str | Path) -> dict:
    mix = json.loads(Path(path).read_text())
    validate_mix(mix)
    return mix


def validate_mix(mix: dict) -> None:
    if mix.get("loop") not in LOOPS:
        raise ValueError(f"loop must be one of {LOOPS}: {mix.get('loop')!r}")
    for key in ("prompt", "output"):
        spec = mix[key]
        if spec.get("dist") not in DISTS:
            raise ValueError(f"{key}.dist must be one of {DISTS}")
        if not 1 <= spec["min"] <= spec["max"]:
            raise ValueError(f"{key}: need 1 <= min <= max")
        if spec["dist"] == "lognormal" and not (
                spec["median"] > 0 and spec["sigma"] > 0):
            raise ValueError(f"{key}: lognormal needs median, sigma > 0")
    if mix["loop"] == "open":
        if not mix["rate_per_s"] > 0:
            raise ValueError("open loop needs rate_per_s > 0")
        if mix.get("arrivals", "poisson") not in ARRIVALS:
            raise ValueError(f"arrivals must be one of {ARRIVALS}")
    elif not mix["queue_depth"] >= 1:
        raise ValueError("batch needs queue_depth >= 1")
    if not mix["strata"] >= 1:
        raise ValueError("strata must be >= 1")


def quantile(spec: dict, u: np.ndarray) -> np.ndarray:
    """Lengths at quantiles ``u`` in (0, 1) of a length distribution."""
    lo, hi = spec["min"], spec["max"]
    if spec["dist"] == "uniform":
        vals = lo + np.floor(u * (hi - lo + 1))
    else:
        z = np.array([_STD_NORMAL.inv_cdf(float(x)) for x in u])
        vals = np.rint(np.exp(math.log(spec["median"]) + spec["sigma"] * z))
    return np.clip(vals, lo, hi).astype(np.int64)


def _strata(rng: np.random.Generator, n: int) -> np.ndarray:
    """``n`` jittered stratified quantiles, stratum order 0..n-1."""
    return (np.arange(n) + rng.uniform(0.0, 1.0, n)) / n


def _deal(values: np.ndarray, s: int, order: np.random.Generator):
    """``values`` (n = r s of them) dealt into r rounds of ``s``: sorted, cut
    into s strata of r consecutive values, each round given one value of
    every stratum; which one, and the order inside a round, from
    ``order``. Returns them round after round."""
    strata = np.sort(values).reshape(s, -1)
    strata = np.stack([order.permutation(row) for row in strata])
    return np.concatenate([order.permutation(col) for col in strata.T])


def _gaps(mix: dict, u: np.ndarray) -> np.ndarray:
    """Gaps between due times at quantiles ``u``: exponential at the mean
    rate, or as ``serving/workload.py``'s bursts of ``burst`` requests whose
    starts are exponential at ``rate / burst`` with gaps 20x tighter than
    the mean inside a burst (every ``burst``-th gap starts a burst)."""
    rate = mix["rate_per_s"]
    gaps = -np.log1p(-u) / rate
    if mix.get("arrivals", "poisson") == "bursty":
        b = int(mix["burst"])
        head = np.arange(len(u)) % b == 0
        gaps = np.where(head, gaps * b, gaps / 20.0)
    return gaps


def tokens(seed: int, index: int, length: int, vocab: int) -> np.ndarray:
    rng = np.random.default_rng([seed, index, 1])
    return rng.integers(0, vocab, length).astype(np.int32)


def open_schedule(mix: dict, seed: int, seconds: float,
                  vocab: int) -> list[Item]:
    """The requests of an open-loop window of ``seconds``, ascending by due
    time: ``rate_per_s * seconds`` of them, rounded to whole rounds."""
    s = int(mix["strata"])
    n = s * max(1, int(round(mix["rate_per_s"] * seconds / s)))
    shape = np.random.default_rng([mix["shape_seed"], 0])
    order = np.random.default_rng([seed, 0])
    if mix.get("arrivals", "poisson") == "poisson":
        gaps = order.permutation(_gaps(mix, _strata(shape, n)))
    else:                   # keep each burst's leading gap in its place
        gaps = _gaps(mix, _strata(shape, n))
        head = np.arange(n) % int(mix["burst"]) == 0
        for mask in (head, ~head):
            gaps[mask] = order.permutation(gaps[mask])
    due = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    prompts = _deal(quantile(mix["prompt"], _strata(shape, n)), s, order)
    outputs = _deal(quantile(mix["output"], _strata(shape, n)), s, order)
    return [Item(i, tokens(seed, i, int(prompts[i]), vocab),
                 int(outputs[i]), float(due[i])) for i in range(n)]


class BatchStream:
    """Endless backlogged requests, ``strata`` to a round."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.mix, self.seed, self.vocab = mix, seed, vocab
        self._round: list[Item] = []
        self._next_round = 0
        self._index = 0

    def _fill(self) -> None:
        s, r = int(self.mix["strata"]), self._next_round
        shape = np.random.default_rng([self.mix["shape_seed"], 1, r])
        order = np.random.default_rng([self.seed, 1, r])
        prompts = order.permutation(quantile(self.mix["prompt"],
                                             _strata(shape, s)))
        outputs = order.permutation(quantile(self.mix["output"],
                                             _strata(shape, s)))
        for p, o in zip(prompts, outputs):
            i = self._index
            self._round.append(Item(i, tokens(self.seed, i, int(p),
                                              self.vocab), int(o), 0.0))
            self._index += 1
        self._next_round += 1

    def next(self) -> Item:
        if not self._round:
            self._fill()
        return self._round.pop(0)


def longest_request(mix: dict) -> int:
    """Cache rows the mix's largest request can need."""
    return mix["prompt"]["max"] + mix["output"]["max"] - 1
