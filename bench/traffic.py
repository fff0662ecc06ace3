"""The one traffic generator: reads a traffic file's parameters and a seed,
and gives the requests of one run.

Every shape of load comes from parameters, so a new mix is a data file:

* ``rate_per_s`` — open-loop Poisson arrivals at that rate.
* ``phases`` — open-loop arrivals whose rate changes: a list of
  ``[rate_per_s, seconds]`` pairs, repeated from the first arrival on. On/off
  bursts are two phases, the second at rate 0.
* ``backlog`` — that many requests, all due when the window opens.

Open-loop arrivals are a unit-rate process mapped through the cumulative
rate. Its gaps are the quantiles of the unit exponential distribution, in an
order fixed by the file's ``schedule_seed``, so every seed gets the same
arrival times. The first arrivals fall ``ramp_s`` before the window opens,
so the queue is in its steady state when the window opens.

The seed draws each request's prompt seed and, from a set with the file's
``arms`` weights in fixed proportions, the order of the arms.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional

import numpy as np

#: prompt seeds stay below 2**31: the executor keys noise by int32 seeds
PROMPT_SEED_LIMIT = 2 ** 31 - 1


@dataclass
class Request:
    rid: int
    due: float  # seconds from window open (negative: a ramp arrival)
    arm: str
    prompt_seed: int
    dispatch: Optional[float] = None
    done: Optional[float] = None
    ok: bool = False
    batch: Optional[int] = None
    output: Optional[np.ndarray] = None


def _arm_counts(arms: dict, n: int) -> List[str]:
    """``n`` arm labels in the file's proportions (largest remainder)."""
    labels = sorted(arms)
    w = np.asarray([float(arms[a]) for a in labels])
    exact = w / w.sum() * n
    counts = np.floor(exact).astype(int)
    for i in np.argsort(-(exact - counts), kind="stable")[: n - counts.sum()]:
        counts[i] += 1
    return [a for a, c in zip(labels, counts) for _ in range(c)]


def _phases(traffic: dict) -> np.ndarray:
    """[[rate_per_s, seconds], ...] of one cycle of the arrival rate."""
    if "phases" in traffic:
        return np.asarray(traffic["phases"], dtype=np.float64)
    return np.asarray([[float(traffic["rate_per_s"]), 1.0]])


def _expected(phases: np.ndarray, t: float) -> float:
    """Arrivals expected in the first ``t`` seconds."""
    period, per_cycle = phases[:, 1].sum(), phases.prod(1).sum()
    cycles, rest = divmod(t, period)
    ends = np.cumsum(phases[:, 1])
    into = np.clip(rest - (ends - phases[:, 1]), 0.0, phases[:, 1])
    return cycles * per_cycle + float((phases[:, 0] * into).sum())


def _time_of(phases: np.ndarray, u: np.ndarray) -> np.ndarray:
    """The times at which ``u`` arrivals are expected (inverse of
    :func:`_expected`); a phase at rate 0 receives none."""
    period, per_cycle = phases[:, 1].sum(), phases.prod(1).sum()
    cycles, rest = np.divmod(u, per_cycle)
    mass = np.cumsum(phases.prod(1))
    k = np.minimum(np.searchsorted(mass, rest, side="right"), len(phases) - 1)
    start = np.cumsum(phases[:, 1]) - phases[:, 1]
    before = mass - phases.prod(1)
    return cycles * period + start[k] + (rest - before[k]) / phases[k, 0]


def arrival_times(traffic: dict, seconds: float) -> np.ndarray:
    """Due times in seconds from window open, sorted."""
    if traffic.get("backlog") is not None:
        return np.zeros(int(traffic["backlog"]))
    phases = _phases(traffic)
    ramp = float(traffic.get("ramp_s", 0.0))
    n = int(round(_expected(phases, ramp + seconds)))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    gaps = np.random.default_rng(traffic["schedule_seed"]).permutation(gaps)
    return -ramp + _time_of(phases, np.cumsum(gaps) - gaps[0])


def requests(traffic: dict, seed: int, seconds: float) -> List[Request]:
    """The requests of one run, sorted by due time."""
    due = arrival_times(traffic, seconds)
    rng = np.random.default_rng(int(seed) % (1 << 64))
    arms = rng.permutation(np.asarray(_arm_counts(traffic["arms"], len(due)),
                                      dtype=object))
    seeds = rng.integers(0, PROMPT_SEED_LIMIT, size=len(due))
    return [Request(i, float(t), str(a), int(s))
            for i, (t, a, s) in enumerate(zip(due, arms, seeds))]


def is_backlog(traffic: dict) -> bool:
    return traffic.get("backlog") is not None
