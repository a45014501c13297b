"""Open-loop traffic from a traffic file and a seed, and the feeder that
releases it into the server.

A traffic file fixes the functions, their popularity law, how the
ranking rotates, the total rate, the arrival times and which arrivals go
to the same function. The seed decides which function holds which
popularity rank and draws each request's prompt seed, so the same seed
gives the same schedule, and every seed the same work: the functions of
a cell share one configuration, so a relabelling changes no queue."""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Callable, List, Optional

import numpy as np


@dataclass(frozen=True)
class Arrival:
    t: float            # due, seconds after the window opens
    fn: int             # function index
    request_seed: int   # {"seed": ...} of the request


def function_ids(config_name: str, traffic: dict) -> List[str]:
    return [f"{config_name}/w{s}" for s in traffic["functions"]["weight_seeds"]]


def shares(traffic: dict) -> np.ndarray:
    """Popularity by rank: Zipf with exponent ``s`` over the functions."""
    pop = traffic["popularity"]
    n = len(traffic["functions"]["weight_seeds"])
    if pop["law"] != "zipf":
        raise ValueError(f"unknown popularity law {pop['law']!r}")
    w = 1.0 / np.arange(1, n + 1) ** float(pop["s"])
    return w / w.sum()


def rng_for(seed: int, stream: int = 0) -> np.random.Generator:
    """A generator for ``seed`` (any whole number) and a sub-stream."""
    return np.random.default_rng([seed % 2**64, stream])


def schedule(traffic: dict, seed: int, seconds: float) -> List[Arrival]:
    """Arrivals in a window of ``seconds``: ``rate * seconds`` of them at
    the same times for every seed, from the traffic file's
    ``arrival_seed``: gaps that are the quantiles of an exponential
    distribution, in an order that seed draws (a Poisson-like stream
    whose bursts do not change from run to run). Each popularity rank
    gets its exact share of the arrivals, and which arrival goes to which
    rank is drawn from ``arrival_seed`` too; ``seed`` draws the function
    that holds each rank and each request's prompt. The ranking moves on
    by one function every ``rotation_s`` seconds (0: fixed)."""
    n_fn = len(traffic["functions"]["weight_seeds"])
    n = int(round(float(traffic["rate"]["inv_s"]) * seconds))
    fixed = rng_for(traffic["arrival_seed"], stream=3)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps = fixed.permutation(gaps)
    gaps *= seconds / max(gaps.sum(), 1e-12)
    times = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])[:n]
    p = shares(traffic) * n
    counts = np.floor(p).astype(int)
    for r in np.argsort(-(p - counts), kind="stable")[:n - counts.sum()]:
        counts[r] += 1
    ranks = fixed.permutation(np.repeat(np.arange(n_fn), counts))
    rot = float(traffic.get("rotation_s", 0) or 0)
    shift = (times // rot).astype(int) if rot > 0 else np.zeros(n, int)
    rng = rng_for(seed)
    holder = rng.permutation(n_fn)
    req = rng.integers(0, 2**31 - 1, size=n)
    return [Arrival(float(t), int(holder[(r + s) % n_fn]), int(q))
            for t, r, s, q in zip(times, ranks, shift, req)]


@dataclass
class Release:
    arrival: Arrival
    due: float          # host monotonic seconds
    release: float      # host monotonic seconds at submit
    inv: object         # the server's Invocation
    offset: float       # host monotonic minus the executor's clock


class Feeder(threading.Thread):
    """Releases each arrival at its due time, never early, and keeps how
    late it ran. ``submit(arrival)`` hands one request to the server and
    returns its Invocation."""

    def __init__(self, arrivals: List[Arrival], origin: float,
                 submit: Callable[[Arrival], object]):
        super().__init__(name="bench-feeder", daemon=True)
        self.arrivals, self.origin, self.submit = arrivals, origin, submit
        self.released: List[Release] = []
        self.error: Optional[BaseException] = None

    def run(self) -> None:
        try:
            for a in self.arrivals:
                due = self.origin + a.t
                while True:
                    left = due - time.monotonic()
                    if left <= 0:
                        break
                    time.sleep(min(left, 0.05))
                release = time.monotonic()
                inv = self.submit(a)
                self.released.append(Release(
                    a, due, release, inv, release - inv.arrival))
        except BaseException as e:  # noqa: BLE001 - re-raised by the caller
            self.error = e
