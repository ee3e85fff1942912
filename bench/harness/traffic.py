"""The one traffic generator: turns a mix's data file and a seed into work.

A mix file (``bench/traffic/<name>.json``) holds parameters only:

``loop``                ``"closed"``: ``clients`` callers that each send
                        their next request when the last one is answered,
                        with no think time. (No other loop is generated
                        yet.)
``clients``             the number of callers.
``windows_per_request`` the window counts of successive requests, cycled
                        in the listed order: every seed asks for the same
                        sizes in the same order, so a closed loop groups
                        them into the same flushes and the seed changes the
                        data, not the work.
``centers``             where window centers fall:
                        ``{"kind": "uniform", "lo", "hi"}`` draws centers
                        uniformly, never repeating one; ``lo`` and ``hi``
                        are ``[anchor, k]`` = anchor + k * b_t, with anchor
                        ``t_min`` or ``t_max`` of the data and ``b_t`` the
                        configuration's temporal bandwidth.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Iterator, List, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class Mix:
    loop: str
    clients: int
    windows_per_request: Tuple[int, ...]
    centers: dict

    @classmethod
    def load(cls, path: str) -> "Mix":
        with open(path) as f:
            d = json.load(f)
        if d.get("loop") != "closed":
            raise ValueError(f"{path}: only a closed loop is generated")
        mix = cls(loop=d["loop"], clients=int(d.get("clients") or 0),
                  windows_per_request=tuple(int(w) for w in d["windows_per_request"]),
                  centers=dict(d["centers"]))
        if mix.clients <= 0:
            raise ValueError(f"{path}: a closed loop needs clients > 0")
        if mix.centers.get("kind") != "uniform":
            raise ValueError(f"{path}: unknown centers kind {mix.centers.get('kind')!r}")
        return mix


def _anchor(spec, t_min: float, t_max: float, b_t: float) -> float:
    base, k = spec
    return {"t_min": t_min, "t_max": t_max}[base] + float(k) * b_t


class Centers:
    """Draws window-center tuples for requests from one seeded stream."""

    def __init__(self, spec: dict, rng: np.random.Generator, *, t_min: float,
                 t_max: float, b_t: float):
        self.rng = rng
        self.lo = _anchor(spec["lo"], t_min, t_max, b_t)
        self.hi = _anchor(spec["hi"], t_min, t_max, b_t)
        self.seen: set = set()

    def draw(self, n_windows: int) -> Tuple[float, ...]:
        out: List[float] = []
        while len(out) < n_windows:
            t = float(self.rng.uniform(self.lo, self.hi))
            if t not in self.seen:
                self.seen.add(t)
                out.append(t)
        return tuple(out)


def closed_stream(mix: Mix, seed: int, *, t_min: float, t_max: float,
                  b_t: float) -> Iterator[Tuple[float, ...]]:
    """The closed loop's requests in submission order (centers tuples):
    whichever client is free next takes the next one. Window counts follow
    the mix's list, cycled in its order."""
    centers = Centers(mix.centers, np.random.default_rng([seed, 2]),
                      t_min=t_min, t_max=t_max, b_t=b_t)
    while True:
        for n in mix.windows_per_request:
            yield centers.draw(n)
