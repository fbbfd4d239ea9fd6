"""Set a deployment up from its file: data and queries from the seed
(through the generator that ``cfg["data"]`` names under ``datasets/``),
the index through the program's own build path (the module that
``cfg["index"]`` names under ``indexes/``), and the service's
continuous-batching scheduler.
"""
from __future__ import annotations

import dataclasses
from pathlib import Path
from typing import Dict

import numpy as np

from bench import spec


def program_config(cfg: dict):
    """The program's ``PHNSWConfig`` with every field the deployment's
    file names."""
    from repro.configs.base import PHNSWConfig
    names = {f.name for f in dataclasses.fields(PHNSWConfig)}
    kw = {k: (tuple(v) if isinstance(v, list) else v)
          for k, v in cfg.items() if k in names}
    return PHNSWConfig(**kw)


def make_data(cfg: dict, seed: int, bench: Path = spec.BENCH
              ) -> np.ndarray:
    return spec.dataset(cfg, bench).make(cfg, seed)


def make_queries(cfg: dict, x: np.ndarray, n: int, seed: int,
                 stream: int, bench: Path = spec.BENCH) -> np.ndarray:
    """``n`` queries of stream ``stream`` for run seed ``seed``."""
    return spec.dataset(cfg, bench).queries(cfg, x, n, [seed, stream])


class QueryPool:
    """The queries of one run, made on demand: row ``i`` is row
    ``i % BLOCK`` of block ``i // BLOCK``, drawn from
    ``(seed, stream, block)``. The same seed gives the same rows, and a
    loop can use as many as the service answers."""

    BLOCK = 4096

    def __init__(self, cfg: dict, x: np.ndarray, seed: int,
                 stream: int, bench: Path = spec.BENCH):
        self.cfg, self.x, self.seed, self.stream = cfg, x, seed, stream
        self._queries = spec.dataset(cfg, bench).queries
        self._blocks: Dict[int, np.ndarray] = {}

    def _block(self, b: int) -> np.ndarray:
        if b not in self._blocks:
            self._blocks[b] = self._queries(
                self.cfg, self.x, self.BLOCK,
                [self.seed, self.stream, b])
        return self._blocks[b]

    def fill(self, n: int) -> None:
        """Make rows ``[0, n)`` now (in set-up, not in the window)."""
        for b in range(-(-n // self.BLOCK)):
            self._block(b)

    def __getitem__(self, i):
        if np.ndim(i) == 0:
            return self._block(int(i) // self.BLOCK)[int(i) % self.BLOCK]
        i = np.asarray(i, np.int64)
        out = np.empty((len(i), self.x.shape[1]), np.float32)
        for b in np.unique(i // self.BLOCK):
            m = i // self.BLOCK == b
            out[m] = self._block(int(b))[i[m] % self.BLOCK]
        return out


def service(cfg: dict, x: np.ndarray, seed: int,
            bench: Path = spec.BENCH):
    """Build the index over ``x`` and return the service over it."""
    return spec.index(cfg, bench).service(cfg, x, seed,
                                          program_config(cfg))


def scheduler(svc, cfg: dict, max_queue: int = 1 << 22):
    """The service's scheduler; its constructor compiles and runs every
    width of the ladder once."""
    return svc.scheduler(n_slots=int(cfg["n_slots"]),
                         quantum=int(cfg["quantum"]), max_queue=max_queue)
