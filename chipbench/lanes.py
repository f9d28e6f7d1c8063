"""A configuration's lanes for one seed.

A configuration file names its fabric, its fabric modes (each with the
data placement the compiler uses for it), its mesh sizes and its lanes;
each lane names a workload kind (``kinds/<kind>.py``) and that kind's
parameters.  The grid is every (mode, size, lane) point, mode-major,
then size-major, as the paper's sweeps stack them.

A lane's data has a shape and values.  The shape (a sparsity pattern, a
graph, an edge's weight: whatever sets the work the fabric does) comes
from the configuration's ``shape_seed``, the same for every run; the
values come from the run's seed.  So every seed gives a request the same
simulated work, and answers that only the seed's values decide.  The
system's compiler turns the data into lanes on every request.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import os

import numpy as np


def load_file(path: str, what: str):
    """Import the module at ``path`` (a kind, a metric reader)."""
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {what} at {path}")
    name = "chipbench_" + os.path.relpath(path).replace(os.sep, "_") \
        .replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_kind(kinds_dir: str, kind: str):
    """The kind module ``kinds_dir/<kind>.py``."""
    return load_file(os.path.join(kinds_dir, f"{kind}.py"),
                     f"workload kind {kind!r}")


@dataclasses.dataclass(frozen=True)
class Point:
    """One lane of the grid: which data, on which mode and mesh."""
    label: str
    lane: int          # index into the configuration's ``lanes``
    mode: str
    placement: str
    size: tuple[int, int]

    @property
    def n_pes(self) -> int:
        return self.size[0] * self.size[1]


class Grid:
    """Every (mode, size, lane) point of a configuration, with the seed's
    data for each lane (shared by all modes and sizes, as in the paper)."""

    @staticmethod
    def _rng(seed: int, lane: int) -> np.random.Generator:
        return np.random.default_rng(np.random.SeedSequence([seed, lane]))

    def __init__(self, config: dict, seed: int, kinds_dir: str):
        if seed < 0:
            raise ValueError(f"seed must be >= 0, got {seed}")
        self.config = config
        self.specs = list(config["lanes"])
        self.kinds = [load_kind(kinds_dir, s["kind"]) for s in self.specs]
        shape_seed = int(config["shape_seed"])
        self.data = [
            kind.generate(spec, self._rng(shape_seed, i), self._rng(seed, i))
            for i, (kind, spec) in enumerate(zip(self.kinds, self.specs))]
        self.points = [
            Point(f"{spec['name']}/{mode}@{w}x{h}", i, mode, placement,
                  (int(w), int(h)))
            for mode, placement in config["modes"].items()
            for (w, h) in config["sizes"]
            for i, spec in enumerate(self.specs)]
        self._refs: dict = {}

    def _cfg(self, size, mem_words: int):
        from repro.core.machine import MachineConfig
        return MachineConfig(width=size[0], height=size[1],
                             mem_words=int(mem_words),
                             max_cycles=int(self.config["fabric"]
                                            ["max_cycles"]))

    @property
    def run_cfg(self):
        """The sweep's machine configuration: the first mesh size, memory
        widened to the largest lane's."""
        return self._cfg(self.config["sizes"][0],
                         max(s["mem_words"] for s in self.specs))

    def build(self, p: Point, cache: dict | None = None):
        """Compile one point; ``cache`` shares a compiled lane between
        modes that use the same placement, as a sweep's caller does."""
        key = (p.lane, p.placement, p.size)
        if cache is not None and key in cache:
            return cache[key]
        spec = self.specs[p.lane]
        wl = self.kinds[p.lane].build(self.data[p.lane],
                                      self._cfg(p.size, spec["mem_words"]),
                                      p.placement)
        if cache is not None:
            cache[key] = wl
        return wl

    def build_all(self) -> list:
        cache: dict = {}
        return [self.build(p, cache) for p in self.points]

    def reference(self, p: Point, dtype=np.int64) -> np.ndarray:
        """The plain answer of a point's lane, in ``dtype`` words, as int64."""
        key = (p.lane, np.dtype(dtype).name)
        if key not in self._refs:
            ref = self.kinds[p.lane].reference(self.data[p.lane], dtype)
            self._refs[key] = np.asarray(ref).astype(np.int64)
        return self._refs[key]
