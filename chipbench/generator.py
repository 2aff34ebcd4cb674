"""The one traffic generator: turns a configuration file and a traffic
mix file into the sequence of ``Scenario.run()`` calls a run makes.

A configuration's ``scenario`` and a mix's ``scenario`` are the
program's own scenario specification in its JSON form (the schema of
``Scenario.to_dict``), merged key by key with the mix on top; whatever
field they set reaches the program as it stands, and whatever they
leave out keeps the program's default. The benchmark keeps that
merged specification beside each call, and the checks read it, never
the program's objects.

Everything that varies from call to call is drawn from the run's seed:
each call gets a fresh scenario seed, so every call does the same amount
of work over a new trace. A mix that names ``candidates`` also walks the
configuration's grid of that name: every combination of one choice per
class, written into the grid's ``field`` at the class's positions,
visited in an order drawn from the seed and then cycled, so every seed
asks the same set of questions in another order.
"""

from __future__ import annotations

import copy
import itertools
import json
from dataclasses import dataclass
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent


def load(kind: str, name: str) -> dict:
    """``configs/<name>.json`` or ``traffic/<name>.json``."""
    path = HERE / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file {path}")
    return json.loads(path.read_text())


def merged(base: dict, top: dict) -> dict:
    """``base`` with ``top`` laid over it; dicts merge key by key."""
    out = copy.deepcopy(base)
    for key, value in top.items():
        if isinstance(value, dict) and isinstance(out.get(key), dict):
            out[key] = merged(out[key], value)
        else:
            out[key] = copy.deepcopy(value)
    return out


def candidates(grid: dict, spec: dict) -> list[dict]:
    """Every specification of a grid: ``field`` is a path into the
    specification to a vector, and each class writes one of its
    ``choices`` at its ``positions``."""
    *path, leaf = grid["field"]
    out = []
    for pick in itertools.product(*(c["choices"] for c in grid["classes"])):
        sp = copy.deepcopy(spec)
        node = sp
        for key in path:
            node = node[key]
        vector = list(node[leaf])
        for cls, value in zip(grid["classes"], pick):
            for p in cls["positions"]:
                vector[p] = value
        node[leaf] = vector
        out.append(sp)
    return out


@dataclass
class Call:
    """One call: the specification as the benchmark wrote it, and the
    program's scenario built from it."""

    spec: dict
    scenario: object


class Traffic:
    """The calls of one run. ``base`` is the specification every call
    builds on; ``warmup_call`` is a call of the same shape for set-up,
    drawn apart from the window's; ``next_call`` gives the window's
    calls in order."""

    def __init__(self, config: dict, mix: dict, seed: int) -> None:
        self.rng = np.random.default_rng(int(seed) % 2**64)  # any whole number
        self.base = merged(
            {"name": config["name"], **config["scenario"]}, mix.get("scenario", {})
        )
        self.grid = None
        if "candidates" in mix:
            grid = candidates(config[mix["candidates"]], self.base)
            self.grid = [grid[i] for i in self.rng.permutation(len(grid))]
        self.n_calls = 0

    def _call(self, spec: dict) -> Call:
        from repro.scenario import Scenario

        spec = {**spec, "seed": int(self.rng.integers(0, 2**63 - 1))}
        return Call(spec, Scenario.from_dict(spec))

    def warmup_call(self) -> Call:
        """A call of the window's shape whose answer nobody reads."""
        return self._call(self.base)

    def next_call(self) -> Call:
        spec = self.base
        if self.grid is not None:
            spec = self.grid[self.n_calls % len(self.grid)]
        self.n_calls += 1
        return self._call(spec)
