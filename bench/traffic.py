"""The benchmark's one traffic generator.

A traffic mix is a data file ``bench/traffic/<name>.json``.  This module
reads it and makes, from the mix's parameters, the configuration and the
run's ``--seed`` alone:

* the flows of each scene (``scene_flows``): per-flow source, sink, start,
  stop, volume, offered rate and NIC buffer, each part of a scene made by
  the pattern it names (``bench/patterns/<pattern>.py``), its endpoints
  moved with the seed by the relabelling the scene names
  (``bench/relabel/<seed>.py``);
* the grid of points of a sweep (``grid_points``): scenes x schemes x the
  grid's parameter values, fixed lists or drawn from the seed.

Every seed gives the same amount of work in a different order or
placement, so no seed changes a shape the program compiles for.
"""

from __future__ import annotations

import dataclasses
import itertools
import json
import os

import numpy as np

from bench.env import BENCH
from bench.lookup import module


@dataclasses.dataclass
class Flows:
    src: np.ndarray
    dst: np.ndarray
    t_start: np.ndarray
    t_stop: np.ndarray
    volume: np.ndarray
    rate: np.ndarray          # B/s
    nic_buffer: np.ndarray    # B
    roll: int = 0

    def __len__(self) -> int:
        return len(self.src)


def load(name: str, root: str = BENCH) -> dict:
    with open(os.path.join(root, "traffic", f"{name}.json")) as f:
        return json.load(f)


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """Independent generator per purpose, from any whole-number seed."""
    words = [seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF, int(seed < 0)]
    return np.random.default_rng(np.random.SeedSequence(
        words + [ord(c) for c in stream]))


def scene_flows(scene: dict, config: dict, mix: dict, seed: int) -> Flows:
    fabric = config["fabric"]
    n_hosts = module("fabrics", fabric["kind"]).hosts(fabric)
    rows = [r for part in scene["parts"]
            for r in module("patterns", part["pattern"]).rows(part, n_hosts, mix)]
    col = lambda i, dt=np.float64: np.asarray([r[i] for r in rows], dt)  # noqa: E731
    src, dst = col(0, np.int64), col(1, np.int64)
    if scene.get("seed"):
        rng = rng_for(seed, "relabel:" + scene["name"])
        moved = module("relabel", scene["seed"]).apply(np.concatenate([src, dst]),
                                                       fabric, rng)
        src, dst = moved[:len(src)], moved[len(src):]
    line = float(config["link"]["line_rate"])
    return Flows(src=src, dst=dst, t_start=col(2), t_stop=col(3), volume=col(4),
                 rate=col(5) * line, nic_buffer=col(6),
                 roll=int(scene.get("roll", fabric.get("roll", 0))))


def param_values(path: str, spec, config: dict, seed: int) -> list:
    """One grid axis: a fixed list, or ``n`` values drawn uniformly in
    [lo, hi] x the configuration's ``of`` parameter from the seed (one
    stream per parameter, named by its last part), sorted and rounded."""
    if isinstance(spec, list):
        return [float(v) for v in spec]
    group, key = spec["of"].split(".")
    of = float(config[group][key])
    vals = rng_for(seed, path.split(".")[-1]).uniform(spec["lo"] * of, spec["hi"] * of,
                                                      int(spec["n"]))
    return [float(v) for v in np.sort(np.round(vals))]


def grid_points(mix: dict, config: dict, seed: int) -> list:
    """[(name, scheme name, {dotted param: value}, Flows)] of a sweep mix."""
    grid = mix["grid"]
    axes = {p: param_values(p, spec, config, seed)
            for p, spec in grid.get("params", {}).items()}
    out = []
    for scene in mix["scenes"]:
        flows = scene_flows(scene, config, mix, seed)
        for scheme in grid["schemes"]:
            for vals in itertools.product(*axes.values()):
                over = dict(zip(axes, vals))
                name = "/".join([scheme, scene["name"]] +
                                [f"{p.split('.')[-1]}={v:g}" for p, v in over.items()])
                out.append((name, scheme, over, flows))
    return out
