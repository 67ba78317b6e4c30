"""Find a part of the benchmark by name: ``bench/<kind>/<name>.py``.

Configurations, traffic mixes and limits are data files; whatever needs
code is a small module of its own, found by the name a data file gives:

* ``drivers/<name>.py``: ``run(cell, config, mix, run) -> Outcome``, how a
  cell's window is driven (a mix's ``driver``);
* ``fabrics/<kind>.py``: ``program(fabric, roll)``, the program's fabric,
  and ``path(fabric, roll, s, d)``, the reference's node path (a
  configuration's ``fabric.kind``);
* ``patterns/<name>.py``: ``rows(part, n_hosts, mix)``, the flows of one
  part of a scene (a part's ``pattern``);
* ``relabel/<name>.py``: ``apply(hosts, fabric, rng)``, how a scene's
  endpoints move with the seed (a scene's ``seed``);
* ``metrics/<name>.py``: ``read(ctx)``, one per-layer metric.

A later cell adds files; it edits none of these.
"""

from __future__ import annotations

import functools
import importlib.util
import os

from bench.env import BENCH


@functools.lru_cache(maxsize=None)
def module(kind: str, name: str):
    path = os.path.join(BENCH, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise KeyError(f"no {kind} named {name!r} (looked for {path})")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}".replace(".", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
