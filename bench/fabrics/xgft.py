"""XGFT(h; m; w) fabrics (Ohring et al.; k-ary n-trees are XGFT(n; k..k;
1, k..k)) with D-mod-K up-routing.

``fabric``: ``{"kind": "xgft", "m": [...], "w": [...]}``, down-arities and
parent multiplicities from level 1 up.
"""

import numpy as np


def program(fabric: dict, roll: int):
    """The program's fabric spec."""
    from repro.net import FabricSpec
    return FabricSpec.xgft(fabric["m"], fabric["w"], roll=roll)


def digits(n: int, m) -> list:
    out = []
    for ml in m:
        out.append(n % ml)
        n //= ml
    return out


def path(fabric: dict, roll: int, s: int, d: int) -> list:
    """Node sequence host s -> host d: up to the lowest common level by
    D-mod-K (the up port at level j is digit ``(d // prod(w[:k])) % w[j]``
    of the destination, ``k = (j + roll) % h``), then down along d's
    digits.  A level-l switch is keyed by (l, y_1..y_l, x_{l+1}..x_h)."""
    m, w = fabric["m"], fabric["w"]
    h = len(m)
    xs, xd = digits(s, m), digits(d, m)
    top = max(j for j in range(h) if xs[j] != xd[j]) + 1
    y = []
    nodes = [("host", s)]
    for j in range(1, top + 1):
        k = (j - 1 + roll) % h
        y.append((d // int(np.prod(w[:k], dtype=np.int64))) % w[j - 1])
        nodes.append((j, tuple(y), tuple(xs[j:])))
    for j in range(top - 1, 0, -1):
        nodes.append((j, tuple(y[:j]), tuple(xd[j:])))
    nodes.append(("host", d))
    return nodes


def links(fabric: dict) -> tuple:
    """(directed links, longest path in hops)."""
    m, w = fabric["m"], fabric["w"]
    h = len(m)
    up = sum(int(np.prod(m[l - 1:]) * np.prod(w[:l - 1])) * w[l - 1]
             for l in range(1, h + 1))
    return 2 * up, 2 * h


def hosts(fabric: dict) -> int:
    return int(np.prod(fabric["m"]))
