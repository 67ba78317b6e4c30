"""Balanced dragonflies (Kim, Dally, Scott and Abts, ISCA 2008): ``g``
groups of ``a`` routers, ``p`` hosts and ``h`` global ports a router,
the routers of a group fully connected, one global channel between
every two groups (``g = a * h + 1``).

``fabric``: ``{"kind": "dragonfly", "a": a, "p": p, "h": h}``.  Host n
sits on router ``(n // p) % a`` of group ``n // (a * p)``.  Global ports
in the consecutive arrangement: port j (0 <= j < a * h) of group G
reaches group j if j < G, else j + 1, and sits on router ``j // h``.

Routes (the paper's section 4): ``path`` is the minimal route (local,
global, local; at most 5 links); ``detour`` the Valiant route through
a random intermediate group (minimal to it, then minimal on; at most 7
links), or through a random intermediate router for a pair inside one
group, drawn from the order-free stream of ``(seed, s, d, slot)``.
"""

import numpy as np

#: links of the longest Valiant route: up, local, global, local,
#: global, local, down
DETOUR_HOPS = 7


def program(fabric: dict, roll: int):
    """The program's fabric spec."""
    from repro.net import FabricSpec
    return FabricSpec.dragonfly(a=int(fabric["a"]), p=int(fabric["p"]),
                                h=int(fabric["h"]))


def _shape(fabric: dict) -> tuple:
    a, p, h = int(fabric["a"]), int(fabric["p"]), int(fabric["h"])
    return a, p, h, a * h + 1


def hosts(fabric: dict) -> int:
    a, p, _, g = _shape(fabric)
    return g * a * p


def links(fabric: dict) -> tuple:
    """(directed links, longest minimal path in hops)."""
    a, _, _, g = _shape(fabric)
    return 2 * hosts(fabric) + g * a * (a - 1) + g * (g - 1), 5


def owner(fabric: dict, grp: int, peer: int) -> int:
    """Router of group ``grp`` that holds the global channel to ``peer``."""
    port = peer if peer < grp else peer - 1
    return port // int(fabric["h"])


def _router(fabric: dict, n: int) -> tuple:
    a, p, _, _ = _shape(fabric)
    return ("router", n // (a * p), (n // p) % a)


def _minimal(fabric: dict, src: tuple, dst: tuple) -> list:
    """Routers from router ``src`` to router ``dst``, minimally."""
    (_, gs, rs), (_, gd, rd) = src, dst
    if gs == gd:
        return [src] if rs == rd else [src, dst]
    out = [src]
    gw = owner(fabric, gs, gd)
    if gw != rs:
        out.append(("router", gs, gw))
    rin = owner(fabric, gd, gs)
    out.append(("router", gd, rin))
    if rin != rd:
        out.append(dst)
    return out


def path(fabric: dict, roll: int, s: int, d: int) -> list:
    """Node sequence host s -> host d on the minimal route."""
    return [("host", s)] + _minimal(fabric, _router(fabric, s),
                                    _router(fabric, d)) + [("host", d)]


def draw(seed: int, s: int, d: int, slot: int, n: int) -> int:
    """The detour's draw among ``n`` choices: the first integer of a
    Mersenne twister seeded with (seed mod 2^31, s, d, slot)."""
    words = np.array([seed & 0x7FFFFFFF, s, d, slot], np.uint32)
    return int(np.random.RandomState(words).randint(n))


def detour(fabric: dict, s: int, d: int, seed: int, slot: int) -> list:
    """Node sequence host s -> host d on the Valiant route of candidate
    ``slot`` (the minimal route where no detour exists)."""
    a, _, _, g = _shape(fabric)
    rs, rd = _router(fabric, s), _router(fabric, d)
    if rs[1] == rd[1]:
        cand = [r for r in range(a) if r not in (rs[2], rd[2])]
        if not cand:
            return path(fabric, 0, s, d)
        mid = ("router", rs[1], cand[draw(seed, s, d, slot, len(cand))])
        return [("host", s), rs, mid, rd, ("host", d)]
    cand = [grp for grp in range(g) if grp not in (rs[1], rd[1])]
    if not cand:
        return path(fabric, 0, s, d)
    gi = cand[draw(seed, s, d, slot, len(cand))]
    # minimal to the intermediate group's arrival router, then on
    leg1 = _minimal(fabric, rs, ("router", gi, owner(fabric, gi, rs[1])))
    return [("host", s)] + leg1 + _minimal(fabric, leg1[-1], rd)[1:] + [("host", d)]
