"""One random permutation per digit level applied to every host id.

On an XGFT whose parent counts equal the child counts above the leaf (a
fat tree) this is an automorphism that commutes with D-mod-K routing:
every link's set of flows maps to another link's, so the work and its
shapes are the same for every seed."""

import numpy as np


def apply(hosts: np.ndarray, fabric: dict, rng) -> np.ndarray:
    m = fabric["m"]
    perms = [rng.permutation(ml) for ml in m]
    out = np.zeros_like(hosts)
    place = 1
    rest = hosts.copy()
    for ml, perm in zip(m, perms):
        out += perm[rest % ml] * place
        rest //= ml
        place *= ml
    return out
