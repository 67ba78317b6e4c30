"""One random permutation of the host slots of each dragonfly group,
applied to every host id, as a source and as a sink alike.

Each group keeps its hosts, and each router its four (or ``p``) host
links, so a group shift stays a group shift: every group still sends
all its hosts' flows to the next group, and every link of the minimal
routes carries as many flows as before.  The seed changes which host
pairs with which, and so the flows' detours, and no compiled shape."""

import numpy as np


def apply(hosts: np.ndarray, fabric: dict, rng) -> np.ndarray:
    size = int(fabric["a"]) * int(fabric["p"])
    groups = int(fabric["a"]) * int(fabric["h"]) + 1
    perms = np.stack([rng.permutation(size) for _ in range(groups)])
    return hosts // size * size + perms[hosts // size, hosts % size]
