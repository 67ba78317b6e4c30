"""Group shift (Kim et al., ISCA 2008, section 4: the worst case for
minimal routing): host j of group G sends to host j of group G + 1 (mod
the group count), every host of the fabric at ``rate_frac`` of line rate
from ``t_start`` to ``t_stop``, window-limited.  Minimal routing puts a
whole group's flows on the one global channel to the next group."""

import math


def rows(part: dict, n_hosts: int, mix: dict) -> list:
    size = int(part["group_size"])
    groups = n_hosts // size
    nic = float(mix.get("nic_buffer", 4e6))
    t0 = float(part.get("t_start", 0.0))
    t1 = float(part.get("t_stop", math.inf))
    rate = float(part.get("rate_frac", 1.0))
    return [(g * size + j, (g + 1) % groups * size + j, t0, t1, math.inf, rate, nic)
            for g in range(groups) for j in range(size)]
