"""Fixed (source, sink) pairs, each at ``rate_frac`` of line rate from
``t_start`` to ``t_stop``."""

import math


def rows(part: dict, n_hosts: int, mix: dict) -> list:
    nic = float(mix.get("nic_buffer", 4e6))
    return [(a, b, part["t_start"], part["t_stop"], math.inf,
             part.get("rate_frac", 1.0), nic) for a, b in part["pairs"]]
