"""Flows listed column by column: ``src``, ``dst``, ``t_start``,
``t_stop``, ``rate_frac`` (share of line rate)."""

import math


def rows(part: dict, n_hosts: int, mix: dict) -> list:
    nic = float(mix.get("nic_buffer", 4e6))
    return [(a, b, t0, t1, math.inf, fr, nic) for a, b, t0, t1, fr in
            zip(part["src"], part["dst"], part["t_start"], part["t_stop"],
                part["rate_frac"])]
