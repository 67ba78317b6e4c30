"""``senders`` hosts each send at line rate to one of ``receivers`` hosts
(round robin), all drawn from a fixed ``layout_seed``, from ``t_start`` to
``t_stop``: a storage incast storm."""

import math

import numpy as np


def rows(part: dict, n_hosts: int, mix: dict) -> list:
    s, r = int(part["senders"]), int(part["receivers"])
    picks = np.random.RandomState(int(part["layout_seed"])).permutation(n_hosts)[:s + r]
    recv, send = picks[:r], picks[r:]
    nic = float(mix.get("nic_buffer", 4e6))
    return [(int(send[i]), int(recv[i % r]), part["t_start"], part["t_stop"],
             math.inf, 1.0, nic) for i in range(s)]
