"""All-to-all among ``participants`` hosts spread evenly over the fabric
(host i * N / n), ``volume`` bytes a pair, the n - 1 shifts opened in
``phases`` groups ``phase_gap`` seconds apart (MoE token exchange)."""

import math


def rows(part: dict, n_hosts: int, mix: dict) -> list:
    n, vol = int(part["participants"]), float(part["volume"])
    nodes = [i * n_hosts // n for i in range(n)]
    phases, gap = int(part["phases"]), float(part["phase_gap"])
    t0 = float(part.get("t_start", 0.0))
    return [(nodes[i], nodes[(i + k) % n], t0 + ((k - 1) % phases) * gap,
             math.inf, vol, 1.0, 2 * vol)
            for k in range(1, n) for i in range(n)]
