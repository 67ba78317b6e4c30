"""The paper's 3-stage CLOS of radix-2k switches, k^3 hosts: XGFT(3; k,k,k;
1,k,k), built by the program's own ``clos3`` constructor.

``fabric``: ``{"kind": "clos3", "arity": k, "m": [k, k, k], "w": [1, k, k]}``;
the reference routes it as the XGFT it is.
"""

from bench.fabrics import xgft


def program(fabric: dict, roll: int):
    from repro.net import FabricSpec
    return FabricSpec.clos3(int(fabric["arity"]), roll=roll)


path, links, hosts = xgft.path, xgft.links, xgft.hosts
