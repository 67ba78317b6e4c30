"""repro.dist — distribution substrate: sharding rules, the ERP-paced
collective scheduler, and pipeline parallelism.

Public surface:
  * sharding: shard / logical_sharding / pspec / DEFAULT_RULES /
    sweep_mesh (run-axis mesh for sharded Sweeps)
  * pacer:    chunk_bytes_of / erp_chunk_schedule
  * pipeline: pipeline_apply
"""

from .sharding import (DEFAULT_RULES, logical_sharding, pspec, shard,
                       sweep_mesh)

__all__ = ["DEFAULT_RULES", "logical_sharding", "pspec", "shard",
           "sweep_mesh"]
