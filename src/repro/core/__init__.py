"""repro.core — the paper's contribution: DCQCN-Rev congestion control.

Public surface:
  * params:      CCConfig / CCScheme / CCSpec / PAPER_CONFIG
  * cc:          the composable stage registries (MARKING /
                 NOTIFICATION / REACTION) — pluggable detection,
                 notification and reaction components selected by
                 traced codes, all combinations riding one jit
  * topology:    make_paper_clos / make_clos3 / Topology
  * routing:     build_flow_routes / clos_route
  * fluid:       Scenario / FluidState / fluid_step / make_step_fn
  * simulator:   run / run_all_schemes / SimResult
  * experiments: ScenarioSpec / Sweep / SweepResult / config_grid —
                 the declarative one-jit sweep API (preferred entrypoint)
  * scenarios:   paper_incast / incast / ... (legacy wrappers over specs)
  * workloads:   collective-workload generator (all-to-all, ring /
                 recursive-doubling allreduce, incast storms, hotspots,
                 bursts) — combine with ``repro.net`` fabrics
  * obs:         host spans, counters and the step's device scopes
                 (``obs.stats()``, ``obs.sweep_op_scopes()``)
"""

from .params import (CCConfig, CCScheme, CCSpec, DCQCNParams, FNCCParams,
                     LinkParams, PAPER_CONFIG, ROUTING_MODES, RevParams,
                     SimParams, SwiftParams)
from . import cc
from .topology import ClosIndex, Topology, make_clos3, make_paper_clos
from .routing import (build_flow_routes, clos_route, link_incidence,
                      route_hops)
from .fluid import (FluidState, Scenario, ScenarioDev, StepParams,
                    delay_depth, dense_reduce_rows, fluid_step,
                    init_state, make_step_fn, scenario_device,
                    step_params)
from .simulator import SimResult, run, run_all_schemes
from .exec_cache import CacheStats, ExecutableCache
from .experiments import (SWEEP_EXEC_CACHE, ScenarioSpec, Sweep,
                          SweepResult, config_grid, pad_scenario,
                          stack_scenarios, trim_final)
from .scenarios import (PAPER_FLOW_NAMES, collective_flows, incast,
                        paper_incast, paper_incast_volume,
                        random_permutation)
from .workloads import Workload
from . import obs, workloads

__all__ = [
    "CCConfig", "CCScheme", "CCSpec", "DCQCNParams", "FNCCParams",
    "LinkParams", "PAPER_CONFIG", "ROUTING_MODES", "RevParams",
    "SimParams", "SwiftParams", "cc",
    "ClosIndex", "Topology", "make_clos3",
    "make_paper_clos", "build_flow_routes", "clos_route",
    "link_incidence", "route_hops",
    "FluidState", "Scenario", "ScenarioDev", "StepParams", "delay_depth",
    "dense_reduce_rows", "fluid_step", "init_state", "make_step_fn",
    "scenario_device", "step_params", "SimResult", "run",
    "run_all_schemes", "CacheStats", "ExecutableCache",
    "SWEEP_EXEC_CACHE",
    "ScenarioSpec", "Sweep", "SweepResult", "config_grid",
    "pad_scenario", "stack_scenarios", "trim_final", "PAPER_FLOW_NAMES",
    "collective_flows", "incast", "paper_incast", "paper_incast_volume",
    "random_permutation", "Workload", "workloads", "obs",
]
