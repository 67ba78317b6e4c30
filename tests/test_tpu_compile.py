"""Compile-only checks of the fluid step for a TPU v5e chip.

The TPU compiler is installed even where no chip is attached: it
compiles for a *described* ``v5e:2x2`` topology.  These tests
ahead-of-time compile the step on each link-reduction engine at the
largest width of the ``--perf`` curve (``dfly272_f4096``: 4096 flows on
a 272-host dragonfly) for one chip of it, so a program the chip's
compiler refuses fails here, before any chip time is spent.  Nothing
runs; the compiled program is only inspected.

The topology is described inside a fixture (never at import): one
process at a time may load the TPU library, and only the test worker
given this file should.
"""

import jax
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import CCSpec, LinkParams, PAPER_CONFIG, ScenarioSpec
from repro.core.fluid import dense_reduce_rows, init_state, make_step_fn
from repro.core.simulator import decimating_scan
from repro.net import FabricSpec

N_SAMPLES = 4
TRACE_EVERY = 10


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compile
    cache off: entries written for a described chip cannot be read
    back without one.  Skips only where the TPU compiler (libtpu) is
    not installed; any other failure to describe the chip fails."""
    pytest.importorskip("libtpu")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


#: the K = 4, V = 2 multipath step (the dragonfly cell's shape)
MULTIPATH = CCSpec(routing="ugal", link=LinkParams(n_vcs=2))


def _dfly4096(cfg, **kw):
    spec = ScenarioSpec.permutation(4096, seed=0,
                                    fabric=FabricSpec.dragonfly(4, 4, 4),
                                    **kw)
    scn = spec.build(cfg)
    return scn, init_state(scn, cfg)


def _compile(scan_fn, st0, sharding) -> str:
    """AOT-compile ``scan_fn(state)`` for ``sharding``'s device; the
    compiled program's text."""
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        st0)
    return jax.jit(scan_fn).lower(shapes).compile().as_text()


@pytest.mark.parametrize("engine", ["default", "segment_sum", "pinned",
                                    "multipath_k4_v2"])
def test_step_tier_compiles(one_chip, engine):
    """The decimating scan of the step compiles for the chip on each
    link-reduction engine (the jagged layout by default, the segment
    sum, the rectangle pinned to the longest list) and on the K = 4,
    V = 2 multipath step, as one XLA program: no custom kernel call."""
    cfg = MULTIPATH if engine == "multipath_k4_v2" else PAPER_CONFIG
    kw = dict(n_paths=4, route_seed=0) if engine == "multipath_k4_v2" \
        else {}
    scn, st0 = _dfly4096(cfg, **kw)
    rows = {"segment_sum": 0,
            "pinned": dense_reduce_rows(scn)}.get(engine)
    assert rows != 0 or engine == "segment_sum"
    step = make_step_fn(scn, cfg, dense_rows=rows)
    text = _compile(lambda st: decimating_scan(
        step, st, N_SAMPLES, TRACE_EVERY, cfg.sim.dt,
        int(getattr(cfg.link, "n_vcs", 1))), st0, one_chip)
    assert "tpu_custom_call" not in text, engine
