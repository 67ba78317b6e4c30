"""Compile-only checks of the fluid step for a TPU v5e chip.

The TPU compiler is installed even where no chip is attached: it
compiles for a *described* ``v5e:2x2`` topology.  These tests
ahead-of-time compile the main path and each Pallas tier at the
largest width of the ``--perf`` curve (``dfly272_f4096``: 4096 flows on
a 272-host dragonfly) for one chip of it, so a kernel the chip's
compiler refuses fails here, before any chip time is spent.  Nothing
runs; the compiled program is only inspected.

The topology is described inside a fixture (never at import): one
process at a time may load the TPU library, and only the test worker
given this file should.
"""

import jax
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import PAPER_CONFIG, ScenarioSpec
from repro.core.fluid import init_state, make_step_fn
from repro.core.simulator import decimating_scan, make_block_fn
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


@pytest.fixture(scope="module")
def dfly4096():
    spec = ScenarioSpec.permutation(4096, seed=0,
                                    fabric=FabricSpec.dragonfly(4, 4, 4))
    scn = spec.build(PAPER_CONFIG)
    return scn, init_state(scn, PAPER_CONFIG)


def _compile(scan_fn, st0, sharding) -> str:
    """AOT-compile ``scan_fn(state)`` for ``sharding``'s device; the
    compiled program's text."""
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        st0)
    return jax.jit(scan_fn).lower(shapes).compile().as_text()


@pytest.mark.parametrize("tier,kw,n_kernels", [
    ("default", {}, 0),
    ("pallas_reduce", {"reduce": "pallas"}, 3),
    ("flow", {"use_kernels": True}, 2),
])
def test_step_tier_compiles(one_chip, dfly4096, tier, kw, n_kernels):
    """The decimating scan of each step tier compiles for the chip, with
    one ``tpu_custom_call`` per Pallas kernel of the tier (none on the
    jnp default)."""
    scn, st0 = dfly4096
    step = make_step_fn(scn, PAPER_CONFIG, **kw)
    text = _compile(lambda st: decimating_scan(
        step, st, N_SAMPLES, TRACE_EVERY, PAPER_CONFIG.sim.dt), st0, one_chip)
    assert text.count("tpu_custom_call") == n_kernels, tier


def test_megakernel_refused(one_chip, dfly4096):
    """The whole-step megakernel gathers inside its body, which the
    chip's kernel compiler refuses today.  A change that makes it
    compile must turn this into a compile check like the others."""
    scn, st0 = dfly4096
    block = make_block_fn(scn, PAPER_CONFIG, TRACE_EVERY)
    with pytest.raises(NotImplementedError, match="gather"):
        _compile(lambda st: decimating_scan(
            None, st, N_SAMPLES, TRACE_EVERY, PAPER_CONFIG.sim.dt,
            block_fn=block), st0, one_chip)
