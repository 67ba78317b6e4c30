"""The benchmark's copy of the bytes model equals the program's today."""

import pytest

from bench.bytes_model import fluid_step_bytes


@pytest.mark.parametrize("shape", [(4288, 1, 6, 6000, 1), (5, 1, 6, 384, 1),
                                   (4096, 4, 5, 2000, 2), (1, 1, 2, 2, 1)])
def test_copy_matches_program(shape):
    from repro.fleet.plan import fluid_step_bytes as program
    assert fluid_step_bytes(*shape) == program(*shape)


def test_ft1000_step_bytes():
    # 2.01 MB per run-step at the ft1000 cell's shapes
    assert fluid_step_bytes(4288, 1, 6, 6000) == 2010144.0
