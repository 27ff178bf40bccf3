"""Each cell's control, the plain reference put in the program's place one
step down (bfloat16 for the float32 tuner; lost records and lost
acknowledged updates for the engine), comes out not correct under the
cell's own limits, while the program's readings on the same requests pass.
The cells are those of BENCHMARK.json whose kind has ``cpu_control``."""

import pytest

from chip_small import SEED, control_cells, small

import control


@pytest.mark.parametrize("cell", control_cells())
def test_control_fails_program_passes(cell):
    spec = small(cell)
    got = control.readings(spec, SEED, 1.0, need_chip=False)
    limits = spec["traffic"].get("limits", {})
    lim = {n: limits.get(n, 0.0) for n in got}
    assert all(sound <= lim[n] for n, (sound, _) in got.items()), got
    assert any(c > lim[n] for n, (_, c) in got.items()), got
