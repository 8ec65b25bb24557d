"""The control, the reference in float8 put in the program's place, fails
the comparison that the program passes: at test size on the CPU, for each
model type, on three seeds. The same readings at the cells' own sizes on
the chip set the cells' limits (``calibrate.py``, PERF.md)."""

import pytest

from conftest import CPU_PEAKS, tiny_cell


@pytest.mark.parametrize("config", ["deepseek-v2-tiny", "qwen2-moe-tiny"])
def test_control_fails_where_program_passes(config):
    import jax

    import run

    cell = tiny_cell(config, "tiny-shuffle-fed")
    lim = cell.limits
    for seed in (1, 2, 3):
        prog, ref = run.run_once(cell, seed, 0.5, False, peaks=CPU_PEAKS)
        assert prog["correct"], prog["checks"]
        ctl = run.reference_steps(cell, seed, 1, jax.devices()[0], fp8=True)
        gaps, _ = run.gaps(ctl, ref, lim["still_leaf_share"])
        assert any(gaps[k] > lim[k] for k in run.GAPS), gaps
