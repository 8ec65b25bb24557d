"""Each fault a cell can have, planted under the timed path, makes
``correct`` come out false; with none planted it comes out true.

The runs skip the look for a chip and drive the rest of a run on the CPU
at test size (``tests/data``): the shuffle engine, the loop, the timed
step, the batch check and the reference. The four-device fault runs in a
child process with four virtual CPU devices."""

import json
import os
import pathlib
import subprocess
import sys

import pytest

from conftest import CPU_PEAKS


SECONDS = 1.0


def run_tiny(cell, seed=5):
    import run

    return run.run_cell(cell, seed, SECONDS, False, peaks=CPU_PEAKS)


def failed_checks(result):
    return sorted(k for k, c in result["checks"].items()
                  if c["value"] > c["limit"])


def test_sound_run_is_correct(deepseek_tiny):
    result = run_tiny(deepseek_tiny)
    assert result["correct"], result["checks"]
    assert result["metrics"]["tokens_per_s"]["value"] > 0


def test_state_left_unchanged(deepseek_tiny, monkeypatch):
    import repro.training as training

    make = training.make_train_step

    def unchanged(*a, **k):
        step = make(*a, **k)

        def fn(params, opt, batch):
            _, _, metrics = step(params, opt, batch)
            return params, opt, metrics
        return fn
    monkeypatch.setattr(training, "make_train_step", unchanged)
    result = run_tiny(deepseek_tiny)
    assert not result["correct"]
    assert {"grad_diff", "change_diff"} <= set(failed_checks(result))


def test_half_batch_left_out(deepseek_tiny, monkeypatch):
    import repro.training as training

    make = training.make_train_step

    def half(*a, **k):
        step = make(*a, **k)

        def fn(params, opt, batch):
            return step(params, opt, {key: v[: v.shape[0] // 2]
                                      for key, v in batch.items()})
        return fn
    monkeypatch.setattr(training, "make_train_step", half)
    result = run_tiny(deepseek_tiny)
    assert not result["correct"]


def test_token_altered_where_produced(deepseek_tiny, monkeypatch):
    from repro.train_input import tokens

    draw = tokens.step_tokens

    def altered(cfg, step):
        toks = draw(cfg, step).copy()
        toks[0, 3] = (toks[0, 3] + 1) % cfg.vocab_size
        return toks
    monkeypatch.setattr(tokens, "step_tokens", altered)
    result = run_tiny(deepseek_tiny)
    assert not result["correct"]
    assert "batches_differing" in failed_checks(result)


CHILD = """
import json, sys
sys.path[:0] = {paths!r}
from conftest import CPU_PEAKS, tiny_cell
import repro.shuffle.dispatch as dispatch
import run
if {fault!r}:
    dispatch._a2a = lambda x, axes: x
cell = tiny_cell("deepseek-v2-tiny", "tiny-ep4-direct", chips=4)
r = run.run_cell(cell, 7, {seconds!r}, False, peaks=CPU_PEAKS)
print(json.dumps({{"correct": r["correct"], "checks": r["checks"]}}))
"""


@pytest.mark.parametrize("fault", [False, True],
                         ids=["sound", "exchange_left_out"])
def test_expert_exchange(fault):
    here = pathlib.Path(__file__).resolve().parent
    paths = [str(here), str(here.parent), str(here.parents[1] / "src")]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, "-c", CHILD.format(paths=paths, fault=fault,
                                            seconds=SECONDS)],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] is (not fault), result["checks"]
