"""A whole run at ``.reduced()`` size on the CPU: the same set-up, loop,
engine, packed kernels (interpret mode), reference and check as on the
chip, with the harness's look for a chip skipped.  A sound run is
correct; a run whose timed path is broken underneath is not, once for
each fault a serving cell can have; and the float8 control fails the
limit by a wide margin."""

import time

import jax
import pytest

import chipbench_tiny
from chipbench import cell as cell_mod
from chipbench import reference

# the tiny model's own limit: its sound runs read under 0.02 and its
# control over 0.33 (CPU, interpret mode), so 0.1 sits between them
LIMIT = 0.1
SEED = 2**31 + 5


def _run(hook=None, control=False):
    return cell_mod.run(chipbench_tiny.cell(LIMIT), SEED, 3.0, False,
                        t_start=time.monotonic(), devices=jax.devices(),
                        peak=chipbench_tiny.PEAK,
                        opts=cell_mod.Options(backend="pallas_interpret",
                                              engine_hook=hook,
                                              control=control))


def _alter_tokens(engine):
    """A token altered where it is produced: the engine's sampler."""
    inner = engine.sampler

    class Altered:
        def sample(self, logits, uid, pos):
            tok = inner.sample(logits, uid, pos)
            return (tok + 1) % 512 if uid >= 0 else tok

    engine.sampler = Altered()


def _state_unchanged(engine):
    """A decode step that returns its state unchanged."""
    inner = engine._decode

    def step(params, state, tokens):
        logits, _ = inner(params, state, tokens)
        return logits, state

    engine._decode = step


@pytest.fixture(scope="module")
def sound():
    return _run(control=True)


def test_sound_run_is_correct_and_reports_its_metrics(sound):
    assert cell_mod.correct(sound)
    assert sound["attempted"] > 5 and sound["failed"] == 0
    gap = sound["check"]["served_logit_gap"]
    assert gap["tokens"] >= 40 and gap["requests"] >= 2
    assert sound["gen_tokens_window"] > 0
    assert sound["prompt_tokens_window"] > 0
    assert sound["dispatched"]["decode"] > 0
    assert sound["dispatched"]["prefill"] > 0
    assert 0 < sound["setup_s"]


def test_control_fails_the_limit(sound):
    gap = sound["check"]["served_logit_gap"]
    assert gap["control"] > LIMIT
    assert gap["control"] >= 3 * gap["value"]


@pytest.mark.parametrize("fault", [_alter_tokens, _state_unchanged],
                         ids=["token_altered", "state_unchanged"])
def test_broken_timed_path_is_not_correct(fault):
    rec = _run(hook=fault)
    assert not cell_mod.correct(rec)
    assert rec["check"]["served_logit_gap"]["value"] > LIMIT


def test_sample_holds_the_longest_finished_request(sound):
    # the sample is drawn again from the same seed's finished requests:
    # the check covered the longest one
    assert sound["check"]["served_logit_gap"]["requests"] == \
        chipbench_tiny.MIX["check"]["sample_requests"]


def test_traced_run_reduces_its_trace_and_prints_a_result_line():
    import json
    import sys

    from chipbench import spec

    sys.path.insert(0, chipbench_tiny.BENCH_DIR)
    import run as run_mod

    rec = cell_mod.run(chipbench_tiny.cell(LIMIT), SEED, 2.0, True,
                       t_start=time.monotonic(), devices=jax.devices(),
                       peak=chipbench_tiny.PEAK,
                       opts=cell_mod.Options(backend="pallas_interpret"))
    trace = rec["trace"]
    # the CPU has no TPU plane: the window is the bench span, nothing busy
    assert trace["window_s"] > 1.5 and trace["busy_s"] == 0
    assert trace["kernel_events"] == 0
    cell = chipbench_tiny.cell(LIMIT)
    cell.per_layer = spec.resolve("stablelm_3b.chat").per_layer
    line = run_mod.result_line(cell, rec, True, {"platform": "cpu",
                                                 "kind": "cpu", "count": 1})
    assert list(line)[-1] == "check" and line["correct"]
    assert line["device"]["window_s"] == trace["window_s"]
    assert "breakdown" in line
    # no kernel ran, so the roofline reader reads nothing
    assert "packed_roofline.decode" not in line["metrics"]
    assert line["metrics"]["device_idle_share.decode"]["value"] == 100.0
    json.dumps(line)


def test_round_fp8_keeps_three_mantissa_bits():
    import jax.numpy as jnp

    x = jnp.asarray([448.0, 1.0, 1.0625, 1.125, 0.3])
    y = reference.round_fp8(x)
    assert float(y[0]) == 448.0 and float(y[1]) == 1.0
    assert float(y[2]) in (1.0, 1.125) and float(y[3]) == 1.125
    assert abs(float(y[4]) - 0.3) <= 0.3 / 16
