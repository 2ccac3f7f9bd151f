"""``chip_smoke.py``'s phases on the CPU at reduced size, with the
interpret-mode kernels standing in for the compiled ones: the wiring the
chip run depends on (operand layouts, the dispatch audit, the logit check)
and its refusal to run without a TPU."""

import importlib.util
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_compile_cache_dir(monkeypatch, tmp_path, env_dir):
    """The compile cache goes where JAX_COMPILATION_CACHE_DIR says (and
    nothing overrides it), else to the checkout's fixed .jax_cache."""
    import jax

    from repro.launch.compile_cache import use_compile_cache

    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(ROOT, ".jax_cache")
    else:
        want = str(tmp_path / env_dir)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    try:
        assert use_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == (
            want if env_dir is None else before)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_refuses_to_run_without_a_tpu(smoke, capsys):
    assert smoke.main([]) == 1
    out = capsys.readouterr().out
    assert '"ok"' not in out and "platform=cpu" in out


def test_kernel_phase_interpret(smoke, capsys):
    smoke.kernel_phase(
        patterns=("8:128", "2:4"), shapes={"mlp": (256, 256)}, batches=(4,),
        backends={"xwT": "pallas_interpret", "xwT_q8": "pallas_interpret",
                  "xwT_block": "block_spmm_interpret",
                  "xwT_block_q8": "block_spmm_interpret"})
    lines = capsys.readouterr().out.splitlines()
    assert [ln.split()[1] for ln in lines] == [
        "xwT", "xwT_q8", "xwT_block", "xwT_block_q8"]


def test_experts_phase_interpret(smoke, capsys):
    """The grouped expert kernel's check at reduced widths: both routings
    of each row count, empty experts among them."""
    smoke.experts_phase(shapes={"gate": (128, 256), "down": (256, 128)},
                        rows=(8, 64), experts=8, top_k=2, pattern="2:32",
                        backend="pallas_interpret")
    line = capsys.readouterr().out.strip()
    assert line.split()[:3] == ["kernel", "experts", "backend=pallas_interpret"]
    assert "cases=8" in line and "empty_experts=0" not in line


def test_serve_phase_reduced_interpret(smoke, capsys):
    cfg, model, params = smoke.build_model(full=False)
    tokens = smoke.serve_phase(cfg, model, params,
                               backend="pallas_interpret",
                               allowed=("pallas_interpret",))
    assert len(tokens) == smoke.SERVE["requests"]
    out = capsys.readouterr().out
    audit = next(ln for ln in out.splitlines()
                 if ln.startswith("kernel_dispatch_total"))
    assert "pallas_interpret" in audit and "reference" not in audit
    with pytest.raises(AssertionError, match="non-Pallas"):
        smoke.serve_phase(cfg, model, params, backend="reference")
    assert not any(json.loads(ln).get("ok") for ln in out.splitlines()
                   if ln.startswith("{"))


def test_logit_check_lists_moved_tokens(smoke, capsys):
    """A position whose argmax moves is listed with its gap and error."""
    import numpy as np

    want, got = smoke.LogitRecorder(4), smoke.LogitRecorder(4)
    want.rows = {(0, 5): np.array([1.0, 0.99, 0.0, 0.0], np.float32)}
    got.rows = {(0, 5): np.array([0.99, 1.0, 0.0, 0.0], np.float32)}
    assert smoke.compare_logits("t", got, want) == [(0, 5)]
    assert "token 0 -> 1, gap 0.0100" in capsys.readouterr().out
    got.rows = {(0, 5): np.array([3.0, 0.0, 0.0, 0.0], np.float32)}
    with pytest.raises(AssertionError, match="differ"):
        smoke.compare_logits("t", got, want)


def test_four_chip_phase_on_host_devices():
    """The ``--four-chips`` phase at reduced size on four CPU devices: TP
    and four replicas, each on its own device."""
    from helpers import run_with_devices

    out = run_with_devices(f"""
import sys
sys.path.insert(0, {ROOT!r})
import chip_smoke
cfg, model, params = chip_smoke.build_model(full=False)
chip_smoke.four_chip_phase(cfg, model, params, backend="reference")
""", n_devices=4)
    assert "logits tp=4 vs one chip" in out and "KV arena V" in out
    assert "replicas=4 tokens identical to one chip: True" in out
