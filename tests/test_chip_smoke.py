"""chip_smoke.py off the chip: the device check refuses a CPU, and the
training -> parity -> serving phases run end to end at a tiny size with
the kernels in interpret mode (the script's first rehearsal)."""

import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod
    spec.loader.exec_module(mod)
    return mod


TINY = dict(vocab=300, topics=16, lambda_k=4, docs=16, doc_len=20,
            len_buckets="32", minibatches=2, inner_iters=4, eval_docs=16,
            parity_docs=8, ref_vocab=120, ref_docs=6, requests=12, slots=4,
            slot_len=32)


def test_device_check_refuses_cpu(smoke):
    with pytest.raises(SystemExit, match="no TPU"):
        smoke.check_device(1)


def test_script_on_cpu_exits_nonzero_without_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         env=env, capture_output=True, text=True,
                         timeout=300, cwd=ROOT)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout


def test_phases_run_at_tiny_size(smoke, tmp_path):
    s = smoke.Sizes(**TINY)
    res = smoke.train_phase(s, 0, tmp_path / "ckpt")
    assert len(res["mean_r"]) == s.minibatches
    assert np.isfinite(res["ppl"])
    par = smoke.parity_phase(s, 0, res["phi_acc"])
    assert par["phi_pallas_vs_ref"] <= smoke.REF_BOUND
    stats = smoke.serve_phase(s, 0, tmp_path / "ckpt")
    assert stats["served"] == s.requests


def test_compile_cache_placed_from_outside_or_fixed(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins and nothing else is set; without it
    an accelerator run caches at one fixed path in the checkout, and a
    CPU run caches nothing."""
    import jax

    from repro.launch import compile_cache as cc
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(cc.CACHE_ENV, "/elsewhere")
    assert cc.use_compile_cache("tpu") == "/elsewhere"
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv(cc.CACHE_ENV)
    assert cc.use_compile_cache("cpu") is None
    assert jax.config.jax_compilation_cache_dir == before
    try:
        assert cc.use_compile_cache("tpu") == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(ROOT / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
