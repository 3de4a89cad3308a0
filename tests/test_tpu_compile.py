"""Every Pallas kernel of the main path, compiled by Mosaic for one
described TPU v5e chip at NYTimes width (W=102,660, K=1000, lambda_W=0.1
so P=10,266 power words; Pk=50), with no chip attached; ``power_pack``
also at PubMed width (W=141,043, K=2000, P=14,104, Pk=100) and at one
topic shard of K=10,000 over four chips (Kl=2,500, Pk=50).

A compile that passes here is not a chip run: nothing executes.  It is
what the chip's compiler accepts — block shapes, VMEM limits, DMA
alignment — checked at no chip time.  Shapes the training carry kernels
cannot take at this width are pinned to the dispatch that routes them to
XLA instead.

The topology is described inside a module-scoped fixture only: the TPU
library may be loaded by one process at a time, and every test worker
imports this file.
"""

import pytest

import jax
import jax.numpy as jnp

W, K, LAMBDA_W, PK = 102_660, 1000, 0.1, 50
P = int(round(LAMBDA_W * W))
T = 65_536                       # token slots: 256 documents x L=256
D = 256


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture
def mosaic(monkeypatch):
    """Kernels lowered for Mosaic instead of the interpreter; traces made
    in this mode never leak into the CPU tests that share the worker."""
    import repro.kernels as kernels
    monkeypatch.setattr(kernels, "INTERPRET", False)
    jax.clear_caches()
    yield
    jax.clear_caches()


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    ma = compiled.memory_analysis()
    print(f"args={ma.argument_size_in_bytes} out={ma.output_size_in_bytes} "
          f"temp={ma.temp_size_in_bytes}")
    return text


def _s(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_bp_update_dense_sweep(one_chip, mosaic):
    from repro.kernels.bp_update.kernel import bp_update_tokens
    kp = 1024                                   # K padded to lanes (ops.py)
    _compile(lambda c, m, th, ph, pt: bp_update_tokens(
        c, m, th, ph, pt, alpha=0.1, beta=0.01, wbeta=W * 0.01),
        _s(one_chip, (T, 1)), _s(one_chip, (T, kp)), _s(one_chip, (T, kp)),
        _s(one_chip, (T, kp)), _s(one_chip, (1, kp)))


def test_power_sweep_packed(one_chip, mosaic):
    from repro.kernels.power_sweep.ops import power_sweep
    _compile(lambda p, c, m, th, pt, ph: power_sweep(
        p, c, m, th, pt, ph, alpha=0.1, beta=0.01, wbeta=W * 0.01),
        _s(one_chip, (T,), jnp.int32), _s(one_chip, (T, 1)),
        _s(one_chip, (T, PK)), _s(one_chip, (T, PK)), _s(one_chip, (T, PK)),
        _s(one_chip, (P, PK)))


@pytest.mark.parametrize("kblocked", [False, True])
def test_carry_fold_in_serving(one_chip, mosaic, kblocked):
    """update_phi=False over the whole NYTimes vocabulary."""
    from repro.kernels.power_sweep.ops import power_sweep_carry
    _compile(lambda p, d, c, m, th, pt, ph, mk: power_sweep_carry(
        p, d, c, m, th, pt, ph, mk, alpha=0.1, beta=0.0, wbeta=1.0,
        update_phi=False, kblocked=kblocked),
        _s(one_chip, (T,), jnp.int32), _s(one_chip, (T,), jnp.int32),
        _s(one_chip, (T, 1)), _s(one_chip, (T, K)), _s(one_chip, (D, K)),
        _s(one_chip, (K,)), _s(one_chip, (W + 1, K)), _s(one_chip, (1, K)))


@pytest.mark.parametrize("kblocked,n_pow", [(False, 256), (True, 880)])
def test_carry_training(one_chip, mosaic, kblocked, n_pow):
    """update_phi=True at K=1000 and the widest power-row tables the
    dispatch hands each carry kernel (below: NYTimes' own P goes to XLA)."""
    from repro.core.sweep_dispatch import resolve_carry
    from repro.kernels.power_sweep.ops import power_sweep_carry
    assert resolve_carry(K, n_pow, D) == ("kblocked" if kblocked
                                          else "dense_layout")
    _compile(lambda p, d, c, m, th, pt, ph, mk: power_sweep_carry(
        p, d, c, m, th, pt, ph, mk, alpha=0.1, beta=0.01, wbeta=W * 0.01,
        update_phi=True, kblocked=kblocked),
        _s(one_chip, (T,), jnp.int32), _s(one_chip, (T,), jnp.int32),
        _s(one_chip, (T, 1)), _s(one_chip, (T, K)), _s(one_chip, (D, K)),
        _s(one_chip, (K,)), _s(one_chip, (n_pow + 1, K)),
        _s(one_chip, (n_pow + 1, K)))


def test_nytimes_training_sweep_dispatches_to_xla():
    """At NYTimes width the carry kernels' one-hot gathers over P+1 power
    rows would cost ~10x the XLA formulation: auto resolves to 'xla' and
    says so, before any kernel is traced."""
    from repro.core import sweep_dispatch as sd
    from repro.core.types import LDAConfig
    cfg = LDAConfig(vocab_size=W, num_topics=K, lambda_w=LAMBDA_W,
                    lambda_k_abs=PK, impl="pallas")
    assert cfg.num_power_words == P
    got = sd.resolve_sweep_policy(cfg, T, K, PK, P, impl="pallas", n_docs=D)
    assert got == "xla"
    assert sd.resolve_carry(K, P, D, "kblocked") == "xla"
    assert any(e["where"] == "selective_sweep" and e["shape"]["P"] == P
               and "MACs" in e["reason"] for e in sd.DISPATCH_LOG)
    # serving never gathers power rows: the fold-in keeps its kernel
    assert sd.resolve_fold_in(K, D) == "dense_layout"


@pytest.mark.parametrize("w,k,p,pk", [(W, K, P, PK),
                                      (141_043, 2000, 14_104, 100),
                                      (141_043, 2500, 14_104, 50)],
                         ids=["nytimes", "pubmed", "pubmed-k10000-shard"])
def test_power_pack_gather_and_scatter(one_chip, mosaic, w, k, p, pk):
    """The packed gather and the tile-visit scatter at NYTimes and PubMed
    width; the scatter's kernel keeps the op name the benchmark's
    ``power_pack.roofline`` reader looks for."""
    import re
    from repro.kernels.power_pack.ops import pack_rows, scatter_add_rows
    mat = _s(one_chip, (w, k))
    sel_w = _s(one_chip, (p,), jnp.int32)
    sel_k = _s(one_chip, (p, pk), jnp.int32)
    _compile(pack_rows, mat, sel_w, sel_k)
    text = _compile(scatter_add_rows, mat, sel_w, sel_k, _s(one_chip, (p, pk)))
    calls = re.findall(
        r'%([\w.]+) = [^\n]*custom_call_target="tpu_custom_call"', text)
    assert len(calls) == 1 and re.fullmatch(r"scatter_add_rows\.\d+",
                                            calls[0]), calls
