"""Topics shared out over the model axis: the exact power-topic merge,
the renormalization over every shard's selected topics, the sentinel
slots of topics another shard owns, and what a run with one topic shard
keeps of none of it.

The comparisons run the production ``shard_map`` path on four CPU
devices, in a child process (``XLA_FLAGS`` must be set before JAX
starts): this file run as a script computes every case once and prints
the results as JSON, and the tests read them.  The meter and the
compiled text of one topic shard need no mesh and run here.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
W, K, D, L = 512, 64, 16, 12


def _cfg(**kw):
    from repro.core.types import LDAConfig
    base = dict(vocab_size=W, num_topics=K, lambda_w=0.1, lambda_k_abs=8,
                inner_iters=6, residual_tol=0.0, impl="jnp")
    base.update(kw)
    return LDAConfig(**base)


def _batch(seed=0):
    rng = np.random.default_rng(seed)
    wid = rng.integers(0, W, (D, L)).astype(np.int32)
    cnt = rng.integers(0, 4, (D, L)).astype(np.float32)
    phi = rng.gamma(0.5, 3.0, (W, K)).astype(np.float32)
    return wid, cnt, phi


# ---------------------------------------------------------------------------
# the cases, computed in the four-device child process
# ---------------------------------------------------------------------------

def _mesh(shape):
    from jax.sharding import Mesh
    n = shape[0] * shape[1]
    return Mesh(np.asarray(jax.devices()[:n]).reshape(shape),
                ("data", "model"))


def _merge_case(K_, Pk, seed):
    """Global ids of the merged selection on mesh (1, 4) and of
    ``top_k`` over the unsharded rows, on rows full of ties."""
    from jax.sharding import PartitionSpec as P

    from repro.core import power as pw
    from repro.core.sync import MeshReducer
    rng = np.random.default_rng(seed)
    r = jnp.asarray(rng.integers(0, 5, (W, K_)).astype(np.float32))
    sel_w = jnp.asarray(rng.choice(W, 40, replace=False).astype(np.int32))
    Kl = K_ // 4

    def local(r_l, sel_w):
        ids = pw.select_power_topics_merged(r_l, sel_w, Pk,
                                            MeshReducer("model"))
        k0 = jax.lax.axis_index("model") * Kl
        return jnp.where(ids < Kl, ids + k0, -1)[None]

    got = jax.jit(jax.shard_map(
        local, mesh=_mesh((1, 4)), in_specs=(P(None, "model"), P()),
        out_specs=P("model")))(r, sel_w)                    # [4, P, Pk]
    got = np.asarray(got)
    owners = (got >= 0).sum(axis=0)
    merged = got.max(axis=0)
    want = np.asarray(jax.lax.top_k(r[sel_w], Pk)[1])
    return {"one_owner": bool((owners == 1).all()),
            "equal": bool(np.array_equal(merged, want))}


def _step_case(shape, base_shape, impl):
    """Relative gaps of one minibatch on mesh ``shape`` against the same
    minibatch on ``base_shape`` (jnp)."""
    from repro.core.pobp import shard_map_minibatch_fn
    wid, cnt, phi = _batch()
    key = jax.random.PRNGKey(3)
    out = {}
    for name, sh, im in (("run", shape, impl), ("base", base_shape, "jnp")):
        fn, _ = shard_map_minibatch_fn(_cfg(impl=im), _mesh(sh))
        out[name] = jax.jit(fn)(wid, cnt, phi, key, jnp.float32(1.0))
    a, b = (np.asarray(out["run"][0]), np.asarray(out["base"][0]))
    return {"phi_acc": float(np.max(np.abs(a - b) / np.abs(b))),
            "mean_r": abs(float(out["run"][2]) - float(out["base"][2]))
            / float(out["base"][2]),
            "iters": [int(out["run"][1]), int(out["base"][1])]}


def _reference_case(blocks):
    sys.path.insert(0, str(ROOT))
    from jax.sharding import NamedSharding, PartitionSpec as P

    from bench import reference as ref, reference_tp as ref_tp
    wid, cnt, phi = _batch(1)
    hp = (("W", W), ("K", K), ("alpha", 0.1), ("beta", 0.01), ("P", 51),
          ("Pk", 8), ("iters", 6), ("tol", 0.0))
    key = jax.random.PRNGKey(5)
    a = ref.pobp_step(jnp.asarray(phi), key, wid, cnt, hp=hp)
    mesh = _mesh((1, 4))
    b = ref_tp.pobp_step(jax.device_put(phi, NamedSharding(
        mesh, P(None, "model"))), key, jnp.asarray(wid), jnp.asarray(cnt),
        hp=hp, mesh=mesh, blocks=blocks)
    return {"phi_acc": float(np.max(np.abs(np.asarray(a[0])
                                           - np.asarray(b[0]))
                                    / np.abs(np.asarray(a[0])))),
            "mean_r": abs(float(a[2]) - float(b[2])) / float(a[2]),
            "iters": [int(a[3]), int(b[3])],
            "sharded": b[0].sharding.spec == P(None, "model")}


def _one_shard_text_case(shape):
    """Compiled text of the driver's step at one topic shard."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.types import LDATrainState
    from repro.launch.lda_train import make_shardmap_train_step
    mesh = _mesh(shape)
    step, _ = make_shardmap_train_step(_cfg(), mesh)
    sds = jax.ShapeDtypeStruct
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    rep = NamedSharding(mesh, P())
    rows = NamedSharding(mesh, P("data", None))
    state = LDATrainState(
        phi_acc=sds((W, K), jnp.float32,
                    sharding=NamedSharding(mesh, P(None, "model"))),
        m=sds((), jnp.int32, sharding=rep),
        rng=sds(key.shape, key.dtype, sharding=rep))
    text = step.lower(state, sds((D, L), jnp.int32, sharding=rows),
                      sds((D, L), jnp.float32, sharding=rows)
                      ).compile().as_text()
    return _merge_free(text)


def _merge_free(text):
    return {"all_gather": "all-gather" in text,
            "topic_merge": "pobp.topic_merge" in text,
            "model_norm": "pobp.model_norm" in text}


CASES = {
    "merge-ties": lambda: _merge_case(64, 8, 0),
    "merge-pk-over-shard": lambda: _merge_case(64, 20, 1),
    "merge-pk-is-k": lambda: _merge_case(16, 16, 2),
    "step-1x4": lambda: _step_case((1, 4), (1, 1), "jnp"),
    "step-2x2": lambda: _step_case((2, 2), (2, 1), "jnp"),
    "step-1x4-pallas": lambda: _step_case((1, 4), (1, 1), "pallas"),
    "reference-blocks-1": lambda: _reference_case(1),
    "reference-blocks-4": lambda: _reference_case(4),
    "text-4x1": lambda: _one_shard_text_case((4, 1)),
}


@pytest.fixture(scope="module")
def four_devices():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [str(ROOT / "src"), str(ROOT),
                    os.environ.get("PYTHONPATH", "")]))
    out = subprocess.run([sys.executable, __file__], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=str(ROOT))
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("case", ["merge-ties", "merge-pk-over-shard",
                                  "merge-pk-is-k"])
def test_merged_selection_is_top_k_over_the_unsharded_row(four_devices,
                                                          case):
    """Bit for bit, ties included: each selected slot is owned by one
    shard, and the owners' global ids are ``top_k``'s, in its order."""
    assert four_devices[case] == {"one_owner": True, "equal": True}


@pytest.mark.parametrize("case", ["step-1x4", "step-2x2",
                                  "step-1x4-pallas"])
def test_topic_sharded_minibatch_matches_one_topic_shard(four_devices,
                                                         case):
    """(1, 4) against (1, 1) and (2, 2) against (2, 1): data shards
    change the first sweep (Fig. 4 line 5), topic shards change nothing
    but the order of float sums.  The pallas case runs the power_pack
    scatter with sentinel ids on every shard."""
    got = four_devices[case]
    assert got["phi_acc"] < 1e-5 and got["mean_r"] < 1e-5, got
    assert got["iters"][0] == got["iters"][1]


@pytest.mark.parametrize("case", ["reference-blocks-1",
                                  "reference-blocks-4"])
def test_topic_sharded_reference_matches_the_reference(four_devices, case):
    got = four_devices[case]
    assert got["sharded"] and got["iters"][0] == got["iters"][1]
    assert got["phi_acc"] < 1e-6 and got["mean_r"] < 1e-6, got


def test_data_sharded_step_holds_no_topic_merge(four_devices):
    assert four_devices["text-4x1"] == {"all_gather": False,
                                        "topic_merge": False,
                                        "model_norm": False}


# ---------------------------------------------------------------------------
# one process, one device
# ---------------------------------------------------------------------------

def test_one_topic_shard_step_holds_no_topic_merge():
    """The streaming step and the driver's step on a (1, 1) mesh: no
    all-gather, no op under the merge or the renormalization scopes."""
    from repro.core.pobp import make_train_step
    from repro.core.types import LDATrainState
    for impl in ("jnp", "pallas"):
        step, _ = make_train_step(_cfg(impl=impl))
        sds = jax.ShapeDtypeStruct
        key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
        state = LDATrainState(phi_acc=sds((W, K), jnp.float32),
                              m=sds((), jnp.int32),
                              rng=sds(key.shape, key.dtype))
        text = step.lower(state, sds((D, L), jnp.int32),
                          sds((D, L), jnp.float32)).compile().as_text()
        assert _merge_free(text) == {"all_gather": False,
                                     "topic_merge": False,
                                     "model_norm": False}, impl
    assert _one_shard_text_case((1, 1)) == {"all_gather": False,
                                            "topic_merge": False,
                                            "model_norm": False}


def test_meter_bills_the_merge_and_the_renormalization():
    """Per selective iteration: S * P * Pk values and as many ids for the
    merge, 2 * T floats for the renormalization (each token's selected
    mass and denominator); both are loop phases."""
    from repro.core.pobp import pobp_shard_body
    from repro.core.sync import CommMeter, LocalReducer, MeshReducer
    S = 4
    cfg = _cfg()
    wid, cnt, phi = _batch()
    meter = CommMeter()
    data = LocalReducer(meter=meter)
    model = MeshReducer("model", meter=meter)

    def shard(phi_l):
        return pobp_shard_body(wid, cnt, phi_l, jax.random.PRNGKey(0),
                               jnp.float32(1.0), cfg, data, model)[:3]

    phis = jnp.asarray(phi).reshape(W, S, K // S).transpose(1, 0, 2)
    jax.jit(jax.vmap(shard, axis_name="model"))(phis)
    P, Pk, T = cfg.num_power_words, cfg.num_power_topics, D * L
    by = meter.bytes_by_phase
    assert by["topic_merge"] == S * P * Pk * 4 + S * P * Pk * 4
    assert by["model_norm_loop"] == 2 * T * 4
    assert meter.per_minibatch_bytes(3) - meter.per_minibatch_bytes(2) == (
        by["topic_merge"] + by["model_norm_loop"] + by["model_rw_loop"])


def test_packed_formulation_refuses_topic_shards():
    from repro.core.pobp import pobp_shard_body
    from repro.core.sync import LocalReducer, MeshReducer
    cfg = _cfg(sweep_policy="packed")
    wid, cnt, phi = _batch()

    def shard(phi_l):
        return pobp_shard_body(wid, cnt, phi_l, jax.random.PRNGKey(0),
                               jnp.float32(1.0), cfg, LocalReducer(),
                               MeshReducer("model"))[:3]

    phis = jnp.asarray(phi).reshape(W, 2, K // 2).transpose(1, 0, 2)
    with pytest.raises(ValueError, match="packed"):
        jax.jit(jax.vmap(shard, axis_name="model"))(phis)


if __name__ == "__main__":
    print(json.dumps({name: case() for name, case in CASES.items()}))
