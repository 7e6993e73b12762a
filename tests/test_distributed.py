"""Distribution substrate tests: sharding rules, ZeRO specs, gradient
compression, elastic re-sharding, straggler scheduling.  Multi-device cases
run through the harness with a forced host device count."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from distributed_harness import assert_ok, run_forced_devices
from repro.distributed.compression import (
    compression_ratio,
    error_feedback_compress,
    init_residual,
    quantize_roundtrip,
)
from repro.distributed.straggler import simulate
from repro.models.common import ParamSpec


# ---------------------------------------------------------------------------
# sharding rules (pure logic; no devices needed)
# ---------------------------------------------------------------------------

def _rules(num_kv=8, tp=1):
    from repro.distributed.sharding import default_rules

    devs = np.array(jax.devices() * max(tp, 1)).reshape(1, tp) if tp > 1 else np.array(
        jax.devices()[:1]
    ).reshape(1, 1)
    mesh = jax.sharding.Mesh(devs, ("data", "model"))
    return default_rules(mesh, num_kv_heads=num_kv)


def test_rules_kv_sharding_threshold():
    # kv heads shard over 'model' only when divisible by the TP degree
    assert _rules(8, tp=4).rules["kv_heads"] == "model"
    assert _rules(8, tp=4).rules["heads_inner"] is None
    assert _rules(1, tp=4).rules["kv_heads"] is None
    assert _rules(1, tp=4).rules["heads_inner"] == "model"
    assert _rules(6, tp=4).rules["kv_heads"] is None  # 6 % 4 != 0


def test_spec_mapping():
    r = _rules()
    assert r.spec_for(("embed", "ff")) == P(None, "model")
    assert r.spec_for(("layers", "embed", "heads")) == P(None, None, "model")
    assert r.spec_for(("vocab", "embed")) == P("model", None)


def test_zero_shard_picks_largest_replicated_dim():
    from repro.distributed.sharding import default_rules, zero_shard_spec

    mesh = jax.sharding.Mesh(np.array(jax.devices() * 4).reshape(4, 1), ("data", "model"))
    r = default_rules(mesh)
    # [layers=8, d=64, ff->model]: ZeRO should shard d (=64, divisible by 4)
    spec = ParamSpec((8, 64, 128), ("layers", "embed", "ff"))
    assert zero_shard_spec(spec, r) == P("data", None, "model") or zero_shard_spec(spec, r) == P(
        None, "data", "model"
    )
    # all dims too small / already sharded -> unchanged
    spec2 = ParamSpec((3,), ("embed",))
    assert zero_shard_spec(spec2, r) == P(None)


def test_constrain_noop_without_context():
    from repro.distributed.sharding import constrain

    x = jnp.ones((2, 3))
    y = constrain(x, ("batch", None))
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# compression
# ---------------------------------------------------------------------------

def test_quantize_roundtrip_error_bounded():
    x = jax.random.normal(jax.random.PRNGKey(0), (1000,)) * 5.0
    y = quantize_roundtrip(x)
    err = jnp.abs(x - y).max()
    assert float(err) <= float(jnp.abs(x).max()) / 127.0 + 1e-6


def test_error_feedback_is_unbiased_over_time():
    """With error feedback, the *accumulated* compressed signal tracks the
    accumulated true signal (residual stays bounded)."""
    g = {"w": jax.random.normal(jax.random.PRNGKey(1), (512,))}
    r = init_residual(g)
    total_true = jnp.zeros((512,))
    total_sent = jnp.zeros((512,))
    for i in range(20):
        gi = {"w": jax.random.normal(jax.random.PRNGKey(i + 2), (512,))}
        comp, r = error_feedback_compress(gi, r)
        total_true += gi["w"]
        total_sent += comp["w"]
    drift = jnp.abs(total_true - total_sent).max()
    assert float(drift) <= float(jnp.abs(total_true).max()) / 100.0 + 0.1


def test_compression_ratio():
    assert compression_ratio(jnp.float32) < 0.26
    assert compression_ratio(jnp.bfloat16) < 0.52


# ---------------------------------------------------------------------------
# straggler scheduling
# ---------------------------------------------------------------------------

def test_straggler_work_stealing_beats_static():
    speeds = [1.0, 1.0, 1.0, 0.1]  # one 10x straggler
    static = simulate(64, speeds, steal=False)
    dynamic = simulate(64, speeds, steal=True)
    assert dynamic["makespan"] < static["makespan"] * 0.5
    done = sorted(b for bs in dynamic["per_host_blocks"].values() for b in bs)
    assert done == list(range(64))  # every block exactly once


def test_straggler_balanced_hosts_no_pathology():
    speeds = [1.0] * 4
    dyn = simulate(32, speeds, steal=True)
    static = simulate(32, speeds, steal=False)
    assert dyn["makespan"] <= static["makespan"] * 1.26


def test_straggler_host_failure_completes_every_block_once():
    # kill the fastest host early: its leases (incl. the in-flight block)
    # requeue and the survivors drain them -- nothing dropped, no duplicates
    out = simulate(40, [4.0, 1.0, 1.0], fail_at={0: 2.0})
    assert out["dead_hosts"] == [0]
    assert out["completed"] == 40
    done = [b for bs in out["per_host_blocks"].values() for b in bs]
    assert sorted(done) == list(range(40))
    healthy = simulate(40, [4.0, 1.0, 1.0])
    assert out["makespan"] >= healthy["makespan"]  # losing a host has a cost


def test_straggler_all_hosts_dead_reports_shortfall():
    out = simulate(40, [1.0, 1.0], fail_at={0: 0.5, 1: 0.5})
    assert out["dead_hosts"] == [0, 1]
    assert out["completed"] < 40  # honest: blocks were lost, not hidden


# ---------------------------------------------------------------------------
# multi-device: compressed psum + elastic restore (subprocess, 8 devices)
# ---------------------------------------------------------------------------

MULTI_DEV_SCRIPT = r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# --- compressed psum over a 'pod' axis -----------------------------------
from repro.distributed.compression import compressed_psum

mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("pod", "data"))
x = jax.random.normal(jax.random.PRNGKey(0), (2, 256))

def f(x):
    return compressed_psum(x, "pod")

y = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("pod", None), out_specs=P("pod", None)))(x)
want = jnp.broadcast_to(x.mean(axis=0, keepdims=True), x.shape)
err = float(jnp.abs(np.asarray(y) - want).max())
rel = err / float(jnp.abs(want).max())
assert rel < 0.02, f"compressed psum error {rel}"

# --- elastic: checkpoint on 8-dev mesh, restore on 2-dev mesh -------------
import tempfile
from repro.checkpoint import store as ckpt
from repro.configs import smoke_config
from repro.distributed.sharding import default_rules
from repro.distributed.elastic import restore_for_mesh, state_shardings
from repro.train.loop import init_state

cfg = smoke_config("llama3.2-1b")
state = init_state(cfg, seed=0)
d = tempfile.mkdtemp()
ckpt.save(d, 3, state, extra={})

mesh_big = Mesh(np.array(jax.devices()).reshape(4, 2), ("data", "model"))
rules_big = default_rules(mesh_big, num_kv_heads=cfg.num_kv_heads)
like = jax.eval_shape(lambda: init_state(cfg, 0))
restored, _ = restore_for_mesh(d, 3, cfg, rules_big, like={"params": like["params"], "opt": like["opt"]})
# leaves actually sharded over the mesh
leaf = restored["opt"]["master"]["layers"]["mlp"]["gate"]["w"]
assert len(leaf.sharding.device_set) > 1, leaf.sharding
np.testing.assert_allclose(
    np.asarray(leaf), np.asarray(state["opt"]["master"]["layers"]["mlp"]["gate"]["w"]), rtol=1e-6
)

# smaller mesh restore
devs = np.array(jax.devices()[:2]).reshape(1, 2)
mesh_small = Mesh(devs, ("data", "model"))
rules_small = default_rules(mesh_small, num_kv_heads=cfg.num_kv_heads)
restored2, _ = restore_for_mesh(d, 3, cfg, rules_small, like={"params": like["params"], "opt": like["opt"]})
leaf2 = restored2["params"]["layers"]["mlp"]["gate"]["w"]
np.testing.assert_allclose(np.asarray(leaf2, np.float32), np.asarray(state["params"]["layers"]["mlp"]["gate"]["w"], np.float32))
print("MULTIDEV_OK")
"""


@pytest.mark.slow
def test_multi_device_substrate():
    assert_ok(
        run_forced_devices(MULTI_DEV_SCRIPT, devices=8, timeout=900),
        marker="MULTIDEV_OK",
    )
