"""Multi-host RSP tests on real ``jax.distributed`` CPU meshes.

Two harness shapes (``tests/distributed_harness.py``):

* ``run_forced_devices`` -- one subprocess with forced XLA host devices,
  for shard_map collectives (the Algorithm-1 all_to_all partition);
* ``run_processes`` -- N coordinated OS processes around a fresh
  coordination-service port, for the distributed query protocol.  Every
  process partitions the same seed-deterministic corpus, so each one can
  check its mesh answer bit-for-bit against the single-host reference it
  computes locally.
"""

import pytest

from distributed_harness import assert_ok, run_forced_devices, run_processes

# ---------------------------------------------------------------------------
# shard_map + all_to_all Algorithm-1 partition (multi-device, one process)
# ---------------------------------------------------------------------------

PARTITION_SOURCE = r"""
import jax, jax.numpy as jnp, numpy as np
from repro.core import distributed_rsp_partition, is_partition, RSPSpec, two_stage_partition_np
from repro.core.similarity import max_label_divergence
from repro.data import make_nonrandom_higgs_like

mesh = jax.sharding.Mesh(np.array(jax.devices()).reshape(8), ("data",))

# class-sorted (worst case) data
x, y = make_nonrandom_higgs_like(6400, seed=1)
data = np.concatenate([x, y[:, None].astype(np.float32)], axis=1)

out = np.asarray(distributed_rsp_partition(jnp.asarray(data), jax.random.PRNGKey(7), mesh, axis="data"))
assert out.shape == (8, 800, 29), out.shape
assert is_partition(out, data), "not a partition"
for k in range(8):
    div = max_label_divergence(out[k][:, -1], y, 2)
    assert div < 0.06, f"block {k} label divergence {div}"

# determinism
out2 = np.asarray(distributed_rsp_partition(jnp.asarray(data), jax.random.PRNGKey(7), mesh, axis="data"))
np.testing.assert_array_equal(out, out2)

# non-square N must raise
try:
    distributed_rsp_partition(jnp.asarray(data[:100]), jax.random.PRNGKey(0), mesh, axis="data")
    raise SystemExit("expected ValueError")
except ValueError:
    pass
print("DISTRIBUTED_RSP_OK")
"""


@pytest.mark.slow
def test_distributed_rsp_partition_8dev():
    assert_ok(
        run_forced_devices(PARTITION_SOURCE, devices=8, timeout=600),
        marker="DISTRIBUTED_RSP_OK",
    )


# ---------------------------------------------------------------------------
# distributed query protocol (N real processes, coordination-service KV)
# ---------------------------------------------------------------------------

MESH_QUERY_SOURCE = r"""
import json
import numpy as np
from repro.distributed.mesh import init_from_env
from repro.rsp.dataset import RSPDataset

t = init_from_env()
rng = np.random.default_rng(7)
data = rng.normal(size=(32768, 4)).astype(np.float32)
data[:, 2] = rng.gamma(2.0, 1.0, size=32768).astype(np.float32)
ds = RSPDataset.partition(data, 32, seed=3)

kwargs = dict(
    aggregates=["mean", "p95"], target_rel_err=0.04, seed=11,
    policy="weighted", where="c2 > 0.5", max_blocks=32,
)
ref = ds.query(**kwargs)

dds = ds.distribute(t, straggler_grace=30.0, poll_interval=0.05)
res = dds.query(**kwargs)

def sig(r):
    return json.dumps({
        "est": {a.name: np.asarray(a.estimate).ravel().tolist() for a in r.aggregates},
        "lo": {a.name: None if a.ci_lo is None else np.asarray(a.ci_lo).ravel().tolist() for a in r.aggregates},
        "hi": {a.name: None if a.ci_hi is None else np.asarray(a.ci_hi).ravel().tolist() for a in r.aggregates},
        "blocks_read": r.blocks_read,
        "converged": r.converged,
    }, sort_keys=True)

assert sig(ref) == sig(res), "distributed != single-host:\n%s\n%s" % (sig(ref), sig(res))
assert len(dds.owned_blocks) > 0  # every host holds part of the deal
print("MESH_QUERY_OK", flush=True)
"""


@pytest.mark.slow
@pytest.mark.parametrize("num_processes", [2, 4])
def test_mesh_query_bit_identical(num_processes):
    results = run_processes(MESH_QUERY_SOURCE, num_processes=num_processes, timeout=300)
    assert_ok(results, marker="MESH_QUERY_OK")


# The last process connects to the mesh, then hangs without computing a
# single payload; the harness SIGKILLs it mid-query.  Survivors must hit the
# straggler grace deadline, steal its leases via the deterministic redeal,
# and still produce the bit-identical single-host answer.
DEAD_HOST_SOURCE = r"""
import json, os, time
import numpy as np
from repro.distributed.mesh import init_from_env
from repro.rsp.dataset import RSPDataset

t = init_from_env()
victim = t.host_id == t.num_hosts - 1

rng = np.random.default_rng(7)
data = rng.normal(size=(32768, 4)).astype(np.float32)
data[:, 2] = rng.gamma(2.0, 1.0, size=32768).astype(np.float32)
ds = RSPDataset.partition(data, 32, seed=3)

if victim:
    time.sleep(600)  # never participates; SIGKILLed by the harness

kwargs = dict(
    aggregates=["mean", "p95"], target_rel_err=0.04, seed=11,
    policy="weighted", where="c2 > 0.5", max_blocks=32,
)
ref = ds.query(**kwargs)
dds = ds.distribute(t, straggler_grace=3.0, poll_interval=0.05)
res = dds.query(**kwargs)

def sig(r):
    return json.dumps({
        "est": {a.name: np.asarray(a.estimate).ravel().tolist() for a in r.aggregates},
        "blocks_read": r.blocks_read, "converged": r.converged,
    }, sort_keys=True)

assert sig(ref) == sig(res), "survivor diverged:\n%s\n%s" % (sig(ref), sig(res))
assert sorted(dds.ownership.hosts()) == list(range(t.num_hosts - 1)), dds.ownership.hosts()

print("DEAD_HOST_OK", flush=True)
# the coordinator (process 0) hosts the coordination service, so it leaves
# last: a peer still running when the service goes away is aborted by it
if t.host_id == 0:
    for h in range(1, t.num_hosts - 1):
        assert t.get("done/%d" % h, timeout=60.0) is not None
else:
    t.put("done/%d" % t.host_id, b"1")
# skip jax.distributed atexit teardown: the coordinator would wait for the
# killed process's orderly shutdown that never comes
os._exit(0)
"""


@pytest.mark.slow
def test_mesh_query_survives_killed_host():
    results = run_processes(
        DEAD_HOST_SOURCE, num_processes=3, timeout=300, kill_after={2: 8.0}
    )
    # the victim has no exit contract at all: it is either SIGKILLed by the
    # harness or aborts itself when the finished coordinator tears down
    assert_ok([r for r in results if r.process_id != 2], marker="DEAD_HOST_OK")
