"""The main-path Pallas kernels compile for a TPU v5e chip.

``block_sketch``, the fused ``plan`` kernel and ``rsp_shuffle`` are compiled
for a *described* v5e chip (no chip attached) at the widths of the HIGGS
workload -- F = 29 columns (28 features + label), the query histogram's
bins, one original block of N / P = 86,016 records -- with the tiles their
tuners may pick.  This catches what interpret mode cannot: BlockSpecs that
break the TPU tiling rule, scalar stores to VMEM, and tiles that overflow
VMEM.  The CPU tests below check that the code asks for compiled kernels
when a TPU is the backend.

The typed ``plan`` kernel and the int32 ``rsp_shuffle`` compile at the
TPC-H lineitem cell's shapes (141,312 x 16 int32).

The topology is described inside a fixture, never at import: one process
at a time may load the TPU library, and every test worker imports this file.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.block_sketch import ops as bs_ops
from repro.kernels.block_sketch.kernel import block_sketch_pallas
from repro.kernels.plan import QueryPlan
from repro.kernels.plan import ops as plan_ops
from repro.kernels.plan.kernel import plan_sketch_pallas
from repro.kernels.rsp_shuffle import ops as rs_ops

F = 29             # 28 HIGGS features + the label column
BLOCK_ROWS = 86_016  # one original block: N / P = 11,010,048 / 128
DELTA = 672        # slice size: N / (P * K) at P = K = 128
BINS = (64, 128)   # the compile-test width and the Query default


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to compile against
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_compile_cache():
    # a described-chip compile written to the persistent cache cannot be
    # read back without the chip; keep these compiles out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile_has_kernel(fn, *shapes):
    hlo = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("bins", BINS)
@pytest.mark.parametrize("tile", bs_ops.PALLAS_TILES)
def test_block_sketch_compiles_for_v5e(one_chip, tile, bins):
    x = jax.ShapeDtypeStruct((BLOCK_ROWS, F), jnp.float32, sharding=one_chip)
    v = jax.ShapeDtypeStruct((F,), jnp.float32, sharding=one_chip)
    _compile_has_kernel(
        lambda x, lo, iw: block_sketch_pallas(
            x, lo, iw, bins=bins, tile_rows=tile, interpret=False
        ),
        x, v, v,
    )


PLANS = {
    "filtered": QueryPlan(predicates="c0 > 1.0"),
    "grouped": QueryPlan(group_by=F - 1, num_classes=2),
    "ungrouped": QueryPlan(),
}


@pytest.mark.parametrize("bins", BINS)
@pytest.mark.parametrize("tile", plan_ops.PALLAS_TILES)
@pytest.mark.parametrize("plan", sorted(PLANS))
def test_plan_kernel_compiles_for_v5e(one_chip, plan, tile, bins):
    x = jax.ShapeDtypeStruct((BLOCK_ROWS, F), jnp.float32, sharding=one_chip)
    v = jax.ShapeDtypeStruct((F,), jnp.float32, sharding=one_chip)
    _compile_has_kernel(
        lambda x, lo, iw: plan_sketch_pallas(
            x, lo, iw, plan=PLANS[plan], bins=bins, tile_rows=tile, interpret=False
        ),
        x, v, v,
    )


@pytest.mark.parametrize("tile", (DELTA, *rs_ops.SHUFFLE_TILES))
def test_rsp_shuffle_compiles_for_v5e(one_chip, tile):
    x = jax.ShapeDtypeStruct((BLOCK_ROWS, F), jnp.float32, sharding=one_chip)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    _compile_has_kernel(
        lambda x, k: rs_ops._randomize(x, k, tile_rows=tile, interpret=False), x, key
    )


# the TPC-H lineitem cell: one original block of N / P = 18,087,936 / 128
# int32 records of 16 columns, dealt in slices of 1,104
TPCH_ROWS, TPCH_COLUMNS, TPCH_DELTA = 141_312, 16, 1_104


def _typed_plans():
    from repro.data import tpch
    from repro.kernels.plan import as_where, bind_plan

    schema = tpch.lineitem_schema()
    return {
        "q1": bind_plan(schema, as_where(tpch.q1_where(90)),
                        [e for _, _, e in tpch.Q1 if e], tpch.Q1_GROUP_BY),
        "q6": bind_plan(schema, as_where(tpch.q6_where("1994-01-01", 0.06, 24)),
                        ["l_extendedprice * l_discount"]),
    }


@pytest.mark.parametrize("plan", ("q1", "q6"))
def test_typed_plan_kernel_compiles_for_v5e(one_chip, plan):
    from repro.kernels.plan.kernel import plan_totals_pallas

    typed = _typed_plans()[plan]
    # a block reaches the device flat (plan.ops._to_device_flat)
    flat = jax.ShapeDtypeStruct((TPCH_ROWS * TPCH_COLUMNS,), jnp.int32, sharding=one_chip)
    _compile_has_kernel(
        lambda flat: plan_totals_pallas(flat.reshape(-1, TPCH_COLUMNS), plan=typed,
                                        tile_rows=plan_ops.TYPED_PALLAS_TILE, interpret=False),
        flat,
    )


@pytest.mark.parametrize("plan", ("q1", "q6"))
def test_typed_jax_fold_compiles_for_v5e(one_chip, plan):
    # the typed fold that plan_totals runs on a TPU (impl="auto")
    flat = jax.ShapeDtypeStruct((TPCH_ROWS * TPCH_COLUMNS,), jnp.int32, sharding=one_chip)
    fold = plan_ops._typed_jax_fold(_typed_plans()[plan])
    assert "fusion" in fold.lower(flat, TPCH_COLUMNS).compile().as_text()


@pytest.mark.parametrize("tile", (TPCH_DELTA, 256))
def test_int32_rsp_shuffle_compiles_for_v5e(one_chip, tile):
    # as the pallas backend runs it on a typed table: the whole block's rows
    # permuted (a gather), then the tiles dealt
    x = jax.ShapeDtypeStruct((TPCH_ROWS, TPCH_COLUMNS), jnp.int32, sharding=one_chip)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    _compile_has_kernel(
        lambda x, k: rs_ops._randomize(x, k, tile_rows=tile, interpret=False, whole_block=True),
        x, key,
    )


# ---------------------------------------------------------------------------
# CPU: what the code asks for when the backend is a TPU
# ---------------------------------------------------------------------------

def _higgs_like(n, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, F - 1)).astype(np.float32)
    y = (rng.random(n) < 0.5).astype(np.float32)
    return np.concatenate([x, y[:, None]], axis=1)


def test_supports_pallas_refuses_misaligned_delta(monkeypatch):
    from repro import rsp
    from repro.core import RSPSpec
    from repro.rsp import backends

    def reason(n, blocks):
        spec = RSPSpec(num_records=n, num_blocks=blocks, num_original_blocks=blocks, seed=0)
        return backends._supports_pallas(rsp.PartitionRequest(data=_higgs_like(n), spec=spec))

    # interpreted (CPU) the kernel has no tiling rule to break
    assert reason(4 * 4 * 25, 4) is None
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert "multiple of 8" in reason(4 * 4 * 25, 4)   # delta = 25
    assert reason(4 * 4 * 24, 4) is None              # delta = 24
    # auto then falls through to numpy instead of a kernel the compiler refuses
    spec = RSPSpec(num_records=400, num_blocks=4, num_original_blocks=4, seed=0)
    req = rsp.PartitionRequest(data=_higgs_like(400), spec=spec)
    assert rsp.select_backend(req).name == "np"


def test_query_path_asks_for_compiled_kernels_on_tpu(monkeypatch, tmp_path):
    """With a TPU backend every Pallas launch on the query path, the tuners'
    timing runs included, is compiled (``interpret=False``)."""
    from repro import rsp
    from repro.kernels import autotune

    seen = []

    def sketch_spy(*args, interpret, **kw):
        seen.append(("block_sketch", interpret))
        return block_sketch_pallas(*args, interpret=True, **kw)  # CPU can only interpret

    def plan_spy(*args, interpret, **kw):
        seen.append(("plan", interpret))
        return plan_sketch_pallas(*args, interpret=True, **kw)

    data = _higgs_like(8 * 8 * 24)
    ds = rsp.partition(data, blocks=8, seed=0, backend="np", num_classes=2)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(bs_ops, "_sketch_pallas", sketch_spy)
    monkeypatch.setattr("repro.kernels.plan.kernel.plan_sketch_pallas", plan_spy)
    monkeypatch.setattr(autotune, "_TUNER", autotune.Autotuner(path=str(tmp_path / "t.json")))
    plan_ops.cache_clear()
    try:
        for impl in ("pallas", "auto"):
            monkeypatch.setenv("REPRO_AUTOTUNE", "on" if impl == "auto" else "off")
            ds.query("p95", sketch_impl=impl, use_sketches=False, max_blocks=3, bins=64)
            # a histogram aggregate: a moments-only plan never needs the kernel
            ds.query(["mean", "p50"], where="c0 > 1.0", sketch_impl=impl, max_blocks=3,
                     bins=64)
    finally:
        plan_ops.cache_clear()
    kinds = {k for k, _ in seen}
    assert kinds == {"block_sketch", "plan"}
    assert all(interpret is False for _, interpret in seen)


def test_compile_cache_dir(monkeypatch, tmp_path):
    """``$JAX_COMPILATION_CACHE_DIR`` wins untouched; otherwise the cache is
    a fixed, git-ignored directory at the checkout's root."""
    from repro import runtime

    prev = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(runtime.CACHE_ENV, str(tmp_path))
    assert runtime.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == prev
    monkeypatch.delenv(runtime.CACHE_ENV)
    try:
        path = runtime.use_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert path == os.path.join(root, ".jax_cache")
    with open(os.path.join(root, ".gitignore")) as fh:
        assert ".jax_cache/" in fh.read().split()


def test_device_peaks_are_keyed_by_kind():
    from repro.launch import mesh

    assert mesh.peaks("TPU v5 lite")["hbm_bw"] == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        mesh.peaks("cpu")
