"""Compile the served path for a described TPU v5e, with no chip attached.

The TPU compiler is installed with jaxlib: ``get_topology_desc`` describes
a ``v5e:2x2`` host, and ``jit(...).lower(shapes).compile()`` raises what
the chip's compiler would raise -- Mosaic tiling and lowering rules, VMEM
limits, device memory, kernels that cannot be partitioned.  Interpret-mode
tests cannot see any of that.  Nothing runs, so these tests say nothing
about results or time.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and it keeps it until exit.
All compiles stay in this one file (one worker), in the test's own
process, with the persistent compile cache off (its entries for a
described chip cannot be read back without one).
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.configs.symed_paper import PAPER_SYMED
from repro.core.symed import receiver_init
from repro.kernels import ops
from repro.kernels.dtw import dtw_pallas
from repro.kernels.ewma import ewma_scan_pallas
from repro.kernels.kmeans import kmeans_assign_pallas
from repro.launch import stream
from repro.utils.jax_compat import make_mesh

SLOTS = 1024            # the deployment's resident slot table
WINDOW = 256            # its window cap
HBM_BYTES = 16e9        # one v5e chip


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    jax.clear_caches()  # drop traces made with the kernel's Mosaic branch


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    mesh = make_mesh((4,), ("data",), devices=topo.devices)
    return mesh, NamedSharding(mesh, P("data"))


@pytest.fixture
def mosaic(monkeypatch):
    """Steer ``kernels.ops`` onto its compiled (non-interpret) branch."""
    monkeypatch.setattr(ops, "on_cpu", lambda: False)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled(fn, *args, **kw):
    compiled = jax.jit(fn, **kw).lower(*args).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
    return compiled.as_text()


def _table_args(sharding, pieces: bool):
    table = jax.eval_shape(lambda: jax.vmap(
        lambda k: receiver_init(PAPER_SYMED, k))(
            jax.random.split(jax.random.key(0), SLOTS)))
    table = jax.tree.map(
        lambda a: _spec(a.shape, a.dtype, sharding), table)
    win_f = _spec((SLOTS, WINDOW), jnp.float32, sharding)
    count = _spec((SLOTS,), jnp.int32, sharding)
    if not pieces:
        return table, win_f, count
    return (table, win_f, _spec((SLOTS, WINDOW), jnp.int32, sharding), count,
            _spec((SLOTS,), jnp.float32, sharding), count)


@pytest.mark.parametrize("slots", [1, 8, SLOTS])
def test_kmeans_kernel_compiles_at_paper_widths(one_chip, slots):
    n, k = PAPER_SYMED.n_max, PAPER_SYMED.k_max
    text = _compiled(
        lambda x, m, c, a: kmeans_assign_pallas(x, m, c, a),
        _spec((slots, n, 2), jnp.float32, one_chip),
        _spec((slots, n), jnp.bool_, one_chip),
        _spec((slots, k, 2), jnp.float32, one_chip),
        _spec((slots, k), jnp.bool_, one_chip))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("band", [None, 32])
def test_dtw_kernel_compiles_at_monitor_length(one_chip, band):
    # the monitor scores one session's whole history: 2048-4096 points
    x = _spec((1, 4096), jnp.float32, one_chip)
    text = _compiled(lambda a, b: dtw_pallas(a, b, band), x, x)
    assert "tpu_custom_call" in text


def test_ewma_kernel_compiles(one_chip):
    ts = _spec((300, 4096), jnp.float32, one_chip)
    text = _compiled(lambda t: ewma_scan_pallas(t, 0.01), ts)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("pieces", [False, True], ids=["raw", "pieces"])
def test_table_step_with_kernel_compiles(one_chip, mosaic, pieces):
    step = stream._table_step_pieces if pieces else stream._table_step
    text = (step.lower(*_table_args(one_chip, pieces), cfg=PAPER_SYMED,
                       digitize_every_k=1, use_kernel=True)
            .compile().as_text())
    assert "tpu_custom_call" in text


def test_sharded_table_step_runs_kernel_per_shard(four_chips, mosaic):
    mesh, sharding = four_chips
    compiled = stream._table_step.lower(
        *_table_args(sharding, False), cfg=PAPER_SYMED, digitize_every_k=1,
        use_kernel=True, mesh=mesh).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # slots are independent: no shard needs another's data
    assert "all-gather" not in text and "all-to-all" not in text
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes < 4e6  # a quarter of the table each
