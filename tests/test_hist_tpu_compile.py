"""The histogram kernel compiled for the chip that is not attached here:
the TPU's compiler is installed, so what Mosaic would refuse on a v5e (a
tile that does not align, more VMEM than a kernel may use) fails in
tier-1 and not on the chip. Nothing runs: no result, no time. One file,
so that one xdist worker loads the TPU's library."""

import functools

import jax
import jax.numpy as jnp
import pytest

from serenedb_tpu.ops import agg

ROWS = 1_000_448                 # the `hits` cells' padded row count


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture
def as_tpu(monkeypatch):
    """`group_count_hist` asks the backend whether to interpret: steer it
    to the Mosaic branch, and keep these compiles out of the persistent
    cache (they could not be read back without a chip)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", old)


def _shapes(sharding):
    return (jax.ShapeDtypeStruct((ROWS // 128, 128), jnp.int32,
                                 sharding=sharding),
            jax.ShapeDtypeStruct((ROWS // 128, 128), jnp.bool_,
                                 sharding=sharding))


# q7's 64 slots, the phrase codes of q5 / q12 / q14, the limit
@pytest.mark.parametrize("cells", [64, 113503, agg.HIST_MAX_CELLS])
def test_kernel_compiles_for_v5e(topo, as_tpu, cells):
    from jax.sharding import SingleDeviceSharding
    one = SingleDeviceSharding(topo.devices[0])
    # the undecorated function: a fresh trace under the steered backend
    fn = functools.partial(agg.group_count_hist.__wrapped__,
                           num_groups=cells)
    compiled = jax.jit(fn).lower(*_shapes(one)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    h, l = agg.hist_shape(cells)
    # the accumulator and a tile's one-hots fit the kernel's VMEM limit
    assert compiled.memory_analysis().temp_size_in_bytes < (64 << 20)
    assert h * l <= agg.HIST_MAX_CELLS


def test_kernel_compiles_under_the_mesh_wrap(topo, as_tpu):
    """`serene_mesh` > 1 shard_maps the aggregate program over the row
    axis and psums the counts: the kernel has to partition."""
    import numpy as np
    from jax import shard_map
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(topo.devices).reshape(-1), ("data",))
    rows = NamedSharding(mesh, P("data", None))

    def core(c, m):
        return jax.lax.psum(
            agg.group_count_hist.__wrapped__(c, m, num_groups=113503),
            "data")

    fn = shard_map(core, mesh=mesh, in_specs=(P("data", None),) * 2,
                   out_specs=P())
    n = ROWS // 128 + (-(ROWS // 128)) % 4
    args = (jax.ShapeDtypeStruct((n, 128), jnp.int32, sharding=rows),
            jax.ShapeDtypeStruct((n, 128), jnp.bool_, sharding=rows))
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text and "all-reduce" in text
