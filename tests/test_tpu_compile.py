"""The main path's device programs, compiled for a described v5e (no chip).

The TPU compiler is installed here and compiles for a chip that is only
described, so what it would refuse — a layout, a program too large for
16 GB, a collective it cannot lower — fails here at no chip time. Nothing
runs, so these tests say nothing about results or speed.

The topology is described inside a module fixture, never at import: the
process that describes it loads libtpu and keeps it until it exits, and
the test runner's workers must all collect the same tests.
"""
import dataclasses
import os

import numpy as np
import pytest

HBM_BYTES = 16 << 30  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _fits_one_chip(compiled):
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert used < HBM_BYTES, f"{used} bytes do not fit one chip"


@pytest.mark.parametrize("size_class", [8 << 10, 64 << 10, 2 << 20])
def test_block_pool_programs_compile(one_chip, size_class):
    """DeviceBlockPool's donating fill and its slice+bitcast view, for
    one size class, with an fp32 payload that fills the block."""
    import jax
    import jax.numpy as jnp

    from brpc_tpu.rpc import device_transport as dt

    assert size_class in dt.DeviceBlockPool.SIZE_CLASSES
    fill, view = dt.pool_programs()
    block = jax.ShapeDtypeStruct((size_class,), jnp.uint8,
                                 sharding=one_chip)
    compiled = fill.lower(block, block).compile()
    _fits_one_chip(compiled)
    compiled = view.lower(block, size_class, np.dtype(np.float32),
                          (size_class // 4,)).compile()
    _fits_one_chip(compiled)


@pytest.mark.parametrize("word,dtype", [("uint16", "bfloat16"),
                                        ("uint32", "float32")])
@pytest.mark.parametrize("size_class", [8 << 10, 64 << 10, 2 << 20])
def test_block_pool_word_programs_compile(one_chip, size_class, word,
                                          dtype):
    """On a block of the payload's word width the view is one elementwise
    bitcast: no temporaries, and none of the byte-block view's padded
    repack (a rank-2 reshape and a shift-and-reduce of its rows)."""
    import re

    import jax

    from brpc_tpu.rpc import device_transport as dt

    assert dt._block_word(dt._np_dtype(dtype)) == word
    fill, view = dt.pool_programs()
    block = jax.ShapeDtypeStruct((size_class // np.dtype(word).itemsize,),
                                 np.dtype(word), sharding=one_chip)
    compiled = fill.lower(block, block).compile()
    assert compiled.memory_analysis().temp_size_in_bytes == 0
    np_dtype = dt._np_dtype(dtype)
    compiled = view.lower(block, size_class, np_dtype,
                          (size_class // np_dtype.itemsize,)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes == 0
    hlo = compiled.as_text()
    assert "bitcast-convert" in hlo
    assert "reduce" not in hlo
    assert not re.search(r"\[\d+,\d+\]\S*\s+reshape\(", hlo)


def test_flagship_train_step_compiles(topo, one_chip, monkeypatch):
    """The flagship step at its bench width (d2048), cut to 2 layers:
    the layer scan's body is the same at any depth."""
    import jax
    import jax.numpy as jnp

    from brpc_tpu.tensor import init_params, make_spmd_train_step
    from brpc_tpu.tensor.config import FLAGSHIP, FLAGSHIP_BATCH, MeshSpec

    # make_mesh takes its devices from jax.devices()
    monkeypatch.setattr(jax, "devices", lambda *a: list(topo.devices))
    cfg = dataclasses.replace(FLAGSHIP, n_layers=2)
    _, step = make_spmd_train_step(cfg, MeshSpec())
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0))))
    tokens = jax.ShapeDtypeStruct(FLAGSHIP_BATCH, jnp.int32,
                                  sharding=one_chip)
    compiled = step.lower(params, tokens, tokens).compile()
    _fits_one_chip(compiled)


def test_allreduce_64mb_compiles_to_all_reduce(topo):
    """A 64 MB fp32 gradient bucket merged over a 4-chip mesh lowers to
    one all-reduce."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from brpc_tpu.parallel import collectives

    mesh = Mesh(np.array(topo.devices[:4]), ("x",))
    shape = (4, (64 << 20) // 4)  # row i: chip i's bucket
    x = jax.ShapeDtypeStruct(shape, jnp.float32,
                             sharding=NamedSharding(mesh, P("x")))
    compiled = collectives._allreduce_fn(
        mesh, "x", shape, "float32", "add").lower(x).compile()
    assert "all-reduce" in compiled.as_text()
    _fits_one_chip(compiled)


def test_table_batch_program_updates_in_place(one_chip):
    """TableService's batch program at the `kv.ycsb_b` cell's shape
    (u32[8388608, 250], 8.6 GB with lanes padded to 256) and its largest
    bucket: the table is aliased input to output, so the in-place update
    never holds a second copy, and the scatter and gather need under
    64 MiB of temporaries."""
    from brpc_tpu.rpc import table_service as ts

    records, words, field_words = 8388608, 250, 25
    compiled = ts.batch_program().lower(*ts.batch_arg_shapes(
        (records, words), field_words, ts.BUCKETS[-1], one_chip)).compile()
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= records * words * 4
    assert "input_output_alias={ {0}: (0, {}" in compiled.as_text()
    assert m.temp_size_in_bytes < 64 << 20
    _fits_one_chip(compiled)
