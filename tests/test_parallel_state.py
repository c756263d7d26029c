"""Mesh-layer tests (parity target: tests/L0/run_transformer/test_parallel_state.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec

from beforeholiday_tpu.parallel import parallel_state as ps


def test_initialize_and_destroy(devices8):
    state = ps.initialize_model_parallel(tensor_model_parallel_size=2,
                                         pipeline_model_parallel_size=2,
                                         devices=devices8)
    assert ps.model_parallel_is_initialized()
    assert state.tensor_model_parallel_size == 2
    assert state.pipeline_model_parallel_size == 2
    assert state.data_parallel_size == 2
    assert ps.get_mesh().shape == {"pipe": 2, "data": 2, "context": 1, "tensor": 2}
    ps.destroy_model_parallel()
    assert not ps.model_parallel_is_initialized()
    with pytest.raises(RuntimeError):
        ps.get_mesh()


@pytest.mark.parametrize("tp,pp", [(1, 1), (2, 1), (1, 2), (4, 2), (8, 1), (2, 4)])
def test_world_size_accounting(devices8, tp, pp):
    ps.initialize_model_parallel(tp, pp, devices=devices8)
    dp = 8 // (tp * pp)
    assert ps.get_tensor_model_parallel_world_size() == tp
    assert ps.get_pipeline_model_parallel_world_size() == pp
    assert ps.get_data_parallel_world_size() == dp


def test_indivisible_world_raises(devices8):
    with pytest.raises(RuntimeError):
        ps.initialize_model_parallel(3, 1, devices=devices8)


def test_virtual_pipeline_requires_pp(devices8):
    with pytest.raises(RuntimeError):
        ps.initialize_model_parallel(1, 1, virtual_pipeline_model_parallel_size=2,
                                     devices=devices8)
    st = ps.initialize_model_parallel(1, 2, virtual_pipeline_model_parallel_size=2,
                                      devices=devices8)
    assert st.virtual_pipeline_model_parallel_size == 2


def test_tensor_axis_is_innermost(devices8):
    """TP peers must be adjacent device ids — mirrors apex placing TP groups on
    consecutive ranks (ref: parallel_state.py:214-233)."""
    ps.initialize_model_parallel(2, 2, devices=devices8)
    mesh = ps.get_mesh()
    dev_ids = np.vectorize(lambda d: d.id)(mesh.devices)
    # first TP group = devices 0,1
    assert list(dev_ids[0, 0, 0, :]) == [0, 1]


def test_axis_index_inside_shard_map(devices8):
    """Rank getters return traced per-device ranks under shard_map."""
    ps.initialize_model_parallel(2, 2, devices=devices8)
    mesh = ps.get_mesh()

    def f(x):
        tp_r = ps.get_tensor_model_parallel_rank()
        pp_r = ps.get_pipeline_model_parallel_rank()
        dp_r = ps.get_data_parallel_rank()
        return x + tp_r + 10 * dp_r + 100 * pp_r

    x = jnp.zeros((8, 1), dtype=jnp.int32)
    out = shard_map(
        f, mesh=mesh,
        in_specs=PartitionSpec(("pipe", "data", "context", "tensor")),
        out_specs=PartitionSpec(("pipe", "data", "context", "tensor")),
    )(x)
    # device order (pp, dp, cp, tp): ranks 0..7 -> codes pp*100+dp*10+tp
    expected = jnp.array([[0], [1], [10], [11], [100], [101], [110], [111]],
                         dtype=jnp.int32)
    assert (out == expected).all()


def test_psum_over_data_axis(devices8):
    """An allreduce over the data axis == apex DDP's NCCL allreduce semantics."""
    ps.initialize_model_parallel(2, 1, devices=devices8)
    mesh = ps.get_mesh()

    def f(x):
        return jax.lax.psum(x, ps.DATA_AXIS)

    x = jnp.arange(8.0).reshape(8, 1)
    out = shard_map(
        f, mesh=mesh,
        in_specs=PartitionSpec(("pipe", "data", "context", "tensor")),
        out_specs=PartitionSpec(("pipe", "data", "context", "tensor")),
    )(x)
    # data axis has size 4 (tp=2): devices grouped as (dp, tp) = x[2*d + t]
    # psum over data sums x[t], x[2+t], x[4+t], x[6+t]
    expected = jnp.array([[0 + 2 + 4 + 6.0], [1 + 3 + 5 + 7.0]] * 4)
    assert jnp.allclose(out, expected)


def test_rank_info_host_side(devices8):
    ps.destroy_model_parallel()
    assert ps.get_rank_info() == (0, 0, 0, 0)
    ps.initialize_model_parallel(2, 1, devices=devices8)
    assert ps.get_rank_info() == (0, 0, 0, 0)
