"""Tensor + sequence parallelism (ref: apex/transformer/tensor_parallel/)."""

from beforeholiday_tpu.transformer.tensor_parallel.collective import (  # noqa: F401
    all_gather_matmul,
    collective_matmul_enabled,
    set_collective_matmul,
)
from beforeholiday_tpu.transformer.tensor_parallel.cross_entropy import (  # noqa: F401
    vocab_parallel_cross_entropy,
)
from beforeholiday_tpu.transformer.tensor_parallel.data import broadcast_data  # noqa: F401
from beforeholiday_tpu.transformer.tensor_parallel.layers import (  # noqa: F401
    column_parallel_linear,
    row_parallel_linear,
    vocab_parallel_embedding,
    vocab_range,
)
from beforeholiday_tpu.transformer.tensor_parallel.mappings import (  # noqa: F401
    copy_to_tensor_model_parallel_region,
    gather_from_sequence_parallel_region,
    gather_from_tensor_model_parallel_region,
    reduce_from_tensor_model_parallel_region,
    reduce_scatter_to_sequence_parallel_region,
    scatter_to_sequence_parallel_region,
    scatter_to_tensor_model_parallel_region,
)
from beforeholiday_tpu.transformer.tensor_parallel.random import (  # noqa: F401
    checkpoint,
    checkpoint_apply,
    data_parallel_seed,
    model_parallel_seed,
)
