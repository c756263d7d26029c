"""Fused TPU kernels — the Pallas equivalent of apex's ``csrc`` extensions.

* ``arena`` — flatten/unflatten tensor lists into flat HBM arenas (``apex_C``).
* ``multi_tensor`` — the multi-tensor-apply family (``amp_C``): scale, axpby,
  l2norm, adam, sgd, lamb, novograd, adagrad, lars, with device-side overflow
  semantics.
* ``normalization`` — fused LayerNorm/RMSNorm incl. mixed-dtype-output variants
  (``fused_layer_norm_cuda``).
* ``softmax`` — the scaled/masked softmax family (4 megatron kernels).
* ``dense`` — fused dense / GELU-epilogue dense / whole-MLP chains
  (``fused_dense_cuda``, ``mlp_cuda``) — XLA-epilogue-fused by construction.
* ``attention`` — Pallas flash attention (``fmhalib``, ``fast_multihead_attn``).
* ``gated_delta`` — the gated delta rule of Gated DeltaNet linear attention,
  chunk-wise, with a Pallas chunk scan (no reference equivalent).
* ``kda`` — the same rule under a decay a key CHANNEL (Kimi Delta Attention): the
  in-chunk scores carry the decay inside their contraction, so the lower triangle
  is cut by halves and each level is one product whose exponents are all <= 0;
  two Pallas kernels, one a pass: each makes a grid step's chunk factors in VMEM
  and walks the chunk scan over them.
* ``deltanet`` — a gated-DeltaNet layer outside its recurrence as two fused
  Pallas passes, each with its backward kernel: convolution, SiLU, L2 norms,
  the key heads' repetition and the heads-first layout; the gated output norm.
* ``short_conv`` — the double-gated short convolution of an LFM2 mixer
  (``C * conv_K(B * x~)``, no activation) as one fused Pallas pass forward and one
  backward, the filter's gradient accumulated over the grid.
* ``ssd`` (``ops.state_space_dual`` is ``ops.ssd.ssd``) — the state-space dual
  of a Mamba-2 mixer, chunk-wise: ``B`` and ``C`` shared by a group's heads, a
  decay a head, the state carried across chunks in VMEM by Pallas kernels
  forward and backward (no reference equivalent).
* ``grouped_matmul`` — rows sorted by expert times each expert's weight panel,
  the panel held in VMEM while its row tiles go by (Pallas; ``jax.lax.ragged_dot``
  off the kernels' shapes): the experts of ``moe/dropless.py``.
* ``segment_sum`` — rows summed onto their tokens without a scatter-add: the landed
  rows listed by tile of tokens, then ``onehot @ chunk`` on the MXU a tile at a time
  (Pallas): the two sums of ``moe/dropless.py`` on the TPU.
* ``quantized`` — fp8-style quantized matmul with per-tensor delayed scaling
  (the O6 tier; no reference equivalent — Transformer-Engine-shaped departure).
"""

from .arena import (  # noqa: F401
    ArenaSpec,
    PackedParams,
    flatten,
    make_spec,
    unflatten,
)
from .multi_tensor import (  # noqa: F401
    adam_flat,
    lamb_flat,
    sgd_flat,
    multi_tensor_adagrad,
    multi_tensor_adam,
    multi_tensor_axpby,
    multi_tensor_l2norm,
    multi_tensor_lamb,
    multi_tensor_lars,
    multi_tensor_novograd,
    multi_tensor_scale,
    multi_tensor_sgd,
)
from .normalization import (  # noqa: F401
    fused_layer_norm,
    fused_rms_norm,
    mixed_dtype_fused_layer_norm,
    mixed_dtype_fused_rms_norm,
)
from .softmax import (  # noqa: F401
    generic_scaled_masked_softmax,
    scaled_masked_softmax,
    scaled_softmax,
    scaled_upper_triang_masked_softmax,
)
from .dense import (  # noqa: F401
    fused_dense,
    fused_dense_gelu_dense,
    init_mlp_params,
    mlp,
)
from .attention import (  # noqa: F401
    flash_attention,
    is_flash_available,
    self_attention,
)
from .indexer import index_select  # noqa: F401
from .gated_delta import gated_delta_rule  # noqa: F401
from .kda import kda_rule  # noqa: F401
from .deltanet import deltanet_gate, deltanet_qkv  # noqa: F401
from .short_conv import gated_short_conv  # noqa: F401
from .ssd import ssd as state_space_dual  # noqa: F401  (``ops.ssd`` stays the module)
from .quantized import (  # noqa: F401
    quantized_matmul,
    quantized_matmul_error_bound,
    quantized_scope,
)
