"""ResNet-50 v1.5 (He et al. 2015; torchvision's ``resnet50``) in plain float32.

Written from the published description: 7x7/2 stem convolution, BatchNorm,
ReLU, 3x3/2 max pool, four stages of bottleneck blocks (1x1, 3x3, 1x1 with a
4x expansion; the stride sits on the 3x3 as in torchvision; a 1x1 projection
where shape changes), global average pool, one linear layer. BatchNorm in
training mode: batch mean and biased variance over N, H, W, eps 1e-5. Input is
uint8 NHWC, normalised by the ImageNet channel statistics as the training
recipe does. Nothing is imported from the program.

Weights are a flat ``{name: array}`` dict with torchvision's names
(``layer1.0.conv1``, ``layer1.0.bn1.scale``, ``fc.w`` ...), convolutions HWIO.

Departures, of memory and program size, not of values: each bottleneck is
recomputed in the backward pass (``jax.checkpoint``) so that float32 activations
of 256 images fit one chip, and a stage's blocks after its first, which are
alike, are one scanned body.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import precision as prec

STACKED_PREFIX = None
_MEAN = np.array([0.485, 0.456, 0.406], np.float32) * 255.0
_STD = np.array([0.229, 0.224, 0.225], np.float32) * 255.0


def batch_norm(x, scale, bias, eps=1e-5):
    mean = jnp.mean(x, (0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), (0, 1, 2))
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def _bn(w, name, x):
    return batch_norm(x, w[name + ".scale"], w[name + ".bias"])


def bottleneck(b, x, stride, mode):
    """One block; ``b`` holds its tensors by their names inside the block."""
    y = jax.nn.relu(_bn(b, "bn1", prec.conv_nhwc(x, b["conv1"], 1, mode)))
    y = jax.nn.relu(_bn(b, "bn2", prec.conv_nhwc(y, b["conv2"], stride, mode)))
    y = _bn(b, "bn3", prec.conv_nhwc(y, b["conv3"], 1, mode))
    if "downsample_conv" in b:
        x = _bn(b, "downsample_bn", prec.conv_nhwc(x, b["downsample_conv"], stride, mode))
    return jax.nn.relu(y + x)


def _block(w, name):
    return {k[len(name) + 1:]: v for k, v in w.items() if k.startswith(name + ".")}


def max_pool_3x3_s2(x):
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
        ((0, 0), (1, 1), (1, 1), (0, 0)))


def logits(w, images, stages, mode="float32"):
    x = (images.astype(jnp.float32) - _MEAN) / _STD
    x = jax.nn.relu(_bn(w, "bn1", prec.conv_nhwc(x, w["conv1"], 2, mode)))
    x = max_pool_3x3_s2(x)
    for i, n_blocks in enumerate(stages):
        first = jax.checkpoint(functools.partial(
            bottleneck, stride=2 if i > 0 else 1, mode=mode))
        x = first(_block(w, f"layer{i + 1}.0"), x)
        # the stage's other blocks are alike: one body, scanned over their stacked tensors
        rest = [_block(w, f"layer{i + 1}.{j}") for j in range(1, n_blocks)]
        if rest:
            body = jax.checkpoint(functools.partial(bottleneck, stride=1, mode=mode))
            x, _ = jax.lax.scan(lambda x, b: (body(b, x), None), x,
                                jax.tree.map(lambda *t: jnp.stack(t), *rest))
    x = jnp.mean(x, (1, 2))
    return prec.matmul(x, w["fc.w"], mode) + w["fc.b"]


def loss(w, batch, cfg, mode="float32"):
    """Mean softmax cross entropy of ``(uint8 images, labels)``."""
    images, labels = batch
    logp = jax.nn.log_softmax(logits(w, images, cfg["layers"], mode), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))
