"""Family ``resnet``: a bottleneck ResNet through the source paper's ImageNet recipe.

The step is ``examples/imagenet/main_amp.py:build_trainer``'s own (amp O5
arena-native + ``FusedSGD``, uint8 input normalised inside the step). That
function takes no weights, so the trainer is built and its state is then made
again, through ``amp.initialize`` and the trainer's optimizer, from the weights
the benchmark makes from the seed.
"""

import importlib.util
import math
import os
import sys

import jax
import jax.numpy as jnp

from benchmark.reference import optim
from benchmark.reference.precision import as_bfloat16_values
from benchmark.reference import resnet as reference  # noqa: F401  (the family's plain reference)

ITEMS_PER_ROW = None                # a row of the batch is one image
GUARDED_OPS = ()                    # no Pallas kernel on this path
_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _convs(cfg):
    """``(name, kernel, cin, cout, stride, out_hw)`` of every convolution."""
    hw = cfg["image_size"] // 2
    out = [("conv1", 7, 3, cfg["width"], 2, hw)]
    hw //= 2                                            # the 3x3/2 max pool
    cin = cfg["width"]
    for i, n_blocks in enumerate(cfg["layers"]):
        mid = cfg["width"] * 2 ** i
        for j in range(n_blocks):
            stride = 2 if (j == 0 and i > 0) else 1
            name, out_hw = f"layer{i + 1}.{j}", hw // stride
            out += [(name + ".conv1", 1, cin, mid, 1, hw),
                    (name + ".conv2", 3, mid, mid, stride, out_hw),
                    (name + ".conv3", 1, mid, 4 * mid, 1, out_hw)]
            if stride != 1 or cin != 4 * mid:
                out.append((name + ".downsample_conv", 1, cin, 4 * mid, stride, out_hw))
            cin, hw = 4 * mid, out_hw
    return out


def weight_shapes(cfg):
    """``{name: (shape, init)}``, torchvision's names; a convolution's init is its std."""
    out = {}
    for name, k, cin, cout, _, _ in _convs(cfg):
        out[name] = ((k, k, cin, cout), math.sqrt(2.0 / (k * k * cout)))
        bn = name.replace("conv", "bn") if "downsample" not in name else name.replace("_conv", "_bn")
        out[bn + ".scale"], out[bn + ".bias"] = ((cout,), "one"), ((cout,), "zero")
    fan_in = 4 * cfg["width"] * 2 ** (len(cfg["layers"]) - 1)
    out["fc.w"] = ((fan_in, cfg["num_classes"]), "uniform")
    out["fc.b"] = ((cfg["num_classes"],), "uniform")
    return out


def param_count(cfg):
    return sum(math.prod(shape) for shape, _ in weight_shapes(cfg).values())


def weights(cfg, key):
    """Seeded float32 weights, every value exactly a bfloat16, initialised as
    torchvision does: Kaiming-normal fan-out convolutions, BatchNorm 1 / 0,
    the linear layer uniform in +-1/sqrt(fan in). Traceable."""
    shapes = weight_shapes(cfg)
    bound = 1.0 / math.sqrt(shapes["fc.w"][0][0])
    out = {}
    for i, (name, (shape, init)) in enumerate(sorted(shapes.items())):
        k = jax.random.fold_in(key, i)
        if init == "uniform":
            w = jax.random.uniform(k, shape, jnp.float32, -bound, bound)
        elif isinstance(init, float):
            w = jax.random.normal(k, shape, jnp.float32) * init
        else:
            w = jnp.full(shape, 1.0 if init == "one" else 0.0, jnp.float32)
        out[name] = as_bfloat16_values(w)
    return out


def batch(cfg, rows, key):
    """``rows`` seeded uint8 images and labels. Traceable."""
    ki, kl = jax.random.split(key)
    size = cfg["image_size"]
    images = jax.random.randint(ki, (rows, size, size, 3), 0, 256, jnp.int32).astype(jnp.uint8)
    return images, jax.random.randint(kl, (rows,), 0, cfg["num_classes"], jnp.int32)


def macs_per_item(cfg):
    """Multiply-adds of one image's forward pass: every convolution and the linear layer."""
    conv = sum(k * k * cin * cout * hw * hw for _, k, cin, cout, _, hw in _convs(cfg))
    return conv + math.prod(weight_shapes(cfg)["fc.w"][0])


def model_flops_per_item(cfg):
    """Forward and backward, a multiply-add counted as two operations."""
    return 3 * 2 * macs_per_item(cfg)


def _lr(cfg, cell):
    return cfg["optimizer"]["lr_per_256"] * cell["per_chip_batch"] * cell["chips"] / 256.0


def reference_optimizer(cfg, cell):
    opt = cfg["optimizer"]
    hyper = dict(lr=_lr(cfg, cell), momentum=opt["momentum"], weight_decay=opt["weight_decay"])
    return optim.sgd_init, lambda p, g, s: optim.sgd_step(p, g, s, **hyper)


def _to_tree(flat):
    """The program's parameter tree: nested dicts, BatchNorm as its own pair type."""
    from beforeholiday_tpu.parallel.sync_batch_norm import BatchNormParams

    tree = {}
    for name in sorted(flat):
        *path, last = name.split(".")
        if last == "bias" and "bn" in path[-1]:
            continue
        node = tree
        for part in path[:-1] if last == "scale" else path:
            node = node.setdefault(part, {})
        if last == "scale":
            node[path[-1]] = BatchNormParams(flat[name], flat[name[:-5] + "bias"])
        else:
            node[last] = flat[name]
    return tree


def _to_flat(tree):
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        flat[".".join(str(getattr(p, "key", getattr(p, "name", p))) for p in path)] = leaf
    return flat


def _main_amp():
    """The recipe's script as a module; it is not in a package, so by its path."""
    name = "benchmark_main_amp"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(_REPO, "examples", "imagenet", "main_amp.py"))
        sys.modules[name] = module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
    return sys.modules[name]


class Program:
    """``build_trainer``'s compiled step, the program that makes its state, and
    views of that state for the check. Building one does no device work."""

    def __init__(self, cfg, cell, weights_of_seed, devices, mesh):
        from beforeholiday_tpu import amp
        from beforeholiday_tpu.models import resnet

        if cell["layout"] != "single":
            raise ValueError("family resnet is wired for layout 'single' only")
        opt = cfg["optimizer"]
        rcfg = resnet.ResNetConfig(
            block="bottleneck", layers=tuple(cfg["layers"]), width=cfg["width"],
            num_classes=cfg["num_classes"])
        self._wd = opt["weight_decay"]
        built = {}

        def make_state(seed):
            """From the seed to the whole training state, in one program (jit
            it). The trainer is built under its trace, so the weights it makes
            for itself, leaf by leaf, cost nothing and are dropped."""
            t = built["trainer"] = _main_amp().build_trainer(
                opt_level=cfg["opt_level"], lr=opt["lr_per_256"],
                momentum=opt["momentum"], weight_decay=opt["weight_decay"],
                global_batch=cell["per_chip_batch"], distributed=False,
                devices=list(devices[:1]), cfg=rcfg)
            params = amp.initialize(
                t.amp_model.apply, _to_tree(weights_of_seed(seed)), None,
                cfg["opt_level"], has_state=True, arena_native=True).params
            return (params, t.amp_model.optimizer.init(params),
                    t.amp_model.scaler.init(), t.bn_state)

        lr = _lr(cfg, cell)

        def step(state, batch):
            # the trainer's own donating step, made by make_state, which runs first
            if "lr" not in built:
                built["lr"] = jax.device_put(jnp.float32(lr), devices[0])
            *state, metrics = built["trainer"].train_step(*state, *batch, built["lr"])
            return tuple(state), metrics["loss"], metrics["found_inf"]

        self.make_state, self.step = make_state, step

    @staticmethod
    def _leaves(arenas, state):
        from beforeholiday_tpu.ops.arena import PackedParams

        return _to_flat(PackedParams(arenas, state[0].layout).unpack())

    def masters(self, state):
        return self._leaves(state[1]["master"], state)

    def first_gradient(self, state, initial):
        """From the momentum buffer after one step, which is the first decayed
        gradient: g = buffer - weight_decay * initial weights."""
        bufs = tuple(s["momentum_buffer"] for s in state[1]["inner"])
        return {k: v - self._wd * initial[k] for k, v in self._leaves(bufs, state).items()}

    def replicas_disagree(self, state):
        return False
