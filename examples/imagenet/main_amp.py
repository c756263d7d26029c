"""ImageNet ResNet trainer — TPU port of the reference flagship example
(ref: examples/imagenet/main_amp.py, the amp O0-O5 + DDP + SyncBN recipe and
BASELINE.md configs 1-3).

What maps where:

* ``torch.distributed.launch --nproc_per_node=N`` + per-process loops
  → ONE process, a ``Mesh(("data",))`` over all chips, the whole train step
  inside ``shard_map`` (batch sharded on ``data``, params replicated).
* ``amp.initialize(model, optimizer, opt_level)`` → the same call here
  (``beforeholiday_tpu.amp.initialize``), with BN running stats threaded as
  uncast model state (``has_state=True``).
* ``DDP(model, delay_allreduce=True)`` + ``amp.scale_loss`` backward hooks
  → ``scaled_value_and_grad(..., reduce_grads=ddp.reduce)``: psum of the
  still-scaled grads, then fused unscale + overflow detection, so every rank
  takes the same skip-step decision (the reference's hot-loop order).
* ``--sync_bn`` / ``convert_syncbn_model`` → ``axis_name="data"`` on the
  model's built-in SyncBN.
* the CUDA-stream ``data_prefetcher`` (main_amp.py:265-318) → device-side
  normalization fused into the jitted step; input pipeline is synthetic
  uint8 batches (no ImageNet on disk here).

Run: ``python examples/imagenet/main_amp.py -a resnet50 -b 128 --opt-level O5 --iters 50``
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import math
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_shard_map = functools.partial(jax.shard_map, check_vma=False)

from beforeholiday_tpu import amp
from beforeholiday_tpu.models import resnet
from beforeholiday_tpu.optimizers import FusedSGD
from beforeholiday_tpu.parallel import DistributedDataParallel, LARC
from beforeholiday_tpu.remat import donate_step
from beforeholiday_tpu.utils.compile_cache import enable_compile_cache

# ImageNet channel stats, in 0-255 space like the reference prefetcher
# (main_amp.py:269-270)
_MEAN = np.array([0.485, 0.456, 0.406], np.float32) * 255.0
_STD = np.array([0.229, 0.224, 0.225], np.float32) * 255.0


def softmax_cross_entropy(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean CE over the batch (ref: nn.CrossEntropyLoss, main_amp.py:176)."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def topk_accuracy(logits, labels, ks=(1, 5)):
    """Prec@k in percent (ref: main_amp.py ``accuracy``)."""
    k = max(ks)
    k = min(k, logits.shape[-1])
    _, top = jax.lax.top_k(logits.astype(jnp.float32), k)
    hit = top == labels[:, None]
    return {f"prec{q}": 100.0 * jnp.mean(jnp.any(hit[:, :min(q, k)], axis=1)) for q in ks}


@dataclasses.dataclass
class Trainer:
    """Bundle of jitted step functions + current training state."""

    cfg: resnet.ResNetConfig
    amp_model: Any
    train_step: Callable  # (state..., images, labels, lr) -> (state..., metrics)
    eval_step: Callable
    params: Any
    opt_state: Any
    scaler_state: Any
    bn_state: Any
    distributed: bool
    mesh: Optional[Mesh]
    global_batch: int

    def step(self, images, labels, lr):
        (self.params, self.opt_state, self.scaler_state, self.bn_state, metrics) = (
            self.train_step(
                self.params, self.opt_state, self.scaler_state, self.bn_state,
                images, labels, jnp.float32(lr),
            )
        )
        return metrics

    def evaluate(self, images, labels):
        return self.eval_step(self.params, self.bn_state, images, labels)

    def shard_batch(self, images: np.ndarray, labels: np.ndarray):
        if self.mesh is None:
            return jnp.asarray(images), jnp.asarray(labels)
        si = NamedSharding(self.mesh, P("data", None, None, None))
        sl = NamedSharding(self.mesh, P("data"))
        return jax.device_put(jnp.asarray(images), si), jax.device_put(
            jnp.asarray(labels), sl
        )


def build_trainer(
    arch: str = "resnet50",
    *,
    opt_level: str = "O0",
    lr: float = 0.1,
    momentum: float = 0.9,
    weight_decay: float = 1e-4,
    loss_scale: Optional[Any] = None,
    keep_batchnorm_fp32: Optional[bool] = None,
    sync_bn: bool = False,
    use_larc: bool = False,
    global_batch: int = 128,
    num_classes: int = 1000,
    distributed: Optional[bool] = None,
    devices: Optional[list] = None,
    seed: int = 0,
    cfg: Optional[resnet.ResNetConfig] = None,
    fused_optimizer: Optional[Any] = None,
    bucket_bytes: Optional[int] = None,
    compress: bool = False,
    overlap_backward: bool = False,
) -> Trainer:
    """Assemble model + amp + optimizer + (optionally) the data-parallel mesh.

    Mirrors main() setup order in the reference (main_amp.py:135-174):
    model → lr scaling by global_batch/256 → SGD → amp.initialize → DDP.
    """
    if devices is None:
        devices = jax.devices()
    if distributed is None:
        distributed = len(devices) > 1
    mesh = Mesh(np.asarray(devices), ("data",)) if distributed else None
    if distributed and global_batch % len(devices) != 0:
        raise ValueError(f"global batch {global_batch} not divisible by {len(devices)} devices")

    if cfg is None:
        cfg = resnet.CONFIGS[arch](num_classes=num_classes)
    params, bn_state = resnet.init(jax.random.PRNGKey(seed), cfg)

    # "Scale learning rate based on global batch size" (main_amp.py:150)
    lr = lr * float(global_batch) / 256.0
    opt = fused_optimizer or FusedSGD(lr, momentum, weight_decay=weight_decay)
    if use_larc:
        opt = LARC(opt)

    bn_axis = "data" if (sync_bn and distributed) else None

    def apply_train(p, bn, images):
        return resnet.forward(p, bn, images, cfg, training=True, axis_name=bn_axis)

    def apply_eval(p, bn, images):
        return resnet.forward(p, bn, images, cfg, training=False)

    # O2/O5 take the arena-native fast path (PackedParams: fp32 masters +
    # optimizer state live flat, grads born flat, master->model cast fused
    # into the optimizer pass — measured ~4-6 ms/step off the O5 ResNet-50
    # step at batch 128). This covers the distributed trainer too: its
    # shard_map replicates params (P() broadcasts over any pytree) and DDP's
    # grad psum maps over the gradient ARENAS exactly as it maps over leaves
    # — verified against the single-device oracle in
    # tests/test_imagenet_trainer.py. LARC / optimizers without a flat step
    # keep the list path.
    from beforeholiday_tpu.optimizers import supports_flat_step

    arena_native = (
        opt is not None
        and not use_larc
        and opt_level in ("O2", "O5")
        and supports_flat_step(opt)
    )
    amp_model = amp.initialize(
        apply_train, params, opt, opt_level,
        keep_batchnorm_fp32=keep_batchnorm_fp32, loss_scale=loss_scale,
        has_state=True, arena_native=arena_native,
    )
    # eval forward shares amp_model.params — just another cast wrapper
    eval_apply = amp.make_apply(amp_model.policy, apply_eval, has_state=True)
    optimizer = amp_model.optimizer
    scaler = amp_model.scaler

    ddp = (
        DistributedDataParallel(
            bucket_bytes=bucket_bytes,
            compress=compress,
            overlap_backward=overlap_backward,
        )
        if distributed
        else None
    )

    def normalize(images):
        # the prefetcher's sub_(mean).div_(std) fused into the step
        return (images.astype(jnp.float32) - _MEAN) / _STD

    def core_step(params, opt_state, scaler_state, bn_state, images, labels, lr):
        x = normalize(images)

        def loss_fn(p):
            if ddp is not None and ddp.overlap_backward:
                # backward-time reduction: hooked boundary makes each param
                # group's grad psum issue inside the backward itself (apex
                # delay_allreduce=False), so no post-backward sweep is needed
                p = ddp.hook(p)
            logits, new_bn = amp_model.apply(p, bn_state, x)
            return softmax_cross_entropy(logits, labels), (new_bn, logits)

        svag = amp.scaled_value_and_grad(
            loss_fn, scaler, has_aux=True,
            reduce_grads=(
                ddp.reduce
                if ddp is not None and not ddp.overlap_backward
                else None
            ),
        )
        loss, (new_bn, logits), grads, found_inf, new_scaler_state = svag(
            params, scaler_state
        )
        new_params, new_opt_state = optimizer.step(
            params, grads, opt_state, found_inf=found_inf, lr=lr
        )
        metrics = {"loss": loss, "found_inf": found_inf,
                   "scale": new_scaler_state["scale"], **topk_accuracy(logits, labels)}
        if ddp is not None:
            # metrics averaged across ranks like reduce_tensor (main_amp.py:378)
            metrics = {k: jax.lax.pmean(v, "data") for k, v in metrics.items()}
            if bn_axis is None:
                # Reference non-sync BN keeps per-rank buffers and an arbitrary
                # rank's copy gets checkpointed; SPMD keeps ONE canonical copy —
                # the cross-rank average (an unbiased estimate of the same stats).
                new_bn = jax.tree.map(
                    lambda s: jax.lax.pmean(s, "data"), new_bn
                )
        return new_params, new_opt_state, new_scaler_state, new_bn, metrics

    def core_eval(params, bn_state, images, labels):
        logits, _ = eval_apply(params, bn_state, normalize(images))
        m = {"loss": softmax_cross_entropy(logits, labels),
             **topk_accuracy(logits, labels)}
        if ddp is not None:
            m = {k: jax.lax.pmean(v, "data") for k, v in m.items()}
        return m

    # params/opt/scaler/BN state (args 0-3) are donated: Trainer.step rebinds
    # them from the outputs, so XLA may alias the update in place instead of
    # holding both copies of the largest buffers live across the step
    _donate = (0, 1, 2, 3)
    if distributed:
        rep = P()
        train_step = donate_step(_shard_map(
            core_step, mesh=mesh,
            in_specs=(rep, rep, rep, rep, P("data"), P("data"), rep),
            out_specs=(rep, rep, rep, rep, rep),
        ), donate_argnums=_donate)
        eval_step = jax.jit(_shard_map(
            core_eval, mesh=mesh,
            in_specs=(rep, rep, P("data"), P("data")),
            out_specs=rep,
        ))
    else:
        train_step = donate_step(core_step, donate_argnums=_donate)
        eval_step = jax.jit(core_eval)

    opt_state = optimizer.init(amp_model.params) if optimizer is not None else None
    return Trainer(
        cfg=cfg, amp_model=amp_model, train_step=train_step, eval_step=eval_step,
        params=amp_model.params, opt_state=opt_state, scaler_state=scaler.init(),
        bn_state=bn_state, distributed=distributed, mesh=mesh,
        global_batch=global_batch,
    )


def adjust_learning_rate(base_lr, epoch, step, steps_per_epoch):
    """Warmup over 5 epochs + /10 decay at 30/60/80 (ref: main_amp.py:440-457)."""
    factor = 0 if epoch < 30 else 1 if epoch < 60 else 2 if epoch < 80 else 3
    lr = base_lr * (0.1**factor)
    if epoch < 5:
        lr = lr * float(1 + step + epoch * steps_per_epoch) / (5.0 * steps_per_epoch)
    return lr


def synthetic_batches(global_batch, image_size, num_classes, n, seed=1234):
    """uint8 image batches + labels, standing in for the ImageFolder loader."""
    rng = np.random.RandomState(seed)
    for _ in range(n):
        yield (
            rng.randint(0, 256, (global_batch, image_size, image_size, 3), np.uint8),
            rng.randint(0, num_classes, (global_batch,), np.int64),
        )


def train(trainer: Trainer, *, iters: int, image_size: int = 224,
          base_lr: float = 0.1, print_freq: int = 10, epoch: int = 0,
          flight=None):
    """One synthetic 'epoch' of ``iters`` steps; prints reference-style lines."""
    num_classes = trainer.cfg.num_classes
    it = synthetic_batches(trainer.global_batch, image_size, num_classes, iters)
    scaled_lr = base_lr * trainer.global_batch / 256.0
    t_end = time.perf_counter()
    speeds = []
    last_print = 0
    for i, (images, labels) in enumerate(it):
        lr = adjust_learning_rate(scaled_lr, epoch, i, iters)
        images, labels = trainer.shard_batch(images, labels)
        metrics = trainer.step(images, labels, lr)
        if (i + 1) % print_freq == 0 or i == iters - 1:
            metrics = {k: float(v) for k, v in metrics.items()}  # host sync
            n_steps = (i + 1) - last_print
            last_print = i + 1
            dt = (time.perf_counter() - t_end) / n_steps
            t_end = time.perf_counter()
            speed = trainer.global_batch / dt
            speeds.append(speed)
            print(
                f"Epoch: [{epoch}][{i + 1}/{iters}]  Speed {speed:.1f} img/s  "
                f"Loss {metrics['loss']:.4f}  Prec@1 {metrics['prec1']:.2f}  "
                f"Prec@5 {metrics['prec5']:.2f}  scale {metrics['scale']:.0f}"
            )
            if flight is not None:
                # snapshot only the rows the print cadence already
                # host-synced — the recorder itself must not add readbacks
                flight.record(epoch * iters + i + 1, metrics)
    return max(speeds) if speeds else 0.0


def parse_args(argv=None):
    p = argparse.ArgumentParser(description="TPU ImageNet training (synthetic data)")
    p.add_argument("--arch", "-a", default="resnet50", choices=sorted(resnet.CONFIGS))
    p.add_argument("--batch-size", "-b", type=int, default=128,
                   help="GLOBAL batch size (the reference's is per-process)")
    p.add_argument("--lr", type=float, default=0.1)
    p.add_argument("--momentum", type=float, default=0.9)
    p.add_argument("--weight-decay", "--wd", type=float, default=1e-4)
    p.add_argument("--opt-level", default="O0",
                   choices=["O0", "O1", "O2", "O3", "O4", "O5"])
    p.add_argument("--keep-batchnorm-fp32", default=None,
                   type=lambda s: {"True": True, "False": False}[s])
    p.add_argument("--loss-scale", default=None,
                   type=lambda s: s if s == "dynamic" else float(s))
    p.add_argument("--sync_bn", action="store_true", help="SyncBN over the data axis")
    p.add_argument("--larc", action="store_true")
    p.add_argument("--iters", type=int, default=50, help="steps per epoch (synthetic)")
    p.add_argument("--epochs", type=int, default=1)
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--num-classes", type=int, default=1000)
    p.add_argument("--print-freq", "-p", type=int, default=10)
    p.add_argument("--deterministic", action="store_true")
    p.add_argument("--profile-dir", default=None,
                   help="write an XProf trace of one epoch here")
    p.add_argument("--flight-recorder", default=None, metavar="PATH",
                   help="keep a ring buffer of recent step metrics and dump "
                        "it (with guard/comms/compile counters) to PATH on "
                        "crash or exit")
    p.add_argument("--bucket-bytes", type=int, default=None,
                   help="coalesce gradient all-reduces into buckets of this "
                        "many bytes (apex allreduce_bucket_cap_mb)")
    p.add_argument("--compress", action="store_true",
                   help="all-reduce gradients in bf16 with fp32 accumulation")
    p.add_argument("--overlap-backward", action="store_true",
                   help="issue each bucket's all-reduce inside the backward "
                        "pass as its grads are produced (apex "
                        "delay_allreduce=False) instead of one post-backward "
                        "sweep")
    return p.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    enable_compile_cache()
    print(f"opt_level = {args.opt_level}")
    print(f"keep_batchnorm_fp32 = {args.keep_batchnorm_fp32}")
    print(f"loss_scale = {args.loss_scale}")
    trainer = build_trainer(
        args.arch, opt_level=args.opt_level, lr=args.lr, momentum=args.momentum,
        weight_decay=args.weight_decay, loss_scale=args.loss_scale,
        keep_batchnorm_fp32=args.keep_batchnorm_fp32, sync_bn=args.sync_bn,
        use_larc=args.larc, global_batch=args.batch_size,
        num_classes=args.num_classes,
        seed=0 if args.deterministic else int(time.time()) % (2**31),
        bucket_bytes=args.bucket_bytes, compress=args.compress,
        overlap_backward=args.overlap_backward,
    )
    print(f"devices: {jax.device_count()}  distributed: {trainer.distributed}")
    from beforeholiday_tpu.monitor.spans import trace as profile_trace

    flight = None
    if args.flight_recorder:
        from beforeholiday_tpu.monitor import FlightRecorder

        # context-managed below: arms a crash dump (uncaught exception →
        # ring dumped to PATH with counters + loss-scale trajectory) and
        # dumps on exception exit too
        flight = FlightRecorder(path=args.flight_recorder)

    import contextlib

    best = 0.0
    with (flight if flight is not None else contextlib.nullcontext()):
        for epoch in range(args.epochs):
            # trace exactly one epoch (the first), as the flag promises —
            # tracing a whole multi-epoch run accumulates unloadable multi-GB
            # profiles
            with profile_trace(args.profile_dir if epoch == 0 else None):
                best = max(best, train(
                    trainer, iters=args.iters, image_size=args.image_size,
                    base_lr=args.lr, print_freq=args.print_freq, epoch=epoch,
                    flight=flight,
                ))
    if flight is not None:
        # the context manager dumps on exception; a clean run still writes
        # the ring so the knob always leaves the file it promised
        flight.dump(reason="run_end")
    print(f"peak speed: {best:.1f} img/s")
    return best


if __name__ == "__main__":
    main()
