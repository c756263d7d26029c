"""ResNet model parity vs a hand-built torch mirror (the role torchvision
plays for the reference, examples/imagenet/main_amp.py:135-140) plus
state-dict interop."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import torch
import torch.nn as nn

from beforeholiday_tpu.models import resnet


def _smap(f, **kw):
    kw["check_vma"] = False
    return jax.shard_map(f, **kw)


class TorchBasicBlock(nn.Module):
    def __init__(self, cin, cout, stride):
        super().__init__()
        self.conv1 = nn.Conv2d(cin, cout, 3, stride, 1, bias=False)
        self.bn1 = nn.BatchNorm2d(cout)
        self.conv2 = nn.Conv2d(cout, cout, 3, 1, 1, bias=False)
        self.bn2 = nn.BatchNorm2d(cout)
        self.downsample = None
        if stride != 1 or cin != cout:
            self.downsample = nn.Sequential(
                nn.Conv2d(cin, cout, 1, stride, bias=False), nn.BatchNorm2d(cout)
            )

    def forward(self, x):
        idn = x if self.downsample is None else self.downsample(x)
        y = torch.relu(self.bn1(self.conv1(x)))
        y = self.bn2(self.conv2(y))
        return torch.relu(y + idn)


class TorchTinyResNet(nn.Module):
    """Mirror of resnet.tiny_test_config(): stem 3x3/1 no pool, stages (1,1),
    widths (8,16), 10 classes."""

    def __init__(self, num_classes=10):
        super().__init__()
        self.conv1 = nn.Conv2d(3, 8, 3, 1, 1, bias=False)
        self.bn1 = nn.BatchNorm2d(8)
        self.layer1 = nn.Sequential(TorchBasicBlock(8, 8, 1))
        self.layer2 = nn.Sequential(TorchBasicBlock(8, 16, 2))
        self.fc = nn.Linear(16, num_classes)

    def forward(self, x):
        y = torch.relu(self.bn1(self.conv1(x)))
        y = self.layer2(self.layer1(y))
        y = y.mean(dim=(2, 3))
        return self.fc(y)


@pytest.fixture
def torch_and_jax():
    torch.manual_seed(0)
    tm = TorchTinyResNet()
    cfg = resnet.tiny_test_config()
    params, bn_state = resnet.from_torch_state_dict(cfg, tm.state_dict())
    return tm, cfg, params, bn_state


def _rand_images(n=4, hw=16, seed=3):
    return np.random.RandomState(seed).randn(n, hw, hw, 3).astype(np.float32)


class TestTorchParity:
    def test_eval_forward_matches(self, torch_and_jax):
        tm, cfg, params, bn_state = torch_and_jax
        x = _rand_images()
        tm.eval()
        with torch.no_grad():
            want = tm(torch.tensor(x).permute(0, 3, 1, 2)).numpy()
        got, _ = resnet.forward(params, bn_state, jnp.asarray(x), cfg, training=False)
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)

    def test_train_forward_and_running_stats_match(self, torch_and_jax):
        tm, cfg, params, bn_state = torch_and_jax
        x = _rand_images(8)
        tm.train()
        want = tm(torch.tensor(x).permute(0, 3, 1, 2)).detach().numpy()
        got, new_bn = resnet.forward(params, bn_state, jnp.asarray(x), cfg, training=True)
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)
        # running stats after one train step (momentum 0.1, unbiased var)
        np.testing.assert_allclose(
            np.asarray(new_bn["bn1"].running_mean),
            tm.bn1.running_mean.numpy(), rtol=1e-4, atol=1e-5,
        )
        np.testing.assert_allclose(
            np.asarray(new_bn["bn1"].running_var),
            tm.bn1.running_var.numpy(), rtol=1e-4, atol=1e-5,
        )

    def test_grads_match(self, torch_and_jax):
        tm, cfg, params, bn_state = torch_and_jax
        x = _rand_images(8)
        tm.train()
        out = tm(torch.tensor(x).permute(0, 3, 1, 2))
        (out**2).mean().backward()
        want_conv1 = tm.conv1.weight.grad.permute(2, 3, 1, 0).numpy()
        want_fc = tm.fc.weight.grad.permute(1, 0).numpy()

        def loss(p):
            logits, _ = resnet.forward(p, bn_state, jnp.asarray(x), cfg, training=True)
            return jnp.mean(logits**2)

        g = jax.grad(loss)(params)
        np.testing.assert_allclose(np.asarray(g["conv1"]), want_conv1, rtol=1e-3, atol=1e-4)
        np.testing.assert_allclose(np.asarray(g["fc"]["w"]), want_fc, rtol=1e-3, atol=1e-4)


class TestArchitecture:
    def test_resnet50_shapes(self):
        cfg = resnet.resnet50(num_classes=1000)
        params, bn_state = resnet.init(jax.random.PRNGKey(0), cfg)
        n = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
        # torchvision resnet50 has 25,557,032 params (incl. BN affine)
        assert n == 25_557_032, n
        logits, _ = jax.eval_shape(
            lambda p, s, x: resnet.forward(p, s, x, cfg, training=False),
            params, bn_state, jax.ShapeDtypeStruct((2, 224, 224, 3), jnp.float32),
        )
        assert logits.shape == (2, 1000)

    def test_resnet18_param_count(self):
        cfg = resnet.resnet18(num_classes=1000)
        params, _ = resnet.init(jax.random.PRNGKey(0), cfg)
        n = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
        assert n == 11_689_512, n  # torchvision resnet18

    def test_zero_init_residual(self):
        cfg = resnet.ResNetConfig(
            block="bottleneck", layers=(1,), width=8, num_classes=4,
            stem_kernel=3, stem_stride=1, stem_pool=False, zero_init_residual=True,
        )
        params, _ = resnet.init(jax.random.PRNGKey(0), cfg)
        assert float(jnp.abs(params["layer1"]["0"]["bn3"].scale).max()) == 0.0
        assert float(jnp.abs(params["layer1"]["0"]["bn1"].scale).max()) == 1.0

    def test_sync_bn_axis_threads_through(self, devices8):
        """forward(axis_name="data") inside shard_map == full-batch forward."""
        import functools
        from jax.sharding import Mesh, PartitionSpec as P

        cfg = resnet.tiny_test_config()
        params, bn_state = resnet.init(jax.random.PRNGKey(1), cfg)
        x = jnp.asarray(_rand_images(8))
        mesh = Mesh(np.asarray(devices8).reshape(8), ("data",))

        @functools.partial(
            _smap, mesh=mesh,
            in_specs=(P(), P(), P("data")), out_specs=(P("data"), P()),
        )
        def f(p, s, xs):
            return resnet.forward(p, s, xs, cfg, training=True, axis_name="data")

        y_sh, bn_sh = jax.jit(f)(params, bn_state, x)
        y_ref, bn_ref = resnet.forward(params, bn_state, x, cfg, training=True)
        np.testing.assert_allclose(np.asarray(y_sh), np.asarray(y_ref), rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(
            np.asarray(bn_sh["bn1"].running_var),
            np.asarray(bn_ref["bn1"].running_var), rtol=1e-4, atol=1e-5,
        )
