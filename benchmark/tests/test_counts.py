"""The operation counts and parameter counts against hand sums."""

from benchmark import run
from benchmark.families import gpt, resnet


def test_gpt2_medium_parameters_and_operations():
    cfg = run.load("configs", "gpt2-medium")
    D, L, V, S, F = 1024, 24, 50304, 1024, 4096
    per_layer = (D * 3 * D + 3 * D) + (D * D + D) + (D * F + F) + (F * D + D) + 4 * D
    assert gpt.param_count(cfg) == V * D + S * D + L * per_layer + 2 * D == 354_871_296
    matmul_params = L * 12 * D * D + V * D          # tied head once, no position table
    causal_attention = 6 * S * D * L                # (2 fwd + 4 bwd products) * 2*S*D / 2
    assert gpt.attention_flops_per_item(cfg) == causal_attention == 150_994_944
    assert gpt.model_flops_per_item(cfg) == 6 * matmul_params + causal_attention == 2_272_002_048


def test_resnet50_parameters_and_multiply_adds():
    cfg = run.load("configs", "resnet50")
    assert resnet.param_count(cfg) == 25_557_032           # torchvision's resnet50
    # stem 7*7*3*64 at 112^2, then the four stages by hand
    stem = 7 * 7 * 3 * 64 * 112 * 112
    def stage(cin, mid, n, hw_in, stride):
        hw = hw_in // stride
        first = cin * mid * hw_in ** 2 + 9 * mid * mid * hw ** 2 + mid * 4 * mid * hw ** 2 \
            + cin * 4 * mid * hw ** 2
        rest = 4 * mid * mid * hw ** 2 + 9 * mid * mid * hw ** 2 + mid * 4 * mid * hw ** 2
        return first + (n - 1) * rest
    hand = stem + stage(64, 64, 3, 56, 1) + stage(256, 128, 4, 56, 2) \
        + stage(512, 256, 6, 28, 2) + stage(1024, 512, 3, 14, 2) + 2048 * 1000
    assert resnet.macs_per_item(cfg) == hand
    assert 4.08e9 < hand < 4.12e9                           # "4.1 GMACs" per 224^2 image
    assert resnet.model_flops_per_item(cfg) == 6 * hand     # a multiply-add counts two
