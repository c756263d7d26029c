"""The deferred on-chip rungs must be registered and skip cleanly off-TPU.

ROADMAP item 2 deferred four measurements to real hardware: the O6 GPT MFU
rung, the O6-vs-O5 step ratio, the S=8192 flash backward, and the
collective-matmul overlap win. This suite pins the CPU-container half of
that contract: all four rungs exist in ``tpu_checks.RUNGS``, each is
callable with no arguments, and on a CPU backend each returns a
``{"skipped": reason}`` dict WITHOUT touching the device — so the next
``python -m beforeholiday_tpu.testing.tpu_checks`` run on a real chip
measures them with no further wiring.
"""

import json

import jax
import pytest

from beforeholiday_tpu.testing import tpu_checks

EXPECTED = {
    "gpt_o6_mfu",
    "o6_vs_o5_step",
    "flash_bwd_s8192",
    "collective_matmul_overlap",
}


def test_all_deferred_rungs_are_registered():
    assert EXPECTED <= set(tpu_checks.RUNGS)
    for name, fn in tpu_checks.RUNGS.items():
        assert callable(fn)
        assert fn.__name__ == name  # the registry key IS the function name
        assert fn.__doc__  # each rung documents what it measures


@pytest.mark.skipif(
    jax.default_backend() == "tpu",
    reason="this pins the OFF-chip contract; on TPU the rungs measure",
)
def test_rungs_skip_cleanly_on_cpu():
    for name in EXPECTED:
        out = tpu_checks.RUNGS[name]()
        assert isinstance(out, dict), name
        assert set(out) == {"skipped"}, (name, out)
        assert "tpu" in out["skipped"].lower(), (name, out)


def test_rung_decorator_registers():
    @tpu_checks.rung
    def _probe_rung():
        return {"skipped": "test probe"}

    try:
        assert tpu_checks.RUNGS["_probe_rung"] is _probe_rung
    finally:
        del tpu_checks.RUNGS["_probe_rung"]


def test_the_grouped_matmul_check_runs_its_comparison(monkeypatch):
    """The chip check at a toy shape on the interpreter: every output and
    cotangent is compared (the timings mean nothing here and are not judged)."""
    import jax.numpy as jnp

    monkeypatch.setattr(tpu_checks, "_GROUPED_SHAPES",
                        (("toy", 512, 4, 128, 256, 300, jnp.float32),))
    results = []
    tpu_checks.check_grouped_matmul(results)
    by_name = {name: (ok, info) for name, ok, info in results}
    assert set(by_name) == {f"grouped_matmul/toy/{k}" for k in
                            ("fwd", "dlhs", "drhs", "ms_a_product")}
    for k in ("fwd", "dlhs", "drhs"):
        assert by_name[f"grouped_matmul/toy/{k}"][0], by_name


def test_the_ssd_check_runs_its_comparison(monkeypatch):
    """The chip check of ``ops.ssd`` at a toy shape on the interpreter: the
    output and every cotangent are compared (the timings are not judged here)."""
    monkeypatch.setattr(tpu_checks, "_SSD_SHAPE", (1, 256, 2, 64, 1, 128))
    results = []
    tpu_checks.check_ssd(results)
    by_name = {name: (ok, info) for name, ok, info in results}
    compared = ("fwd", "dx", "ddt", "dA", "dB", "dC", "dD")
    assert set(by_name) == {f"ssd/{k}" for k in compared + ("ms_a_block",)}
    for k in compared:
        assert by_name[f"ssd/{k}"][0], by_name


def test_the_moe_rows_check_runs_its_comparison(monkeypatch):
    """The chip check of the sort's two sides at a toy shape on the CPU: the four
    row movements of a layer against plain indexing at three fills, the tail
    NaN, as loops and with the two sums by token — taken here through the
    interpreter, where ``token_order`` would keep the loop (the timings are not
    judged here)."""
    from beforeholiday_tpu.moe import dropless
    from beforeholiday_tpu.ops import segment_sum as seg

    monkeypatch.setattr(dropless, "_row_tile", lambda D: 256)
    monkeypatch.setattr(dropless, "token_order", lambda *a, **kw: seg.token_order(
        *a, **{**kw, "impl": "pallas"}))
    results = []
    tpu_checks.check_moe_rows(results, shapes=(("toy", 64, 512, 128, 300),), tiles=(64,),
                              plans=((128, 128),))
    by_name = {name: (ok, info) for name, ok, info in results}
    moved = ("dispatch_fwd", "dispatch_bwd", "combine_fwd", "combine_bwd")
    fills = ("cell", "eighth", "full")
    assert set(by_name) == {
        f"moe_rows/toy/{form}/{fill}/{m}" for form in ("loop", "by_token") for fill in fills
        for m in moved} | {f"moe_rows/toy/{form}/{fill}/ms" for form in ("256", "by_token")
                           for fill in fills} | {"moe_rows/toy/ms_a_layer",
                                                 "moe_rows/toy/two_sums_ms"}
    for name, (ok, info) in by_name.items():
        assert ok or name.endswith(("ms_a_layer", "two_sums_ms")), (name, info)
    timed = json.loads(by_name["moe_rows/toy/by_token/cell/ms"][1])
    assert set(timed) == set(moved) | {"layer", "token_order", "into_token_order",
                                       "sum_kernel", "sum_kernel_scaled"}
    assert {"one_shot_cell", "64_full", "256_eighth", "by_token_full",
            "by_token_128x128_cell"} <= set(json.loads(by_name["moe_rows/toy/ms_a_layer"][1]))


@pytest.mark.parametrize("check", ("check_ssd", "check_moe_rows"))
def test_the_check_is_one_of_the_groups_main_runs(check):
    import inspect

    assert check in inspect.getsource(tpu_checks.main)


def test_the_short_conv_check_runs_its_comparison():
    """The chip check of ``ops.short_conv`` at a toy shape on the interpreter:
    the output and both cotangents are compared with the chain's (the timings
    are not judged here), and ``main`` runs the group."""
    import inspect

    results = []
    tpu_checks.check_short_conv(results, S=128, D=128)
    by_name = {name: (ok, info) for name, ok, info in results}
    compared = ("parity/y", "parity/dbcx", "parity/dw")
    assert set(by_name) == {f"short_conv/{k}" for k in
                            compared + ("ms_and_gbps_a_layer", "fwd_bwd_ms_a_layer")}
    for k in compared:
        assert by_name[f"short_conv/{k}"][0], by_name
    assert "check_short_conv" in inspect.getsource(tpu_checks.main)


def test_the_flash_mla_check_runs_its_comparison():
    """The chip check of the two-width flash kernels at a toy length on the
    interpreter: the output and the three cotangents at 192 / 128 are compared
    with the jnp path's (the timings are not judged here), each grid block is
    read through a ladder that is put back, and ``main`` runs the group."""
    import inspect
    import json

    from beforeholiday_tpu.ops import attention as A

    ladder, results = A._block_size, []
    tpu_checks.check_flash_mla(results, H=2, S=256, blocks=(128, 256))
    by_name = {name: (ok, info) for name, ok, info in results}
    compared = ("parity/o", "parity/dq", "parity/dk", "parity/dv", "widths")
    assert set(by_name) == {f"flash_mla/{k}" for k in compared + ("ms_a_layer_by_block",)}
    for k in compared:
        assert by_name[f"flash_mla/{k}"][0], by_name
    read = json.loads(by_name["flash_mla/ms_a_layer_by_block"][1])
    assert sorted(read) == ["128", "256"] and all(isinstance(v, dict) for v in read.values()), read
    assert A._block_size is ladder and A._tile_plan(8192, 8192, 192, True, None, 128).bq == 1024
    assert "check_flash_mla" in inspect.getsource(tpu_checks.main)


def test_the_flash_fused_check_runs_its_comparison():
    """The chip check of the fused backward of several blocks at toy shapes on
    the interpreter (3 x 3 blocks of 128): the plan is the fused one, dq, dk and
    dv are compared with the dq + dkv pair's on the same residuals — a windowed
    head's on the band's grid too (PR 48) — the four 8k cells' calls and the
    Mellum band are what it times by default (the timings are not judged here),
    and ``main`` runs the group."""
    import inspect
    import json

    results = []
    tpu_checks.check_flash_fused(
        results, parity=((2, 384, 192, 128), (2, 384, 64, 64), (2, 640, 64, 64, 200)),
        timed=((1, 384, 64, 64), (1, 640, 64, 64, 200)))
    by_name = {name: (ok, info) for name, ok, info in results}
    compared = {f"flash_fused/{shape}/{k}"
                for shape in ("s384_d192_128", "s384_d64", "s640_d64_w200")
                for k in ("plan", "dq", "dk", "dv")}
    timed = {"flash_fused/ms_a_layer/1x384x64", "flash_fused/ms_a_layer/1x640x64_w200"}
    assert set(by_name) == compared | timed
    for name in compared:
        assert by_name[name][0], (name, by_name[name])
    assert "3 x 3 blocks of 128" in by_name["flash_fused/s384_d64/plan"][1]
    # a windowed head (PR 48): the same one call on the band's grid
    assert "5 x 5 blocks of 128, a band of 3" in by_name["flash_fused/s640_d64_w200/plan"][1]
    for name in timed:
        assert sorted(json.loads(by_name[name][1])) == ["fused", "two_calls"]
    assert tpu_checks._FUSED_SHAPES == ((32, 8192, 192, 128), (32, 8192, 64, 64),
                                        (32, 8192, 128, 128), (16, 8192, 256, 256))
    assert tpu_checks._BAND_SHAPES[0] == (32, 8192, 128, 128, 1024)      # the Mellum cell's
    assert "check_flash_fused" in inspect.getsource(tpu_checks.main)


def test_the_flash_fwd_live_check_runs_its_comparison():
    """The chip check of the causal forward's live axis at toy shapes on the
    interpreter (3 x 3 blocks of 128): ``o`` and ``lse`` under the parent's maps,
    the clamp and the live axis agree bit for bit, as do dq, dk and dv of the
    dq + dkv pair under the parent's maps and the clamp; the three forwards and
    the two pairs are what it times (not judged here), the four 8k cells' calls
    are its default, the module's maps are put back, and ``main`` runs the group."""
    import inspect
    import json

    from beforeholiday_tpu.ops import attention as A

    live_axis, maps, results = A.TilePlan.live_axis, A._block_maps, []
    tpu_checks.check_flash_fwd_live(results, timed=((2, 384, 192, 128), (1, 384, 64, 64)),
                                    two_calls=(1, 384, 64, 64))
    by_name = {name: (ok, info) for name, ok, info in results}
    assert set(by_name) == {f"flash_fwd_live/{k}" for k in (
        "2x384x192_128/bit_for_bit", "2x384x192_128/fwd_ms_a_layer", "1x384x64/bit_for_bit",
        "1x384x64/fwd_ms_a_layer", "1x384x64/two_calls_bit_for_bit",
        "1x384x64/two_calls_ms_a_layer")}
    for name, (ok, info) in by_name.items():
        assert ok or name.endswith("ms_a_layer"), (name, info)
    assert "3 x 3 blocks of 128" in by_name["flash_fwd_live/1x384x64/bit_for_bit"][1]
    assert sorted(json.loads(by_name["flash_fwd_live/1x384x64/fwd_ms_a_layer"][1])) == [
        "clamp", "live", "parent_maps"]
    assert sorted(json.loads(by_name["flash_fwd_live/1x384x64/two_calls_ms_a_layer"][1])) == [
        "clamp", "parent_maps"]
    assert A.TilePlan.live_axis is live_axis and A._block_maps is maps
    assert A._tile_plan(384, 384, 64, True).live_axis
    defaults = inspect.signature(tpu_checks.check_flash_fwd_live).parameters
    assert defaults["timed"].default == tpu_checks._FUSED_SHAPES
    assert defaults["two_calls"].default == (32, 8192, 192, 128)
    assert "check_flash_fwd_live" in inspect.getsource(tpu_checks.main)


def test_the_index_select_check_runs_its_comparison():
    """The chip check of ``ops.indexer`` at toy lengths on the interpreter (one
    of them padded): the kept pairs are counted, the selection is held to
    ``lax.top_k``'s and the scores to float32's (the timings are not judged
    here), and ``main`` runs the group."""
    import inspect

    results = []
    tpu_checks.check_index_select(results, lengths=(200, 256), topk=48)
    by_name = {name: (ok, info) for name, ok, info in results}
    compared = ("pairs", "agrees_with_top_k", "scores_vs_float32")
    assert set(by_name) == {f"index_select/S{S}/{k}" for S in (200, 256)
                            for k in compared + ("ms_a_layer",)}
    for name, (ok, info) in by_name.items():
        assert ok, (name, info)
    assert "kept 8472 of 8472" in by_name["index_select/S200/pairs"][1]
    assert "check_index_select" in inspect.getsource(tpu_checks.main)


def test_the_flash_sparse_check_runs_its_comparison():
    """The chip check of the selected-keys flash kernels at a toy length on the
    interpreter: the output and the three cotangents under a selection the
    indexer's kernel made are compared with the jnp path's, the sparse call is
    timed beside the plain causal one, and ``main`` runs the group."""
    import inspect
    import json

    results = []
    tpu_checks.check_flash_sparse(results, H=2, D=64, parity=(256,), timed=(256,), topk=48)
    by_name = {name: (ok, info) for name, ok, info in results}
    compared = tuple(f"S256/parity/{k}" for k in ("o", "dq", "dk", "dv"))
    assert set(by_name) == {f"flash_sparse/{k}" for k in compared + ("S256/ms_a_layer",)}
    for k in compared:
        assert by_name[f"flash_sparse/{k}"][0], by_name
    read = json.loads(by_name["flash_sparse/S256/ms_a_layer"][1])
    assert set(read) == {"sparse", "causal"} and "fwd_pct_of_peak" in read["sparse"]
    assert "check_flash_sparse" in inspect.getsource(tpu_checks.main)
