"""``correct`` on the CPU at fixture size: sound runs pass, the lower-precision
control fails, and a timed path broken underneath comes out false.

The fixture cells' limits were set as the real cells' are, from CPU readings at
this size on seeds 1-6 (``read_limits.py``): the program's largest / the fp8
control's smallest first-gradient gap is 0.0012 / 0.0098 for tiny-gpt (limit
0.003) and 0.096 / 0.194 for tiny-resnet (limit 0.15); the update-norm gap reads
0.18 and 0.10 against 1.0 for a state returned unchanged (limit 0.5); the loss
gap 2.6e-5 and 1.1e-3 (limits 1e-4 and 4e-3, held against half the batch fed
twice, which moves the loss by more than 1e-3)"""

import json
import re

import jax
import jax.numpy as jnp
import pytest

from benchmark import check, run

SEEDS = (1, 2, 3)
CELLS = ("tiny-gpt.train", "tiny-resnet.train")


def _cell(name, seed):
    cell = run.load("workloads", name)
    c = run.Cell(cell, run.load("configs", cell["config"]), jax.devices()[:cell["chips"]])
    c.start(seed)
    return c


def _last_line(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("seed", SEEDS)
def test_program_passes_and_fp8_control_fails(name, seed):
    c = _cell(name, seed)
    reference = c.reference()
    control = check.compare(c.reference("fp8"), reference, c.cell["limits"])
    assert not all(r["ok"] for r in control), control
    c.build()
    sound = check.compare(c.program_numbers(), reference, c.cell["limits"])
    assert all(r["ok"] for r in sound), sound


@pytest.mark.parametrize("name", CELLS + ("tiny-gpt.train-dp4",))
def test_a_sound_run_is_correct_and_reports_no_time(name, capsys):
    assert run.main(["--workload", name, "--seed", "2147483659", "--seconds", "0.3",
                     "--trace", "0"]) == 0
    line = _last_line(capsys)
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert set(line["metrics"]) == {"setup_s"}, "a rehearsal reports no time-derived metric"


def _broken(monkeypatch, wrap):
    real_build = run.Cell.build

    def build(self):
        real_build(self)
        self.program.step = wrap(self.program.step)

    monkeypatch.setattr(run.Cell, "build", build)


def _unchanged_state(step):
    return lambda state, batch: (state, jnp.float32(1.0), jnp.bool_(False))


def _half_the_batch(step):
    def broken(state, batch):
        half = jax.tree.map(lambda x: jnp.concatenate([x[: len(x) // 2]] * 2), batch)
        return step(state, half)
    return broken


@pytest.mark.parametrize("fault", (_unchanged_state, _half_the_batch), ids=lambda f: f.__name__)
def test_a_broken_timed_path_is_not_correct(fault, monkeypatch, capsys):
    _broken(monkeypatch, fault)
    assert run.main(["--workload", "tiny-gpt.train", "--seed", "4", "--seconds", "0.2",
                     "--trace", "0"]) == 0
    assert _last_line(capsys)["correct"] is False


def test_a_compilation_inside_the_window_is_not_correct(monkeypatch, capsys):
    real_dispatch = run.Cell.dispatch

    def dispatch(self, i):
        if i == 5:
            jax.jit(lambda x: x * 3 + i)(jnp.ones(7))      # a shape the set-up never saw
        return real_dispatch(self, i)

    monkeypatch.setattr(run.Cell, "dispatch", dispatch)
    assert run.main(["--workload", "tiny-gpt.train", "--seed", "4", "--seconds", "0.2",
                     "--trace", "0"]) == 0
    out = capsys.readouterr().out
    assert re.search(r"window\.compilations = [1-9]\d*  limit 0  FAILED", out)
    assert json.loads(out.strip().splitlines()[-1])["correct"] is False


def test_a_kernel_degraded_to_jnp_is_reported():
    from beforeholiday_tpu.guard import dispatch

    dispatch.reset_dispatch_counters()
    c = _cell("tiny-gpt.train", 1)
    c.build()
    c.run_step(0)                       # off the chip every guarded op resolves to jnp
    errors = run.dispatch_errors(c.family.GUARDED_OPS)
    assert any("flash_attention" in e for e in errors) and any("layer_norm" in e for e in errors)


@pytest.mark.parametrize("name", ("gpt2-medium.train", "resnet50.train", "gpt2-medium.train-dp4"))
def test_a_real_cell_refuses_any_backend_but_tpu(name, capsys):
    assert run.main(["--workload", name, "--seed", "1", "--seconds", "1", "--trace", "0"]) == 1
    captured = capsys.readouterr()
    assert captured.out == "" and "tpu" in captured.err
