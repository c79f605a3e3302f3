"""The two proofs that `correct` can come out false, at a size a test run holds:
the control (the reference in the next precision down, in the program's place)
fails a limit, and a run whose timed path is broken underneath is not correct."""

import pytest

from benchmarks import run
from conftest import cpu_devices

TRAIN, SERVE = "mistral7b-train-1chip", "mistral7b-serve-chat"


def drive(root, cell, seed, control=None, seconds=0.5):
    """What run.py does between the device check and the result line."""
    _, runner, ctx = run.prepare(cell, seed, seconds, root=root,
                                 device_check=cpu_devices)
    ctx.say = lambda msg: None
    job = runner.setup(ctx)
    samples = job.window(seconds)
    job.release()
    return {c.name: c for c in job.check(samples, control)}


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("cell,control", [(TRAIN, "float8"), (SERVE, "float8")])
def test_the_control_fails_and_the_program_passes(tiny_root, cell, control, seed):
    checks = drive(tiny_root, cell, seed, control)
    sound = {k: c for k, c in checks.items() if not k.startswith("control.")}
    low = {k: c for k, c in checks.items() if k.startswith("control.")}
    assert sound and all(c.ok for c in sound.values()), sound
    assert low and not all(c.ok for c in low.values()), low


def test_a_step_that_returns_its_state_unchanged_is_not_correct(tiny_root, monkeypatch):
    from tf_operator_tpu.train.trainer import Trainer

    def lazy(self, state, batch):  # the right loss, and no update
        return state, {"loss": self.loss_fn(state.params, batch, state.extra)}

    monkeypatch.setattr(Trainer, "step", lazy)
    result = run.run_cell(TRAIN, 4, 0.5, False, root=tiny_root, device_check=cpu_devices)
    assert result["correct"] is False
    assert result["attempted"] > 0 and result["failed"] == 0  # losses stay finite


def test_a_token_altered_where_it_is_produced_is_not_correct(tiny_root, monkeypatch):
    from tf_operator_tpu.serve.engine import ServeEngine

    real = ServeEngine.compile

    def compile_then_tamper(self):
        out = real(self)
        decode = self._decode

        def off_by_one(*args):
            kp, vp, tok = decode(*args)
            return kp, vp, (tok + 1) % self.cfg.vocab

        self._decode = off_by_one
        return out

    monkeypatch.setattr(ServeEngine, "compile", compile_then_tamper)
    result = run.run_cell(SERVE, 4, 1.0, False, root=tiny_root, device_check=cpu_devices)
    assert result["correct"] is False
    assert result["failed"] == 0  # every request still finishes, with wrong tokens
