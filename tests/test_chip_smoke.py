"""``chip_smoke.py`` on the CPU: it refuses to run without a TPU, and its
train and serve phases pass at reduced width (the TPU placement check
stubbed), so a change that breaks the chip smoke shows here first."""
import os
import subprocess
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def chip_smoke(monkeypatch):
    monkeypatch.syspath_prepend(ROOT)
    import chip_smoke as cs

    monkeypatch.setattr(cs, "on_tpu", lambda tree: True)
    return cs


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert "'cpu'" in out.stderr
    assert '"ok"' not in out.stdout


def test_train_and_serve_phases_at_reduced_width(chip_smoke, tmp_path):
    from repro.checkpoint import save

    dev = jax.devices()[0]
    trainer = chip_smoke.train_phase(chip_smoke.TRAIN_ARGV + ["--reduced"],
                                     dev)
    assert len(trainer.history) == 3
    save(str(tmp_path), trainer.round - 1, trainer.state.inner.params)
    del trainer
    chip_smoke.serve_phase(str(tmp_path), True, dev)
