"""A training cell driven end to end on the CPU at a tiny size, past the
harness's look for a chip: a sound run comes out correct; each fault a
training cell on one chip can have, planted in the timed path, and the
control (the plain reference in float8, put in the program's place) come
out not correct.

The limits here are this size's own, set from its readings (program: gaps
under 0.01 on every seed tried, ``grad_dir_gap`` about 0.03; half the
batch: ``grad_dir_gap`` 0.47 to 0.76; float8: ``grad_gap`` near 3); the
cell's limits are set from readings at its own size on the chip
(``calibrate.py``)."""
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parents[1] / "src"))

from lib import common, train_cell  # noqa: E402
from lib.precision import mm_fp8  # noqa: E402

LIMITS = {"loss_gap_1": 0.002, "loss_gap_2": 0.002, "loss_gap_3": 0.002,
          "grad_norm_gap": 0.01, "grad_gap": 0.03,
          "change_gap": 0.03, "grad_dir_gap": 0.15}
SEED = 2 ** 33 + 5


def tiny_cell():
    cell = common.cell_from_files("bert-large", "phase1-s128")
    cell.config.update(hidden_size=64, num_hidden_layers=2,
                       num_attention_heads=4, intermediate_size=128,
                       vocab_size=256, padded_vocab_size=256)
    cell.traffic.update(batch=4, seq_len=16, limits=LIMITS)
    return cell


def drive(cell):
    return train_cell.run(cell, SEED, 0.3, False, time.perf_counter(),
                          common.CompileClock())


def plant(monkeypatch, wrap):
    """Replace the trainer's step with ``wrap(step)``."""
    from repro.train import steps
    build = steps.build_train_step

    def faulty(run):
        bundle = build(run)
        bundle.fn = wrap(bundle.fn)
        return bundle

    monkeypatch.setattr(steps, "build_train_step", faulty)


def test_sound_run_is_correct():
    r = drive(tiny_cell())
    assert all(c.ok for c in r.checks), r.checks
    assert r.attempted > 0 and r.failed == 0
    assert set(r.end_to_end) == {"train_tokens_per_s", "setup_s"}
    assert r.end_to_end["train_tokens_per_s"] > 0


def test_state_left_unchanged_fails(monkeypatch):
    plant(monkeypatch, lambda step: lambda state, batch: (
        state, step(state, batch)[1]))
    checks = {c.name: c for c in drive(tiny_cell()).checks}
    assert checks["grad_gap"].value == pytest.approx(1.0)
    assert checks["change_gap"].value == pytest.approx(1.0)
    assert checks["grad_dir_gap"].value == pytest.approx(1.0)
    assert not all(c.ok for c in checks.values())


def test_half_batch_left_out_fails(monkeypatch):
    plant(monkeypatch, lambda step: lambda state, batch: step(
        state, {k: v[:v.shape[0] // 2] for k, v in batch.items()}))
    checks = {c.name: c for c in drive(tiny_cell()).checks}
    assert not checks["grad_dir_gap"].ok, checks


def test_control_in_float8_fails():
    cell = tiny_cell()
    trainer = train_cell.Trainer(cell, SEED)
    _, batches = train_cell.program_readings(trainer)
    trainer.close()
    ref = train_cell.reference_readings(cell, SEED, batches,
                                        keep_direction=True)
    control = train_cell.reference_readings(
        cell, SEED, batches, direction=ref.pop("direction"), mm=mm_fp8)
    assert not all(c.ok for c in train_cell.compare(control, ref, LIMITS))
