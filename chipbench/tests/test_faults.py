"""Whole runs at the smoke size on the CPU, with the platform check skipped:
a sound run is correct; the float8 control put in the program's place, and
each fault the cells can have, planted under the timed path, is not."""
import dataclasses
import time

import numpy as np
import pytest

from chipbench import run
from chipbench.serve_cell import ServeCell
from chipbench.train_cell import TrainCell

TRAIN = "smollm-360m.train.host-state"
SERVE = "smollm-360m.decode.host-weights"
SEED = 2**31 + 99


def smoke_run(workload, seconds=0.3):
    return run.run_cell(workload, SEED, seconds, False, smoke=True,
                        t_start=time.perf_counter(), log=lambda m: None)


@pytest.fixture
def full_width(monkeypatch):
    """smollm-360m at its published width (d 960, 15 heads of 64 over 5 KV
    heads, d_ff 2560) with 2 layers and a 16384-token vocabulary: the logits
    have the spread of the real model's, which the control's gap scales with."""
    import repro.configs as rc

    smoke = rc.get_smoke_config

    def wide(arch):
        return dataclasses.replace(
            smoke(arch), d_model=960, n_heads=15, n_kv_heads=5, head_dim=64,
            d_ff=2560, vocab_size=16384,
        )

    monkeypatch.setattr(rc, "get_smoke_config", wide)


@pytest.mark.parametrize("workload", [TRAIN, SERVE])
def test_sound_run_is_correct(workload):
    r = smoke_run(workload)
    assert r["correct"], r["checks"]
    assert list(r)[-1] == "checks"
    assert set(r) >= {"correct", "attempted", "failed", "metrics", "device"}


def test_train_control_is_not_correct(monkeypatch):
    monkeypatch.setattr(
        TrainCell, "program_readings", lambda self: self.reference_readings(quant=True)
    )
    assert not smoke_run(TRAIN)["correct"]


def test_serve_control_is_not_correct(monkeypatch, full_width):
    gaps = ServeCell.gaps
    monkeypatch.setattr(
        ServeCell, "gaps", lambda self, quant=False: gaps(self, quant=True)[::-1]
    )
    assert not smoke_run(SERVE, seconds=4.0)["correct"]


def test_serve_is_correct_at_full_width(full_width):
    assert smoke_run(SERVE, seconds=4.0)["correct"]


def test_step_that_returns_its_state_unchanged(monkeypatch):
    step = TrainCell._step

    def unchanged(self, i):
        _, metrics = step(self, i)
        return self.state, metrics

    monkeypatch.setattr(TrainCell, "_step", unchanged)
    r = smoke_run(TRAIN)
    assert not r["correct"]
    assert r["checks"]["delta_gap"]["value"] > 0.9


def test_half_of_the_batch_left_out(monkeypatch):
    def half(self, i):
        tokens, targets = self.batch(i)
        h = tokens.shape[0] // 2
        return self.driver.step_fn(
            self.state, {"tokens": tokens[:h], "targets": targets[:h]}
        )

    monkeypatch.setattr(TrainCell, "_step", half)
    assert not smoke_run(TRAIN)["correct"]


def test_token_altered_where_it_is_produced(monkeypatch):
    from repro.launch.serve import ServeSession

    step = ServeSession.step

    def altered(self):
        emitted = step(self)
        for rid in list(emitted)[:1]:
            req = self.requests[rid]
            tok = (req.emitted[-1] + 1) % self.cfg.vocab_size
            req.emitted[-1] = tok
            req.next_token = np.asarray(tok, np.int32)
            emitted[rid] = tok
        return emitted

    monkeypatch.setattr(ServeSession, "step", altered)
    assert not smoke_run(SERVE)["correct"]
