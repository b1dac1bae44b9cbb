"""``correct`` on one chip's worth of CPU: a sound run passes, and the
control and each fault a one-chip training cell can have fail."""
import json

import jax
import pytest

import cbtiny

cbtiny.use_harness()

import compare  # noqa: E402
import sgd_reference  # noqa: E402
from spec import Spec  # noqa: E402

COMMITTED = [w["name"] for w in json.loads(
    (cbtiny.ROOT / "BENCHMARK.json").read_text())["workloads"]]


def _break_step(monkeypatch, wrap):
    from repro.train import engine
    real = engine.make_bsp_step
    monkeypatch.setattr(engine, "make_bsp_step",
                        lambda *a, **kw: wrap(real(*a, **kw)))


def test_sound_run_is_correct(tiny_bench):
    res = cbtiny.run(tiny_bench)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["compiles_in_window"] == 0
    assert set(res["metrics"]) == {"train_images_per_s", "setup_s"}


def test_unchanged_state_is_not_correct(tiny_bench, monkeypatch):
    _break_step(monkeypatch, lambda step: (
        lambda state, batch, rng: (state, step(state, batch, rng)[1])))
    res = cbtiny.run(tiny_bench)
    assert not res["correct"]
    assert res["checks"]["grad_gap"]["value"] == pytest.approx(1.0)


def test_half_batch_is_not_correct(tiny_bench, monkeypatch):
    def half(batch):
        return jax.tree.map(lambda v: v[:v.shape[0] // 2], batch)
    _break_step(monkeypatch, lambda step: (
        lambda state, batch, rng: step(state, half(batch), rng)))
    res = cbtiny.run(tiny_bench)
    assert not res["correct"], res["checks"]


@pytest.fixture(scope="module")
def control_readings(tiny_bench):
    """The reference one precision below the configuration's, in the
    program's place, against the reference."""
    spec = Spec(tiny_bench)
    cell = spec.cell("tiny")
    conf, ref = spec.config("alexnet-tiny"), spec.reference("alexnet-tiny")
    want = sgd_reference.follow(ref, conf, cell, 11)
    control = sgd_reference.follow(ref, conf, cell, 11,
                                   compute=conf["precision"]["control"])
    return compare.readings(control, want)


def test_control_is_not_correct(tiny_bench, control_readings):
    correct, checks = compare.judge(control_readings,
                                    Spec(tiny_bench).cell("tiny")["limits"])
    assert not correct, checks


@pytest.mark.parametrize("name", COMMITTED)
def test_control_fails_the_committed_limits(control_readings, name):
    """The control fails each committed cell's own limits too, not only
    the tiny cell's."""
    limits = Spec(cbtiny.CHIPBENCH).cell(name)["limits"]
    correct, checks = compare.judge(control_readings, limits)
    assert not correct, checks
