"""Per-program attribution and anomaly detection: ProgramProfile cost
capture + gauge math, StreamDetector / FleetDetector firing rules,
detection-driven straggler marking through ``elastic_train``
(multi-device subprocess), and the adversarial-input contracts of the
validators (diagnostics, never tracebacks)."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import telemetry
from repro.telemetry import anomaly, profile, trace
from repro.telemetry.schema import (SCHEMA_VERSION, validate_metrics_jsonl,
                                    validate_record, validate_trace)


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    was = telemetry.enabled()
    telemetry.reset()
    trace.reset()
    telemetry.set_enabled(True)
    yield
    telemetry.set_enabled(was)
    telemetry.reset()
    trace.reset()


# ---------------------------------------------------------------------------
# ProgramProfile: capture -> observe -> gauges
# ---------------------------------------------------------------------------

def test_capture_records_cost_and_join_emits_gauges(monkeypatch):
    @jax.jit
    def f(x):
        return x @ x

    x = jnp.ones((64, 64), jnp.float32)
    prof = profile.capture("test/prog", f, x, coll_bytes=1e6)
    assert prof is not None and prof.captured
    # 64^3 * 2 flops for a square matmul
    assert prof.flops == pytest.approx(2 * 64 ** 3, rel=0.25)
    assert prof.hbm_bytes > 0
    assert prof.coll_bytes == 1e6

    profile.observe("test/prog", 0.010)
    profile.observe("test/prog", 0.020)
    assert prof.calls == 2
    assert prof.mean_time_s == pytest.approx(0.015)
    assert prof.achieved_flops_s == pytest.approx(prof.flops / 0.015)

    # MFU divides by the env-overridable peak model
    monkeypatch.setenv("REPRO_PEAK_FLOPS", "1e9")
    rl = prof.roofline()
    assert rl["mfu"] == pytest.approx(prof.achieved_flops_s / 1e9)
    assert rl["t_roofline_s"] > 0 and rl["bound"] in ("compute", "memory",
                                                      "collective")

    profile.emit()
    reg = telemetry.default_registry()
    for q in ("flops", "hbm_bytes", "coll_bytes", "calls", "mean_time_s",
              "achieved_flops_s", "mfu", "achieved_coll_bw"):
        assert reg[f"profile/test_prog/{q}"].value is not None
    assert reg["profile/test_prog/flops"].value == prof.flops


def test_unknown_device_gets_no_peak_gauges(monkeypatch):
    """A device missing from the peak table (the CPU here) yields no MFU
    or roofline ratio unless a REPRO_PEAK_* names that peak."""
    from repro.roofline.analysis import PEAKS, peaks

    for name in ("REPRO_PEAK_FLOPS", "REPRO_PEAK_HBM_BW",
                 "REPRO_PEAK_ICI_BW"):
        monkeypatch.delenv(name, raising=False)
    assert peaks("cpu") == {}
    assert peaks("TPU v5 lite") == PEAKS["TPU v5 lite"]

    @jax.jit
    def f(x):
        return x @ x

    prof = profile.capture("test/nopeak", f, jnp.ones((32, 32)))
    profile.observe("test/nopeak", 0.01)
    gauges = prof.gauges()
    assert gauges["profile/test_nopeak/achieved_flops_s"] > 0
    assert not any(g.endswith(("/mfu", "/hbm_frac", "/coll_frac"))
                   for g in gauges)
    monkeypatch.setenv("REPRO_PEAK_HBM_BW", "1e9")
    assert set(prof.roofline()) == {"hbm_frac", "t_roofline_s", "bound"}


def test_capture_failure_is_a_counter_not_an_exception():
    class Broken:
        def lower(self, *a, **k):
            raise RuntimeError("no lowering for you")

    assert profile.capture("test/broken", Broken()) is None
    reg = telemetry.default_registry()
    assert reg["profile/capture_errors"].value == 1
    assert "capture_error" in profile.get("test/broken").meta


def test_instrument_first_call_records_compile_time_and_passthrough():
    calls = []

    @jax.jit
    def f(x):
        calls.append(1)
        return x * 2

    w = profile.instrument("test/instr", f)
    x = jnp.arange(8.0)
    y1, y2 = w(x), w(x)
    assert np.array_equal(np.asarray(y1), np.asarray(y2))
    prof = profile.get("test/instr")
    assert prof.captured and prof.compile_time_s > 0
    assert len(calls) == 1          # lower() shared the jit trace cache
    profile.emit()
    assert telemetry.default_registry()["compile/test_instr_s"].value > 0


def test_profile_disabled_by_config_knob():
    telemetry.configure(profile=False)
    try:
        assert not profile.enabled()

        @jax.jit
        def f(x):
            return x + 1

        assert profile.capture("test/off", f, jnp.ones(4)) is None
        profile.observe("test/off", 1.0)
        assert profile.get("test/off") is None
    finally:
        telemetry.configure(profile=True)


def test_instrument_leaves_jitted_program_bytes_identical():
    """The attribution wrapper must never alter the program: lowered text
    of the wrapped jit is identical with profiling on and off."""
    def g(x):
        return jnp.sin(x) * x

    x = jax.ShapeDtypeStruct((16,), jnp.float32)
    telemetry.configure(profile=True)
    on = jax.jit(g).lower(x).as_text()
    telemetry.configure(profile=False)
    off = jax.jit(g).lower(x).as_text()
    telemetry.configure(profile=True)
    assert on == off


# ---------------------------------------------------------------------------
# StreamDetector: spikes + regressions
# ---------------------------------------------------------------------------

def test_stream_detector_flags_spike_not_steady_state():
    det = anomaly.StreamDetector("test/stream", min_n=8, spike_z=8.0)
    rng = np.random.default_rng(0)
    for _ in range(32):
        r = det.observe(0.1 + rng.uniform(-0.001, 0.001))
        assert not r["spike"]
    r = det.observe(1.0)            # 10x step time
    assert r["spike"] and r["z"] > 8.0
    assert det.spikes == 1
    reg = telemetry.default_registry()
    assert reg["anomaly/test_stream/spikes"].value == 1
    assert any(e[1] == "anomaly/spike" for e in trace.events())


def test_stream_detector_regression_fires_once_then_reanchors():
    det = anomaly.StreamDetector("test/reg", min_n=4, patience=3,
                                 regress_tol=0.5, spike_z=1e9)
    for _ in range(16):
        det.observe(0.1)
    fired = [det.observe(0.2)["regression"] for _ in range(30)]
    assert sum(fired) == 1          # re-anchor: sustained shift reports once
    assert det.regressions == 1


def test_stream_detector_silent_when_disabled():
    det = anomaly.StreamDetector("test/off")
    telemetry.set_enabled(False)
    for _ in range(64):
        r = det.observe(0.1)
    r = det.observe(100.0)
    assert not r["spike"] and det.spikes == 0


# ---------------------------------------------------------------------------
# FleetDetector: cross-sectional stragglers
# ---------------------------------------------------------------------------

def test_fleet_detector_flags_relative_outlier_with_tied_fleet():
    det = anomaly.FleetDetector()
    # MAD = 0 (everyone ties): the relative arm must still catch 8x
    assert det.observe({0: 0.1, 1: 0.1, 2: 0.1, 3: 0.8}) == [3]
    assert det.observe({0: 0.1, 1: 0.1, 2: 0.1, 3: 0.1}) == []
    # 2x is inside rel_thresh=3 — not a straggler
    assert det.observe({0: 0.1, 1: 0.1, 2: 0.1, 3: 0.2}) == []


def test_fleet_detector_respects_min_workers_and_patience():
    det = anomaly.FleetDetector(patience=2)
    assert det.observe({0: 0.1, 1: 0.9}) == []          # < min_workers
    d3 = {0: 0.1, 1: 0.1, 2: 0.1, 3: 0.9}
    assert det.observe(d3) == []                         # streak 1 < 2
    assert det.observe(d3) == [3]                        # streak 2
    ok = {0: 0.1, 1: 0.1, 2: 0.1, 3: 0.1}
    det.observe(ok)                                      # streak resets
    assert det.observe(d3) == []


def test_mark_straggling_counts_observed_separately():
    from repro.fault.membership import MembershipController, WorkerState
    c = MembershipController([0, 1, 2, 3], alpha=0.5)
    assert c.mark_straggling(3, 2)
    assert c.state_of(3) == WorkerState.STRAGGLING
    assert c.observed_straggles == 1
    assert 3 not in c.reporting()
    assert not c.mark_straggling(9)      # unknown worker: no count
    assert c.observed_straggles == 1


# ---------------------------------------------------------------------------
# "slow" fault kind + detection through elastic_train (subprocess, 8 dev)
# ---------------------------------------------------------------------------

def test_slow_fault_event_spec_roundtrip_and_validation():
    from repro.fault.inject import FaultEvent, FaultPlan
    plan = FaultPlan.from_spec("slow:2@4x3,kill:1@9")
    ev = plan.events_at(4)[0]
    assert ev.kind == "slow" and ev.rounds == 3 and ev.factor == 8.0
    assert plan.to_spec() == "slow:2@4x3,kill:1@9"
    with pytest.raises(ValueError):
        FaultEvent("slow", 0, 1, factor=0.5)


_SLOW_SCRIPT = r"""
import json, os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import numpy as np

from repro.configs import get_smoke_config
from repro.data.synthetic import LMTokenSource
from repro.models import build_model
from repro.optim import constant, sgd_momentum
from repro.train.engine import TrainPlan
from repro.fault.elastic import elastic_train
from repro.fault.membership import WorkerState

cfg = get_smoke_config("llama3.2-1b").with_overrides(
    vocab_size=64, d_ff=128, num_layers=2, dtype="float32")
model = build_model(cfg)
src = LMTokenSource(cfg.vocab_size, 16, seed=0)
batch_fn = lambda step, k: src.batch(4 * k, step)
plan = TrainPlan(algo="easgd", tau=2, alpha=0.5, exchanger="ar", quorum=2)

def run():
    return elastic_train(model, sgd_momentum(weight_decay=0.0),
                         constant(0.05), batch_fn, plan=plan,
                         num_workers=4, num_steps=16, seed=0,
                         fault_plan="slow:2@4x3", print_fn=None)

_, r1 = run()
_, r2 = run()
from repro.telemetry import trace
flag_steps = sorted(e[5]["step"] for e in trace.events()
                    if e[1] == "anomaly/straggler")
out = dict(slows=r1.slows, detected=r1.stragglers_detected,
           detected_replay=r2.stragglers_detected,
           straggles_injected=r1.straggles,
           flag_steps=flag_steps[:4],
           rounds_synced=r1.rounds_synced,
           final_workers=list(r1.final_workers))
print("RESULTS_JSON:" + json.dumps(out))
"""


def test_elastic_detects_injected_slowdown_within_three_rounds():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", _SLOW_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = None
    for line in proc.stdout.splitlines():
        if line.startswith("RESULTS_JSON:"):
            out = json.loads(line[len("RESULTS_JSON:"):])
    assert out is not None, proc.stdout[-2000:]
    assert out["slows"] == 1, out
    # the controller was never told ("straggle" was not injected) — the
    # detector discovered the slow worker from observed timing alone
    assert out["straggles_injected"] == 0, out
    assert out["detected"] >= 1, out
    # ...at the very first slowed step (well inside 3 tau rounds: the
    # slow window starts at step 4; 3 rounds of tau=2 end at step 9)
    assert out["flag_steps"] and out["flag_steps"][0] <= 9, out
    # deterministic: the replay flags identically and the fleet survives
    assert out["detected_replay"] == out["detected"], out
    assert out["final_workers"] == [0, 1, 2, 3], out


# ---------------------------------------------------------------------------
# train/serve integration: gauges for train step, decode step, exchange half
# ---------------------------------------------------------------------------

def test_train_loop_emits_program_and_compile_gauges(monkeypatch):
    from repro.optim import constant, sgd_momentum
    from repro.train.loop import train
    from tests.test_engine import _batches, _mesh1, _tiny_lm

    cfg, model = _tiny_lm()
    mesh = _mesh1()
    n = 4
    # the CPU has no peak in the table: name one, or no MFU is emitted
    monkeypatch.setenv("REPRO_PEAK_FLOPS", "1e12")
    train(model, sgd_momentum(), constant(0.01), mesh, _batches(cfg, n),
          num_steps=n, log_every=2, print_fn=lambda *a: None)
    profile.emit()
    reg = telemetry.default_registry()
    # train step: cost captured and the compiling call timed
    assert reg["profile/train_step/flops"].value > 0
    assert reg["profile/train_step/hbm_bytes"].value > 0
    assert reg["compile/train_step_s"].value > 0
    # host dispatch times are not joined into the step's profile (they are
    # not the device's time under async dispatch), so no rate or MFU
    names = set(reg.names())
    for gone in ("calls", "mean_time_s", "mfu"):
        assert f"profile/train_step/{gone}" not in names
    # nor are the exchange halves rebuilt and timed as programs of their own
    assert profile.get("exchange/rs") is None
    assert profile.get("exchange/ag") is None
    assert not any(g.startswith(("profile/exchange_", "compile/exchange_"))
                   for g in names)


def test_serve_engine_emits_decode_attribution(monkeypatch):
    from tests.test_telemetry import _serve_run

    monkeypatch.setenv("REPRO_PEAK_FLOPS", "1e12")
    _, engine = _serve_run()
    profile.emit()
    reg = telemetry.default_registry()
    assert profile.get("serve/decode_step").captured
    assert reg["profile/serve_decode_step/flops"].value > 0
    assert reg["profile/serve_decode_step/mfu"].value > 0
    assert reg["compile/serve_decode_step_s"].value > 0
    assert profile.get("serve/prefill_chunk").captured
    assert reg["compile/serve_prefill_chunk_s"].value > 0
    # compile-once survives the lower() capture (shared trace cache)
    assert engine.trace_counts["decode"] == 1
    assert engine.trace_counts["prefill"] == 1


# ---------------------------------------------------------------------------
# adversarial validator inputs: diagnostics, never tracebacks
# ---------------------------------------------------------------------------

def test_validate_jsonl_truncated_line_is_a_diagnostic(tmp_path):
    p = tmp_path / "m.jsonl"
    good = json.dumps({"schema_version": SCHEMA_VERSION, "kind": "run",
                       "ts": 1.0, "run": {"host": "h", "backend": "cpu"}})
    line = json.dumps({"schema_version": SCHEMA_VERSION, "kind": "counter",
                       "ts": 1.0, "name": "a/b", "value": 3})
    p.write_text(good + "\n" + line[: len(line) // 2] + "\n")
    errs = validate_metrics_jsonl(str(p))
    assert errs and any("bad json" in e for e in errs)


def test_validate_unknown_schema_version_is_a_diagnostic():
    errs = validate_record({"schema_version": 999, "kind": "counter",
                            "ts": 1.0, "name": "x", "value": 1})
    assert any("schema_version" in e for e in errs)


def test_validate_histogram_nonnumeric_bounds_no_traceback():
    rec = {"schema_version": SCHEMA_VERSION, "kind": "histogram", "ts": 1.0,
           "name": "h", "bounds": ["a", None], "counts": [0, 0, 0],
           "count": 0, "sum": 0.0, "min": 0.0, "max": 0.0}
    errs = validate_record(rec)
    assert any("non-numeric histogram bounds" in e for e in errs)
    rec2 = dict(rec, bounds=[1.0, 2.0], counts=[0, "x", 0])
    assert any("non-integer histogram counts" in e
               for e in validate_record(rec2))


def test_validate_trace_async_end_before_begin(tmp_path):
    p = tmp_path / "t.json"
    ev = {"name": "s", "ph": "e", "pid": 1, "tid": 1, "ts": 1.0, "id": 7}
    p.write_text(json.dumps({
        "traceEvents": [ev],
        "otherData": {"schema_version": SCHEMA_VERSION,
                      "run": {"backend": "cpu"}}}))
    errs = validate_trace(str(p))
    assert any("async end before begin" in e for e in errs)
    # balanced begin/end is clean
    b = dict(ev, ph="b")
    p.write_text(json.dumps({
        "traceEvents": [b, ev],
        "otherData": {"schema_version": SCHEMA_VERSION,
                      "run": {"backend": "cpu"}}}))
    assert validate_trace(str(p)) == []


def test_validate_trace_events_not_a_list(tmp_path):
    p = tmp_path / "t.json"
    p.write_text(json.dumps({"traceEvents": {"oops": 1}}))
    errs = validate_trace(str(p))
    assert errs and "not a list" in errs[0]
