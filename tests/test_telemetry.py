"""repro.telemetry: histogram/percentile math vs numpy, sink round-trips,
span nesting + Chrome-trace schema, the no-op fast path, analytic wire
accounting, and the on-vs-off parity contracts (serve outputs bit-identical,
compile-once guards hold with telemetry enabled)."""
import functools
import json

import jax
import numpy as np
import pytest

from repro import telemetry
from repro.launch.mesh import make_mesh
from repro.telemetry import metrics, trace
from repro.telemetry.registry import (NOOP, Histogram, JsonlSink, MemorySink,
                                      Registry, exp_buckets)
from repro.telemetry.schema import (SCHEMA_VERSION, validate_metrics_jsonl,
                                    validate_record, validate_trace)


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    """Each test gets a clean default registry, empty trace buffer, and the
    enabled switch restored afterwards."""
    was = telemetry.enabled()
    telemetry.reset()
    trace.reset()
    yield
    telemetry.set_enabled(was)
    telemetry.reset()
    trace.reset()


# ---------------------------------------------------------------------------
# histogram bucket math + percentiles vs numpy
# ---------------------------------------------------------------------------

def test_exp_buckets_cover_range():
    b = exp_buckets(1e-5, 100.0, 8)
    assert b[0] == pytest.approx(1e-5)
    assert b[-1] >= 100.0
    assert list(b) == sorted(b)
    # 8 per decade over 7 decades
    assert len(b) == 7 * 8 + 1


def test_histogram_bucket_assignment():
    h = Histogram("t", buckets=(1.0, 2.0, 4.0))
    for v in (0.5, 1.0, 1.5, 3.0, 100.0):
        h.observe(v)
    # bisect_right: buckets are left-closed, a boundary value starts the
    # bucket above it
    assert h.counts == [1, 2, 1, 1]
    assert h.count == 5 == sum(h.counts)
    assert h.sum == pytest.approx(106.0)
    assert h.min == 0.5 and h.max == 100.0
    assert h.mean == pytest.approx(106.0 / 5)


def test_histogram_percentiles_within_one_bucket_width():
    """The interpolated percentile must land within one bucket width of
    numpy's exact percentile, across distributions."""
    rng = np.random.RandomState(0)
    bounds = exp_buckets(1e-4, 10.0, 16)
    for dist in (rng.lognormal(-3, 1.0, 5000),
                 rng.uniform(1e-3, 1.0, 5000),
                 np.full(100, 0.01)):
        h = Histogram("t", buckets=bounds)
        for v in dist:
            h.observe(v)
        for q in (10, 50, 90, 99):
            exact = float(np.percentile(dist, q))
            got = h.percentile(q)
            i = int(np.searchsorted(bounds, exact))
            lo = bounds[i - 1] if i > 0 else 0.0
            hi = bounds[i] if i < len(bounds) else h.max
            width = hi - lo
            assert abs(got - exact) <= width + 1e-12, \
                f"p{q}: got {got}, exact {exact}, bucket width {width}"
            assert h.min <= got <= h.max


def test_histogram_percentile_edge_cases():
    h = Histogram("t", buckets=(1.0, 2.0))
    assert h.percentile(50) == 0.0                    # empty
    h.observe(1.5)
    assert h.percentile(0) == pytest.approx(1.5)      # single observation
    assert h.percentile(100) == pytest.approx(1.5)
    h2 = Histogram("t2", buckets=(1.0,))
    h2.observe(5.0)                                   # overflow bucket only
    assert h2.percentile(99) == pytest.approx(5.0)


def test_histogram_rejects_unsorted_bounds():
    with pytest.raises(ValueError):
        Histogram("t", buckets=(2.0, 1.0))
    with pytest.raises(ValueError):
        exp_buckets(1.0, 0.5)


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------

def test_registry_get_or_create_and_kind_check():
    r = Registry()
    c = r.counter("a/b")
    assert r.counter("a/b") is c
    c.inc(3)
    c.inc()
    assert c.value == 4
    with pytest.raises(TypeError):
        r.gauge("a/b")
    g = r.gauge("a/g")
    g.set(2.5)
    g.inc()
    assert g.value == 3.5
    r.info("a/i", strategy="asa", dtype="int8")
    assert r["a/i"].labels == {"strategy": "asa", "dtype": "int8"}
    assert "a/b" in r and "missing" not in r
    assert r.names() == ["a/b", "a/g", "a/i"]


def test_registry_snapshot_records_validate():
    r = Registry(label="x")
    r.counter("c").inc(7)
    r.histogram("h", buckets=(1.0,)).observe(0.5)
    for rec in r.snapshot():
        assert validate_record(rec) == []
        assert rec["schema_version"] == SCHEMA_VERSION
        assert rec["reg"] == "x"


# ---------------------------------------------------------------------------
# sinks
# ---------------------------------------------------------------------------

def test_memory_sink_round_trip():
    r = Registry()
    sink = MemorySink()
    r.add_sink(sink)
    r.counter("n").inc(2)
    r.flush()
    r.counter("n").inc(3)
    r.flush()
    assert [s[0]["value"] for s in sink.snapshots] == [2, 5]


def test_jsonl_sink_round_trip(tmp_path):
    path = str(tmp_path / "m.jsonl")
    r = Registry()
    r.add_sink(JsonlSink(path))
    r.counter("train/steps").inc(10)
    r.gauge("train/loss").set(1.25)
    h = r.histogram("train/step_time_s", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    r.close()
    assert validate_metrics_jsonl(path) == []
    recs = [json.loads(l) for l in open(path)]
    assert recs[0]["kind"] == "run" and "backend" in recs[0]["run"]
    by_name = {r0["name"]: r0 for r0 in recs[1:]}
    assert by_name["train/steps"]["value"] == 10
    assert by_name["train/loss"]["value"] == 1.25
    hr = by_name["train/step_time_s"]
    assert hr["counts"] == [1, 1, 0] and hr["count"] == 2


def test_jsonl_sink_periodic_interval_skips_unforced(tmp_path):
    path = str(tmp_path / "m.jsonl")
    r = Registry()
    r.add_sink(JsonlSink(path, every_s=3600))
    r.counter("c").inc()
    r.flush(force=False)        # first emit: interval starts
    r.flush(force=False)        # within interval -> skipped
    r.flush(force=True)         # force always writes
    r.close()                   # close forces one more
    recs = [json.loads(l) for l in open(path)]
    assert sum(1 for x in recs if x["kind"] == "counter") == 3


def test_dump_metrics_includes_attached_registries(tmp_path):
    path = str(tmp_path / "m.jsonl")
    eng_reg = Registry(label="serve")
    eng_reg.counter("serve/decoded_tokens").inc(42)
    telemetry.attach_registry(eng_reg)
    metrics.counter("train/steps").inc(1)
    telemetry.dump_metrics(path)
    telemetry.detach_registry(eng_reg)
    assert validate_metrics_jsonl(path) == []
    names = {json.loads(l).get("name") for l in open(path)}
    assert {"serve/decoded_tokens", "train/steps"} <= names


# ---------------------------------------------------------------------------
# spans + chrome trace export
# ---------------------------------------------------------------------------

def test_span_nesting_and_export_schema(tmp_path):
    with trace.span("outer", step=1):
        with trace.span("inner"):
            pass
    trace.instant("marker", note="here")
    trace.async_begin("req", 7, prompt=3)
    trace.async_end("req", 7)
    evs = trace.events()
    assert [e[0] for e in evs] == ["X", "X", "i", "b", "e"]
    # inner closes first and must nest inside outer's [t0, t0+dur] window
    inner, outer = evs[0], evs[1]
    assert inner[1] == "inner" and outer[1] == "outer"
    assert outer[2] <= inner[2]
    assert inner[2] + inner[3] <= outer[2] + outer[3] + 1e-9

    path = str(tmp_path / "t.json")
    trace.export(path)
    assert validate_trace(path) == []
    obj = json.load(open(path))
    assert obj["otherData"]["schema_version"] == SCHEMA_VERSION
    assert obj["otherData"]["dropped_events"] == 0
    by_name = {e["name"]: e for e in obj["traceEvents"]}
    assert by_name["outer"]["args"] == {"step": 1}
    assert by_name["outer"]["dur"] >= by_name["inner"]["dur"]
    req_evs = [e for e in obj["traceEvents"] if e["name"] == "req"]
    assert [e["ph"] for e in req_evs] == ["b", "e"]
    assert all(e["id"] == 7 for e in req_evs)


def test_trace_buffer_bounded(monkeypatch):
    monkeypatch.setattr(trace, "MAX_EVENTS", 4)
    for _ in range(6):
        trace.instant("x")
    assert len(trace.events()) == 4
    assert trace.dropped() == 2
    trace.reset()
    assert trace.events() == [] and trace.dropped() == 0


# ---------------------------------------------------------------------------
# the no-op fast path
# ---------------------------------------------------------------------------

def test_disabled_accessors_share_noop_singleton():
    telemetry.set_enabled(False)
    assert metrics.counter("x") is NOOP
    assert metrics.gauge("x") is NOOP
    assert metrics.histogram("x") is NOOP
    assert metrics.info("x", a=1) is NOOP
    NOOP.inc()
    NOOP.set(3)
    NOOP.observe(1.0)
    assert NOOP.value == 0 and NOOP.percentile(50) == 0.0
    # nothing was created in the registry
    assert telemetry.default_registry().names() == []


def test_disabled_spans_record_nothing_and_allocate_nothing():
    telemetry.set_enabled(False)
    s1 = trace.span("a", big=list(range(10)))
    s2 = trace.span("b")
    assert s1 is s2                       # the shared no-op span object
    with s1:
        pass
    trace.instant("i")
    trace.async_begin("r", 1)
    trace.async_end("r", 1)
    assert trace.events() == []


def test_disabled_path_is_allocation_free():
    """The off path must not allocate per call (the <1% contract's
    mechanism): after warmup, a tracemalloc window around 1000 disabled
    record calls shows no growth attributable to telemetry."""
    import tracemalloc
    telemetry.set_enabled(False)

    def hot():
        for _ in range(1000):
            metrics.counter("k").inc()
            metrics.histogram("h").observe(0.1)
            with trace.span("s"):
                pass

    hot()                                 # warm caches/interned state
    tracemalloc.start()
    before = tracemalloc.take_snapshot()
    hot()
    after = tracemalloc.take_snapshot()
    tracemalloc.stop()
    here = __file__.rstrip("co")
    grown = [d for d in after.compare_to(before, "lineno")
             if d.size_diff > 0 and any(
                 fr.filename.endswith(("registry.py", "metrics.py",
                                       "trace.py", "_runtime.py"))
                 or fr.filename == here
                 for fr in d.traceback)]
    # 3000 record calls: even 2 bytes/call would trip this — what passes
    # is O(1) interpreter noise (a few cached frames), not per-call growth
    assert sum(d.size_diff for d in grown) < 4096, \
        f"disabled telemetry allocated: {[str(d) for d in grown[:5]]}"


def test_enabled_switch_round_trip():
    telemetry.set_enabled(True)
    metrics.counter("on/c").inc(2)
    telemetry.set_enabled(False)
    metrics.counter("on/c").inc(5)        # no-op: different object
    telemetry.set_enabled(True)
    assert metrics.counter("on/c").value == 2


# ---------------------------------------------------------------------------
# analytic wire accounting (exchange/bytes_wire source)
# ---------------------------------------------------------------------------

def test_wire_summary_matches_hand_computation():
    import jax.numpy as jnp
    from repro.core.exchanger import get_exchanger, make_rs_plan, \
        wire_summary

    grads = {"w": jnp.zeros((1024,), jnp.float32),
             "b": jnp.zeros((4,), jnp.float32)}       # below min_leaf -> psum
    k = 4
    for strat, g_bytes in (("asa", 4), ("asa16", 2), ("asa8", 1)):
        ex = get_exchanger(strat)
        plan = make_rs_plan(grads, k, small_leaf=64)
        ws = wire_summary(ex, plan)
        b = plan.buckets[0]
        want_rs = (k - 1) * b.shard_len * g_bytes
        if g_bytes == 1:                               # int8 rows carry scales
            want_rs += (k - 1) * 4
        want_ag = (k - 1) * b.shard_len * g_bytes
        if g_bytes == 1:
            want_ag += (k - 1) * 4
        small = int(2 * (k - 1) / k * 4 * 4)
        assert ws["rs_bytes"] == want_rs, strat
        assert ws["ag_bytes"] == want_ag, strat
        assert ws["small_bytes"] == small
        assert ws["bytes_per_exchange"] == want_rs + want_ag + small
        assert ws["bytes_per_step"] == ws["bytes_per_exchange"]
        assert ws["k"] == k and ws["strategy"] == strat

    # ar: fused allreduce volume 2(k-1)/k at fp32, split rs/ag halves
    ex = get_exchanger("ar")
    plan = make_rs_plan(grads, k, small_leaf=64)
    ws = wire_summary(ex, plan)
    full = int(2 * (k - 1) / k * plan.buckets[0].padded * 4)
    assert ws["rs_bytes"] + ws["ag_bytes"] == pytest.approx(full, abs=2)

    # tau scales per-step traffic down, not per-exchange
    ws_tau = wire_summary(get_exchanger("asa"), plan, sync_every=4)
    assert ws_tau["bytes_per_step"] * 4 == ws_tau["bytes_per_exchange"]


def test_engine_exposes_wire_and_gspmd_does_not():
    from repro.optim import constant, sgd_momentum
    from repro.train.engine import TrainPlan, build_engine
    from tests.test_engine import _mesh1, _tiny_lm

    cfg, model = _tiny_lm()
    mesh = _mesh1()
    eng = build_engine(TrainPlan(algo="bsp", exchanger="asa16"), model,
                       sgd_momentum(), constant(0.01), mesh)
    assert eng.wire is not None
    assert eng.wire["strategy"] == "asa16"
    assert eng.wire["wire_dtype"] == "float16"
    assert eng.wire["k"] == 1
    # one worker: nothing moves on the wire (egress accounting is per-rank)
    assert eng.wire["bytes_per_step"] == 0
    assert len(eng.wire["per_bucket"]) == eng.wire["num_buckets"]
    if len(jax.devices()) >= 8:   # k>1 wire accounting needs a real 8-mesh
        mesh8 = make_mesh((8,), ("data",))
        jax.set_mesh(mesh8)
        try:
            eng8 = build_engine(TrainPlan(algo="bsp", exchanger="asa16"),
                                model, sgd_momentum(), constant(0.01), mesh8)
            assert eng8.wire["k"] == 8
            assert eng8.wire["bytes_per_step"] > 0
        finally:
            jax.set_mesh(mesh)
    g = build_engine(TrainPlan(algo="gspmd"), model, sgd_momentum(),
                     constant(0.01), mesh)
    assert g.wire is None


# ---------------------------------------------------------------------------
# train loop integration: metrics recorded, first step split out
# ---------------------------------------------------------------------------

def test_train_loop_records_metrics_and_compile_split(capsys):
    from repro.optim import constant, sgd_momentum
    from repro.train.loop import train
    from tests.test_engine import _batches, _mesh1, _tiny_lm

    telemetry.set_enabled(True)
    cfg, model = _tiny_lm()
    mesh = _mesh1()
    n = 5
    _, report = train(model, sgd_momentum(), constant(0.01), mesh,
                      _batches(cfg, n), num_steps=n, log_every=2,
                      print_fn=lambda *a: None)
    assert report.steps == n
    assert report.compile_time > 0
    assert report.steady_examples_per_s > 0
    # steady-state rate excludes the compile step, so it beats the
    # total-wall-clock rate on a short run
    assert report.steady_examples_per_s > report.examples_per_s
    reg = telemetry.default_registry()
    assert reg["train/steps"].value == n
    assert reg["train/examples"].value == n * 8
    assert reg["train/tokens"].value == n * 8 * 32
    # the first (compile) step is excluded from the step-time histogram
    assert reg["train/step_time_s"].count == n - 1
    assert reg["train/data_time_s"].count == n
    assert reg["train/loss"].value == pytest.approx(report.losses[-1])
    # k=1 mesh: the analytic per-rank egress is zero, but the exchange
    # metrics/info are still published (nonzero-k math is pinned in
    # test_wire_summary_matches_hand_computation)
    assert reg["exchange/bytes_wire"].value == 0
    assert reg["exchange/config"].labels["strategy"] == "asa"
    assert reg["train/examples_per_s"].value > 0
    # the 6·params·tokens rate and its MFU are gone: for a convnet they
    # counted images as tokens
    assert "train/model_flops_s" not in reg.names()
    assert "train/mfu" not in reg.names()
    assert reg["train/plan"].labels["algo"] == "bsp"
    # spans made it into the trace buffer (data/step per step + flushes)
    names = {e[1] for e in trace.events()}
    assert {"train/data", "train/step", "train/compile_block",
            "train/flush", "train/final_block"} <= names


LOOP_SPANS = ("train", "train/data", "train/step", "train/flush")


@pytest.mark.parametrize("on", [True, False])
def test_train_loop_spans_reach_the_profiler_trace(tmp_path, on):
    """Inside a ``jax.profiler`` session the loop's step and phase spans are
    host events of the ``.xplane.pb``, by name; telemetry off records none."""
    from jax.profiler import ProfileData

    from repro.optim import constant, sgd_momentum
    from repro.train.loop import train
    from tests.test_engine import _batches, _mesh1, _tiny_lm

    cfg, model = _tiny_lm()
    mesh = _mesh1()
    telemetry.set_enabled(on)
    jax.profiler.start_trace(str(tmp_path))
    try:
        train(model, sgd_momentum(), constant(0.01), mesh, _batches(cfg, 4),
              num_steps=4, log_every=2, print_fn=lambda *a: None)
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    host = [e for plane in ProfileData.from_file(str(path)).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]
    count = {n: sum(e.name == n for e in host) for n in LOOP_SPANS}
    if on:
        # flushes: one read per boundary, each once the next step is
        # enqueued: step 0's in iteration 1, step 2's in iteration 3; the
        # last step's read follows train/final_block and waits on nothing
        assert count == {"train": 4, "train/data": 4, "train/step": 4,
                         "train/flush": 2}
        steps = [e for e in host if e.name == "train"]
        for e in host:
            if e.name in LOOP_SPANS[1:]:
                # each phase lies inside its step's span
                assert any(s.start_ns <= e.start_ns
                           and e.start_ns + e.duration_ns
                           <= s.start_ns + s.duration_ns for s in steps)
    else:
        assert count == dict.fromkeys(LOOP_SPANS, 0)


def test_train_loop_telemetry_off_identical_losses():
    from repro.optim import constant, sgd_momentum
    from repro.train.loop import train
    from tests.test_engine import _batches, _mesh1, _tiny_lm

    cfg, model = _tiny_lm()
    mesh = _mesh1()

    def run():
        _, rep = train(model, sgd_momentum(), constant(0.01), mesh,
                       _batches(cfg, 3), num_steps=3, log_every=0,
                       print_fn=lambda *a: None)
        return rep.losses

    telemetry.set_enabled(True)
    on = run()
    telemetry.set_enabled(False)
    off = run()
    assert on == off
    assert trace.events() == [] or not telemetry.enabled()


# ---------------------------------------------------------------------------
# serve parity + compile-once with telemetry on
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _serve_setup():
    from repro.configs import get_smoke_config
    from repro.models import build_model
    cfg = get_smoke_config("llama3.2-1b")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


def _serve_run():
    from repro.serve import Engine
    cfg, model, params = _serve_setup()
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, size=n).tolist()
               for n in (5, 12, 9, 17)]
    news = [6, 3, 9, 5]
    eng = Engine(model, params, max_slots=2, max_seq=64, prefill_chunk=8)
    rids = [eng.submit(p, m) for p, m in zip(prompts, news)]
    res = eng.run()
    return [res[r] for r in rids], eng


def test_serve_outputs_identical_telemetry_on_vs_off():
    """The whole point of host-side-only: enabling telemetry must not
    change a single generated token, and the decode step still compiles
    exactly once under churn."""
    telemetry.set_enabled(True)
    out_on, eng_on = _serve_run()
    assert eng_on.trace_counts["decode"] == 1
    assert eng_on.trace_counts["prefill"] == 1
    telemetry.set_enabled(False)
    out_off, eng_off = _serve_run()
    assert eng_off.trace_counts["decode"] == 1
    assert out_on == out_off


def test_serve_stats_live_with_telemetry_off():
    """EngineStats owns a private registry: TTFT/queue-wait/throughput must
    work with the global switch off."""
    telemetry.set_enabled(False)
    outs, eng = _serve_run()
    st = eng.stats
    # each request's first token is sampled at prefill, the rest in decode
    assert st.decoded_tokens == sum(len(o) for o in outs) - len(outs)
    assert st.admissions == 4
    ttft = st.ttft_percentiles()
    qw = st.queue_wait_percentiles()
    assert ttft[99] >= ttft[50] > 0
    assert qw[99] >= qw[50] >= 0
    for st_slot in eng.sched.finished.values():
        assert st_slot.req.ttft >= st_slot.req.queue_wait >= 0


def test_serve_request_lifecycle_spans():
    telemetry.set_enabled(True)
    outs, eng = _serve_run()
    evs = trace.events()
    by_name = {}
    for ph, name, *_ in evs:
        by_name.setdefault(name, []).append(ph)
    # every admitted request opens and closes each lifecycle stage
    for stage in ("serve/req/queued", "serve/req/prefill",
                  "serve/req/decode"):
        assert by_name[stage].count("b") == 4, stage
        assert by_name[stage].count("e") == 4, stage
    assert "serve/prefill" in by_name and "serve/decode_step" in by_name
    # registry-side accounting agrees with the scheduler's view
    st = eng.stats
    reg = st.registry
    assert reg["serve/admissions"].value == 4
    assert reg["serve/evictions"].value == 4       # all requests finished
    assert reg["serve/decoded_tokens"].value == st.decoded_tokens
    assert reg["serve/ttft_s"].count == 4
