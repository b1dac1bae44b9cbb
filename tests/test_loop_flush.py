"""The training loop reads its losses one step behind (repro.train.loop):
a boundary's blocking read comes only once the next step is enqueued, and
what the loop prints, gauges and reports is what a loop that reads every
loss as soon as it is enqueued would give, bit for bit."""
import jax
import pytest

from repro import telemetry
from repro.optim import sgd_momentum, warmup_cosine
from repro.telemetry import trace
from repro.telemetry.registry import MemorySink
from repro.train.engine import TrainPlan, build_engine
import repro.train.loop as loop_mod
from repro.train.loop import train
from tests.test_engine import _batches, _mesh1, _tiny_lm

SEED = 3


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    was_on, was_gn = telemetry.enabled(), telemetry.config().grad_norm
    telemetry.reset()
    trace.reset()
    telemetry.set_enabled(True)
    yield
    telemetry.set_enabled(was_on)
    telemetry.configure(grad_norm=was_gn)
    telemetry.reset()
    trace.reset()


def _lr():
    return warmup_cosine(0.05, 3, 20)


def _gauges(sink, name):
    """The values of gauge ``name`` in each snapshot the sink received
    before the loop's final forced flush."""
    return [r["value"] for snap in sink.snapshots[:-1] for r in snap
            if r["name"] == name]


def _synchronous(model, cfg, n, log_every):
    """The reference: the same engine stepped by hand, every loss read as
    soon as its step is enqueued."""
    mesh = _mesh1()
    engine = build_engine(TrainPlan(), model, sgd_momentum(), _lr(), mesh)
    state = engine.init_state(jax.random.key(SEED))
    rng = jax.random.key(SEED + 1)
    losses, lines, logged, lrs, norms = [], [], [], [], []
    for i, batch in enumerate(_batches(cfg, n)):
        state, m = engine.step(state, batch, jax.random.fold_in(rng, i),
                               step_idx=i)
        losses.append(float(m["loss"]))
        if log_every and (i % log_every == 0 or i == n - 1):
            lines.append(f"step {i:5d}  loss {losses[-1]:.4f}")
            logged.append(losses[-1])
            lrs.append(float(_lr()(i)))
            norms.append(float(m["grad_norm"]))
    return losses, lines, logged, lrs, norms


@pytest.mark.parametrize("log_every,cap,n", [
    (2, 100, 8),      # log boundaries only, the last step among them
    (3, 100, 8),      # the last step is a boundary of its own
    (0, 3, 8),        # no logging: the buffer moves every 3 losses
    (5, 2, 9),        # buffer moves between log boundaries
])
def test_loop_output_matches_synchronous_reads(monkeypatch, log_every, cap,
                                               n):
    monkeypatch.setattr(loop_mod, "_FLUSH_CAP", cap)
    telemetry.configure(grad_norm=True)
    cfg, model = _tiny_lm()
    want = _synchronous(model, cfg, n, log_every)
    telemetry.reset()
    sink = MemorySink()
    telemetry.add_sink(sink)
    lines = []
    _, report = train(model, sgd_momentum(), _lr(), _mesh1(),
                      _batches(cfg, n), num_steps=n, seed=SEED,
                      log_every=log_every, print_fn=lines.append)
    assert report.losses == want[0]
    assert lines == want[1]
    assert _gauges(sink, "train/loss") == want[2]
    assert _gauges(sink, "train/lr") == want[3]
    assert _gauges(sink, "train/grad_norm") == want[4]
    assert telemetry.default_registry()["train/queue_drains"].value == 0


def test_boundary_read_follows_the_next_enqueue():
    """With ``log_every=2`` over 8 steps, each boundary's ``train/flush``
    starts after the ``train/step`` of the step after it; the last step's
    read has no flush span, coming after ``train/final_block``."""
    cfg, model = _tiny_lm()
    n = 8
    train(model, sgd_momentum(), _lr(), _mesh1(), _batches(cfg, n),
          num_steps=n, log_every=2, print_fn=lambda *a: None)
    events = trace.events()
    enqueued = {e[5]["step"]: e[2] + e[3] for e in events
                if e[1] == "train/step"}
    flushes = [(e[5]["step"], e[2]) for e in events if e[1] == "train/flush"]
    assert [j for j, _ in flushes] == [0, 2, 4, 6]
    for j, start in flushes:
        assert start >= enqueued[j + 1]
    (final,) = [e[2] for e in events if e[1] == "train/final_block"]
    assert all(start < final for _, start in flushes)
    assert telemetry.default_registry()["train/queue_drains"].value == 0


def test_checkpoint_saves_count_as_queue_drains(tmp_path, monkeypatch):
    monkeypatch.setattr(loop_mod, "save_checkpoint", lambda *a, **kw: None)
    cfg, model = _tiny_lm()
    train(model, sgd_momentum(), _lr(), _mesh1(), _batches(cfg, 7),
          num_steps=7, log_every=2, ckpt_path=str(tmp_path / "ck"),
          ckpt_every=3, print_fn=lambda *a: None)
    # in-loop saves after steps 3 and 6; the final save follows
    # train/final_block
    assert telemetry.default_registry()["train/queue_drains"].value == 2


def test_lr_gauge_without_a_cpu_backend_counts_a_drain(monkeypatch):
    """Where JAX has no CPU backend the schedule runs on the default
    device, behind the queued steps: each logged read is then a drain."""
    real = jax.local_devices

    def no_cpu(*a, backend=None, **kw):
        if backend == "cpu":
            raise RuntimeError("Unknown backend cpu")
        return real(*a, backend=backend, **kw)

    monkeypatch.setattr(jax, "local_devices", no_cpu)
    cfg, model = _tiny_lm()
    sink = MemorySink()
    telemetry.add_sink(sink)
    train(model, sgd_momentum(), _lr(), _mesh1(), _batches(cfg, 5),
          num_steps=5, log_every=2, print_fn=lambda *a: None)
    assert _gauges(sink, "train/lr") == [float(_lr()(i)) for i in (0, 2, 4)]
    assert telemetry.default_registry()["train/queue_drains"].value == 3
