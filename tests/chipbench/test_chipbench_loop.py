"""The training loop's phase metrics, read from the loop's host spans and
the devices' busy time in a traced stretch."""
import pytest

import cbtiny

cbtiny.use_harness()

import loopspans  # noqa: E402
import tracereduce as tr  # noqa: E402
from spec import Spec  # noqa: E402

TESTDATA = cbtiny.CHIPBENCH / "testdata"
LOOP = ("loop.data_ms", "loop.dispatch_ms", "loop.sync_ms", "loop.host_ms",
        "device.idle_host_share")
MS = 1_000_000          # nanoseconds
# the five metrics of the recorded two-step trace with the loop's spans
RECORDED = {"loop.data_ms": 0.0352695, "loop.dispatch_ms": 18.248438,
            "loop.sync_ms": 23.9977305, "loop.host_ms": 18.4266345,
            "device.idle_host_share": 8.87890801567633}


def _readers():
    spec = Spec(cbtiny.CHIPBENCH)
    return {name: spec.metric_reader(name) for name in LOOP}


def _summary(busy_per_device, host, window=(0, 100 * MS)):
    devices = [tr.Device([]) for _ in busy_per_device]
    for d, busy in zip(devices, busy_per_device):
        d.busy = tr.union(tr.clip([(s * MS, e * MS) for s, e in busy],
                                  window))
    host = [(n, s * MS, e * MS, depth) for n, s, e, depth in host]
    return tr.Summary(window, devices, [("chipbench/traced", *window, 0)]
                      + host)


# a stretch of 100 ms and two steps: the first iteration is cut by the
# stretch's start (its dispatch alone is traced), two whole ones follow,
# and a step span runs past the end
HOST = [("train/step", 2, 8, 0),
        ("train", 10, 50, 0), ("train/data", 11, 13, 1),
        ("train/step", 14, 24, 1), ("train/flush", 30, 40, 1),
        ("train", 52, 96, 0), ("train/data", 53, 54, 1),
        ("train/step", 55, 65, 1),
        ("DeferredTpuAllocator::Allocate", 60, 64, 2),
        ("train", 98, 130, 0)]
BUSY = [(5, 30), (45, 58), (70, 95)]


def test_loop_metrics_on_a_hand_built_stretch():
    readers = _readers()
    ctx = {"trace": _summary([BUSY, [(0, 100)]], HOST), "traced_steps": 2}
    got = {name: r.read(ctx) for name, r in readers.items()}
    # host work: 2..8, 10..30, 40..50, 52..96, 98..100 (the flush excluded)
    assert got["loop.host_ms"] == pytest.approx(82 / 2)
    assert got["loop.data_ms"] == pytest.approx(3 / 2)
    assert got["loop.dispatch_ms"] == pytest.approx(26 / 2)
    assert got["loop.sync_ms"] == pytest.approx(10 / 2)
    # chip 0 idles 0..5, 30..45, 58..70, 95..100 (37%); of that, 2..5,
    # 40..45, 58..70, 95..96 and 98..100 are host work: 23%. Chip 1 is
    # busy throughout.
    assert got["device.idle_host_share"] == pytest.approx(23 / 2)
    only0 = dict(ctx, trace=_summary([BUSY], HOST))
    assert readers["device.idle_host_share"].read(only0) == pytest.approx(23)
    assert 100 * only0["trace"].idle_share() == pytest.approx(37)


def test_loop_metrics_without_flushes_read_no_sync():
    host = [h for h in HOST if h[0] != "train/flush"]
    ctx = {"trace": _summary([BUSY], host), "traced_steps": 2}
    readers = _readers()
    assert readers["loop.sync_ms"].read(ctx) == 0
    assert readers["loop.host_ms"].read(ctx) == pytest.approx(92 / 2)


@pytest.mark.parametrize("ctx,names", [
    ({"trace": None, "traced_steps": 2}, LOOP),
    ({"trace": _summary([BUSY], [("PjitFunction(step)", 10, 50, 0)]),
      "traced_steps": 2}, LOOP),
    # a share of the stretch needs no count of its steps
    ({"trace": _summary([BUSY], HOST), "traced_steps": None}, LOOP[:4])],
    ids=["no-trace", "no-loop-spans", "no-steps"])
def test_loop_metrics_are_none_without_their_spans(ctx, names):
    for name, reader in _readers().items():
        assert (reader.read(ctx) is None) == (name in names), name


@pytest.mark.parametrize("name,steps,spans", [
    ("alexnet-bsp-1chip.2steps.xplane.pb", 2, False),
    ("alexnet-bsp-1chip.spans.2steps.xplane.pb", 2, True)])
def test_loop_metrics_on_a_recorded_trace(name, steps, spans):
    """Two steps of ``alexnet-bsp-1chip`` recorded on a TPU v5e by a
    ``--trace 1`` run and cut by ``testdata/trim_trace.py``: a program
    without the loop's spans gives no loop metric; one with them gives
    all five, the host-caused idle within the whole idle."""
    trace = tr.reduce(str(TESTDATA / name), 1)
    ctx = {"trace": trace, "traced_steps": steps}
    got = {name: r.read(ctx) for name, r in _readers().items()}
    if not spans:
        assert got == dict.fromkeys(LOOP)
        return
    assert all(v is not None and v >= 0 for v in got.values()), got
    assert got["device.idle_host_share"] <= 100 * trace.idle_share()
    assert (got["loop.data_ms"] + got["loop.dispatch_ms"]
            <= got["loop.host_ms"])
    # the edge: the iteration the stretch's start cuts has no step span,
    # and its dispatch counts as host work all the same
    steps = loopspans.spans(trace, loopspans.STEP)
    first = loopspans.spans(trace, loopspans.DISPATCH)[0]
    assert first[1] <= steps[0][0]
    assert tr.length(tr.intersect(loopspans.host_work(trace), [first])) == (
        first[1] - first[0])
    # the host ran ahead: the cut holds the dispatch of ten steps and the
    # first two flushes, one of them clipped by the stretch's end
    assert got == pytest.approx(RECORDED, rel=1e-6)
