"""The reduction from a profiler trace to the per-layer numbers."""
import pytest

import cbtiny

cbtiny.use_harness()

import kernelcost  # noqa: E402
import tracereduce as tr  # noqa: E402

TESTDATA = cbtiny.CHIPBENCH / "testdata"


def _op(name, start, end, operand="%x"):
    """An event as the trace names it: its HLO instruction's text."""
    opcode = name.rsplit(".", 1)[0]
    return tr.Op(f"%{name} = f32[8]{{0}} {opcode}(f32[8]{{0}} {operand}), "
                 f"metadata={{op_name=\"jit(step)\"}}", start, end)


def _summary(ops_per_device, window=(0, 100), host=()):
    devices = [tr.Device([_op(*o) for o in ops]) for ops in ops_per_device]
    for d in devices:
        d.busy = tr.union(tr.clip(d.busy, window))
    return tr.Summary(window, devices, list(host))


def test_busy_union_idle_share_and_gaps():
    s = _summary([[("fusion.1", 10, 30), ("convolution.7", 30, 40),
                   ("fusion.2", 60, 70), ("copy.3", 95, 120)]],
                 host=[("PjitFunction(step)", 40, 60, 0),
                       ("chipbench/traced", 0, 100, 0)])
    assert s.busy_s == pytest.approx(45e-9)
    assert s.window_s == pytest.approx(100e-9)
    assert s.idle_share() == pytest.approx(0.55)
    assert s.idle_gaps() == [["no host event", pytest.approx(25e-9)],
                             ["PjitFunction(step)", pytest.approx(20e-9)],
                             ["no host event", pytest.approx(10e-9)]]
    ops = s.op_seconds()
    assert ops["fusion"] == pytest.approx(30e-9)
    assert ops["convolution"] == pytest.approx(10e-9)


def test_collectives_and_their_exposed_part():
    dev = [("all-gather-start.4", 10, 12), ("fusion.1", 12, 30),
           ("all-gather-done.7", 40, 42, "%all-gather-start.4"),
           ("while.2", 45, 70), ("all-to-all.2", 50, 60),
           ("fusion.3", 62, 65)]
    s = _summary([dev, dev])
    total, exposed = s.collective_s()
    # in flight 10..42 and 50..60; compute covers 12..30 only (the loop
    # that holds the all-to-all hides nothing by itself)
    assert total == pytest.approx(42e-9)
    assert exposed == pytest.approx(42e-9 - 18e-9)
    ops = s.op_seconds()
    assert ops["while"] == pytest.approx(2 * 12e-9)
    assert ops["all-to-all"] == pytest.approx(2 * 10e-9)


def test_interval_helpers():
    assert tr.union([(5, 7), (1, 3), (2, 4)]) == [[1, 4], [5, 7]]
    assert tr.intersect([[1, 4], [5, 9]], [[3, 6]]) == [[3, 4], [5, 6]]
    assert tr.clip([(0, 5), (8, 12)], (2, 10)) == [(2, 5), (8, 10)]
    op = tr.Op("%fused_rs_update.16 = (f32[2048]{0:T(1024)}, f32[2048]{0}) "
               "custom-call(f16[4,2048]{1,0} %a, f32[2048]{0} %b), "
               "custom_call_target=\"tpu_custom_call\", "
               "operand_layout_constraints={f16[4,2048]{1,0}, f32[2048]{0}}",
               0, 1)
    assert (op.stable, op.opcode) == ("fused_rs_update", "custom-call")
    assert kernelcost.hlo_bytes(op) == 2 * 2048 * 4 + 4 * 2048 * 2 + 2048 * 4


# the buckets of AlexNet's parameters over 2048 elements, padded to the
# kernel's 2048-element blocks: one fused_rs_update call each per step
ALEXNET_BUCKETS = sorted([36864, 307200, 884736, 663552, 442368, 4096,
                          37748736, 4096, 16777216, 4096000])


@pytest.mark.parametrize("name,chips,steps", [
    ("alexnet-bsp-1chip.2steps.xplane.pb", 1, 2)])
def test_recorded_trace(name, chips, steps):
    """Two steps of a cell, recorded on a TPU v5e by a ``--trace 1`` run and
    cut down by ``testdata/trim_trace.py``."""
    s = tr.reduce(str(TESTDATA / name), chips)
    assert s is not None and len(s.devices) == chips
    assert 0 < s.busy_s < s.window_s
    assert 0 < s.idle_share() < 0.5
    calls = s.kernel_ops("fused_rs_update")
    assert sorted(int(op.result.split("[")[1].split("]")[0])
                  for op in calls) == sorted(ALEXNET_BUCKETS * steps * chips)
    for op in calls:
        n = int(op.result.split("[")[1].split("]")[0])
        wire = 4 if chips == 1 else 2
        assert kernelcost.hlo_bytes(op) == kernelcost.fused_rs_update_cost(
            chips, n, wire)[0]
    total, exposed = s.collective_s()
    assert 0 <= exposed <= total
    assert (total > 0) == (chips > 1)
    br = s.breakdown()
    assert br["device_ops"][1][0] == "fused_rs_update"
    assert 0 < len(br["device_ops"]) <= 10 and len(br["idle_gaps"]) <= 10
