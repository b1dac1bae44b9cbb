"""Fault-tolerance stack tests: injection plans, membership/quorum math,
crash-safe checkpoints, loader failure propagation, and the elastic
end-to-end properties (quorum parity, staleness absorption, replay
determinism, preempt->resume) on 8 virtual CPU devices."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.fault.inject import (FaultEvent, FaultPlan, bitflip,
                                payload_checksum)
from repro.fault.membership import MembershipController, WorkerState
from repro.launch.mesh import make_mesh


# ---------------------------------------------------------------------------
# FaultPlan: spec grammar, ordering, seeded determinism
# ---------------------------------------------------------------------------

def test_fault_event_validation():
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultEvent("explode", 0, 1)
    with pytest.raises(ValueError, match=">= 0"):
        FaultEvent("kill", -1, 1)
    with pytest.raises(ValueError, match="rounds"):
        FaultEvent("straggle", 0, 1, rounds=0)


def test_fault_plan_spec_roundtrip():
    spec = "kill:1@9,straggle:2@5x3,corrupt:0@13"
    plan = FaultPlan.from_spec(spec, seed=7)
    # events sort by (step, worker); to_spec reflects that order
    assert plan.to_spec() == "straggle:2@5x3,kill:1@9,corrupt:0@13"
    assert FaultPlan.from_spec(plan.to_spec(), seed=7) == plan
    assert plan.events_at(9) == [FaultEvent("kill", 1, 9)]
    assert plan.events_at(5)[0].rounds == 3
    assert plan.events_at(4) == []
    with pytest.raises(ValueError, match="bad fault spec"):
        FaultPlan.from_spec("kill:1")
    with pytest.raises(ValueError, match="bad fault spec"):
        FaultPlan.from_spec("kill:one@2")


def test_fault_plan_random_is_seed_deterministic():
    a = FaultPlan.random(3, num_workers=4, num_steps=50)
    b = FaultPlan.random(3, num_workers=4, num_steps=50)
    c = FaultPlan.random(4, num_workers=4, num_steps=50)
    assert a == b
    assert a != c
    # kills never empty the fleet
    heavy = FaultPlan.random(0, num_workers=2, num_steps=50, n_events=20,
                             kinds=("kill",))
    assert sum(e.kind == "kill" for e in heavy.events) <= 1


def test_event_rng_and_bitflip_determinism():
    plan = FaultPlan.from_spec("corrupt:0@3,corrupt:1@7", seed=11)
    e0, e1 = plan.events
    x = np.arange(16, dtype=np.float32)
    f1 = bitflip(x, plan.event_rng(e0))
    f2 = bitflip(x, plan.event_rng(e0))
    assert np.array_equal(f1, f2)                      # same event -> same bit
    assert not np.array_equal(f1, bitflip(x, plan.event_rng(e1)))
    assert np.array_equal(x, np.arange(16, dtype=np.float32))  # input intact
    # crc32 catches every single-bit flip (here and for bf16-width dtypes)
    assert payload_checksum(f1) != payload_checksum(x)
    h = np.arange(8, dtype=np.float16)
    hf = bitflip(h, plan.event_rng(e0))
    assert payload_checksum(hf) != payload_checksum(h)
    # list payloads chain the crc
    assert payload_checksum([x, h]) != payload_checksum([f1, h])


# ---------------------------------------------------------------------------
# MembershipController: quorum boundary, staleness, weights, join/leave
# ---------------------------------------------------------------------------

def test_quorum_boundary_exactly_at_vs_one_below():
    c = MembershipController(range(4), alpha=0.5, quorum=3)
    assert c.quorum_count == 3
    assert c.has_quorum([0, 1, 2])           # exactly at
    assert not c.has_quorum([0, 1])          # one below
    # default: majority of the live fleet
    d = MembershipController(range(4), alpha=0.5)
    assert d.quorum_count == 3
    assert MembershipController(range(5), alpha=0.5).quorum_count == 3
    assert MembershipController([7], alpha=0.5).quorum_count == 1


def test_round_weights_hand_computed_staleness():
    alpha = 0.5
    c = MembershipController(range(4), alpha=alpha, quorum=2)
    # age worker 1 one round, worker 3 three rounds
    c.commit_round([0, 2, 3])                # 1 ages to 1
    assert c.staleness_of(1) == 1
    for _ in range(3):
        c.commit_round([0, 1, 2])            # 3 ages to 3, 1 resets
    assert c.staleness_of(3) == 3 and c.staleness_of(1) == 0
    absorb, attract = c.round_weights([0, 1, 3])
    # absorb_i = alpha / (1 + staleness_i); non-reporting row 2 gets 0
    np.testing.assert_allclose(
        absorb, [alpha / 1, alpha / 1, 0.0, alpha / 4], rtol=0, atol=0)
    np.testing.assert_array_equal(absorb, attract)
    assert absorb.dtype == np.float32


def test_skip_round_ages_everyone():
    c = MembershipController(range(3), alpha=0.5, quorum=3)
    c.skip_round()
    c.skip_round()
    assert [c.staleness_of(w) for w in range(3)] == [2, 2, 2]
    a, _ = c.round_weights([0, 1, 2])
    np.testing.assert_allclose(a, [0.5 / 3] * 3)


def test_straggler_lifecycle():
    c = MembershipController(range(3), alpha=0.5, quorum=1)
    assert c.straggle(1, rounds=2)
    assert c.state_of(1) == WorkerState.STRAGGLING
    assert c.reporting() == [0, 2]
    c.commit_round(c.reporting())            # round 1 missed
    assert c.reporting() == [0, 2]
    c.commit_round(c.reporting())            # round 2 missed
    assert c.reporting() == [0, 1, 2]        # straggle expired
    assert c.staleness_of(1) == 2            # absorbed late next round
    assert not c.straggle(99)                # unknown worker


def test_kill_join_at_round_boundary():
    c = MembershipController(range(3), alpha=0.5, num_slots=4)
    assert c.kill(1)
    assert not c.kill(1)                     # idempotent
    assert c.state_of(1) == WorkerState.LEAVING
    assert c.reporting() == [0, 2]           # killed never reports
    assert c.request_join(5)
    assert c.state_of(5) == WorkerState.JOINING
    assert c.workers == (0, 1, 2)            # nothing applied yet
    old, new, left, joined = c.apply_pending()
    assert old == (0, 1, 2) and new == (0, 2, 5)
    assert left == (1,) and joined == (5,)
    assert c.state_of(1) == WorkerState.DEAD
    assert c.staleness_of(5) == 0            # joiner starts at the center
    # slot 1 was freed and reused by the joiner
    assert c.slot_of(5) == 1


def test_join_rejected_when_no_slot_free():
    c = MembershipController(range(2), alpha=0.5, num_slots=2)
    assert c.request_join(9)
    old, new, left, joined = c.apply_pending()
    assert new == (0, 1) and joined == ()
    assert c.rejected_joins == 1


def test_fleet_cannot_empty():
    c = MembershipController([0], alpha=0.5)
    c.kill(0)
    with pytest.raises(RuntimeError, match="emptied the fleet"):
        c.apply_pending()


def test_controller_validation():
    with pytest.raises(ValueError, match="duplicate"):
        MembershipController([1, 1], alpha=0.5)
    with pytest.raises(ValueError, match="at least one"):
        MembershipController([], alpha=0.5)
    with pytest.raises(ValueError, match="quorum"):
        MembershipController([0], alpha=0.5, quorum=0)
    with pytest.raises(ValueError, match="slots"):
        MembershipController(range(3), alpha=0.5, num_slots=2)


def test_trainplan_quorum_validation():
    from repro.train.engine import TrainPlan, build_engine
    with pytest.raises(ValueError, match="quorum"):
        TrainPlan(algo="bsp", quorum=2)
    with pytest.raises(ValueError, match="quorum"):
        TrainPlan(algo="easgd", quorum=0)
    plan = TrainPlan(algo="easgd", quorum=2, exchanger="ar")
    with pytest.raises(ValueError, match="elastic"):
        build_engine(plan, None, None, None, None)


# ---------------------------------------------------------------------------
# crash-safe checkpoints
# ---------------------------------------------------------------------------

def _ck_state(v):
    return {"params": {"w": np.full((4,), float(v), np.float32)},
            "step": np.asarray(v, np.int32)}


def test_ckpt_retention_and_layout(tmp_path):
    from repro.checkpoint.ckpt import latest_step, save_checkpoint
    d = str(tmp_path / "ck")
    for s in (1, 2, 3, 4):
        save_checkpoint(d, _ck_state(s), step=s, keep=2)
    names = sorted(os.listdir(d))
    assert names == ["meta-00000003.json", "meta-00000004.json",
                     "meta.json", "state-00000003.npz",
                     "state-00000004.npz"]
    assert latest_step(d) == 4


def test_ckpt_truncation_falls_back(tmp_path):
    from repro.checkpoint.ckpt import restore_for_resume, save_checkpoint
    d = str(tmp_path / "ck")
    save_checkpoint(d, _ck_state(3), step=3, algo="bsp")
    save_checkpoint(d, _ck_state(6), step=6, algo="bsp")
    # truncate the latest state file mid-write (simulated torn save)
    p = os.path.join(d, "state-00000006.npz")
    with open(p, "r+b") as f:
        f.truncate(os.path.getsize(p) // 2)
    with pytest.warns(RuntimeWarning, match="falling back"):
        state, step = restore_for_resume(d, _ck_state(0), expect_algo="bsp")
    assert step == 3 and float(state["params"]["w"][0]) == 3.0


def test_ckpt_bit_corruption_detected(tmp_path):
    from repro.checkpoint.ckpt import restore_checkpoint, save_checkpoint
    d = str(tmp_path / "ck")
    save_checkpoint(d, _ck_state(1), step=1)
    save_checkpoint(d, _ck_state(2), step=2)
    p = os.path.join(d, "state-00000002.npz")
    raw = bytearray(open(p, "rb").read())
    raw[len(raw) // 2] ^= 0x10
    open(p, "wb").write(bytes(raw))
    with pytest.warns(RuntimeWarning, match="falling back"):
        state = restore_checkpoint(d, _ck_state(0))
    assert float(state["params"]["w"][0]) == 1.0


def test_ckpt_no_valid_checkpoint_is_loud(tmp_path):
    from repro.checkpoint.ckpt import save_checkpoint, restore_checkpoint
    d = str(tmp_path / "ck")
    save_checkpoint(d, _ck_state(1), step=1, keep=1)
    os.unlink(os.path.join(d, "state-00000001.npz"))
    with pytest.raises(FileNotFoundError, match="integrity"):
        restore_checkpoint(d, _ck_state(0))


def test_ckpt_legacy_single_file_layout(tmp_path):
    from repro.checkpoint.ckpt import restore_for_resume
    d = tmp_path / "ck"
    d.mkdir()
    st = _ck_state(5)
    np.savez(d / "state.npz", **{"params/w": st["params"]["w"],
                                 "step": st["step"]})
    (d / "meta.json").write_text(json.dumps({"step": 5}))
    state, step = restore_for_resume(str(d), _ck_state(0))
    assert step == 5 and float(state["params"]["w"][0]) == 5.0


def test_ckpt_workers_recorded(tmp_path):
    from repro.checkpoint.ckpt import load_meta, save_checkpoint
    d = str(tmp_path / "ck")
    save_checkpoint(d, _ck_state(1), step=1, algo="easgd",
                    workers=(0, 2, 5))
    meta = load_meta(d)
    assert meta["workers"] == [0, 2, 5] and meta["algo"] == "easgd"


# ---------------------------------------------------------------------------
# ParallelLoader failure propagation (the hang fix)
# ---------------------------------------------------------------------------

def test_loader_worker_exception_propagates(tmp_path):
    from repro.data.prefetch import LoaderError, ParallelLoader
    ok = str(tmp_path / "ok.npz")
    np.savez(ok, x=np.arange(4))
    l = ParallelLoader([ok, str(tmp_path / "missing.npz"), ok], timeout=30)
    got = list()
    with pytest.raises(LoaderError, match="FileNotFoundError"):
        for b in l:
            got.append(b)
    assert len(got) == 1
    with pytest.raises(LoaderError):         # failure is terminal
        l.get()
    l.stop()                                 # and stop() still returns


def test_loader_get_times_out_with_diagnosis(tmp_path):
    from repro.data.prefetch import ParallelLoader
    ok = str(tmp_path / "ok.npz")
    np.savez(ok, x=np.arange(4))
    l = ParallelLoader([ok], io_delay_ms=60_000, timeout=0.2)
    with pytest.raises(TimeoutError, match="loader thread"):
        l.get()


def test_loader_normal_stream_unaffected(tmp_path):
    from repro.data.prefetch import ParallelLoader
    ok = str(tmp_path / "ok.npz")
    np.savez(ok, x=np.arange(4))
    l = ParallelLoader([ok, ok, ok], timeout=30)
    assert len(list(l)) == 3


# ---------------------------------------------------------------------------
# elastic end-to-end properties (8 virtual devices, subprocess)
# ---------------------------------------------------------------------------

_SCRIPT = r"""
import json, os, tempfile
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import numpy as np
import jax

from repro.configs import get_smoke_config
from repro.data.synthetic import LMTokenSource
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.optim import constant, sgd_momentum
from repro.train.engine import TrainPlan, build_engine
from repro.fault.elastic import Preempted, elastic_train

cfg = get_smoke_config("llama3.2-1b").with_overrides(
    vocab_size=64, d_ff=128, num_layers=2, dtype="float32")
model = build_model(cfg)
opt = sgd_momentum(weight_decay=0.0)
src = LMTokenSource(cfg.vocab_size, 16, seed=0)
batch_fn = lambda step, k: src.batch(4 * k, step)

def center_of(state):
    return [np.asarray(l, np.float32) for l in jax.tree.leaves(state["center"])]

def maxerr(a, b):
    return max(float(np.abs(x - y).max()) for x, y in zip(a, b))

out = {}

# 1) quorum path at full participation == the fixed sync step, exactly
plan_q = TrainPlan(algo="easgd", tau=2, alpha=0.5, exchanger="ar", quorum=4)
sq, _ = elastic_train(model, opt, constant(0.05), batch_fn, plan=plan_q,
                      num_workers=4, num_steps=8, seed=0, print_fn=None)
mesh = make_mesh((4,), ("data",))
# the fixed-membership programs run under their mesh; the elastic runs
# below build their own meshes as the fleet changes
ctx = jax.set_mesh(mesh)
eng = build_engine(TrainPlan(algo="easgd", tau=2, alpha=0.5, exchanger="ar"),
                   model, opt, constant(0.05), mesh)
st = eng.init_state(jax.random.key(0))
rng = jax.random.key(1)
for i in range(8):
    st, _ = eng.step(st, batch_fn(i, 4), jax.random.fold_in(rng, i),
                     step_idx=i)
out["quorum_parity_err"] = maxerr(center_of(sq), center_of(st))

# 2) one quorum round against a numpy reference: c' = c + sum_i w_i*(x_i-c)
from repro.train.engine import build_elastic_programs
progs = build_elastic_programs(plan_q, model, opt, constant(0.0), mesh)
state = progs.init_state(jax.random.key(2))
state, _ = progs.local(state, batch_fn(0, 4), jax.random.fold_in(rng, 0))
pre_stack = [np.asarray(l, np.float32)
             for l in jax.tree.leaves(state["params"])]
pre_center = center_of(state)
absorb = np.asarray([0.5, 0.25, 0.0, 0.125], np.float32)  # staleness 0,1,-,3
# lr=0 -> the sync step's local update is a no-op, params stay pre_stack
state2, _ = progs.sync(state, batch_fn(1, 4), jax.random.fold_in(rng, 1),
                       absorb, absorb)
ctx.__exit__(None, None, None)
expect = [c + sum(absorb[i] * (s[i] - c) for i in range(4))
          for s, c in zip(pre_stack, pre_center)]
out["absorb_math_err"] = maxerr(center_of(state2), expect)
# non-reporting row 2 kept its params bit-identically
post_stack = [np.asarray(l, np.float32)
              for l in jax.tree.leaves(state2["params"])]
out["nonreporting_untouched"] = bool(all(
    np.array_equal(a[2], b[2]) for a, b in zip(pre_stack, post_stack)))

# 3) chaos replay determinism + kill/rejoin convergence
plan = TrainPlan(algo="easgd", tau=4, alpha=0.5, exchanger="ar", quorum=2)
spec = "kill:3@9,straggle:2@13x2,corrupt:1@21,drop:0@29,join:3@33"
def chaos(**kw):
    return elastic_train(model, opt, constant(0.05), batch_fn, plan=plan,
                         num_workers=4, num_steps=40, seed=0,
                         fault_plan=spec, print_fn=None, **kw)
s1, r1 = chaos()
s2, r2 = chaos()
out["replay_bitwise"] = bool(all(
    np.array_equal(a, b) for a, b in zip(center_of(s1), center_of(s2))))
out["replay_round_log"] = r1.round_log == r2.round_log
out["chaos_first_loss"] = r1.losses[0]
out["chaos_last_loss"] = r1.losses[-1]
out["chaos_counts"] = dict(kills=r1.kills, joins=r1.joins,
                           rebuilds=r1.rebuilds, corrupt=r1.payloads_corrupt,
                           dropped=r1.payloads_dropped,
                           skipped=r1.rounds_skipped_quorum)
out["final_workers"] = list(r1.final_workers)
# staleness audit: at the step-23 round the returning straggler (worker 2,
# staleness 2) is absorbed with alpha/(1+2) while worker 1's payload is
# corrupt-excluded (weight 0); row order is (0, 1, 2) after the kill
out["late_absorb_weight"] = [w for s, rep, w in r1.round_log if s == 23][0]

# 4) preempt -> resume loss band, per algo
bands = {}
for algo, lr in (("easgd", 0.05), ("asgd", 0.02)):
    p = TrainPlan(algo=algo, tau=4, alpha=0.5 if algo == "easgd" else None,
                  exchanger="ar", quorum=2)
    def run(**kw):
        return elastic_train(model, opt, constant(lr), batch_fn, plan=p,
                             num_workers=4, num_steps=32, seed=0,
                             fault_plan="kill:3@9", print_fn=None, **kw)
    _, ref = run()
    d = tempfile.mkdtemp()
    try:
        run(ckpt_path=d, ckpt_every=8, stop_at_step=18)
        bands[algo] = dict(preempted=False)
        continue
    except Preempted:
        pass
    _, res = run(resume_from=d)
    bands[algo] = dict(preempted=True, ref=ref.losses[-1],
                       resumed=res.losses[-1], steps=res.steps)
out["resume"] = bands
print("RESULTS_JSON:" + json.dumps(out))
"""


def test_elastic_properties_multidevice():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = None
    for line in proc.stdout.splitlines():
        if line.startswith("RESULTS_JSON:"):
            out = json.loads(line[len("RESULTS_JSON:"):])
    assert out is not None, proc.stdout[-2000:]
    # full participation at staleness 0 == the fixed sync step, exactly
    assert out["quorum_parity_err"] == 0.0, out
    # center math matches the numpy reference, non-reporters untouched
    assert out["absorb_math_err"] < 1e-5, out
    assert out["nonreporting_untouched"], out
    # seeded chaos replay is bit-identical
    assert out["replay_bitwise"] and out["replay_round_log"], out
    # every injected fault kind actually fired, and the fleet healed
    assert out["chaos_counts"] == dict(kills=1, joins=1, rebuilds=2,
                                       corrupt=1, dropped=1, skipped=0), out
    assert out["final_workers"] == [0, 1, 2, 3], out
    # chaos run still trains through kill/corrupt/drop/rejoin
    assert out["chaos_last_loss"] < 0.6 * out["chaos_first_loss"], out
    # the straggler's delta was absorbed late at alpha/(1+2); the
    # corrupt-excluded worker contributed nothing that round
    w = out["late_absorb_weight"]
    assert abs(w[2] - 0.5 / 3) < 1e-6, out
    assert abs(w[0] - 0.5) < 1e-6 and w[1] == 0.0, out
    # preempt -> resume: full step count, same band as uninterrupted
    for algo in ("easgd", "asgd"):
        r = out["resume"][algo]
        assert r["preempted"], out
        assert r["steps"] == 32, out
        assert abs(r["resumed"] - r["ref"]) <= 0.05 * max(
            1.0, abs(r["ref"])), out


def test_bsp_restart_after_corrupt_checkpoint(tmp_path):
    """bsp/gspmd fault tolerance is checkpoint restart: corrupting the
    latest checkpoint must fall back to an earlier valid one and the
    resumed run must still land where the uninterrupted run does."""
    import jax
    from repro.configs import get_smoke_config
    from repro.data.synthetic import LMTokenSource
    from repro.models import build_model
    from repro.optim import constant, sgd_momentum
    from repro.train.loop import train

    cfg = get_smoke_config("llama3.2-1b").with_overrides(
        vocab_size=64, d_ff=128, num_layers=2, dtype="float32")
    model = build_model(cfg)
    mesh = make_mesh((1,), ("data",))
    jax.set_mesh(mesh)
    opt = sgd_momentum(weight_decay=0.0)
    src = LMTokenSource(cfg.vocab_size, 16, seed=0)
    batches = [src.batch(8, i) for i in range(12)]

    _, ref = train(model, opt, constant(0.05), mesh, batches,
                   num_steps=12, log_every=0, print_fn=None)
    d = str(tmp_path / "ck")
    train(model, opt, constant(0.05), mesh, batches[:8], num_steps=8,
          log_every=0, ckpt_path=d, ckpt_every=4, print_fn=None)
    # the step-8 save is torn by the crash; step 4 must carry the resume
    p = os.path.join(d, "state-00000008.npz")
    with open(p, "r+b") as f:
        f.truncate(os.path.getsize(p) // 2)
    with pytest.warns(RuntimeWarning, match="falling back"):
        _, rep = train(model, opt, constant(0.05), mesh, batches,
                       num_steps=12, log_every=0, resume_from=d,
                       print_fn=None)
    assert rep.steps == 12
    assert abs(rep.losses[-1] - ref.losses[-1]) < 1e-5
