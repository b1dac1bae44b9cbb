"""Unified training engine (repro.train.engine): plan validation, canonical
state layout, cross-algo parity, resumable runs, loop accounting.

Parity anchors (derivations in DESIGN.md "Training engine"):

- EASGD at ``alpha=1, tau=1`` is synchronous model averaging, which from a
  synced start equals BSP gradient averaging with the learning rate scaled
  by ``k`` (momentum states stay per-worker but their mean tracks the BSP
  momentum by linearity). Exercised at k=1 here and k=8 in the subprocess
  test (which also checks the fp16-wire center exchange).
- GSPMD ``zero1`` and BSP ``sharded_update`` are the same ASA/ZeRO-1
  schedule, declarative vs explicit — losses and params must agree.
- A run restored from a mid-run checkpoint replays the uninterrupted run
  bitwise (state + step + rng fold offset), for every algo.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.data.synthetic import LMTokenSource
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.optim import adamw, constant, sgd_momentum
from repro.train.engine import TrainPlan, build_engine
from repro.train.loop import train


def _tiny_lm(dtype=None):
    over = dict(vocab_size=64, d_ff=128, num_layers=2)
    if dtype:
        over["dtype"] = dtype
    cfg = get_smoke_config("llama3.2-1b").with_overrides(**over)
    return cfg, build_model(cfg)


def _batches(cfg, n, bsz=8, seq=32):
    src = LMTokenSource(cfg.vocab_size, seq, seed=0)
    return [src.batch(bsz, i) for i in range(n)]


def _mesh1():
    mesh = make_mesh((1,), ("data",))
    jax.set_mesh(mesh)
    return mesh


# ---------------------------------------------------------------------------
# plan validation
# ---------------------------------------------------------------------------

def test_trainplan_validation():
    with pytest.raises(ValueError, match="unknown algo"):
        TrainPlan(algo="hogwild")
    with pytest.raises(ValueError, match="unknown scheme"):
        TrainPlan(scheme="avg")
    with pytest.raises(ValueError, match="unknown gspmd mode"):
        TrainPlan(mode="zero3")
    with pytest.raises(ValueError, match="tau"):
        TrainPlan(algo="easgd", tau=0)
    with pytest.raises(ValueError, match="BSP-only"):
        TrainPlan(algo="easgd", sharded_update=True)
    with pytest.raises(ValueError, match="BSP-only"):
        TrainPlan(algo="gspmd", microbatches=4)
    with pytest.raises(ValueError, match="exchanger"):
        TrainPlan(algo="asgd", exchanger="none")
    # non-applicable knobs fail loudly instead of being silently ignored
    with pytest.raises(ValueError, match="easgd/asgd knob"):
        TrainPlan(algo="bsp", tau=4)
    with pytest.raises(ValueError, match="does not apply"):
        TrainPlan(algo="gspmd", exchanger="asa16")
    with pytest.raises(ValueError, match="gspmd knob"):
        TrainPlan(algo="easgd", mode="ar")
    with pytest.raises(ValueError, match="BSP-only"):
        TrainPlan(algo="gspmd", scheme="awagd")
    with pytest.raises(ValueError, match="async knob"):
        TrainPlan(algo="bsp", alpha=0.9)
    with pytest.raises(ValueError, match="pinned to alpha=1"):
        TrainPlan(algo="asgd", alpha=0.3)
    with pytest.raises(ValueError, match="pinned to alpha=1"):
        TrainPlan(algo="asgd", alpha=0.5)   # no sentinel collision
    # alpha=None resolves to the algo default (self-describing plans)
    assert TrainPlan(algo="asgd").alpha == 1.0
    assert TrainPlan(algo="asgd", alpha=1.0).alpha == 1.0
    assert TrainPlan(algo="easgd").alpha == 0.5
    assert TrainPlan(algo="easgd", tau=4).is_async
    assert not TrainPlan().is_async


# ---------------------------------------------------------------------------
# canonical layout: one entry point drives every algo
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("plan", [
    TrainPlan(algo="bsp"),
    TrainPlan(algo="bsp", sharded_update=True),
    TrainPlan(algo="easgd", tau=2),
    TrainPlan(algo="asgd", tau=2),
    TrainPlan(algo="gspmd"),
], ids=lambda p: p.algo + ("+sharded" if p.sharded_update else ""))
def test_engine_canonical_layout(plan):
    cfg, model = _tiny_lm()
    mesh = _mesh1()
    opt = sgd_momentum(weight_decay=0.0)
    eng = build_engine(plan, model, opt, constant(0.02), mesh)
    state = eng.init_state(jax.random.key(0))
    assert {"params", "opt", "step"} <= set(state)
    assert ("center" in state) == plan.is_async
    state, m = eng.step(state, _batches(cfg, 1)[0], jax.random.key(1),
                        step_idx=0)
    assert int(state["step"]) == 1
    assert np.isfinite(float(m["loss"]))
    sh = eng.state_shardings(state)
    assert jax.tree.structure(sh) == jax.tree.structure(state)


def test_easgd_adamw_first_class():
    """Per-worker updates go through the shared Optimizer interface: adamw
    (with its t counter) trains under the async scaffolding."""
    cfg, model = _tiny_lm()
    mesh = _mesh1()
    eng = build_engine(TrainPlan(algo="easgd", tau=2, alpha=0.5), model,
                       adamw(weight_decay=0.0), constant(2e-3), mesh)
    state = eng.init_state(jax.random.key(0))
    losses = []
    for i, b in enumerate(_batches(cfg, 20)):
        state, m = eng.step(state, b, jax.random.fold_in(jax.random.key(1), i),
                            step_idx=i)
        losses.append(float(m["loss"]))
    assert np.mean(losses[-5:]) < np.mean(losses[:5])
    # adamw's t advanced once per step on the worker replica
    assert int(np.asarray(state["opt"]["t"])[0]) == 20


# ---------------------------------------------------------------------------
# parity
# ---------------------------------------------------------------------------

def _run_engine(plan, model, opt, lr, batches, mesh):
    eng = build_engine(plan, model, opt, constant(lr), mesh)
    state = eng.init_state(jax.random.key(0))
    losses = []
    for i, b in enumerate(batches):
        state, m = eng.step(state, b, jax.random.fold_in(jax.random.key(1), i),
                            step_idx=i)
        losses.append(float(m["loss"]))
    return state, losses


def test_easgd_tau1_parity_with_bsp():
    """alpha=1, tau=1 elastic averaging == BSP all-reduce momentum-SGD
    (k=1: no lr rescale needed)."""
    cfg, model = _tiny_lm(dtype="float32")
    mesh = _mesh1()
    opt = sgd_momentum(weight_decay=0.0)
    batches = _batches(cfg, 6)
    sb, lb = _run_engine(TrainPlan(algo="bsp", exchanger="ar"), model, opt,
                         0.05, batches, mesh)
    se, le = _run_engine(TrainPlan(algo="easgd", exchanger="ar", tau=1,
                                   alpha=1.0), model, opt, 0.05, batches,
                         mesh)
    assert lb == pytest.approx(le, rel=1e-5)
    for a, b in zip(jax.tree.leaves(sb["params"]),
                    jax.tree.leaves(se["center"])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-5, atol=1e-6)
    # workers re-fetched the center (alpha=1 snap)
    for w, c in zip(jax.tree.leaves(se["params"]),
                    jax.tree.leaves(se["center"])):
        np.testing.assert_array_equal(np.asarray(w)[0], np.asarray(c))


def test_asgd_is_the_alpha1_point():
    """asgd == easgd with alpha forced to 1 (same scaffolding, bitwise)."""
    cfg, model = _tiny_lm()
    mesh = _mesh1()
    opt = sgd_momentum(weight_decay=0.0)
    batches = _batches(cfg, 5)
    s1, l1 = _run_engine(TrainPlan(algo="asgd", tau=2), model, opt, 0.02,
                         batches, mesh)
    s2, l2 = _run_engine(TrainPlan(algo="easgd", tau=2, alpha=1.0), model,
                         opt, 0.02, batches, mesh)
    assert l1 == l2
    for a, b in zip(jax.tree.leaves(s1), jax.tree.leaves(s2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_gspmd_zero1_parity_with_bsp_sharded_update():
    """The declarative (GSPMD) and explicit (RS->update->AG) ZeRO-1 paths
    compute the same training trajectory."""
    cfg, model = _tiny_lm(dtype="float32")
    mesh = _mesh1()
    opt = sgd_momentum(weight_decay=0.0)
    batches = _batches(cfg, 6)
    ss, ls = _run_engine(TrainPlan(algo="bsp", exchanger="asa",
                                   sharded_update=True), model, opt, 0.05,
                         batches, mesh)
    sg, lg = _run_engine(TrainPlan(algo="gspmd", mode="zero1"), model, opt,
                         0.05, batches, mesh)
    assert ls == pytest.approx(lg, rel=1e-5)
    for a, b in zip(jax.tree.leaves(ss["params"]),
                    jax.tree.leaves(sg["params"])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-4, atol=1e-6)


# ---------------------------------------------------------------------------
# resumable runs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("plan", [
    TrainPlan(algo="bsp", sharded_update=True),
    TrainPlan(algo="easgd", tau=2),
    TrainPlan(algo="asgd", tau=3),
    TrainPlan(algo="gspmd"),
], ids=lambda p: p.algo + ("+sharded" if p.sharded_update else ""))
def test_resume_is_bitwise(plan, tmp_path):
    """save at step 4 -> resume -> identical to the uninterrupted 8-step
    run, for every algo (state, losses, step counter). Exercises the
    global-step rng fold, the batch skip, tau phase alignment, and the
    sharded opt-state placement on restore."""
    cfg, model = _tiny_lm()
    mesh = _mesh1()
    opt = sgd_momentum(weight_decay=0.0)
    batches = _batches(cfg, 8)
    kw = dict(num_steps=8, log_every=0, print_fn=lambda *_: None)
    ck = str(tmp_path / "ck")
    s_full, r_full = train(model, opt, constant(0.02), mesh, batches,
                           plan=plan, **kw)
    train(model, opt, constant(0.02), mesh, batches, plan=plan,
          num_steps=4, log_every=0, ckpt_path=ck, print_fn=lambda *_: None)
    s_res, r_res = train(model, opt, constant(0.02), mesh, batches,
                         plan=plan, resume_from=ck, **kw)
    assert r_res.steps == 8
    assert r_res.losses == r_full.losses[4:]
    for a, b in zip(jax.tree.leaves(s_full), jax.tree.leaves(s_res)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_resume_layout_mismatch_fails_cleanly(tmp_path):
    """A checkpoint with no recorded algo (pre-engine) and a different
    state layout dies on the key check, not a cryptic KeyError."""
    from repro.checkpoint.ckpt import save_checkpoint
    cfg, model = _tiny_lm()
    mesh = _mesh1()
    opt = sgd_momentum(weight_decay=0.0)
    batches = _batches(cfg, 4)
    ck = str(tmp_path / "ck")
    state, _ = train(model, opt, constant(0.02), mesh, batches,
                     plan=TrainPlan(), num_steps=2, log_every=0,
                     print_fn=lambda *_: None)
    save_checkpoint(ck, state, step=2)   # no algo recorded
    with pytest.raises(ValueError, match="layout mismatch"):
        train(model, opt, constant(0.02), mesh, batches,
              plan=TrainPlan(algo="easgd"), num_steps=4, log_every=0,
              resume_from=ck, print_fn=lambda *_: None)


def test_resume_algo_mismatch_fails_cleanly(tmp_path):
    """bsp and gspmd checkpoints are layout-identical; the recorded algo
    meta is what refuses the cross-resume."""
    cfg, model = _tiny_lm()
    mesh = _mesh1()
    opt = sgd_momentum(weight_decay=0.0)
    batches = _batches(cfg, 4)
    ck = str(tmp_path / "ck")
    train(model, opt, constant(0.02), mesh, batches, plan=TrainPlan(),
          num_steps=2, log_every=0, ckpt_path=ck, print_fn=lambda *_: None)
    with pytest.raises(ValueError, match="algo mismatch"):
        train(model, opt, constant(0.02), mesh, batches,
              plan=TrainPlan(algo="gspmd"), num_steps=4, log_every=0,
              resume_from=ck, print_fn=lambda *_: None)


def test_resume_at_end_is_noop(tmp_path):
    cfg, model = _tiny_lm()
    mesh = _mesh1()
    opt = sgd_momentum(weight_decay=0.0)
    batches = _batches(cfg, 4)
    ck = str(tmp_path / "ck")
    train(model, opt, constant(0.02), mesh, batches, num_steps=4,
          log_every=0, ckpt_path=ck, print_fn=lambda *_: None)
    _, report = train(model, opt, constant(0.02), mesh, batches,
                      num_steps=4, log_every=0, resume_from=ck,
                      print_fn=lambda *_: None)
    assert report.steps == 4 and report.losses == []


# ---------------------------------------------------------------------------
# loop accounting (the satellite fixes)
# ---------------------------------------------------------------------------

def test_final_checkpoint_saved_once(tmp_path, monkeypatch):
    """ckpt_every dividing the last step used to save the same step twice
    (in-loop + final)."""
    import repro.train.loop as loop_mod
    calls = []
    monkeypatch.setattr(loop_mod, "save_checkpoint",
                        lambda path, state, step=None, algo=None, **kw:
                        calls.append(step))
    cfg, model = _tiny_lm()
    mesh = _mesh1()
    opt = sgd_momentum(weight_decay=0.0)
    train(model, opt, constant(0.02), mesh, _batches(cfg, 6),
          num_steps=6, log_every=0, ckpt_path=str(tmp_path / "ck"),
          ckpt_every=3, print_fn=lambda *_: None)
    assert calls == [3, 6]


def test_losses_flushed_at_log_boundaries():
    """device_losses is flushed to host floats in bounded windows; the
    report still carries one loss per step, in order."""
    cfg, model = _tiny_lm()
    mesh = _mesh1()
    opt = sgd_momentum(weight_decay=0.0)
    _, report = train(model, opt, constant(0.02), mesh, _batches(cfg, 7),
                      num_steps=7, log_every=2, print_fn=lambda *_: None)
    assert len(report.losses) == 7
    assert all(np.isfinite(l) for l in report.losses)


# ---------------------------------------------------------------------------
# 8-worker parity + fp16-wire center exchange (subprocess)
# ---------------------------------------------------------------------------

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, numpy as np
from repro.configs import get_smoke_config
from repro.data.synthetic import LMTokenSource
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.optim import constant, sgd_momentum
from repro.train.engine import TrainPlan, build_engine

cfg = get_smoke_config("llama3.2-1b").with_overrides(
    vocab_size=64, d_ff=128, num_layers=2, dtype="float32")
model = build_model(cfg)
mesh = make_mesh((8,), ("data",))
jax.set_mesh(mesh)
src = LMTokenSource(cfg.vocab_size, 16, seed=0)
batches = [src.batch(32, i) for i in range(4)]
opt = sgd_momentum(weight_decay=0.0)

def run(plan, lr):
    eng = build_engine(plan, model, opt, constant(lr), mesh)
    st = eng.init_state(jax.random.key(0))
    losses = []
    for i, b in enumerate(batches):
        st, m = eng.step(st, b, jax.random.fold_in(jax.random.key(1), i),
                         step_idx=i)
        losses.append(float(m["loss"]))
    return st, losses

def maxerr(ta, tb):
    errs = []
    for a, b in zip(jax.tree.leaves(ta), jax.tree.leaves(tb)):
        a = np.asarray(a, np.float32); b = np.asarray(b, np.float32)
        errs.append(float(np.abs(a - b).max() / (np.abs(a).max() + 1e-9)))
    return max(errs)

out = {}
# EASGD(alpha=1, tau=1, lr=eta/k) == BSP(lr=eta) across 8 workers
sb, lb = run(TrainPlan(algo="bsp", exchanger="ar"), 0.16)
se, le = run(TrainPlan(algo="easgd", exchanger="ar", tau=1, alpha=1.0),
             0.16 / 8)
out["parity_err"] = maxerr(sb["params"], se["center"])
out["parity_loss_err"] = max(abs(a - b) for a, b in zip(lb, le))
# the fp16-wire center exchange (asa16) stays close to the fp32 one
s16, _ = run(TrainPlan(algo="easgd", exchanger="asa16", tau=1, alpha=1.0),
             0.16 / 8)
out["fp16_wire_err"] = maxerr(se["center"], s16["center"])
# asgd at tau=2: staleness-bounded async still trains
sa, la = run(TrainPlan(algo="asgd", exchanger="asa16", tau=2), 0.02)
out["asgd_losses"] = la
out["asgd_finite"] = bool(np.isfinite(
    np.asarray(jax.tree.leaves(sa["center"])[0], np.float32)).all())
print("RESULTS_JSON:" + json.dumps(out))
"""


def test_engine_multiworker_parity_and_wire():
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
    assert out["parity_err"] < 1e-4, out
    assert out["parity_loss_err"] < 1e-4, out
    assert out["fp16_wire_err"] < 5e-3, out
    assert out["asgd_finite"] and np.isfinite(out["asgd_losses"]).all(), out
