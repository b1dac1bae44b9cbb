"""End-to-end behaviour: multi-step training decreases loss (BSP subgd &
awagd, EASGD), generation runs, GSPMD/ZeRO-1 path agrees with BSP, and the
chip smoke run refuses to pass without a TPU."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core import get_exchanger, init_train_state, make_bsp_step
from repro.core.gspmd import make_gspmd_step
from repro.data.synthetic import LMTokenSource
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.optim import constant, sgd_momentum
from repro.train.engine import TrainPlan
from repro.train.loop import train
from repro.train.serve import generate


def _tiny_lm():
    cfg = get_smoke_config("llama3.2-1b").with_overrides(
        vocab_size=64, d_ff=128, num_layers=2)
    return cfg, build_model(cfg)


def _batches(cfg, n, bsz=8, seq=32):
    src = LMTokenSource(cfg.vocab_size, seq, seed=0)
    return [src.batch(bsz, i) for i in range(n)]


def test_bsp_training_decreases_loss():
    cfg, model = _tiny_lm()
    mesh = make_mesh((1,), ("data",))
    jax.set_mesh(mesh)
    opt = sgd_momentum(weight_decay=0.0)
    _, report = train(model, opt, constant(0.02), mesh,
                      _batches(cfg, 40), exchanger="asa", num_steps=40,
                      log_every=0, print_fn=lambda *_: None)
    first = np.mean(report.losses[:5])
    last = np.mean(report.losses[-5:])
    assert last < first - 0.1, f"no learning: {first:.3f} -> {last:.3f}"


def test_awagd_scheme_trains():
    cfg, model = _tiny_lm()
    mesh = make_mesh((1,), ("data",))
    jax.set_mesh(mesh)
    opt = sgd_momentum(weight_decay=0.0)
    _, report = train(model, opt, constant(0.02), mesh,
                      _batches(cfg, 25), exchanger="ar", scheme="awagd",
                      num_steps=25, log_every=0, print_fn=lambda *_: None)
    assert np.mean(report.losses[-5:]) < np.mean(report.losses[:5])


def test_easgd_trains_center():
    cfg, model = _tiny_lm()
    mesh = make_mesh((1,), ("data",))
    jax.set_mesh(mesh)
    opt = sgd_momentum(weight_decay=0.0)
    state, report = train(model, opt, constant(0.02), mesh,
                          _batches(cfg, 30),
                          plan=TrainPlan(algo="easgd", alpha=0.5, tau=2),
                          num_steps=30, log_every=0,
                          print_fn=lambda *_: None)
    assert np.mean(report.losses[-5:]) < np.mean(report.losses[:5])
    # center was pulled toward workers
    c = jax.tree.leaves(state["center"])[0]
    assert bool(jnp.isfinite(c).all())


def test_gspmd_zero1_matches_bsp_ar_one_step():
    cfg, model = _tiny_lm()
    mesh = make_mesh((1,), ("data",))
    jax.set_mesh(mesh)
    opt = sgd_momentum(weight_decay=0.0)
    state = init_train_state(model, opt, jax.random.key(0))
    batch = _batches(cfg, 1)[0]
    bsp = jax.jit(make_bsp_step(model, opt, get_exchanger("ar"),
                                constant(0.1), mesh))
    gsp = jax.jit(make_gspmd_step(model, opt, constant(0.1), mesh,
                                  mode="zero1"))
    s1, m1 = bsp(state, batch, jax.random.key(1))
    s2, m2 = gsp(state, batch, jax.random.key(1))
    assert float(m1["loss"]) == pytest.approx(float(m2["loss"]), rel=1e-5)
    for a, b in zip(jax.tree.leaves(s1["params"]),
                    jax.tree.leaves(s2["params"])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-4, atol=1e-5)


def test_generation_shapes_and_determinism():
    cfg, model = _tiny_lm()
    params = model.init(jax.random.key(0))
    prompt = jnp.ones((2, 4), jnp.int32)
    out1 = generate(model, params, prompt, max_new=6, seq_len=10)
    out2 = generate(model, params, prompt, max_new=6, seq_len=10)
    assert out1.shape == (2, 10)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    np.testing.assert_array_equal(np.asarray(out1[:, :4]),
                                  np.asarray(prompt))


def test_microbatch_accumulation_matches_full_batch():
    """grad(mean over batch) == mean of microbatch grads (linearity).

    fp32 compute: bf16 matmul accumulation order differs between the split
    and unsplit batch shapes and would mask real errors."""
    cfg, model = _tiny_lm()
    from repro.models import build_model
    cfg = cfg.with_overrides(dtype="float32")
    model = build_model(cfg)
    mesh = make_mesh((1,), ("data",))
    jax.set_mesh(mesh)
    opt = sgd_momentum(weight_decay=0.0)
    state = init_train_state(model, opt, jax.random.key(0))
    batch = _batches(cfg, 1, bsz=8)[0]
    s_full = jax.jit(make_bsp_step(model, opt, get_exchanger("ar"),
                                   constant(0.05), mesh))
    s_micro = jax.jit(make_bsp_step(model, opt, get_exchanger("ar"),
                                    constant(0.05), mesh, microbatches=4))
    a, ma = s_full(state, batch, jax.random.key(1))
    b, mb = s_micro(state, batch, jax.random.key(1))
    assert float(ma["loss"]) == pytest.approx(float(mb["loss"]), rel=1e-4)
    for x, y in zip(jax.tree.leaves(a["params"]),
                    jax.tree.leaves(b["params"])):
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32),
                                   rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_chip_smoke_fails_without_tpu(where, tmp_path):
    """Under JAX_PLATFORMS=cpu, and from a directory holding only the
    script, chip_smoke.py exits nonzero and prints no result line."""
    script = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    if where == "alone":
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "REPRO_PALLAS_INTERPRET",
                        "REPRO_ATTN_IMPL")}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, str(script)], cwd=script.parent,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    # alone: no package to import, unless one is installed site-wide
    reasons = ["no TPU"] + (["repro package"] if where == "alone" else [])
    assert any(r in proc.stderr for r in reasons), proc.stderr[-2000:]
