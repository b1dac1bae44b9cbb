"""A ``googlenet-tiny`` cell of the benchmark on the CPU: 96 px, 10
classes, 8 images per chip through ``googlenet-bsp-1chip``'s plan, added
to the tiny copy as a later change would add a cell. A sound run is
correct, and one that trains on half of each batch is not."""
import json
import shutil

import jax
import pytest

import cbtiny

cbtiny.use_harness()

import spec  # noqa: E402

CONF = json.loads((cbtiny.CHIPBENCH / "configs" / "googlenet.json")
                  .read_text())
# from CPU readings of this cell: four sound seeds read at most 0.0059,
# 2.2e-5 and 0.0071; the float8 control at least 0.13, 0.15 and 0.51 and
# the half-batch fault 288, 0.37 and 65 (two seeds each). From He-normal
# weights the first steps move the loss by a third or more each, so a
# float32 rounding after one step grows a hundredfold by the third: loss_gap
# and delta_gap get more room above the sound runs than cbtiny.LIMITS gives
LIMITS = {"loss_gap": 0.02, "grad_gap": 1e-3, "delta_gap": 0.05}


@pytest.fixture(scope="module")
def googlenet_tiny(tiny_bench):
    """The tiny copy with ``googlenet-tiny``'s files and entries."""
    bench_dir = tiny_bench
    ref = spec._load_module(cbtiny.CHIPBENCH / "configs" / "googlenet.py",
                            "googlenet_tiny_ref")
    conf = dict(CONF, name="googlenet-tiny", image_size=96, num_classes=10,
                reduced=["image_size", "num_classes", "params"])
    shapes = jax.eval_shape(lambda k: ref.init_params(k, conf),
                            jax.random.key(0))
    conf["params"] = sum(l.size for l in jax.tree.leaves(shapes))
    (bench_dir / "configs" / "googlenet-tiny.json").write_text(
        json.dumps(conf))
    shutil.copy(cbtiny.CHIPBENCH / "configs" / "googlenet.py",
                bench_dir / "configs" / "googlenet-tiny.py")
    cell = json.loads((cbtiny.CHIPBENCH / "workloads"
                       / "googlenet-bsp-1chip.json").read_text())
    cell.update(name="googlenet-tiny", config="googlenet-tiny",
                images_per_chip=8, limits=LIMITS,
                trace={"after_s": 0.0, "steps": 2})
    (bench_dir / "workloads" / "googlenet-tiny.json").write_text(
        json.dumps(cell))
    bench_path = bench_dir.parent / "BENCHMARK.json"
    bench = json.loads(bench_path.read_text())
    bench["configs"].append({"name": "googlenet-tiny"})
    bench["workloads"].append({"name": "googlenet-tiny",
                               "config": "googlenet-tiny", "chips": 1})
    for m in bench["per_layer"]:
        m["workloads"].append("googlenet-tiny")
    bench_path.write_text(json.dumps(bench))
    return bench_dir


def test_googlenet_tiny_sound_run_is_correct(googlenet_tiny):
    res = cbtiny.run(googlenet_tiny, "googlenet-tiny")
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["compiles_in_window"] == 0
    assert set(res["metrics"]) == {"train_images_per_s", "setup_s"}


def test_googlenet_tiny_half_batch_is_not_correct(googlenet_tiny,
                                                  monkeypatch):
    from repro.train import engine
    real = engine.make_bsp_step

    def half(batch):
        return jax.tree.map(lambda v: v[:v.shape[0] // 2], batch)

    monkeypatch.setattr(engine, "make_bsp_step", lambda *a, **kw: (
        lambda step: lambda state, batch, rng: step(state, half(batch),
                                                    rng))(real(*a, **kw)))
    res = cbtiny.run(googlenet_tiny, "googlenet-tiny")
    assert not res["correct"], res["checks"]
