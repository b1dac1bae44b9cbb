"""A copy of the chip benchmark with one cell cut to a size the CPU runs
in seconds: AlexNet's layers at 67 px and 10 classes, 8 images per chip.

The harness's modules are imported from the repository's ``chipbench/``;
the copy is read through ``Spec`` alone, so files added to it are found
by name as a later change would add them.
"""
from __future__ import annotations

import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
CHIPBENCH = ROOT / "chipbench"
# sound CPU runs read under a third of these; the control and the faults
# read above them (test_chipbench_faults.py)
LIMITS = {"loss_gap": 6e-3, "grad_gap": 1e-3, "delta_gap": 6e-3}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def use_harness():
    for p in (str(CHIPBENCH), str(ROOT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)


def make(dest: Path, chips: int = 1) -> Path:
    """Copies ``chipbench/`` under ``dest`` and adds the config
    ``alexnet-tiny`` and the cell ``tiny``; returns the copy's directory."""
    use_harness()
    import jax

    import spec
    bench_dir = Path(dest) / "chipbench"
    shutil.copytree(CHIPBENCH, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    conf = json.loads((CHIPBENCH / "configs" / "alexnet.json").read_text())
    conf.update(name="alexnet-tiny", image_size=67, num_classes=10,
                reduced=["image_size", "num_classes", "params"])
    ref = spec._load_module(CHIPBENCH / "configs" / "alexnet.py", "cbtiny_ref")
    shapes = jax.eval_shape(lambda k: ref.init_params(k, conf),
                            jax.random.key(0))
    conf["params"] = sum(l.size for l in jax.tree.leaves(shapes))
    (bench_dir / "configs" / "alexnet-tiny.json").write_text(json.dumps(conf))
    shutil.copy(CHIPBENCH / "configs" / "alexnet.py",
                bench_dir / "configs" / "alexnet-tiny.py")
    cell = json.loads(
        (CHIPBENCH / "workloads" / "alexnet-bsp-1chip.json").read_text())
    cell.update(name="tiny", config="alexnet-tiny", chips=chips,
                images_per_chip=8, limits=LIMITS,
                trace={"after_s": 0.0, "steps": 2})
    (bench_dir / "workloads" / "tiny.json").write_text(json.dumps(cell))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "alexnet-tiny"})
    bench["workloads"].append({"name": "tiny", "config": "alexnet-tiny",
                               "chips": chips})
    for m in bench["per_layer"]:
        m.setdefault("workloads", []).append("tiny")
    (bench_dir.parent / "BENCHMARK.json").write_text(json.dumps(bench))
    return bench_dir


def run(bench_dir: Path, cell: str = "tiny", seed: int = 2**31 + 7,
        seconds: float = 0.3, trace: bool = False) -> dict:
    """One run of ``cell`` on the CPU devices, past the look for a chip."""
    use_harness()
    import jax

    import run as harness
    from spec import Spec
    spec = Spec(bench_dir)
    chips = spec.cell(cell)["chips"]
    return harness.run_cell(spec, cell, seed, seconds, trace,
                            jax.devices()[:chips], PEAKS, time.perf_counter())
