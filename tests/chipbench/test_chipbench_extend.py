"""A later change adds a cell, a configuration and a per-layer metric as
files and entries; the harness finds them by name with no file edited."""
import filecmp
import json
import shutil

import cbtiny

READER = '''"""Images the cell trains per step on each chip."""


def read(ctx):
    return ctx["global_batch"] / ctx["cell"]["chips"]
'''


def test_added_files_are_found_by_name(tiny_bench):
    bench_dir = tiny_bench
    conf = json.loads((bench_dir / "configs" / "alexnet-tiny.json")
                      .read_text())
    conf.update(name="alexnet-tiny12", num_classes=12)
    conf["params"] += 2 * 4097    # two more classes in f8
    (bench_dir / "configs" / "alexnet-tiny12.json").write_text(
        json.dumps(conf))
    shutil.copy(bench_dir / "configs" / "alexnet-tiny.py",
                bench_dir / "configs" / "alexnet-tiny12.py")
    cell = json.loads((bench_dir / "workloads" / "tiny.json").read_text())
    cell.update(name="tiny12", config="alexnet-tiny12", images_per_chip=4,
                plan=dict(cell["plan"], microbatches=2))
    (bench_dir / "workloads" / "tiny12.json").write_text(json.dumps(cell))
    (bench_dir / "metrics" / "throwaway.images_per_chip_step.py") \
        .write_text(READER)
    bench_path = bench_dir.parent / "BENCHMARK.json"
    bench = json.loads(bench_path.read_text())
    bench["configs"].append({"name": "alexnet-tiny12"})
    bench["workloads"].append({"name": "tiny12", "config": "alexnet-tiny12",
                               "chips": 1})
    bench["per_layer"].append({"name": "throwaway.images_per_chip_step",
                               "unit": "images", "workloads": ["tiny12"]})
    bench_path.write_text(json.dumps(bench))

    timed = cbtiny.run(bench_dir, "tiny12")
    traced = cbtiny.run(bench_dir, "tiny12", trace=True)
    assert timed["correct"] and traced["correct"]
    assert set(timed["metrics"]) == {"train_images_per_s", "setup_s"}
    assert traced["metrics"]["throwaway.images_per_chip_step"] == {
        "value": 4.0, "unit": "images"}
    # everything that was there before is byte for byte as it was
    cmp = filecmp.dircmp(cbtiny.CHIPBENCH, bench_dir,
                         ignore=["__pycache__", "testdata"])
    assert not cmp.diff_files
    for sub in cmp.subdirs.values():
        assert not sub.diff_files and not sub.left_only
