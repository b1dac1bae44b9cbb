"""``BENCHMARK.json`` holds the shape the checks expect, every name in it
has its file, and the command refuses without a TPU."""
import json
import os
import re
import shutil
import subprocess
import sys

import cbtiny

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BENCH = json.loads((cbtiny.ROOT / "BENCHMARK.json").read_text())


def test_entries_have_their_files_and_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    root = cbtiny.CHIPBENCH
    for c in BENCH["configs"]:
        assert NAME.match(c["name"])
        assert c["file"] == f"chipbench/configs/{c['name']}.json"
        assert (root / "configs" / f"{c['name']}.py").is_file()
        conf = json.loads((cbtiny.ROOT / c["file"]).read_text())
        assert conf["reduced"] == c["reduced"]
    configs = {c["name"] for c in BENCH["configs"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    for w in BENCH["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in configs
        assert w["chips"] in (1, 4) and 0 < len(w["why"]) <= 200
        cell = json.loads((root / "workloads" / f"{w['name']}.json")
                          .read_text())
        assert (cell["config"], cell["chips"]) == (w["config"], w["chips"])
        assert (root / "runners" / f"{cell['runner']}.py").is_file()
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    assert {m["name"] for m in BENCH["end_to_end"]} >= {"setup_s"}
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert (root / "metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
    assert 2 * sum(w["chips"] == 4 for w in BENCH["workloads"]) <= len(cells)


def _run_command(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, *BENCH["command"][1:], "--workload",
         BENCH["workloads"][0]["name"], "--seed", str(2**31 + 3),
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_without_a_tpu():
    proc = _run_command(cbtiny.ROOT)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not any(l.startswith("{") for l in proc.stdout.splitlines())


def test_command_refuses_with_only_the_benchmark(tmp_path):
    shutil.copy(cbtiny.ROOT / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(cbtiny.ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_command(tmp_path)
    assert proc.returncode != 0
    assert not any(l.startswith("{") for l in proc.stdout.splitlines())
