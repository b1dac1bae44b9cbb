"""Runs one cell of the chip benchmark once and prints its result.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its kind of run and its per-layer metrics are
found by name from ``BENCHMARK.json`` (``spec.py``). With ``--trace 0`` the
result's metrics are the cell's end-to-end metrics; with ``--trace 1`` a
stretch of the window is recorded with ``jax.profiler`` and the metrics are
the cell's per-layer ones, each read from the trace by
``metrics/<name>.py``.

The run refuses, with a nonzero exit and no result line, when JAX finds no
TPU, fewer chips than the cell asks for, or a device kind that the peaks
table does not hold, and when the program is not beside the benchmark.
Its last lines on standard error, and the last key of its result line, are
the numbers that decide ``correct``, each beside its limit.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parent
# a fixed path in the checkout: the directory is part of the cache's key
CACHE_DIR = CHECKOUT / ".jax_cache"


class Refused(Exception):
    pass


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache, where ``JAX_COMPILATION_CACHE_DIR``
    says or at ``.jax_cache/`` in the checkout; every program is kept."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def tpu_devices(chips: int):
    """The first ``chips`` TPU devices, and the peaks of their kind."""
    import jax

    from spec import SpecError, peaks
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise Refused(f"no TPU: JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise Refused(f"{len(devices)} chips, the cell needs {chips}")
    for var in ("REPRO_PALLAS_INTERPRET", "REPRO_ATTN_IMPL"):
        if var in os.environ:
            raise Refused(f"{var} is set: it could hide the device path")
    try:
        return devices[:chips], peaks(devices[0].device_kind)
    except SpecError as e:
        raise Refused(str(e)) from e


def import_program():
    sys.path.insert(0, str(CHECKOUT / "src"))
    try:
        import repro  # noqa: F401
    except ImportError as e:
        raise Refused(f"the program (src/repro) is not beside the "
                      f"benchmark: {e}") from e


def run_cell(spec, name: str, seed: int, seconds: float, trace: bool,
             devices, peak: dict, t_start: float) -> dict:
    """One run of cell ``name`` on ``devices``: the result line's fields
    (without ``device``), and the numbers compared under ``checks``."""
    import tracereduce

    cell = spec.cell(name)
    conf = spec.config(cell["config"])
    runner = spec.runner(cell["runner"])
    ref = spec.reference(cell["config"])
    with tempfile.TemporaryDirectory(prefix="chipbench-trace-") as tdir:
        tracer = tracereduce.Tracer(tdir) if trace else None
        out = runner.run(cell, conf, ref, seed, seconds, devices, t_start,
                         tracer)
        summary = (tracereduce.reduce(tracer.xplane(), len(devices))
                   if tracer is not None and tracer.done else None)
    result = {"correct": bool(out["correct"]), "attempted": out["attempted"],
              "failed": out["failed"], "metrics": {}}
    if trace:
        ctx = dict(out["context"], trace=summary, peaks=peak)
        for m in spec.metrics_for("per_layer", name):
            value = spec.metric_reader(m["name"]).read(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        if summary is not None:
            result["busy_s"] = summary.busy_s
            result["window_s"] = summary.window_s
            result["breakdown"] = summary.breakdown()
    else:
        for m in spec.metrics_for("end_to_end", name):
            result["metrics"][m["name"]] = {"value": out["metrics"][m["name"]],
                                            "unit": m["unit"]}
    result["memory_peak_bytes"] = out["memory_peak_bytes"]
    result["compiles_in_window"] = out["compiles_in_window"]
    result["checks"] = out["checks"]
    return result


def result_line(result: dict, devices) -> dict:
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": result["memory_peak_bytes"]}
    line = {k: result[k] for k in ("correct", "attempted", "failed",
                                   "metrics")}
    if "busy_s" in result:
        device["busy_s"] = result["busy_s"]
        device["window_s"] = result["window_s"]
    line["device"] = device
    if "breakdown" in result:
        line["breakdown"] = result["breakdown"]
    line["checks"] = result["checks"]
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(HERE))
    from spec import Spec, SpecError
    try:
        spec = Spec(HERE)
        cell = spec.cell(args.workload)
        devices, peak = tpu_devices(cell["chips"])
        import_program()
        enable_compile_cache()
        result = run_cell(spec, args.workload, args.seed, args.seconds,
                          bool(args.trace), devices, peak, T_START)
    except (Refused, SpecError) as e:
        print(f"refused: {e}", file=sys.stderr)
        return 2
    print(f"compiles in the window: {result['compiles_in_window']}",
          file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result_line(result, devices)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
