"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,... \
        [--control-seeds 1,2,3] [--fault-seeds 1,2,3] [--out FILE]

In one process (the chip belongs to one process at a time):

- the program: for each seed, the cell's set-up through ``train()`` with
  the shortest window, and its compared numbers against the reference:
  the lower readings;
- the control: for each control seed, the reference computed one
  precision below the configuration's (its ``precision.control``), put in
  the program's place: the upper readings;
- the faults: for each fault seed, the reference with each of
  ``sgd_reference.FAULTS`` that the cell can have planted in it.

The benchmark's own runs never run this. It prints one JSON line per
reading and writes them all to ``--out``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(HERE))
    import run
    from spec import Spec
    spec = Spec(HERE)
    cell = spec.cell(args.workload)
    devices, _ = run.tpu_devices(cell["chips"])
    run.import_program()
    run.enable_compile_cache()

    import compare
    import sgd_reference
    conf = spec.config(cell["config"])
    ref = spec.reference(cell["config"])
    runner = spec.runner(cell["runner"])
    seeds = lambda s: [int(x) for x in s.split(",") if x]  # noqa: E731
    rows = []

    def emit(row):
        print(json.dumps(row), flush=True)
        rows.append(row)

    for seed in seeds(args.seeds):
        t0 = time.perf_counter()
        out = runner.run(cell, conf, ref, seed, 0.0, devices, t0)
        emit({"kind": "program", "seed": seed,
              **{n: c["value"] for n, c in out["checks"].items()},
              "setup_s": out["metrics"]["setup_s"]})
    faults = ["half_batch"] + (["no_exchange"] if cell["chips"] > 1 else [])
    for seed in sorted(set(seeds(args.control_seeds)
                           + seeds(args.fault_seeds))):
        s = runner.run_seed(seed)
        want = sgd_reference.follow(ref, conf, cell, s, device=devices[0])
        variants = []
        if seed in seeds(args.control_seeds):
            variants.append(("control",
                             {"compute": conf["precision"]["control"]}))
        if seed in seeds(args.fault_seeds):
            variants += [(f, {"fault": f}) for f in faults]
        for kind, kw in variants:
            got = sgd_reference.follow(ref, conf, cell, s,
                                       device=devices[0], **kw)
            emit({"kind": kind, "seed": seed,
                  **compare.readings(got, want)})
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
