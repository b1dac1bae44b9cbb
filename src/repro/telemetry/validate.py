"""Schema validator CLI — what the CI telemetry-smoke step runs.

    python -m repro.telemetry.validate metrics.jsonl more.jsonl \
        --trace trace.json

Exit 0 iff every file validates; problems print one per line.
"""
from __future__ import annotations

import argparse
import sys

from repro.telemetry.schema import validate_metrics_jsonl, validate_trace


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("metrics", nargs="*", help="metrics JSONL files")
    ap.add_argument("--trace", nargs="*", default=[],
                    help="Chrome-trace/Perfetto JSON files")
    args = ap.parse_args(argv)
    if not (args.metrics or args.trace):
        ap.error("nothing to validate")
    errs = []
    for p in args.metrics:
        errs.extend(validate_metrics_jsonl(p))
    for p in args.trace:
        errs.extend(validate_trace(p))
    for e in errs:
        print(e, file=sys.stderr)
    n = len(args.metrics) + len(args.trace)
    print(f"validated {n} file(s): "
          + ("OK" if not errs else f"{len(errs)} problem(s)"))
    return 1 if errs else 0


if __name__ == "__main__":
    raise SystemExit(main())
