"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload phi35moe.longdecode.1c \
        --seed 7 --seconds 51 --trace 0

Prints progress on stderr and, as the last line on stdout, one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer ones), ``device`` and, last,
``check`` (each number compared for ``correct`` beside its limit).  Exits
non-zero and prints no result without a TPU, or with fewer chips than the
cell asks for.  One process holds the chips and starts no other.

Options the benchmark's own runs never pass, for setting the limits:
``--repeat N`` runs seeds seed..seed+N-1 in this one process, and
``--control 1`` puts the fp8 control in the program's place at the same
positions: ``correct`` and ``check`` then read the control, which has to
come out not correct (the program's own reading stays on stderr).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--repeat", type=int, default=1)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import harness, spec
    try:
        cell = spec.load_cell(args.workload)
    except (KeyError, FileNotFoundError) as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    harness.configure_compile_cache()
    for seed in range(args.seed, args.seed + args.repeat):
        try:
            out = harness.run(cell, seed, args.seconds, bool(args.trace),
                              control=bool(args.control))
        except harness.NoChip as e:
            print(f"run.py: {e}", file=sys.stderr)
            return 3
        info = out["_info"]
        harness.log("info " + json.dumps(info))
        for name, c in out["check"].items():
            harness.log(f"check {name} {c['value']!r} limit {c['limit']!r}")
        print(harness.result_line(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
