#!/usr/bin/env python3
"""Read, on the chip, the numbers a cell's limits are set from.

    python3 perfbench/tools/read_limits.py --workload <cell> --seeds 1,2,3 \\
        [--control-seeds 1,2] [--seconds 5]

One process: for every seed it drives the cell as a run does (set-up, a window
of ``--seconds``, the reference) and prints each number compared; for the
control seeds it also puts the reference, computed in the precision the cell's
limits file names under ``control``, in the program's place and prints the
same numbers for it.  The benchmark's own runs never run the control.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
from perfbench import run as _run  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", required=True,
                    type=lambda v: [int(x) for x in v.split(",")])
    ap.add_argument("--control-seeds", default=[],
                    type=lambda v: [int(x) for x in v.split(",")])
    own, rest = ap.parse_known_args(argv)
    seeds, control = own.seeds, own.control_seeds
    rows = []
    for seed in seeds:
        args = _run.parse(rest + ["--seed", str(seed)])
        h = _run.prepare(args)
        if seed in control:
            h.control = h.manifest.load_json(
                "limits", h.name + ".json")["control"]
        line = _run.execute(h)
        row = dict(h.readings, seed=seed, correct=line["correct"],
                   setup_s=h.setup_s)
        rows.append(row)
        print("[reading] " + json.dumps(row), flush=True)
        print("[line] " + json.dumps(line), flush=True)
    keys = sorted({k for r in rows for k in r} - {"seed", "correct"})
    for k in keys:
        vals = [r[k] for r in rows if r.get(k) is not None]
        if vals and all(isinstance(v, (int, float)) for v in vals):
            print("[summary] %-36s n=%d min %.6g max %.6g"
                  % (k, len(vals), min(vals), max(vals)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
