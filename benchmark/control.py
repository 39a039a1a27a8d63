#!/usr/bin/env python3
"""The control of `correct`, on the chip at a cell's own size: short
runs of the cell on several seeds, sound ones and ones with a fault
planted (benchmark/faults.py), in one call; one JSON line per run with
every number compared beside its limit. Not part of a benchmark run —
the builder of a benchmark PR runs it and writes the readings into
PERF.md.

    python3 benchmark/control.py --workload <cell> --seeds 11,12,13 \\
        --seconds 10 --faults none,wrong_matrix,below_quorum

and, for a cell that reads, `flip_get_byte` and `deaf_deframer` among
the faults; for a configuration with dead drives, `unblocked_roots`.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import cells, faults, run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10)
    ap.add_argument("--faults", default="none")
    args = ap.parse_args()
    cells.load_cell(args.workload)         # an unknown cell: said at once
    bad = 0
    for fault in args.faults.split(","):
        for seed in (int(s) for s in args.seeds.split(",")):
            hooks = {} if fault == "none" else faults.hooks_for(fault)
            try:
                res, _ = run.run_cell(args.workload, seed, args.seconds,
                                      False, hooks)
                line = {"workload": args.workload, "fault": fault,
                        "seed": seed, "correct": res["correct"],
                        "compared": {k: v["value"] for k, v
                                     in res["compared"].items()},
                        "attempted": res["attempted"],
                        "failed": res["failed"],
                        "metrics": {k: v["value"] for k, v
                                    in res["metrics"].items()},
                        "cell": res["cell"], "device": res["device"]}
                # a sound run has to be correct, a faulty one must not be
                bad += res["correct"] != (fault == "none")
            except run.NoAccelerator as e:
                print(f"control: {e}", file=sys.stderr)
                return 3
            except Exception as e:  # noqa: BLE001 - a crash sets no reading
                # a run that crashes gives no reading: stop and mend it
                print(json.dumps({"workload": args.workload, "fault": fault,
                                  "seed": seed,
                                  "error": f"{type(e).__name__}: {e}"}),
                      flush=True)
                raise
            print(json.dumps(line), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
