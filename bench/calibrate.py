"""Readings that a cell's limits are set from (not part of a run).

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 10 [--control]

In one process, for each seed: a run of the cell with a short window,
then the plain reference over the same sample of finished requests a
run compares, giving the program's ``logit_gap_max``. With
``--control``, also the control's reading on the same requests: the
reference in float8 in the program's place (the gap, in the float32
reference, of the token the float8 forward ranks first). Prints one
JSON line per seed, with the verdict ``bench/run.py`` would give each
reading under the cell's limits: the control's has to read not correct.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import run  # noqa: E402  (puts src/ on the path)
from bench import check, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    why = run.device_check(cell.chips)
    if why:
        print(f"calibrate: {why}", file=sys.stderr)
        return 2
    run.use_cache()
    conf = cell.config
    for seed in (int(s) for s in args.seeds.split(",")):
        result, params, records = run.measure(
            cell, seed, args.seconds, False, t_process=time.perf_counter())
        picked = check.sample(records, seed, cell.traffic["check"])
        prog = check.compare(params, conf, picked, conf["vocab_size"])
        line = {"seed": seed, "metrics": result["metrics"], "program": prog,
                "program_correct": check.verdict(prog, cell.limits)}
        if args.control:
            ctrl = check.compare(params, conf, picked, conf["vocab_size"],
                                 control=True)
            line["control"] = ctrl
            line["control_correct"] = check.verdict(ctrl, cell.limits)
        print(json.dumps(line), flush=True)
        del params, records
    return 0


if __name__ == "__main__":
    sys.exit(main())
