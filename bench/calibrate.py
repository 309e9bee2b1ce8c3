"""Readings for the correctness limit of a cell: for each seed, one short
run of the cell (its own sizes and load) and the widest logit gap of the
program's served tokens against the reference; for the control seeds, the
gap of the tokens the reference one precision step down puts first at the
same positions. One process. Each seed's line gives the harness's verdict
(``run.verdict``) on the program's checks and on the control's; the run
exits non-zero unless every program seed reads correct and every control
seed reads not correct.

    python bench/calibrate.py --workload <name> --seeds 1,2,3 --control 1,2,3
"""
import argparse
import sys

import run
from benchlib import REPO_ROOT, spec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default="")
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    bench = spec.load_benchmark(REPO_ROOT)
    cell = spec.workload(bench, args.workload)
    dev = run.require_devices(cell["chips"])
    run.enable_cache()
    control = {int(s) for s in args.control.split(",") if s}
    sound = True
    for seed in (int(s) for s in args.seeds.split(",")):
        seen = {}

        def inspect(sample, params, block, dims, cfile, seed=seed):
            if seed in control:
                seen["control"] = run.check_outputs(
                    block, sample, params, dims, cfile["eos_token_id"],
                    cfile["check"]["logit_gap"], quant="control")
        res = run.run_cell(bench, cell, seed, args.seconds, False, dev,
                           inspect=inspect)
        line = (f"CALIBRATE seed {seed} program "
                f"{res['checks']['logit_gap']['value']!r} correct "
                f"{res['correct']}")
        sound &= res["correct"]
        if "control" in seen:
            ctl = seen["control"]
            line += (f" control {ctl['logit_gap'][0]!r} limit "
                     f"{ctl['logit_gap'][1]!r} correct {run.verdict(ctl)}")
            sound &= not run.verdict(ctl)
        print(line, flush=True)
    return 0 if sound else 1


if __name__ == "__main__":
    sys.exit(main())
