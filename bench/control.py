"""Readings for a cell's limit, on the chip: the program's widest logit
gap and the fp8 control's, over many seeds in one process.

    python3 bench/control.py --workload yi6b-chat --seeds 1,2,3 --seconds 20

For each seed this drives a whole run of the cell (set-up, a window of
``--seconds`` at the cell's own load, the check) and, in the same
reference pass, reads the control: the reference with its weights
rounded to e4m3 (per output channel) in the program's place, and the
gap of the token it puts first at each position of the same prompts
and served tokens.  The lower reading is the largest program gap over
the seeds, the upper the smallest control gap.  The benchmark's own
runs never run the control.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def readings(cell, seeds, seconds, devs, log, t_process):
    from bench import harness
    rows = []
    for seed in seeds:
        r = harness.run_cell(cell, seed=seed, seconds=seconds, trace=False,
                             devs=devs, t_process=t_process, log=log,
                             control=True)
        gap = r["check"].get("max_logit_gap", {}).get("value")
        ctl = (r.get("control") or {}).get("max_logit_gap")
        rows.append({"seed": seed, "program": gap, "control": ctl,
                     "failed": r["failed"], "attempted": r["attempted"]})
        log(f"seed {seed}: program {gap}, control {ctl}, failed "
            f"{r['failed']} of {r['attempted']}")
        t_process = time.perf_counter()
    prog = [x["program"] for x in rows if x["program"] is not None]
    ctl = [x["control"] for x in rows if x["control"] is not None]
    return {"rows": rows, "lower": max(prog) if prog else None,
            "upper": min(ctl) if ctl else None}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from bench import harness
    cell = harness.load_cell(args.workload)
    try:
        devs = harness.require_chips(cell["cell"]["chips"])
    except harness.NoChip as e:
        print(e, file=sys.stderr)
        return 3
    harness.enable_compile_cache()
    log = lambda m: print(m, flush=True)  # noqa: E731
    out = readings(cell, [int(s) for s in args.seeds.split(",")],
                   args.seconds, devs, log, T_PROCESS)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
