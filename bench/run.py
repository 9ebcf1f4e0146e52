"""Run one benchmark cell once and print its result.

    python3 bench/run.py --workload yi6b-chat --seed 7 --seconds 30 --trace 0

Set-up (weights from the seed on the device, compile or cache load,
warm-up of the cell's own shapes) is timed from process start; then the
cell's traffic is served for ``--seconds`` through the program's
``ChunkedServer.serve_online``; then, with the program freed, a sample
of the greedy requests it finished is compared with a float32 reference.
``--trace 1`` also profiles a few seconds of the window and reports the
cell's per-layer metrics instead of its end-to-end ones.  The last line
of standard output is the JSON result; the numbers compared, with their
limits, are the last lines of standard error.  Without an accelerator,
or with fewer chips than the cell asks for, it exits nonzero and prints
no result.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness
    cell = harness.load_cell(args.workload)
    try:
        devs = harness.require_chips(cell["cell"]["chips"])
    except harness.NoChip as e:
        print(e, file=sys.stderr)
        return 3
    log = lambda m: print(m, flush=True)  # noqa: E731
    d = devs[0]
    log(f"device: platform {d.platform}, device_kind {d.device_kind}, "
        f"count {len(devs)}; compile cache "
        f"{harness.enable_compile_cache()}")
    result = harness.run_cell(cell, seed=args.seed, seconds=args.seconds,
                              trace=bool(args.trace), devs=devs,
                              t_process=T_PROCESS, log=log)
    line = harness.summary_line(result)
    print(f"{line}; correct {result['correct']}", file=sys.stderr)
    out = {k: result[k] for k in ("correct", "attempted", "failed",
                                  "metrics", "device")}
    if "breakdown" in result:
        out["breakdown"] = result["breakdown"]
    out["compared"] = result["check"]
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
