"""Find a cell's knee once, by hand, on the chip: serve its open-loop
mix at several fixed rates, one window each, in one process.

    python3 bench/sweep.py --workload yi6b-chat --rates 1,1.5,2,2.5 --seconds 30

Give the rates in rising order: a rate whose requests have not all
finished ``--drain`` seconds after its window ends the sweep.  For each rate it prints the
requests sent and finished, TTFT and TPOT percentiles, the queue wait of the last third of the arrivals against
the first third (a backlog that grows shows as a rising wait), and the
seconds the drain took after the last arrival.  The benchmark's cells
then run at a fixed rate below the knee; nothing here searches during
a benchmark run.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--drain", type=float, default=60.0,
                    help="seconds after the window before the rest of a "
                         "rate's requests count as unfinished")
    args = ap.parse_args(argv)
    from bench import harness, traffic, weights
    from repro.runtime.server import ChunkedServer
    c = harness.load_cell(args.workload)
    harness.require_chips(c["cell"]["chips"])
    harness.enable_compile_cache()
    conf, mix = c["conf"], dict(c["mix"])
    dm = weights.dims(conf)
    params = harness.make_params(harness.program_config(conf), conf,
                                 args.seed)
    tracer = harness.make_tracer()
    srv = ChunkedServer(harness.program_config(conf), params,
                        batch_slots=conf["batch_slots"],
                        max_len=conf["max_len"], tracer=tracer)
    harness.warm(srv, traffic.warmup_items(mix, args.seed, dm["V"], srv.B,
                                           srv.chunk))
    drv = harness.Driver(srv, dm)
    rows = []
    for rate in [float(r) for r in args.rates.split(",")]:
        mix["rate_rps"] = rate
        tracer.clear()
        items = traffic.open_loop(mix, args.seed, dm["V"], args.seconds)
        t0 = time.perf_counter()
        drv.deadline = t0 + args.seconds + args.drain
        timed_out = False
        try:
            srv.serve_online(harness.to_requests(items))
        except harness.DrainTimeout:
            timed_out = True
        wall = time.perf_counter() - t0
        recs = tracer.requests
        tt = [harness._ttft(recs.get(i.rid)) for i in items]
        tp = [recs[i.rid].tpot_s for i in items
              if i.rid in recs and recs[i.rid].tpot_s]
        qw = [harness._ttft(recs.get(i.rid)) if recs.get(i.rid) is None
              or recs[i.rid].queue_delay_s is None
              else recs[i.rid].queue_delay_s for i in items]
        done = sum(1 for i in items if i.rid in recs
                   and recs[i.rid].t_done is not None)
        third = max(len(items) // 3, 1)
        row = {"rate": rate, "sent": len(items), "finished": done,
               "drain_s": wall - items[-1].t_arrival,
               "ttft_p50_ms": harness.pct(tt, 50) * 1e3,
               "ttft_p90_ms": harness.pct(tt, 90) * 1e3,
               "tpot_p90_ms": harness.pct(tp, 90) * 1e3,
               "wait_first_third_ms": harness.pct(qw[:third], 50) * 1e3,
               "wait_last_third_ms": harness.pct(qw[-third:], 50) * 1e3}
        rows.append(row)
        print(json.dumps(row), flush=True)
        if timed_out:       # the slots still hold this rate's requests
            break
    print(json.dumps({"sweep": args.workload, "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
