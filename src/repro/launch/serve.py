"""Serving entry point: continuous-batching server over an arch config.

    PYTHONPATH=src python -m repro.launch.serve --arch yi-6b \
        --requests 8 --slots 4

serves the published config (Yi-6B: 32 layers, d_model 4096) with
random bf16 weights made from ``--seed`` on the device — a TPU run.
On the CPU serve the same-family reduced config instead:

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.serve \
        --arch yi-6b --reduced

``--workload sysprompt``
serves the shared-prefix mix (a few system-prompt templates × unique
tails) and prints the radix prefix cache's hit-rate stats; disable the
cache with ``--no-prefix-cache`` for an A/B run.  ``--spec-decode K``
turns on speculative decoding (n-gram drafts + one-dispatch verify,
bit-identical outputs); pair it with ``--workload repetitive`` to see
the accepted-tokens-per-step climb above 1.  ``--tp N`` serves
tensor-parallel over an N-device mesh (weights head-wise/column-row,
KV pool along the KV-head axis; outputs agree with tp=1 to the order
of the projections' fp32 sums) and prints the per-device sharding
stats:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
        PYTHONPATH=src python -m repro.launch.serve --reduced --tp 4

``--arrival-rate R`` switches from the closed batch to open-loop
serving: requests arrive on a Poisson stream at R req/s
(runtime/arrivals) and queue delay is charged from arrival.
``--duration S`` sizes the stream to ~R*S requests; ``--slo-ttft-ms``
/ ``--slo-tpot-ms`` (always together) score the run against latency
deadlines and print the attainment / goodput / windowed-throughput
summary (obs/slo, obs/windows).  All of it composes with
``--trace/--trace-out``:

    PYTHONPATH=src python -m repro.launch.serve --reduced --arrival-rate 4 \
        --duration 5 --slo-ttft-ms 500 --slo-tpot-ms 80 \
        --trace --trace-out /tmp/online
"""

from __future__ import annotations

import argparse

import jax

from repro.configs import get_config, list_archs, reduced_config
from repro.launch import compile_cache
from repro.launch.mesh import make_tp_mesh
from repro.models import api
from repro.obs import (SLOSpec, Tracer, gap_summary, phase_summary,
                       slo_report, summary_table, window_series,
                       window_summary, write_chrome_trace, write_jsonl)
from repro.runtime.arrivals import poisson_stream
from repro.runtime.server import (ChunkedServer, repetitive_requests,
                                  sharegpt_like_requests,
                                  sysprompt_sharegpt_requests)
from repro.sharding import plans


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b", choices=list_archs())
    ap.add_argument("--reduced", action="store_true",
                    help="serve the same-family reduced config (2 "
                         "layers, d_model 64, vocab 256) instead of the "
                         "published one — for CPU runs")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--chunk", type=int, default=16,
                    help="prefill chunk size")
    ap.add_argument("--span", type=int, default=8,
                    help="device-resident decode steps per dispatch")
    ap.add_argument("--block-size", type=int, default=16,
                    help="KV tokens per paged-cache block")
    ap.add_argument("--pool-blocks", type=int, default=None,
                    help="paged-cache pool size in blocks (default: "
                         "slots * ceil(max_len / block_size))")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable the radix-tree prefix cache over the "
                         "paged pool (A/B; cached greedy outputs are "
                         "bit-identical either way)")
    ap.add_argument("--eos-id", type=int, default=None,
                    help="stop a request when it emits this token id "
                         "(device-side); default: "
                         "length-only stopping")
    ap.add_argument("--spec-decode", type=int, default=0, metavar="K",
                    help="speculative decoding: draft "
                         "up to K tokens per slot from a device-"
                         "resident n-gram suffix table and verify all "
                         "of them in one fixed-shape dispatch, "
                         "accepting the longest prefix that matches "
                         "the model's own greedy argmax — outputs are "
                         "K=0's (up to the attention's fp32 summation "
                         "order), only the number of model dispatches "
                         "per token changes.  "
                         "Default 0 = off (plain decode spans)")
    ap.add_argument("--kernel", action="store_true",
                    help="read paged KV through the fused Pallas "
                         "block-table kernels (kernels/paged_attention"
                         ") instead of the gather path — outputs "
                         "agree within the kernel tests' tolerance")
    ap.add_argument("--fp8-kv", action="store_true",
                    help="store the paged KV pool as fp8 e4m3 codes + "
                         "per-row f32 scales (~0.53x pool bytes at "
                         "head_dim 64; tolerance-tier outputs)")
    ap.add_argument("--fp8-linear", action="store_true",
                    help="serve the projection/MLP matmuls through "
                         "fp8-quantized weights (te/linear; tp=1, "
                         "dense only)")
    ap.add_argument("--tp", type=int, default=1, metavar="N",
                    help="tensor-parallel degree: "
                         "shard the weights head-wise/column-row-wise "
                         "and the paged KV pool along its KV-head axis "
                         "over an N-device mesh "
                         "(sharding/plans.ServingPlan); outputs agree "
                         "with tp=1 to the order of fp32 sums.  "
                         "On CPU fan "
                         "devices out first: XLA_FLAGS=--xla_force_"
                         "host_platform_device_count=N")
    ap.add_argument("--workload", default="sharegpt",
                    choices=("sharegpt", "sysprompt", "repetitive"),
                    help="sharegpt: log-normal independent prompts; "
                         "sysprompt: shared system-prompt templates x "
                         "unique tails (exercises prefix sharing); "
                         "repetitive: tiled-motif prompts (high n-gram "
                         "hit rate — exercises --spec-decode)")
    ap.add_argument("--templates", type=int, default=2,
                    help="number of shared templates (sysprompt) / "
                         "motifs (repetitive)")
    ap.add_argument("--max-input", type=int, default=32)
    ap.add_argument("--max-output", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--arrival-rate", type=float, default=None,
                    metavar="RPS",
                    help="serve open-loop: Poisson request arrivals at "
                         "RPS req/s against the monotonic clock "
                         "(queue delay and TTFT are "
                         "charged from arrival).  Default: closed "
                         "batch, all requests at t=0")
    ap.add_argument("--duration", type=float, default=None, metavar="S",
                    help="with --arrival-rate, size the stream to "
                         "~rate*S requests (~S seconds of offered "
                         "traffic) instead of --requests")
    ap.add_argument("--slo-ttft-ms", type=float, default=None,
                    help="TTFT deadline in ms; with --slo-tpot-ms, "
                         "score the run's SLO attainment and goodput "
                         "(obs/slo; implies --trace)")
    ap.add_argument("--slo-tpot-ms", type=float, default=None,
                    help="TPOT deadline in ms (mean inter-token time "
                         "after the first); see --slo-ttft-ms")
    ap.add_argument("--trace", action="store_true",
                    help="record per-request lifecycle events + "
                         "dispatch timings (repro.obs) and print the "
                         "latency/phase/occupancy summary table; "
                         "host-side only, outputs stay bit-identical")
    ap.add_argument("--trace-out", metavar="PREFIX", default=None,
                    help="with --trace, also write PREFIX.jsonl "
                         "(structured events) and PREFIX.trace.json "
                         "(Chrome trace-event format, Perfetto-"
                         "loadable)")
    args = ap.parse_args()

    if (args.slo_ttft_ms is None) != (args.slo_tpot_ms is None):
        raise SystemExit("--slo-ttft-ms and --slo-tpot-ms go together "
                         "(the SLO predicate needs both deadlines)")
    if args.duration is not None and args.arrival_rate is None:
        raise SystemExit("--duration needs --arrival-rate (it sizes "
                         "the open-loop stream)")
    if args.slo_ttft_ms is not None:
        args.trace = True   # attainment is scored off the tracer
    if args.duration is not None:
        args.requests = max(1, round(args.arrival_rate * args.duration))

    compile_cache.enable()
    cfg = (reduced_config if args.reduced else get_config)(args.arch)
    if cfg.family not in ("dense", "moe", "vlm"):
        raise SystemExit(
            f"{args.arch} ({cfg.family}): the serving engine currently "
            "drive the transformer decode path; SSM/hybrid/enc-dec "
            "decode is exercised via api.decode_step (see tests).")
    # serving weights: cfg.dtype, built on the device, and under a tp
    # mesh built already sharded (no whole copy on the first device)
    mesh = make_tp_mesh(args.tp) if args.tp > 1 else None
    params = api.init(
        cfg, jax.random.PRNGKey(args.seed), dtype=cfg.dtype,
        shardings=(plans.serving_plan(mesh).param_shardings(cfg)
                   if mesh is not None else None))
    max_len = args.max_input + args.max_output + 8
    tracer = Tracer() if args.trace else None
    srv = ChunkedServer(cfg, params, batch_slots=args.slots,
                        max_len=max_len, chunk=args.chunk,
                        span=args.span, block_size=args.block_size,
                        num_blocks=args.pool_blocks,
                        prefix_cache=not args.no_prefix_cache,
                        eos_id=args.eos_id,
                        spec_decode=args.spec_decode,
                        kernel=args.kernel, fp8_kv=args.fp8_kv,
                        fp8_linear=args.fp8_linear,
                        mesh=mesh, tracer=tracer)
    if args.tp > 1:
        import jax.tree_util as jtu
        param_bytes = sum(x.nbytes for x in jtu.tree_leaves(srv.params))
        kv_bytes = sum(x.nbytes for x in jtu.tree_leaves(srv.cache))
        leaves = jtu.tree_leaves(srv.params)
        sharded = sum(1 for x in leaves
                      if not x.sharding.is_fully_replicated)
        per_dev = sum(x.nbytes if x.sharding.is_fully_replicated
                      else x.nbytes // srv.tp for x in leaves)
        print(f"  tp-mesh: {srv.tp} devices on axis "
              f"'{srv.mesh.axis_names[0]}' "
              f"({[str(d) for d in srv.mesh.devices.ravel()]})")
        print(f"  sharding: {sharded}/{len(leaves)} param tensors "
              f"sharded, {param_bytes / 1e6:.2f} MB params -> "
              f"{per_dev / 1e6:.2f} MB/device, "
              f"KV pool {kv_bytes / 1e6:.2f} MB -> "
              f"{kv_bytes // srv.tp / 1e6:.2f} MB/device "
              f"(KV-head axis {cfg.num_kv_heads} -> "
              f"{cfg.num_kv_heads // srv.tp}/device)")
    if args.workload == "repetitive":
        reqs = repetitive_requests(args.requests, cfg.vocab_size,
                                   num_motifs=args.templates,
                                   motif_len=max(args.max_input // 4, 1),
                                   reps=4, max_output=args.max_output,
                                   seed=args.seed)
    elif args.workload == "sysprompt":
        if args.max_input < 2:
            raise SystemExit(
                "--workload sysprompt needs --max-input >= 2 (a shared "
                "template prefix plus at least one unique tail token)")
        reqs = sysprompt_sharegpt_requests(
            args.requests, cfg.vocab_size, num_templates=args.templates,
            template_len=max(args.max_input // 2, 1),
            max_input=args.max_input, max_output=args.max_output,
            seed=args.seed)
    else:
        reqs = sharegpt_like_requests(args.requests, cfg.vocab_size,
                                      max_input=args.max_input,
                                      max_output=args.max_output,
                                      seed=args.seed)
    if args.arrival_rate is not None:
        stream = poisson_stream(reqs, args.arrival_rate,
                                seed=args.seed)
        stats = srv.serve_online(stream)
    else:
        stats = srv.serve(reqs)
    print(f"arch={args.arch} workload={args.workload} "
          f"requests={int(stats['requests'])} "
          f"tokens={int(stats['tokens'])} "
          f"throughput={stats['tokens_per_s']:.1f} tok/s "
          f"(paper Table XII protocol)")
    if args.arrival_rate is not None:
        print(f"  open-loop: target={args.arrival_rate:.2f} req/s "
              f"offered={stats['offered_rate_rps']:.2f} req/s over "
              f"{stats['arrival_span_s']:.2f}s of arrivals, "
              f"peak-queue-depth={int(stats['peak_queue_depth'])}, "
              f"idle={stats['idle_s']:.2f}s of {stats['seconds']:.2f}s")
    counts = srv.compile_counts()
    per_program = " ".join(f"{name}={max(n, 0)}"
                           for name, n in sorted(counts.items()))
    # per-phase dispatch counts + wall-time breakdown from the metrics
    # registry the dispatch methods feed (repro.obs) — live with or
    # without --trace
    phases = phase_summary(srv.metrics)
    gap = gap_summary(srv.metrics)
    breakdown = " ".join(
        f"{name}={d['wall_s']:.2f}s/{d['dispatches']}d"
        f"(host={d['host_s']:.2f}s,wait={d['wait_s']:.2f}s)"
        for name, d in phases.items() if d["dispatches"])
    print(f"  phases: {breakdown} gap={gap['gap_s']:.2f}s "
          f"compiled_programs="
          f"{sum(max(v, 0) for v in counts.values())} "
          f"({per_program})")
    print(f"  paged-kv: {int(stats['peak_blocks_in_use'])}/"
          f"{int(stats['pool_blocks'])} blocks peak "
          f"(x{int(stats['block_size'])} tokens, "
          f"utilization={stats['pool_utilization']:.2f}, "
          f"stalls={int(stats['admission_stalls'])}, "
          f"capacity {int(stats['kv_tokens_capacity'])} tokens)")
    if "spec_k" in stats:
        print(f"  spec-decode: K={int(stats['spec_k'])} "
              f"accepted={int(stats['spec_accepted_tokens'])}/"
              f"{int(stats['spec_drafted_tokens'])} drafts "
              f"(rate={stats['spec_acceptance_rate']:.2f}), "
              f"{stats['spec_tokens_per_step']:.2f} tokens/step "
              f"over {int(stats['spec_steps'])} verify dispatches")
    if "prefix_cache_enabled" in stats:
        print(f"  prefix-cache: hit-rate="
              f"{stats['prefix_hit_rate']:.2f} "
              f"({int(stats['prefix_hit_requests'])}/"
              f"{int(stats['requests'])} requests), "
              f"cached-token-frac={stats['cached_token_fraction']:.2f} "
              f"({int(stats['prefix_cached_tokens'])}/"
              f"{int(stats['prompt_tokens_total'])} prompt tokens), "
              f"resident={int(stats['cached_blocks'])} blocks, "
              f"evictions={int(stats['cache_evictions'])}")
    if tracer is not None:
        print(summary_table(tracer))
        window_s = max(stats["seconds"] / 8.0, 0.02)
        if args.slo_ttft_ms is not None:
            slo = SLOSpec(ttft_s=args.slo_ttft_ms / 1e3,
                          tpot_s=args.slo_tpot_ms / 1e3)
            rep = slo_report(tracer, slo, stats["seconds"])
            wsum = window_summary(window_series(tracer, window_s))
            tps = wsum["tokens_per_s"]
            print(f"  slo: ttft<={args.slo_ttft_ms:.0f}ms "
                  f"tpot<={args.slo_tpot_ms:.0f}ms -> "
                  f"attainment={rep['attainment']:.2%} "
                  f"({rep['met']}/{rep['finished']} met, "
                  f"{rep['ttft_misses']} ttft / "
                  f"{rep['tpot_misses']} tpot misses)")
            print(f"  goodput: {rep['goodput_tok_s']:.1f} of "
                  f"{rep['throughput_tok_s']:.1f} tok/s from SLO-met "
                  f"requests ({int(rep['good_tokens'])}/"
                  f"{int(rep['finished_tokens'])} output tokens)")
            print(f"  windowed({window_s * 1e3:.0f}ms x "
                  f"{wsum['n_windows']}): tok/s p50={tps['p50']:.1f} "
                  f"p95={tps['p95']:.1f} p99={tps['p99']:.1f}, "
                  f"peak-queue-depth={wsum['peak_queue_depth']}, "
                  f"stalls={wsum['stalls']}")
        if args.trace_out:
            n = write_jsonl(tracer, f"{args.trace_out}.jsonl")
            m = write_chrome_trace(tracer,
                                   f"{args.trace_out}.trace.json",
                                   window_s=window_s)
            print(f"  wrote {args.trace_out}.jsonl ({n} lines), "
                  f"{args.trace_out}.trace.json ({m} events)")


if __name__ == "__main__":
    main()
