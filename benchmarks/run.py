"""Benchmark runner: one registered benchmark per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [names...]

Prints ``name,us_per_call,derived`` CSV (plus # section markers).
After a run that includes ``llm_generation``, writes the serving
numbers (tokens/s, prefill/decode split, compile counts, parity, pool
utilization, and the shared-prefix mix's prefix-cache hit rate /
cached-token fraction / with-vs-without-sharing speedup) to
``BENCH_serving.json`` so future PRs have a perf trajectory to compare
against; CI uploads the file as a workflow artifact per run.
"""

from __future__ import annotations

import json
import sys

# importing registers every benchmark
from benchmarks import (async_copy, dpx, dsm, llm_gen, memory,  # noqa: F401
                        roofline_table, te_layer, te_linear,
                        tensorcore)
from repro.core.bench import run_all
from repro.launch import compile_cache

SERVING_JSON = "BENCH_serving.json"


def main() -> None:
    names = sys.argv[1:] or None
    compile_cache.enable()
    failures = run_all(names)
    if llm_gen.SERVING_RESULTS:
        with open(SERVING_JSON, "w") as f:
            json.dump(llm_gen.SERVING_RESULTS, f, indent=2, sort_keys=True)
        print(f"# wrote {SERVING_JSON}")
    if failures:
        raise SystemExit(f"{failures} benchmark(s) failed")


if __name__ == "__main__":
    main()
