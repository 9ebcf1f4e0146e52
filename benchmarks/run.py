"""Benchmark runner: one registered benchmark per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [names...]

Prints ``name,us_per_call,derived`` CSV (plus # section markers).  The
serving engine's benchmark is ``bench/`` (``BENCHMARK.json``, on the
chip); these are the paper-reproduction microbenchmarks.
"""

from __future__ import annotations

import sys

# importing registers every benchmark
from benchmarks import (async_copy, dpx, dsm, memory,  # noqa: F401
                        roofline_table, te_layer, te_linear,
                        tensorcore)
from repro.core.bench import run_all
from repro.launch import compile_cache


def main() -> None:
    names = sys.argv[1:] or None
    compile_cache.enable()
    failures = run_all(names)
    if failures:
        raise SystemExit(f"{failures} benchmark(s) failed")


if __name__ == "__main__":
    main()
