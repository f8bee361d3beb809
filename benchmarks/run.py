"""Benchmark aggregator: one section per paper table + the roofline table.

    PYTHONPATH=src python -m benchmarks.run [--smoke] [--out-dir DIR]

Each section's structured result is written to ``BENCH_<section>.json`` in
``--out-dir`` (default: current directory). ``--smoke`` runs every table at
tiny scale — the CI smoke job uses it to prove the benchmarks execute
end-to-end and to upload the JSON artifacts; any section that raises makes
the process exit non-zero.
"""
from __future__ import annotations

import argparse
import os
import sys
import time
import traceback

from benchmarks.bench_out import write_bench


def _section(name: str, fn, *, smoke: bool, out_dir: str) -> bool:
    print(f"\n== {name} " + "=" * max(1, 60 - len(name)))
    t0 = time.time()
    ok = True
    try:
        result = fn(smoke=smoke, out_dir=out_dir)
    except Exception as e:  # keep the harness running, fail at exit
        print(f"ERROR,{type(e).__name__}: {e}")
        traceback.print_exc()
        result = {"error": f"{type(e).__name__}: {e}"}
        ok = False
    path = write_bench(name, result, smoke=smoke, ok=ok, out_dir=out_dir)
    print(f"-- {name} done in {time.time() - t0:.1f}s -> {path}")
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-scale run of every table (CI smoke job)")
    ap.add_argument("--out-dir", default=".",
                    help="where to write BENCH_*.json (default: cwd)")
    args = ap.parse_args(argv)
    os.makedirs(args.out_dir, exist_ok=True)
    from repro.utils.backend import use_compile_cache
    use_compile_cache(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

    from benchmarks import (roofline, stream_window, table1_llpr,
                            table2_kmeans, table3_terasort, wan_scenario)

    sections = [
        ("table1_llpr", table1_llpr.main),
        ("table2_kmeans", table2_kmeans.main),
        ("table3_terasort", table3_terasort.main),
        ("stream_window", stream_window.main),
        ("wan", wan_scenario.main),
        ("roofline", roofline.main),
    ]
    failed = [name for name, fn in sections
              if not _section(name, fn, smoke=args.smoke,
                              out_dir=args.out_dir)]
    if failed:
        print(f"\nFAILED sections: {', '.join(failed)}")
        return 1
    print(f"\nall {len(sections)} sections ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
