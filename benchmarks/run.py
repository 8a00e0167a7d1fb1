"""Benchmark harness — one module per paper table/figure.

  table1          paper Table 1: lookup time / memory / encode overhead
  figure1         paper Figure 1: accuracy of the four attention variants
  decode_scaling  Table-1 inside a full transformer (O(1) vs O(n) decode)
  mass_serving    the §2.2 retrieval scenario: encode once, query many
  roofline        §Roofline summary from the dry-run artifacts

``python -m benchmarks.run [--fast] [--only NAME]`` prints CSV lines.
"""

from __future__ import annotations

import argparse
import sys
import time


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="reduced figure-1 steps")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    from benchmarks import decode_scaling, figure1, mass_serving, \
        roofline, table1

    benches = {
        "table1": table1.main,
        "decode_scaling": decode_scaling.main,
        "mass_serving": mass_serving.main,
        "roofline": roofline.main,
        "figure1": (lambda: figure1.main(steps=240)) if args.fast
        else figure1.main,
    }
    if args.only:
        benches = {args.only: benches[args.only]}

    failed = []
    for name, fn in benches.items():
        t0 = time.time()
        print(f"# --- {name} ---", flush=True)
        try:
            for line in fn():
                print(line, flush=True)
        except Exception as e:  # report and continue
            failed.append(name)
            print(f"{name},ERROR,{type(e).__name__}: {e}", flush=True)
        print(f"# {name} done in {time.time()-t0:.0f}s", flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
