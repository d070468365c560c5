"""Benchmark harness: one module per paper table/figure + roofline + micro.
Prints ``name,us_per_call,derived`` CSV rows (benchmarks.common.emit)."""
from __future__ import annotations

import sys
import time
import traceback


def main() -> None:
    from benchmarks import (
        fig3_training,
        fig4_interactive,
        fig5_elastic,
        fig6_7_platforms,
        fig8_response,
        microbench,
        paged_decode,
        placement,
        roofline,
    )

    modules = [
        ("fig4_interactive", fig4_interactive),
        ("fig5_elastic", fig5_elastic),
        ("fig6_7_platforms", fig6_7_platforms),
        ("fig8_response", fig8_response),
        ("placement", placement),
        ("fig3_training", fig3_training),
        ("roofline", roofline),
        ("microbench", microbench),
        ("paged_decode", paged_decode),
    ]
    print("name,us_per_call,derived")
    failures = 0
    for name, mod in modules:
        t0 = time.time()
        try:
            mod.main()
        except Exception:
            failures += 1
            print(f"{name},0.0,ERROR:{traceback.format_exc().splitlines()[-1][:120]}")
        print(f"# {name} done in {time.time()-t0:.1f}s", file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    from repro.jax_cache import enable_compile_cache

    enable_compile_cache()
    main()
