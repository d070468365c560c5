"""Run one benchmark cell once on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout: ``BENCHMARK.json`` names the cell, its
configuration and its traffic mix, which this directory holds (see
``spec.py``); the program under test is the checkout's ``src/``.

It refuses to run (exit 2, no result) unless JAX's first device is a TPU and
there are as many as the cell asks for. Earlier lines of standard output
give the device, the compiles counted in each phase (none is expected in
the window), how late the generator ran, and what was served. With
``--trace 0`` the result holds the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics from a profiler trace of the middle of
the window. The numbers that decide ``correct`` are printed beside their
limits as the last lines of standard error. The last line of standard
output is the result's JSON object.

JAX's persistent compilation cache is kept at ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parent


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CHECKOUT / ".jax_cache")
    sys.path[:0] = [str(HERE), str(CHECKOUT / "src")]
    import spec

    parts = spec.Parts(HERE, CHECKOUT / "BENCHMARK.json")
    cell = parts.workload(args.workload)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" or len(devices) < cell["chips"]:
        print(f"run: cell {args.workload} needs {cell['chips']} TPU chip(s); JAX has "
              f"{len(devices)} {dev.platform} device(s) ({dev.device_kind}). Not running.",
              file=sys.stderr)
        return 2
    print(f"platform {dev.platform}, device_kind {dev.device_kind}, device count {len(devices)}",
          flush=True)
    parts.peaks(dev.device_kind)                 # an unknown device is an error
    from repro.jax_cache import enable_compile_cache

    print(f"compile cache {enable_compile_cache()}", flush=True)
    import harness

    res = harness.run(parts, args.workload, args.seed, args.seconds, bool(args.trace), T_START)
    info = res.pop("info")
    print(f"compiles by phase {json.dumps(info.pop('compiles'))} (window: expect none)")
    print(f"generator late ms {json.dumps(info.pop('generator_late_ms'))}")
    for k, v in info.items():
        print(f"{k} {json.dumps(v)}")
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
              "memory_peak_bytes": res.pop("memory_peak_bytes")}
    trace = res.pop("device_trace", None)
    if trace is not None:
        device.update(trace)
    checks = res.pop("checks")
    out = {"correct": res["correct"], "attempted": res["attempted"], "failed": res["failed"],
           "metrics": res["metrics"], "device": device}
    if "breakdown" in res:
        out["breakdown"] = res["breakdown"]
    out["checks"] = checks
    sys.stdout.flush()
    for name, c in checks.items():
        print(f"check {name} {c['value']} limit {c['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
