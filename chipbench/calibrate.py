"""Readings a cell's fixed numbers are set from, taken on the chip in one
process per command (set-up once, then one short serve per rate or seed).

    python3 chipbench/calibrate.py sweep --workload <cell> --rates 0.5,1,2 --seconds 40 [--drain 60]
    python3 chipbench/calibrate.py limits --workload <cell> --seeds 1,2,3 --seconds 20 \
        [--drain 60] [--control] [--fault token|cache]

``sweep`` serves the cell's mix at each rate and prints, per rate, how many
of the window's requests finished within the drain, the time to first token
of the window's first and last thirds (a backlog that grows shows as a
rising tail), and the tokens per second: the knee is the highest rate whose
backlog does not grow. ``limits`` serves the mix once per seed with that
seed's weights and prints the program's numbers that ``correct`` compares
and, with ``--control``, the run's verdict (``harness.verdict``: the checks
beside their limits, and ``correct``) with the tokens of the reference
computed in the configuration's control precision in the program's place.
``--fault`` plants one of ``faults.py``'s faults in every engine first, and
the verdicts are of the broken program. ``--drain`` is how long each serve
waits for the window's requests after it closes. Neither command is part of
a benchmark run.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("what", choices=("sweep", "limits"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", default="")
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--drain", type=float, default=60.0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault", choices=("token", "cache"))
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CHECKOUT / ".jax_cache")
    sys.path[:0] = [str(HERE), str(CHECKOUT / "src")]
    import jax
    import numpy as np

    import faults
    import harness
    import spec

    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 2
    from repro.jax_cache import enable_compile_cache

    enable_compile_cache()
    parts = spec.Parts(HERE, CHECKOUT / "BENCHMARK.json")
    seeds = [int(s) for s in args.seeds.split(",")]
    s = harness.Session(parts, args.workload, seeds[0])
    if args.fault:
        for eng in s.dep.engines.values():
            faults.FAULTS[args.fault](eng)
    print(f"set-up {time.monotonic() - T_START:.1f}s", flush=True)
    if args.what == "sweep":
        for rate in [float(r) for r in args.rates.split(",")]:
            mix = json.loads(json.dumps(s.mix))
            mix["arrivals"]["rate_per_s"] = rate
            sched = s.schedule(seeds[0], args.seconds, mix)
            sv = s.serve(sched, drain_s=args.drain)
            win = sv["window"]
            ttft = [s_.token_times[0] - (sv["t0"] + r.due_s) for r in win
                    for s_ in [sv["seqs"].get(r.rid)] if s_ is not None and r.rid in sv["done"]]
            third = max(1, len(win) // 3)
            first = [s_.token_times[0] - (sv["t0"] + r.due_s) for r in win[:third]
                     for s_ in [sv["seqs"].get(r.rid)] if s_ is not None]
            last = [s_.token_times[0] - (sv["t0"] + r.due_s) for r in win[-third:]
                    for s_ in [sv["seqs"].get(r.rid)] if s_ is not None]
            toks = sum(1 for q in list(sv["seqs"].values()) for t in q.token_times
                       if sv["t0"] <= t < sv["close"]) + sum(
                1 for ts in s.live_stamps() for t in ts if sv["t0"] <= t < sv["close"])
            in_close = sum(1 for r in win if r.rid in sv["done"]
                           and sv["requests"][r.rid].finish_t <= sv["close"])
            tiers = {}
            for r in win:
                t = harness.tier_name(sv["requests"][r.rid])
                tiers[t] = tiers.get(t, 0) + 1
            gaps = [b - a for r in win for s_ in [sv["seqs"].get(r.rid)] if s_ is not None
                    for a, b in zip(s_.token_times, s_.token_times[1:])]
            pct = lambda v, q: round(float(np.percentile(v, q)), 3) if v else None
            print(json.dumps({
                "rate": rate, "due": len(win), "done": len(sv["done"] & {r.rid for r in win}),
                "done_by_close": in_close, "failed": sv["failed"],
                "ttft_p50_s": pct(ttft, 50), "ttft_p95_s": pct(ttft, 95),
                "itl_p50_s": pct(gaps, 50), "itl_p95_s": pct(gaps, 95),
                "ttft_first_third_p50_s": pct(first, 50), "ttft_last_third_p50_s": pct(last, 50),
                "out_tok_s": toks / args.seconds, "tiers": tiers,
                "compiles": sv["compiles"]}), flush=True)
            time.sleep(5.0)
    else:
        for i, seed in enumerate(seeds):
            if i:
                s.reseed(seed)
            sched = s.schedule(seed, args.seconds)
            sv = s.serve(sched, drain_s=args.drain)
            t = time.monotonic()
            checks, correct, numbers, tiers = harness.verdict(s, sv, seed)
            numbers.update({"seed": seed, "fault": args.fault, "tiers": tiers,
                            "correct": correct, "checks": checks,
                            "reference_s": time.monotonic() - t})
            if args.control:
                checks, correct, _, _ = harness.verdict(s, sv, seed, control=True)
                numbers["control"] = {"correct": correct, "checks": checks}
            print(json.dumps(numbers), flush=True)
            time.sleep(3.0)
    s.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
