"""A smoke-size cell for CPU rehearsals of the harness: a copy of this
benchmark's directory with ``testdata/smoke``'s configuration, traffic mix
and limits added, and a row of peaks for the CPU (which the benchmark
itself never has: a run on the CPU is refused)."""
import json
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import spec  # noqa: E402

CELL = "smoke.tiny"
BIG_SEED = 2**31 + 12345


def parts(tmp: Path) -> spec.Parts:
    root = tmp / "chipbench"
    shutil.copytree(BENCH, root, ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for sub in ("configs", "traffic", "limits"):
        for f in (root / "testdata" / "smoke" / sub).iterdir():
            shutil.copy(f, root / sub / f.name)
    peaks = spec.load_json(root / "peaks.json")
    peaks["devices"]["cpu"] = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}
    (root / "peaks.json").write_text(json.dumps(peaks))
    return spec.Parts(root, root / "testdata" / "smoke" / "BENCHMARK.json")
