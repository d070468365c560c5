"""``BENCHMARK.json`` and the files it names: each part is found by name, an
unknown device has no peaks, and a new configuration, traffic mix, arrival
kind and metric are added as new files without editing any file there is."""
import hashlib
import json
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import deploy  # noqa: E402
import spec  # noqa: E402
import traffic  # noqa: E402

BENCHMARK = json.loads((BENCH.parent / "BENCHMARK.json").read_text())


def test_peaks():
    parts = spec.Parts(BENCH)
    v5e = parts.peaks("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert "cloud.google.com/tpu/docs/v5e" in spec.load_json(BENCH / "peaks.json")["source"]
    for kind in ("cpu", "TPU v4", "TPU v5"):
        with pytest.raises(spec.UnknownDevice):
            parts.peaks(kind)


def test_every_named_part_is_there():
    parts = spec.Parts(BENCH)
    assert BENCHMARK["paths"] == ["chipbench"]
    for c in BENCHMARK["configs"]:
        cfg = parts.config(c["name"])
        assert c["file"] == f"chipbench/configs/{c['name']}.json"
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        # every key changed from the source is listed, with its published value
        assert sorted(cfg["published"]) == sorted(c["reduced"])
        for k in c["reduced"]:
            assert cfg[k] != cfg["published"][k], k
        parts.reference(cfg["reference"])
    for w in BENCHMARK["workloads"]:
        assert w["config"] in {c["name"] for c in BENCHMARK["configs"]}
        t = parts.traffic(w["traffic"])
        parts.arrivals(t["arrivals"]["kind"])
        parts.lengths(t["prompt_tokens"]["dist"])
        assert set(parts.limits(w["name"])) >= {"max_logit_gap"}
        e2e = [m["name"] for m in parts.end_to_end_for(w["name"])]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert parts.per_layer_for(w["name"])
    for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]:
        if m["name"] != "setup_s":
            assert callable(parts.metric(m["name"]).read), m["name"]


def test_program_runs_the_files_model():
    """Every feature and size the file states is set on the program's model;
    the reference refuses a feature it does not compute."""
    parts = spec.Parts(BENCH)
    for c in BENCHMARK["configs"]:
        cfg = parts.config(c["name"])
        mc = deploy.model_config(cfg, use_pallas=True)
        m = cfg["model"]
        assert mc.use_pallas and mc.hd == m["head_dim"]
        for k in deploy.PROGRAM_KEYS:
            assert getattr(mc, k) == m[k], (c["name"], k)
        ref = parts.reference(cfg["reference"])
        ref.check(m)
        for k, bad in (("qkv_bias", True), ("norm", "layernorm"), ("gated_mlp", False)):
            with pytest.raises(ValueError, match=k):
                ref.check(dict(m, **{k: bad}))
    smollm = parts.config("smollm-360m")
    assert smollm["tie_word_embeddings"] and smollm["model"]["tie_embeddings"]
    assert deploy.model_config(smollm, use_pallas=True).tie_embeddings


def _digest(root: Path):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()}


NEW_METRIC = '''"""Requests due in the window that finished (count)."""


def read(obs):
    return sum(1 for r in obs["requests"] if r["done"]) or None
'''

NEW_REFERENCE = '''"""A llama reference that also computes query, key and value biases."""
SUPPORTS = {"tie_embeddings": (False, True), "qkv_bias": (False, True), "norm": ("rmsnorm",),
            "gated_mlp": (True,)}


def check(m):
    assert all(m[k] in ok for k, ok in SUPPORTS.items())
'''

NEW_ARRIVALS = '''"""Evenly spaced arrivals at ``rate_per_s``."""
import numpy as np


def count(p, span_s):
    return int(round(p["rate_per_s"] * span_s))


def times(p, n, span_s, rng):
    return np.arange(n) * (span_s / max(n, 1))
'''


def test_additions_need_no_edit(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests", "testdata"))
    shutil.copy(BENCH.parent / "BENCHMARK.json", root / "BENCHMARK.json")
    bench = root / "chipbench"
    before = _digest(bench)

    cfg = spec.load_json(bench / "configs" / "smollm-360m.json")
    cfg["name"] = "smollm-360m-b"
    cfg["reference"] = "llama_bias"
    cfg["model"]["qkv_bias"] = True
    (bench / "reference" / "llama_bias.py").write_text(NEW_REFERENCE)
    (bench / "configs" / "smollm-360m-b.json").write_text(json.dumps(cfg))
    mix = spec.load_json(bench / "traffic" / "chat.json")
    mix["arrivals"] = {"kind": "even", "rate_per_s": 2.0}
    (bench / "traffic" / "steady.json").write_text(json.dumps(mix))
    (bench / "arrivals" / "even.py").write_text(NEW_ARRIVALS)
    (bench / "metrics" / "done_count.py").write_text(NEW_METRIC)
    (bench / "limits" / "smollm-360m-b.steady.json").write_text('{"max_logit_gap": 1.0}')
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "smollm-360m-b", "source": cfg["source"],
                         "file": "chipbench/configs/smollm-360m-b.json", "reduced": cfg["reduced"],
                         "why": "a second deployment"})
    b["workloads"].append({"name": "smollm-360m-b.steady", "config": "smollm-360m-b",
                           "traffic": "steady", "chips": 1, "why": "even arrivals"})
    b["per_layer"].append({"name": "done_count", "unit": "requests", "better": "higher",
                           "source": "host_clock", "layer": "router", "moves": "out_tok_s",
                           "workloads": ["smollm-360m-b.steady"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))

    parts = spec.Parts(bench, root / "BENCHMARK.json")
    w = parts.workload("smollm-360m-b.steady")
    cfg_b = parts.config(w["config"])
    assert cfg_b["name"] == "smollm-360m-b"
    parts.reference(cfg_b["reference"]).check(cfg_b["model"])
    assert deploy.model_config(cfg_b, use_pallas=True).qkv_bias
    s = traffic.schedule(parts, parts.traffic(w["traffic"]), 3, 10.0, 49152)
    assert [round(r.due_s, 6) for r in s.window] == [round(0.5 * i, 6) for i in range(20)]
    assert parts.limits(w["name"])["max_logit_gap"] == 1.0
    names = [m["name"] for m in parts.per_layer_for(w["name"])]
    assert "done_count" in names and "queue_wait_p95_ms" not in names
    assert parts.metric("done_count").read({"requests": [{"done": True}, {"done": False}]}) == 1
    # nothing that was there changed
    after = _digest(bench)
    assert {k: after[k] for k in before} == before
