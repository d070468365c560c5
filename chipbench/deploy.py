"""The served deployment of one configuration, built from the program's
library pieces as ``repro.launch.serve.serve`` builds it: one
``PagedInferenceEngine`` per tier, one ``EngineLoop`` each, a ``Backend``
per tier and a ``StraightLineRouter`` running Algorithm 1.

Engine and policy knobs are the program's launcher defaults, parsed from
the configuration's ``launcher_args`` by ``build_parser()``, so a change of
a launcher default moves the benchmark as it moves users. The tier layout,
row length and page size come from the configuration file. Unlike the
launcher, the elastic tier is built with the others (its cold start is not
part of a cell), and each backend's ``wait_fn`` returns the finished
``Sequence`` so that its token stamps can be read.

Spans: each engine's ``step`` is wrapped (on the instance) to record its
host interval and the tokens it processed, inside a
``jax.profiler.TraceAnnotation`` named ``engine.step.<tier>``.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List

import jax

TIERS = ("interactive", "batch", "elastic")      # in the order of repro.core.Tier


@dataclass
class StepLog:
    """What the step wrapper records, per step: (tier, t0, t1, prefill spans
    [(start, stop, emits_last)], decoded positions)."""
    records: List[tuple] = field(default_factory=list)
    on: bool = False
    lock: threading.Lock = field(default_factory=threading.Lock)


def _cache_len(seq, slot_len: int) -> int:
    """Tokens of ``seq`` whose keys and values are in the cache: the chunk
    cursor while it prefills, prompt plus output less the pending token
    once it decodes."""
    return len(seq.prompt) + len(seq.out) - 1 if seq.out else int(slot_len)


def _wrap_step(engine, tier: str, log: StepLog) -> None:
    """Record each step's interval and the positions it processed, read from
    the engine's public slot state before and after the step."""
    inner = engine.step
    name = f"engine.step.{tier}"

    def step():
        if not log.on:
            with jax.profiler.TraceAnnotation(name):
                return inner()
        before = {id(s): (s, _cache_len(s, engine.slot_len[i]))
                  for i, s in enumerate(engine.slot_seq) if s is not None}
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation(name):
            finished = inner()
        t1 = time.monotonic()
        after = {id(s): (s, _cache_len(s, engine.slot_len[i]))
                 for i, s in enumerate(engine.slot_seq) if s is not None}
        for s in finished:
            after[id(s)] = (s, _cache_len(s, 0))
        prefill, decoded = [], []
        for key, (s, l1) in after.items():
            l0 = before.get(key, (s, 0))[1]
            P = len(s.prompt)
            if l0 < P:
                stop = min(l1, P)
                prefill.append((l0, stop, stop == P))
            decoded.extend(range(max(l0, P), l1))
        with log.lock:
            log.records.append((tier, t0, t1, prefill, decoded))
        return finished

    engine.step = step


@dataclass
class Deployment:
    engines: Dict[str, object]
    loops: Dict[str, object]
    router: object
    args: object
    model_cfg: object
    log: StepLog
    results: Dict[int, object] = field(default_factory=dict)


# ``model`` keys of a configuration file that are fields of the program's
# ModelConfig: the program runs every one of them as the file states it.
PROGRAM_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff",
                "vocab_size", "rope_theta", "tie_embeddings", "qkv_bias", "norm", "gated_mlp")


def model_config(cfg: dict, use_pallas: bool):
    """The program's model configuration for ``cfg``: its registry entry (the
    smoke-size one where ``cfg`` says ``smoke``, as the launcher's ``--smoke``
    takes it) with each of the file's ``PROGRAM_KEYS`` set on it."""
    from repro.configs.registry import get_config

    m = cfg["model"]
    mc = get_config(cfg["arch"], smoke=cfg.get("smoke", False))
    if cfg.get("smoke", False):
        mc = mc.replace(attn_chunk=64)
    return mc.replace(use_pallas=use_pallas, **{k: m[k] for k in PROGRAM_KEYS})


def launcher_args(cfg: dict, output_tokens: int):
    from repro.launch.serve import build_parser

    return build_parser().parse_args(
        list(cfg["launcher_args"]) + ["--max-new-tokens", str(output_tokens)])


def build(cfg: dict, params, output_tokens: int, prompts: Dict[int, List[int]]) -> Deployment:
    """The three tiers over ``params``, their loops started and the router's
    workers running. ``prompts`` maps request ids to prompt tokens."""
    from repro.core import StraightLinePolicy, Thresholds, Tier
    from repro.core.router import Backend, StraightLineRouter
    from repro.core.telemetry import MetricsRegistry
    from repro.serving.engine import PagedEngineConfig, PagedInferenceEngine
    from repro.serving.scheduler import EngineLoop

    args = launcher_args(cfg, output_tokens)
    mcfg = model_config(cfg, args.use_pallas)
    row, page = cfg["row_tokens"], cfg["page_tokens"]
    registry = MetricsRegistry()
    log = StepLog()
    engines, loops, backends, results = {}, {}, {}, {}
    tier_of = {"interactive": Tier.FLASK, "batch": Tier.DOCKER, "elastic": Tier.SERVERLESS}
    for name in TIERS:
        t = cfg["tiers"][name]
        eng = PagedInferenceEngine(mcfg, PagedEngineConfig(
            page_size=page, num_pages=1 + t["slots"] * row // page, max_slots=t["slots"],
            max_seq_len=row, max_new_tokens=args.max_new_tokens,
            chunk_tokens=args.chunk_tokens, step_token_budget=args.step_budget,
        ), params=params)
        _wrap_step(eng, name, log)
        loop = EngineLoop(eng, name=name, registry=registry).start()
        engines[name], loops[name] = eng, loop

        def submit(req, loop=loop):
            return req.rid, loop.submit(prompts[req.rid])

        def wait(ticket, timeout, loop=loop):
            seq = loop.wait(ticket[1], timeout)
            results[ticket[0]] = seq
            return seq

        backends[tier_of[name]] = Backend(
            tier_of[name], run=None, capacity=t["capacity"], queue_cap=t["queue_cap"],
            stats_fn=loop.capacity_now, submit_fn=submit, wait_fn=wait)
    router = StraightLineRouter(
        backends, policy=StraightLinePolicy(Thresholds(F=args.F, D=args.D)),
        window_s=cfg["router_window_s"], hedge_after_s=args.hedge_after, registry=registry)
    router.start(cfg["router_workers"])
    return Deployment(engines, loops, router, args, mcfg, log, results)
