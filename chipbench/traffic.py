"""The one traffic generator: reads a traffic file and makes the open-loop
schedule of a run from ``--seed``, before the run starts.

A traffic file holds:

- ``arrivals``: ``{"kind": <module under arrivals/>, ...its parameters}``;
- ``prompt_tokens``: ``{"dist": <module under lengths/>, ...its parameters}``;
- ``payload_bytes_per_token``: the request's payload size per prompt token
  (Algorithm 1 reads payload bytes against its threshold D);
- ``output_tokens``: tokens generated per request;
- ``lead_in_s``: seconds of the same traffic sent before the window opens,
  so the router's frequency window and the queues are in their steady
  state when the window opens (counted as set-up);
- ``drain_s``: how long after the window closes the run waits for requests
  due in the window; a request still unfinished then counts as failed. 0
  means the cell judges only what happened inside the window (a cell run
  above capacity, whose backlog never drains);
- ``check_requests``: finished requests whose served tokens are compared
  with the reference.

The seed draws the token ids. The arrival times and the prompt length of
each request are one fixed draw of the mix's distributions (``PATTERN``),
the same for every seed: in a queue the order of the work is part of the
work, and on the chip runs whose seeds only reordered the same gaps and
lengths spread five times wider than two runs of one seed (PERF.md).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List

import numpy as np


@dataclass
class Request:
    rid: int
    due_s: float                # due time, seconds from the window's opening
    prompt: List[int]
    payload_bytes: float

    @property
    def in_window(self) -> bool:
        return self.due_s >= 0.0


@dataclass
class Schedule:
    requests: List[Request]     # sorted by due time; lead-in first (due < 0)
    output_tokens: int
    window_s: float
    lead_in_s: float

    @property
    def window(self) -> List[Request]:
        return [r for r in self.requests if r.in_window]


PATTERN = 0          # the stream that draws arrival times and prompt lengths


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    """An independent numpy stream per use of the seed (any non-negative
    integer, larger than 32 bits too)."""
    return np.random.default_rng([int(seed), stream])


def schedule(parts, traffic: dict, seed: int, seconds: float, vocab_size: int) -> Schedule:
    arr = dict(traffic["arrivals"])
    arrivals = parts.arrivals(arr.pop("kind"))
    lens_p = dict(traffic["prompt_tokens"])
    lengths = parts.lengths(lens_p.pop("dist"))
    lead = float(traffic.get("lead_in_s", 0.0))
    spans = [(-lead, lead), (0.0, float(seconds))]          # lead-in, window
    due: List[float] = []
    for k, (start, span) in enumerate(spans):
        n = arrivals.count(arr, span)
        due.extend(start + arrivals.times(arr, n, span, seed_rng(PATTERN, 1 + k)))
    n = len(due)
    plen = seed_rng(PATTERN, 3).permutation(lengths.quantiles(lens_p, n))
    tok_rng = seed_rng(seed, 4)
    bpt = float(traffic["payload_bytes_per_token"])
    reqs = [
        Request(rid=i, due_s=float(due[i]),
                prompt=tok_rng.integers(1, vocab_size, int(plen[i])).tolist(),
                payload_bytes=bpt * int(plen[i]))
        for i in range(n)
    ]
    return Schedule(reqs, int(traffic["output_tokens"]), float(seconds), lead)
