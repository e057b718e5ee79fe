"""The one traffic generator: a traffic file and a seed -> a schedule.

A traffic file states distributions; this module turns them into a FIXED
cycle of requests. For `n` requests the prompt and output lengths are the
`n` evenly spaced quantiles of the stated distributions
(u_i = (i + 0.5) / n), shuffled by the traffic file's `base_seed`, and the
arrival times are `n` sorted uniform draws over the window from the same
generator: a Poisson process conditioned on its count, NOT one per slot,
so the queueing a cell exists to show stays. That cycle is the cell's.
`--seed` chooses (a) where in the cycle the window opens (a rotation: the
same sizes and the same gaps between arrivals, in another order at other
instants), (b) the instant inside that first gap and (c) the prompt token
ids. The ramp before the window is the cycle's preceding requests, so that
the system is in the state the cycle left it in. Every seed thus offers
the same number of requests, and in the window the same prompt tokens,
output tokens and gaps. (A fresh permutation and fresh arrival times for
every seed was measured first: two seeds then differed by 9-10 % in the
median time to first token where two runs of one seed differed by 2.6 %:
the seed was changing the work. PERF.md, Findings, PR 24.)

Imports no JAX and nothing from the program under test.
"""

from __future__ import annotations

import json
import math
import os
from typing import Any, Dict, List

import numpy as np

def load_traffic(bench_dir: str, name: str) -> Dict[str, Any]:
    path = os.path.join(bench_dir, "traffic", name + ".json")
    with open(path) as fh:
        spec = json.load(fh)
    if spec.get("kind") not in ("open", "closed"):
        raise ValueError(f"{path}: kind must be 'open' or 'closed'")
    return spec


def quantile(dist: Dict[str, Any], u: float) -> int:
    """Inverse CDF of a length distribution at u in (0, 1), as an int."""
    kind = dist["dist"]
    lo, hi = int(dist["lo"]), int(dist["hi"])
    if not 0 < lo <= hi:
        raise ValueError(f"bad bounds in {dist}")
    if kind == "constant":
        return lo
    if kind == "uniform":
        return min(hi, lo + int(u * (hi - lo + 1)))
    if kind == "bounded_pareto":
        # Pareto(alpha) truncated to [lo, hi] and renormalised (the
        # program's serving/qos.py::_bounded_pareto caps at hi instead,
        # which piles mass on hi; truncation matches the stated medians).
        a = float(dist["alpha"])
        x = lo / (1.0 - u * (1.0 - (lo / hi) ** a)) ** (1.0 / a)
        return int(min(hi, max(lo, round(x))))
    raise ValueError(f"unknown dist {kind!r}")


def stratified(dist: Dict[str, Any], n: int) -> List[int]:
    """The n evenly spaced quantiles of `dist`: the cell's multiset."""
    return [quantile(dist, (i + 0.5) / n) for i in range(n)]


def _cycle(spec: Dict[str, Any], n: int, vocab: int):
    """The cell's fixed draw: the stratified multiset in the order the
    traffic file's `base_seed` shuffles it into (prompts and outputs
    shuffled independently), the same for every --seed."""
    base = np.random.default_rng([int(spec.get("base_seed", 0)), 0xBA5E])
    prompts = np.asarray(stratified(spec["prompt_tokens"], n))
    outputs = np.asarray(stratified(spec["output_tokens"], n))
    return base, prompts[base.permutation(n)], outputs[base.permutation(n)]


def _request(phase: str, prompt: int, output: int, rng, vocab: int):
    ids = rng.integers(0, vocab, size=int(prompt))
    return {"phase": phase, "prompt_ids": [int(t) for t in ids],
            "max_tokens": int(output)}


def build_schedule(spec: Dict[str, Any], seed: int, seconds: float,
                   vocab: int) -> Dict[str, Any]:
    """The whole offered load of one run. Times are seconds relative to
    the opening of the measured window; ramp requests are due before 0.

    open:   {"kind", "requests": [{due_s, phase, prompt_ids, max_tokens}]}
    closed: {"kind", "clients", "requests": [...]}; clients take requests
            in turn from the list, cyclically, from -ramp_s until the
            window closes.
    """
    rng = np.random.default_rng(int(seed))
    ramp_s = float(spec.get("ramp_s", 0.0))
    if spec["kind"] == "closed":
        n = int(spec["requests"])
        _, prompts, outputs = _cycle(spec, n, vocab)
        k = int(rng.integers(n))
        reqs = [_request("window", prompts[(k + j) % n],
                         outputs[(k + j) % n], rng, vocab)
                for j in range(n)]
        return {"kind": "closed", "clients": int(spec["clients"]),
                "ramp_s": ramp_s, "seconds": float(seconds),
                "requests": reqs}
    rate = float(spec["rate_per_s"])
    n = int(round(rate * seconds))
    n_ramp = min(int(round(rate * ramp_s)), n)
    base, prompts, outputs = _cycle(spec, n, vocab)
    t = np.sort(base.uniform(0.0, seconds, n))
    # the cycle's gaps: gap[i] separates request i from request i + 1,
    # the last one wraps round to the first
    gap = np.append(np.diff(t), seconds - t[-1] + t[0])
    k = int(rng.integers(n))
    due = float(rng.uniform(0.0, 1.0)) * float(gap[k - 1])
    window = []
    for j in range(n):
        i = (k + j) % n
        r = _request("window", prompts[i], outputs[i], rng, vocab)
        r["due_s"] = due
        window.append(r)
        due += float(gap[i])
    ramp, due = [], window[0]["due_s"]
    for m in range(1, n_ramp + 1):
        i = (k - m) % n
        due -= float(gap[i])
        r = _request("ramp", prompts[i], outputs[i], rng, vocab)
        r["due_s"] = due
        ramp.append(r)
    ramp.reverse()
    return {"kind": "open", "seconds": float(seconds),
            "ramp_s": -ramp[0]["due_s"] if ramp else 0.0,
            "requests": ramp + window}


def words(ids) -> str:
    """Token ids as the text the benchmark tokenizer maps back to them:
    one whitespace-separated word per token."""
    return " ".join(f"w{int(i)}" for i in ids)


def corpus_files(spec: Dict[str, Any], seed: int, vocab: int):
    """Seeded documents for a retrieval cell: `files` texts that the
    chain server's splitter cuts into `chunks` chunks of `chunk_tokens`
    words in all. Yields (filename, text)."""
    c = spec["corpus"]
    rng = np.random.default_rng([int(seed), 0xC0])
    per_file = int(math.ceil(c["chunks"] / c["files"]))
    for f in range(int(c["files"])):
        ids = rng.integers(0, vocab, size=per_file * int(c["chunk_tokens"]))
        yield f"corpus-{f:03d}.txt", words(ids)
