"""Traffic from a data file: one general generator per ``kind``.

A traffic mix is ``traffic/<mix>.json``: a ``kind`` and its parameters.  A
new mix of a kind that exists is a new file and nothing else.

Every seed gets the same work.  For request traffic the sequence of
(prompt, answer) lengths and the set of inter-arrival gaps are drawn once
from the mix's own ``population_seed``; the run's ``--seed`` draws the token
values and reorders the gaps within runs of ``shuffle_block`` consecutive
arrivals.  Two seeds never differ in load or in the order of the work, only
in the moments at which it arrives.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Iterator, List

import numpy as np


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), int(stream)])


# -- training batches --------------------------------------------------------

def lm_batches(params: Dict[str, Any], seed: int) -> Iterator[Dict[str, np.ndarray]]:
    """Next-token batches: every row a different uniform draw of tokens."""
    rng = _rng(seed, 1)
    shape = (int(params["batch_size"]), int(params["seq_len"]))
    while True:
        yield {"tokens": rng.integers(
            0, int(params["vocab_size"]), shape, dtype=np.int32)}


BATCH_KINDS = {"lm_batches": lm_batches}


def batches(traffic: Dict[str, Any], seed: int) -> Iterator[Dict[str, np.ndarray]]:
    kind = traffic["kind"]
    if kind not in BATCH_KINDS:
        raise ValueError(f"traffic kind {kind!r} makes no training batches; "
                         f"known: {sorted(BATCH_KINDS)}")
    return BATCH_KINDS[kind](traffic, seed)


def tokens_per_batch(traffic: Dict[str, Any]) -> int:
    return int(traffic["batch_size"]) * int(traffic["seq_len"])


# -- open-loop requests --------------------------------------------------------

@dataclasses.dataclass
class Request:
    due_s: float            # offset from the start of the window; < 0 in the lead-in
    prompt: np.ndarray      # int32 token ids
    max_new_tokens: int


def _lognormal_lengths(rng, n, spec) -> np.ndarray:
    """Log-normal lengths with the given median, clipped, and rounded up to
    ``buckets`` where the mix gives them."""
    draws = np.exp(rng.normal(np.log(float(spec["median"])),
                              float(spec["sigma"]), n))
    lengths = np.clip(np.rint(draws), int(spec["min"]), int(spec["max"]))
    buckets = spec.get("round_up_to")
    if buckets:
        buckets = np.asarray(sorted(buckets))
        lengths = buckets[np.searchsorted(buckets, lengths, side="left")]
    return lengths.astype(np.int64)


def _arrival_gaps(rng, n, spec) -> np.ndarray:
    process = spec["process"]
    rate = float(spec["rate_per_s"])
    if rate <= 0:
        raise ValueError(f"arrival rate must be > 0 requests/s, got {rate}")
    if process == "poisson":
        return rng.exponential(1.0 / rate, n)
    raise ValueError(f"unknown arrival process {process!r}")


def _shuffle_in_blocks(rng, n, block) -> np.ndarray:
    """A permutation of 0..n-1 that moves nothing out of its run of
    ``block`` consecutive places: the last arrival of every such run falls
    at the same instant for every seed."""
    return np.concatenate([start + rng.permutation(min(block, n - start))
                           for start in range(0, n, block)])


def open_loop_requests(traffic: Dict[str, Any], seed: int,
                       seconds: float) -> List[Request]:
    """The requests of a run: ``rate x (lead_in_s + seconds)`` of them.  The
    sequence of (prompt, answer) lengths and the set of arrival gaps are the
    mix's own, the same for every seed: the work is fixed, as a training
    cell's shapes are.  The seed draws the token values and reorders the
    gaps within runs of ``shuffle_block`` consecutive arrivals.  Those due
    before 0 are the mix's lead-in: they bring the server to the state the
    mix describes before the window opens, and are not measured."""
    arrivals = traffic["arrivals"]
    lead_in = float(traffic["lead_in_s"])
    span = lead_in + seconds
    n = max(1, int(round(float(arrivals["rate_per_s"]) * span)))
    pop = _rng(int(traffic["population_seed"]), 3)
    gaps = _arrival_gaps(pop, n, arrivals)
    gaps *= span * n / (n + 1) / gaps.sum()     # n arrivals inside the span
    prompt_len = _lognormal_lengths(pop, n, traffic["prompt_tokens"])
    output_len = _lognormal_lengths(pop, n, traffic["output_tokens"])
    gaps = gaps[_shuffle_in_blocks(
        _rng(seed, 4), n, int(traffic["shuffle_block"]))]
    due = np.cumsum(gaps) - lead_in
    content = _rng(seed, 5)
    vocab = int(traffic["vocab_size"])
    return [Request(float(due[i]),
                    content.integers(0, vocab, int(prompt_len[i]), dtype=np.int32),
                    int(output_len[i]))
            for i in range(n)]


def prompt_lengths(traffic: Dict[str, Any]) -> List[int]:
    """Every prompt length the mix can produce: the shapes to warm up."""
    spec = traffic["prompt_tokens"]
    if not spec.get("round_up_to"):
        raise ValueError(
            "a request mix must round prompt lengths to a stated set "
            "(prompt_tokens.round_up_to): the server compiles one prefill "
            "program per length")
    lo, hi = int(spec["min"]), int(spec["max"])
    buckets = sorted(int(b) for b in spec["round_up_to"])
    if hi > buckets[-1]:
        raise ValueError(f"prompt max {hi} exceeds the largest bucket")
    return [b for i, b in enumerate(buckets)
            if b >= lo and (i == 0 or buckets[i - 1] < hi)]
