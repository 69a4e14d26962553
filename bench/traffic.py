"""Seeded traffic: one general generator per loop kind, read from data.

Every seed gives the same multiset of sizes and inter-arrival gaps (quota
sampling: each value of a weighted set appears its apportioned number of
times, and the gaps are the exponential distribution's quantiles), drawn
in a seed-dependent order with seed-dependent token contents.  So runs
with different seeds do the same amount of work, and their spread is the
system's and not the generator's.  Where a few long requests decide a
tail, their order alone moves it; an open-loop mix that sets
``schedule_seed`` draws the order and the arrivals from that fixed number
instead, and only the token contents from the run's seed.

The open-loop schedule follows ``ServingRuntime.drive`` (submit when due,
step in between, sleep only when idle) but keeps each request's due time,
so a stall during admission shows as lateness of the requests behind it.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterator, List, Sequence, Tuple

import numpy as np


def apportion(weights: Sequence[float], n: int) -> List[int]:
    """Largest-remainder split of ``n`` draws over ``weights``."""
    w = np.asarray(weights, np.float64)
    exact = n * w / w.sum()
    counts = np.floor(exact).astype(int)
    for i in np.argsort(-(exact - counts), kind="stable")[:n - counts.sum()]:
        counts[i] += 1
    return counts.tolist()


def quota(values: Sequence, weights: Sequence[float], n: int,
          rng: np.random.Generator) -> List:
    """``n`` values, each appearing its apportioned number of times, in a
    random order."""
    out = [v for v, c in zip(values, apportion(weights, n)) for _ in range(c)]
    return [out[i] for i in rng.permutation(n)]


def zipf_weights(n: int, s: float) -> List[float]:
    return [1.0 / (k ** s) for k in range(1, n + 1)]


def exp_gaps(n: int, duration: float, rng: np.random.Generator) -> np.ndarray:
    """``n`` Poisson inter-arrival gaps that sum to ``duration``: the
    exponential quantiles at (i + 1/2)/n, rescaled, in a random order."""
    q = -np.log1p(-(np.arange(n) + 0.5) / n)
    return rng.permutation(q * (duration / q.sum()))


@dataclasses.dataclass
class LMRequest:
    index: int
    due: float            # seconds after the window opens (< 0: ramp)
    prompt: np.ndarray    # int32 token ids
    n_new: int
    in_window: bool
    tag: str = ""         # what the prompt shares (e.g. "sys3")


# ---------------------------------------------------------------------------
# shared system prompts under Poisson arrivals (drivers/serve_open_loop.py)
# ---------------------------------------------------------------------------

def system_prompts(p: Dict, vocab: int, seed_rng: np.random.Generator
                   ) -> List[np.ndarray]:
    return [seed_rng.integers(0, vocab, p["system_prompt_len"],
                              dtype=np.int32)
            for _ in range(p["n_system_prompts"])]


def open_loop_phase(p: Dict, start: float, duration: float, in_window: bool,
                    first_index: int, prompts: List[np.ndarray], vocab: int,
                    order: np.random.Generator,
                    tokens: np.random.Generator) -> List[LMRequest]:
    """The requests due in ``[start, start + duration)``: ``rate × duration``
    of them, each a system prompt (Zipf) + a random user suffix.  ``order``
    draws which sizes come when, ``tokens`` the user suffixes."""
    n = max(1, int(round(p["rate"] * duration)))
    sys_ids = quota(range(len(prompts)),
                    zipf_weights(len(prompts), p["zipf_s"]), n, order)
    suffix = quota(p["suffix_lens"], p["suffix_weights"], n, order)
    n_new = quota(p["n_new"], p["n_new_weights"], n, order)
    dues = start + np.concatenate([[0.0], np.cumsum(
        exp_gaps(n, duration, order))[:-1]])
    out = []
    for i in range(n):
        user = tokens.integers(0, vocab, suffix[i], dtype=np.int32)
        out.append(LMRequest(first_index + i, float(dues[i]),
                             np.concatenate([prompts[sys_ids[i]], user]),
                             int(n_new[i]), in_window, f"sys{sys_ids[i]}"))
    return out


def open_loop_schedule(p: Dict, seconds: float, vocab: int, seed: int,
                       rng_for
                       ) -> Tuple[List[np.ndarray], Iterator[LMRequest]]:
    """System prompts, and the stream of requests: a ramp of
    ``p["ramp_s"]`` before the window, the window, then further phases of
    the same traffic for as long as the drain after the window lasts."""
    prompts = system_prompts(p, vocab, rng_for(seed, 1))
    order_seed = p.get("schedule_seed", seed)

    def stream():
        idx, phase = 0, 0
        spans = [(-p["ramp_s"], p["ramp_s"], False), (0.0, seconds, True)]
        t = seconds
        while True:
            if phase < len(spans):
                start, dur, inw = spans[phase]
            else:
                start, dur, inw = t, seconds, False
                t += seconds
            if dur > 0:
                block = open_loop_phase(p, start, dur, inw, idx, prompts,
                                        vocab, rng_for(order_seed, 2, phase),
                                        rng_for(seed, 8, phase))
                idx += len(block)
                yield from block
            phase += 1
    return prompts, stream()


def open_loop_shapes(p: Dict) -> Dict[str, List[int]]:
    """Every length the open-loop traffic can make the program compile
    for: partial-hit suffixes, and whole prompts (a miss, after the system
    prompt was evicted from the prefix cache)."""
    return {"suffix": sorted(p["suffix_lens"]),
            "prompt": sorted(p["system_prompt_len"] + s
                             for s in p["suffix_lens"])}


# ---------------------------------------------------------------------------
# unshared prompts, always more queued than rows (drivers/serve_backlog.py)
# ---------------------------------------------------------------------------

def backlog_stream(p: Dict, vocab: int, seed: int, rng_for
                   ) -> Iterator[LMRequest]:
    """Blocks of ``p["block"]`` unshared requests, prompt lengths and
    n_new apportioned by their weights in each block, each block in its
    own order."""
    idx, k = 0, 0
    while True:
        rng = rng_for(seed, 7, k)
        n = p["block"]
        lens = quota(p["prompt_lens"], p["prompt_weights"], n, rng)
        n_new = quota(p["n_new"], p["n_new_weights"], n, rng)
        for i in range(n):
            yield LMRequest(idx, 0.0,
                            rng.integers(0, vocab, lens[i], dtype=np.int32),
                            int(n_new[i]), False, "unshared")
            idx += 1
        k += 1


def backlog_shapes(p: Dict) -> Dict[str, List[int]]:
    """Every miss-prefill length the backlog can compile for."""
    return {"suffix": [], "prompt": sorted(p["prompt_lens"])}


# ---------------------------------------------------------------------------
# image batches for one closed-loop client (drivers/vit_closed_loop.py)
# ---------------------------------------------------------------------------

def batch_sizes(p: Dict, seed: int, rng_for) -> Iterator[int]:
    """Blocks of ``p["block"]`` batches, the sizes apportioned over
    ``p["batch_sizes"]`` by ``p["batch_weights"]`` in each block, each
    block in its own order."""
    k = 0
    while True:
        yield from quota(p["batch_sizes"], p["batch_weights"], p["block"],
                         rng_for(seed, 3, k))
        k += 1
