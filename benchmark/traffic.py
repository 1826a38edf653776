"""The one general traffic generator: a mix file in, requests out.

A mix (``benchmark/traffic/<name>.json``) gives each prompt-length class
and each output-length class a share. For a planned number of requests the
generator builds the EXACT multiset of (prompt length, output length) pairs
(largest-remainder rounding of the classes' joint shares) and, for
arrivals, of gaps (the
exponential distribution's quantiles at ``(i + 0.5) / n``, rescaled so they
sum to the segment's length). The seed only permutes them, inside blocks
of a few requests, and draws the token ids, so every run under every seed
offers the same work and the same burstiness in another order: seed-to-seed variance of an
i.i.d. draw (``telemetry/loadgen.build_requests``, ``PoissonArrivals``) is
what a tail cannot afford.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Sequence

import numpy as np


@dataclasses.dataclass
class Request:
    uid: int
    prompt: List[int]
    gen_len: int
    due_s: float = 0.0        # offset from the start of the schedule
    segment: str = "window"   # "ramp" | "window" | "tail" (arrivals only)


def exact_counts(shares: Sequence[float], n: int) -> List[int]:
    """Largest-remainder apportionment of ``n`` items over ``shares``."""
    total = float(sum(shares))
    quota = [s / total * n for s in shares]
    counts = [int(math.floor(q)) for q in quota]
    by_remainder = sorted(range(len(shares)),
                          key=lambda i: (-(quota[i] - counts[i]), i))
    for i in by_remainder[:n - sum(counts)]:
        counts[i] += 1
    return counts


def exponential_gaps(n: int, span_s: float) -> np.ndarray:
    """The exponential's quantiles at (i + 0.5) / n, rescaled to sum to
    ``span_s``: the burstiness of Poisson arrivals with no sampling noise."""
    q = -np.log1p(-(np.arange(n) + 0.5) / n)
    return q * (span_s / q.sum())


def _token_ids(rng: np.random.Generator, n: int, vocab: int) -> List[int]:
    return rng.integers(0, vocab, size=int(n), dtype=np.int64).tolist()


def _spread(counts: Sequence[int]) -> List[int]:
    """Class indices, each class dealt evenly over the whole sequence:
    class j's i-th member sits at (i + 0.5) / counts[j] of the way."""
    at = [((i + 0.5) / c, j) for j, c in enumerate(counts)
          for i in range(c)]
    return [j for _, j in sorted(at)]


def _shuffle_in_blocks(items: Sequence[Any], block: int,
                       rng: np.random.Generator) -> List[Any]:
    """Permute ``items`` inside consecutive blocks of ``block``."""
    out: List[Any] = []
    for i in range(0, len(items), block):
        part = list(items[i:i + block])
        out.extend(part[k] for k in rng.permutation(len(part)))
    return out


def _lengths(mix: Dict[str, Any], n: int, rng: np.random.Generator):
    """``n`` (prompt, output) length pairs: the exact multiset of the
    PAIRS (prompt and output classes independent, so a pair's share is the
    product of its classes' shares), every class dealt evenly over the
    sequence, and the seed permuting only inside blocks of the mix's
    ``shuffle_block`` requests. Pairs, not two separately permuted
    columns: which prompt meets which output decides a sequence's context,
    and so the work. Blocks, not one permutation of the whole: where the
    long requests cluster decides how many sequences are live, which moved
    a 40 s window's tails by a fifth from seed to seed (PERF.md, PR 24)."""
    pairs = [(p, g) for p in mix["prompt_lens"] for g in mix["gen_lens"]]
    shares = [ps * gs for ps in mix["prompt_shares"]
              for gs in mix["gen_shares"]]
    idx = _shuffle_in_blocks(_spread(exact_counts(shares, n)),
                             int(mix["shuffle_block"]), rng)
    return ([pairs[i][0] for i in idx], [pairs[i][1] for i in idx])


def _gaps(mix: Dict[str, Any], n: int, span_s: float,
          rng: np.random.Generator) -> np.ndarray:
    """The exponential's quantiles, dealt round-robin into blocks of
    ``shuffle_block`` (each block holds short and long gaps alike) and
    permuted by the seed inside each block."""
    q = exponential_gaps(n, span_s)
    block = int(mix["shuffle_block"])
    n_blocks = -(-n // block)
    dealt = [q[i] for b in range(n_blocks) for i in range(b, n, n_blocks)]
    return np.asarray(_shuffle_in_blocks(dealt, block, rng))


def arrivals_schedule(mix: Dict[str, Any], rate_rps: float,
                      segments: Sequence[tuple], seed: int,
                      vocab: int) -> List[Request]:
    """Open-loop schedule. ``segments`` is ``[(name, seconds), ...]`` in
    time order; each segment holds ``round(rate * seconds)`` requests with
    its own exact multisets, permuted by the seed, and its last arrival
    falls on the segment's end."""
    rng = np.random.default_rng(seed)
    out: List[Request] = []
    t0 = 0.0
    for name, seconds in segments:
        n = int(round(rate_rps * seconds))
        if n <= 0:
            t0 += seconds
            continue
        prompts, gens = _lengths(mix, n, rng)
        due = t0 + np.cumsum(_gaps(mix, n, seconds, rng))
        for p, g, d in zip(prompts, gens, due):
            out.append(Request(len(out), _token_ids(rng, p, vocab), int(g),
                               float(d), name))
        t0 += seconds
    return out


def closed_loop_requests(mix: Dict[str, Any], n: int, seed: int,
                         vocab: int) -> List[Request]:
    """``n`` requests for a closed loop (no arrival times): exact
    multisets, seeded order."""
    rng = np.random.default_rng(seed)
    prompts, gens = _lengths(mix, n, rng)
    return [Request(i, _token_ids(rng, p, vocab), int(g))
            for i, (p, g) in enumerate(zip(prompts, gens))]


def first_wave(mix: Dict[str, Any], clients: int, quantum: int, seed: int,
               vocab: int) -> List[Request]:
    """The closed loop's first wave, staggered as if it were caught
    mid-flight: the ``k``-th client of a (prompt, output L) class has
    already generated ``quantum * (k mod L/quantum)`` tokens, so those move from its output
    budget into its prompt. Contexts and remaining budgets are then those
    of a steady state and the window sees steady refill, not one
    synchronised wave. uids are negative: the wave is set-up."""
    # the lengths are the same under every seed (the wave is set-up, and
    # slots are symmetric); the seed draws only the token ids
    prompts, gens = _lengths(mix, clients, np.random.default_rng(0))
    rng = np.random.default_rng(seed ^ 0x5EED)
    seen: Dict[tuple, int] = {}
    out = []
    for j, (p, g) in enumerate(sorted(zip(prompts, gens))):
        k = seen.get((p, g), 0)
        seen[(p, g)] = k + 1
        done = quantum * (k % max(1, g // quantum))
        out.append(Request(-1 - j, _token_ids(rng, p + done, vocab),
                           g - done))
    return out


def mix_stats(mix: Dict[str, Any]) -> Dict[str, float]:
    """Means a cell file quotes (pool bytes in use, offered tokens/s)."""
    def mean(vals, shares):
        return float(np.dot(vals, shares) / np.sum(shares))
    p = mean(mix["prompt_lens"], mix["prompt_shares"])
    g = mean(mix["gen_lens"], mix["gen_shares"])
    g2 = mean(np.square(mix["gen_lens"]), mix["gen_shares"])
    # a sequence of output length L is live for L steps, at a mean of L/2
    # generated tokens: weight each class by how long it stays
    return {"mean_prompt": p, "mean_gen": g,
            "mean_live_context": p + g2 / (2 * g)}
