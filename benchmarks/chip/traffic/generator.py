"""The one generator every traffic mix goes through.  A mix is a JSON file
beside this one; this module turns it and a seed into requests or rows.

Serving mixes give lengths as values with weights.  Lengths are dealt
from decks: each deck of ``DECK`` cards holds every value in proportion
to its weight and is shuffled by the seed, so every seed serves the same
set of lengths in another order and seeds do not change the work.
Arrivals are either a backlog (requests are taken as slots can use
them) or an open loop at ``rate_per_s`` whose gaps are exponential,
dealt the same way: each block of ``DECK`` gaps holds the exponential
distribution's quantiles, shuffled.

Training rows are uniform token ids, a pure function of (seed, row
index): every row differs, and a recovered job that replays a step gets
the same rows again.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Iterator, List, Optional, Sequence, Tuple

import numpy as np

#: cards in a deck of lengths, and gaps in a block of arrivals
DECK = 20

def _deck(values: Sequence[int], weights: Sequence[float], size: int
          ) -> List[int]:
    counts = [int(round(w * size)) for w in weights]
    counts[int(np.argmax(weights))] += size - sum(counts)
    return [v for v, c in zip(values, counts) for _ in range(c)]


def dealt(values, weights, size: int, rng: np.random.Generator
          ) -> Iterator[int]:
    cards = _deck(values, weights, size)
    while True:
        for i in rng.permutation(len(cards)):
            yield int(cards[i])


def exponential_gaps(rate: float, size: int, rng: np.random.Generator
                     ) -> Iterator[float]:
    q = (np.arange(size) + 0.5) / size
    gaps = -np.log1p(-q) / rate
    while True:
        for i in rng.permutation(size):
            yield float(gaps[i])


@dataclasses.dataclass(frozen=True)
class Arrival:
    rid: str
    prompt: Tuple[int, ...]
    max_new_tokens: int
    due_s: Optional[float]       # seconds after the window opens; None
    #                              for a backlog (due when taken)


def serve_stream(mix: dict, seed: int, vocab: int, *, prefix: str = "r"
                 ) -> Iterator[Arrival]:
    """Requests of a serving mix, in arrival order, without end."""
    rng = np.random.default_rng([int(seed), 7])
    size = DECK
    plen = dealt(mix["prompt_lens"], mix["prompt_weights"], size, rng)
    olen = dealt(mix["output_lens"], mix["output_weights"], size, rng)
    arrival = mix["arrival"]
    gaps = (exponential_gaps(float(arrival["rate_per_s"]), size, rng)
            if arrival["kind"] == "poisson" else None)
    t = 0.0
    for i in range(10 ** 9):
        L, m = next(plen), next(olen)
        prompt = tuple(int(x) for x in rng.integers(0, vocab, size=L))
        due = None
        if gaps is not None:
            t += next(gaps)
            due = t
        yield Arrival(f"{prefix}{i:05d}", prompt, m, due)


class TrainRows:
    """A token source in the shape ``repro.data.pipeline.DataPipeline``
    reads: ``sequence_batch(seed, start_seq, n_seqs, seq_len)``."""

    def __init__(self, vocab_size: int):
        self.vocab_size = vocab_size

    def sequence_batch(self, seed: int, start_seq: int, n_seqs: int,
                       seq_len: int) -> np.ndarray:
        return np.stack([
            np.random.default_rng([int(seed), 11, int(start_seq + r)])
            .integers(0, self.vocab_size, size=seq_len, dtype=np.int32)
            for r in range(n_seqs)])


def mean_output_tokens(mix: dict) -> float:
    w = np.asarray(mix["output_weights"], np.float64)
    return float(np.dot(w / w.sum(), mix["output_lens"]))


def max_len(mix: dict) -> int:
    return max(mix["prompt_lens"]) + max(mix["output_lens"])


def check_mix(mix: dict, t_max: int):
    """A mix whose longest request would not fit a lane is refused here,
    not in the middle of a window."""
    if max_len(mix) > t_max:
        raise ValueError(f"longest request {max_len(mix)} tokens exceeds "
                         f"t_max {t_max}")
    for k in ("prompt_weights", "output_weights"):
        if not math.isclose(sum(mix[k]), 1.0, rel_tol=1e-6):
            raise ValueError(f"{k} sum to {sum(mix[k])}, not 1")
