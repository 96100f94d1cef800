"""The traffic generator: the same seed gives the same requests, every
seed deals the same set of lengths and gaps (in another order), rows are
a pure function of (seed, row), and seeds past 2**32 work."""
import itertools
import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from traffic import generator  # noqa: E402

MIX = json.loads((BENCH / "traffic" / "serve-chat.json").read_text())
SEEDS = [0, 2 ** 31 + 3, 2 ** 40 + 11]


def take(mix, seed, n):
    return list(itertools.islice(generator.serve_stream(mix, seed, 50304),
                                 n))


@pytest.mark.parametrize("seed", SEEDS)
def test_same_seed_same_requests(seed):
    assert take(MIX, seed, 40) == take(MIX, seed, 40)


def test_seeds_share_the_set_not_the_order():
    deck = generator.DECK
    runs = [take(MIX, s, 2 * deck) for s in SEEDS]
    sets = [sorted((len(a.prompt), a.max_new_tokens) for a in r[:deck])
            for r in runs]
    plens = [sorted(len(a.prompt) for a in r[:deck]) for r in runs]
    assert plens[0] == plens[1] == plens[2]
    assert sorted(a.max_new_tokens for a in runs[0][:deck]) == \
        sorted(a.max_new_tokens for a in runs[1][:deck])
    assert sets[0] != sets[1] or runs[0][0].prompt != runs[1][0].prompt
    want = {L: round(w * deck) for L, w in zip(MIX["prompt_lens"],
                                              MIX["prompt_weights"])}
    assert {L: plens[0].count(L) for L in want} == want


def test_open_loop_rate():
    deck = generator.DECK
    r = take(MIX, 5, 10 * deck)
    due = np.array([a.due_s for a in r])
    assert np.all(np.diff(due) > 0)
    rate = MIX["arrival"]["rate_per_s"]
    # whole decks of stratified exponential gaps average 1/rate closely
    assert abs(due[deck - 1] * rate / deck - 1) < 0.1
    assert abs(due[-1] * rate / len(due) - 1) < 0.05


def test_backlog_has_no_due_time():
    mix = json.loads((BENCH / "traffic" / "serve-backlog-durable.json")
                     .read_text())
    assert all(a.due_s is None for a in take(mix, 1, 10))


def test_rows_pure_and_distinct():
    rows = generator.TrainRows(50304)
    a = rows.sequence_batch(2 ** 33, 4, 3, 2049)
    b = rows.sequence_batch(2 ** 33, 5, 2, 2049)
    assert np.array_equal(a[1:], b)
    assert len({r.tobytes() for r in a}) == 3
    assert a.dtype == np.int32 and a.max() < 50304


def test_check_mix():
    generator.check_mix(MIX, MIX["t_max"])
    with pytest.raises(ValueError):
        generator.check_mix(MIX, 512)
