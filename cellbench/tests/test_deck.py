"""The deck: one multiset for every seed, balanced block by block, no
neighbours of one prompt length, made as it is drawn from."""
import collections

import numpy as np
import pytest

from cellbench import deck, run

SEEDS = (0, 1, 7, 2 ** 31 + 12345, 2 ** 32 + 5)


@pytest.fixture(scope="module")
def traffic():
    return run.load_json(run.HERE, "traffic", "chat_deck_saturated.json")


def test_same_multiset_for_every_seed(traffic):
    want = None
    for seed in SEEDS:
        blocks = deck.stream(traffic, seed, 3)
        flat = collections.Counter(r for b in blocks for r in b)
        if want is None:
            want = flat
        assert flat == want
    per_deck = traffic["blocks"] * len(traffic["prompt_lengths"])
    pairs = len(traffic["prompt_lengths"]) * len(traffic["output_lengths"])
    assert set(want.values()) == {3 * per_deck // pairs}
    assert len(want) == pairs


def test_deck_is_the_issue_s_deck():
    traffic = run.load_json(run.HERE, "traffic",
                            "chat_deck_saturated.json")
    one = [r for b in deck.stream(traffic, 3, 1) for r in b]
    assert len(one) == 32
    assert sum(p for p, _ in one) == 15360
    assert sum(o for _, o in one) == 1920
    assert max(p + o for p, o in one) == 1152


def test_every_block_is_balanced(traffic):
    for seed in SEEDS:
        for block in deck.stream(traffic, seed, 2):
            assert sorted(p for p, _ in block) == sorted(
                traffic["prompt_lengths"])
            assert sorted(o for _, o in block) == sorted(
                traffic["output_lengths"])


def test_no_neighbours_share_a_prompt_length(traffic):
    for seed in SEEDS + tuple(range(100, 140)):
        flat = [p for b in deck.stream(traffic, seed, 4) for p, _ in b]
        assert all(a != b for a, b in zip(flat, flat[1:]))


def test_seed_changes_order_and_ids_only(traffic):
    a = deck.requests(traffic, 11, 2, 1000)
    b = deck.requests(traffic, 12, 2, 1000)
    again = deck.requests(traffic, 11, 2, 1000)
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in b]
    assert all(np.array_equal(x["prompt"], y["prompt"])
               for x, y in zip(a, again))
    assert all(0 <= r["prompt"].min() and r["prompt"].max() < 1000
               for r in a)


def test_the_stream_is_made_as_it_is_drawn_and_never_runs_dry(traffic):
    s = deck.Stream(traffic, 11, 1000)
    assert not s.shapes
    far = s[32 * 40 + 5]                     # forty decks on
    assert len(far["prompt"]) in traffic["prompt_lengths"]
    assert len(s.shapes) == 8 * 41
    # drawn in any order, request i is the request i of a list
    listed = deck.requests(traffic, 11, 2, 1000)
    assert all(np.array_equal(s[i]["prompt"], listed[i]["prompt"]) and
               s[i]["max_new"] == listed[i]["max_new"]
               for i in (63, 0, 31, 32))
