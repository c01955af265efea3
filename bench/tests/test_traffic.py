"""The traffic generator under each mix file: deck composition, step
shapes, determinism from the seed, and ad hoc plans that never equal a
registered dashboard."""

from __future__ import annotations

import random
from collections import Counter
from pathlib import Path

import pytest

from bench.traffic import OLTP, Deck, Mix, Schema

from .conftest import TINY_SCHEMA

MIXES = sorted(p.stem for p in
               (Path(__file__).resolve().parents[1] / "mixes").glob("*.json"))
SCHEMA = Schema(**TINY_SCHEMA)
SPEC_KINDS = {"scan", "agg", "multi", "group"}


def _drive(gen, answer):
    """Run a step generator to its end, answering every read and query
    with `answer(step)`; returns the steps it yielded."""
    steps, reply = [], None
    while True:
        try:
            step = gen.send(reply)
        except StopIteration:
            return steps
        steps.append(step)
        reply = answer(step)


def _answer(step):
    if step[0] == "r":
        return {"next_o_id": 8, "ytd": 0} if step[1].startswith("district") \
            else 50
    if step[0] == "olap" and step[1][0] == "scan":
        return [{"next_o_id": 8, "ytd": 0}] * len(step[1][1])
    return None


def _queries(mix: Mix, seed: int, n: int) -> list:
    rng = random.Random(seed)
    deck = mix.stream_deck(rng)
    return [s[1] for _ in range(n)
            for s in _drive(mix.query(deck.deal(), rng), _answer)
            if s[0] == "olap"]


@pytest.mark.parametrize("name", MIXES)
def test_mix_decks_hold_their_cards(name):
    mix = Mix.load(name, SCHEMA)
    n_oltp = sum(c for _f, c in mix.oltp)
    deck = mix.terminal_deck(random.Random(1))
    dealt = Counter(deck.deal().__name__ for _ in range(3 * n_oltp))
    assert dealt == Counter({f.__name__: 3 * c for f, c in mix.oltp})
    n_olap = sum(c for _q, c in mix.queries)
    sdeck = mix.stream_deck(random.Random(2))
    shapes = Counter(sdeck.deal()[0].__name__ for _ in range(2 * n_olap))
    assert sum(shapes.values()) == 2 * n_olap


@pytest.mark.parametrize("name", MIXES)
def test_mix_steps_are_well_formed_and_seeded(name):
    mix = Mix.load(name, SCHEMA)
    specs = _queries(mix, 7, 40)
    assert specs and all(s[0] in SPEC_KINDS for s in specs)
    assert specs == _queries(mix, 7, 40)
    for fn, _c in mix.oltp:
        gen, read_only = mix.transaction(fn, random.Random(3))
        steps = _drive(gen, _answer)
        assert steps and all(s[0] in ("r", "w", "out") for s in steps)
        assert read_only == all(s[0] != "w" for s in steps)


def test_tpcc_terminal_mix_is_the_repos():
    for name in MIXES:
        mix = Mix.load(name, SCHEMA)
        assert {f.__name__: c for f, c in mix.oltp} == \
            {"new_order": 45, "payment": 43, "order_status": 12}
        assert set(f.__name__ for f, _c in mix.oltp) <= set(OLTP)


def test_adhoc_never_asks_a_registered_dashboard():
    mix = Mix.load("adhoc", SCHEMA)
    views = set(SCHEMA.dashboards.values())
    specs = _queries(mix, 11, 600)
    assert {s[0] for s in specs} == SPEC_KINDS
    assert not views & set(specs)


def test_dashboards_ask_only_registered_plans():
    mix = Mix.load("dashboards", SCHEMA)
    specs = _queries(mix, 13, 40)
    assert set(specs) == set(SCHEMA.dashboards.values())


def test_adhoc_thresholds_are_drawn_within_their_ranges():
    mix = Mix.load("adhoc", SCHEMA)
    stock = [s[2][2] for s in _queries(mix, 17, 600)
             if s[0] == "agg" and s[2][0] == "count_below"]
    assert stock and min(stock) >= 10 and max(stock) <= 20
    assert len(set(stock)) > 3


def test_deck_deals_every_card_once_per_round():
    deck = Deck([("a", 2), ("b", 1)], random.Random(5))
    assert sorted(deck.deal() for _ in range(3)) == ["a", "a", "b"]
    with pytest.raises(ValueError):
        Deck([("a", 0)], random.Random(5))
