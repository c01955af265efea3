"""The benchmark's one traffic generator.

A mix file (`bench/mixes/<name>.json`) holds only parameters: the OLTP
transaction weights and the analytic query shapes with their weights and
parameter ranges.  This module turns a mix and a schema into step
generators, the shape `repro.mvcc.driver` drives:

    ("r", key)               read one key
    ("w", key, value)        write one key
    ("olap", spec)           serve one query plan; the generator receives
                             the result
    ("out", value)           emit a result (free)

Query plans are the benchmark's own specs, not the program's IR, so the
plain reference (`bench/reference.py`) can evaluate them without importing
the program:

    ("scan",  keys)                    list of values
    ("agg",   keys, op)                one int
    ("multi", keys, (op, ...))         tuple of ints
    ("group", (keys, ...), (op, ...))  tuple over groups of tuples of ints

with op = (kind, field, threshold).  `bench/harness.py` lowers a spec to
the program's plan IR.

The OLTP transactions and the analytic query shapes are copies of
`repro.mvcc.workload` (the TPC-C-style writers and the CH-like queries),
kept here so that a change to the program cannot move the yardstick.  The
analytic shapes take their parameters from the mix (drawn per query from
the stream's seeded generator) instead of the program's fixed constants.
"""

from __future__ import annotations

import json
import random
from dataclasses import dataclass
from functools import cached_property
from pathlib import Path
from typing import Iterator

BENCH = Path(__file__).resolve().parent


@dataclass(frozen=True)
class Schema:
    """The CH-benCHmark key space of one configuration (its `schema`)."""
    warehouses: int
    districts: int
    customers: int
    items: int
    order_capacity: int

    @classmethod
    def from_config(cls, cfg: dict) -> "Schema":
        return cls(**{k: int(cfg["schema"][k]) for k in
                      ("warehouses", "districts", "customers", "items",
                       "order_capacity")})

    # key tuples are built once per schema: plans share them, and the
    # program hashes a plan's key tuple on every serve
    @cached_property
    def stock_keys(self) -> tuple:
        return tuple(f"stock:{w}:{i}" for w in range(self.warehouses)
                     for i in range(self.items))

    @cached_property
    def customer_keys(self) -> tuple:
        return tuple(f"customer:{w}:{d}:{c}" for w in range(self.warehouses)
                     for d in range(self.districts)
                     for c in range(self.customers))

    @cached_property
    def district_keys(self) -> tuple:
        return tuple(f"district:{w}:{d}" for w in range(self.warehouses)
                     for d in range(self.districts))

    def order_range(self, w: int, d: int) -> tuple:
        return tuple(f"order:{w}:{d}:{o}"
                     for o in range(self.order_capacity))

    def key_families(self) -> list:
        """Every statically known key, family-major, in the order the
        plans enumerate them (reserved contiguously on the paged mirror,
        as `repro.mvcc.workload.Scale.key_families` does)."""
        return ([f"warehouse:{w}" for w in range(self.warehouses)]
                + list(self.district_keys) + list(self.customer_keys)
                + list(self.stock_keys)
                + [k for w in range(self.warehouses)
                   for d in range(self.districts)
                   for k in self.order_range(w, d)])

    def initial_rows(self, rng: random.Random) -> list:
        """The initial population (one transaction), as
        `workload.load_initial`, plus each district's statically addressed
        orders (TPC-C populates every district with orders; clause
        4.3.3.1), drawn like a new-order's, with `next_o_id` past them."""
        rows = []
        for w in range(self.warehouses):
            rows.append((f"warehouse:{w}", 0))
            for d in range(self.districts):
                rows.append((f"district:{w}:{d}",
                             {"next_o_id": self.order_capacity, "ytd": 0}))
                for c in range(self.customers):
                    rows.append((f"customer:{w}:{d}:{c}", 1000))
                for o in range(self.order_capacity):
                    n = rng.randint(5, 15)
                    rows.append((f"order:{w}:{d}:{o}", {
                        "items": [rng.randrange(self.items)
                                  for _ in range(n)],
                        "total": sum(rng.randint(1, 10) for _ in range(n))}))
            for i in range(self.items):
                rows.append((f"stock:{w}:{i}", 100))
        return rows

    @cached_property
    def dashboards(self) -> dict:
        """The four fixed-key plans that
        `repro.mvcc.workload.Scale.materialized_plans` registers as views,
        by name."""
        return {
            "stock_level": ("agg", self.stock_keys,
                            ("count_below", "int", 50)),
            "customer_balance": ("agg", self.customer_keys,
                                 ("sum", "int", None)),
            "stock_overview": ("multi", self.stock_keys,
                               (("sum", "int", None), ("count", "int", None),
                                ("min", "int", None),
                                ("count_above", "int", 90))),
            "district_revenue": ("group",
                                 tuple(self.order_range(w, d)
                                       for w in range(self.warehouses)
                                       for d in range(self.districts)),
                                 (("sum", "total", None),
                                  ("count", "total", None))),
        }


# ------------------------------------------------------------------ OLTP
def new_order(rng: random.Random, sc: Schema) -> Iterator[tuple]:
    w = rng.randrange(sc.warehouses)
    d = rng.randrange(sc.districts)
    dk = f"district:{w}:{d}"
    dist = yield ("r", dk)
    o_id = (dist or {"next_o_id": 0})["next_o_id"]
    yield ("w", dk, {"next_o_id": o_id + 1, "ytd": (dist or {}).get("ytd", 0)})
    total = 0
    items = []
    for _ in range(rng.randint(5, 15)):
        i = rng.randrange(sc.items)
        skey = f"stock:{w}:{i}"
        qty = yield ("r", skey)
        qty = qty if isinstance(qty, int) else 100
        take = rng.randint(1, 10)
        yield ("w", skey, qty - take if qty - take >= 10 else qty - take + 91)
        total += take
        items.append(i)
    yield ("w", f"order:{w}:{d}:{o_id}", {"items": items, "total": total})


def payment(rng: random.Random, sc: Schema) -> Iterator[tuple]:
    w = rng.randrange(sc.warehouses)
    d = rng.randrange(sc.districts)
    cu = rng.randrange(sc.customers)
    amount = rng.randint(1, 5000)
    wkey = f"warehouse:{w}"
    bal = yield ("r", wkey)
    yield ("w", wkey, (bal if isinstance(bal, int) else 0) + amount)
    ckey = f"customer:{w}:{d}:{cu}"
    cbal = yield ("r", ckey)
    yield ("w", ckey, (cbal if isinstance(cbal, int) else 0) - amount)


def order_status(rng: random.Random, sc: Schema) -> Iterator[tuple]:
    """Read-only; runs under SSI, not RSS."""
    w = rng.randrange(sc.warehouses)
    d = rng.randrange(sc.districts)
    dist = yield ("r", f"district:{w}:{d}")
    o_id = max(((dist or {"next_o_id": 1})["next_o_id"]) - 1, 0)
    order = yield ("r", f"order:{w}:{d}:{o_id}")
    yield ("out", order)


OLTP = {"new_order": new_order, "payment": payment,
        "order_status": order_status}
READ_ONLY = frozenset({"order_status"})


# ----------------------------------------------------------- ad hoc OLAP
def _draw(rng: random.Random, bounds) -> int:
    lo, hi = bounds
    return rng.randint(int(lo), int(hi))


def _recent_orders(dkeys, dists, last_n: int) -> tuple:
    groups = []
    for dk, dist in zip(dkeys, dists):
        _, w, d = dk.split(":")
        hi = (dist or {"next_o_id": 0})["next_o_id"]
        groups.append(tuple(f"order:{w}:{d}:{o}"
                            for o in range(max(hi - last_n, 0), hi)))
    return tuple(groups)


def q_stock_level(rng, sc, p):
    """Stock below a threshold (TPC-C Stock-Level's 10-20) everywhere."""
    thr = _draw(rng, p["threshold"])
    low = yield ("olap", ("agg", sc.stock_keys, ("count_below", "int", thr)))
    yield ("out", low)


def q_customer_balance(rng, sc, p):
    """Total balance of customers under a credit bound."""
    thr = _draw(rng, p["threshold"])
    total = yield ("olap", ("agg", sc.customer_keys,
                            ("sum_below", "int", thr)))
    yield ("out", total)


def q_order_revenue(rng, sc, p):
    """District pass, then revenue of each district's last orders."""
    last_n = _draw(rng, p["last_orders"])
    dkeys = sc.district_keys
    dists = yield ("olap", ("scan", dkeys))
    keys = tuple(k for g in _recent_orders(dkeys, dists, last_n) for k in g)
    rev = 0
    if keys:
        rev = yield ("olap", ("agg", keys, ("sum", "total", None)))
    yield ("out", rev)


def q_district_revenue_group(rng, sc, p):
    """GROUP BY district over each district's last orders: sum, count."""
    last_n = _draw(rng, p["last_orders"])
    dkeys = sc.district_keys
    dists = yield ("olap", ("scan", dkeys))
    rows = yield ("olap", ("group", _recent_orders(dkeys, dists, last_n),
                           (("sum", "total", None), ("count", "total", None))))
    yield ("out", rows)


def q_district_revenue_all(rng, sc, p):
    """GROUP BY district over the static order ranges, with the orders
    above a drawn total counted."""
    thr = _draw(rng, p["threshold"])
    groups = tuple(sc.order_range(w, d) for w in range(sc.warehouses)
                   for d in range(sc.districts))
    rows = yield ("olap", ("group", groups,
                           (("sum", "total", None), ("count", "total", None),
                            ("count_above", "total", thr))))
    yield ("out", rows)


def q_stock_overview(rng, sc, p):
    """Total, count, floor and the rows above a drawn level of stock."""
    thr = _draw(rng, p["threshold"])
    out = yield ("olap", ("multi", sc.stock_keys,
                          (("sum", "int", None), ("count", "int", None),
                           ("min", "int", None),
                           ("count_above", "int", thr))))
    yield ("out", out)


def q_dashboard(rng, sc, p):
    """A registered dashboard plan, as registered."""
    out = yield ("olap", sc.dashboards[p["plan"]])
    yield ("out", out)


QUERIES = {"stock_level": q_stock_level,
           "customer_balance": q_customer_balance,
           "order_revenue": q_order_revenue,
           "district_revenue_group": q_district_revenue_group,
           "district_revenue_all": q_district_revenue_all,
           "stock_overview": q_stock_overview,
           "dashboard": q_dashboard}


# -------------------------------------------------------------------- mix
class Deck:
    """A shuffled deck of cards, dealt one at a time and reshuffled when
    empty (the card-deck method of TPC-C clause 5.2.4.2): every item
    appears exactly as many times per deck as its count, so the mix of a
    run depends on the seed only in its order."""

    def __init__(self, cards: list, rng: random.Random) -> None:
        self.cards = [item for item, n in cards for _ in range(int(n))]
        if not self.cards:
            raise ValueError("empty deck")
        self.rng = rng
        self.left: list = []

    def deal(self):
        if not self.left:
            self.left = list(self.cards)
            self.rng.shuffle(self.left)
        return self.left.pop()


class Mix:
    """A traffic mix read from its data file: card counts of the OLTP
    transactions and of the analytic query shapes, with each shape's
    parameter ranges, and the warm-up rounds."""

    def __init__(self, spec: dict, schema: Schema) -> None:
        self.name = spec["name"]
        self.schema = schema
        self.oltp = [(OLTP[n], c) for n, c in spec["oltp"].items()]
        self.queries = [((QUERIES[q["shape"]], q.get("params", {})),
                         q["cards"]) for q in spec["olap"]]
        self.warmup_rounds = int(spec["warmup_rounds"])

    @classmethod
    def load(cls, name: str, schema: Schema, root: Path = BENCH) -> "Mix":
        """The mix `<root>/mixes/<name>.json`."""
        spec = json.loads((root / "mixes" / f"{name}.json").read_text())
        if spec["name"] != name:
            raise ValueError(f"mix file {name}.json names {spec['name']!r}")
        return cls(spec, schema)

    def terminal_deck(self, rng: random.Random) -> Deck:
        return Deck(self.oltp, rng)

    def stream_deck(self, rng: random.Random) -> Deck:
        return Deck(self.queries, rng)

    def transaction(self, fn, rng: random.Random):
        """(step generator, read_only) of a dealt terminal transaction."""
        return fn(rng, self.schema), fn.__name__ in READ_ONLY

    def query(self, card, rng: random.Random):
        """Step generator of a dealt analytic query."""
        fn, params = card
        return fn(rng, self.schema, params)
