"""Plain reference for the served analytic results.

It keeps every committed write the benchmark's clients made, with the
commit's sequence number and the time it was acknowledged, and answers a
query spec (see `bench/traffic.py`) at a snapshot given as the program
states it: a floor sequence number (every commit at or below it is
visible) and the explicit member sequence numbers above the floor.  A
key's visible value is its newest visible write; a key never written reads
as the initial value 0.  Aggregates follow the plan semantics the program
documents: field "int" takes integer values (0 included), field "total"
takes the "total" of order rows; min and max of nothing are 0.

It imports nothing of the program: it sees the writes the clients sent and
the commit numbers the engine acknowledged, never the program's pages,
tiles or results.  `consistency` holds those acknowledged writes to the
conditions a serializable execution of the TPC-C transactions keeps.
"""

from __future__ import annotations

import bisect

import numpy as np


class Reference:
    def __init__(self) -> None:
        self.key_id: dict[str, int] = {}
        self._k: list[int] = []       # per version: key id
        self._s: list[int] = []       # commit seq
        self._v: list = []            # value as written
        self._arrays = None           # (k, s, ival, iok, tot, tok), lazily
        self._chosen: dict = {}       # snapshot -> visible version per key
        self._kids: dict = {}         # key tuple -> key ids
        self.ack_seq: list[int] = []  # acknowledged commits with writes
        self.ack_t: list[float] = []

    # -------------------------------------------------------------- record
    def commit(self, seq: int, writes, t_ack: float) -> None:
        """One acknowledged commit: `writes` is [(key, value)]."""
        if not writes:
            return
        if self.ack_seq and seq <= self.ack_seq[-1]:
            raise ValueError(f"commit seq {seq} not above {self.ack_seq[-1]}")
        for key, value in writes:
            kid = self.key_id.setdefault(key, len(self.key_id))
            self._k.append(kid)
            self._s.append(seq)
            self._v.append(value)
        self.ack_seq.append(seq)
        self.ack_t.append(t_ack)
        self._arrays = None
        self._chosen.clear()

    @property
    def last_seq(self) -> int:
        return self.ack_seq[-1] if self.ack_seq else 0

    def histories(self, prefixes: tuple) -> dict:
        """Per key that starts with one of `prefixes`, the values written to
        it, in commit order."""
        names = {kid: key for key, kid in self.key_id.items()
                 if key.startswith(prefixes)}
        out: dict = {key: [] for key in names.values()}
        for kid, value in zip(self._k, self._v):
            key = names.get(kid)
            if key is not None:
                out[key].append(value)
        return out

    # ----------------------------------------------------------- staleness
    def staleness(self, floor: int, members, t_serve: float) -> float:
        """Seconds from the acknowledgement of the oldest commit acknowledged
        before `t_serve` that the snapshot does not see, to `t_serve`; 0
        when it sees every one."""
        mem = set(members)
        i = bisect.bisect_right(self.ack_seq, floor)
        while i < len(self.ack_seq) and self.ack_t[i] <= t_serve:
            if self.ack_seq[i] not in mem:
                return t_serve - self.ack_t[i]
            i += 1
        return 0.0

    # ----------------------------------------------------------- visibility
    def _build(self):
        if self._arrays is None:
            n = len(self._v)
            ival = np.zeros(n, np.int64)
            iok = np.zeros(n, bool)
            tot = np.zeros(n, np.int64)
            tok = np.zeros(n, bool)
            for i, v in enumerate(self._v):
                if isinstance(v, int) and not isinstance(v, bool):
                    ival[i], iok[i] = v, True
                elif isinstance(v, dict) and "total" in v:
                    tot[i], tok[i] = v["total"], True
            self._arrays = (np.asarray(self._k, np.int64),
                            np.asarray(self._s, np.int64), ival, iok, tot,
                            tok)
        return self._arrays

    def _visible(self, floor: int, members: tuple) -> np.ndarray:
        """Per key id, the index of its newest visible version (-1: none)."""
        snap = (int(floor), tuple(members))
        chosen = self._chosen.get(snap)
        if chosen is not None:
            return chosen
        k, s = self._build()[:2]
        vis = s <= floor
        if members:
            vis |= np.isin(s, np.asarray(members, np.int64))
        best = np.full(len(self.key_id), -1, np.int64)
        np.maximum.at(best, k[vis], s[vis])
        chosen = np.full(len(self.key_id), -1, np.int64)
        sel = np.nonzero(vis & (s == best[k]))[0]
        chosen[k[sel]] = sel
        self._chosen[snap] = chosen
        return chosen

    def _versions(self, keys: tuple, chosen: np.ndarray) -> np.ndarray:
        kids = self._kids.get(keys)
        if kids is None:
            kids = np.fromiter((self.key_id.get(key, -1) for key in keys),
                               np.int64, count=len(keys))
            self._kids[keys] = kids
        return np.where(kids >= 0, chosen[np.maximum(kids, 0)], -1)

    # ----------------------------------------------------------- evaluation
    def _field(self, ver: np.ndarray, field: str):
        _k, _s, ival, iok, tot, tok = self._build()
        hit = ver >= 0
        v = np.maximum(ver, 0)
        if field == "int":                  # unwritten keys read as int 0
            return np.where(hit, ival[v], 0), np.where(hit, iok[v], True)
        if field == "total":
            return np.where(hit, tot[v], 0), np.where(hit, tok[v], False)
        raise ValueError(f"unknown field {field!r}")

    def _agg(self, ver: np.ndarray, op) -> int:
        kind, field, thr = op
        x, ok = self._field(ver, field)
        x = x[ok]
        if kind == "sum":
            return int(x.sum())
        if kind == "count":
            return int(x.size)
        if kind == "count_below":
            return int((x < thr).sum())
        if kind == "count_above":
            return int((x > thr).sum())
        if kind == "sum_below":
            return int(x[x < thr].sum())
        if kind == "min":
            return int(x.min()) if x.size else 0
        if kind == "max":
            return int(x.max()) if x.size else 0
        raise ValueError(f"unknown aggregate {kind!r}")

    def evaluate(self, spec: tuple, floor: int, members=()):
        """The spec's answer at the snapshot (floor, members)."""
        chosen = self._visible(floor, tuple(members))
        kind = spec[0]
        if kind == "scan":
            ver = self._versions(spec[1], chosen)
            return [self._v[v] if v >= 0 else 0 for v in ver.tolist()]
        if kind == "agg":
            return self._agg(self._versions(spec[1], chosen), spec[2])
        if kind == "multi":
            ver = self._versions(spec[1], chosen)
            return tuple(self._agg(ver, op) for op in spec[2])
        if kind == "group":
            out = []
            for grp in spec[1]:
                ver = self._versions(tuple(grp), chosen)
                out.append(tuple(self._agg(ver, op) for op in spec[2]))
            return tuple(out)
        raise ValueError(f"unknown spec {kind!r}")


def consistency(ref: Reference) -> dict:
    """The consistency conditions that every serial order of the benchmark's
    TPC-C transactions (`bench/traffic.py`) keeps, in the manner of TPC-C
    clause 3.3.2, read from the acknowledged writes; each is 0 when it holds.
    A lost update or a write skew that certification let through breaks one.

    - `balance_drift`: a payment moves an amount from a customer to its
      warehouse, so the sum of warehouse and customer balances never moves;
      the distance of the final sum from the initial one.
    - `order_id_drift`: a new-order writes its district's `next_o_id` + 1, so
      each district's final `next_o_id` is the initial one plus the
      new-orders committed there (the commits that wrote the district);
      summed distance over districts.
    - `orders_written_twice`: a new-order writes the order id it took from
      `next_o_id`, and the initial load writes every order below it once,
      so no order key has a second write; the count of second writes."""
    h = ref.histories(("warehouse:", "customer:", "district:", "order:"))
    money = [v for k, v in h.items() if k.startswith(("warehouse:",
                                                       "customer:"))]
    balance_drift = abs(sum(v[-1] for v in money) - sum(v[0] for v in money))
    order_id_drift = sum(
        abs(v[-1]["next_o_id"] - v[0]["next_o_id"] - (len(v) - 1))
        for k, v in h.items() if k.startswith("district:"))
    orders_written_twice = sum(len(v) - 1 for k, v in h.items()
                               if k.startswith("order:"))
    return {"balance_drift": balance_drift,
            "order_id_drift": order_id_drift,
            "orders_written_twice": orders_written_twice}


def same(result, expected) -> bool:
    """Served result equal to the reference's answer (tuples and lists
    compare by content)."""
    if isinstance(expected, (tuple, list)):
        return (isinstance(result, (tuple, list))
                and len(result) == len(expected)
                and all(same(r, e) for r, e in zip(result, expected)))
    return result == expected
