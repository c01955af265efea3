"""In-memory multiversion storage (the paper's PostgreSQL-heap analogue).

Every key maps to a chain of committed versions, newest last.  Versions carry
(commit_seq, writer txn id, value).  Version 0 (writer T0==0, commit_seq 0) is
the initial version of every key.  Uncommitted writes never enter the chain —
transactions buffer their writesets until commit (install-at-commit, which
makes First-Committer-Wins the natural SI-W rule).

GC: `prune(floor_seq)` drops versions strictly older than the newest version
at-or-below `floor_seq` per key — the replica/PRoT pin (hot_standby_feedback
analogue) sets the floor.  A chain holding a single version has nothing to
drop, so a pass visits only the store's `candidates`: the chains holding two
or more versions.  A chain a store created enters `candidates` from its own
`install` when it reaches two versions, whichever path installed it (engine
commit, replica WAL replay, a direct `chain(key).install`); a pass takes out
every chain it leaves with one.  Each pass adds the chains it visited (the
candidates) and those that dropped at least one version to the
`gc_chains_visited` / `gc_chains_pruned` counters, once per pass; its
callers time it as the `gc_prune` span (`GC_PRUNE_H`, labelled by node).
"""

from __future__ import annotations

from bisect import bisect_right
from dataclasses import dataclass
from typing import Any, Callable, Iterable, Iterator, Optional

from ..obs import REGISTRY

_CHAINS_VISITED = REGISTRY.counter("gc_chains_visited")
_CHAINS_PRUNED = REGISTRY.counter("gc_chains_pruned")
# one GC pass over a node's chain store (`Engine.prune_versions` on the
# primary, `Replica.gc_versions` on a replica)
GC_PRUNE_H = {node: REGISTRY.histogram("gc_prune_seconds", node=node)
              for node in ("primary", "replica")}


@dataclass(frozen=True)
class Version:
    commit_seq: int
    writer: int
    value: Any


class VersionChain:
    __slots__ = ("versions", "key", "_candidates")

    def __init__(self, initial: Any = 0, key: Optional[str] = None,
                 candidates: Optional[dict] = None) -> None:
        """`key` and `candidates` come from the owning `Store`: the chain
        enters `candidates[key]` when it reaches two versions.  A chain
        made without them keeps no such record."""
        self.versions: list[Version] = [Version(0, 0, initial)]
        self.key = key
        self._candidates = candidates

    def install(self, commit_seq: int, writer: int, value: Any) -> None:
        assert commit_seq > self.versions[-1].commit_seq
        self.versions.append(Version(commit_seq, writer, value))
        if len(self.versions) == 2 and self._candidates is not None:
            self._candidates[self.key] = self

    def newest(self) -> Version:
        return self.versions[-1]

    def visible_at(self, snapshot_seq: int) -> Version:
        """SI-V: newest version with commit_seq <= snapshot_seq."""
        seqs = [v.commit_seq for v in self.versions]
        i = bisect_right(seqs, snapshot_seq) - 1
        return self.versions[max(i, 0)]

    def visible_in(self, member: Callable[[int, int], bool]) -> Version:
        """RSS read protocol: newest version whose writer is in the snapshot
        set (walk newest-to-oldest; RSS closure guarantees consistency).
        `member` is called with (writer txn id, commit seq) — the seq lets
        compressed snapshots resolve floor-covered members without per-txn
        bookkeeping (`RssSnapshot.visible`)."""
        for v in reversed(self.versions):
            if v.writer == 0 or member(v.writer, v.commit_seq):
                return v
        return self.versions[0]

    def prune(self, floor_seq: int) -> int:
        """Drop versions not visible at any snapshot >= floor_seq."""
        seqs = [v.commit_seq for v in self.versions]
        i = bisect_right(seqs, floor_seq) - 1
        if i > 0:
            dropped = i
            self.versions = self.versions[i:]
            return dropped
        return 0


class Store:
    def __init__(self) -> None:
        self.chains: dict[str, VersionChain] = {}
        # the chains holding two or more versions, the only ones GC can
        # shorten; kept by `VersionChain.install` and `prune`
        self.candidates: dict[str, VersionChain] = {}

    def chain(self, key: str) -> VersionChain:
        ch = self.chains.get(key)
        if ch is None:
            ch = self.chains[key] = VersionChain(
                key=key, candidates=self.candidates)
        return ch

    def keys(self) -> Iterator[str]:
        return iter(self.chains)

    def newest_seq(self) -> int:
        return max((c.newest().commit_seq for c in self.chains.values()),
                   default=0)

    def prune(self, floor_seq: int) -> int:
        """Prune every candidate chain at `floor_seq`, take out of
        `candidates` each one left with a single version, and return the
        versions dropped.  Counts the candidates as the chains visited."""
        cands = self.candidates
        dropped = [c.prune(floor_seq) for c in cands.values()]
        _CHAINS_VISITED.inc(len(dropped))
        _CHAINS_PRUNED.inc(len(dropped) - dropped.count(0))
        for key in [k for k, c in cands.items() if len(c.versions) == 1]:
            del cands[key]
        return sum(dropped)

    def version_count(self) -> int:
        return sum(len(c.versions) for c in self.chains.values())
