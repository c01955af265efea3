"""Version GC (`mvcc/store.py:Store.prune`, on the primary and every
replica): the share of version chains a GC pass visited that dropped at
least one version, in %, from the program's `gc_chains_pruned` and
`gc_chains_visited` counters."""


def read(li):
    visited = li.totals.get("gc_chains_visited")
    if not visited:
        return None
    return 100.0 * li.totals.get("gc_chains_pruned", 0) / visited
