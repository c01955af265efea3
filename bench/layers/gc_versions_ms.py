"""Version GC (`mvcc/store.py` prune, through
`MultiNodeHTAP.gc_versions` on the primary and every replica): mean host
time of one GC pass in the window, from the harness's clock.  A pass holds
up the whole round, so it takes its share from every client."""


def read(li):
    span = li.spans.get("gc_versions")
    if span is None or not span[1]:
        return None
    return span[0] * 1e3 / span[1]
