"""Replication (`cluster/cluster.py` through `MultiNodeHTAP.ship_log`):
mean host time of one ship to one replica in the window, from the
harness's clock."""


def read(li):
    span = li.spans.get("ship_log")
    if span is None or not span[1]:
        return None
    return span[0] * 1e3 / span[1]
