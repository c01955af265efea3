"""Replica placement (`MultiNodeHTAP(replica_devices=...)`): the least busy
chip's device busy time over the busiest chip's, in %, from the trace's
busy time per chip; None with fewer than two chips in the trace."""


def read(li):
    busy = getattr(li.trace, "busy_by_chip", None) or {}
    if len(busy) < 2 or not max(busy.values()):
        return None
    return 100.0 * min(busy.values()) / max(busy.values())
