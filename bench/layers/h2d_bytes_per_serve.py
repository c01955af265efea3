"""Kernel ops (`tensorstore/mirror.py`, `tensorstore/materialized.py`):
bytes the serve and view paths copied from the host to the device in the
window, per plan served, from the program's `mirror_h2d_bytes` counter
(sub-stores, member and group arrays, view tiles and delta buffers)."""


def read(li):
    total = li.totals.get("mirror_h2d_bytes")
    if total is None or not li.window.plan_serves:
        return None
    return total / li.window.plan_serves
