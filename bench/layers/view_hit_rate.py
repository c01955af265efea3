"""Views (`tensorstore/materialized.py`): registered plans served from a
view tile, as a share of registered plans served (hits and fallbacks),
in %, from the program's registry counters."""


def read(li):
    hits = li.totals.get("mirror_exec_view_hits", 0)
    falls = li.totals.get("mirror_exec_view_fallbacks", 0)
    if not hits + falls:
        return None
    return 100.0 * hits / (hits + falls)
