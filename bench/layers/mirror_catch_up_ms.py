"""Paged mirror (`tensorstore/mirror.py`): mean time of the program's
`mirror_catch_up` span, the mirror's WAL replay inside
`SingleNodeHTAP.refresh_rss`, from the `mirror_catch_up_seconds` sum and
count in the registry totals."""


def read(li):
    n = li.totals.get("mirror_catch_up_seconds_count")
    if not n:
        return None
    return li.totals["mirror_catch_up_seconds_sum"] * 1e3 / n
