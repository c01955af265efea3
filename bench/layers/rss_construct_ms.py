"""RSS construction (`core/rss.py`, `core/replica.py`): mean time of the
program's `rss_construct` span, the RSS manager's WAL replay and
construction inside `SingleNodeHTAP.refresh_rss`, from the
`rss_construct_seconds` sum and count in the registry totals."""


def read(li):
    n = li.totals.get("rss_construct_seconds_count")
    if not n:
        return None
    return li.totals["rss_construct_seconds_sum"] * 1e3 / n
