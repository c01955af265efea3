"""OLTP engine (`mvcc/engine.py`): mean time of the program's
`oltp_commit` span, the commit path (certify, version install, WAL emit,
bookkeeping GC) of every commit on the engine, from the
`oltp_commit_seconds` sum and count in the registry totals.  On the
unified node the analytic readers' read-only commits are among them."""


def read(li):
    n = li.totals.get("oltp_commit_seconds_count")
    if not n:
        return None
    return li.totals["oltp_commit_seconds_sum"] * 1e3 / n
