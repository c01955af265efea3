"""OLTP engine (`mvcc/engine.py`, `certify.py`, `core/wal.py`): host time
of every terminal step and commit in the window, from the harness's clock,
per acknowledged commit."""


def read(li):
    span = li.spans.get("oltp")
    if span is None or not li.window.commits:
        return None
    return span[0] * 1e3 / li.window.commits
