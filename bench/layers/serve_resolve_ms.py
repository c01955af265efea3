"""Paged mirror resolve (`tensorstore/mirror.py`): the program's
`olap_stage_seconds{stage=resolve}` sum over the window per plan served.
A view hit on a replica resolves nothing, so a window of hits alone
reads 0."""


def read(li):
    if not li.window.plan_serves:
        return None
    stage = li.stages.get("resolve") or {"sum_us": 0.0}
    return stage["sum_us"] * 1e-3 / li.window.plan_serves
