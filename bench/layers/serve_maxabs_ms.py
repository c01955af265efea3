"""Kernel ops (`kernels/rss_scan_agg/ops.py:field_maxabs`): the program's
`serve_maxabs` span, the overflow guard's device-to-host copy of a
scanned sub-store and its host reduction, summed over the window per
plan served."""


def read(li):
    total = li.totals.get("serve_maxabs_seconds_sum")
    if total is None or not li.window.plan_serves:
        return None
    return total * 1e3 / li.window.plan_serves
