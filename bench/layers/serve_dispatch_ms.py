"""Kernel ops and transfers (`kernels/rss_scan_agg/ops.py`, and the view
tiles' serve): the program's `olap_stage_seconds{stage=dispatch}` sum
over the window per plan served.  A dispatch waits for its results, so
it includes the device time."""


def read(li):
    stage = li.stages.get("dispatch")
    if stage is None or not li.window.plan_serves:
        return None
    return stage["sum_us"] * 1e-3 / li.window.plan_serves
