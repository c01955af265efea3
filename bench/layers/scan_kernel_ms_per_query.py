"""Kernels (`kernels/rss_scan_agg/kernel.py`): the device time of the
fused scan kernels' events in the trace, per analytic query completed in
the window."""

from bench.cost import is_scan_kernel


def read(li):
    if li.trace is None or not li.window.query_s:
        return None
    seconds = li.trace.seconds_where(is_scan_kernel)
    if not seconds:
        return None
    return seconds * 1e3 / len(li.window.query_s)
