"""Kernels: the fused scan kernels' share of their roofline, in %.  The
least time the scans could take is the bytes they need (`bench/cost.py`,
from the plans alone, for every serve that launched a scan kernel) over
the chip's HBM bandwidth (`bench/peaks.json`); the share is that over the
kernels' device time in the trace.  Memory bound: the scans do about one
comparison per byte read."""

from bench.cost import is_scan_kernel


def read(li):
    if li.trace is None or "hbm_bytes_per_s" not in li.peaks:
        return None
    seconds = li.trace.seconds_where(is_scan_kernel)
    if not seconds or not li.window.kernel_bytes:
        return None
    least = li.window.kernel_bytes / li.peaks["hbm_bytes_per_s"]
    return 100.0 * least / seconds
