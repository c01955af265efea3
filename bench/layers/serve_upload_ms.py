"""Kernel ops (`tensorstore/mirror.py:jnp_store_for`): the program's
`serve_upload` span, the host gather and device copy of a scanned
sub-store when the mirror's store cache misses, summed over the window
per plan served.  The copy is asynchronous, so what waits for its end
lands in the next span that reads the store (`serve_maxabs`)."""


def read(li):
    total = li.totals.get("serve_upload_seconds_sum")
    if total is None or not li.window.plan_serves:
        return None
    return total * 1e3 / li.window.plan_serves
