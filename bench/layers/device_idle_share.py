"""Device (TPU): the share of the window in which no operation ran on the
device, in %: 1 minus the union of the trace's device op intervals over
the annotated window, averaged over the chips."""


def read(li):
    if li.trace is None or not li.trace.window_s:
        return None
    return 100.0 * li.trace.idle_share
