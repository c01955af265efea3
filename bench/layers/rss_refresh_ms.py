"""RSS construction and mirror catch-up (`core/replica.py` through
`SingleNodeHTAP.refresh_rss`): mean host time of one refresh in the
window, from the harness's clock."""


def read(li):
    span = li.spans.get("refresh_rss")
    if span is None or not span[1]:
        return None
    return span[0] * 1e3 / span[1]
