"""The paged mirror's per-page field bound == the overflow guard's device read.

`PagedMirror` keeps `page_maxabs`, each page's exact largest |payload
element 1| over its K slots, where `_publish` writes the page.  Every
sub-store it builds carries the bound of its own pages, and the kernel ops
take that bound instead of copying the store back from the device.  The
contract: for every store the mirror builds, the bound it passes equals,
bit for bit, what `field_maxabs` reads from that store — under negative
and int32-extreme values, recycling of large values out of a page (with
and without a `gc_floor`-pinned slot), growth past `capacity`, missing
keys, dense and gathered page sets, sublane padding, the whole-mirror
export and store-cache hits across an `apply`.
"""

import random

import numpy as np
import pytest

from repro.core.wal import WalRecord
from repro.kernels.rss_scan_agg import ops as kops
from repro.tensorstore import PagedMirror

KEYS = [f"k:{i}" for i in range(20)]
EXTREMES = (2**31 - 1, -(2**31 - 1), -2**31)


def _device_bound(store) -> int:
    col = np.asarray(store["data"])[:, :, 1].astype(np.int64)
    return int(np.abs(col).max()) if col.size else 0


def _check(store, maxabs):
    assert maxabs == _device_bound(store)
    assert maxabs == kops.field_maxabs(store)


def _export_bound(mirror) -> int:
    """The bound of `jnp_store()`'s export: its written pages' bounds
    (padding pages add 0)."""
    return int(mirror.page_maxabs[:mirror.n_pages].max(initial=0))


def _value(rng):
    r = rng.random()
    if r < 0.15:
        return rng.choice(EXTREMES)
    if r < 0.22:
        return {"next_o_id": rng.randrange(-2**31, 2**31),
                "ytd": rng.randrange(-2**31, 2**31)}
    if r < 0.3:
        return {"items": [rng.randrange(9)] * rng.randrange(3),
                "total": rng.randrange(-2**31, 2**31)}
    if r < 0.75:                 # small: recycles large values out
        return rng.randrange(-50, 50)
    return rng.randrange(-2**31, 2**31)


def _page_sets(rng):
    """Dense runs, shuffled gathers with missing keys, padded and empty
    key sequences over the mirror's keys."""
    lo = rng.randrange(len(KEYS))
    hi = rng.randrange(lo, len(KEYS) + 1)
    gather = rng.sample(KEYS, rng.randint(1, len(KEYS)))
    gather += [f"missing:{i}" for i in range(rng.randrange(4))]
    rng.shuffle(gather)
    return [tuple(KEYS[lo:hi]), tuple(KEYS[:8]), tuple(KEYS[:16]),
            tuple(gather), ("missing:a", "missing:b"), ()]


@pytest.mark.parametrize("pin", [False, True])
@pytest.mark.parametrize("seed", range(4))
def test_bound_equals_device_read(seed, pin):
    rng = random.Random(seed)
    mirror = PagedMirror(capacity=4)          # grows past capacity
    mirror.reserve(KEYS[:10])                 # a dense page run
    pinned = 0
    verdicts, falls = set(), 0
    for lsn in range(1, 241):
        writes = tuple((k, _value(rng))
                       for k in rng.sample(KEYS[:12], rng.randint(1, 3)))
        if pin and lsn == 40:
            pinned = mirror.watermark         # pin one slot per page
        before = mirror.page_maxabs.copy()
        mirror.apply(WalRecord(lsn=lsn, type="commit", txn=lsn,
                               writes=writes, seq=lsn), gc_floor=pinned)
        falls += int((mirror.page_maxabs[:len(before)] < before).sum())
        n = mirror.n_pages
        want = np.abs(mirror.data[:n, :, 1].astype(np.int64)).max(axis=1)
        assert (mirror.page_maxabs[:n] == want).all()
        assert not mirror.page_maxabs[n:].any()
        if lsn % 20:
            continue
        for keys in _page_sets(rng):
            store, maxabs = mirror.jnp_store_for(mirror.page_index(keys))
            _check(store, maxabs)
            verdicts.add(mirror._last_range_verdict)
        _check(mirror.jnp_store(), _export_bound(mirror))
    assert verdicts == {"dense", "gather"}
    assert mirror.data.shape[0] > 4
    assert falls                # large values were recycled out of pages


def test_large_value_recycled_out_of_a_page():
    mirror = PagedMirror()
    mirror.apply(WalRecord(lsn=1, type="commit", txn=1,
                           writes=(("a", -2**31), ("b", 3)), seq=1))
    keys = ("a", "b")
    assert mirror._store_for(keys, mirror.page_index(keys))[1] == 2**31
    for lsn in range(2, 2 + mirror.slots):
        mirror.apply(WalRecord(lsn=lsn, type="commit", txn=lsn,
                               writes=(("a", -lsn),), seq=lsn))
        store, maxabs = mirror._store_for(keys, mirror.page_index(keys))
        _check(store, maxabs)
    assert maxabs == mirror.slots + 1         # the bound fell


def test_pinned_slot_keeps_its_bound():
    """A gc_floor-pinned version is never recycled, so its value stays in
    the page's bound however many versions follow."""
    mirror = PagedMirror()
    mirror.apply(WalRecord(lsn=1, type="commit", txn=1,
                           writes=(("a", 2**31 - 1),), seq=1))
    for lsn in range(2, 4 * mirror.slots):
        mirror.apply(WalRecord(lsn=lsn, type="commit", txn=lsn,
                               writes=(("a", lsn),), seq=lsn), gc_floor=1)
    store, maxabs = mirror.jnp_store_for(mirror.page_index(("a",)))
    _check(store, maxabs)
    assert maxabs == 2**31 - 1


def test_store_cache_hit_keeps_bound_until_apply():
    mirror = PagedMirror()
    mirror.apply(WalRecord(lsn=1, type="commit", txn=1,
                           writes=(("a", 5), ("b", -9)), seq=1))
    keys = ("a", "b", "missing")
    pages = mirror.page_index(keys)
    first = mirror._store_for(keys, pages)
    hit = mirror._store_for(keys, pages)
    assert hit[0] is first[0] and hit[1] == first[1] == 9
    assert mirror.cache_stats["store_hits"] == 1
    mirror.apply(WalRecord(lsn=2, type="commit", txn=2,
                           writes=(("b", 7), ("c", -2**31)), seq=2))
    again = mirror._store_for(keys, mirror.page_index(keys))
    assert again[0] is not first[0]
    _check(*again)
    assert again[1] == 9                      # -9 still in a slot of "b"
    assert mirror._store_for(keys, mirror.page_index(keys))[1] == 9


def test_empty_mirror_exports_zero_bound():
    mirror = PagedMirror()
    _check(mirror.jnp_store(), _export_bound(mirror))
    _check(*mirror.jnp_store_for(mirror.page_index(())))
    assert _export_bound(mirror) == 0


def test_the_ops_require_the_bound():
    """No op reads the bound back from the device: a call without one
    is an error, and a call with the mirror's bound aggregates exactly."""
    mirror = PagedMirror()
    mirror.apply(WalRecord(lsn=1, type="commit", txn=1,
                           writes=tuple((f"k:{i}", i) for i in range(8)),
                           seq=1))
    store, maxabs = mirror.jnp_store_for(mirror.page_index(
        tuple(f"k:{i}" for i in range(8))))
    mem = np.zeros((0,), np.int32)
    gid = np.zeros(int(store["ts"].shape[0]), np.int32)
    for op, args in ((kops.snapshot_agg_members, ()),
                     (kops.snapshot_group_agg_members, (gid, 1)),
                     (kops.grouped_agg_auto, (gid, 1))):
        with pytest.raises(TypeError, match="maxabs"):
            op(store, *args, mem, 1, **({"tag_main": 1}
                                        if not args else {}))
    got = kops.snapshot_agg_members(store, mem, 1, tag_main=1,
                                    maxabs=maxabs, use_kernel=False)
    assert got[:2] == [sum(range(8)), 8]


# ------------------------------------------------------- facade threading
_GUARD_STATS = ("dispatches", "pallas_calls", "host", "flat", "chunked",
                "overflow_fallbacks")
_GUARDED_OPS = ("snapshot_agg_members", "snapshot_group_agg_members",
                "grouped_agg_auto")


@pytest.mark.parametrize("mode", [None, "chunked"])
@pytest.mark.parametrize("facade", ["single", "multi"])
def test_facade_bound_matches_forced_device_read(facade, mode, monkeypatch):
    """Both HTAP facades serve ad hoc plans and registered dashboard views
    (with min/max demotions) twice on one seed: once on the mirror's bound,
    whose every use is checked against the device read, and once with the
    device read forced in its place.  `check_scans` holds each serve to the
    per-key engine oracle; the two runs must choose the same modes, fall
    back as often, and give the same outputs."""
    from repro.mvcc.driver import run_multi_node, run_single_node
    from repro.obs import REGISTRY

    if mode:
        monkeypatch.setenv(kops.GROUPED_MODE_ENV, mode)
    run, extra = ((run_single_node, {}) if facade == "single"
                  else (run_multi_node, {"n_replicas": 2}))
    real = {name: getattr(kops, name) for name in _GUARDED_OPS}
    checks = []

    def guarded(name, forced):
        def call(store, *args, maxabs, **kwargs):
            device = kops.field_maxabs(store)
            checks.append(maxabs == device)
            return real[name](store, *args, maxabs=device if forced
                              else maxabs, **kwargs)
        return call

    runs = []
    for forced in (False, True):
        for name in _GUARDED_OPS:
            monkeypatch.setattr(kops, name, guarded(name, forced))
        m = run(olap_mode="ssi+rss", oltp_clients=3, olap_clients=2,
                rounds=600, olap_scan=True, paged_olap=True,
                check_scans=True, materialize=True, batch_plans=True,
                seed=5, **extra)
        runs.append((m, {k: REGISTRY.total(f"kernel_launch_{k}")
                         for k in _GUARD_STATS}))
    (m, stats), (m_dev, stats_dev) = runs
    assert checks and all(checks)
    assert stats == stats_dev
    assert m.olap_view_demotions > 0 and m.olap_view_hits > 0
    assert (m.olap_mode_flat, m.olap_mode_chunked, m.olap_mode_host) == \
        (m_dev.olap_mode_flat, m_dev.olap_mode_chunked, m_dev.olap_mode_host)
    assert m.olap_view_demotions == m_dev.olap_view_demotions
    assert m.olap_outputs == m_dev.olap_outputs
    if mode:
        assert m.olap_mode_chunked > 0
