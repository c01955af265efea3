"""Every HTAP facade under every commit certifier, oracle-checked.

A certifier changes which OLTP transactions commit, and so the WAL that the
RSS manager, the paged mirror, the views and the replicas consume.  Each
case runs the driver with `olap_scan=True` and `check_scans=True`, so every
served plan is asserted equal to the per-key engine read path in-run, and
then checks the reader guarantee its facade promises:

  * RSS readers (single node and replicas) never abort and never wait;
  * SafeSnapshots readers wait instead of aborting;
  * plain SSI readers are certified, so only parity and progress hold.
"""

import pytest

from repro.mvcc import run_multi_node, run_single_node

FACADES = {
    "single-ssi": (run_single_node, dict(olap_mode="ssi")),
    "single-safesnapshots": (run_single_node,
                             dict(olap_mode="ssi+safesnapshots")),
    "single-rss": (run_single_node, dict(olap_mode="ssi+rss")),
    "single-rss-paged-views": (run_single_node,
                               dict(olap_mode="ssi+rss", paged_olap=True,
                                    materialize=True)),
    "multi-rss-1": (run_multi_node, dict(olap_mode="ssi+rss",
                                         n_replicas=1)),
    "multi-rss-paged-2": (run_multi_node,
                          dict(olap_mode="ssi+rss", paged_olap=True,
                               n_replicas=2)),
}
CERTIFIERS = {"conservative": "conservative-ssi",
              "commit-order": "commit-order-ssi", "ssn": "ssn"}


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("certifier", sorted(CERTIFIERS))
@pytest.mark.parametrize("facade", sorted(FACADES))
def test_facade_serves_oracle_answers_under_certifier(facade, certifier,
                                                      seed):
    run, kw = FACADES[facade]
    m = run(oltp_clients=4, olap_clients=2, rounds=400, seed=seed,
            olap_scan=True, check_scans=True, certifier=certifier, **kw)
    assert m.certifier == CERTIFIERS[certifier]   # threaded to the engine
    assert m.oltp_commits > 0
    assert m.olap_commits > 0
    assert m.olap_agg_steps > 0 and m.olap_group_steps > 0
    if kw["olap_mode"] == "ssi+rss":
        assert m.olap_aborts == 0 and m.olap_wait_rounds == 0
    elif kw["olap_mode"] == "ssi+safesnapshots":
        assert m.olap_aborts == 0
        assert m.olap_wait_rounds > 0     # deferred readers wait instead
    if kw.get("paged_olap"):
        # plans were served by the mirror's dense page-range path
        assert m.dense_range_hit_rate() > 0
        assert m.olap_agg_dispatches > 0
    if kw.get("materialize"):
        assert m.olap_view_hits > 0
