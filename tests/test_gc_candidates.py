"""Version GC visits only the candidate chains (two or more versions).

Random installs through the three install paths — `Engine.commit`, a
replica's WAL replay (`ship_log`), and a direct `store.chain(k).install` —
interleave with prunes at random rising floors.  After every pass each
store must equal a plain full walk over a deep copy, version for version,
with the same count dropped, and its candidate set must be exactly the
chains holding two or more versions.
"""

import copy
import random

import pytest

from repro.mvcc.htap import MultiNodeHTAP
from repro.mvcc.store import Store, VersionChain

N_KEYS = 300


def _plain_prune(chains: dict, floor: int) -> int:
    """Full walk over version lists: keep, per key, the newest version at or
    below `floor` and everything after it."""
    dropped = 0
    for key, versions in chains.items():
        at_or_below = [i for i, v in enumerate(versions)
                       if v.commit_seq <= floor]
        i = at_or_below[-1] if at_or_below else 0
        chains[key] = versions[i:]
        dropped += i
    return dropped


def _assert_candidates_exact(store: Store) -> None:
    multi = {k for k, c in store.chains.items() if len(c.versions) >= 2}
    assert set(store.candidates) == multi
    assert all(store.candidates[k] is store.chains[k] for k in multi)


def _check_pass(store: Store, floor: int, prune) -> None:
    want = {k: c.versions for k, c in copy.deepcopy(store.chains).items()}
    want_dropped = _plain_prune(want, floor)
    assert prune(floor) == want_dropped
    assert {k: c.versions for k, c in store.chains.items()} == want
    _assert_candidates_exact(store)


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4])
def test_candidate_prune_equals_full_walk(seed):
    rng = random.Random(seed)
    keys = [f"k{i}" for i in range(N_KEYS)]
    hot = keys[:12]
    htap = MultiNodeHTAP("ssi+rss", n_replicas=1)
    eng, rep = htap.primary, htap.replica

    def pick():
        return rng.choice(hot) if rng.random() < 0.5 else rng.choice(keys)

    t = eng.begin()                       # initial load: every key once
    for k in keys:
        eng.write(t, k, 1)
    eng.commit(t)
    htap.ship_log()
    _assert_candidates_exact(eng.store)
    _assert_candidates_exact(rep.store)
    floor_p = floor_r = 0
    passes = 0
    for _ in range(400):
        r = rng.random()
        if r < 0.45:                      # engine commit
            t = eng.begin()
            for k in rng.sample(keys, rng.randint(1, 4)):
                eng.write(t, k, rng.randint(0, 99))
            eng.commit(t)
        elif r < 0.65:                    # direct install on the primary
            eng.store.chain(pick()).install(eng._tick(), 0, rng.randint(0, 99))
        elif r < 0.8:                     # WAL replay into the replica
            htap.ship_log()
        else:                             # a pass on each node
            floor_p = rng.randint(floor_p, eng.seq)
            floor_r = rng.randint(floor_r, rep.applied_seq)
            _check_pass(eng.store, floor_p, eng.prune_versions)
            _check_pass(rep.store, floor_r, rep.store.prune)
            passes += 1
    htap.ship_log()
    _check_pass(eng.store, eng.seq, eng.prune_versions)
    _check_pass(rep.store, rep.applied_seq, rep.store.prune)
    assert passes > 0
    assert not eng.store.candidates and not rep.store.candidates


def test_standalone_chain_keeps_no_candidate_record():
    ch = VersionChain()
    ch.install(1, 1, 10)
    ch.install(2, 2, 20)
    assert ch.prune(2) == 2
    assert [v.commit_seq for v in ch.versions] == [2]
