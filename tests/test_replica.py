"""WAL / replica behaviour."""

import pytest

from repro.core import RSSManager, PRoTManager, Wal, WalRecord, replicate


class TestWal:
    def test_roundtrip(self, tmp_path):
        wal = Wal()
        wal.log_begin(1)
        wal.log_commit(1, [("k", 5)])
        wal.log_deps(2, [1, 3])
        p = str(tmp_path / "wal.jsonl")
        wal.dump(p)
        wal2 = Wal.load(p)
        assert wal2.records == wal.records

    def test_tail_streams_increments(self):
        wal = Wal()
        wal.log_begin(1)
        assert len(list(wal.tail(0))) == 1
        assert len(list(wal.tail(1))) == 0
        wal.log_commit(1)
        assert len(list(wal.tail(1))) == 1

    def test_truncate_recycles_prefix(self):
        wal = Wal()
        for i in range(1, 5):
            wal.log_begin(i)
        assert wal.truncate(2) == 2
        assert wal.base_lsn == 2 and wal.head_lsn == 4
        assert [r.lsn for r in wal.tail(2)] == [3, 4]
        wal.log_begin(9)
        assert wal.records[-1].lsn == 5          # LSNs keep counting
        with pytest.raises(LookupError):
            list(wal.tail(1))                    # prefix is gone
        assert wal.truncate(99) == 3             # clamps at head

    def test_truncated_dump_load_roundtrip(self, tmp_path):
        wal = Wal()
        wal.log_begin(1); wal.log_commit(1, [("k", 5)]); wal.log_begin(2)
        wal.truncate(1)
        p = str(tmp_path / "wal.jsonl")
        wal.dump(p)
        wal2 = Wal.load(p)
        assert wal2.base_lsn == 1
        assert wal2.records == wal.records
        assert [r.lsn for r in wal2.tail(1)] == [2, 3]

    def test_fully_truncated_dump_load_keeps_lsn_clock(self, tmp_path):
        """A WAL truncated down to zero records must reload with its LSN
        clock intact — otherwise fresh appends reuse old LSNs and resumed
        consumers silently drop them via the idempotent-replay guard."""
        wal = Wal()
        wal.log_begin(1); wal.log_commit(1); wal.log_begin(2)
        wal.truncate(3)
        assert not wal.records and wal.head_lsn == 3
        p = str(tmp_path / "wal.jsonl")
        wal.dump(p)
        wal2 = Wal.load(p)
        assert wal2.base_lsn == 3 and wal2.head_lsn == 3
        assert wal2.log_begin(9).lsn == 4              # clock continues


class TestRSSManager:
    def test_idempotent_replay(self):
        wal = Wal()
        wal.log_begin(1); wal.log_commit(1)
        m = RSSManager()
        m.catch_up(wal)
        lsn = m.applied_lsn
        m.catch_up(wal)              # no-op
        assert m.applied_lsn == lsn
        for rec in wal.records:      # direct re-apply is also idempotent
            m.apply(rec)
        assert m.applied_lsn == lsn

    def test_batched_lag(self):
        wal = Wal()
        for i in range(1, 6):
            wal.log_begin(i); wal.log_commit(i)
        m = RSSManager()
        snap = replicate(wal, m, batch=3)
        assert m.applied_lsn == 3
        snap = replicate(wal, m)
        assert m.applied_lsn == 10
        # all five commits are Clear members, folded into the floor
        assert all(m.is_member(t, snap) for t in range(1, 6))
        assert snap.floor_seq == m.commit_seq[5]
        assert snap.txns == frozenset()      # nothing above the floor

    def test_active_txn_blocks_clear(self):
        wal = Wal()
        wal.log_begin(1)             # stays active
        wal.log_begin(2); wal.log_commit(2)
        m = RSSManager()
        m.catch_up(wal)
        assert m.clear() == set()    # T2 concurrent with active T1
        snap = m.construct()
        assert snap.txns == frozenset() and snap.floor_seq == 0
        assert not m.is_member(2, snap)

    def test_deps_pull_obscure_txn_into_rss(self):
        wal = Wal()
        wal.log_begin(1); wal.log_commit(1)           # T1 clear
        wal.log_begin(2)
        wal.log_begin(3)                              # active
        wal.log_commit(2)
        wal.log_deps(2, [1])                          # T2 -rw-> T1 (clear)
        m = RSSManager()
        m.catch_up(wal)
        assert m.clear() == {1}
        snap = m.construct()
        assert m.is_member(1, snap) and m.is_member(2, snap)
        # T2 is commit-seq contiguous with T1, so both fold into the floor
        assert snap.floor_seq == m.commit_seq[2]
        assert snap.txns == frozenset()

    def test_pulled_member_above_floor_stays_explicit(self):
        """A pulled member separated from the floor by a non-member keeps
        its id/seq in the compressed snapshot's above-floor set."""
        wal = Wal()
        wal.log_begin(1); wal.log_commit(1)           # T1 clear
        wal.log_begin(5)                              # active forever
        wal.log_begin(2); wal.log_commit(2)           # obscure, not pulled
        wal.log_begin(4); wal.log_commit(4)           # obscure...
        wal.log_deps(4, [1])                          # ...pulled via T1
        m = RSSManager()
        m.catch_up(wal)
        snap = m.construct()
        assert m.is_member(1, snap)
        assert not m.is_member(2, snap)               # gap non-member
        assert m.is_member(4, snap)
        assert snap.floor_seq == m.commit_seq[1]      # blocked by T2
        assert set(snap.txns) == {4}
        assert snap.member_seqs == (m.commit_seq[4],)

    def test_legacy_seq_fallback_never_regresses(self):
        """Mixing seq-stamped and legacy commit records must not mint a
        fallback seq that collides with or regresses below shipped seqs
        (a dense local clock corrupted floor_seq)."""
        wal = Wal()
        wal.log_begin(1); wal.log_commit(1, seq=7)    # shipped seq
        wal.log_begin(2); wal.log_commit(2)           # legacy record
        wal.log_begin(3); wal.log_commit(3, seq=12)
        wal.log_begin(4); wal.log_commit(4)           # legacy again
        m = RSSManager()
        m.catch_up(wal)
        assert m.commit_seq[2] == 8                   # max(seen) + 1, not 2
        assert m.commit_seq[4] == 13
        seqs = [m.commit_seq[t] for t in (1, 2, 3, 4)]
        assert seqs == sorted(seqs) and len(set(seqs)) == 4
        snap = m.construct()
        assert snap.floor_seq == 13                   # all Clear, monotone

    def test_stamped_seq_colliding_with_minted_fallback_is_bumped(self):
        """The converse collision: a legacy record mints max(seen)+1, then
        the primary ships that very seq for a later commit.  The shared
        clock (effective_commit_seq) re-stamps it strictly above everything
        seen, so an obscure non-member can never become floor-covered and
        all WAL consumers stay bit-identical."""
        from repro.tensorstore import PagedMirror
        wal = Wal()
        wal.log_begin(1); wal.log_commit(1, seq=7, writes=[("k", 1)])
        wal.log_begin(2); wal.log_commit(2, writes=[("k", 2)])  # minted 8
        wal.log_begin(9)                               # stays active
        wal.log_begin(3)
        wal.log_commit(3, seq=8, writes=[("k", 3)])    # primary's own 8!
        m = RSSManager()
        m.catch_up(wal)
        assert m.commit_seq[2] == 8
        assert m.commit_seq[3] == 9                    # bumped, no collision
        snap = m.construct()
        assert snap.floor_seq == 8                     # T1, T2 Clear
        # T3 is obscure (concurrent with active T9): must NOT be a member,
        # and in particular must not be floor-covered via the collision
        assert not m.is_member(3, snap)
        mirror = PagedMirror()
        mirror.catch_up(wal)
        assert mirror.commit_seq == m.commit_seq       # consumers agree
        assert mirror.read_members("k", snap) == 2     # T2's write, not T3's


class TestPRoTManager:
    def test_pin_release_gc_floor(self):
        wal = Wal()
        wal.log_begin(1); wal.log_commit(1)
        m = RSSManager(); m.catch_up(wal); m.construct()
        prot = PRoTManager(m)
        rid, snap = prot.acquire()
        assert snap.visible(1, m.commit_seq[1])   # floor-covered member
        assert m.is_member(1, snap)
        assert prot.gc_floor() == snap.lsn
        prot.release(rid)
        assert prot.pinned == 0


class TestRSSManagerGC:
    def test_state_pruned_below_pins_and_horizon(self):
        wal = Wal()
        for i in range(1, 51):
            wal.log_begin(i); wal.log_commit(i, seq=i)
        m = RSSManager(); m.catch_up(wal); m.construct()
        prot = PRoTManager(m)
        rid, pinned = prot.acquire()
        assert m.tracked_txns() == 50
        m.gc(keep_lsn=prot.gc_floor(), keep_seq=prot.gc_floor_seq())
        assert m.tracked_txns() == 0            # everything Clear + folded
        # pruned ids still answer membership via the floor
        assert all(m.is_member(t, pinned) for t in range(1, 51))
        prot.release(rid)

    def test_gc_preserves_pinned_visibility_and_future_construction(self):
        wal = Wal()
        wal.log_begin(1); wal.log_commit(1, seq=1)
        wal.log_begin(2)                              # long-running active
        wal.log_begin(3); wal.log_commit(3, seq=2)    # obscure (conc. w/ T2)
        m = RSSManager(); m.catch_up(wal)
        snap = m.construct()
        prot = PRoTManager(m)
        rid, _ = prot.acquire()
        m.gc(keep_lsn=prot.gc_floor(), keep_seq=prot.gc_floor_seq())
        assert 2 in m.begun and 3 in m.begun          # active+obscure kept
        assert 1 not in m.begun                       # clear member pruned
        # T3's deps edge into pruned-Clear T1 still pulls T3 in, even with
        # T2 active (T3 stays obscure: membership comes from the pull alone)
        wal.log_deps(3, [1])
        m.catch_up(wal)
        snap2 = m.construct()
        assert m.is_member(3, snap2)
        assert m.stats["edges_pruned_pull"] == 1
        assert snap2.floor_seq >= snap.floor_seq      # floor is monotone
