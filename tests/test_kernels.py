"""Per-kernel shape/dtype sweeps against the pure-jnp oracles
(interpret mode executes the kernel bodies on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.kernels.version_gather.kernel import version_gather
from repro.kernels.version_gather.ref import version_gather_ref


class TestVersionGather:
    @pytest.mark.parametrize("P,K,E", [(8, 2, 256), (32, 4, 512),
                                       (16, 8, 128), (64, 3, 1024)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_shapes_dtypes(self, P, K, E, dtype):
        key = jax.random.PRNGKey(P * K)
        data = jax.random.normal(key, (P, K, E)).astype(dtype)
        ts = jax.random.randint(key, (P, K), 0, 50)
        for wm in (0, 13, 49):
            out = version_gather(data, ts, wm,
                                 block_pages=min(8, P),
                                 block_elems=min(256, E))
            ref = version_gather_ref(data, ts, wm)
            np.testing.assert_allclose(np.asarray(out, np.float32),
                                       np.asarray(ref, np.float32))

    @settings(max_examples=20, deadline=None)
    @given(seed=st.integers(0, 1000), wm=st.integers(0, 60))
    def test_property_matches_per_page_scan(self, seed, wm):
        """Against an independent per-page python oracle."""
        key = jax.random.PRNGKey(seed)
        P, K, E = 16, 4, 128
        data = jax.random.normal(key, (P, K, E), jnp.float32)
        ts = jax.random.randint(jax.random.fold_in(key, 1), (P, K), 0, 50)
        out = np.asarray(version_gather(data, ts, wm))
        tsn, datan = np.asarray(ts), np.asarray(data)
        for p in range(P):
            vis = [k for k in range(K) if tsn[p, k] <= wm]
            best = max(vis, key=lambda k: (tsn[p, k], -k)) if vis else \
                int(np.argmax(np.where(tsn[p] <= wm, tsn[p], -1)))
            np.testing.assert_allclose(out[p], datan[p, best])


class TestInterpretResolution:
    """`interpret=None` resolves from the backend at call time
    (`repro.kernels.config`): compiled on TPU, interpret mode elsewhere;
    an explicit argument always wins."""

    @pytest.mark.parametrize("backend,want", [
        ("cpu", True), ("gpu", True), ("tpu", False)])
    def test_default_follows_backend(self, monkeypatch, backend, want):
        from repro.kernels.config import default_interpret
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        assert default_interpret() is want

    @pytest.mark.parametrize("backend,want", [
        ("cpu", True), ("gpu", True), ("tpu", False)])
    def test_none_resolves_from_backend(self, monkeypatch, backend, want):
        from repro.kernels.config import resolve_interpret
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        assert resolve_interpret(None) is want

    @pytest.mark.parametrize("backend", ["cpu", "tpu"])
    @pytest.mark.parametrize("explicit", [True, False])
    def test_explicit_argument_wins(self, monkeypatch, backend, explicit):
        from repro.kernels.config import resolve_interpret
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        assert resolve_interpret(explicit) is explicit

    def test_no_environment_switch(self, monkeypatch):
        """No environment variable can steer the mode: on a TPU backend a
        leftover REPRO_INTERPRET=1 still resolves to compiled."""
        from repro.kernels.config import resolve_interpret
        monkeypatch.setenv("REPRO_INTERPRET", "1")
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert resolve_interpret(None) is False

    def test_resolved_at_call_time(self, monkeypatch):
        """The backend is read per call, never cached at import."""
        from repro.kernels.config import resolve_interpret
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert resolve_interpret(None) is False
        monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
        assert resolve_interpret(None) is True

    @pytest.mark.parametrize("op", ["scalar", "flat", "chunked", "delta"])
    def test_ops_default_matches_oracle(self, op):
        """Each serve-path op called with interpret=None (interpret mode
        on this CPU backend) matches its jnp oracle."""
        from repro.kernels.rss_scan_agg import ops as kops
        from repro.kernels.rss_scan_agg.ref import (
            rss_delta_fold_ref, rss_scan_agg_chunked_ref,
            rss_scan_agg_grouped_ref, rss_scan_agg_ref)
        rng = np.random.default_rng(0)
        P, G = 16, 3
        data = np.zeros((P, 2, 8), np.int32)
        data[:, :, 0] = 1
        data[:, :, 1] = rng.integers(-50, 50, (P, 2))
        ts = rng.integers(0, 9, (P, 2)).astype(np.int32)
        store = {"data": jnp.asarray(data), "ts": jnp.asarray(ts)}
        mem = jnp.asarray([6, 8], jnp.int32)
        gid = rng.integers(-1, G, (P, 1)).astype(np.int32)
        args = (store["data"], store["ts"])
        if op == "scalar":
            out = kops.snapshot_agg_members(
                store, mem, 5, tag_main=1, tag_alt=0,
                maxabs=kops.field_maxabs(store), interpret=None)
            ref = kops.fold_partials(
                rss_scan_agg_ref(*args, mem, 5, 1, 0))
        elif op == "flat":
            out = kops.snapshot_group_agg_members(
                store, gid, G, mem, 5, maxabs=kops.field_maxabs(store),
                interpret=None)
            ref = kops.fold_group_partials(rss_scan_agg_grouped_ref(
                *args, jnp.asarray(gid), mem, 5, n_groups=G))
        elif op == "chunked":
            out = kops.snapshot_group_agg_chunked(store, gid, G, mem, 5,
                                                  interpret=None)
            ref = kops.fold_group_partials(rss_scan_agg_chunked_ref(
                *args, jnp.asarray(gid), mem, 5, n_groups=G))
        else:
            acc = np.zeros((8, 128), np.int32)
            delta = np.zeros((8, 128), np.int32)
            delta[:, 0] = rng.integers(-1, 8, 8)
            delta[:, 1:6] = rng.integers(0, 2, (8, 5))
            out = np.asarray(kops.delta_fold(acc, delta, interpret=None))
            ref = np.asarray(rss_delta_fold_ref(jnp.asarray(acc),
                                                jnp.asarray(delta)))
            np.testing.assert_array_equal(out, ref)
            return
        assert out == ref
