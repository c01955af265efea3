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
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.decode_attention.kernel import decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.wkv_scan.kernel import wkv_scan
from repro.kernels.wkv_scan.ref import wkv_scan_ref

TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
       jnp.bfloat16: dict(rtol=3e-2, atol=3e-2)}


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


class TestFlashAttention:
    @pytest.mark.parametrize("B,H,K,S,hd", [(1, 4, 4, 128, 64),
                                            (2, 8, 2, 256, 64),
                                            (1, 6, 6, 192, 32),
                                            (2, 4, 1, 128, 128)])
    @pytest.mark.parametrize("causal,window", [(True, 0), (True, 64),
                                               (False, 0)])
    def test_shapes(self, B, H, K, S, hd, causal, window):
        key = jax.random.PRNGKey(B * S)
        q = jax.random.normal(key, (B, H, S, hd), jnp.float32)
        k = jax.random.normal(jax.random.fold_in(key, 1), (B, K, S, hd),
                              jnp.float32)
        v = jax.random.normal(jax.random.fold_in(key, 2), (B, K, S, hd),
                              jnp.float32)
        o = flash_attention(q, k, v, causal=causal, window=window,
                            block_q=64, block_k=64)
        r = attention_ref(q, k, v, causal=causal, window=window)
        np.testing.assert_allclose(o, r, **TOL[jnp.float32])

    def test_bf16(self):
        key = jax.random.PRNGKey(7)
        q = jax.random.normal(key, (1, 4, 128, 64)).astype(jnp.bfloat16)
        k = jax.random.normal(key, (1, 2, 128, 64)).astype(jnp.bfloat16)
        v = jax.random.normal(key, (1, 2, 128, 64)).astype(jnp.bfloat16)
        o = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
        r = attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(o, np.float32),
                                   np.asarray(r, np.float32),
                                   **TOL[jnp.bfloat16])

    def test_matches_model_attention_path(self):
        """The kernel agrees with the model's chunked-flash XLA path."""
        from repro.models.layers import flash_attention_xla
        key = jax.random.PRNGKey(3)
        q = jax.random.normal(key, (2, 128, 8, 64), jnp.float32)   # BSHD
        k = jax.random.normal(jax.random.fold_in(key, 1),
                              (2, 128, 2, 64), jnp.float32)
        v = jax.random.normal(jax.random.fold_in(key, 2),
                              (2, 128, 2, 64), jnp.float32)
        xla = flash_attention_xla(q, k, v, causal=True, chunk=64)
        pal = flash_attention(q.transpose(0, 2, 1, 3),
                              k.transpose(0, 2, 1, 3),
                              v.transpose(0, 2, 1, 3),
                              causal=True, block_q=64, block_k=64)
        np.testing.assert_allclose(xla.transpose(0, 2, 1, 3), pal,
                                   rtol=2e-5, atol=2e-5)


class TestDecodeAttention:
    @pytest.mark.parametrize("B,H,K,T,hd", [(2, 8, 2, 512, 64),
                                            (1, 4, 4, 256, 128),
                                            (4, 4, 1, 1024, 64)])
    def test_shapes(self, B, H, K, T, hd):
        key = jax.random.PRNGKey(T)
        q = jax.random.normal(key, (B, H, hd), jnp.float32)
        k = jax.random.normal(jax.random.fold_in(key, 1), (B, K, T, hd),
                              jnp.float32)
        v = jax.random.normal(jax.random.fold_in(key, 2), (B, K, T, hd),
                              jnp.float32)
        for vl in (1, T // 3, T):
            o = decode_attention(q, k, v, vl, block_t=128)
            r = decode_attention_ref(q, k, v, vl)
            np.testing.assert_allclose(o, r, **TOL[jnp.float32])


class TestWkvScan:
    @pytest.mark.parametrize("BH,T,N,chunk", [(2, 128, 64, 32),
                                              (4, 256, 64, 128),
                                              (1, 64, 32, 64)])
    def test_shapes(self, BH, T, N, chunk):
        key = jax.random.PRNGKey(T + N)
        r = jax.random.normal(key, (BH, T, N), jnp.float32) * 0.5
        k = jax.random.normal(jax.random.fold_in(key, 1), (BH, T, N)) * 0.5
        v = jax.random.normal(jax.random.fold_in(key, 2), (BH, T, N))
        w_log = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 3),
                                           (BH, T, N)) - 2)
        u = jax.random.normal(jax.random.fold_in(key, 4), (BH, N)) * 0.1
        o, S = wkv_scan(r, k, v, w_log, u, chunk=chunk)
        orf, Srf = wkv_scan_ref(r, k, v, w_log, u)
        np.testing.assert_allclose(o, orf, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(S, Srf, rtol=1e-4, atol=1e-4)

    def test_matches_model_rwkv_layer_scan(self):
        """Kernel recurrence == the model's associative-scan WKV."""
        from repro.models.layers import _wkv_chunked
        key = jax.random.PRNGKey(9)
        B, T, H, N = 2, 64, 2, 32
        shp = (B, T, H, N)
        r = jax.random.normal(key, shp) * 0.5
        k = jax.random.normal(jax.random.fold_in(key, 1), shp) * 0.5
        v = jax.random.normal(jax.random.fold_in(key, 2), shp)
        w_log = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 3), shp)
                         - 2)
        u = jax.random.normal(jax.random.fold_in(key, 4), (H, N)) * 0.1
        o_model, S_model = _wkv_chunked(r, k, v, w_log, u, chunk=16)
        flat = lambda x: x.transpose(0, 2, 1, 3).reshape(B * H, T, N)
        uf = jnp.broadcast_to(u[None], (B, H, N)).reshape(B * H, N)
        o_k, S_k = wkv_scan(flat(r), flat(k), flat(v), flat(w_log), uf,
                            chunk=32)
        np.testing.assert_allclose(
            o_k.reshape(B, H, T, N).transpose(0, 2, 1, 3), o_model,
            rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(S_k.reshape(B, H, N, N), S_model,
                                   rtol=1e-4, atol=1e-4)


class TestSsmScan:
    @pytest.mark.parametrize("Bb,T,Di,N,chunk", [(2, 64, 128, 8, 32),
                                                 (1, 128, 256, 16, 128)])
    def test_matches_oracle(self, Bb, T, Di, N, chunk):
        from repro.kernels.ssm_scan.kernel import ssm_scan
        from repro.kernels.ssm_scan.ref import ssm_scan_ref
        key = jax.random.PRNGKey(T + Di)
        u = jax.random.normal(key, (Bb, T, Di), jnp.float32)
        dt = jax.nn.softplus(
            jax.random.normal(jax.random.fold_in(key, 1), (Bb, T, Di)) - 1)
        B = jax.random.normal(jax.random.fold_in(key, 2), (Bb, T, N))
        C = jax.random.normal(jax.random.fold_in(key, 3), (Bb, T, N))
        A = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 4), (Di, N)))
        D = jax.random.normal(jax.random.fold_in(key, 5), (Di,))
        y, h = ssm_scan(u, dt, B, C, A, D, chunk=chunk, block_di=64)
        yr, hr = ssm_scan_ref(u, dt, B, C, A, D)
        np.testing.assert_allclose(y, yr, rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(h, hr, rtol=2e-4, atol=2e-4)

    def test_matches_model_mamba_chunked(self):
        """Kernel == the model's associative-scan formulation."""
        from repro.kernels.ssm_scan.kernel import ssm_scan
        from repro.models.layers import _mamba_scan_chunked
        key = jax.random.PRNGKey(11)
        Bb, T, Di, N = 2, 64, 64, 8
        u = jax.random.normal(key, (Bb, T, Di), jnp.float32)
        dt = jax.nn.softplus(
            jax.random.normal(jax.random.fold_in(key, 1), (Bb, T, Di)) - 1)
        B = jax.random.normal(jax.random.fold_in(key, 2), (Bb, T, N))
        C = jax.random.normal(jax.random.fold_in(key, 3), (Bb, T, N))
        A = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 4), (Di, N)))
        y_model, h_model = _mamba_scan_chunked(u, dt, B, C, A, chunk=32)
        y_k, h_k = ssm_scan(u, dt, B, C, A, jnp.zeros((Di,)), chunk=32,
                            block_di=64)
        np.testing.assert_allclose(y_k, y_model, rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(h_k, h_model, rtol=2e-4, atol=2e-4)


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
            out = kops.snapshot_agg_members(store, mem, 5, tag_main=1,
                                            tag_alt=0, interpret=None)
            ref = kops.fold_partials(
                rss_scan_agg_ref(*args, mem, 5, 1, 0))
        elif op == "flat":
            out = kops.snapshot_group_agg_members(store, gid, G, mem, 5,
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
