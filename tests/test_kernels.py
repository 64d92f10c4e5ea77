"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs jnp oracle."""

import functools

import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.paged_attention.kernel import (paged_attention,
                                                  paged_attention_chunk)
from repro.kernels.paged_attention.ref import (paged_attention_chunk_ref,
                                               paged_attention_ref)
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.ssd_scan.kernel import ssd_scan
from repro.kernels.ssd_scan.ref import ssd_scan_ref
from repro.kernels.rg_lru.kernel import rg_lru
from repro.kernels.rg_lru.ref import rg_lru_ref


def _tol(dt):
    return 1e-4 if dt == jnp.float32 else 6e-2


class TestPagedAttention:
    @pytest.mark.parametrize("B,H,KH,hd,psz,maxp,P,dt", [
        (4, 8, 2, 128, 16, 6, 64, jnp.float32),
        (2, 4, 4, 64, 8, 4, 32, jnp.float32),
        (3, 8, 1, 128, 32, 3, 16, jnp.bfloat16),
        (1, 16, 8, 64, 16, 5, 48, jnp.float32),
    ])
    def test_vs_ref(self, B, H, KH, hd, psz, maxp, P, dt):
        rng = np.random.RandomState(hash((B, H, KH)) % 2**31)
        q = jnp.asarray(rng.randn(B, H, hd), dt)
        kp = jnp.asarray(rng.randn(P, KH, psz, hd), dt)
        vp = jnp.asarray(rng.randn(P, KH, psz, hd), dt)
        lens = jnp.asarray(rng.randint(1, maxp * psz, B), jnp.int32)
        table = np.full((B, maxp), -1, np.int32)
        used = set()
        for b in range(B):
            for i in range(int(np.ceil(float(lens[b]) / psz))):
                pid = rng.randint(0, P)
                while pid in used:
                    pid = rng.randint(0, P)
                used.add(pid)
                table[b, i] = pid
        table = jnp.asarray(table)
        ref = paged_attention_ref(q, kp, vp, table, lens)
        out = paged_attention(q, kp, vp, table, lens, interpret=True)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=_tol(dt), rtol=_tol(dt))

    @settings(max_examples=8, deadline=None)
    @given(seed=st.integers(0, 2**16), psz=st.sampled_from([8, 16]),
           maxp=st.integers(2, 5))
    def test_property_random_tables(self, seed, psz, maxp):
        rng = np.random.RandomState(seed)
        B, H, KH, hd, P = 2, 4, 2, 64, 24
        q = jnp.asarray(rng.randn(B, H, hd), jnp.float32)
        kp = jnp.asarray(rng.randn(P, KH, psz, hd), jnp.float32)
        vp = jnp.asarray(rng.randn(P, KH, psz, hd), jnp.float32)
        lens = jnp.asarray(rng.randint(1, maxp * psz, B), jnp.int32)
        table = np.full((B, maxp), -1, np.int32)
        avail = list(range(P))
        rng.shuffle(avail)
        for b in range(B):
            for i in range(int(np.ceil(float(lens[b]) / psz))):
                table[b, i] = avail.pop()
        ref = paged_attention_ref(q, kp, vp, jnp.asarray(table), lens)
        out = paged_attention(q, kp, vp, jnp.asarray(table), lens,
                              interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-4, rtol=1e-4)


class TestPagedAttentionChunk:
    def _case(self, rng, B, T, H, KH, hd, psz, maxp, P, dt,
              ragged_base=True):
        hi = max((maxp - 1) * psz - T, 1)
        base = rng.randint(0, hi, B).astype(np.int32) if ragged_base \
            else np.zeros(B, np.int32)
        q = jnp.asarray(rng.randn(B, T, H, hd), dt)
        kp = jnp.asarray(rng.randn(P, KH, psz, hd), dt)
        vp = jnp.asarray(rng.randn(P, KH, psz, hd), dt)
        table = np.full((B, maxp), -1, np.int32)
        avail = list(range(P))
        rng.shuffle(avail)
        for b in range(B):
            for i in range(int(np.ceil((base[b] + T) / psz))):
                table[b, i] = avail.pop()
        return q, kp, vp, jnp.asarray(table), jnp.asarray(base)

    @pytest.mark.parametrize("B,T,H,KH,hd,psz,maxp,P,dt", [
        (3, 4, 8, 2, 64, 8, 5, 32, jnp.float32),
        (2, 8, 4, 4, 64, 8, 4, 32, jnp.float32),
        (2, 5, 8, 1, 128, 16, 3, 16, jnp.bfloat16),
        (1, 16, 16, 8, 64, 16, 4, 48, jnp.float32),
    ])
    def test_vs_ref(self, B, T, H, KH, hd, psz, maxp, P, dt):
        rng = np.random.RandomState(hash((B, T, H, KH)) % 2**31)
        q, kp, vp, table, base = self._case(rng, B, T, H, KH, hd, psz,
                                            maxp, P, dt)
        ref = paged_attention_chunk_ref(q, kp, vp, table, base)
        out = paged_attention_chunk(q, kp, vp, table, base, interpret=True)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=_tol(dt), rtol=_tol(dt))

    def test_t1_matches_decode_kernel(self):
        """The chunk kernel at T=1 equals the single-token decode path
        (query at position base attends to base + 1 tokens)."""
        rng = np.random.RandomState(11)
        B, H, KH, hd, psz, maxp, P = 3, 8, 2, 64, 8, 4, 24
        q, kp, vp, table, base = self._case(rng, B, 1, H, KH, hd, psz,
                                            maxp, P, jnp.float32)
        out = paged_attention_chunk(q, kp, vp, table, base, interpret=True)
        ref = paged_attention_ref(q[:, 0], kp, vp, table, base + 1)
        np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(ref),
                                   atol=1e-4, rtol=1e-4)

    def test_causal_within_chunk(self):
        """Row t must ignore chunk tokens written at positions > base+t:
        scrambling the future tokens' K/V leaves earlier rows unchanged."""
        rng = np.random.RandomState(12)
        B, T, H, KH, hd, psz, maxp, P = 1, 6, 4, 2, 64, 8, 3, 12
        q, kp, vp, table, base = self._case(rng, B, T, H, KH, hd, psz,
                                            maxp, P, jnp.float32,
                                            ragged_base=False)
        out1 = paged_attention_chunk_ref(q, kp, vp, table, base)
        # scramble K/V at absolute positions >= base + tcut
        tcut = 3
        kp2, vp2 = np.asarray(kp).copy(), np.asarray(vp).copy()
        tbl = np.asarray(table)
        for t in range(tcut, T):
            pos = int(base[0]) + t
            pid = tbl[0, pos // psz]
            kp2[pid, :, pos % psz] = 99.0
            vp2[pid, :, pos % psz] = -99.0
        out2 = paged_attention_chunk_ref(q, jnp.asarray(kp2),
                                         jnp.asarray(vp2), table, base)
        np.testing.assert_allclose(np.asarray(out1[:, :tcut]),
                                   np.asarray(out2[:, :tcut]),
                                   atol=1e-6, rtol=1e-6)
        assert not np.allclose(np.asarray(out1[:, tcut:]),
                               np.asarray(out2[:, tcut:]))

    def test_all_masked_row_outputs_zeros(self):
        """An idle slot (page table all -1, the engine runs every batch
        slot) must output exact zeros from kernel and ref alike — not a
        mean of the clamped fallback page's V."""
        rng = np.random.RandomState(13)
        B, T, H, KH, hd, psz, maxp, P = 2, 4, 4, 2, 64, 8, 3, 12
        q, kp, vp, table, base = self._case(rng, B, T, H, KH, hd, psz,
                                            maxp, P, jnp.float32)
        table = table.at[1].set(-1)          # slot 1: nothing resident
        base = base.at[1].set(0)
        ref = paged_attention_chunk_ref(q, kp, vp, table, base)
        out = paged_attention_chunk(q, kp, vp, table, base, interpret=True)
        assert np.all(np.asarray(ref[1]) == 0.0)
        assert np.all(np.asarray(out[1]) == 0.0)
        np.testing.assert_allclose(np.asarray(out[0]), np.asarray(ref[0]),
                                   atol=1e-4, rtol=1e-4)

    @settings(max_examples=8, deadline=None)
    @given(seed=st.integers(0, 2**16), psz=st.sampled_from([8, 16]),
           T=st.integers(1, 8))
    def test_property_random_chunks(self, seed, psz, T):
        rng = np.random.RandomState(seed)
        B, H, KH, hd, maxp, P = 2, 4, 2, 64, 4, 24
        q, kp, vp, table, base = self._case(rng, B, T, H, KH, hd, psz,
                                            maxp, P, jnp.float32)
        ref = paged_attention_chunk_ref(q, kp, vp, table, base)
        out = paged_attention_chunk(q, kp, vp, table, base, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-4, rtol=1e-4)


class TestStackedPool:
    """The serve step reads the stacked pool [L, DP, P, KH, psz, hd] in
    place: ``_paged_attn_chunk`` offsets shard d's page ids of layer l
    by (l*DP + d)*P into the merged stack.  Every layer and shard holds
    different data, so a wrong offset reads another layer's pages."""

    @pytest.mark.parametrize("kernel,T", [("chunk", 1), ("chunk", 64),
                                          ("verify", 5)])
    def test_layer_offset_reads_that_layers_pages(self, monkeypatch,
                                                  kernel, T):
        from repro.kernels.paged_attention import ops as paged_ops
        from repro.kernels.verify_attention import ops as verify_ops
        from repro.kernels.verify_attention.ref import verify_attention_ref
        from repro.models import transformer as tfm
        monkeypatch.setattr(tfm, "paged_attention_chunk", functools.partial(
            paged_ops.paged_attention_chunk, force="interpret"))
        monkeypatch.setattr(tfm, "verify_attention", functools.partial(
            verify_ops.verify_attention, force="interpret"))
        rng = np.random.RandomState(17 + T)
        L, DP, Bl, H, KH, hd, psz, maxp, P = 3, 2, 2, 4, 2, 64, 16, 6, 16
        layer = 2
        kst = jnp.asarray(rng.randn(L, DP, P, KH, psz, hd), jnp.float32)
        vst = jnp.asarray(rng.randn(L, DP, P, KH, psz, hd), jnp.float32)
        q = jnp.asarray(rng.randn(DP, Bl, T, H, hd), jnp.float32)
        base = rng.randint(0, maxp * psz - T, (DP, Bl)).astype(np.int32)
        tables = np.full((DP, Bl, maxp), -1, np.int32)
        for d in range(DP):
            avail = list(rng.permutation(P))
            for b in range(Bl):
                for i in range(-(-(base[d, b] + T) // psz)):
                    tables[d, b, i] = avail.pop()
        tables, base = jnp.asarray(tables), jnp.asarray(base)
        out = tfm._paged_attn_chunk(q, kst, vst, jnp.int32(layer), tables,
                                    base, verify=kernel == "verify")
        ref_fn = (verify_attention_ref if kernel == "verify"
                  else paged_attention_chunk_ref)
        for d in range(DP):
            ref = ref_fn(q[d], kst[layer, d], vst[layer, d], tables[d],
                         base[d])
            np.testing.assert_allclose(np.asarray(out[d]), np.asarray(ref),
                                       atol=1e-4, rtol=1e-4)

    @pytest.mark.parametrize("T", [1, 5, 8, 13])
    def test_chunk_write_touches_only_its_slots(self, T):
        """``_paged_write_chunk`` rewrites whole pages: every valid token
        of layer l lands in its page's slot, every other slot of every
        layer and shard keeps its value, and tokens past a lane's length
        or on an unmapped page are dropped."""
        from repro.models import transformer as tfm
        rng = np.random.RandomState(29 + T)
        L, DP, Bl, KH, hd, psz, maxp, P = 3, 2, 3, 2, 16, 8, 5, 16
        layer = 1
        kst = rng.randn(L, DP, P, KH, psz, hd).astype(np.float32)
        vst = rng.randn(L, DP, P, KH, psz, hd).astype(np.float32)
        kn = rng.randn(DP, Bl, T, KH, hd).astype(np.float32)
        vn = rng.randn(DP, Bl, T, KH, hd).astype(np.float32)
        base = rng.randint(0, maxp * psz - T + 1, (DP, Bl)).astype(np.int32)
        lens = rng.randint(1, T + 1, (DP, Bl))
        lens[0, 0] = 0                                   # an idle lane
        tables = np.full((DP, Bl, maxp), -1, np.int32)
        for d in range(DP):
            tables[d] = rng.permutation(P)[:Bl * maxp].reshape(Bl, maxp)
        tables[1, 2, (base[1, 2] + T - 1) // psz] = -1  # an unmapped page
        valid = np.arange(T)[None, None] < lens[..., None]
        k2, v2 = tfm._paged_write_chunk(
            jnp.asarray(kst), jnp.asarray(vst), jnp.int32(layer),
            jnp.asarray(kn), jnp.asarray(vn), jnp.asarray(tables),
            jnp.asarray(base), jnp.asarray(valid))
        want_k, want_v = kst.copy(), vst.copy()
        for d in range(DP):
            for b in range(Bl):
                for t in range(lens[d, b]):
                    pos = base[d, b] + t
                    pid = tables[d, b, pos // psz]
                    if pid >= 0:
                        want_k[layer, d, pid, :, pos % psz] = kn[d, b, t]
                        want_v[layer, d, pid, :, pos % psz] = vn[d, b, t]
        np.testing.assert_array_equal(np.asarray(k2), want_k)
        np.testing.assert_array_equal(np.asarray(v2), want_v)


class TestFlashAttention:
    @pytest.mark.parametrize("B,H,S,hd,bq,bk,dt", [
        (2, 3, 512, 64, 256, 256, jnp.float32),
        (1, 2, 256, 128, 128, 64, jnp.float32),
        (2, 2, 256, 128, 128, 128, jnp.bfloat16),
        (1, 1, 128, 64, 64, 128, jnp.float32),
    ])
    def test_vs_ref_causal(self, B, H, S, hd, bq, bk, dt):
        rng = np.random.RandomState(0)
        q = jnp.asarray(rng.randn(B, H, S, hd), dt)
        k = jnp.asarray(rng.randn(B, H, S, hd), dt)
        v = jnp.asarray(rng.randn(B, H, S, hd), dt)
        ref = flash_attention_ref(q, k, v, causal=True)
        out = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                              interpret=True)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            atol=_tol(dt), rtol=_tol(dt))

    def test_bidirectional(self):
        rng = np.random.RandomState(1)
        q = jnp.asarray(rng.randn(1, 2, 256, 64), jnp.float32)
        k = jnp.asarray(rng.randn(1, 2, 256, 64), jnp.float32)
        v = jnp.asarray(rng.randn(1, 2, 256, 64), jnp.float32)
        ref = flash_attention_ref(q, k, v, causal=False)
        out = flash_attention(q, k, v, causal=False, block_q=128,
                              block_k=128, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-4, rtol=1e-4)


class TestSSDScan:
    @pytest.mark.parametrize("B,S,H,P,N,chunk", [
        (2, 256, 4, 64, 32, 64),
        (1, 128, 2, 32, 64, 128),
        (1, 128, 1, 64, 128, 32),
    ])
    def test_vs_sequential_recurrence(self, B, S, H, P, N, chunk):
        rng = np.random.RandomState(2)
        x = jnp.asarray(rng.randn(B, S, H, P) * 0.5, jnp.float32)
        dt = jnp.asarray(rng.rand(B, S, H) * 0.5 + 0.1, jnp.float32)
        A = jnp.asarray(-np.abs(rng.randn(H)) * 0.5, jnp.float32)
        Bm = jnp.asarray(rng.randn(B, S, N) * 0.3, jnp.float32)
        Cm = jnp.asarray(rng.randn(B, S, N) * 0.3, jnp.float32)
        D = jnp.asarray(rng.randn(H) * 0.1, jnp.float32)
        yr, hr = ssd_scan_ref(x, dt, A, Bm, Cm, D)
        yk, hk = ssd_scan(x, dt, A, Bm, Cm, D, chunk=chunk, interpret=True)
        np.testing.assert_allclose(np.asarray(yk), np.asarray(yr),
                                   atol=2e-3, rtol=2e-3)
        np.testing.assert_allclose(np.asarray(hk.transpose(0, 1, 3, 2)),
                                   np.asarray(hr), atol=2e-3, rtol=2e-3)

    def test_matches_model_ssd_chunked(self):
        """The model-layer chunked SSD and the kernel agree."""
        from repro.models.ssm import ssd_chunked
        rng = np.random.RandomState(3)
        B, S, H, P, N = 1, 128, 2, 32, 16
        x = jnp.asarray(rng.randn(B, S, H, P) * 0.5, jnp.float32)
        dt = jnp.asarray(rng.rand(B, S, H) * 0.5 + 0.1, jnp.float32)
        A = jnp.asarray(-np.abs(rng.randn(H)) * 0.5, jnp.float32)
        Bm = jnp.asarray(rng.randn(B, S, N) * 0.3, jnp.float32)
        Cm = jnp.asarray(rng.randn(B, S, N) * 0.3, jnp.float32)
        D = jnp.asarray(rng.randn(H) * 0.1, jnp.float32)
        ym, hm = ssd_chunked(x, dt, A, Bm, Cm, D, chunk=64)
        yk, hk = ssd_scan(x, dt, A, Bm, Cm, D, chunk=64, interpret=True)
        np.testing.assert_allclose(np.asarray(yk), np.asarray(ym),
                                   atol=2e-3, rtol=2e-3)
        np.testing.assert_allclose(
            np.asarray(hk.transpose(0, 1, 3, 2)), np.asarray(hm),
            atol=2e-3, rtol=2e-3)


class TestRGLRU:
    @pytest.mark.parametrize("B,S,d", [(2, 256, 256), (1, 128, 512),
                                       (3, 128, 128)])
    def test_vs_sequential(self, B, S, d):
        rng = np.random.RandomState(4)
        a = jnp.asarray(rng.rand(B, S, d) * 0.9, jnp.float32)
        b = jnp.asarray(rng.randn(B, S, d) * 0.5, jnp.float32)
        hr, hfr = rg_lru_ref(a, b)
        hk, hfk = rg_lru(a, b, interpret=True)
        np.testing.assert_allclose(np.asarray(hk), np.asarray(hr),
                                   atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(np.asarray(hfk), np.asarray(hfr),
                                   atol=1e-4, rtol=1e-4)

    def test_initial_state(self):
        rng = np.random.RandomState(5)
        B, S, d = 2, 128, 256
        a = jnp.asarray(rng.rand(B, S, d) * 0.9, jnp.float32)
        b = jnp.asarray(rng.randn(B, S, d) * 0.5, jnp.float32)
        h0 = jnp.asarray(rng.randn(B, d), jnp.float32)
        hr, _ = rg_lru_ref(a, b, h0)
        hk, _ = rg_lru(a, b, h0, interpret=True)
        np.testing.assert_allclose(np.asarray(hk), np.asarray(hr),
                                   atol=1e-4, rtol=1e-4)
