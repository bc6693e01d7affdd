"""Per-kernel validation: shape/dtype sweeps vs the pure-jnp oracles
(interpret mode on CPU, per the assignment)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref
from repro.kernels.ckpt_codec import dequantize_blocks, quantize_blocks
from repro.kernels.decode_attention import decode_attention_bhd
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.rwkv6 import wkv6_bhsd


def _rand(rng, shape, dtype):
    x = rng.standard_normal(shape).astype(np.float32)
    return jnp.asarray(x, dtype)


ATTN_SHAPES = [
    # (BH, S, hd, blk_q, blk_k)
    (2, 64, 32, 32, 32),
    (4, 128, 64, 64, 32),
    (1, 256, 16, 64, 64),
    (3, 128, 128, 128, 128),
]


class TestFlashAttention:
    @pytest.mark.parametrize("bh,s,hd,bq,bk", ATTN_SHAPES)
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("causal", [True, False])
    def test_vs_oracle(self, bh, s, hd, bq, bk, dtype, causal):
        rng = np.random.default_rng(hash((bh, s, hd, str(dtype), causal)) % 2**31)
        q = _rand(rng, (bh, s, hd), dtype)
        k = _rand(rng, (bh, s, hd), dtype)
        v = _rand(rng, (bh, s, hd), dtype)
        got = flash_attention_bhsd(
            q, k, v, causal=causal, blk_q=bq, blk_k=bk, interpret=True
        )
        want = ref.flash_attention_ref(q, k, v, causal=causal)
        tol = 2e-6 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            atol=tol, rtol=tol,
        )

    def test_model_layout_wrapper(self):
        rng = np.random.default_rng(0)
        B, S, H, hd = 2, 64, 4, 32
        q = _rand(rng, (B, S, H, hd), jnp.float32)
        k = _rand(rng, (B, S, H, hd), jnp.float32)
        v = _rand(rng, (B, S, H, hd), jnp.float32)
        got = ops.flash_attention(q, k, v, blk_q=32, blk_k=32)
        qf = jnp.moveaxis(q, 2, 1).reshape(B * H, S, hd)
        kf = jnp.moveaxis(k, 2, 1).reshape(B * H, S, hd)
        vf = jnp.moveaxis(v, 2, 1).reshape(B * H, S, hd)
        want = jnp.moveaxis(
            ref.flash_attention_ref(qf, kf, vf).reshape(B, H, S, hd), 1, 2
        )
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-6)


class TestDecodeAttention:
    @pytest.mark.parametrize("bh,s,hd", [(2, 128, 32), (4, 256, 64), (1, 64, 128)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("pos_frac", [0.0, 0.3, 0.99])
    def test_vs_oracle(self, bh, s, hd, dtype, pos_frac):
        rng = np.random.default_rng(hash((bh, s, hd, pos_frac)) % 2**31)
        q = _rand(rng, (bh, hd), dtype)
        k = _rand(rng, (bh, s, hd), dtype)
        v = _rand(rng, (bh, s, hd), dtype)
        pos = jnp.asarray(int(pos_frac * (s - 1)), jnp.int32)
        got = decode_attention_bhd(q, k, v, pos, blk_k=32, interpret=True)
        want = ref.decode_attention_ref(q, k, v, pos)
        tol = 2e-6 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            atol=tol, rtol=tol,
        )


class TestWKV6:
    @pytest.mark.parametrize("bh,s,hd,chunk", [
        (2, 64, 16, 16), (1, 128, 32, 64), (3, 32, 64, 32), (2, 96, 16, 96),
    ])
    def test_vs_oracle(self, bh, s, hd, chunk):
        rng = np.random.default_rng(hash((bh, s, hd, chunk)) % 2**31)
        r = _rand(rng, (bh, s, hd), jnp.float32) * 0.3
        k = _rand(rng, (bh, s, hd), jnp.float32) * 0.3
        v = _rand(rng, (bh, s, hd), jnp.float32) * 0.3
        w = jnp.asarray(rng.uniform(0.001, 0.9999, (bh, s, hd)), jnp.float32)
        u = _rand(rng, (bh, hd), jnp.float32) * 0.1
        s0 = _rand(rng, (bh, hd, hd), jnp.float32) * 0.05
        y, sT = wkv6_bhsd(r, k, v, w, u, s0, chunk=chunk, interpret=True)
        y_ref, sT_ref = ref.wkv6_ref(r, k, v, w, u, s0)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-5)
        np.testing.assert_allclose(np.asarray(sT), np.asarray(sT_ref), atol=1e-5)

    def test_matches_model_ssm_path(self):
        """Kernel == models.ssm._wkv_scan (the model's exact scan)."""
        from repro.models.ssm import _wkv_scan

        rng = np.random.default_rng(5)
        B, S, H, hd = 2, 32, 2, 16
        r = _rand(rng, (B, S, H, hd), jnp.float32) * 0.3
        k = _rand(rng, (B, S, H, hd), jnp.float32) * 0.3
        v = _rand(rng, (B, S, H, hd), jnp.float32) * 0.3
        w = jnp.asarray(rng.uniform(0.01, 0.999, (B, S, H, hd)), jnp.float32)
        u = _rand(rng, (H, hd), jnp.float32) * 0.1
        s0 = jnp.zeros((B, H, hd, hd), jnp.float32)
        y_model, s_model = _wkv_scan(r, k, v, w, u, s0)
        y_kern, s_kern = ops.wkv6(r, k, v, w, u, s0, chunk=16)
        np.testing.assert_allclose(
            np.asarray(y_kern), np.asarray(y_model), atol=1e-5
        )
        np.testing.assert_allclose(
            np.asarray(s_kern), np.asarray(s_model), atol=1e-5
        )


class TestCkptCodecKernel:
    @pytest.mark.parametrize("nblocks,tile", [(4, 2), (16, 16), (8, 4)])
    @pytest.mark.parametrize("delta", [False, True])
    def test_vs_oracle(self, nblocks, tile, delta):
        rng = np.random.default_rng(nblocks * 100 + tile + delta)
        x = jnp.asarray(rng.standard_normal((nblocks, 256)), jnp.float32)
        prev = (
            x + jnp.asarray(rng.standard_normal((nblocks, 256)) * 1e-3, jnp.float32)
            if delta
            else None
        )
        q, s = quantize_blocks(x, prev, tile=tile, interpret=True)
        q_ref, s_ref = ref.quantize_ref(x, prev)
        np.testing.assert_array_equal(np.asarray(q), np.asarray(q_ref))
        np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), rtol=1e-6)
        back = dequantize_blocks(q, s, prev, tile=tile, interpret=True)
        want = ref.dequantize_ref(q_ref, s_ref, prev)
        np.testing.assert_allclose(np.asarray(back), np.asarray(want), rtol=1e-6)

    def test_host_codec_interop(self):
        """Kernel output decodes with the host (checkpoint/codec.py) layout."""
        rng = np.random.default_rng(9)
        x = rng.standard_normal(1000).astype(np.float32)
        q, s, n = ops.quantize_checkpoint(jnp.asarray(x))
        back = ops.dequantize_checkpoint(q, s, n, (1000,))
        assert np.abs(np.asarray(back) - x).max() < np.abs(x).max() / 127.0 + 1e-6


class TestCounterRNG:
    """The device trace generator's counter-based RNG primitives: the
    NumPy reference (core/events.py) and the jnp twins (kernels/
    sim_step.py) must agree bit-for-bit, and both must reproduce the
    published reference sequences."""

    #: Random123 known-answer vectors for Threefry-2x32, 20 rounds
    TF_KATS = [
        ((0, 0), (0, 0), (0x6B200159, 0x99BA4EFE)),
        (
            (0xFFFFFFFF, 0xFFFFFFFF),
            (0xFFFFFFFF, 0xFFFFFFFF),
            (0x1CB996FC, 0xBB002BE7),
        ),
        (
            (0x13198A2E, 0x03707344),
            (0x243F6A88, 0x85A308D3),
            (0xC4923A9C, 0x483DF7A0),
        ),
    ]

    #: SplitMix64 reference outputs for seed 0 (Vigna's splitmix64.c)
    SM_KATS = [0xE220A8397B1DCDAF, 0x6E789E6AA1B965F4, 0x06C45D188009454F]

    def test_threefry_known_answers(self):
        from repro.core import events as E

        for (k0, k1), (c0, c1), (w0, w1) in self.TF_KATS:
            x0, x1 = E.threefry2x32(k0, k1, c0, c1, rounds=20)
            assert (int(x0), int(x1)) == (w0, w1)

    def test_splitmix_known_answers(self):
        from repro.core import events as E

        for i, want in enumerate(self.SM_KATS):
            x0, x1 = E.splitmix64(np.uint64(0), np.int64(i))
            assert (int(x0) << 32) | int(x1) == want

    def test_numpy_vs_jnp_bit_equality(self):
        from repro.core import events as E
        from repro.kernels import sim_step as K

        rng = np.random.default_rng(3)
        k0, k1, c0, c1 = (
            rng.integers(0, 2**32, size=257, dtype=np.uint32) for _ in range(4)
        )
        for rounds in (13, 20):
            a = E.threefry2x32(k0, k1, c0, c1, rounds=rounds)
            b = K.threefry2x32(k0, k1, c0, c1, rounds=rounds)
            np.testing.assert_array_equal(a[0], np.asarray(b[0]))
            np.testing.assert_array_equal(a[1], np.asarray(b[1]))
        with jax.enable_x64(True):
            key = rng.integers(0, 2**64, size=129, dtype=np.uint64)
            ctr = rng.integers(0, 2**20, size=129).astype(np.int64)
            a = E.splitmix64(key, ctr)
            b = K.splitmix64(jnp.asarray(key), jnp.asarray(ctr))
            np.testing.assert_array_equal(a[0], np.asarray(b[0]))
            np.testing.assert_array_equal(a[1], np.asarray(b[1]))

    def test_pallas_stream_advance_matches_jnp(self):
        """The Pallas sampling kernel entry and the shared jnp body are
        bit-identical (interpret mode on CPU)."""
        from repro.core import events as E
        from repro.kernels import sim_step as K

        with jax.enable_x64(True):
            L = 256
            rng = np.random.default_rng(11)
            k0, k1 = E.stream_subkey_np(7, np.arange(L), E.STREAM_FAULT_GAP)
            key = K.stream_key(jnp.asarray(k0), jnp.asarray(k1), x64=True)
            mask = jnp.asarray(rng.random(L) < 0.7)
            ctr = jnp.asarray(rng.integers(0, 50, L), jnp.int32)
            tm = jnp.asarray(rng.random(L) * 1e5, jnp.float64)
            mean = jnp.full((L,), 6e4, jnp.float64)
            horizon = jnp.full((L,), 1e6, jnp.float64)
            for kind, param in [("exponential", 0.0), ("weibull", 0.7),
                                ("lognormal", 1.0), ("uniform", 0.0)]:
                a = K.stream_advance(
                    mask, ctr, tm, key, mean, horizon, kind=kind, param=param
                )
                b = K.masked_stream_advance(
                    mask, ctr, tm, key, mean, horizon, kind=kind, param=param,
                    interpret=True,
                )
                np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
                if kind == "lognormal":
                    # the two compilation paths may contract the
                    # transcendental chain (log/cos/exp) differently: ulp
                    np.testing.assert_allclose(
                        np.asarray(a[1]), np.asarray(b[1]), rtol=1e-12
                    )
                else:
                    np.testing.assert_array_equal(
                        np.asarray(a[1]), np.asarray(b[1])
                    )
