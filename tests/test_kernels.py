"""Pallas kernel validation: interpret-mode execution vs the pure-jnp
oracles in repro.kernels.ref, swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.folb_aggregate import TILE_D, folb_aggregate
from repro.kernels.ssm_scan import ssd_scan


def tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


class TestFlashAttention:
    @pytest.mark.parametrize("B,S,H,KV,d", [
        (1, 128, 2, 2, 64),      # MHA
        (2, 256, 4, 2, 64),      # GQA
        (1, 256, 4, 1, 64),      # MQA
        (2, 128, 2, 2, 128),     # wide head
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_causal_sweep(self, B, S, H, KV, d, dtype):
        ks = jax.random.split(jax.random.PRNGKey(B * S + H), 3)
        q = jax.random.normal(ks[0], (B, S, H, d), dtype)
        k = jax.random.normal(ks[1], (B, S, KV, d), dtype)
        v = jax.random.normal(ks[2], (B, S, KV, d), dtype)
        o = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                            interpret=True)
        o_ref = ref.flash_attention_ref(q, k, v, causal=True)
        err = float(jnp.max(jnp.abs(
            o.astype(jnp.float32) - o_ref.astype(jnp.float32))))
        assert err < tol(dtype), err

    @pytest.mark.parametrize("window", [64, 128, 192])
    def test_sliding_window(self, window):
        ks = jax.random.split(jax.random.PRNGKey(window), 3)
        q = jax.random.normal(ks[0], (2, 256, 2, 64))
        k = jax.random.normal(ks[1], (2, 256, 2, 64))
        v = jax.random.normal(ks[2], (2, 256, 2, 64))
        o = flash_attention(q, k, v, causal=True, sliding_window=window,
                            block_q=64, block_k=64, interpret=True)
        o_ref = ref.flash_attention_ref(q, k, v, causal=True,
                                        sliding_window=window)
        assert float(jnp.max(jnp.abs(o - o_ref))) < 2e-5

    def test_bidirectional(self):
        ks = jax.random.split(jax.random.PRNGKey(7), 3)
        q = jax.random.normal(ks[0], (1, 128, 2, 64))
        k = jax.random.normal(ks[1], (1, 128, 2, 64))
        v = jax.random.normal(ks[2], (1, 128, 2, 64))
        o = flash_attention(q, k, v, causal=False, block_q=64, block_k=64,
                            interpret=True)
        o_ref = ref.flash_attention_ref(q, k, v, causal=False)
        assert float(jnp.max(jnp.abs(o - o_ref))) < 2e-5

    def test_matches_model_attention_path(self):
        """Kernel vs the model's chunked-jnp attention (the hot path it
        replaces on TPU)."""
        from repro.configs import get_config
        from repro.models import attention as attn_lib
        cfg = get_config("starcoder2-7b").reduced()
        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        B, S, H, d = 2, 128, cfg.n_heads, cfg.resolved_head_dim
        q = jax.random.normal(ks[0], (B, S, H, d))
        k = jax.random.normal(ks[1], (B, S, cfg.n_kv_heads, d))
        v = jax.random.normal(ks[2], (B, S, cfg.n_kv_heads, d))
        mask = attn_lib.make_mask(cfg, S, S)
        o_model = attn_lib._attend(cfg, q, k, v, mask)
        o_kernel = flash_attention(q, k, v, causal=True,
                                   sliding_window=cfg.sliding_window,
                                   block_q=64, block_k=64, interpret=True)
        o_kernel = o_kernel.reshape(B, S, H * d)
        assert float(jnp.max(jnp.abs(o_model - o_kernel))) < 1e-4


class TestFolbAggregate:
    @pytest.mark.parametrize("K,D", [(2, TILE_D), (5, 2 * TILE_D),
                                     (8, 4 * TILE_D)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_sweep(self, K, D, dtype):
        ks = jax.random.split(jax.random.PRNGKey(K * D), 4)
        w = jax.random.normal(ks[0], (D,), dtype)
        deltas = (jax.random.normal(ks[1], (K, D)) * 0.1).astype(dtype)
        grads = jax.random.normal(ks[2], (K, D), dtype)
        g1 = jnp.mean(grads.astype(jnp.float32), 0)
        pg = jnp.abs(jax.random.normal(ks[3], (K,))) * 0.01
        g1sq = jnp.sum(g1 * g1)
        w2, s2 = folb_aggregate(w, deltas, grads, g1, pg, g1sq,
                                interpret=True)
        wr, sr = ref.folb_aggregate_ref(w, deltas, grads, g1, pg, g1sq)
        assert float(jnp.max(jnp.abs(
            w2.astype(jnp.float32) - wr.astype(jnp.float32)))) < tol(dtype)
        assert float(jnp.max(jnp.abs(s2 - sr) / (jnp.abs(sr) + 1))) < 1e-4

    def test_matches_core_aggregation(self):
        """Kernel result == repro.core.aggregation.folb_single_set on the
        same flattened problem."""
        from repro.core import aggregation
        K, D = 4, TILE_D
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        w = {"x": jax.random.normal(ks[0], (D,))}
        deltas = {"x": jax.random.normal(ks[1], (K, D)) * 0.1}
        grads = {"x": jax.random.normal(ks[2], (K, D))}
        expected = aggregation.folb_single_set(w, deltas, grads)
        g1 = jnp.mean(grads["x"], 0)
        got, _ = folb_aggregate(w["x"], deltas["x"], grads["x"], g1,
                                jnp.zeros((K,)), jnp.sum(g1 * g1),
                                interpret=True)
        assert float(jnp.max(jnp.abs(got - expected["x"]))) < 1e-4

    def test_tree_frontend(self):
        # fp32 buffers isolate the ravel/pad/unravel plumbing; the default
        # bf16 buffer dtype is covered by tests/test_flat.py
        from repro.kernels import ops
        from repro.core import aggregation
        key = jax.random.PRNGKey(1)
        w = {"a": jax.random.normal(key, (300,)),
             "b": jax.random.normal(jax.random.fold_in(key, 1), (7, 11))}
        K = 3
        deltas = jax.tree.map(
            lambda x: jax.random.normal(key, (K,) + x.shape) * 0.1, w)
        grads = jax.tree.map(
            lambda x: jax.random.normal(jax.random.fold_in(key, 2),
                                        (K,) + x.shape), w)
        got, _ = ops.folb_aggregate_tree(w, deltas, grads,
                                         buf_dtype=jnp.float32)
        exp = aggregation.folb_single_set(w, deltas, grads)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(exp)):
            assert np.allclose(np.asarray(a), np.asarray(b), atol=1e-4)

    @pytest.mark.parametrize("D", [TILE_D, 3 * TILE_D, 257 * TILE_D,
                                   4096 * TILE_D, 98_360 * TILE_D])
    def test_tile_unchanged_at_small_k(self, D):
        """K ≤ 16 keeps the D-only tile rule the kernels always used, so
        their results (reduction order included) are unchanged there."""
        from repro.kernels.folb_aggregate import _MAX_TILE_D, _pick_tile
        old = TILE_D
        while old < _MAX_TILE_D and D % (2 * old) == 0 and D // old > 256:
            old *= 2
        for K in range(1, 17):
            for itemsize in (2, 4):
                for n_streams in (1, 2):
                    assert _pick_tile(D, K, itemsize, n_streams) == old

    @pytest.mark.parametrize("K", [17, 64, 100, 128, 512, 2048])
    @pytest.mark.parametrize("itemsize,n_streams", [(2, 1), (4, 1), (2, 2),
                                                    (4, 2)])
    def test_tile_budget_at_large_k(self, K, itemsize, n_streams):
        from repro.kernels import folb_aggregate as F
        D = 1 << 22
        t = F._pick_tile(D, K, itemsize, n_streams)
        assert D % t == 0 and t >= F._MIN_TILE_D
        rows = -(-K // (32 // itemsize)) * (32 // itemsize)
        assert n_streams * 2 * rows * t * itemsize <= F._VMEM_BLOCK_BUDGET


class TestSSDScan:
    @pytest.mark.parametrize("S,P,N,chunk", [
        (64, 8, 8, 16), (128, 16, 8, 32), (256, 32, 16, 64)])
    def test_sweep(self, S, P, N, chunk):
        BH = 2
        ks = jax.random.split(jax.random.PRNGKey(S + P), 5)
        x = jax.random.normal(ks[0], (BH, S, P))
        loga = -jax.nn.softplus(jax.random.normal(ks[1], (BH, S)))
        w = jax.nn.sigmoid(jax.random.normal(ks[2], (BH, S)))
        Bm = jax.random.normal(ks[3], (BH, S, N))
        Cm = jax.random.normal(ks[4], (BH, S, N))
        y = ssd_scan(x, loga, w, Bm, Cm, chunk=chunk, interpret=True)
        for i in range(BH):
            yr, _ = ref.ssm_scan_ref(x[i][:, None], loga[i][:, None],
                                     w[i][:, None], Bm[i], Cm[i])
            assert float(jnp.max(jnp.abs(y[i] - yr[:, 0]))) < 1e-3

    def test_matches_model_ssd(self):
        """Kernel vs repro.models.ssm.ssd_chunked (the training path)."""
        from repro.models.ssm import ssd_chunked
        BH, S, P, N = 2, 128, 16, 8
        ks = jax.random.split(jax.random.PRNGKey(9), 5)
        x = jax.random.normal(ks[0], (BH, S, 1, P))   # B=BH, H=1
        loga = -jax.nn.softplus(jax.random.normal(ks[1], (BH, S, 1)))
        w = jax.nn.sigmoid(jax.random.normal(ks[2], (BH, S, 1)))
        Bm = jax.random.normal(ks[3], (BH, S, 1, N))
        Cm = jax.random.normal(ks[4], (BH, S, 1, N))
        y_model, _ = ssd_chunked(x, loga, w, Bm, Cm, chunk=32)
        y_kernel = ssd_scan(x[:, :, 0], loga[..., 0], w[..., 0],
                            Bm[:, :, 0], Cm[:, :, 0], chunk=32,
                            interpret=True)
        assert float(jnp.max(jnp.abs(y_model[:, :, 0] - y_kernel))) < 1e-3


class TestSLSTMScan:
    @pytest.mark.parametrize("S,chunk", [(32, 8), (64, 16), (128, 64)])
    def test_matches_model_cell(self, S, chunk):
        """Kernel vs repro.models.xlstm._slstm_cell scan."""
        from repro.configs import get_config
        from repro.kernels.slstm_scan import slstm_scan
        from repro.models import layers, xlstm as xl

        cfg = get_config("xlstm-1.3b").reduced()
        p = xl.init_slstm(cfg, jax.random.PRNGKey(0))
        B, d = 2, cfg.d_model
        x = jax.random.normal(jax.random.PRNGKey(S), (B, S, d)) * 0.3
        xg = layers.apply_linear(p["wx"], x)

        def step(carry, xg_t):
            h, c, n = carry
            h2, c2, n2 = xl._slstm_cell(cfg, p, xg_t, h, c, n)
            return (h2, c2, n2), h2

        zeros = jnp.zeros((B, d))
        _, hs = jax.lax.scan(step, (zeros, zeros, zeros),
                             jnp.moveaxis(xg, 1, 0))
        y_ref = jnp.moveaxis(hs, 0, 1)
        y = slstm_scan(xg, p["r"], cfg.n_heads, chunk=chunk, interpret=True)
        assert float(jnp.max(jnp.abs(y - y_ref))) < 1e-5
