"""Pallas kernel sweeps: shapes x dtypes, interpret mode vs ref.py oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.gossip_gather import gossip_gather_pallas
from repro.kernels.pushsum_mix import pushsum_mix_pallas
from repro.kernels.rglru import rglru_pallas


# ---------------------------------------------------------------------------
# pushsum_mix
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("m,d", [(4, 64), (8, 100), (16, 513),
                                 (100, 777), (3, 2048),
                                 (7, 129), (13, 33), (9, 511)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pushsum_mix_sweep(m, d, dtype):
    key = jax.random.PRNGKey(m * 1000 + d)
    P = jax.random.dirichlet(key, jnp.ones((m,)), (m,))
    U = jax.random.normal(jax.random.fold_in(key, 1), (m, d)).astype(dtype)
    got = pushsum_mix_pallas(P, U, interpret=True)
    want = ref.pushsum_mix_ref(P, U)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)
    assert got.dtype == U.dtype


def test_pushsum_mix_row_stochastic_preserves_constant():
    """P row-stochastic => mixing a constant vector is the identity."""
    m = 16
    P = jax.random.dirichlet(jax.random.PRNGKey(0), jnp.ones((m,)), (m,))
    U = jnp.full((m, 256), 3.14159)
    got = pushsum_mix_pallas(P, U, interpret=True)
    np.testing.assert_allclose(np.asarray(got), 3.14159, rtol=1e-5)


# ---------------------------------------------------------------------------
# gossip_gather — the sparse neighbor-indexed mix (docs/gossip.md)
# ---------------------------------------------------------------------------
def _sparse_mix_inputs(m, k, d, dtype):
    key = jax.random.PRNGKey(m * 100 + k * 10 + d)
    idx = jax.random.randint(key, (m, k), 0, m, jnp.int32)
    w = jax.random.uniform(jax.random.fold_in(key, 1), (m, k))
    w = w / w.sum(1, keepdims=True)
    U = jax.random.normal(jax.random.fold_in(key, 2), (m, d)).astype(dtype)
    return idx, w, U


# m not a multiple of 8, d not a multiple of 512, k odd / k=1 edge
@pytest.mark.parametrize("m,k,d", [(5, 2, 64), (33, 4, 1100), (100, 11, 513),
                                   (8, 1, 512), (17, 3, 129), (64, 8, 2048)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gossip_gather_sweep(m, k, d, dtype):
    idx, w, U = _sparse_mix_inputs(m, k, d, dtype)
    got = gossip_gather_pallas(idx, w, U, interpret=True)
    want = ref.gossip_gather_ref(idx, w, U)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)
    assert got.dtype == U.dtype


def test_gossip_gather_row_stochastic_preserves_constant():
    """Row-stochastic weights => mixing a constant buffer is the identity."""
    idx, w, _ = _sparse_mix_inputs(16, 4, 384, jnp.float32)
    U = jnp.full((16, 384), 2.71828)
    got = gossip_gather_pallas(idx, w, U, interpret=True)
    np.testing.assert_allclose(np.asarray(got), 2.71828, rtol=1e-5)


def test_gossip_gather_matches_dense_matrix():
    """The kernel on a SparseTopology == the dense pushsum contraction."""
    from repro.core import topology
    topo = topology.directed_random(jax.random.PRNGKey(3), 12, 4)
    U = jax.random.normal(jax.random.PRNGKey(4), (12, 700))
    got = gossip_gather_pallas(topo.idx, topo.w, U, interpret=True)
    want = ref.pushsum_mix_ref(topo.dense(), U)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_gossip_gather_ops_dispatch():
    idx, w, U = _sparse_mix_inputs(9, 3, 260, jnp.float32)
    np.testing.assert_allclose(np.asarray(ops.gossip_gather(idx, w, U)),
                               np.asarray(ref.gossip_gather_ref(idx, w, U)),
                               rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(ops.gossip_gather(idx, w, U, force="pallas")),
        np.asarray(ref.gossip_gather_ref(idx, w, U)), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("kernel", ["gossip_gather", "gossip_scatter",
                                    "topk_gather"])
def test_smem_row_chunks_match_oracle(kernel, monkeypatch):
    """A neighbor or row table larger than one call's SMEM budget is split
    into row chunks; the chunked result equals the oracle."""
    from repro.kernels import gossip_gather, gossip_scatter, topk_gather
    m, k, d = 40, 3, 130
    idx, w, U = _sparse_mix_inputs(m, k, d, jnp.float32)
    # budgets of 16 rows: three chunks, the last one short
    monkeypatch.setattr(gossip_gather, "SMEM_ENTRIES", 16 * k)
    monkeypatch.setattr(gossip_scatter, "SMEM_ROWS", 16)
    if kernel == "gossip_gather":
        got = gossip_gather.gossip_gather_pallas(idx, w, U, interpret=True)
        want = ref.gossip_gather_ref(idx, w, U)
    elif kernel == "topk_gather":
        cols = jnp.tile(jnp.arange(0, d, 13, dtype=jnp.int32), (m, 1))
        vals = U[:, :cols.shape[1]]
        got = topk_gather.topk_gather_pallas(idx, w, vals, cols, d,
                                             interpret=True)
        want = ref.topk_gather_ref(idx, w, vals, cols, d)
    else:
        rows = jnp.arange(0, m, 1, dtype=jnp.int32)[::-1][:37]
        X = U[:37] * 2.0
        got = gossip_scatter.gossip_scatter_pallas(rows, X, U,
                                                   accumulate=True,
                                                   interpret=True)
        want = ref.gossip_scatter_ref(rows, X, U, accumulate=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,S,H,Hkv,hd,window", [
    (1, 128, 4, 4, 64, 0),      # MHA
    (2, 256, 4, 2, 64, 0),      # GQA 2:1
    (1, 256, 8, 1, 32, 0),      # MQA
    (1, 256, 4, 2, 64, 64),     # sliding window
    (1, 512, 2, 2, 128, 128),   # window = block
    (2, 128, 2, 1, 128, 96),    # window not multiple of block
])
def test_flash_attention_sweep(B, S, H, Hkv, hd, window):
    key = jax.random.PRNGKey(S + H)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, Hkv, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, Hkv, hd), jnp.float32)
    got = flash_attention_pallas(q, k, v, window=window, interpret=True,
                                 bq=64, bk=64)
    want = ref.flash_attention_ref(q, k, v, window=window)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("dtype", [jnp.bfloat16])
def test_flash_attention_bf16(dtype):
    key = jax.random.PRNGKey(7)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (1, 128, 4, 64)).astype(dtype)
    k = jax.random.normal(ks[1], (1, 128, 2, 64)).astype(dtype)
    v = jax.random.normal(ks[2], (1, 128, 2, 64)).astype(dtype)
    got = flash_attention_pallas(q, k, v, interpret=True, bq=64, bk=64)
    want = ref.flash_attention_ref(q, k, v)
    assert got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=3e-2, atol=3e-2)


def test_flash_matches_model_block_attention():
    """kernel == layers.block_attention == full-matrix ref (same math)."""
    from repro.models import layers as L
    key = jax.random.PRNGKey(3)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (1, 256, 4, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 256, 2, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 256, 2, 64), jnp.float32)
    a = flash_attention_pallas(q, k, v, interpret=True, bq=64, bk=64)
    b = L.block_attention(q, k, v, q_block=64)
    c = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(b, c, rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# rglru
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B,S,W", [(1, 256, 128), (2, 512, 128),
                                   (1, 1024, 256), (3, 256, 384)])
def test_rglru_sweep(B, S, W):
    key = jax.random.PRNGKey(B * S)
    ks = jax.random.split(key, 2)
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (B, S, W))) * 0.98
    b = jax.random.normal(ks[1], (B, S, W))
    got = rglru_pallas(a, b, interpret=True)
    want = ref.rglru_ref(a, b)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_rglru_matches_model_scan():
    """Kernel recurrence == hybrid.py's associative_scan core."""
    import jax.numpy as jnp
    key = jax.random.PRNGKey(5)
    ks = jax.random.split(key, 2)
    B, S, W = 2, 256, 128
    a = jax.nn.sigmoid(jax.random.normal(ks[0], (B, S, W))) * 0.99
    b = jax.random.normal(ks[1], (B, S, W))

    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, a2 * b1 + b2

    _, h_assoc = jax.lax.associative_scan(combine, (a, b), axis=1)
    h_kernel = rglru_pallas(a, b, interpret=True)
    np.testing.assert_allclose(h_kernel, h_assoc, rtol=2e-4, atol=2e-4)


def test_rglru_decay_bound():
    """|h_t| stays bounded by sup|b|/(1-sup a) — recurrence stability."""
    key = jax.random.PRNGKey(9)
    a = jnp.full((1, 512, 128), 0.9)
    b = jax.random.uniform(key, (1, 512, 128), minval=-1.0, maxval=1.0)
    h = rglru_pallas(a, b, interpret=True)
    assert float(jnp.abs(h).max()) <= 1.0 / (1 - 0.9) + 1e-3


# ---------------------------------------------------------------------------
# ops dispatch
# ---------------------------------------------------------------------------
def test_ops_dispatch_cpu_uses_ref():
    m, d = 8, 64
    P = jax.random.dirichlet(jax.random.PRNGKey(0), jnp.ones((m,)), (m,))
    U = jax.random.normal(jax.random.PRNGKey(1), (m, d))
    np.testing.assert_allclose(ops.pushsum_mix(P, U),
                               ref.pushsum_mix_ref(P, U), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(ops.pushsum_mix(P, U, force="pallas")),
        np.asarray(ref.pushsum_mix_ref(P, U)), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# loud-knob rule: every pallas-only knob raises off-pallas (ops.py)
# ---------------------------------------------------------------------------
def _knob_args():
    """Minimal valid argument tuples for every ops entry point."""
    key = jax.random.PRNGKey(7)
    m, k, d, dd = 4, 2, 8, 6
    P = jax.random.dirichlet(key, jnp.ones((m,)), (m,))
    U = jax.random.normal(jax.random.fold_in(key, 1), (m, d))
    idx = jnp.tile(jnp.arange(k, dtype=jnp.int32), (m, 1))
    w = jnp.full((m, k), 1.0 / k)
    vals = jax.random.normal(jax.random.fold_in(key, 2), (m, 3))
    cols = jnp.tile(jnp.arange(3, dtype=jnp.int32), (m, 1))
    uid = jnp.asarray([0, 2], jnp.int32)
    H = jax.random.normal(jax.random.fold_in(key, 3), (2, dd))
    W = jax.random.normal(jax.random.fold_in(key, 4), (m, dd, 3))
    bias = jnp.zeros((m, 3))
    qkv = jax.random.normal(jax.random.fold_in(key, 5), (1, 4, 1, 4))
    ab = jax.random.uniform(jax.random.fold_in(key, 6), (1, 4, dd),
                            minval=0.1, maxval=0.9)
    return {
        "pushsum_mix": (ops.pushsum_mix, (P, U), ("block_d",)),
        "gossip_gather": (ops.gossip_gather, (idx, w, U),
                          ("block_m", "block_d")),
        "gossip_scatter": (ops.gossip_scatter, (uid, U[:2], U),
                           ("block_m", "block_d")),
        "topk_gather": (ops.topk_gather, (idx, w, vals, cols, d),
                        ("block_m", "block_d")),
        "head_gather_matmul": (ops.head_gather_matmul, (uid, H, W, bias),
                               ("block_b", "block_n")),
        "flash_attention": (ops.flash_attention, (qkv, qkv, qkv),
                            ("bq", "bk")),
        "rglru": (ops.rglru, (ab, ab), ("bs", "bw")),
    }


@pytest.mark.parametrize("op", ["pushsum_mix", "gossip_gather",
                                "gossip_scatter", "topk_gather",
                                "head_gather_matmul", "flash_attention",
                                "rglru"])
def test_every_pallas_knob_raises_off_pallas(op):
    fn, base, knobs = _knob_args()[op]
    # the bare ref dispatch works...
    fn(*base, force="ref")
    for knob in knobs:
        # ...but any pallas-only knob on it raises, naming the knob
        with pytest.raises(ValueError, match=knob):
            fn(*base, force="ref", **{knob: 8})
