"""Pallas kernel correctness: shape/dtype sweeps vs the pure-jnp oracles.

Kernels execute under ``interpret=True`` on CPU (the TPU BlockSpec path run
in Python), asserted allclose against ``ref.py``.  Hypothesis drives random
shapes; fixed sweeps cover the MXU-aligned and the ragged/padded cases.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st  # optional-hypothesis shim

from repro.kernels.quadconv import kernel as K, ops
from repro.kernels.quadconv import quadconv_contract, quadconv_contract_ref
from repro.ml.quadconv import QuadConv


def _rand(key, *shape, dtype=jnp.float32):
    return jax.random.normal(key, shape).astype(dtype) * 0.3


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _g(key, J, I, O, C, dtype=jnp.float32):
    """A kernel tensor drawn as [J, I, O, C], in the contraction's layout
    [J, O*C, I]."""
    return _rand(key, J, I, O, C, dtype=dtype).transpose(0, 2, 3, 1) \
        .reshape(J, O * C, I)


@pytest.mark.parametrize("B,I,C,J,O", [
    (1, 16, 4, 8, 8),        # tiny
    (4, 96, 4, 48, 16),      # paper-ish channels
    (2, 128, 16, 128, 16),   # MXU-aligned K and N
    (3, 50, 3, 17, 5),       # ragged everything (exercises padding)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quadconv_kernel_sweep(B, I, C, J, O, dtype):
    ks = jax.random.split(jax.random.key(0), 3)
    f = _rand(ks[0], B, I, C, dtype=dtype)
    w = jax.random.uniform(ks[1], (I,)).astype(dtype)
    g = _g(ks[2], J, I, O, C, dtype=dtype)
    ref = quadconv_contract_ref(f, w, g)
    out = quadconv_contract(f, w, g, "interpret")
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=TOL[dtype], rtol=TOL[dtype])


@settings(max_examples=10, deadline=None)
@given(st.integers(1, 4), st.integers(1, 40), st.integers(1, 6),
       st.integers(1, 24), st.integers(1, 8))
def test_quadconv_kernel_property(B, I, C, J, O):
    ks = jax.random.split(jax.random.key(B * 1000 + I), 3)
    f = _rand(ks[0], B, I, C)
    w = jax.random.uniform(ks[1], (I,))
    g = _g(ks[2], J, I, O, C)
    ref = quadconv_contract_ref(f, w, g)
    out = quadconv_contract(f, w, g, "interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


def test_quadconv_kernel_grads_match_ref():
    ks = jax.random.split(jax.random.key(1), 3)
    f = _rand(ks[0], 2, 32, 4)
    w = jax.random.uniform(ks[1], (32,))
    g = _g(ks[2], 16, 32, 8, 4)

    def loss(f, w, g, mode):
        return jnp.sum(quadconv_contract(f, w, g, mode) ** 2)

    g_ref = jax.grad(loss, argnums=(0, 1, 2))(f, w, g, "ref")
    g_int = jax.grad(loss, argnums=(0, 1, 2))(f, w, g, "interpret")
    for a, b in zip(g_ref, g_int):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_quadconv_linearity():
    """Contraction is linear in f: K(af1 + bf2) == aK(f1) + bK(f2)."""
    ks = jax.random.split(jax.random.key(2), 4)
    f1, f2 = _rand(ks[0], 2, 24, 4), _rand(ks[1], 2, 24, 4)
    w = jax.random.uniform(ks[2], (24,))
    g = _g(ks[3], 12, 24, 8, 4)
    lhs = quadconv_contract(2.0 * f1 + 3.0 * f2, w, g, "interpret")
    rhs = 2.0 * quadconv_contract(f1, w, g, "interpret") \
        + 3.0 * quadconv_contract(f2, w, g, "interpret")
    np.testing.assert_allclose(np.asarray(lhs), np.asarray(rhs), atol=1e-4)


# The autoencoder's layer shapes scaled down (points J = I; channels 4->16
# and 16->16) and sizes that pad: the batch to 8, the rows O*C to a
# multiple of 8, the input points to whole blocks of 128.
QC_LAYERS = [
    pytest.param(4, 256, 4, 16, id="enc0-4to16"),
    pytest.param(4, 128, 16, 16, id="16to16"),
    pytest.param(1, 64, 16, 16, id="16to16-batch1"),
    pytest.param(3, 40, 3, 5, id="ragged-rows"),
]


def _layer(B, P, C, O, key=5):
    ks = jax.random.split(jax.random.key(key), 4)
    return (_rand(ks[0], B, P, C), jax.random.uniform(ks[1], (P,)),
            _g(ks[2], P, P, O, C), _rand(ks[3], B, P, O))


@pytest.mark.parametrize("B,P,C,O", QC_LAYERS)
def test_quadconv_vjp_matches_oracle(B, P, C, O):
    """Forward, df, dw and dG of the kernels against autodiff of the
    einsum oracle, for one cotangent."""
    f, w, g, ct = _layer(B, P, C, O)
    outs = {}
    for mode in ("ref", "interpret"):
        out, vjp = jax.vjp(lambda f, w, g, m=mode: quadconv_contract(
            f, w, g, m), f, w, g)
        outs[mode] = (out, *vjp(ct))
    for name, a, b in zip(("out", "df", "dw", "dG"), outs["ref"],
                          outs["interpret"]):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=1e-4,
                                   rtol=1e-4, err_msg=name)


@pytest.mark.parametrize("B,P,C,O,bj,bi", [
    pytest.param(2, 256, 16, 16, 4, 128, id="16to16-multiblock"),
    pytest.param(3, 256, 4, 16, 2, 128, id="enc0-multiblock"),
])
def test_quadconv_kernels_over_many_blocks(B, P, C, O, bj, bi):
    """Each kernel accumulates or tiles over several blocks on both points
    axes, with blocks smaller than the entry point would choose."""
    f, w, g, ct = _layer(B, P, C, O, key=6)
    bp, width = 8, 128
    fw = jnp.pad((f * w[:, None]).transpose(2, 0, 1), ((0, 0), (0, bp - B),
                                                      (0, 0)))
    fwr = jnp.pad(fw.reshape(C * bp, P), ((0, width - C * bp), (0, 0)))
    ctr = jnp.pad(jnp.repeat(ct, C, axis=-1), ((0, bp - B), (0, 0), (0, 0)))
    ctr = ctr.reshape(bp, P * O * C)
    kw = dict(bj=bj, bi=bi, interpret=True)
    z = K.quadconv_matmul(g, fwr.T, c=C, bp=bp, **kw)
    out = z.reshape(bp, P, O, C).sum(-1)[:B]
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(quadconv_contract_ref(f, w, g)),
                               atol=1e-4)
    _, vjp = jax.vjp(quadconv_contract_ref, f, w, g)
    _, _, dg_ref = vjp(ct)
    q = K.quadconv_bwd_q(g, ctr, c=C, w=width, **kw)
    q = q[:C * bp].reshape(C, bp, P)[:, :B].transpose(1, 2, 0)
    np.testing.assert_allclose(
        np.asarray(q * w[:, None]), np.asarray(vjp(ct)[0]), atol=1e-4)
    dg = K.quadconv_bwd_dg(ctr, fwr, c=C, shape=g.shape, dtype=g.dtype, **kw)
    np.testing.assert_allclose(np.asarray(dg), np.asarray(dg_ref), atol=1e-4)


def test_quadconv_blocks_follow_the_shapes():
    """Whole rows of input points, then output points up to 4 MiB of G,
    with every block tiling-legal: the autoencoder's layers (1,024 and 256
    points, 16 and 4 input channels) and a layer too wide for one row."""
    assert ops.blocks(1024, 256, 1024) == (4, 1024)
    assert ops.blocks(1024, 64, 1024) == (16, 1024)
    assert ops.blocks(256, 256, 256) == (16, 256)
    assert ops.blocks(8192, 256, 8192) == (1, 4096)
    for j, r, i in [(1024, 256, 1024), (300, 64, 5000), (17, 15, 50)]:
        bj, bi = ops.blocks(j, r, i)
        assert bj * r * bi <= ops.BLOCK_ELEMS or bj == 1
        assert (bj * r) % 128 == 0 or bj == j
        assert bi == i or bi % 128 == 0


def test_quadconv_grad_through_kernel_tensor_matches_ref():
    """jax.grad of a loss through the filter MLP's kernel tensor and the
    contraction: the kernels' custom VJP against autodiff of the oracle."""
    conv_k = QuadConv(c_in=4, c_out=8, mlp_width=16, mlp_depth=3,
                      mode="interpret")
    conv_r = QuadConv(c_in=4, c_out=8, mlp_width=16, mlp_depth=3, mode="ref")
    ks = jax.random.split(jax.random.key(7), 3)
    params = conv_k.init(ks[0], 48)
    coords = jax.random.uniform(ks[1], (48, 3))
    f = _rand(ks[2], 2, 48, 4)

    def loss(p, f, conv):
        return jnp.sum(jnp.square(conv.apply(p, f, coords, coords)))

    gk = jax.grad(loss, argnums=(0, 1))(params, f, conv_k)
    gr = jax.grad(loss, argnums=(0, 1))(params, f, conv_r)
    for a, b in zip(jax.tree.leaves(gk), jax.tree.leaves(gr)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4,
                                   rtol=1e-4)


# ---------------------------------------------------------------------------
# Flash attention kernel
# ---------------------------------------------------------------------------

from repro.kernels.attention import mha, mha_ref


@pytest.mark.parametrize("B,S,H,K,dh,causal", [
    (1, 128, 2, 2, 64, True),       # MHA
    (2, 256, 4, 2, 64, True),       # GQA 2:1
    (1, 128, 8, 2, 128, True),      # GQA 4:1, wide head
    (1, 128, 4, 4, 64, False),      # bidirectional (encoder)
    (1, 384, 2, 1, 64, True),       # MQA, 3 kv blocks
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, S, H, K, dh, causal, dtype):
    ks = jax.random.split(jax.random.key(B * S + H), 3)
    q = (jax.random.normal(ks[0], (B, S, H, dh)) * 0.5).astype(dtype)
    k = (jax.random.normal(ks[1], (B, S, K, dh)) * 0.5).astype(dtype)
    v = (jax.random.normal(ks[2], (B, S, K, dh)) * 0.5).astype(dtype)
    ref = mha_ref(q, k, v, causal)
    out = mha(q, k, v, causal, "interpret")
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=TOL[dtype], rtol=TOL[dtype])


def test_flash_attention_grads():
    ks = jax.random.split(jax.random.key(9), 3)
    q = jax.random.normal(ks[0], (1, 128, 2, 64)) * 0.5
    k = jax.random.normal(ks[1], (1, 128, 2, 64)) * 0.5
    v = jax.random.normal(ks[2], (1, 128, 2, 64)) * 0.5
    g1 = jax.grad(lambda q_: jnp.sum(mha(q_, k, v, True, "interpret") ** 2))(q)
    g2 = jax.grad(lambda q_: jnp.sum(mha_ref(q_, k, v, True) ** 2))(q)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-5)


def test_flash_attention_long_context_numerics():
    """Streaming softmax stays exact over many KV blocks."""
    ks = jax.random.split(jax.random.key(11), 3)
    q = jax.random.normal(ks[0], (1, 512, 1, 64))
    k = jax.random.normal(ks[1], (1, 512, 1, 64))
    v = jax.random.normal(ks[2], (1, 512, 1, 64))
    ref = mha_ref(q, k, v, True)
    out = mha(q, k, v, True, "interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5)


# ---------------------------------------------------------------------------
# SSD intra-chunk kernel
# ---------------------------------------------------------------------------

from repro.kernels.ssd import ssd_scan
from repro.models.ssd import ssd_scan_ref


@pytest.mark.parametrize("B,S,H,P,N,Q", [
    (1, 16, 2, 4, 8, 8),
    (2, 32, 4, 8, 16, 8),
    (1, 64, 8, 16, 32, 16),     # multi head-block
    (2, 24, 6, 8, 16, 8),       # H not a multiple of default blk_h
])
def test_ssd_kernel_sweep(B, S, H, P, N, Q):
    ks = jax.random.split(jax.random.key(B * 100 + S), 4)
    xdt = jax.random.normal(ks[0], (B, S, H, P)) * 0.5
    a = -jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    b = jax.random.normal(ks[2], (B, S, N)) * 0.5
    c = jax.random.normal(ks[3], (B, S, N)) * 0.5
    y_ref, h_ref = ssd_scan_ref(xdt, a, b, c)
    blk = H if H % 2 else 2
    from repro.kernels.ssd.ops import ssd_scan as scan
    y, h = scan(xdt, a, b, c, chunk=Q, mode="interpret")
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=3e-5)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref), atol=3e-5)


@settings(max_examples=6, deadline=None)
@given(st.integers(1, 2), st.integers(1, 4), st.integers(1, 3),
       st.integers(1, 3))
def test_ssd_kernel_property(B, nc, h2, p2):
    H, P, N, Q = 2 * h2, 4 * p2, 8, 8
    S = nc * Q
    ks = jax.random.split(jax.random.key(B * 7 + S), 4)
    xdt = jax.random.normal(ks[0], (B, S, H, P)) * 0.5
    a = -jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    b = jax.random.normal(ks[2], (B, S, N)) * 0.5
    c = jax.random.normal(ks[3], (B, S, N)) * 0.5
    y_ref, h_ref = ssd_scan_ref(xdt, a, b, c)
    y, h = ssd_scan(xdt, a, b, c, chunk=Q, mode="interpret")
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=3e-5)


@pytest.mark.parametrize("B,S,H,K,dh,causal", [
    (1, 128, 2, 2, 64, True),       # MHA causal
    (2, 256, 4, 2, 64, True),       # GQA (group-summed dk/dv)
    (1, 128, 4, 4, 64, False),      # bidirectional
    (1, 384, 2, 1, 64, True),       # MQA, 3 kv blocks
])
def test_flash_attention_bwd_kernel(B, S, H, K, dh, causal):
    """Pallas FA-2 backward == oracle VJP (dq, dk, dv)."""
    ks = jax.random.split(jax.random.key(B * S + H), 4)
    q = jax.random.normal(ks[0], (B, S, H, dh)) * 0.5
    k = jax.random.normal(ks[1], (B, S, K, dh)) * 0.5
    v = jax.random.normal(ks[2], (B, S, K, dh)) * 0.5
    ct = jax.random.normal(ks[3], (B, S, H, dh)) * 0.5
    g1 = jax.grad(lambda *a: jnp.sum(mha(*a, causal, "interpret") * ct),
                  argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda *a: jnp.sum(mha_ref(*a, causal) * ct),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)
