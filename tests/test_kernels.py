"""Pallas kernels vs pure-jnp oracles (interpret=True on CPU). Deterministic
cases only — the hypothesis shape/dtype sweeps live in
test_kernels_property.py so this module collects without hypothesis."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.gram_update import batched_gram_apply_pallas, \
    gram_apply_pallas


# ---------------------------------------------------------------------------
# gram_apply: V = X (X^T Q) / n
# ---------------------------------------------------------------------------
def test_gram_apply_padding_exact():
    """n not a multiple of block_n: zero-padding must not change the result."""
    x = jax.random.normal(jax.random.PRNGKey(0), (32, 513))
    q = jax.random.normal(jax.random.PRNGKey(1), (32, 8))
    out = ops.gram_apply(x, q, block_n=256, use_pallas=True)
    want = ref.gram_apply_ref(x, q)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-4,
                               atol=1e-5)


def test_gram_apply_kernel_direct():
    """Direct pallas_call path (no wrapper) on an aligned shape."""
    x = jax.random.normal(jax.random.PRNGKey(2), (128, 1024))
    q = jax.random.normal(jax.random.PRNGKey(3), (128, 128))
    v = gram_apply_pallas(x, q, block_n=256, interpret=True)
    want = ref.gram_apply_ref(x, q, normalize=False)
    np.testing.assert_allclose(np.asarray(v), np.asarray(want, np.float32),
                               rtol=1e-4, atol=1e-3)


def test_gram_apply_equals_explicit_covariance():
    """The kernel IS Step 5 of Alg. 1: X(X^T Q)/n == (XX^T/n) Q."""
    x = jax.random.normal(jax.random.PRNGKey(4), (24, 512))
    q = jax.random.normal(jax.random.PRNGKey(5), (24, 4))
    m = x @ x.T / x.shape[1]
    out = ops.gram_apply(x, q, use_pallas=True, block_n=256)
    np.testing.assert_allclose(np.asarray(out), np.asarray(m @ q), rtol=1e-4,
                               atol=1e-5)


# ---------------------------------------------------------------------------
# batched gram_apply: V[i] = X_i (X_i^T Q_i) / n_i, (node, col-block) grid
# ---------------------------------------------------------------------------
def test_batched_gram_apply_kernel_direct():
    """Direct pallas_call on aligned shapes: per-node results independent."""
    n_nodes, d, n, r = 3, 64, 512, 8
    key = jax.random.PRNGKey(11)
    kx, kq = jax.random.split(key)
    x = jax.random.normal(kx, (n_nodes, d, n))
    q = jax.random.normal(kq, (n_nodes, d, r))
    v = batched_gram_apply_pallas(x, q, block_n=256, interpret=True)
    for i in range(n_nodes):
        want = ref.gram_apply_ref(x[i], q[i], normalize=False)
        np.testing.assert_allclose(np.asarray(v[i]),
                                   np.asarray(want, np.float32),
                                   rtol=1e-4, atol=1e-3)


def test_batched_gram_apply_ragged_padding_exact():
    """Ragged n_i via zero padding must equal the per-node unpadded oracle."""
    rng = np.random.default_rng(0)
    n_true = np.array([300, 150, 512, 77])
    n_nodes, d, r = len(n_true), 32, 5
    n_max = int(n_true.max())
    x_stack = np.zeros((n_nodes, d, n_max), np.float32)
    for i, ni in enumerate(n_true):
        x_stack[i, :, :ni] = rng.standard_normal((d, ni))
    q = jnp.asarray(rng.standard_normal((n_nodes, d, r)), jnp.float32)
    out = ops.batched_gram_apply(jnp.asarray(x_stack), q,
                                 jnp.asarray(n_true, jnp.float32),
                                 block_n=256, use_pallas=True, interpret=True)
    for i, ni in enumerate(n_true):
        want = ref.gram_apply_ref(jnp.asarray(x_stack[i, :, :ni]), q[i])
        np.testing.assert_allclose(np.asarray(out[i]), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)


def test_batched_gram_apply_ref_fallback_matches_kernel():
    """CPU auto-dispatch (oracle) == explicit interpret-mode kernel."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((2, 16, 256)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((2, 16, 4)), jnp.float32)
    n_true = jnp.asarray([256.0, 200.0], jnp.float32)
    a = ops.batched_gram_apply(x, q, n_true, use_pallas=False)
    b = ops.batched_gram_apply(x, q, n_true, block_n=128, use_pallas=True,
                               interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                               atol=1e-5)


@pytest.mark.parametrize("n", [1, 127, 128, 500, 512, 513, 640, 662, 1024,
                               1500])
def test_gram_tiling_covers_n_in_lane_aligned_blocks(n):
    width, block_n = ops.gram_tiling(n)
    blocks = width // block_n
    assert width >= n
    assert width % 128 == 0 and block_n % 128 == 0
    assert width % block_n == 0
    assert block_n <= 512
    assert width < n + 128 * blocks
    # a stack built at the width tiles to itself, and an untiled one pads
    # to the same width
    assert ops.gram_tiling(width) == (width, block_n)
    assert -(-n // block_n) * block_n == width


def test_gram_tiling_of_the_lfw_row():
    assert ops.gram_tiling(662) == (768, 384)
    assert ops.gram_tiling(500) == (512, 512)


def _primitives(jaxpr):
    """Names of every primitive in ``jaxpr`` and the jaxprs inside it."""
    names = set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        for param in eqn.params.values():
            for sub in (param if isinstance(param, (list, tuple))
                        else [param]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    names |= _primitives(inner)
    return names


@pytest.mark.parametrize("n_true", [[662, 661, 662], [500, 431]],
                         ids=["two-blocks", "one-block"])
def test_batched_gram_apply_on_a_tiled_stack_pads_nothing(n_true):
    """A stack built at ``gram_tiling``'s width goes to the kernel as it
    is: no pad in the traced apply, and the per-node oracle's answer."""
    rng = np.random.default_rng(2)
    n_nodes, d, r = len(n_true), 16, 3
    width, _ = ops.gram_tiling(max(n_true))
    x_stack = np.zeros((n_nodes, d, width), np.float32)
    for i, ni in enumerate(n_true):
        x_stack[i, :, :ni] = rng.standard_normal((d, ni))
    x_stack = jnp.asarray(x_stack)
    q = jnp.asarray(rng.standard_normal((n_nodes, d, r)), jnp.float32)
    nt = jnp.asarray(n_true, jnp.float32)

    def apply(x, q, nt):
        return ops.batched_gram_apply(x, q, nt, use_pallas=True,
                                      interpret=True)

    jaxpr = jax.make_jaxpr(apply)(x_stack, q, nt).jaxpr
    prims = _primitives(jaxpr)
    assert "pallas_call" in prims
    assert "pad" not in prims
    # an untiled stack is still padded, on every call
    assert "pad" in _primitives(
        jax.make_jaxpr(apply)(x_stack[:, :, :max(n_true)], q, nt).jaxpr)
    out = apply(x_stack, q, nt)
    want = ref.batched_gram_apply_ref(x_stack, q, nt)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-4,
                               atol=2e-4)


@pytest.mark.parametrize("kernel", [False, True],
                         ids=["fallback", "pallas"])
def test_stack_data_width_follows_the_apply_path(monkeypatch, kernel):
    """``_stack_data`` builds n_max columns where the apply takes the
    oracle, and the kernel's tiled width where it takes the kernel; the
    columns past each block's n_i are zero either way."""
    from repro.core.sdot import _stack_data

    if kernel:
        monkeypatch.setattr(ops, "on_tpu", lambda: True)
    rng = np.random.default_rng(3)
    n_true, d, r = [662, 661, 640], 16, 7
    xs = [jnp.asarray(rng.standard_normal((d, ni)), jnp.float32)
          for ni in n_true]
    stack, nt, tiled = _stack_data(xs, r)
    assert tiled is kernel
    assert stack.shape == (len(xs), d, 768 if kernel else 662)
    np.testing.assert_array_equal(np.asarray(nt), n_true)
    for i, ni in enumerate(n_true):
        np.testing.assert_array_equal(np.asarray(stack[i, :, :ni]),
                                      np.asarray(xs[i]))
        assert not np.asarray(stack[i, :, ni:]).any()


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("window", [32, 64, 128])
def test_flash_attention_sliding_window(window):
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 256, 32))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 2, 256, 32))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 2, 256, 32))
    out = ops.flash_attention(q, k, v, causal=True, window=window,
                              use_pallas=True)
    want = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-5,
                               atol=2e-5)


def test_flash_attention_cross_lengths():
    """Decode-style: sq < skv, positions aligned at the end."""
    q = jax.random.normal(jax.random.PRNGKey(3), (1, 2, 128, 32))
    k = jax.random.normal(jax.random.PRNGKey(4), (1, 2, 384, 32))
    v = jax.random.normal(jax.random.PRNGKey(5), (1, 2, 384, 32))
    out = ops.flash_attention(q, k, v, causal=True, use_pallas=True)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-5,
                               atol=2e-5)


def test_flash_attention_small_falls_back():
    """Below one block the wrapper must use the oracle (still correct)."""
    q = jax.random.normal(jax.random.PRNGKey(6), (1, 2, 17, 16))
    k = jax.random.normal(jax.random.PRNGKey(7), (1, 2, 17, 16))
    v = jax.random.normal(jax.random.PRNGKey(8), (1, 2, 17, 16))
    out = ops.flash_attention(q, k, v, causal=True, use_pallas=True)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-5,
                               atol=1e-5)


def test_flash_attention_rows_sum_to_one_property():
    """Output of attention over constant V equals that constant (softmax
    weights sum to 1 — catches masking/normalization bugs)."""
    q = jax.random.normal(jax.random.PRNGKey(9), (1, 2, 256, 32))
    k = jax.random.normal(jax.random.PRNGKey(10), (1, 2, 256, 32))
    v = jnp.ones((1, 2, 256, 32))
    out = ops.flash_attention(q, k, v, causal=True, use_pallas=True)
    np.testing.assert_allclose(np.asarray(out), 1.0, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# gram_qr: G = V^T V (CholeskyQR hot matmul)
# ---------------------------------------------------------------------------
def test_gram_qr_matches_ref_aligned():
    v = jax.random.normal(jax.random.PRNGKey(12), (1536, 8))
    out = ops.gram_qr(v, block_d=512, use_pallas=True)
    want = ref.gram_qr_ref(v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-3)


def test_gram_qr_symmetric_psd():
    v = jax.random.normal(jax.random.PRNGKey(1), (2048, 16))
    g = np.asarray(ops.gram_qr(v, use_pallas=True))
    np.testing.assert_allclose(g, g.T, rtol=1e-6)
    assert np.linalg.eigvalsh(g).min() > -1e-3


# ---------------------------------------------------------------------------
# slab ops: Z[i] = X_i^T Q_i and V[i] = X_i S_i (fused F-DOT hot matmuls)
# ---------------------------------------------------------------------------
def test_batched_slab_tq_matches_ref():
    """(node, sample-block) kernel vs fused-einsum oracle, unaligned n."""
    key = jax.random.PRNGKey(21)
    kx, kq = jax.random.split(key)
    x = jax.random.normal(kx, (4, 8, 700))
    q = jax.random.normal(kq, (4, 8, 5))
    out = ops.batched_slab_tq(x, q, block_n=256, use_pallas=True,
                              interpret=True)
    want = ref.batched_slab_tq_ref(x, q)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-4,
                               atol=1e-4)


def test_batched_slab_apply_matches_ref():
    key = jax.random.PRNGKey(22)
    kx, ks = jax.random.split(key)
    x = jax.random.normal(kx, (4, 8, 700))
    s = jax.random.normal(ks, (4, 700, 5))
    out = ops.batched_slab_apply(x, s, block_n=256, use_pallas=True,
                                 interpret=True)
    want = ref.batched_slab_apply_ref(x, s)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-4,
                               atol=1e-3)


def test_slab_ops_zero_row_padding_exact():
    """Padded feature rows (ragged slabs stacked to d_max) stay null."""
    key = jax.random.PRNGKey(23)
    kx, kq = jax.random.split(key)
    x = jax.random.normal(kx, (2, 6, 512))
    q = jax.random.normal(kq, (2, 6, 3))
    x = x.at[1, 4:].set(0.0)        # node 1 has only 4 real features
    q = q.at[1, 4:].set(0.0)
    z = ops.batched_slab_tq(x, q, block_n=256, use_pallas=True,
                            interpret=True)
    want = ref.batched_slab_tq_ref(x[1:, :4], q[1:, :4])
    np.testing.assert_allclose(np.asarray(z[1]), np.asarray(want[0]),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# grid ops: Z[i,j] = X_ij^T Q_i and V[i,j] = X_ij S_j (fused B-DOT matmuls)
# ---------------------------------------------------------------------------
def test_grid_block_tq_matches_ref():
    """(row, column, sample-block) kernel vs fused-einsum oracle, unaligned n."""
    key = jax.random.PRNGKey(31)
    kx, kq = jax.random.split(key)
    x = jax.random.normal(kx, (3, 2, 8, 700))
    q = jax.random.normal(kq, (3, 8, 5))
    out = ops.grid_block_tq(x, q, block_n=256, use_pallas=True,
                            interpret=True)
    want = ref.grid_block_tq_ref(x, q)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-4,
                               atol=1e-4)


def test_grid_block_apply_matches_ref():
    key = jax.random.PRNGKey(32)
    kx, ks = jax.random.split(key)
    x = jax.random.normal(kx, (3, 2, 8, 700))
    s = jax.random.normal(ks, (2, 700, 5))
    out = ops.grid_block_apply(x, s, block_n=256, use_pallas=True,
                               interpret=True)
    want = ref.grid_block_apply_ref(x, s)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-4,
                               atol=1e-3)


def test_grid_ops_zero_padding_exact():
    """Padded feature rows AND sample columns of the (I, J) stack stay null
    (the fused B-DOT masking invariants)."""
    key = jax.random.PRNGKey(33)
    kx, kq, ks = jax.random.split(key, 3)
    x = jax.random.normal(kx, (2, 2, 6, 512))
    q = jax.random.normal(kq, (2, 6, 3))
    s = jax.random.normal(ks, (2, 512, 3))
    # block row 1 has 4 real features; grid column 1 has 400 real samples
    x = x.at[1, :, 4:].set(0.0)
    q = q.at[1, 4:].set(0.0)
    x = x.at[:, 1, :, 400:].set(0.0)
    s = s.at[1, 400:].set(0.0)
    z = ops.grid_block_tq(x, q, block_n=256, use_pallas=True, interpret=True)
    want = ref.grid_block_tq_ref(x[1:, 1:, :4, :400], q[1:, :4])
    np.testing.assert_allclose(np.asarray(z[1, 1, :400]),
                               np.asarray(want[0, 0]), rtol=1e-4, atol=1e-4)
    assert float(jnp.abs(z[1, 1, 400:]).max()) == 0.0
    v = ops.grid_block_apply(x, s, block_n=256, use_pallas=True,
                             interpret=True)
    want_v = ref.grid_block_apply_ref(x[1:, 1:, :4, :400], s[1:, :400])
    np.testing.assert_allclose(np.asarray(v[1, 1, :4]),
                               np.asarray(want_v[0, 0]), rtol=1e-4, atol=1e-3)
    assert float(jnp.abs(v[1, 1, 4:]).max()) == 0.0
