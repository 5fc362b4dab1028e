"""Per-kernel interpret=True validation vs pure-jnp oracles (shape/dtype sweeps)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.cycle_gain import cycle_gain_padded, cycle_gain_ref
from repro.kernels.embedding_bag import embedding_bag_padded, embedding_bag_ref
from repro.kernels.flash_attention import attention_ref, flash_attention
from repro.kernels.flash_attention.ops import attention

# ----------------------------- cycle_gain ---------------------------------


@pytest.mark.parametrize("m,n", [(64, 128), (256, 256), (300, 200), (8, 640)])
@pytest.mark.parametrize("density", [0.1, 0.5, 1.0])
def test_cycle_gain_matches_ref(m, n, density):
    rng = np.random.default_rng(m * 1000 + n + int(density * 10))
    a = rng.uniform(0.1, 1.0, (m, n)) * (rng.random((m, n)) < density)
    a2 = rng.uniform(0.1, 1.0, (m, n)) * (rng.random((m, n)) < density)
    u = rng.uniform(0.0, 1.0, m).astype(np.float32)
    v = rng.uniform(0.0, 1.0, n).astype(np.float32)
    a = jnp.asarray(a, jnp.float32)
    a2 = jnp.asarray(a2, jnp.float32)
    gk, rk = cycle_gain_padded(a, a2, jnp.asarray(u), jnp.asarray(v),
                               tm=128, tn=128)
    gr, rr = cycle_gain_ref(a, a2, jnp.asarray(u), jnp.asarray(v))
    np.testing.assert_allclose(np.array(gk), np.array(gr), rtol=1e-6)
    np.testing.assert_array_equal(np.array(rk), np.array(rr))


def test_cycle_gain_tiling_invariance():
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.uniform(0.1, 1, (384, 384)) * (rng.random((384, 384)) < 0.3),
                    jnp.float32)
    a2 = jnp.asarray(rng.uniform(0.1, 1, (384, 384)) * (rng.random((384, 384)) < 0.3),
                     jnp.float32)
    u = jnp.asarray(rng.uniform(0, 1, 384), jnp.float32)
    v = jnp.asarray(rng.uniform(0, 1, 384), jnp.float32)
    g1, r1 = cycle_gain_padded(a, a2, u, v, tm=128, tn=128)
    g2, r2 = cycle_gain_padded(a, a2, u, v, tm=384, tn=384)
    np.testing.assert_allclose(np.array(g1), np.array(g2), rtol=1e-6)
    np.testing.assert_array_equal(np.array(r1), np.array(r2))


def test_cycle_gain_empty_columns():
    a = jnp.zeros((64, 128), jnp.float32)
    a2 = jnp.zeros((64, 128), jnp.float32)
    g, r = cycle_gain_padded(a, a2, jnp.zeros(64), jnp.zeros(128))
    assert np.all(np.array(r) == -1)
    assert np.all(np.isneginf(np.array(g)))


# --------------------------- flash attention -------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("b,h,hkv,s,d", [
    (1, 4, 4, 256, 64),
    (2, 8, 2, 256, 64),   # GQA 4:1
    (1, 2, 1, 512, 128),  # MQA
])
def test_flash_attention_matches_ref(b, h, hkv, s, d, causal, dtype):
    rng = np.random.default_rng(42)
    q = jnp.asarray(rng.normal(size=(b, h, s, d)), dtype)
    k = jnp.asarray(rng.normal(size=(b, hkv, s, d)), dtype)
    v = jnp.asarray(rng.normal(size=(b, hkv, s, d)), dtype)
    o = flash_attention(q, k, v, causal=causal, tq=128, tk=128)
    o_ref = attention_ref(q, k, v, causal=causal)
    rtol, atol = (2e-2, 2e-2) if dtype == jnp.bfloat16 else (2e-5, 2e-5)
    np.testing.assert_allclose(np.array(o, np.float32), np.array(o_ref, np.float32),
                               rtol=rtol, atol=atol)


def test_flash_attention_grad_path():
    # custom_vjp recompute backward matches full-jnp grads
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, 2, 256, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 2, 256, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 2, 256, 64)), jnp.float32)
    qm = jnp.swapaxes(q, 1, 2)
    km = jnp.swapaxes(k, 1, 2)
    vm = jnp.swapaxes(v, 1, 2)

    def loss_kernel(q, k, v):
        return attention(q, k, v, causal=True, use_kernel=True).sum()

    def loss_ref(q, k, v):
        return attention(q, k, v, causal=True, use_kernel=False).sum()

    gk = jax.grad(loss_kernel, argnums=(0, 1, 2))(qm, km, vm)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(qm, km, vm)
    for a, b_ in zip(gk, gr):
        np.testing.assert_allclose(np.array(a), np.array(b_), rtol=1e-4, atol=1e-4)


# ---------------------------- embedding bag --------------------------------


@pytest.mark.parametrize("b,l,v,d", [(16, 8, 1024, 64), (8, 32, 600, 32),
                                     (33, 5, 2000, 128)])
def test_embedding_bag_matches_ref(b, l, v, d):
    rng = np.random.default_rng(b + l)
    idx = rng.integers(-1, v, (b, l)).astype(np.int32)  # -1 = padding
    w = rng.uniform(0, 1, (b, l)).astype(np.float32)
    table = rng.normal(size=(v, d)).astype(np.float32)
    out = embedding_bag_padded(jnp.asarray(idx), jnp.asarray(w), jnp.asarray(table),
                               tb=8, tv=256)
    ref = embedding_bag_ref(jnp.asarray(idx), jnp.asarray(w), jnp.asarray(table))
    np.testing.assert_allclose(np.array(out), np.array(ref), rtol=1e-5, atol=1e-5)


def test_embedding_bag_all_padding():
    idx = jnp.full((8, 4), -1, jnp.int32)
    w = jnp.ones((8, 4), jnp.float32)
    table = jnp.ones((256, 16), jnp.float32)
    out = embedding_bag_padded(idx, w, table, tb=8, tv=256)
    np.testing.assert_array_equal(np.array(out), 0.0)


# ---------------------------- router swap ----------------------------------


@pytest.mark.parametrize("t,e", [(128, 8), (300, 60), (512, 64)])
def test_router_swap_matches_ref(t, e):
    from repro.kernels.router_swap import router_swap_padded, router_swap_ref

    rng = np.random.default_rng(t + e)
    aff = jnp.asarray(rng.normal(size=(t, e)), jnp.float32)
    assign = jnp.asarray(rng.integers(0, e, t), jnp.int32)
    cur = jnp.take_along_axis(aff, assign[:, None], axis=1)[:, 0]
    gk, rk = router_swap_padded(aff, assign, cur, ti=128, tj=128)
    gr, rr = router_swap_ref(aff, assign, cur)
    np.testing.assert_allclose(np.array(gk), np.array(gr), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_array_equal(np.array(rk), np.array(rr))


def test_router_swap_mutual_best_consistency():
    """The kernel's winners drive the same mutual-best swaps as the XLA path
    in moe.swap_improve."""
    from repro.kernels.router_swap import router_swap_ref

    rng = np.random.default_rng(0)
    t, e = 64, 8
    aff = jnp.asarray(rng.normal(size=(t, e)), jnp.float32)
    assign = jnp.asarray(rng.integers(0, e, t), jnp.int32)
    cur = jnp.take_along_axis(aff, assign[:, None], axis=1)[:, 0]
    g, bp = router_swap_ref(aff, assign, cur)
    tok = np.arange(t)
    bp_np = np.array(bp)
    mutual = (bp_np[bp_np[tok]] == tok) & (np.array(g) > 1e-6)
    # mutual-best pairs must be symmetric
    for i in np.nonzero(mutual)[0]:
        assert mutual[bp_np[i]]


# ------------------- execution mode (non-TPU != interpreter) ----------------


def test_resolve_execution_per_platform(monkeypatch):
    from repro.kernels import backend as kbackend

    for plat, expect in [("cpu", True), ("tpu", False), ("gpu", False),
                         ("cuda", False), ("rocm", False)]:
        monkeypatch.setattr(jax, "default_backend", lambda p=plat: p)
        mode = kbackend.resolve_execution(None)
        assert mode.interpret is expect, (plat, mode)
        assert mode.platform == plat
        assert mode.forced is False
        assert mode.describe() == f"interpret={expect}"


def test_resolve_execution_explicit_wins_and_is_recorded(monkeypatch):
    from repro.kernels import backend as kbackend

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert kbackend.resolve_interpret(True) is True
    last = kbackend.last_execution()
    assert last.forced is True and last.interpret is True
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert kbackend.resolve_interpret(False) is False
    assert kbackend.last_execution().forced is True


def test_kernel_wrappers_record_last_execution(monkeypatch):
    from repro.kernels import backend as kbackend

    monkeypatch.setattr(kbackend, "_LAST_MODE", None)
    # a shape no other test traces, so the wrapper resolves its mode anew
    a = jnp.ones((24, 40), jnp.float32)
    cycle_gain_padded(a, a, jnp.zeros(24), jnp.zeros(40))
    last = kbackend.last_execution()
    assert last is not None
    assert last.platform == jax.default_backend()
    expect = jax.default_backend() not in kbackend.COMPILED_PLATFORMS
    assert last.interpret is expect
