"""Pallas kernels vs pure-jnp oracles — shape/dtype sweeps (interpret mode)."""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.kernels import collision_force as k1, ops, ref

# ---------------------------------------------------------------------------
# K1: collision force
# ---------------------------------------------------------------------------

ADH = ((0.5, 0.1), (0.1, 0.7))


def _k1_case(rng, n, c, dims, box, adhesion, active_frac=1.0):
    # keep diameters small enough that box >= max interaction distance
    max_dia = box - 0.45
    pos = rng.uniform(0, dims[0] * box * 0.99, (n, 3)).astype(np.float32)
    dia = rng.uniform(0.4, max_dia, (n,)).astype(np.float32)
    typ = rng.integers(0, 2, (n,)).astype(np.int32)
    P = np.zeros((c, 3), np.float32); P[:n] = pos
    D = np.zeros((c,), np.float32); D[:n] = dia
    T = np.zeros((c,), np.int32); T[:n] = typ
    alive = np.zeros((c,), bool); alive[:n] = True
    active = alive.copy()
    if active_frac < 1.0:
        active[:n] = rng.random(n) < active_frac
    f, nnz, ovf = ops.collision_force(
        jnp.asarray(P), jnp.asarray(D), jnp.asarray(T), jnp.asarray(alive),
        jnp.asarray(active), jnp.zeros(3), jnp.asarray(box),
        dims=dims, k_rep=2.0, adhesion=adhesion, adhesion_band=0.4)
    assert not bool(ovf)
    fr, nr = ref.collision_force_ref(
        jnp.asarray(P), jnp.asarray(D), jnp.asarray(T), jnp.asarray(alive),
        2.0, adhesion, 0.4)
    # reference restricted to active rows (inactive rows are not computed)
    fr = jnp.where(jnp.asarray(active)[:, None], fr, 0.0)
    nr = jnp.where(jnp.asarray(active), nr, 0)
    return f, nnz, fr, nr


@pytest.mark.parametrize("n,c,dims,box,adhesion", [
    (60, 128, (8, 8, 8), 2.0, None),
    (200, 256, (10, 10, 10), 2.0, ADH),
    (500, 512, (12, 12, 12), 1.5, ADH),
    (128, 128, (6, 6, 6), 3.0, None),     # capacity == n (no padding slots)
    (1, 128, (8, 8, 8), 2.0, None),       # single agent: zero force
])
def test_collision_force_matches_ref(rng, n, c, dims, box, adhesion):
    f, nnz, fr, nr = _k1_case(rng, n, c, dims, box, adhesion)
    np.testing.assert_allclose(np.asarray(f), np.asarray(fr), atol=1e-4)
    np.testing.assert_array_equal(np.asarray(nnz), np.asarray(nr))


def test_collision_force_static_rows_skipped(rng):
    """Inactive (static) rows get zero output but still push active neighbors."""
    f, nnz, fr, nr = _k1_case(rng, 300, 384, (10, 10, 10), 2.0, ADH,
                              active_frac=0.5)
    np.testing.assert_allclose(np.asarray(f), np.asarray(fr), atol=1e-4)
    np.testing.assert_array_equal(np.asarray(nnz), np.asarray(nr))


@settings(max_examples=10, deadline=None)
@given(st.integers(2, 150), st.integers(0, 10_000))
def test_collision_force_property(n, seed):
    rng = np.random.default_rng(seed)
    f, nnz, fr, nr = _k1_case(rng, n, ((n + 127) // 128) * 128, (8, 8, 8), 2.5,
                              None)
    np.testing.assert_allclose(np.asarray(f), np.asarray(fr), atol=1e-4)


def test_collision_force_newton(rng):
    """Σ forces = 0 (momentum conservation) when all agents are active."""
    f, nnz, fr, nr = _k1_case(rng, 256, 256, (8, 8, 8), 2.5, ADH)
    np.testing.assert_allclose(np.asarray(f).sum(0), np.zeros(3), atol=1e-3)


def test_collision_force_multi_launch_bit_exact(rng, monkeypatch):
    """The column table cut into several kernel launches — how it stays in
    SMEM at large capacity — gives the single launch's bits, with the pool
    padded to whole launches."""
    n, c = 500, 640                                  # 5 row blocks
    pos = rng.uniform(0, 12 * 1.5 * 0.99, (n, 3)).astype(np.float32)
    P = np.zeros((c, 3), np.float32); P[:n] = pos
    D = np.zeros((c,), np.float32); D[:n] = rng.uniform(0.4, 1.0, n)
    T = np.zeros((c,), np.int32); T[:n] = rng.integers(0, 2, n)
    alive = np.zeros((c,), bool); alive[:n] = True
    args = tuple(jnp.asarray(a) for a in (P, D, T, alive, alive)) + (
        jnp.zeros(3), jnp.asarray(1.5))
    kw = dict(dims=(12, 12, 12), k_rep=2.0, adhesion=ADH, adhesion_band=0.4)
    one = ops.collision_force(*args, **kw)
    # two row blocks per launch: 5 blocks pad to 6, run as 3 launches
    monkeypatch.setattr(k1, "SMEM_TABLE_ENTRIES", 2 * 64)
    assert k1.padded_size(c, 64) == 6 * k1.BLOCK
    ops.collision_force.clear_cache()
    try:
        many = ops.collision_force(*args, **kw)
    finally:
        ops.collision_force.clear_cache()
    assert not bool(many[2])
    np.testing.assert_array_equal(np.asarray(many[0]), np.asarray(one[0]))
    np.testing.assert_array_equal(np.asarray(many[1]), np.asarray(one[1]))
    assert int(np.asarray(one[1]).sum()) > 0


# ---------------------------------------------------------------------------
# K2: flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,hq,hkv,sq,sk,d,causal,dtype", [
    (2, 4, 2, 128, 128, 64, True, jnp.float32),
    (1, 8, 8, 256, 256, 32, True, jnp.float32),
    (1, 4, 1, 100, 100, 64, True, jnp.float32),     # non-aligned seq
    (2, 2, 2, 64, 192, 32, True, jnp.float32),      # chunked decode (Sq < Sk)
    (1, 4, 2, 128, 128, 64, False, jnp.float32),    # non-causal (encoder)
    (1, 2, 2, 128, 128, 128, True, jnp.bfloat16),   # bf16 inputs
    (1, 2, 1, 384, 384, 64, True, jnp.float32),     # multi-block both axes
])
def test_flash_attention_matches_ref(rng, b, hq, hkv, sq, sk, d, causal, dtype):
    q = jnp.asarray(rng.standard_normal((b, hq, sq, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, hkv, sk, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, hkv, sk, d)), dtype)
    out = ops.flash_attention(q, k, v, causal=causal)
    expect = ref.flash_attention_ref(q, k, v, causal=causal)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), atol=tol)


@settings(max_examples=8, deadline=None)
@given(st.integers(1, 2), st.sampled_from([1, 2, 4]), st.integers(1, 3),
       st.sampled_from([32, 64]), st.integers(0, 10_000))
def test_flash_attention_property(b, group, hkv, d, seed):
    rng = np.random.default_rng(seed)
    sq = int(rng.integers(2, 200))
    hq = group * hkv
    q = jnp.asarray(rng.standard_normal((b, hq, sq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, hkv, sq, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, hkv, sq, d)), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=True)
    expect = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect), atol=3e-5)


def test_flash_attention_rows_sum_to_one_property(rng):
    """softmax sanity: attending to identical V returns V."""
    b, h, s, d = 1, 2, 130, 32
    q = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
    v = jnp.broadcast_to(jnp.asarray(rng.standard_normal((1, 1, 1, d)),
                                     jnp.float32), (b, h, s, d))
    out = ops.flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(v), atol=1e-5)
