"""Per-kernel validation: shape/dtype sweeps asserting allclose against the
pure-jnp oracles (interpret mode on CPU), plus hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.attention.ops import attention
from repro.kernels.attention.ref import attention_ref
from repro.kernels.ssd.ops import ssd
from repro.kernels.ssd.ref import ssd_ref
from repro.kernels.arbiter import dispatch
from repro.kernels.arbiter import ops as arb_ops
from repro.kernels.arbiter.ref import (priority_arbiter_ref, srpt_topk_ref,
                                      srpt_topk_rounds)


# ------------------------------------------------------------ attention ----

ATTN_CASES = [
    # (B, Sq, Skv, H, KV, d, causal, window, dtype)
    (1, 64, 64, 4, 4, 32, True, None, jnp.float32),
    (2, 96, 96, 4, 2, 16, True, None, jnp.float32),
    (1, 128, 128, 8, 1, 64, True, 32, jnp.float32),
    (2, 64, 64, 2, 2, 32, False, None, jnp.float32),
    (1, 80, 80, 4, 4, 32, True, None, jnp.bfloat16),
    (1, 33, 33, 2, 2, 8, True, None, jnp.float32),   # ragged block
]


@pytest.mark.parametrize("case", ATTN_CASES)
def test_attention_matches_ref(case):
    B, Sq, Skv, H, KV, d, causal, window, dtype = case
    ks = jax.random.split(jax.random.key(42), 3)
    q = jax.random.normal(ks[0], (B, Sq, H, d), dtype)
    k = jax.random.normal(ks[1], (B, Skv, KV, d), dtype)
    v = jax.random.normal(ks[2], (B, Skv, KV, d), dtype)
    out = attention(q, k, v, causal=causal, window=window,
                    block_q=32, block_kv=32, interpret=True)
    ref = attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 3), st.integers(1, 4), st.integers(1, 3),
       st.sampled_from([8, 16, 32]), st.booleans())
def test_attention_property(b, kv, g, d, causal):
    """Rows of the attention output are convex combinations of V rows:
    output must lie within [min(v), max(v)] per dim."""
    h = kv * g
    s = 40
    ks = jax.random.split(jax.random.key(b * 100 + kv * 10 + g), 3)
    q = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, kv, d))
    v = jax.random.normal(ks[2], (b, s, kv, d))
    out = np.asarray(attention(q, k, v, causal=causal, block_q=16,
                               block_kv=16, interpret=True), np.float32)
    vmax = float(np.asarray(v, np.float32).max())
    vmin = float(np.asarray(v, np.float32).min())
    assert out.max() <= vmax + 1e-3 and out.min() >= vmin - 1e-3
    assert np.isfinite(out).all()


# ------------------------------------------------------------------ SSD ----

SSD_CASES = [
    # (B, S, H, P, N, chunk)
    (1, 32, 2, 8, 8, 8),
    (2, 64, 3, 8, 16, 16),
    (1, 48, 1, 16, 16, 16),   # pad path
    (2, 128, 4, 16, 32, 32),
]


@pytest.mark.parametrize("case", SSD_CASES)
def test_ssd_matches_ref(case):
    B, S, H, P, N, chunk = case
    ks = jax.random.split(jax.random.key(7), 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, N)) * 0.5
    Cm = jax.random.normal(ks[4], (B, S, N)) * 0.5
    y, fs = ssd(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    yr, fr = ssd_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               atol=5e-4, rtol=5e-4)
    np.testing.assert_allclose(np.asarray(fs), np.asarray(fr),
                               atol=5e-4, rtol=5e-4)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000))
def test_ssd_decay_property(seed):
    """With A << 0 (fast decay) the state forgets: doubling early inputs must
    not change late outputs materially."""
    ks = jax.random.split(jax.random.key(seed), 5)
    B, S, H, P, N = 1, 32, 1, 4, 4
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jnp.ones((B, S, H)) * 2.0
    A = jnp.full((H,), -8.0)
    Bm = jax.random.normal(ks[3], (B, S, N))
    Cm = jax.random.normal(ks[4], (B, S, N))
    y1, _ = ssd(x, dt, A, Bm, Cm, chunk=8, interpret=True)
    x2 = x.at[:, :8].mul(2.0)
    y2, _ = ssd(x2, dt, A, Bm, Cm, chunk=8, interpret=True)
    np.testing.assert_allclose(np.asarray(y1[:, -8:]), np.asarray(y2[:, -8:]),
                               atol=1e-3)


# -------------------------------------------------------------- arbiter ----

# (13, 100) and (8, 1000) exercise the padded ragged path: the old
# heuristic (`bc = 256 if cap % 256 == 0 else cap`) degenerated to one
# un-tiled block for any non-multiple capacity; dispatch now pads
# columns up to the block multiple instead (satellite fix).
@pytest.mark.parametrize("H,cap", [(8, 256), (16, 512), (4, 64), (13, 100),
                                   (8, 1000), (1, 1)])
def test_arbiter_matches_ref(H, cap):
    rng = np.random.default_rng(H * cap)
    prio = jnp.asarray(rng.integers(0, 8, (H, cap)), jnp.int32)
    seq = jnp.asarray(rng.integers(0, 10_000, (H, cap)), jnp.int32)
    elig = jnp.asarray(rng.random((H, cap)) < 0.3)
    bp, bi = arb_ops.arbitrate(prio, seq, elig, interpret=True)
    rp, ri = priority_arbiter_ref(prio, seq, elig)
    np.testing.assert_array_equal(np.asarray(bp), np.asarray(rp))
    # exact index equality: both backends break (prio, seq) ties toward
    # the lowest slot, and the simulator's ring state depends on it
    np.testing.assert_array_equal(np.asarray(bi), np.asarray(ri))


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 12), st.integers(1, 60), st.integers(0, 2 ** 16),
       st.sampled_from([0.0, 0.3, 1.0]))
def test_arbitrate_matches_ring_drain_select(H, cap, seed, p_elig):
    """Property (satellite): ``dispatch.arbitrate`` equals the simulator's
    ``ring_drain_select`` oracle — winner index, priority, eligibility —
    over ragged H/cap shapes, dense ties, and all-ineligible rows, for
    BOTH backends."""
    from repro.core.fabric import ring_drain_select
    rng = np.random.default_rng(seed)
    prio = jnp.asarray(rng.integers(0, 4, (H, cap)), jnp.int32)
    seq = jnp.asarray(rng.integers(0, 8, (H, cap)), jnp.int32)  # dense ties
    elig = jnp.asarray(rng.random((H, cap)) < p_elig)
    elig = elig.at[0].set(False)              # force an all-ineligible row
    slot_idx, any_e, pmin = ring_drain_select(prio, seq, elig)
    for backend in ("reference", "pallas"):
        bp, bi = dispatch.arbitrate(prio, seq, elig, backend=backend,
                                    interpret=True)
        np.testing.assert_array_equal(np.asarray(bp), np.asarray(pmin))
        np.testing.assert_array_equal(np.asarray(bp < 2 ** 30),
                                      np.asarray(any_e))
        np.testing.assert_array_equal(np.asarray(bi), np.asarray(slot_idx))


def _assert_topk_forms_match_ref(keys, K):
    """Every top-K form equals ``srpt_topk_ref``: the pallas kernel
    (interpret mode), the reference rounds, and the reference backend's
    dispatch, whichever of the two reference forms it picks."""
    rv, ri = srpt_topk_ref(keys, K)
    forms = {"pallas": arb_ops.topk(keys, K, interpret=True),
             "rounds": srpt_topk_rounds(keys, K),
             "reference": dispatch.topk(keys, K, backend="reference")}
    for form, (vals, idx) in forms.items():
        np.testing.assert_array_equal(np.asarray(vals), np.asarray(rv),
                                      err_msg=form)
        np.testing.assert_array_equal(np.asarray(idx), np.asarray(ri),
                                      err_msg=form)


# random: half the keys zero; ties: positive keys from {1, 2, 3}, so
# every row is full of positive ties, with a fifth zero; zero: all-zero
# rows (nothing eligible)
@pytest.mark.parametrize("H,M,K,kind", [
    pytest.param(8, 512, 7, "random", id="8-512-7"),
    pytest.param(16, 1024, 4, "random", id="16-1024-4"),
    pytest.param(4, 128, 1, "random", id="4-128-1"),
    pytest.param(8, 512, 8, "random", id="8-512-8"),
    pytest.param(13, 60, 5, "random", id="13-60-5"),
    pytest.param(8, 300, 7, "ties", id="ties-8-300-7"),
    pytest.param(6, 64, 7, "zero", id="zero-6-64-7"),
    pytest.param(5, 3, 6, "ties", id="short-5-3-6"),
    pytest.param(9, 200, 1, "ties", id="k1-9-200-1"),
    pytest.param(7, 24, 24, "ties", id="kM-7-24-24"),
])
def test_topk_matches_ref(H, M, K, kind):
    rng = np.random.default_rng(H + M + K)
    if kind == "random":
        keys = jnp.asarray(rng.integers(0, 1 << 28, (H, M)), jnp.int32)
        keys = jnp.where(jnp.asarray(rng.random((H, M)) < 0.5), keys, 0)
    elif kind == "ties":
        keys = jnp.asarray(rng.integers(1, 4, (H, M)), jnp.int32)
        keys = jnp.where(jnp.asarray(rng.random((H, M)) < 0.8), keys, 0)
    else:
        keys = jnp.zeros((H, M), jnp.int32)
    _assert_topk_forms_match_ref(keys, K)


def test_topk_short_rows_use_ineligible_sentinel():
    """Regression (satellite): with M < K the columns used to be
    zero-filled, which collides with legitimate zero keys — with an
    index output that could surface a padding column as a winner. Pads
    must use the NEG sentinel: absent slots report (0, -1) and no index
    ever points outside the real columns."""
    keys = jnp.asarray([[0, 5, 0]], jnp.int32)          # legit zero keys
    vals, idx = arb_ops.topk(keys, 5, interpret=True)
    np.testing.assert_array_equal(np.asarray(vals), [[5, 0, 0, 0, 0]])
    np.testing.assert_array_equal(np.asarray(idx), [[1, -1, -1, -1, -1]])
    rv, ri = srpt_topk_ref(keys, 5)
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(rv))
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ri))
    # all-zero rows: nothing is eligible, nothing points at padding
    z_vals, z_idx = arb_ops.topk(jnp.zeros((2, 3), jnp.int32), 4,
                                 interpret=True)
    assert (np.asarray(z_vals) == 0).all() and (np.asarray(z_idx) == -1).all()


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 12), st.integers(1, 60), st.integers(1, 8),
       st.integers(0, 2 ** 16), st.sampled_from([3, 1 << 20]))
def test_topk_property(H, M, K, seed, hi):
    rng = np.random.default_rng(seed)
    keys = jnp.asarray(rng.integers(0, hi, (H, M)), jnp.int32)
    _assert_topk_forms_match_ref(keys, K)


def test_topk_forms_match_ref_on_a_midrun_homa_grant_matrix():
    """The (144, 6,000) grant keys of Homa on 144 hosts with 6,000 W4
    messages, stopped at slot 512 of an overloaded burst so that several
    receivers hold more than K grantable messages."""
    from repro.core import SimConfig, make_messages, protocols, simulate
    table = make_messages("W4", n_hosts=144, load=16.0, n_messages=6000,
                          slot_bytes=256, seed=3)
    cfg = SimConfig(n_hosts=144, max_slots=512, protocol="homa",
                    backend="reference")
    res = simulate(cfg, table, return_state=True)
    st, S = res.state, res.static
    eligible = jnp.asarray((st["recv"] > 0) & (st["completion"] < 0))
    mat, K = protocols.srpt_grant_matrix(cfg, st, S, eligible,
                                         res.alloc.n_sched)
    assert mat.shape == (144, 6000) and K == 7
    assert dispatch.topk_rounds(K, 6000) == K
    assert ((np.asarray(mat) > 0).sum(axis=1) > K).sum() >= 5
    _assert_topk_forms_match_ref(mat, K)


def test_simulate_is_the_same_with_the_reference_topk_sorting(monkeypatch):
    """Homa over 1,024 messages selects its grants by rounds; forced onto
    ``lax.top_k`` it ends in the same state, bit for bit."""
    from repro.core import SimConfig, make_messages, sim, simulate
    table = make_messages("W4", n_hosts=32, load=16.0, n_messages=1024,
                          slot_bytes=256, seed=3)
    cfg = SimConfig(n_hosts=32, max_slots=512, protocol="homa",
                    backend="reference")
    rounds = simulate(cfg, table, return_state=True)
    assert dispatch.topk_rounds(rounds.alloc.n_sched, 1024) > 0
    monkeypatch.setattr(dispatch, "topk_rounds", lambda *a, **k: 0)
    sim._run.clear_cache()
    try:
        sorting = simulate(cfg, table, return_state=True)
    finally:
        sim._run.clear_cache()
    assert (rounds.completion >= 0).sum() > 20
    for k, v in rounds.state.items():
        np.testing.assert_array_equal(np.asarray(sorting.state[k]),
                                      np.asarray(v), err_msg=k)


def _crossover(M):
    return max(K for K in range(1, 400) if dispatch.topk_rounds(K, M))


@pytest.mark.parametrize("M,K", [(6000, 1), (6000, 7), (6000, "max"),
                                 (6000, "max+1"), (512, "max"),
                                 (512, "max+1")])
def test_reference_topk_sorts_only_above_the_crossover(M, K):
    """The reference top-K lowers to rounds (no sort, no top_k) for K at
    or under the crossover of ``dispatch.topk_rounds`` and to
    ``lax.top_k`` above it: the path choice the grant stage's speed
    rests on."""
    import re
    if isinstance(K, str):
        K = _crossover(M) + (K == "max+1")
    text = jax.jit(lambda k: dispatch.topk(k, K, backend="reference")).lower(
        jax.ShapeDtypeStruct((144, M), jnp.int32)).as_text()
    ops = set(re.findall(r"\b(?:stablehlo|chlo|mhlo)\.[a-z_]+", text))
    sorts = bool(ops & {"stablehlo.sort", "chlo.top_k"})
    assert sorts == (dispatch.topk_rounds(K, M) == 0)
    assert sorts == (K > _crossover(M))
