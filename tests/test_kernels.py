"""Per-kernel validation (interpret=True on CPU) against pure-jnp oracles:
shape/dtype sweeps + statistical identities, per the kernel test contract."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_allclose

from repro.kernels import prng
from repro.kernels.decode_attention import ops as da_ops
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.poisson_bootstrap import ops as pb_ops
from repro.kernels.poisson_bootstrap import ref as pb_ref
from repro.kernels.poisson_bootstrap.kernel import poisson_bootstrap_moments
from repro.kernels.segment_agg import ops as sa_ops
from repro.kernels.segment_agg.ref import (segment_aggregate_ref,
                                           segment_bootstrap_moments_ref)

# ---------------------------------------------------------------------------
# prng
# ---------------------------------------------------------------------------


def test_prng_uniformity_and_determinism():
    rows = jax.lax.broadcasted_iota(jnp.uint32, (256, 256), 0)
    cols = jax.lax.broadcasted_iota(jnp.uint32, (256, 256), 1)
    u = np.asarray(prng.uniform01(prng.hash3(jnp.uint32(1), rows, cols)))
    assert 0.0 <= u.min() and u.max() < 1.0
    assert abs(u.mean() - 0.5) < 0.01
    assert abs(u.var() - 1 / 12) < 0.005
    u2 = np.asarray(prng.uniform01(prng.hash3(jnp.uint32(1), rows, cols)))
    assert_allclose(u, u2)
    u3 = np.asarray(prng.uniform01(prng.hash3(jnp.uint32(2), rows, cols)))
    assert not np.allclose(u, u3)


def test_uniform01_bits_equal_direct_uint32_cast():
    """The int32 detour Mosaic needs changes no bit of the uniforms."""
    rng = np.random.default_rng(0)
    edges = np.asarray([0, 1, 255, 256, 2**24, 2**31 - 1, 2**31, 2**32 - 1],
                       np.uint64)
    bits = jnp.asarray(np.concatenate(
        [edges, rng.integers(0, 2**32, 1 << 16, dtype=np.uint64)]
    ).astype(np.uint32))
    direct = (bits >> 8).astype(jnp.float32) * (2.0**-24)
    got = prng.uniform01(bits)
    assert np.asarray(got).tobytes() == np.asarray(direct).tobytes()
    assert float(got[-1 - (1 << 16)]) == 1.0 - 2.0**-24    # 2^32 - 1
    assert float(got[0]) == 0.0


def test_prng_poisson_ladder_matches_core():
    from repro.core.bootstrap import _POISSON1_CDF

    assert tuple(prng.POISSON1_CDF) == tuple(_POISSON1_CDF)


# ---------------------------------------------------------------------------
# poisson_bootstrap
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,B,tb,tn", [
    (512, 256, 256, 512),
    (1000, 500, 256, 512),
    (4096, 512, 128, 1024),
    (300, 128, 128, 512),
])
def test_poisson_bootstrap_kernel_vs_oracle(n, B, tb, tn):
    rng = np.random.default_rng(n + B)
    x = jnp.asarray(rng.exponential(1.0, n).astype(np.float32))
    mask = jnp.asarray((rng.uniform(size=n) > 0.1).astype(np.float32))
    n_pad = ((n + tn - 1) // tn) * tn
    B_pad = ((B + tb - 1) // tb) * tb
    feats = pb_ops.build_feats(x, mask, n_pad)
    seed = jnp.asarray([123], jnp.uint32)
    got = poisson_bootstrap_moments(feats, seed, B_pad, tb=tb, tn=tn,
                                    interpret=True)
    want = pb_ref.poisson_bootstrap_moments_ref(feats, seed, B_pad)
    assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-3, atol=1e-2)


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_poisson_bootstrap_dtype_cast(dtype):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(700).astype(dtype))
    mask = jnp.ones(700, jnp.float32)
    M = pb_ops.bootstrap_moments(x, mask, jnp.uint32(5), B=256, interpret=True)
    assert M.shape == (256, 5)
    assert M.dtype == jnp.float32
    assert bool(jnp.all(jnp.isfinite(M)))


def test_poisson_bootstrap_replicate_statistics():
    """Replicate means must center on the sample mean with sd sigma/sqrt(n)."""
    rng = np.random.default_rng(1)
    n = 2048
    x = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    mask = jnp.ones(n, jnp.float32)
    M = np.asarray(pb_ops.bootstrap_moments(x, mask, jnp.uint32(9), B=512,
                                            interpret=True))
    means = M[:, 1] / M[:, 0]
    assert abs(means.mean() - float(x.mean())) < 4 / np.sqrt(n)
    assert_allclose(means.std(), 1 / np.sqrt(n), rtol=0.3)
    # Total resample counts ~ Poisson(n): sd sqrt(n).
    assert_allclose(M[:, 0].mean(), n, rtol=0.05)


def test_bootstrap_moments_masked_matches_ref():
    """Variable-width masked entry vs the jnp oracle (same counter stream)."""
    rng = np.random.default_rng(7)
    g, n, B = 3, 700, 200
    x = jnp.asarray(rng.exponential(1.0, (g, n)).astype(np.float32))
    mask = jnp.asarray((rng.uniform(size=(g, n)) > 0.2).astype(np.float32))
    seeds = jnp.arange(100, 100 + g, dtype=jnp.uint32)
    got = pb_ops.bootstrap_moments_masked(x, mask, seeds, B, interpret=True)
    want = pb_ref.bootstrap_moments_masked_ref(x, mask, seeds, B)
    assert got.shape == (g, B, 5)
    assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-3, atol=1e-2)


def test_bootstrap_moments_masked_width_invariant():
    """Padding with zero-mask rows must not change the replicate sums: draws
    are a pure function of (seed, absolute row, replicate) -- the width-
    bucket contract of DESIGN.md SS7 phase C."""
    rng = np.random.default_rng(8)
    g, n, B = 2, 512, 128
    x = rng.standard_normal((g, n)).astype(np.float32)
    mask = (rng.uniform(size=(g, n)) > 0.1).astype(np.float32)
    seeds = jnp.asarray([11, 12], jnp.uint32)
    narrow = pb_ops.bootstrap_moments_masked(
        jnp.asarray(x), jnp.asarray(mask), seeds, B, interpret=True)
    pad = 1024 - n
    wide = pb_ops.bootstrap_moments_masked(
        jnp.asarray(np.pad(x, ((0, 0), (0, pad)))),
        jnp.asarray(np.pad(mask, ((0, 0), (0, pad)))), seeds, B,
        interpret=True)
    assert_allclose(np.asarray(narrow), np.asarray(wide), rtol=1e-6,
                    atol=1e-4)
    # Same invariance holds for the oracle itself.
    ref_n = pb_ref.bootstrap_moments_masked_ref(
        jnp.asarray(x), jnp.asarray(mask), seeds, B)
    ref_w = pb_ref.bootstrap_moments_masked_ref(
        jnp.asarray(np.pad(x, ((0, 0), (0, pad)))),
        jnp.asarray(np.pad(mask, ((0, 0), (0, pad)))), seeds, B)
    assert_allclose(np.asarray(ref_n), np.asarray(ref_w), rtol=1e-6,
                    atol=1e-4)


def test_bootstrap_moments_masked_gated_vs_ungated():
    """Grid-level predication (DESIGN.md SS7 phase E): with a mixed
    ``lane_active`` pattern, active groups' replicate moment sums are
    BIT-equal to the all-true call (the gate skips tiles, it never touches
    active groups' compute), and inactive groups report exact zeros."""
    rng = np.random.default_rng(21)
    g, n, B = 5, 700, 200
    x = jnp.asarray(rng.exponential(1.0, (g, n)).astype(np.float32))
    mask = jnp.asarray((rng.uniform(size=(g, n)) > 0.2).astype(np.float32))
    seeds = jnp.arange(900, 900 + g, dtype=jnp.uint32)
    act = jnp.asarray([1, 0, 1, 0, 1], jnp.int32)
    ungated = np.asarray(pb_ops.bootstrap_moments_masked(
        x, mask, seeds, B, interpret=True))
    alltrue = np.asarray(pb_ops.bootstrap_moments_masked(
        x, mask, seeds, B, lane_active=jnp.ones((g,), jnp.int32),
        interpret=True))
    gated = np.asarray(pb_ops.bootstrap_moments_masked(
        x, mask, seeds, B, lane_active=act, interpret=True))
    assert np.array_equal(alltrue, ungated)
    for i, a in enumerate([1, 0, 1, 0, 1]):
        if a:
            assert np.array_equal(gated[i], ungated[i]), i
        else:
            assert np.all(gated[i] == 0.0), i
    # The jnp oracle implements the same gating contract.
    ref_gated = np.asarray(pb_ref.bootstrap_moments_masked_ref(
        x, mask, seeds, B, lane_active=act))
    assert_allclose(gated, ref_gated, rtol=2e-3, atol=1e-2)


def test_lane_moment_sums_kernel_gating_matches_jnp():
    """core.bootstrap._lane_moment_sums must report the SAME sums per lane
    on the kernel path and the jnp path for any lane_active pattern --
    inactive lanes fall back to the plain-sample sums on both (the dead-
    replicate guard), active lanes agree to f32 accumulation noise."""
    from repro.core.bootstrap import _lane_moment_sums

    rng = np.random.default_rng(22)
    q, m, w, B = 3, 2, 512, 128
    v = jnp.asarray(rng.standard_normal((q, m, w)).astype(np.float32))
    mf = jnp.asarray((rng.uniform(size=(q, m, w)) > 0.1).astype(np.float32))
    seeds = jnp.arange(50, 50 + q * m, dtype=jnp.uint32).reshape(q, m)
    act = jnp.asarray([True, False, True])
    M_j, Mp_j = _lane_moment_sums(v, mf, seeds, B, False, None,
                                  lane_active=act)
    M_k, Mp_k = _lane_moment_sums(v, mf, seeds, B, True, True,
                                  lane_active=act)
    assert_allclose(np.asarray(M_k), np.asarray(M_j), rtol=2e-3, atol=1e-2)
    assert_allclose(np.asarray(Mp_k), np.asarray(Mp_j), rtol=1e-5)
    # Inactive lane 1 reports the plain sums (guard) on BOTH paths.
    want_j = np.broadcast_to(np.asarray(Mp_j)[1][:, None, :], (2, B, 3))
    want_k = np.broadcast_to(np.asarray(Mp_k)[1][:, None, :], (2, B, 3))
    assert_allclose(np.asarray(M_j)[1], want_j)
    assert_allclose(np.asarray(M_k)[1], want_k)


def test_estimate_error_moments_matches_jnp_path():
    from repro.core import bootstrap as bs
    from repro.core import estimators

    rng = np.random.default_rng(2)
    sample = jnp.asarray(rng.exponential(1.0, (3, 1024, 1)).astype(np.float32))
    mask = jnp.ones((3, 1024), jnp.float32)
    scale = jnp.ones((3,), jnp.float32)
    for est_name in ("avg", "var", "sum"):
        e_k, th_k = pb_ops.estimate_error_moments(
            est_name, sample, mask, scale, jax.random.PRNGKey(0), 0.05,
            B=256, interpret=True)
        e_j, th_j = bs.estimate_error(
            estimators.get(est_name), sample, mask, scale,
            jax.random.PRNGKey(0), 0.05, B=256)
        assert_allclose(np.asarray(th_k), np.asarray(th_j), rtol=1e-4)
        # Different RNG streams: errors agree within bootstrap quantile noise.
        assert_allclose(float(e_k), float(e_j), rtol=0.3)


# ---------------------------------------------------------------------------
# segment_agg
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,m,tn", [
    (2048, 4, 1024),
    (5000, 9, 1024),
    (1024, 128, 512),
    (999, 2, 512),
])
def test_segment_agg_vs_oracle(n, m, tn):
    rng = np.random.default_rng(n + m)
    gid = jnp.asarray(rng.integers(0, m, n).astype(np.int32))
    x = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    mask = jnp.asarray((rng.uniform(size=n) > 0.05).astype(np.float32))
    got = sa_ops.segment_aggregate(gid, x, mask, m, tn=tn, interpret=True)
    want = segment_aggregate_ref(x=x, gid=gid, mask=mask, m=m)
    for key in ("count", "sum", "sumsq", "sum3", "sum4"):
        assert_allclose(np.asarray(got[key]), np.asarray(want[key]),
                        rtol=2e-4, atol=2e-3, err_msg=key)
    # min/max only defined for non-empty groups.
    nonempty = np.asarray(want["count"]) > 0
    assert_allclose(np.asarray(got["min"])[nonempty],
                    np.asarray(want["min"])[nonempty], rtol=1e-6)
    assert_allclose(np.asarray(got["max"])[nonempty],
                    np.asarray(want["max"])[nonempty], rtol=1e-6)


def test_segment_agg_group_means_match_numpy():
    rng = np.random.default_rng(3)
    n, m = 4096, 7
    gid = rng.integers(0, m, n).astype(np.int32)
    x = rng.exponential(2.0, n).astype(np.float32)
    got = sa_ops.segment_aggregate(jnp.asarray(gid), jnp.asarray(x),
                                   jnp.ones(n, jnp.float32), m, interpret=True)
    means = np.asarray(got["sum"]) / np.asarray(got["count"])
    for g in range(m):
        assert_allclose(means[g], x[gid == g].mean(), rtol=1e-4)


def test_segment_agg_multipass_m300():
    """m > 128 tiles across ceil(m/128) passes over the same stream; the
    stitched output must equal the oracle on every group, including the
    boundary groups 127/128 and 255/256."""
    rng = np.random.default_rng(300)
    n, m = 20000, 300
    gid = jnp.asarray(rng.integers(0, m, n).astype(np.int32))
    x = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    mask = jnp.asarray((rng.uniform(size=n) > 0.05).astype(np.float32))
    got = sa_ops.segment_aggregate(gid, x, mask, m, tn=1024, interpret=True)
    want = segment_aggregate_ref(x=x, gid=gid, mask=mask, m=m)
    assert got["count"].shape == (m,)
    for key in ("count", "sum", "sumsq", "sum3", "sum4"):
        assert_allclose(np.asarray(got[key]), np.asarray(want[key]),
                        rtol=2e-4, atol=2e-3, err_msg=key)
    nonempty = np.asarray(want["count"]) > 0
    assert nonempty.all()  # 20k rows over 300 groups: every group hit
    assert_allclose(np.asarray(got["min"]), np.asarray(want["min"]),
                    rtol=1e-6)
    assert_allclose(np.asarray(got["max"]), np.asarray(want["max"]),
                    rtol=1e-6)


def _bootstrap_case(seed, n, m, B):
    rng = np.random.default_rng(seed)
    gid = rng.integers(0, m, n).astype(np.int32)
    # Absolute slot indices: unique per (group, position), like a packed
    # lane stream.
    slot = np.empty(n, np.int32)
    for g in range(m):
        idx = np.flatnonzero(gid == g)
        slot[idx] = np.arange(len(idx)) + 10000 * g
    x = rng.standard_normal(n).astype(np.float32)
    mask = (rng.uniform(size=n) > 0.1).astype(np.float32)
    lane_seed = (np.uint32(0xABC) + gid.astype(np.uint32) * np.uint32(977))
    return (jnp.asarray(gid), jnp.asarray(slot), jnp.asarray(x),
            jnp.asarray(mask), jnp.asarray(lane_seed))


@pytest.mark.parametrize("n,m,B", [(2048, 3, 64), (999, 8, 100)])
def test_segment_bootstrap_kernel_bit_equals_ref(n, m, B):
    """The jnp ref mirrors the kernel tile-for-tile (same tile shapes, same
    dot_general accumulation order), so interpret-mode runs are BIT-identical
    -- the guarantee that lets the fused loop swap paths without perturbing
    trajectories."""
    gid, slot, x, mask, seed = _bootstrap_case(n + m, n, m, B)
    got = sa_ops.segment_bootstrap_moments(gid, slot, x, mask, seed, m, B,
                                           interpret=True)
    want = segment_bootstrap_moments_ref(gid, slot, x, mask, seed, m, B)
    assert got.shape == (m, B, 3)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_segment_bootstrap_matches_direct_poisson_weights():
    """Replicate moments equal a naive per-group computation with the same
    counter-PRNG Poisson weights w = poisson1(uniform01(hash3(seed, slot,
    b))) -- i.e. the kernel computes the statistic it claims, not just a
    self-consistent one."""
    n, m, B = 1500, 4, 32
    gid, slot, x, mask, seed = _bootstrap_case(42, n, m, B)
    got = np.asarray(sa_ops.segment_bootstrap_moments(
        gid, slot, x, mask, seed, m, B, interpret=True))
    rep = jnp.arange(B, dtype=jnp.uint32)
    w = np.asarray(prng.poisson1_from_uniform(prng.uniform01(prng.hash3(
        jnp.asarray(seed)[:, None].astype(jnp.uint32),
        jnp.asarray(slot)[:, None].astype(jnp.uint32),
        rep[None, :]))))                                   # (n, B)
    gid_np, x_np, mask_np = (np.asarray(gid), np.asarray(x), np.asarray(mask))
    for g in range(m):
        sel = (gid_np == g) & (mask_np > 0)
        for p, feat in enumerate([np.ones(n, np.float32), x_np, x_np * x_np]):
            want = (w[sel] * (mask_np * feat)[sel, None]).sum(axis=0)
            assert_allclose(got[g, :, p], want, rtol=1e-5, atol=1e-4,
                            err_msg=f"group {g} moment {p}")


def test_segment_bootstrap_mean_weight_is_one():
    """Poisson(1) replicate weights: E[w] = 1, so replicate count-moments
    scatter around the true per-group masked counts."""
    n, m, B = 4096, 2, 256
    gid, slot, x, mask, seed = _bootstrap_case(9, n, m, B)
    got = np.asarray(sa_ops.segment_bootstrap_moments(
        gid, slot, x, mask, seed, m, B, interpret=True))
    counts = np.asarray(segment_aggregate_ref(gid=gid, x=x, mask=mask,
                                              m=m)["count"])
    assert_allclose(got[:, :, 0].mean(axis=1), counts, rtol=0.05)


# ---------------------------------------------------------------------------
# decode_attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("B,Hq,Hkv,d,S,tk", [
    (1, 8, 2, 128, 1024, 512),
    (2, 4, 4, 64, 600, 256),    # kv_len not a tile multiple
    (1, 16, 8, 128, 512, 128),
    (2, 8, 1, 128, 768, 256),   # MQA
])
def test_decode_attention_vs_oracle(B, Hq, Hkv, d, S, tk):
    rng = np.random.default_rng(B * 1000 + S)
    q = jnp.asarray(rng.standard_normal((B, Hq, d)).astype(np.float32)) * 0.3
    k = jnp.asarray(rng.standard_normal((B, S, Hkv, d)).astype(np.float32)) * 0.3
    v = jnp.asarray(rng.standard_normal((B, S, Hkv, d)).astype(np.float32))
    got = da_ops.decode_attention(q, k, v, kv_len=S, tk=tk, interpret=True)
    G = Hq // Hkv
    qg = q.reshape(B, Hkv, G, d)
    kk = k.transpose(0, 2, 1, 3)
    vv = v.transpose(0, 2, 1, 3)
    want = jax.vmap(lambda a, b, c: decode_attention_ref(a, b, c, kv_len=S))(
        qg, kk, vv).reshape(B, Hq, d)
    assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)


def test_decode_attention_respects_kv_len():
    """Entries beyond kv_len must not contribute."""
    rng = np.random.default_rng(5)
    B, Hq, Hkv, d, S = 1, 4, 2, 64, 512
    q = jnp.asarray(rng.standard_normal((B, Hq, d)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((B, S, Hkv, d)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((B, S, Hkv, d)).astype(np.float32))
    # Poison the tail.
    k = k.at[:, 300:].set(100.0)
    v = v.at[:, 300:].set(1e9)
    got = da_ops.decode_attention(q, k, v, kv_len=300, tk=256, interpret=True)
    assert bool(jnp.all(jnp.isfinite(got)))
    assert float(jnp.max(jnp.abs(got))) < 100.0


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_dtypes(dtype):
    rng = np.random.default_rng(6)
    B, Hq, Hkv, d, S = 1, 8, 4, 128, 512
    q = jnp.asarray(rng.standard_normal((B, Hq, d)), dtype) * 0.3
    k = jnp.asarray(rng.standard_normal((B, S, Hkv, d)), dtype) * 0.3
    v = jnp.asarray(rng.standard_normal((B, S, Hkv, d)), dtype)
    got = da_ops.decode_attention(q, k, v, kv_len=S, tk=256, interpret=True)
    assert got.dtype == dtype
    qg = np.asarray(q, np.float32).reshape(B, Hkv, 2, d)
    want = jax.vmap(lambda a, b, c: decode_attention_ref(a, b, c, kv_len=S))(
        jnp.asarray(qg),
        jnp.asarray(np.asarray(k, np.float32).transpose(0, 2, 1, 3)),
        jnp.asarray(np.asarray(v, np.float32).transpose(0, 2, 1, 3)),
    ).reshape(B, Hq, d)
    tol = 3e-2 if dtype == jnp.bfloat16 else 3e-4
    assert_allclose(np.asarray(got, np.float32), np.asarray(want), rtol=tol,
                    atol=tol)
