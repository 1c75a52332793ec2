"""SampleStore (incremental permuted-prefix sampling) invariants:
prefix nesting, per-group uniformity, delta-based cost accounting,
invalidation on refresh/reshuffle, host/device parity, and value bindings."""
import numpy as np
import pytest

from repro.core.sampling import GroupedData, SampleStore

Np = np.asarray


@pytest.fixture()
def small_data():
    rng = np.random.default_rng(0)
    groups = [rng.normal(i, 1.0, size=s)
              for i, s in enumerate([5_000, 3_000, 8_000])]
    return GroupedData.from_group_arrays(groups)


def _masked(sample, mask):
    return np.asarray(sample)[..., 0] * np.asarray(mask)


def test_prefix_nesting(small_data):
    """sample(n) must be a prefix of sample(n + delta), incl. across the
    capacity-bucket boundary (buffer growth must not reshuffle)."""
    store = SampleStore(small_data, seed=1)
    n1 = np.array([100, 60, 200])
    s1, m1 = store.sample(n1)
    a1 = np.asarray(s1).copy()
    # Grow within the bucket, then far past it (256 -> 2048).
    for n2 in (n1 + 37, np.array([900, 700, 2000])):
        s2, m2 = store.sample(n2)
        a2 = np.asarray(s2)
        for i, k in enumerate(n1):
            np.testing.assert_array_equal(a2[i, :k], a1[i, :k])
    # Shrinking n touches nothing and returns the same prefix.
    cost = store.sample_cost(n1)
    assert cost == 0
    s3, m3 = store.sample(n1)
    for i, k in enumerate(n1):
        np.testing.assert_array_equal(np.asarray(s3)[i, :k], a1[i, :k])


def test_full_prefix_is_exact_permutation(small_data):
    """sample(|group|) enumerates the group's extent exactly once."""
    store = SampleStore(small_data, seed=2)
    idx, mask = store.prefix_indices(small_data.sizes)
    for i in range(small_data.num_groups):
        k = int(small_data.sizes[i])
        got = np.sort(idx[i, :k])
        np.testing.assert_array_equal(
            got, np.arange(small_data.offsets[i], small_data.offsets[i + 1]))


def test_uniformity_per_group():
    """Each extent position is equally likely to land in a small prefix."""
    size = 40
    data = GroupedData.from_group_arrays(
        [np.arange(size, dtype=np.float64)])
    trials, k = 3000, 4
    counts = np.zeros(size)
    store = SampleStore(data, seed=0)
    for t in range(trials):
        idx, _ = store.prefix_indices(np.array([k]))
        counts[idx[0, :k]] += 1
        store.reshuffle()
    expect = trials * k / size
    # Binomial(trials, k/size): sd ~ sqrt(expect) ~ 17; allow 5 sd.
    assert np.all(np.abs(counts - expect) < 5 * np.sqrt(expect) + 1), counts


def test_delta_cost_accounting(small_data):
    store = SampleStore(small_data, seed=3)
    n1 = np.array([50, 50, 50])
    assert store.sample_cost(n1) == 150
    store.sample(n1)
    assert store.rows_touched == 150
    n2 = np.array([80, 50, 10])
    assert store.sample_cost(n2) == 30       # only group 0 grows
    store.sample(n2)
    assert store.rows_touched == 180
    # Clamped at the population: cost never exceeds the extent.
    huge = np.array([10**9] * 3)
    assert store.sample_cost(huge) == int(small_data.sizes.sum()) - 180


def test_invalidation_on_refresh(small_data):
    store = SampleStore(small_data, seed=4)
    n = np.array([64, 64, 64])
    idx1, _ = store.prefix_indices(n)
    store.sample(n)
    rows_before = store.rows_touched
    store.refresh()
    # New epoch: permutations redrawn, resident rows dropped (next sample
    # re-gathers), but the work counter keeps accumulating.
    idx2, _ = store.prefix_indices(n)
    assert not np.array_equal(idx1, idx2)
    assert store.sample_cost(n) == 192
    store.sample(n)
    assert store.rows_touched == rows_before + 192
    # Refresh onto changed values: samples must read the new table.
    vals = np.asarray(small_data.values).copy()
    vals[:] = 7.25
    new_data = GroupedData(vals, small_data.offsets.copy())
    store.refresh(new_data)
    s, m = store.sample(n)
    assert np.all(_masked(s, m)[np.asarray(m) > 0] == 7.25)


def test_reshuffle_decorrelates(small_data):
    store = SampleStore(small_data, seed=5)
    n = np.array([128, 128, 128])
    idx1, _ = store.prefix_indices(n)
    store.reshuffle()
    idx2, _ = store.prefix_indices(n)
    assert not np.array_equal(idx1, idx2)


def test_host_device_parity(small_data):
    """The device-buffer path and the numpy host path gather identical
    samples (same permutations, same alignment), prefix and windowed."""
    store = SampleStore(small_data, seed=6)
    n = np.array([300, 37, 1000])
    dev, dmask = store.sample(n)
    host, hmask = store.sample_host(n)
    np.testing.assert_array_equal(np.asarray(dmask), hmask)
    np.testing.assert_allclose(
        np.asarray(dev) * np.asarray(dmask)[..., None],
        host * hmask[..., None])
    base = np.array([10, 0, 500])
    dev, dmask = store.sample(n, base)
    host, hmask = store.sample_host(n, base)
    np.testing.assert_array_equal(np.asarray(dmask), hmask)
    np.testing.assert_allclose(
        np.asarray(dev) * np.asarray(dmask)[..., None],
        host * hmask[..., None])


def test_windowed_sampling(small_data):
    """Stacked windows are disjoint slices of the same permutation and
    their union is the prefix (the init-phase contract of l2miss)."""
    store = SampleStore(small_data, seed=7)
    n = np.array([100, 100, 100])
    i0, _ = store.prefix_indices(n)                    # window [0, 100)
    i1, _ = store.prefix_indices(n, base=n)            # window [100, 200)
    pre, _ = store.prefix_indices(2 * n)               # prefix  [0, 200)
    for g in range(3):
        assert not set(i0[g, :100]) & set(i1[g, :100])
        np.testing.assert_array_equal(pre[g, :100], i0[g, :100])
        np.testing.assert_array_equal(pre[g, 100:200], i1[g, :100])
    # A window overrunning the extent is shifted back, never truncated.
    tiny = GroupedData.from_group_arrays([np.arange(50, dtype=np.float64)])
    st = SampleStore(tiny, seed=0)
    idx, mask = st.prefix_indices(np.array([30]), base=np.array([40]))
    assert mask[0].sum() == 30
    assert idx[0, :30].max() < 50


def test_binding_shares_permutations(small_data):
    """A bound derived column reads the same rows as the primary binding."""
    store = SampleStore(small_data, seed=8)
    vals = np.asarray(small_data.values)[:, 0]
    derived = (vals > vals.mean()).astype(np.float32)
    binding = store.bind(derived)
    n = np.array([200, 200, 200])
    idx, mask = store.prefix_indices(n)
    ds, dm = binding.sample(n)
    for g in range(3):
        np.testing.assert_allclose(
            np.asarray(ds)[g, :200, 0], derived[idx[g, :200]])
    # Binding gathers are counted in the aggregate store total.
    assert store.rows_touched >= 600


def test_store_capacity_bucketing(small_data):
    store = SampleStore(small_data, seed=9)
    store.sample(np.array([10, 10, 10]))
    assert store.capacity == 256                 # base bucket
    store.sample(np.array([300, 10, 10]))
    assert store.capacity == 512
    store.sample(np.array([300, 10, 3000]))
    assert store.capacity == 4096


# ---------------------------------------------------------------------------
# Session-level reuse: one resident store, shared fused prefixes, reshuffle
# ---------------------------------------------------------------------------

def _answer(sess, queries):
    """Submit ``queries`` to the session, drain it, return responses in
    query order."""
    from repro.aqp.query import Request

    reqs = [Request(query=q) for q in queries]
    for r in reqs:
        sess.submit(r)
    by_rid = {r.rid: r for r in sess.drain()}
    return [by_rid[r.rid] for r in reqs]


def test_aqp_service_resident_store_and_reshuffle():
    from repro.aqp.query import Query
    from repro.data import make_grouped
    from repro.serve import AQPSession

    data = make_grouped(["normal", "exp"], 60_000, seed=11, biases=[4.0, 2.0])
    sess = AQPSession(data, B=100, n_min=300, n_max=600, max_iters=12,
                      n_cap=1 << 12, seed=0, reshuffle_every=3)
    assert sess.store is sess.engine.store    # one store, shared with engine

    qs = [Query(func="avg", epsilon=0.2), Query(func="avg", epsilon=0.15)]
    rs = _answer(sess, qs)
    assert all(r.success for r in rs)
    epoch0 = sess.store.epoch
    skey0 = np.asarray(sess._sample_key).copy()

    # Host-engine queries extend the same resident prefixes.
    before = sess.rows_touched
    r = _answer(sess, [Query(func="median", epsilon=0.3)])[0]
    assert r.success
    assert sess.rows_touched > before

    # The decorrelation policy rotated after >= 3 queries.
    assert sess.store.epoch > epoch0
    assert not np.array_equal(np.asarray(sess._sample_key), skey0)

    # refresh() invalidates on data update and keeps serving.
    sess.refresh(data)
    r = _answer(sess, [Query(func="avg", epsilon=0.2)])[0]
    assert r.success


def test_aqp_service_predicate_not_fused():
    """A predicate query with a fusable func must take the host path (the
    fused program has no predicate column): the answer is the predicated
    proportion-style value, not the plain group estimate."""
    from repro.aqp.query import Query
    from repro.data import make_grouped
    from repro.serve import AQPSession, Route

    data = make_grouped(["normal", "exp"], 60_000, seed=11, biases=[4.0, 2.0])
    sess = AQPSession(data, B=100, n_min=300, n_max=600, max_iters=12,
                      n_cap=1 << 12, seed=0, reshuffle_every=1000)
    q = Query(func="avg", epsilon=0.1, predicate=lambda v: (v[:, 0] > 3.0))
    r = _answer(sess, [q])[0]
    assert r.route is Route.HOST
    assert sess.fused_dispatches == 0         # host path, not fused
    assert r.success
    truth = sess.engine.exact(q).ravel()      # predicated ground truth
    assert np.linalg.norm(r.theta.ravel() - truth) <= 0.2
    # Sanity: the predicated answer differs from the unpredicated means.
    plain = sess.engine.exact(Query(func="avg", epsilon=0.1)).ravel()
    assert np.linalg.norm(plain - truth) > 0.3
