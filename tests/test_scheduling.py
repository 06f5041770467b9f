"""Batch scheduling (paper Sec. 4 / Fig. 7)."""
import numpy as np
import pytest

from repro.core.scheduling import (
    _raw_draws, _tour_len, label_distributions, pairwise_kl_distance,
    tsp_max_order, weighted_sampling_order, make_schedule)


def _dists(seed=0, n=8, c=5):
    rng = np.random.default_rng(seed)
    labs = [rng.integers(0, c, size=rng.integers(10, 50)) for _ in range(n)]
    p = label_distributions(labs, c)
    return labs, pairwise_kl_distance(p)


def test_kl_distance_properties():
    _, d = _dists()
    assert np.allclose(d, d.T)
    assert (d >= -1e-9).all()
    assert np.allclose(np.diag(d), 0.0)


def test_tsp_beats_random_order():
    _, d = _dists(n=10)
    rng = np.random.default_rng(0)
    rand_len = np.mean([
        d[o, np.roll(o, -1)].sum()
        for o in (rng.permutation(10) for _ in range(50))])
    tsp = tsp_max_order(d, iters=5000)
    tsp_len = d[tsp, np.roll(tsp, -1)].sum()
    assert tsp_len >= rand_len          # maximizing tour must beat average
    assert sorted(tsp.tolist()) == list(range(10))


def test_weighted_order_is_permutation_per_epoch():
    _, d = _dists(n=7)
    order = weighted_sampling_order(d, num_epochs=3)
    for e in range(3):
        epoch = order[e * 7:(e + 1) * 7]
        assert sorted(epoch.tolist()) == list(range(7))


def test_make_schedule_modes():
    labs, _ = _dists(n=6)
    for mode in ("tsp", "weighted", "none"):
        s = make_schedule(labs, 5, mode=mode, num_epochs=2)
        assert len(s) == 12


# ------------------------------------------------- tsp_max_order vs NumPy
def _reference_tsp_max_order(dist, iters=20_000, seed=0, t0=1.0, t1=1e-3):
    """The annealing loop written with NumPy calls: the oracle whose array
    `tsp_max_order` must return."""
    n = dist.shape[0]
    if n <= 2:
        return np.arange(n)
    rng = np.random.default_rng(seed)
    order = rng.permutation(n)

    def tour_len(o):
        return dist[o, np.roll(o, -1)].sum()

    cur = tour_len(order)
    best, best_len = order.copy(), cur
    for it in range(iters):
        temp = t0 * (t1 / t0) ** (it / max(iters - 1, 1))
        i, j = sorted(rng.integers(0, n, size=2))
        if i == j:
            continue
        if rng.random() < 0.5:
            cand = order.copy()
            cand[i:j + 1] = cand[i:j + 1][::-1]     # 2-opt segment reversal
        else:
            cand = order.copy()
            cand[i], cand[j] = cand[j], cand[i]     # swap
        new = tour_len(cand)
        # MAXIMIZE: accept if longer, or with SA probability
        if new > cur or rng.random() < np.exp((new - cur) / max(temp, 1e-9)):
            order, cur = cand, new
            if cur > best_len:
                best, best_len = order.copy(), cur
    return best


def _oracle_dists(n, seed):
    """KL distances (symmetric), an asymmetric matrix whose entries span 16
    decades (summation order shows in the last bits), and exact ties
    (identical label histograms: every tour has length 0)."""
    rng = np.random.default_rng(seed)
    _, kl = _dists(seed, n=n)
    wide = rng.random((n, n)) * 10.0 ** rng.integers(-8, 9, size=(n, n))
    ties = pairwise_kl_distance(label_distributions(
        [np.array([0, 1, 1, 2])] * n, 3))
    return {"kl": kl, "asymmetric": wide, "ties": ties}


@pytest.mark.parametrize("iters", [0, 1, 2, 2000])
@pytest.mark.parametrize("n", [1, 2, 3, 7, 8, 9, 11, 16, 17, 64, 129])
def test_tsp_matches_numpy_reference(n, iters):
    for seed in (0, 7, 2**31 + 12345):
        for kind, d in _oracle_dists(n, seed % 1000).items():
            got = tsp_max_order(d, iters=iters, seed=seed)
            want = _reference_tsp_max_order(d, iters=iters, seed=seed)
            assert got.dtype == want.dtype
            assert np.array_equal(got, want), (kind, seed)


@pytest.mark.parametrize("seed", [0, 3])
def test_tsp_matches_numpy_reference_at_defaults(seed):
    d = _oracle_dists(11, seed)["kl"]
    assert np.array_equal(tsp_max_order(d, seed=seed),
                          _reference_tsp_max_order(d, seed=seed))


@pytest.mark.parametrize("t0,t1", [(5.0, 1e-2), (0.05, 1e-6), (1e-3, 1.0)])
def test_tsp_matches_numpy_reference_off_default_temperatures(t0, t1):
    for kind, d in _oracle_dists(16, 5).items():
        assert np.array_equal(
            tsp_max_order(d, iters=3000, seed=11, t0=t0, t1=t1),
            _reference_tsp_max_order(d, iters=3000, seed=11, t0=t0, t1=t1)), kind


def test_tsp_leaves_a_passed_generator_as_the_reference_does():
    d = _oracle_dists(9, 1)["asymmetric"]
    mine, ref = np.random.default_rng(42), np.random.default_rng(42)
    assert np.array_equal(tsp_max_order(d, iters=500, seed=mine),
                          _reference_tsp_max_order(d, iters=500, seed=ref))
    assert mine.bit_generator.state == ref.bit_generator.state
    assert mine.random() == ref.random()


def test_tsp_refuses_what_it_cannot_reproduce():
    d = _oracle_dists(5, 0)["kl"]
    with pytest.raises(TypeError, match="float64"):
        tsp_max_order(d.astype(np.float32))
    with pytest.raises(ValueError, match="32-bit buffer"):
        tsp_max_order(d, seed=np.random.Generator(np.random.MT19937(0)))


@pytest.mark.parametrize("n", [1, 5, 8, 11, 16, 23, 64, 127, 128, 129, 300])
def test_tour_len_is_numpys_sum_bit_for_bit(n):
    rng = np.random.default_rng(n)
    for _ in range(20):
        d = rng.random((n, n)) * 10.0 ** rng.integers(-8, 9, size=(n, n))
        o = rng.permutation(n)
        want = d[o, np.roll(o, -1)].sum()
        got = _tour_len(d.tolist(), o.tolist())
        assert type(got) is float
        assert np.float64(got).tobytes() == want.tobytes()


_BIT_GENERATORS = [np.random.PCG64, np.random.PCG64DXSM, np.random.Philox,
                   np.random.SFC64]


@pytest.mark.parametrize("bitgen", _BIT_GENERATORS,
                         ids=lambda b: b.__name__)
@pytest.mark.parametrize("n", [3, 11, 129, 2**31 + 1])
def test_raw_draws_are_generators_own(bitgen, n):
    """`_raw_draws` against `Generator.integers(0, n, size=2)` and
    `Generator.random()`, called in a mixed sequence that starts after
    `permutation` (which leaves the 32-bit buffer in use) and ends with the
    bit generator in the same state. At n = 2**31 + 1 Lemire's threshold
    rejects about half of all 32-bit draws. A NumPy release that changes
    these C paths fails here, not as a silently different schedule."""
    calls = np.random.default_rng(n % 1000).random(400) < 0.6
    mine = np.random.Generator(bitgen(1234))
    ref = np.random.Generator(bitgen(1234))
    assert np.array_equal(mine.permutation(min(n, 50)),
                          ref.permutation(min(n, 50)))
    pair_below, random, sync = _raw_draws(mine)
    for k, pair in enumerate(calls):
        if pair:
            want = tuple(int(v) for v in ref.integers(0, n, size=2))
            assert pair_below(n) == want, (
                f"call {k}: integers(0, {n}, size=2) is no longer Lemire's "
                f"method on the buffered 32-bit draws of this NumPy")
        else:
            assert random() == ref.random(), (
                f"call {k}: random() is no longer (word >> 11) * 2**-53 "
                f"on this NumPy")
    sync()
    state = mine.bit_generator.state
    want = ref.bit_generator.state
    assert (state["has_uint32"], state["uinteger"]) == \
        (want["has_uint32"], want["uinteger"])
    assert mine.random() == ref.random()


def test_raw_draws_reject_as_lemire_does():
    """At n = 2**31 + 1 the pairs consume more 32-bit draws than they
    return: the rejection loop runs, and matches."""
    n, pairs = 2**31 + 1, 64
    mine, ref = np.random.default_rng(9), np.random.default_rng(9)
    pair_below, _, sync = _raw_draws(mine)
    got = [pair_below(n) for _ in range(pairs)]
    want = [tuple(int(v) for v in ref.integers(0, n, size=2))
            for _ in range(pairs)]
    assert got == want
    sync()
    assert mine.bit_generator.state == ref.bit_generator.state
    probe = np.random.default_rng(9)
    probe.bit_generator.random_raw(pairs)      # no rejection: one word a pair
    assert probe.bit_generator.state["state"] != ref.bit_generator.state["state"]


def test_fit_with_fast_tour_matches_numpy_reference(tiny_ds, monkeypatch):
    """Same schedule, so the same training: 3 epochs of `fit` in TSP order
    give bitwise-equal parameters with either tour."""
    import jax
    from repro.core import IBMBPipeline, IBMBConfig, scheduling
    from repro.models.gnn import GNNConfig
    from repro.train import GNNTrainer
    pipe = IBMBPipeline(tiny_ds, IBMBConfig(
        variant="node", k_per_output=8, max_outputs_per_batch=16,
        pad_multiple=32))
    tr = pipe.preprocess("train")
    va = pipe.preprocess("val", for_inference=True)
    assert len(tr) > 8                      # 8-accumulator tour sums
    cfg = GNNConfig(kind="gcn", in_dim=tiny_ds.feat_dim, hidden=16,
                    out_dim=tiny_ds.num_classes, num_layers=2, dropout=0.3)

    def fit():
        return GNNTrainer(cfg, lr=1e-2, seed=0).fit(
            tr, va, tiny_ds.num_classes, epochs=3, schedule_mode="tsp").params

    fast = fit()
    with monkeypatch.context() as m:
        m.setattr(scheduling, "tsp_max_order", _reference_tsp_max_order)
        ref = fit()
    for a, b in zip(jax.tree_util.tree_leaves(fast),
                    jax.tree_util.tree_leaves(ref)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
