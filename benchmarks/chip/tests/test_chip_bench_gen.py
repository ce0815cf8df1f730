"""The seeded inputs: they repeat for one seed, differ across seeds, and
follow the distributions the configurations state."""
import json

import numpy as np

from chip_bench_cells import BENCH
from chipbench import gen

SEED = 2 ** 31 + 5


def imagenet_ds(**kw):
    ds = json.loads((BENCH / "configs" / "imagenet-1k-files.json")
                    .read_text())["dataset"]
    ds.update(kw)
    return ds


def test_file_sizes_mean_and_bounds_match_the_configuration():
    ds = imagenet_ds()
    sizes = gen.file_sizes(ds, SEED)
    assert sizes.shape == (ds["num_files"],)
    assert sizes.min() >= ds["min_bytes"] and sizes.max() <= ds["max_bytes"]
    # the mean of 32,768 lognormal draws with sigma 0.5 lies within about
    # 0.3% of the stated mean; allow 2%
    assert abs(sizes.mean() / ds["mean_bytes"] - 1) < 0.02
    logs = np.log(sizes)
    assert abs(logs.std() - ds["sigma"]) < 0.02


def test_every_seed_deals_out_the_same_set_of_sizes():
    """A seed changes which file has which size, never the sizes: every
    run reads the same bytes in all, and packs batches of one capacity."""
    ds = imagenet_ds()
    a, b = gen.file_sizes(ds, SEED), gen.file_sizes(ds, SEED + 1)
    assert np.array_equal(np.sort(a), np.sort(b))
    assert not np.array_equal(a, b)
    assert np.array_equal(a, gen.file_sizes(ds, SEED))


def test_small_files_repeat_for_a_seed_and_differ_across_seeds():
    ds = imagenet_ds(num_files=64, num_classes=8, mean_bytes=2000)
    p1, f1 = gen.small_files(ds, SEED)
    p2, f2 = gen.small_files(ds, SEED)
    p3, f3 = gen.small_files(ds, SEED + 1)
    assert p1 == p2 == p3 and len(set(p1)) == 64
    assert all(bytes(f1[p]) == bytes(f2[p]) for p in p1)
    assert sum(bytes(f1[p]) != bytes(f3[p]) for p in p1) == 64
    assert len({p.split("/")[1] for p in p1}) == 8


def test_tokens_repeat_for_a_seed_and_differ_across_seeds():
    a = gen.markov_tokens(16, 64, 32001, SEED)
    b = gen.markov_tokens(16, 64, 32001, SEED)
    c = gen.markov_tokens(16, 64, 32001, SEED + 1)
    assert a.dtype == np.int32 and a.shape == (16, 64)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert a.min() >= 0 and a.max() < 32001
    assert len(np.unique(a)) <= 64


def test_chain_step_draws_as_the_plain_argmax_does():
    rng = np.random.default_rng(0)
    trans = rng.dirichlet(np.ones(64) * 0.2, size=64)
    state = rng.integers(0, 64, 10_000)
    u = rng.random(10_000)
    plain = (u[:, None] < np.cumsum(trans, axis=1)[state]).argmax(axis=1)
    fast = gen.next_states(gen.shifted_cdf(trans), 64, state, u)
    # a draw can differ only where u falls within rounding of a CDF entry
    assert (fast != plain).sum() <= 2


def test_epoch_order_is_the_program_samplers_order():
    from repro.data.sampler import GlobalUniformSampler
    s = GlobalUniformSampler(100, 8, seed=SEED)
    for step in range(30):          # past the first epoch's 12 batches
        assert np.array_equal(s.next_batch(),
                              gen.batch_indices(100, 8, SEED, step))
    assert sorted(gen.epoch_order(100, SEED, 3)) == list(range(100))
