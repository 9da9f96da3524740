"""The traffic generator: the same seed gives the same inputs, another
seed other data but the same sizes and arrival times."""

import json

import numpy as np
import jax

import _paths
from bench import generator as gen

SEED = 2 ** 32 + 5          # wider than 32 bits: the high word must count


def _traffic(name):
    return json.loads((_paths.ROOT / f"bench/traffic/{name}.json").read_text())


def _data(k):
    return np.asarray(jax.random.key_data(k))


def test_seed_keys_use_every_bit():
    assert np.array_equal(_data(gen.seed_key(SEED, 1)),
                          _data(gen.seed_key(SEED, 1)))
    assert not np.array_equal(_data(gen.seed_key(SEED)),
                              _data(gen.seed_key(5)))
    streams = [gen.weights_key(SEED), gen.producer_key(SEED),
               gen.producer_key(SEED, 0), gen.epoch_key(SEED, 0)]
    assert len({tuple(_data(k)) for k in streams}) == len(streams)


def test_images_are_deterministic_and_sized_by_the_mix():
    tf = dict(_traffic("closed8"), images_per_client=2)
    a = np.asarray(gen.images(SEED, tf, (3, 8, 8)))
    b = np.asarray(gen.images(SEED, tf, (3, 8, 8)))
    c = np.asarray(gen.images(SEED + 1, tf, (3, 8, 8)))
    assert a.shape == (tf["clients"], 2, 3, 8, 8)
    assert np.array_equal(a, b)
    assert not np.allclose(a, c)


def test_arrivals_are_fixed_by_the_mix():
    tf = _traffic("closed8")
    for ready in (0.0, 12.5, 1e4):
        assert gen.send_time(tf, ready) == ready + tf["think_s"]
    assert gen.send_time(dict(tf, think_s=0.25), 3.0) == 3.25


def test_epoch_draws_are_deterministic():
    from bench.configs import quadconv_ae_ref as ref
    tf = _traffic("insitu")
    a = ref.epoch_batches(tf["gather"], tf["batch"], gen.epoch_key(SEED, 3), 60)
    b = ref.epoch_batches(tf["gather"], tf["batch"], gen.epoch_key(SEED, 3), 60)
    assert np.array_equal(a[0], b[0]) and a[1] == b[1]
    assert np.array_equal(a[2], b[2])
    assert a[0].shape == (tf["gather"],) and 0 <= a[0].min() <= a[0].max() < 60


def test_producer_snapshots_are_deterministic():
    from bench.configs import quadconv_ae_ref as ref
    cfg = json.loads((_paths.ROOT / "bench/configs/quadconv_ae.json").read_text())
    cfg = dict(cfg, grid=[4, 4, 2], n_points=32)
    snap = ref.make_snapshots(cfg)
    keys = jax.numpy.stack([gen.producer_key(SEED, r) for r in range(2)])
    steps = jax.numpy.array([0, 7])
    a, b = np.asarray(snap(keys, steps)), np.asarray(snap(keys, steps))
    assert a.shape == (2, 4, 32) and np.array_equal(a, b)
    assert np.all(np.isfinite(a))
