"""The generators are deterministic in the seed, and the reference's
counter-based words are the standard Threefry-2x32."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chip import inputs
from chip.kinds import train
from chip.reference import dvnr as ref
from chip.tests import tiny

BIG = 2**31 + 12345          # more than 32 signed bits hold


@pytest.mark.parametrize("seed", [0, BIG, 2**40 + 3])
def test_draws_repeat(seed):
    a, b = inputs.draws(seed), inputs.draws(seed)
    assert (a.key == b.key).all() and (a.weights_key == b.weights_key).all()
    assert a.rng.uniform() == b.rng.uniform()
    assert not (inputs.draws(seed + 1).key == a.key).all()


def test_volumes_and_weights_repeat():
    c = tiny.config()
    v1, r1 = inputs.make_volumes(c, 0.3)
    v2, r2 = inputs.make_volumes(c, 0.3)
    assert v1.shape == (2, 10, 10, 10)
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
    np.testing.assert_array_equal(r1, r2)
    owned = np.asarray(v1)[:, 1:-1, 1:-1, 1:-1]
    assert owned.min() == 0.0 and owned.max() == pytest.approx(1.0)
    key = inputs.draws(BIG).weights_key
    w1 = inputs.make_weights(c["model"], 2, key, 1e-4)
    w2 = inputs.make_weights(c["model"], 2, key, 1e-4)
    for a, b in zip(jax.tree.leaves(w1), jax.tree.leaves(w2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(jnp.abs(w1["tables"]).max()) <= 1e-4


def test_boxes_tile_the_domain():
    b = inputs.boxes(8)
    assert inputs.partition_grid(8) == (2, 2, 2)
    assert inputs.partition_grid(64) == (4, 4, 4)
    assert sorted(o for o, _ in b) == sorted(
        (x / 2, y / 2, z / 2) for x in (0, 1) for y in (0, 1) for z in (0, 1))


@pytest.mark.parametrize("chips, per_chip", [(1, 8), (4, 2)])
def test_checked_ranks_are_seeded_and_cover_every_chip(chips, per_chip):
    from types import SimpleNamespace as NS

    run = NS(cell=NS(config={"ranks": 64 if chips == 4 else 8},
                     traffic={"ranks_checked_per_chip": per_chip}),
             devices=[None] * chips)
    a = train.checked_ranks(run, inputs.draws(BIG).rng)
    assert a == train.checked_ranks(run, inputs.draws(BIG).rng)
    assert len(a) == chips * per_chip == len(set(a))
    per = run.cell.config["ranks"] // chips
    assert sorted({r // per for r in a}) == list(range(chips))


def test_threefry_is_the_standard_cipher():
    from jax.extend.random import threefry_2x32

    key = jnp.asarray([0x13198A2E, 0x03707344], jnp.uint32)
    count = jnp.arange(8, dtype=jnp.uint32)
    want = np.asarray(threefry_2x32(key, count))
    x0, x1 = ref.threefry2x32(key[0], key[1], count[:4], count[4:])
    np.testing.assert_array_equal(np.concatenate([x0, x1]), want)


def test_batch_is_deterministic_and_split():
    seed = ref.step_seed(np.asarray([1, 2], np.uint32), 5, 3)
    a = np.asarray(ref.batch_coords(seed, 1000, 0.15, 0.005))
    b = np.asarray(ref.batch_coords(seed, 1000, 0.15, 0.005))
    np.testing.assert_array_equal(a, b)
    assert ((a >= 0) & (a <= 1)).all()
    near_face = np.minimum(a, 1 - a).min(axis=1)
    assert (near_face[850:] < 0.05).all()       # the boundary rows
