"""Store access kernels: Pallas (interpret) ≡ ref parity + complexity.

The fused probe/sample/gather kernels must produce *bit-identical*
results in every mode, on both engines, and neither the kernels nor the
routed store ops may materialize an ``[n, capacity]`` intermediate
(asserted structurally on the jaxpr).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import store as S
from repro.core.store import TableSpec

MODES = ("ref", "interpret")


def _filled(engine: str, capacity: int = 12, n_put: int = 7, shape=(3,)):
    """Keys 1..n_put — distinct mod capacity, so both engines keep all."""
    spec = TableSpec("t", shape=shape, capacity=capacity, engine=engine)
    st = S.init_table(spec)
    for i in range(n_put):
        st = S.put(spec, st, jnp.uint32(i + 1), jnp.full(shape, 10.0 + i))
    return spec, st


@pytest.mark.parametrize("engine", ["hash", "ring"])
def test_get_many_parity_both_engines(engine):
    spec, st = _filled(engine)
    # present, absent and reserved keys, in mixed order
    q = jnp.concatenate([
        jnp.arange(1, 8, dtype=jnp.uint32),
        jnp.arange(100, 103, dtype=jnp.uint32),
        jnp.array([S.EMPTY_KEY], jnp.uint32),
    ])
    outs = {m: S.get_many(spec, st, q, m) for m in MODES}
    v_ref, f_ref = outs["ref"]
    v_int, f_int = outs["interpret"]
    np.testing.assert_array_equal(np.asarray(v_ref), np.asarray(v_int))
    np.testing.assert_array_equal(np.asarray(f_ref), np.asarray(f_int))
    # semantics: the 7 present keys found with their values, rest absent
    assert np.asarray(f_ref).tolist() == [True] * 7 + [False] * 4
    np.testing.assert_allclose(np.asarray(v_ref)[:7, 0],
                               10.0 + np.arange(7))
    np.testing.assert_allclose(np.asarray(v_ref)[7:], 0.0)


@pytest.mark.parametrize("engine", ["hash", "ring"])
def test_get_many_after_delete_parity(engine):
    spec, st = _filled(engine)
    st = S.delete(spec, st, jnp.uint32(4))
    q = jnp.arange(1, 8, dtype=jnp.uint32)
    outs = {m: S.get_many(spec, st, q, m) for m in MODES}
    np.testing.assert_array_equal(np.asarray(outs["ref"][1]),
                                  np.asarray(outs["interpret"][1]))
    founds = np.asarray(outs["ref"][1])
    assert not founds[3] and founds.sum() == 6


def test_get_many_duplicate_key_lowest_slot():
    """Ring tables can hold one key in several slots; both paths must
    agree on the historical tie-break (lowest slot index)."""
    spec = TableSpec("t", shape=(2,), capacity=8, engine="ring")
    st = S.init_table(spec)
    k = S.make_key(0, 5)
    st = S.put(spec, st, k, jnp.array([1.0, 1.0]))     # slot 0
    st = S.put(spec, st, k, jnp.array([2.0, 2.0]))     # slot 1, same key
    for m in MODES:
        v, f = S.get_many(spec, st, jnp.array([k]), m)
        assert bool(np.asarray(f)[0])
        np.testing.assert_allclose(np.asarray(v)[0], [1.0, 1.0]), m


@pytest.mark.parametrize("engine", ["hash", "ring"])
def test_sample_parity_both_engines(engine):
    spec, st = _filled(engine)
    rng = jax.random.key(7)
    outs = {m: S.sample(spec, st, rng, 16, m) for m in MODES}
    v_ref, k_ref, ok_ref = outs["ref"]
    v_int, k_int, ok_int = outs["interpret"]
    np.testing.assert_array_equal(np.asarray(v_ref), np.asarray(v_int))
    np.testing.assert_array_equal(np.asarray(k_ref), np.asarray(k_int))
    assert bool(ok_ref) == bool(ok_int) is True
    # all sampled values come from live slots
    assert set(np.asarray(v_ref)[:, 0].tolist()) <= set(
        (10.0 + np.arange(7)).tolist())


def test_empty_key_reserved_consistently():
    """A slot holding the reserved EMPTY_KEY reads as absent through
    every lookup verb (get, poll and the batched probe agree)."""
    spec = TableSpec("t", shape=(2,), capacity=4, engine="ring")
    st = S.init_table(spec)
    st = S.put(spec, st, jnp.uint32(S.EMPTY_KEY), jnp.ones(2))
    _, found = S.get(spec, st, S.EMPTY_KEY)
    assert not bool(found)
    assert not bool(S.poll(spec, st, S.EMPTY_KEY))
    for m in MODES:
        _, founds = S.get_many(spec, st, jnp.array([S.EMPTY_KEY],
                                                   jnp.uint32), m)
        assert not bool(np.asarray(founds)[0])


@pytest.mark.parametrize("mode", MODES)
def test_sample_empty_table(mode):
    spec = TableSpec("t", shape=(3,), capacity=4, engine="ring")
    st = S.init_table(spec)
    vals, keys, ok = S.sample(spec, st, jax.random.key(0), 4, mode)
    assert not bool(ok)
    np.testing.assert_allclose(np.asarray(vals), 0.0)


@pytest.mark.parametrize("capacity,n", [(256, 300), (300, 7)])
def test_multi_block_grid_parity(capacity, n):
    """Tables and query batches larger than one (128-lane, 128-row)
    block: the capacity axis accumulates across grid steps and padded
    slots/queries never leak into results."""
    from repro.kernels.store import ops as kops
    rng = np.random.default_rng(capacity + n)
    keys = rng.integers(0, 2 * capacity, capacity).astype(np.uint32)
    keys[rng.random(capacity) < 0.1] = S.EMPTY_KEY
    ver = (rng.random(capacity) < 0.7) * rng.integers(1, 9, capacity)
    keys, ver = jnp.asarray(keys), jnp.asarray(ver, jnp.int32)
    query = rng.integers(0, 3 * capacity, n).astype(np.uint32)
    query[0] = S.EMPTY_KEY
    probes = [kops.probe_slots(keys, ver, jnp.asarray(query), m)
              for m in MODES]
    for a, b in zip(*probes):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    nvalid = int((ver > 0).sum())
    ranks = jnp.asarray(np.concatenate(
        [rng.integers(0, nvalid, n), [nvalid, capacity + 3]]), jnp.int32)
    slots = [kops.sample_slots(ver, ranks, m) for m in MODES]
    np.testing.assert_array_equal(np.asarray(slots[0]), np.asarray(slots[1]))
    slab = jnp.asarray(rng.normal(size=(capacity, 4, 33)), jnp.float32)
    safe = jnp.minimum(slots[0], capacity - 1)
    rows = [kops.gather_rows(slab, safe, m) for m in MODES]
    np.testing.assert_array_equal(np.asarray(rows[0]), np.asarray(rows[1]))


# ---------------------------------------------------------------------------
# Sharded gather (the slab-sharded data plane's shard-local fetch)
# ---------------------------------------------------------------------------

class TestShardedGather:
    """``gather_rows_sharded``: each shard fetches only the slots it owns
    (zeros elsewhere); summing the shard results reassembles the global
    gather bit-exactly.  Parity across ref and interpret modes."""

    def _slab(self, capacity=16, shape=(3, 5)):
        return jax.random.normal(jax.random.key(0), (capacity, *shape))

    @pytest.mark.parametrize("mode", MODES)
    def test_shards_sum_to_global_gather(self, mode):
        from repro.kernels.store import ops as kops
        slab = self._slab()
        slots = jnp.array([0, 3, 7, 8, 11, 15, 2, 9, 8, 0], jnp.int32)
        full = kops.gather_rows(slab, slots, mode)
        for n_shards in (2, 4):
            cl = slab.shape[0] // n_shards
            parts = [kops.gather_rows_sharded(slab[i * cl:(i + 1) * cl],
                                              slots, i * cl, mode)
                     for i in range(n_shards)]
            np.testing.assert_array_equal(
                np.asarray(sum(parts)), np.asarray(full))
            # exactly one shard owns each row
            owned = sum((np.abs(np.asarray(p)).sum(axis=(1, 2)) > 0)
                        .astype(int) for p in parts)
            assert (owned <= 1).all()

    def test_ref_interpret_parity(self):
        from repro.kernels.store import ops as kops
        slab = self._slab(capacity=8)
        slots = jnp.array([7, 0, 3, 4, 5, 1], jnp.int32)
        for off in (0, 4):
            local = slab[off:off + 4]
            r = kops.gather_rows_sharded(local, slots, off, "ref")
            k = kops.gather_rows_sharded(local, slots, off, "interpret")
            np.testing.assert_array_equal(np.asarray(r), np.asarray(k))

    @pytest.mark.parametrize("mode", MODES)
    def test_traced_offset(self, mode):
        """The shard offset is a traced scalar inside shard_map
        (``axis_index * local_cap``); both paths must accept it."""
        from repro.kernels.store import ops as kops
        slab = self._slab(capacity=8)
        slots = jnp.array([1, 6, 3], jnp.int32)

        out = jax.jit(lambda off: kops.gather_rows_sharded(
            slab[4:], slots, off, mode))(jnp.int32(4))
        ref = kops.gather_rows_sharded(slab[4:], slots, 4, "ref")
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    @pytest.mark.parametrize("mode", MODES)
    def test_sample_sharded_psum_equals_sample(self, mode):
        """``store.sample_sharded_impl`` under a real 1-axis shard_map on
        the available devices must reproduce ``sample_impl`` bit-exactly
        (on 1 device the shard owns everything — the degenerate identity;
        multi-device equality is covered by the subprocess tests)."""
        from functools import partial
        from jax.experimental.shard_map import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.parallel.sharding import data_mesh

        spec, st = _filled("ring")
        mesh = data_mesh(len(jax.devices()))
        rng = jax.random.key(11)
        want = S.sample_impl(spec, st, rng, 6, mode)

        body = partial(S.sample_sharded_impl, spec, n=6, axis="data",
                       mode=mode)
        got = jax.jit(shard_map(
            lambda state, k: body(state, k),
            mesh=mesh,
            in_specs=(S.TableState(slab=P("data"), keys=P(), version=P(),
                                   ptr=P(), count=P()), P()),
            out_specs=(P(), P(), P()), check_rep=False))(st, rng)
        for a, b in zip(want, got):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# Complexity: no [n, capacity] intermediate anywhere in the routed ops
# ---------------------------------------------------------------------------

def _all_eqn_shapes(jaxpr, acc):
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            if aval is not None and hasattr(aval, "shape"):
                acc.add(tuple(aval.shape))
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else [p]):
                inner = getattr(sub, "jaxpr", None)
                if inner is not None and hasattr(inner, "eqns"):
                    _all_eqn_shapes(inner, acc)
                elif hasattr(sub, "eqns"):
                    _all_eqn_shapes(sub, acc)
    return acc


@pytest.mark.parametrize("engine", ["hash", "ring"])
def test_no_quadratic_intermediates(engine):
    n, cap = 32, 512
    spec = TableSpec("t", shape=(4,), capacity=cap, engine=engine)
    st = S.init_table(spec)
    keys = S.make_key(jnp.zeros(n, jnp.int32), jnp.arange(n))

    shapes = _all_eqn_shapes(
        jax.make_jaxpr(lambda s, k: S.get_many_impl(spec, s, k))(st, keys)
        .jaxpr, set())
    shapes |= _all_eqn_shapes(
        jax.make_jaxpr(
            lambda s, r: S.sample_impl(spec, s, r, n))(st, jax.random.key(0))
        .jaxpr, set())

    bad = {sh for sh in shapes if (n, cap) == sh or (cap, n) == sh
           or (n in sh and cap in sh)}
    assert not bad, f"quadratic [n, capacity] intermediates found: {bad}"


# ---------------------------------------------------------------------------
# Fused producer/consumer ops
# ---------------------------------------------------------------------------

def test_capture_scan_equals_sequential_puts():
    spec = TableSpec("t", shape=(3,), capacity=8, engine="ring")

    def step_fn(carry, t):
        return carry + 1.0, S.make_key(0, t), \
            jnp.full((3,), t.astype(jnp.float32))

    a, carry = S.capture_scan(spec, S.init_table(spec), step_fn,
                              jnp.zeros(()), 7, 2)
    b = S.init_table(spec)
    for t in range(7):
        if t % 2 == 0:
            b = S.put(spec, b, S.make_key(0, t), jnp.full((3,), float(t)))
    for x, y, name in zip(a, b, a._fields):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y), name)
    assert float(carry) == 7.0
    assert S.capture_emit_count(7, 2) == 4 == int(a.count)


def test_capture_scan_t0_offsets_chunks():
    """Chunked capture (traced t0) ≡ one long capture."""
    spec = TableSpec("t", shape=(2,), capacity=16, engine="ring")

    def step_fn(carry, t):
        return carry, S.make_key(1, t), jnp.full((2,), t.astype(jnp.float32))

    whole, _ = S.capture_scan(spec, S.init_table(spec), step_fn,
                              jnp.zeros(()), 12, 3)
    chunked = S.init_table(spec)
    for base in (0, 6):
        chunked, _ = S.capture_scan(spec, chunked, step_fn, jnp.zeros(()),
                                    6, 3, t0=base)
    for x, y, name in zip(whole, chunked, whole._fields):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y), name)


def test_put_stream_folds_trajectory():
    spec = TableSpec("t", shape=(3,), capacity=16, engine="ring")
    t_steps, ranks = 4, 2
    keys = S.make_key(
        jnp.broadcast_to(jnp.arange(ranks)[None, :], (t_steps, ranks)),
        jnp.broadcast_to(jnp.arange(t_steps)[:, None], (t_steps, ranks)))
    vals = jnp.arange(t_steps * ranks, dtype=jnp.float32) \
        .reshape(t_steps, ranks, 1).repeat(3, -1)
    a = S.put_stream(spec, S.init_table(spec), keys, vals)
    b = S.init_table(spec)
    for t in range(t_steps):
        b = S.put_many(spec, b, keys[t], vals[t])
    for x, y, name in zip(a, b, a._fields):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y), name)
    assert int(a.count) == t_steps * ranks


def test_sample_and_step_fuses_gather_and_microstep():
    spec, st = _filled("ring")

    def micro(w, values):
        return w + jnp.sum(values), jnp.mean(values)

    w, aux, ok = S.sample_and_step(spec, st, jax.random.key(3), 4, micro,
                                   jnp.zeros(()))
    assert bool(ok)
    # reproduce with the unfused ops and the same rng
    vals, _, _ = S.sample(spec, st, jax.random.key(3), 4)
    np.testing.assert_allclose(float(w), float(jnp.sum(vals)), rtol=1e-6)
    np.testing.assert_allclose(float(aux), float(jnp.mean(vals)), rtol=1e-6)
