"""The clustered data plane, core level: masked-put / collect-scan
equivalence with the in-scan capture tiers, staged-transfer telemetry,
spec-threaded element staging, `split_devices` / fan-in edge cases, and
the poll-loop backoff deadline clamp.

Session-level clustered scenarios (plans, staged predictions, the
slab-sharded clustered tier) live in ``tests/test_session.py`` and
``tests/test_plan_properties.py``; the real split-mesh runs are
subprocess tests there."""

import textwrap
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import run_subprocess

from repro.core import (Client, Clustered, Colocated, StoreServer,
                        TableSpec, make_clustered_1d, split_devices)
from repro.core import store as S
from repro.parallel.sharding import data_mesh

SPEC = TableSpec("t", shape=(3,), capacity=4, engine="ring")


def _step(c, t):
    return c + 1.0, S.make_key(0, t), jnp.full((3,), t, jnp.float32)


def _step_multi(c, r, t):
    return c + 1.0, S.make_key(r, t), jnp.full((3,), t * 10 + r,
                                               jnp.float32)


def _assert_states_equal(a: S.TableState, b: S.TableState):
    for la, lb in zip(a, b):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


class TestPutMasked:
    """put_masked == replaying the masked elements' per-verb puts."""

    def test_ring_matches_sequential_puts(self):
        keys = jnp.asarray([3, 7, 11, 15, 19, 23], jnp.uint32)
        vals = jnp.arange(18, dtype=jnp.float32).reshape(6, 3)
        mask = jnp.asarray([True, False, True, True, False, True])
        ref = S.init_table(SPEC)
        for k, v, m in zip(keys, vals, mask):
            if bool(m):
                ref = S.put(SPEC, ref, k, v)
        got = S.put_masked(SPEC, S.init_table(SPEC), keys, vals, mask)
        _assert_states_equal(ref, got)
        assert int(got.count) == 4

    def test_ring_wraparound_last_writer_wins(self):
        """More masked elements than capacity: ring wrap, every overwrite
        still bumps count — byte-identical to sequential replay."""
        n = 11   # > 2 * capacity
        keys = jnp.arange(1, n + 1, dtype=jnp.uint32)
        vals = jnp.arange(3 * n, dtype=jnp.float32).reshape(n, 3)
        mask = jnp.ones((n,), bool).at[4].set(False)
        ref = S.init_table(SPEC)
        for k, v, m in zip(keys, vals, mask):
            if bool(m):
                ref = S.put(SPEC, ref, k, v)
        got = S.put_masked(SPEC, S.init_table(SPEC), keys, vals, mask)
        _assert_states_equal(ref, got)
        assert int(got.count) == n - 1

    def test_hash_collisions_match_put_many(self):
        hspec = TableSpec("h", shape=(2,), capacity=4, engine="hash")
        keys = jnp.asarray([1, 5, 2, 9, 13], jnp.uint32)  # 1≡5≡9≡13 mod 4
        vals = jnp.arange(10, dtype=jnp.float32).reshape(5, 2)
        mask = jnp.asarray([True, True, False, True, True])
        ref = S.init_table(hspec)
        for k, v, m in zip(keys, vals, mask):
            if bool(m):
                ref = S.put_many(hspec, ref, k[None], v[None])
        got = S.put_masked(hspec, S.init_table(hspec), keys, vals, mask)
        _assert_states_equal(ref, got)

    def test_empty_mask_is_noop(self):
        keys = jnp.asarray([1, 2], jnp.uint32)
        vals = jnp.zeros((2, 3))
        st0 = S.init_table(SPEC)
        got = S.put_masked(SPEC, jax.tree.map(jnp.copy, st0), keys, vals,
                           jnp.zeros((2,), bool))
        _assert_states_equal(st0, got)
        assert int(got.count) == 0


class TestCaptureCollect:
    """collect + put_masked == the in-scan capture_scan tiers."""

    def test_single_rank_equivalence(self):
        ref, c_ref = S.capture_scan(SPEC, S.init_table(SPEC), _step,
                                    jnp.zeros(()), 10, 2, t0=0)
        c, keys, vals, mask = S.capture_scan_collect(
            SPEC, _step, jnp.zeros(()), 10, 2, t0=0)
        got = S.put_masked(SPEC, S.init_table(SPEC), keys, vals, mask)
        _assert_states_equal(ref, got)
        assert float(c) == float(c_ref)

    def test_multi_rank_equivalence(self):
        ref, _ = S.capture_scan_multi(SPEC, S.init_table(SPEC),
                                      _step_multi, jnp.zeros((3,)), 7, 3,
                                      2, t0=0)
        _, keys, vals, mask = S.capture_scan_collect_multi(
            SPEC, _step_multi, jnp.zeros((3,)), 7, 3, 2, t0=0)
        got = S.put_masked(SPEC, S.init_table(SPEC), keys, vals, mask)
        _assert_states_equal(ref, got)
        assert int(got.count) == 3 * 4   # ranks * emits

    def test_compact_payload_scales_with_emissions(self):
        """A sparse emit_every must not ship zero rows across the
        interconnect: the collected buffer holds capture_rows(length,
        emit_every) rows, not one per step."""
        _, keys, vals, mask = S.capture_scan_collect(
            SPEC, _step, jnp.zeros(()), 32, 8, t0=0)
        assert vals.shape[0] == keys.shape[0] == S.capture_rows(32, 8) == 4
        assert int(jnp.sum(mask)) == 4
        # multi form: rows * ranks, rank-major
        _, keys, vals, mask = S.capture_scan_collect_multi(
            SPEC, _step_multi, jnp.zeros((3,)), 32, 3, 8, t0=0)
        assert vals.shape[0] == 4 * 3

    def test_bucketed_tail_and_traced_t0(self):
        """valid masking (bucketed tails) + traced t0 chunk clocks."""
        t0, valid = jnp.asarray(3), jnp.asarray(5)
        ref, c_ref = S.capture_scan(SPEC, S.init_table(SPEC), _step,
                                    jnp.zeros(()), 8, 2, t0=t0,
                                    valid=valid)
        c, keys, vals, mask = S.capture_scan_collect(
            SPEC, _step, jnp.zeros(()), 8, 2, t0=t0, valid=valid)
        got = S.put_masked(SPEC, S.init_table(SPEC), keys, vals, mask)
        _assert_states_equal(ref, got)
        assert float(c) == float(c_ref)     # dead steps advance nothing
        assert int(jnp.sum(mask)) == 2       # t in {4, 6}


class TestStagedTelemetry:
    """stats()['staged_transfers'] counts exactly the interconnect hops."""

    def _clustered_server(self):
        srv = StoreServer(make_clustered_1d())   # degenerate shared device
        srv.create_table(TableSpec("t", shape=(3,), capacity=8))
        return srv

    def test_fused_chunk_stages_once(self):
        srv = self._clustered_server()
        cli = Client(srv)
        cli.capture_scan("t", _step, jnp.zeros(()), 10, emit_every=2)
        st = srv.stats()
        assert st["staged_transfers"] == 1      # ONE hop for 5 puts
        # overlap holds the (sole) chunk in the pipeline: the hop is paid
        # but the insert waits for the drain at end-of-capture.
        cli.drain_captures("t")
        st = srv.stats()
        assert st["staged_transfers"] == 1      # drain inserts, never stages
        assert st["op_count"] == 2              # capture + drain flush
        assert srv.watermark("t") == 5 == srv.watermark_device("t")

    def test_fused_chunk_equals_colocated_replay(self):
        srv = self._clustered_server()
        cli = Client(srv)
        cli.capture_scan("t", _step, jnp.zeros(()), 10, emit_every=2)
        cli.drain_captures("t")
        srv2 = StoreServer()
        srv2.create_table(TableSpec("t", shape=(3,), capacity=8))
        Client(srv2).capture_scan("t", _step, jnp.zeros(()), 10,
                                  emit_every=2)
        _assert_states_equal(srv.checkout("t"), srv2.checkout("t"))

    def test_per_verb_stages_per_element(self):
        srv = self._clustered_server()
        for t in range(3):
            srv.put("t", S.make_key(0, t), jnp.ones((3,)))
        assert srv.stats()["staged_transfers"] == 3

    def test_batched_verbs_stage_once(self):
        srv = self._clustered_server()
        srv.put_many("t", jnp.arange(4, dtype=jnp.uint32),
                     jnp.ones((4, 3)))
        assert srv.stats()["staged_transfers"] == 1
        srv.put_stream("t", jnp.arange(6, dtype=jnp.uint32).reshape(3, 2),
                       jnp.ones((3, 2, 3)))
        assert srv.stats()["staged_transfers"] == 2

    def test_sample_staged_counts_one(self):
        srv = self._clustered_server()
        srv.put("t", S.make_key(0, 0), jnp.ones((3,)))
        before = srv.stats()
        vals, ok = srv.sample_staged("t", jax.random.key(0), 4)
        after = srv.stats()
        assert vals.shape == (4, 3) and bool(ok)
        assert after["staged_transfers"] == before["staged_transfers"] + 1
        assert after["op_count"] == before["op_count"] + 1

    def test_colocated_and_local_never_stage(self):
        for dep in (None, Colocated(data_mesh(1))):
            srv = StoreServer(dep)
            srv.create_table(TableSpec("t", shape=(3,), capacity=8))
            srv.put("t", S.make_key(0, 0), jnp.ones((3,)))
            Client(srv).capture_scan("t", _step, jnp.zeros(()), 4)
            srv.sample_staged("t", jax.random.key(0), 2)
            assert srv.stats()["staged_transfers"] == 0


class TestDeploymentEdges:
    def test_split_devices_extreme_fractions(self):
        devs = list(range(8))     # split_devices only slices the list
        clients, db = split_devices(devs, db_fraction=0.0)
        assert db == [7] and clients == devs[:7]   # at least one db device
        clients, db = split_devices(devs, db_fraction=1.0)
        assert clients == [0] and db == devs[1:]   # at least one client
        clients, db = split_devices([42], db_fraction=0.5)
        assert clients == db == [42]               # degenerate shared

    def test_fan_in_ceiling_division(self):
        """fan_in is the BUSIEST shard's client count — ceiling division
        (the old floor quietly reported 1 for 3 clients on 2 shards),
        flooring at 1 when clients < db shards.  The plan's
        ``ComponentPlan.fan_in`` must agree with the deployment on every
        non-divisible split because both call ``fan_in_ratio``."""
        from repro.core.deployment import fan_in_ratio
        from repro.insitu import plan as P
        def fake_mesh(n):
            return SimpleNamespace(shape={"data": n})
        dep = Clustered.__new__(Clustered)
        dep.elem_spec = ()
        dep.slab_axis = None
        for clients, db, expect in [(1, 3, 1), (3, 1, 3), (3, 2, 2),
                                    (7, 2, 4), (4, 4, 1), (5, 3, 2)]:
            dep.client_mesh = fake_mesh(clients)
            dep.db_mesh = fake_mesh(db)
            dep.__post_init__()
            assert dep.fan_in == expect, (clients, db, dep.fan_in)
            # plan == deployment: one ceiling-division source for both
            assert P.fan_in_ratio(clients, db) == dep.fan_in
        assert P.fan_in_ratio is fan_in_ratio

    def test_deployment_star_exports_helpers(self):
        """Regression: ``make_colocated_1d`` was missing from __all__ —
        invisible to star imports and check_docs dotted-ref resolution."""
        from repro.core import deployment as D
        assert "make_colocated_1d" in D.__all__
        assert "make_clustered_1d" in D.__all__
        ns = {}
        exec("from repro.core.deployment import *", ns)
        assert callable(ns["make_colocated_1d"])

    def test_elem_spec_threaded_through_staging(self):
        """Regression: ``Clustered.stage`` discarded the table spec
        (``elem_sharding(None)``), so spec-dependent layouts never
        applied.  The staged element must land with the spec-fitted
        element sharding."""
        from jax.sharding import PartitionSpec as P
        dep = make_clustered_1d(elem_spec=P("data", None))
        srv = StoreServer(dep)
        spec = srv.create_table(TableSpec("t", shape=(4, 6), capacity=4))
        srv.put("t", S.make_key(0, 0), jnp.ones((4, 6)))
        v, found = srv.get("t", S.make_key(0, 0))
        assert bool(found)
        assert dep.elem_sharding(spec).spec == P("data", None)
        # non-divisible element dim falls back to replicated, not an error
        spec3 = TableSpec("odd", shape=(3, 6), capacity=4)
        fitted = dep.elem_sharding(spec3)
        assert fitted.mesh is dep.db_mesh
        staged = dep.stage(jnp.ones((3, 6)), spec3)
        assert staged.shape == (3, 6)
        # an elem_spec LONGER than the element rank stays loud
        with pytest.raises(ValueError):
            dep.elem_sharding(TableSpec("r1", shape=(4,), capacity=4))


class TestBackoffDeadlines:
    """Satellite: exponential backoff must clamp its sleeps to the
    remaining budget instead of overshooting ``timeout`` by up to
    ``max_interval``."""

    def test_wait_watermark_never_overshoots(self):
        srv = StoreServer()
        srv.create_table(TableSpec("t", shape=(2,), capacity=4))
        t0 = time.perf_counter()
        ok = srv.wait_watermark("t", 1, timeout=0.15, interval=0.001,
                                max_interval=10.0, strict=False)
        took = time.perf_counter() - t0
        assert not ok
        # without the clamp the doubling backoff sleeps past the deadline
        # by seconds; with it the call returns at ~timeout
        assert took < 0.15 + 0.1, took

    def test_poll_tensor_never_overshoots(self):
        srv = StoreServer()
        srv.create_table(TableSpec("t", shape=(2,), capacity=4))
        client = Client(srv)
        t0 = time.perf_counter()
        ok = client.poll_tensor("missing", table="t", timeout=0.15,
                                interval=0.001, max_interval=10.0,
                                strict=False)
        took = time.perf_counter() - t0
        assert not ok
        assert took < 0.15 + 0.25, took   # polls dispatch device ops

    def test_wait_watermark_still_succeeds_late(self):
        srv = StoreServer()
        srv.create_table(TableSpec("t", shape=(2,), capacity=4))
        import threading

        def put_later():
            time.sleep(0.05)
            srv.put("t", S.make_key(0, 0), jnp.zeros((2,)))

        threading.Thread(target=put_later, daemon=True).start()
        assert srv.wait_watermark("t", 1, timeout=5.0)


class TestOverlapPipeline:
    """Double-buffered staging (chunk N's reshard overlapped with chunk
    N+1's collect-scan) must be byte-identical to serial staging across
    {divisible, masked-tail} captures x {ring wrap, no wrap} x chaos
    restage — same table leaves, same watermark, same staged hops; the
    pipeline only adds drain dispatches, never data differences."""

    def _run(self, overlap, *events, capacity=16, length=8, emit_every=2,
             n_chunks=3):
        from repro.core.faults import FaultEvent, FaultPlan, RetryPolicy
        plan = FaultPlan(events=tuple(events),
                         retry=RetryPolicy(interval=1e-4,
                                           max_interval=1e-3))
        srv = StoreServer(make_clustered_1d(overlap=overlap), faults=plan)
        srv.create_table(TableSpec("t", shape=(3,), capacity=capacity))
        cli = Client(srv)
        for i in range(n_chunks):
            cli.capture_scan("t", _step, jnp.zeros(()), length,
                             emit_every=emit_every, t0=i * length)
        cli.drain_captures("t")
        return srv, cli

    def _assert_parity(self, **kw):
        ov_srv, ov_cli = self._run(True, **kw)
        se_srv, se_cli = self._run(False, **kw)
        assert ov_srv.watermark("t") == se_srv.watermark("t")
        _assert_states_equal(ov_srv.checkout("t"), se_srv.checkout("t"))
        ov, se = ov_srv.stats(), se_srv.stats()
        # one hop per wire crossing, identically in both schedules
        assert ov["staged_transfers"] == se["staged_transfers"]
        return ov_srv, se_srv, ov_cli, se_cli

    def test_divisible_no_wrap(self):
        # 3 chunks x 4 puts, capacity 16: exact buckets, no ring wrap
        ov, se, *_ = self._assert_parity(capacity=16, length=8,
                                         emit_every=2)
        assert ov.watermark("t") == 12
        assert ov.stats()["staged_transfers"] == 3
        # overlap costs exactly the end-of-capture drain flush
        assert ov.stats()["op_count"] == se.stats()["op_count"] + 1

    def test_masked_tail_no_wrap(self):
        # length 7, emit_every 2 -> 4 live rows + a masked bucket tail
        ov, *_ = self._assert_parity(capacity=16, length=7, emit_every=2)
        assert ov.watermark("t") == 11

    def test_divisible_ring_wrap(self):
        # 12 puts into capacity 4: wraps twice, last writer wins
        ov, *_ = self._assert_parity(capacity=4, length=8, emit_every=2)
        assert ov.watermark("t") == 12
        assert int(ov.checkout("t").count) == 12

    def test_masked_tail_ring_wrap(self):
        ov, *_ = self._assert_parity(capacity=4, length=7, emit_every=2)
        assert ov.watermark("t") == 11

    def test_chaos_restage_parity(self):
        """A dropped transfer mid-pipeline forces the drain-on-restage
        flush; a later duplicate is deduped by the ack set.  Both
        schedules retry under the same chunk id and land byte-identical
        to each other and to the fault-free run."""
        from repro.core.faults import FaultEvent
        events = (FaultEvent("drop_chunk", table="t", at=1),
                  FaultEvent("dup_chunk", table="t", at=3))
        ov, se, ov_cli, se_cli = self._assert_parity(capacity=8, length=8,
                                                     emit_every=2,
                                                     n_chunks=3)
        base_wm = ov.watermark("t")
        ov_srv, ov_cli2 = self._run(True, *events, capacity=8)
        se_srv, se_cli2 = self._run(False, *events, capacity=8)
        assert ov_cli2.retries == 1 == se_cli2.retries
        assert ov_srv.stats()["faults_injected"] == 2
        assert ov_srv.watermark("t") == se_srv.watermark("t") == base_wm
        _assert_states_equal(ov_srv.checkout("t"), se_srv.checkout("t"))
        _assert_states_equal(ov_srv.checkout("t"), ov.checkout("t"))
        # drop pays its hop again on retry, dup pays one extra: +2 hops,
        # identically in both schedules
        assert ov_srv.stats()["staged_transfers"] == 5
        assert se_srv.stats()["staged_transfers"] == 5


@pytest.mark.slow
def test_clustered_core_real_split_mesh():
    """The core clustered mechanics on a REAL 4-device split (2 clients +
    2 db): the staged chunk equals the co-located replay byte-for-byte,
    staged transfers count one per chunk, the element layout honors the
    fitted ``elem_spec``, and the slot-partitioned slab lives only on the
    db devices."""
    run_subprocess(textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core import (Client, StoreServer, TableSpec,
                                make_clustered_1d)
        from repro.core import store as S

        def step(c, t):
            return c + 1.0, S.make_key(0, t), \\
                jnp.arange(8, dtype=jnp.float32) * (t + 1.0)

        # slab_axis colliding with an elem_spec axis is rejected (a
        # partitioned slot lives whole on its shard)
        try:
            make_clustered_1d(db_fraction=0.5, elem_spec=P("data"),
                              slab_axis="data")
            raise SystemExit("collision not rejected")
        except ValueError:
            pass

        # 2 clients : 2 db, slab slot-partitioned over the db mesh
        dep = make_clustered_1d(db_fraction=0.5, slab_axis="data")
        assert dep.fan_in == 1
        srv = StoreServer(dep)
        spec = srv.create_table(TableSpec("t", shape=(8,), capacity=8))

        # placement: slab slot-partitioned on the two db devices only
        slab = srv.checkout("t").slab
        devs = sorted(d.id for s in slab.addressable_shards
                      for d in [s.device])
        db_ids = sorted(d.id for d in dep.db_mesh.devices.ravel())
        assert sorted(set(devs)) == db_ids, (devs, db_ids)

        # fused chunk: ONE staged hop, byte-identical to local replay.
        # Overlap parks the chunk in the two-slot pipeline; draining
        # flushes it in one extra store op without re-staging.
        cli = Client(srv)
        cli.capture_scan("t", step, jnp.zeros(()), 10, emit_every=2)
        cli.drain_captures("t")
        st = srv.stats()
        assert st["staged_transfers"] == 1 and st["op_count"] == 2
        srv2 = StoreServer()
        srv2.create_table(TableSpec("t", shape=(8,), capacity=8))
        Client(srv2).capture_scan("t", step, jnp.zeros(()), 10,
                                  emit_every=2)
        for a, b in zip(srv.checkout("t"), srv2.checkout("t")):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

        # per-verb element staging counts its hop
        srv.put("t", S.make_key(0, 99), jnp.ones((8,)))
        assert srv.stats()["staged_transfers"] == 2

        # element-sharded layout (no slot partitioning): staged elements
        # land sharded across the db devices; non-divisible dims fit back
        # to replicated instead of mis-placing
        dep2 = make_clustered_1d(db_fraction=0.5, elem_spec=P("data"))
        spec8 = TableSpec("e", shape=(8,), capacity=4)
        staged = dep2.stage(jnp.ones((8,)), spec8)
        assert len({s.device.id for s in staged.addressable_shards}) == 2
        assert max(s.data.nbytes for s in staged.addressable_shards) \\
            == staged.nbytes // 2
        assert dep2.elem_sharding(TableSpec("o", shape=(3,), capacity=4)
                                  ).spec == P(None)

        # staged gather: assembled on the db mesh, returned to clients
        vals, ok = srv.sample_staged("t", jax.random.key(0), 4)
        assert bool(ok) and vals.shape == (4, 8)
        vdevs = {d.id for s in vals.addressable_shards
                 for d in [s.device]}
        client_ids = {d.id for d in dep.client_mesh.devices.ravel()}
        assert vdevs <= client_ids, (vdevs, client_ids)
        assert srv.stats()["staged_transfers"] == 3
        print("CLUSTERED_CORE_OK")
    """), n_devices=4, timeout=600.0)
