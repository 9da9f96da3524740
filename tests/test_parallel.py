"""Distribution machinery: sharding rules, pipeline parallelism (multi-
device via subprocess), gradient compression, co-located zero-collective
proof, clustered transfer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.parallel import sharding as shd
from repro.parallel.sharding import auto_mesh
from repro.parallel.compress import (ErrorFeedback, dequantize_int8,
                                     quantize_int8)

from conftest import run_subprocess


class TestShardingRules:
    def test_spec_for_filters_missing_axes(self):
        mesh = auto_mesh((1,), ("data",))
        spec = shd.spec_for(("batch", "heads"), mesh)
        assert tuple(spec) == ("data", None)       # no pod/model in mesh

    def test_no_axis_reuse(self):
        mesh = auto_mesh((1,), ("data",))
        spec = shd.spec_for(("batch", "embed"), mesh)   # both want "data"
        used = [s for s in tuple(spec) if s is not None]
        assert len(used) == len(set(used)) <= 1

    def test_fitted_sharding_keeps_divisible(self):
        mesh = auto_mesh((1,), ("model",))
        sh = shd.fitted_sharding(mesh, (7,), ("vocab",))
        assert tuple(sh.spec) == ("model",)     # 7 % 1 == 0
        # non-divisible drop is exercised at 16-way in the dry-run tests

    def test_param_spec_init(self):
        spec = {"w": shd.ParamSpec((4, 8), ("embed", "mlp")),
                "b": shd.ParamSpec((8,), (None,), "zeros")}
        params = shd.init_params(jax.random.key(0), spec, jnp.float32)
        assert params["w"].shape == (4, 8)
        assert float(jnp.abs(params["b"]).sum()) == 0.0

    def test_shard_noop_without_mesh(self):
        x = jnp.ones((4, 4))
        assert shd.shard(x, "batch", None) is x


@pytest.mark.slow
def test_pipeline_matches_sequential():
    """2-stage GPipe over ppermute == plain sequential stack (fwd + grads)."""
    run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.parallel.sharding import auto_mesh
        from repro.parallel.pipeline import pipeline_forward, split_stages
        mesh = auto_mesh((2,), ("pod",))
        P_layers, D, M, mb = 4, 8, 4, 2
        key = jax.random.key(0)
        w = jax.random.normal(key, (P_layers, D, D)) * (0.5 / D**0.5)

        def layer(wi, x):
            return x + jnp.tanh(x @ wi)

        def stage_fn(w_stage, x):       # w_stage [P/2, D, D]
            def body(x, wi):
                return layer(wi, x), None
            x, _ = jax.lax.scan(body, x, w_stage)
            return x

        x = jax.random.normal(jax.random.key(1), (M, mb, D))
        # sequential reference
        ref = x
        def body(c, wi):
            return layer(wi, c), None
        ref, _ = jax.lax.scan(body, x.reshape(M*mb, D), w)
        ref = ref.reshape(M, mb, D)

        staged = split_stages(w, 2)
        out = pipeline_forward(stage_fn, staged, x, mesh, stage_axis="pod")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)

        # grads flow through the pipeline
        def loss_pipe(w_staged):
            return jnp.sum(pipeline_forward(stage_fn, w_staged, x, mesh,
                                            stage_axis="pod") ** 2)
        def loss_ref(w_):
            h, _ = jax.lax.scan(body, x.reshape(M*mb, D), w_)
            return jnp.sum(h ** 2)
        g_pipe = jax.grad(loss_pipe)(staged).reshape(w.shape)
        g_ref = jax.grad(loss_ref)(w)
        np.testing.assert_allclose(np.asarray(g_pipe), np.asarray(g_ref),
                                   atol=2e-4)
        print("PIPELINE_OK")
    """, n_devices=2)


@pytest.mark.slow
def test_colocated_put_has_zero_collectives():
    """THE paper claim, structurally: a co-located (sharding-aligned) store
    put compiles to zero collective ops; a clustered (misaligned) staging
    transfer does not."""
    run_subprocess("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import store as S
        from repro.core.store import TableSpec
        from repro.analysis.hlo import collective_bytes, count_ops
        from repro.parallel.sharding import auto_mesh
        mesh = auto_mesh((8,), ("data",))
        spec = TableSpec("f", shape=(64, 128), capacity=4, engine="ring")
        slab_sh = NamedSharding(mesh, P(None, "data", None))
        elem_sh = NamedSharding(mesh, P("data", None))
        state = S.init_table(spec, slab_sh)
        val = jax.ShapeDtypeStruct((64, 128), jnp.float32, sharding=elem_sh)
        key = jax.ShapeDtypeStruct((), jnp.uint32)
        st_abs = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding),
            state)
        lowered = jax.jit(lambda st, k, v: S.put(spec, st, k, v),
                          donate_argnums=0).lower(st_abs, key, val)
        txt = lowered.compile().as_text()
        cb = collective_bytes(txt)
        assert cb.get("total", 0) == 0, f"co-located put has collectives: {cb}"

        # clustered: element resharded from data-sharded to replicated
        # (the dedicated-DB hop) — must show collective traffic
        lowered2 = jax.jit(lambda v: v,
                           out_shardings=NamedSharding(mesh, P())
                           ).lower(val)
        cb2 = collective_bytes(lowered2.compile().as_text())
        assert cb2.get("total", 0) > 0, f"clustered stage shows none: {cb2}"
        print("ZERO_COLLECTIVE_OK", cb, cb2)
    """, n_devices=8)


@pytest.mark.slow
def test_colocated_fused_put_path_collective_free():
    """Extends the zero-collective proof to the FUSED tier: a whole
    ``capture_scan`` chunk (k solver steps + k ring puts in one dispatch)
    against a co-located slab-sharded table must also compile to zero
    collectives — fusing the producer must not introduce any resharding."""
    run_subprocess("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import store as S
        from repro.core.store import TableSpec
        from repro.analysis.hlo import assert_collective_free
        from repro.parallel.sharding import auto_mesh
        mesh = auto_mesh((8,), ("data",))
        spec = TableSpec("f", shape=(64, 128), capacity=4, engine="ring")
        slab_sh = NamedSharding(mesh, P(None, "data", None))
        state = S.init_table(spec, slab_sh)
        elem_sh = NamedSharding(mesh, P("data", None))

        def step_fn(carry, t):
            # element dims carry the SAME sharding as the slab (co-located)
            snap = jax.lax.with_sharding_constraint(
                carry * (1.0 + t.astype(jnp.float32)), elem_sh)
            return carry, S.make_key(0, t), snap

        st_abs = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=a.sharding), state)
        carry = jax.ShapeDtypeStruct((64, 128), jnp.float32,
                                     sharding=elem_sh)
        lowered = jax.jit(
            lambda st, c: S.capture_scan_impl(spec, st, step_fn, c, 8, 2),
            donate_argnums=0).lower(st_abs, carry)
        assert_collective_free(lowered.compile().as_text(),
                               "co-located fused capture_scan")
        print("FUSED_ZERO_COLLECTIVE_OK")
    """, n_devices=8)


@pytest.mark.slow
def test_slab_sharded_epoch_no_table_allgather():
    """The slab-sharded data plane's structural claims, from compiled HLO:

    1. the slab-sharded epoch (tier ``slab_sharded``) contains NO
       all-gather — the table enters the shard_map pre-partitioned and the
       batch is reassembled by an explicit psum (all-reduce), so the
       collective moved from an implicit whole-slab gather to an explicit
       per-epoch batch sum;
    2. the *contrast*: the replicated-entry tier fed the same sharded
       table MUST all-gather the slab on entry — proving assertion 1 is
       not vacuous;
    3. the co-located fused put path (a whole capture_scan chunk) stays
       collective-free even when the slab it writes is slot-axis sharded.
    """
    run_subprocess("""
        import jax, jax.numpy as jnp
        from dataclasses import replace
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.analysis.hlo import assert_collective_free, count_ops
        from repro.core import store as S
        from repro.core.store import TableSpec
        from repro.ml import autoencoder as ae, trainer as tr
        from repro.parallel.sharding import data_mesh, slab_sharding
        from repro.sim import flatplate as fp
        from repro.train import optimizer as opt

        fcfg = fp.FlatPlateConfig(nx=8, ny=8, nz=4)
        n = fcfg.n_points
        spec = TableSpec("field", shape=(4, n), capacity=16, engine="ring")
        mesh = data_mesh(2)
        sh = slab_sharding(spec, mesh)
        st = S.init_table(spec, sh)

        aecfg = ae.AEConfig(n_points=n, mode="ref", latent=16, mlp_width=16)
        levels = ae.coords_pyramid(aecfg, fp.grid_coords(fcfg))
        tx = opt.adam(1e-3)
        cfg_rep = tr.TrainerConfig(ae=aecfg, gather=6, batch_size=4,
                                   lr=1e-3, mesh=mesh)
        cfg_slab = replace(cfg_rep, slab_sharded=True)
        state0 = tr.init_state(cfg_rep, jax.random.key(0), tx)
        mu, sd = jnp.zeros((4,)), jnp.ones((4,))
        args = (st, state0, jax.random.key(7), mu, sd)

        # 1) slab-sharded entry: zero all-gather, DDP + gather all-reduces
        ep_slab = tr.EPOCH_BUILDERS["slab_sharded"](cfg_slab, levels, tx,
                                                    spec)
        c = count_ops(ep_slab.lower(*args).compile().as_text())
        assert c.get("all-gather", 0) == 0, c
        assert c.get("all-reduce", 0) >= 2, c

        # 2) contrast: replicated entry on the same sharded table
        #    all-gathers the slab
        ep_rep = tr.EPOCH_BUILDERS["sharded_fused"](cfg_rep, levels, tx,
                                                    spec)
        c2 = count_ops(ep_rep.lower(*args).compile().as_text())
        assert c2.get("all-gather", 0) > 0, c2

        # 3) the fused put path stays collective-free against the
        #    slot-axis-sharded slab
        def step_fn(carry, t):
            return carry, S.make_key(0, t), \\
                jnp.broadcast_to(t.astype(jnp.float32), (4, n))
        st_abs = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=a.sharding), st)
        lowered = jax.jit(
            lambda s, c: S.capture_scan_impl(spec, s, step_fn, c, 8, 2),
            donate_argnums=0).lower(st_abs, jnp.zeros(()))
        assert_collective_free(lowered.compile().as_text(),
                               "fused put into slot-sharded slab")
        print("SLAB_HLO_OK", c, c2)
    """, n_devices=2)


@pytest.mark.slow
def test_compressed_allreduce_matches_mean():
    run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.parallel.sharding import auto_mesh
        from repro.parallel.compress import compressed_allreduce
        mesh = auto_mesh((4,), ("data",))
        g = jax.random.normal(jax.random.key(0), (4, 33))   # 4 ranks
        out = compressed_allreduce({"w": g}, mesh, axis="data")["w"]
        ref = g.mean(0)
        err = float(jnp.max(jnp.abs(out - ref)))
        rel = err / float(jnp.max(jnp.abs(ref)))
        assert rel < 0.15, rel          # int8 wire: ~1% typical, 15% bound
        print("COMPRESS_ALLREDUCE_OK", rel)
    """, n_devices=4)


class TestCompression:
    def test_int8_roundtrip_error_bound(self):
        x = jax.random.normal(jax.random.key(0), (1000,))
        qt = quantize_int8(x, block=128)
        y = dequantize_int8(qt, x.shape)
        err = float(jnp.max(jnp.abs(x - y)))
        scale = float(jnp.max(jnp.abs(x)))
        assert err <= scale / 127.0 + 1e-6

    def test_error_feedback_unbiased_over_time(self):
        """Sum of compressed grads + final residual == sum of true grads."""
        ef = ErrorFeedback()
        true_sum = jnp.zeros(64)
        comp_sum = jnp.zeros(64)
        for i in range(20):
            g = {"w": jax.random.normal(jax.random.key(i), (64,)) * 0.01}
            true_sum = true_sum + g["w"]
            _, deq = ef.compress(g)
            comp_sum = comp_sum + deq["w"]
        total_err = float(jnp.max(jnp.abs(
            true_sum - comp_sum - ef.residual["w"])))
        assert total_err < 1e-4

    def test_compression_ratio(self):
        from repro.parallel.compress import compression_ratio
        x = jnp.zeros(4096)
        assert compression_ratio(x) > 3.5


@pytest.mark.slow
def test_elastic_checkpoint_reshard():
    """Save sharded state on a (4,) mesh, restore onto a (2,) mesh —
    the survivor path after losing half the fleet."""
    run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np, tempfile
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.train import checkpoint as ck
        from repro.train.elastic import plan_mesh

        mesh4 = plan_mesh(4, model_degree=1)
        sh4 = NamedSharding(mesh4, P("data"))
        state = {"w": jax.device_put(jnp.arange(16.0), sh4),
                 "step": jnp.int32(5)}
        d = tempfile.mkdtemp()
        ck.save(d, 5, state)

        mesh2 = plan_mesh(2, model_degree=1)
        sh2 = NamedSharding(mesh2, P("data"))
        like = {"w": jax.device_put(jnp.zeros(16), sh2),
                "step": jnp.int32(0)}
        restored = ck.restore(d, like)
        np.testing.assert_allclose(np.asarray(restored["w"]),
                                   np.arange(16.0))
        assert restored["w"].sharding.mesh.shape["data"] == 2
        print("ELASTIC_OK")
    """, n_devices=4)
