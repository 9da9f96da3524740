"""A whole run with the timed path broken underneath reports
``correct: false``: one fault per kind the cell can have (a step that
leaves its state unchanged, half of the batch left out, an answer altered
where it is produced).  The harness's look for a chip is skipped; sizes
are small, on the CPU."""

import jax
import jax.numpy as jnp
import pytest

import _tiny


def _unchanged_epoch(monkeypatch):
    from repro.ml import trainer as tr
    real = tr.make_fused_epoch

    def make(*args, **kw):
        epoch = real(*args, **kw)

        def stale(table_state, state, rng, mu, sd):
            _, metrics = epoch(table_state, state, rng, mu, sd)
            return state, metrics
        return stale
    monkeypatch.setattr(tr, "make_fused_epoch", make)


def _half_batch(monkeypatch):
    from repro.ml import autoencoder as ae
    real = ae.loss_fn

    def half(params, cfg, levels, f):
        return real(params, cfg, levels, f[: max(1, f.shape[0] // 2)])
    monkeypatch.setattr(ae, "loss_fn", half)


def _altered_logit(monkeypatch):
    from repro.ml import resnet
    real = resnet.apply_resnet50

    def altered(params, x):
        y = real(params, x)
        return y.at[:, 0].add(0.05 * jnp.max(jnp.abs(y)))
    monkeypatch.setattr(resnet, "apply_resnet50", altered)
    jax.clear_caches()      # serve_batch is cached per model function


def _altered_snapshot(monkeypatch):
    from repro.sim import flatplate as fp
    real = fp.snapshot

    def altered(cfg, key, step):
        s = real(cfg, key, step)
        return s.at[1, 0].add(0.05 * jnp.max(jnp.abs(s)))
    monkeypatch.setattr(fp, "snapshot", altered)


def _dropped_puts(monkeypatch):
    from repro.core import store as S
    real = S.capture_scan_multi_impl

    def unchanged(spec, state, step_fn, carry, *args, **kw):
        _, carry = real(spec, state, step_fn, carry, *args, **kw)
        return state, carry
    monkeypatch.setattr(S, "capture_scan_multi", jax.jit(
        unchanged, static_argnums=(0, 2, 4, 5, 6),
        static_argnames=("elem_sharding",)))


FAULTS = [("quadconv_ae.train", _unchanged_epoch),
          ("quadconv_ae.train", _half_batch),
          ("resnet50.serve", _altered_logit),
          ("flatplate.capture", _altered_snapshot),
          ("flatplate.capture", _dropped_puts)]


@pytest.mark.parametrize("name,fault", FAULTS,
                         ids=[f"{n}-{f.__name__.strip('_')}" for n, f in FAULTS])
def test_fault_makes_the_run_incorrect(monkeypatch, name, fault):
    fault(monkeypatch)
    res = _tiny.run(name, seed=2 ** 31 + 3)
    assert not res.correct, [(c.name, c.value, c.limit)
                             for c in res.outcome.checks]


def test_sound_run_is_correct():
    res = _tiny.run("quadconv_ae.train", seed=2 ** 31 + 3)
    assert res.correct, [(c.name, c.value, c.limit) for c in res.outcome.checks]
