"""End-to-end behaviour of the in-situ coupling system (the paper's §4
workflow at laptop scale) + fault-tolerance properties."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (Client, Colocated, InSituDriver, StoreServer,
                        StragglerPolicy, TableSpec)
from repro.ml import autoencoder as ae
from repro.ml import trainer as tr
from repro.sim import flatplate as fp


FCFG = fp.FlatPlateConfig(nx=8, ny=8, nz=4)
N = FCFG.n_points


def _driver(capacity=16):
    return InSituDriver(tables=[TableSpec("field", shape=(4, N),
                                          capacity=capacity, engine="ring")])


def _producer(n_steps=30, sleep=0.005):
    def fn(client, stop):
        key = jax.random.key(0)
        done = 0
        for step in range(n_steps):
            if stop.is_set():
                break
            snap = fp.snapshot(FCFG, key, step)
            client.send_step("field", step, snap)
            done += 1
            time.sleep(sleep)
        return done
    return fn


def _consumer(epochs=8):
    def fn(client, stop):
        coords = fp.grid_coords(FCFG)
        cfg = tr.TrainerConfig(
            ae=ae.AEConfig(n_points=N, mode="ref", latent=16, mlp_width=16),
            epochs=epochs, gather=6, batch_size=4, lr=1e-3)
        state, history, levels, stats = tr.insitu_train(
            client, coords, cfg, stop_event=stop)
        assert history, "no epochs completed"
        import numpy as _np
        head = _np.mean([h.train_loss for h in history[:2]])
        tail = _np.mean([h.train_loss for h in history[-2:]])
        assert tail < head, \
            f"training loss did not decrease in situ ({head} -> {tail})"
        # register the encoder for the inference phase
        client.set_model("encoder",
                         lambda p, f: ae.encode(p, cfg.ae, levels, f),
                         state.params)
        return len(history)
    return fn


@pytest.mark.slow
def test_insitu_training_end_to_end():
    """Producer and consumer run concurrently, coupled only by the store;
    training converges; component timers land in the paper's buckets."""
    driver = _driver()
    res = driver.run({"sim": _producer(), "ml": _consumer()}, max_wall_s=300)
    assert res.ok, {k: v.error for k, v in res.components.items()}
    assert res.components["sim"].steps == 30
    assert res.components["ml"].steps == 8
    summary = res.timers.summary()
    for bucket in ("client_init", "send", "retrieve", "train"):
        assert bucket in summary, bucket
    # paper claim at this scale: send overhead is far below compute+train
    assert summary["send"]["total_s"] < summary["train"]["total_s"]

    # ---- in-situ inference with the trained model (3-step protocol) ------
    client = driver.client(rank=99)
    assert driver.server.has_model("encoder")
    mu, sd = client.get_metadata("norm_stats")
    snap = fp.snapshot(FCFG, jax.random.key(0), 100)
    x = (snap.T[None] - mu) / sd
    z = client.infer("encoder", x)
    assert z.shape == (1, 16) and bool(jnp.isfinite(z).all())


def test_consumer_never_blocks_on_dead_producer():
    """Straggler/fault tolerance: producer dies after 2 sends — consumer
    still completes its epochs on stale data instead of deadlocking."""
    driver = _driver()

    def dying_producer(client, stop):
        for step in range(2):
            client.send_step("field", step, fp.snapshot(FCFG,
                                                        jax.random.key(0),
                                                        step))
        raise RuntimeError("simulated node failure")

    # stop_on_error=False keeps the fully-loose coupling under test here:
    # the consumer deliberately finishes on stale data after the producer
    # died (the default now fires a prompt shutdown instead).
    res = driver.run({"sim": dying_producer, "ml": _consumer(epochs=3)},
                     max_wall_s=240, stop_on_error=False)
    assert not res.components["sim"].ok
    assert res.components["sim"].error_type == "RuntimeError"
    assert res.failed is None
    assert res.components["ml"].ok, res.components["ml"].error
    assert res.components["ml"].steps == 3


def test_failure_isolation_consumer_crash():
    driver = _driver()

    def bad_consumer(client, stop):
        raise ValueError("simulated OOM")

    res = driver.run({"sim": _producer(n_steps=5), "ml": bad_consumer},
                     max_wall_s=120)
    assert res.components["sim"].ok
    assert not res.components["ml"].ok
    assert "simulated OOM" in res.components["ml"].error
    # the typed taxonomy + prompt-shutdown attribution survive the format
    assert res.components["ml"].error_type == "ValueError"
    assert res.failed == "ml"


def test_three_step_inference_protocol():
    """put_tensor → run_model → get_tensor, each one client call (paper)."""
    server = StoreServer()
    server.create_table(TableSpec("infer_in", shape=(4,), capacity=4,
                                  engine="hash"))
    server.create_table(TableSpec("infer_out", shape=(2,), capacity=4,
                                  engine="hash"))
    client = Client(server)
    client.set_model("head", lambda p, x: x @ p["w"],
                     {"w": jnp.ones((4, 2))})
    client.put_tensor("x", jnp.arange(4.0), table="infer_in")
    client.run_model("head", inputs=["x"], outputs=["y"],
                     table="infer_in", out_table="infer_out")
    y, found = client.get_tensor("y", table="infer_out")
    assert bool(found)
    np.testing.assert_allclose(np.asarray(y), [6.0, 6.0])
    # all three components timed (paper Fig. 7 buckets)
    s = client.timers.summary()
    assert {"send", "model_eval", "retrieve"} <= set(s)


def test_in_memory_checkpoint_restart():
    """The store doubles as an in-RAM checkpoint: a 'failed' trainer
    restarts from the parked state without touching the filesystem."""
    from repro.train.checkpoint import MemoryCheckpoint
    server = StoreServer()
    mc = MemoryCheckpoint(server)
    state = {"w": jnp.arange(3.0), "step": jnp.int32(7)}
    mc.save(7, state)
    got = mc.restore()
    assert got is not None
    step, restored = got
    assert step == 7
    np.testing.assert_allclose(np.asarray(restored["w"]), [0, 1, 2])


@pytest.mark.parametrize("failed", [False, True])
def test_launcher_exit_code_reports_component_failure(monkeypatch, capsys,
                                                      failed):
    """``python -m repro.launch.insitu`` exits non-zero exactly when a
    component recorded an error (the orchestrator isolates component
    exceptions, so the session itself returns normally)."""
    from types import SimpleNamespace

    from repro.core.orchestrator import ComponentResult
    from repro.launch import insitu

    comps = {"producer": ComponentResult(name="producer"),
             "trainer": ComponentResult(name="trainer")}
    if failed:
        comps["trainer"].error = "Traceback: boom"
        comps["trainer"].error_type = "RuntimeError"
    monkeypatch.setattr(insitu, "run", lambda **kw: SimpleNamespace(
        run=SimpleNamespace(components=comps)))
    monkeypatch.setattr(insitu, "configure_compile_cache", lambda root: "")
    monkeypatch.setattr("sys.argv", ["insitu"])
    assert insitu.main() == (1 if failed else 0)
    assert ("'trainer' failed" in capsys.readouterr().err) == failed
