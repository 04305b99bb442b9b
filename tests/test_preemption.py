"""Preemption: SIGTERM mid-training -> checkpoint at step boundary -> resume."""
import os
import signal

import jax
import jax.numpy as jnp
import optax

from k8s_distributed_deeplearning_tpu.models import mnist
from k8s_distributed_deeplearning_tpu.parallel import data_parallel as dp
from k8s_distributed_deeplearning_tpu.parallel import mesh as mesh_lib
from k8s_distributed_deeplearning_tpu.train import data as data_lib
from k8s_distributed_deeplearning_tpu.train import loop
from k8s_distributed_deeplearning_tpu.train.checkpoint import Checkpointer
from k8s_distributed_deeplearning_tpu.train.preemption import PreemptionHandler


def _setup(mesh):
    model = mnist.MNISTConvNet()
    params = model.init(jax.random.key(0), jnp.zeros((1, 28, 28, 1)),
                        train=False)["params"]
    opt = optax.adam(1e-3)
    state = dp.init_state(dp.replicate(params, mesh), opt, mesh)
    step = dp.make_train_step(lambda p, b, r: mnist.loss_fn(model, p, b, r),
                              opt, mesh)
    x, y = data_lib.synthetic_mnist(16, seed=0)
    batch = dp.shard_batch({"image": x, "label": y}, mesh)

    def batches(start):
        while True:
            yield batch
    return state, step, batches


def test_sigterm_checkpoints_and_stops(tmp_path, mesh8):
    """A real SIGTERM mid-step exits the loop at the boundary with a save."""
    state, step, batches = _setup(mesh8)
    handler = PreemptionHandler.install()
    try:
        calls = {"n": 0}

        def counting_step(s, b, r):
            calls["n"] += 1
            if calls["n"] == 3:       # deliver SIGTERM mid-training
                os.kill(os.getpid(), signal.SIGTERM)
            return step(s, b, r)

        ck = Checkpointer(str(tmp_path / "ck"))
        out = loop.fit(counting_step, state, batches, num_steps=50,
                       rng=jax.random.key(0), checkpointer=ck,
                       checkpoint_every=1000, preemption=handler)
        assert handler.triggered
        assert calls["n"] == 3, "loop must stop at the signalled step"
        assert int(jax.device_get(out.step)) == 3
        assert ck.latest_step() == 3
    finally:
        handler.uninstall()


def test_preemption_flag_stops_loop_and_saves(tmp_path, mesh8):
    state, step, batches = _setup(mesh8)
    handler = PreemptionHandler()

    def triggering_step(s, b, r):
        out = step(s, b, r)
        if int(jax.device_get(out[0].step)) == 3:
            handler.request()
        return out

    ck = Checkpointer(str(tmp_path / "ck"))
    out = loop.fit(triggering_step, state, batches, num_steps=50,
                   rng=jax.random.key(0), checkpointer=ck,
                   checkpoint_every=1000, preemption=handler)
    assert int(jax.device_get(out.step)) == 3
    assert ck.latest_step() == 3

    # Restart: the loop resumes from the preemption checkpoint, not step 0.
    state2, step2, batches2 = _setup(mesh8)
    ck2 = Checkpointer(str(tmp_path / "ck"))
    out2 = loop.fit(step2, state2, batches2, num_steps=6,
                    rng=jax.random.key(0), checkpointer=ck2,
                    checkpoint_every=1000)
    assert int(jax.device_get(out2.step)) == 6


def test_real_sigterm_sets_flag(mesh8):
    handler = PreemptionHandler.install()
    try:
        assert not handler.triggered
        os.kill(os.getpid(), signal.SIGTERM)
        assert handler.triggered
    finally:
        handler.uninstall()


def test_agreed_single_process_equals_local_flag():
    h = PreemptionHandler()
    assert h.agreed() is False
    h.request()
    assert h.agreed() is True
