"""The plain reference's first step against the system's, at the tiny
preset, and the proof that the tolerances see what they must."""
import json
import os
import types

import numpy as np
import pytest

from conftest import ROOT


def _first_step(config_name, *, system_weight_decay=None, compute="bfloat16"):
    import functools

    import jax
    import jax.numpy as jnp
    from ddp_tpu.models import get_model
    from ddp_tpu.optim.schedule import triangular_lr
    from ddp_tpu.optim.sgd import SGDConfig
    from ddp_tpu.parallel.mesh import make_mesh

    from benchmark import datagen, reference_check

    with open(os.path.join(ROOT, "benchmark", "configs",
                           config_name + ".json")) as f:
        config = json.load(f)
    opt = config["optimizer"]
    mix = {"resident": False, "check_batch_per_chip": 16}
    mesh = make_mesh(1)
    model = get_model(config["model"])
    params, stats = jax.device_get(model.init(jax.random.key(5)))
    images, labels = datagen.make({"n_train": 16}, 5)
    sched_kw = dict(num_epochs=20, steps_per_epoch=6,
                    peak_frac=opt["peak_frac"])
    peak_lr = 0.05
    sgd = SGDConfig(lr=peak_lr, momentum=opt["momentum"],
                    weight_decay=opt["weight_decay"])
    # What the system runs may be made to differ from the configuration:
    # the reference always follows the configuration (``sgd``).
    sys_sgd = (sgd if system_weight_decay is None
               else sgd._replace(weight_decay=system_weight_decay))
    real = reference_check.first_step

    def first_step(**kw):
        # Build the system's step with sys_sgd, the reference with sgd.
        from ddp_tpu.train import step as step_mod
        orig = step_mod.make_train_step
        step_mod.make_train_step = lambda m, _s, *a, **k: orig(
            m, sys_sgd, *a, **k)
        try:
            return real(**kw)
        finally:
            step_mod.make_train_step = orig

    return first_step(
        config=config, mix=mix, mesh=mesh, model=model, sgd=sgd,
        schedule=functools.partial(triangular_lr, base_lr=peak_lr,
                                   **sched_kw),
        sched_kw=sched_kw,
        compute_dtype={"bfloat16": jnp.bfloat16, "float32": None}[compute],
        params_host=params, stats_host=stats, images=images, labels=labels,
        trainer=types.SimpleNamespace(rng=jax.random.key(5)))


@pytest.mark.parametrize("config_name", ["vgg_cifar10", "resnet18_cifar10"])
def test_system_first_step_matches_reference(config_name):
    check = _first_step(config_name)
    assert check["ok"], check
    assert check["lr"] == pytest.approx(0.05)  # taken at the peak
    assert np.isfinite(list(check["errors"].values())).all()


def test_float32_system_is_far_inside_the_tolerances():
    check = _first_step("vgg_cifar10", compute="float32")
    assert check["ok"], check
    assert check["errors"]["momentum_rel"] < 0.02
    assert check["errors"]["decay_rel"] < 0.01


def test_a_dropped_weight_decay_fails_the_check():
    check = _first_step("vgg_cifar10", system_weight_decay=0.0)
    assert not check["ok"]
    assert check["errors"]["decay_rel"] > 0.9  # the whole term is missing
