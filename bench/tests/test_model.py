"""Configuration files and weights: published values with the program's
departures laid over them, and norm scales that do not move with the
seed."""
import os

import jax
import numpy as np
import pytest

from conftest import BENCH, DUMMY_CONFIG, ROOT
from harness import model

ARCH = model.load_architecture(ROOT, "dense_lm")


def test_departures_are_laid_over_published_values():
    c = model.load_config(os.path.join(BENCH, "configs", "minicpm-2b.json"))
    assert (c["scale_emb"], c["dim_model_base"], c["rms_norm_eps"]) == (
        12, 256, 1e-05)
    dm = ARCH.dims("minicpm-2b", c)
    assert (dm.emb_scale, dm.logit_scale, dm.rms_eps) == (1.0, 1.0, 1e-06)
    assert dm.residual_scale == pytest.approx(1.4 / 40 ** 0.5)
    ARCH.program_config(dm)
    published = {k: v for k, v in c.items() if k != "departures"}
    with pytest.raises(ValueError):
        ARCH.program_config(ARCH.dims("minicpm-2b", published))


def test_unknown_architecture_is_refused():
    with pytest.raises(ValueError):
        model.load_architecture(ROOT, "no_such_architecture")


def test_norm_scales_are_fixed_and_the_rest_follows_the_seed():
    mcfg = ARCH.program_config(ARCH.dims("dummy", DUMMY_CONFIG))
    a, b = model.make_params(mcfg, 1), model.make_params(mcfg, 2 ** 40 + 1)
    flat_a = jax.tree_util.tree_flatten_with_path(a)[0]
    flat_b = jax.tree_util.tree_leaves(b)
    for (path, x), y in zip(flat_a, flat_b):
        same = np.array_equal(np.asarray(x), np.asarray(y))
        assert same == (path[-1].key == "scale"), path
