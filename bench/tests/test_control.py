"""The control, the reference computed in float8 put in the program's
place, comes out not correct against the limits (at the dummy size)."""
import types

import jax
import numpy as np

from conftest import DUMMY_CONFIG, DUMMY_LIMITS, ROOT
from harness import graph, model, serve

ARCH = model.load_architecture(ROOT, "dense_lm")


def ctx_for(workload, seed, traffic):
    dm = ARCH.dims("dummy", DUMMY_CONFIG)
    ctx = types.SimpleNamespace(
        arch=ARCH, dims=dm, mcfg=ARCH.program_config(dm), seed=seed,
        control=False,
        traffic=traffic, limits=DUMMY_LIMITS[workload], checks={},
        log=lambda msg: None)

    def compare(name, value, limit=None):
        ctx.checks[name] = {"value": value,
                            "limit": ctx.limits[name]["limit"]}
    ctx.compare = compare
    return ctx


def test_float8_logits_fail_the_graph_limit():
    for seed in (1, 2, 3):
        ctx = ctx_for("dummy.graph", seed, {})
        params = model.make_params(ctx.mcfg, seed)
        tokens = np.asarray(jax.random.randint(
            jax.random.key(seed), (1, 64), 0, 512))
        got = ARCH.logits(params, tokens[0], np.arange(64), ctx.dims,
                          "fp8")[None]
        graph.check(ctx, got, tokens)
        c = ctx.checks["logit_err"]
        assert c["value"] > c["limit"], (seed, c)


def test_float8_greedy_tokens_fail_the_serve_limit():
    for seed in (1, 2, 3):
        ctx = ctx_for("dummy.serve", seed, {"check_requests": 3})
        params = model.make_params(ctx.mcfg, seed)
        rng = np.random.default_rng(seed)
        done = []
        for rid in range(3):
            prompt = rng.integers(1, 512, 30 + 10 * rid).tolist()
            out = []
            for _ in range(12):       # greedy decode by the float8 reference
                lg = ARCH.logits(params, prompt + out,
                                 [len(prompt) + len(out) - 1], ctx.dims,
                                 "fp8")
                out.append(int(np.argmax(lg[0])))
            done.append(types.SimpleNamespace(prompt=prompt, output=out))
        serve.check(ctx, done)
        c = ctx.checks["token_gap"]
        assert c["value"] > c["limit"], (seed, c)
