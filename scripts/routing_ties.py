"""Where the program and its float32 reference part ways in routing, on the
DeepSeek-V3 cell (``deepseek-v3.longdecode16``).

For each seed: the weights the benchmark makes from it, the cell's longest
request (its prompt and a continuation of its output length drawn from
the seed), the program's prefill over that sequence (bf16, the Pallas
kernels, layer by layer, each MoE layer's router input recorded), and the
reference's float32 forward.  One JSON line per seed gives:

- ``stray``: quantiles of the largest distance, over the 256 experts,
  between the program's selection scores and the reference's at each
  position and MoE layer (``stray_highest``: the program's router input
  scored at ``highest`` precision, as the reference scores);
- ``flips``: (layer, position) pairs at which the program picks other held
  experts than the reference, and the least routing delta at which
  ``held_picks`` leaves each undecided (null: decided at every delta);
- ``undecided``: the share of positions left out of the check at each
  delta;
- ``gap``: the check's number, the reference's best logit less its logit
  for the program's argmax, over every position, over those with and
  without a flip, and over the decided ones at each delta; the same for
  the float8 control (``--control``) and for the program with held expert
  1's output times -3 or times 0.5 (``--fault``);
- ``bf16_operands`` (``--bf16``): the reference with every matmul operand
  rounded to bfloat16, against the float32 one: its stray, its flips and
  its gap, for what bfloat16 rounding alone does to the routing.

Run on the chip, from the root of a checkout:

    python3 scripts/routing_ties.py --seeds 11,12 --control 11 --fault 11 \
        --bf16 12 --out ties.jsonl
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "src"), os.path.join(ROOT, "bench")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from harness import model as hm  # noqa: E402
from harness import reference as hr  # noqa: E402
from harness import traffic as ht  # noqa: E402
from repro.models import ffn  # noqa: E402
from repro.models import transformer as tf  # noqa: E402

DELTAS = (0.0005, 0.001, 0.002, 0.003, 0.004, 0.006, 0.008)
FAULTS = {"times_-3": -3.0, "times_0.5": 0.5}
SEQ = 9216              # the cell's longest sequence, padded; 18 x 512


def mm_bf16(eq, a, b, mode, a_axis, b_axis, _mm=hr.mm):
    """The reference's matmul; in ``"bf16"`` its operands rounded to
    bfloat16 first."""
    if mode == "bf16":
        a, b = (v.astype(jnp.bfloat16).astype(jnp.float32) for v in (a, b))
        mode = "f32"
    return _mm(eq, a, b, mode, a_axis, b_axis)


def cell(config="deepseek-v3", mix="longdecode16"):
    arch = hm.load_architecture(ROOT, "mla_moe_lm")
    hr.mm = arch.mm = mm_bf16
    conf = hm.load_config(os.path.join(ROOT, "bench", "configs",
                                       config + ".json"))
    dm = arch.dims(config, conf)
    t = ht.load_traffic(os.path.join(ROOT, "bench", "traffic", mix + ".json"))
    return arch, dm, arch.program_config(dm), t


def program_layer(mcfg, kind, fault=None):
    """One block of the program, jitted: (x', selection scores by the
    program's router, the same at ``highest`` precision, the program's
    routed experts), the last three None for a dense layer."""
    def run(p, x):
        rec = {}
        route, expert_mlp = ffn.route, ffn._expert_mlp

        def spy(p_router, xr, e, rng=None):
            out = route(p_router, xr, e, rng)
            xf = xr.astype(jnp.float32)
            for name, prec in (("select", None), ("select_hi", "highest")):
                z = jnp.einsum("nd,de->ne", xf, p_router["w"], precision=prec)
                rec[name] = jax.nn.sigmoid(z) + p_router["b"][None, :]
            rec["idx"] = out[1]
            return out

        def altered(p_experts, buf, use_kernels, rows=None):
            out = expert_mlp(p_experts, buf, use_kernels, rows)
            return out.at[1].multiply(jnp.asarray(fault, out.dtype))

        ffn.route = spy
        if fault is not None:
            ffn._expert_mlp = altered
        try:
            pos = jnp.arange(x.shape[1])[None]
            y, _, _ = tf.block_seq(p, x, mcfg, pos, jnp.int32(1 << 30), None,
                                   True, kind)
        finally:
            ffn.route, ffn._expert_mlp = route, expert_mlp
        return y, rec.get("select"), rec.get("select_hi"), rec.get("idx")
    return jax.jit(run)


def program(params, toks, pos, mcfg, layers):
    """The program's argmax at ``pos`` and, per MoE layer, its selection
    scores (as run and at ``highest``) and held-expert picks there."""
    x = tf.embed(params["embed"], jnp.asarray(toks)[None])
    sel, sel_hi, idx = [], [], []
    for stack, (kind, n, _) in zip(params["stacks"], tf.stack_meta(mcfg)):
        for i in range(n):
            p = jax.tree_util.tree_map(lambda a: a[i], stack)
            x, s, s_hi, ix = layers[kind](p, x)
            if s is not None:
                sel.append(s[pos])
                sel_hi.append(s_hi[pos])
                idx.append(ix[pos])
    h = tf.apply_norm(params["final_norm"], x[:, pos], mcfg.norm)
    logits = tf.unembed(params["head"], h)[0]
    return (jnp.argmax(logits, -1), jnp.stack(sel), jnp.stack(sel_hi),
            jnp.stack(idx))


def quantiles(v):
    v = np.asarray(v, np.float64).ravel()
    return {q: float(np.quantile(v, float(q))) for q in ("0.5", "0.99", "0.999")} \
        | {"max": float(v.max())}


def gaps(best, ref, choice, flip_any, und):
    g = np.asarray(best - ref[np.arange(len(choice)), choice])
    out = {"all": float(g.max()),
           "flipped": float(g[flip_any].max()) if flip_any.any() else 0.0,
           "unflipped": float(g[~flip_any].max())}
    out["decided"] = {str(d): float(g[~u].max()) for d, u in und.items()}
    return out


def one_seed(seed, arch, dm, mcfg, t, layers, faults, control, fault, bf16):
    t0 = time.perf_counter()
    params = jax.block_until_ready(hm.make_params(mcfg, seed))
    reqs = ht.requests(t, seed, 51.0, dm.vocab)
    r = max(reqs, key=lambda q: len(q.prompt) + q.max_tokens)
    out = np.random.default_rng(seed).integers(1, dm.vocab, r.max_tokens)
    toks = list(r.prompt) + out[:-1].tolist()
    pos = np.arange(len(r.prompt) - 1, len(toks))
    padded = np.zeros(SEQ, np.int32)
    padded[: len(toks)] = toks
    jpos = jnp.asarray(pos)
    choice, sel, sel_hi, idx = program(params, padded, jpos, mcfg, layers)
    ref, sel_ref = arch.reference(params, toks, pos, dm, "f32")
    best = ref.max(-1)
    held = dm.expert_offset + np.arange(dm.experts_held)
    picks = (np.asarray(idx)[..., None] == held).any(-2)           # [L,P,H]
    picks_ref = np.asarray(arch.held_picks(sel_ref, dm, 0.0)[0])
    flip = (picks != picks_ref).any(-1)                              # [L,P]
    und_layer = {}
    for d in DELTAS:
        surely, maybe = arch.held_picks(sel_ref, dm, d)
        und_layer[d] = np.asarray((surely != maybe).any(-1))        # [L,P]
    und = {d: u.any(0) for d, u in und_layer.items()}
    flips = []
    for layer, p in zip(*np.nonzero(flip)):
        least = next((d for d in DELTAS if und_layer[d][layer, p]), None)
        flips.append([int(layer), int(p), least])
    stray = np.abs(np.asarray(sel) - np.asarray(sel_ref)).max(-1)
    stray_hi = np.abs(np.asarray(sel_hi) - np.asarray(sel_ref)).max(-1)
    line = {
        "seed": seed, "request": [len(r.prompt), r.max_tokens],
        "positions": len(pos), "stray": quantiles(stray),
        "stray_highest": quantiles(stray_hi),
        "flips": flips, "flipped_positions": int(flip.any(0).sum()),
        "undecided": {str(d): float(u.mean()) for d, u in und.items()},
        "gap": gaps(best, ref, np.asarray(choice), flip.any(0), und)}
    if seed in control:
        c = arch.reference(params, toks, pos, dm, "fp8")[0].argmax(-1)
        line["control_gap"] = gaps(best, ref, np.asarray(c), flip.any(0), und)
    if seed in fault:
        line["fault_gap"] = {
            name: gaps(best, ref, np.asarray(program(params, padded, jpos, mcfg,
                                                     f)[0]), flip.any(0), und)
            for name, f in faults.items()}
    if seed in bf16:
        lg, sel_b = arch.reference(params, toks, pos, dm, "bf16")
        picks_b = np.asarray(arch.held_picks(sel_b, dm, 0.0)[0])
        flip_b = (picks_b != picks_ref).any(-1)
        line["bf16_operands"] = {
            "stray": quantiles(np.abs(np.asarray(sel_b)
                                      - np.asarray(sel_ref)).max(-1)),
            "flips": int(flip_b.sum()),
            "gap": gaps(best, ref, np.asarray(lg.argmax(-1)), flip_b.any(0),
                        und)}
    line["seconds"] = time.perf_counter() - t0
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default="")
    ap.add_argument("--fault", default="")
    ap.add_argument("--bf16", default="")
    ap.add_argument("--out", required=True,
                    help="a file the JSON lines are appended to")
    args = ap.parse_args(argv)

    def ints(s):
        return [int(v) for v in s.split(",") if v]

    arch, dm, mcfg, t = cell()
    layers = {kind: program_layer(mcfg, kind)
              for kind, _, _ in tf.stack_meta(mcfg)}
    faults = {name: {**layers, "moe": program_layer(mcfg, "moe", f)}
              for name, f in FAULTS.items()}
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "a") as f:
        for seed in ints(args.seeds):
            line = one_seed(seed, arch, dm, mcfg, t, layers, faults,
                            set(ints(args.control)), set(ints(args.fault)),
                            set(ints(args.bf16)))
            print(json.dumps(line), flush=True)
            f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
