"""What ``benchmark/reference/kimi_linear.py``'s comparison catches: the
reference against itself with one thing wrong, at the published widths.

    python scripts/kimi_linear_probes.py [--toy] [--seed N] [--out FILE]

For each probe (a bfloat16 delta-rule state, everything in bfloat16, a
dropped 1/sqrt(192), a rotated k_pe, expert weights normalised over the held
experts only, rows dropped at half an even share) it prints the three
numbers ``compare`` holds to its tolerances: the relative loss difference,
the relative L2 difference of the logits at the compared rows, and the worst
relative L2 difference of the compared gradients on the prefix. The numbers
in the reference's docstring and in PERF.md come from a run of this on the
chip; on a CPU use ``--toy``.
"""

import argparse
import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def kimi_probes(jnp, sizes, length):
    share = length * sizes["num_experts_per_token"] / sizes["router_experts"]
    return {
        "bf16_state": {"state_dtype": jnp.bfloat16},
        "all_bf16": {"dtype": jnp.bfloat16},
        "no_softmax_scale": {"scaled": False},
        "rotated_k_pe": {"rotate": True},
        "weights_over_held_only": {"over_held_only": True},
        "rows_dropped_at_half_a_share": {"drop_after": max(int(share / 2), 1)},
    }


def main(argv=None, config="kimi_linear", make_probes=kimi_probes,
         doc=__doc__):
    """The probes ``make_probes(jnp, sizes, length)`` names, through
    ``benchmark/reference/<config>.py`` on seeded weights of
    ``models.<config>_from_config``. Where the reference has ``slowed``
    (parameters with the delta rule's decay slowed), the logits at those
    parameters are compared too."""
    ap = argparse.ArgumentParser(description=doc.split("\n\n")[0])
    ap.add_argument("--toy", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--length", type=int, default=None)
    ap.add_argument("--only", default=None, help="comma-separated probes")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    sys.path.insert(0, ROOT)
    from apex_tpu import models

    spec = importlib.util.spec_from_file_location(
        "reference_" + config,
        os.path.join(ROOT, "benchmark", "reference", config + ".py"))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    with open(os.path.join(ROOT, "benchmark", "configs",
                           config + ".json")) as f:
        sizes = json.load(f)
    if args.toy:
        sizes = {**sizes, **sizes["toy"]}
    length = args.length or (192 if args.toy else 8192)
    probes = make_probes(jnp, sizes, length)
    if args.only:
        probes = {k: probes[k] for k in args.only.split(",")}
    key = jax.random.PRNGKey(args.seed)
    tokens = jax.random.randint(jax.random.fold_in(key, 1), (length,), 0,
                                sizes["vocab_size"])
    model = getattr(models, config + "_from_config")(sizes)
    params = jax.jit(lambda k: model.init(
        k, jnp.zeros((1, length), jnp.int32))["params"])(key)
    rows = ref.logit_rows(length)
    prefix = tokens[:min(ref.GRAD_PREFIX, length)]
    paths = ref.GRAD_LEAVES
    leaves = [ref._leaf(params, p) for p in paths]
    slow = ref.slowed(params, sizes) if hasattr(ref, "slowed") else None

    def run(probe):
        with jax.default_matmul_precision("highest"):
            fwd = jax.jit(lambda p, t: ref.loss_and_logits(
                p, t, sizes, rows=rows, **probe))
            loss, logits = fwd(params, tokens)
            slow_logits = None if slow is None else fwd(slow, tokens)[1]
            grads = jax.jit(jax.grad(lambda leaves, p, t: ref.lm_loss(
                ref._with_leaves(p, paths, leaves), t, sizes, **probe)))(
                    leaves, params, prefix)
        return float(loss), logits, grads, slow_logits

    base = run({})
    found = {"length": length, "grad_prefix": int(prefix.shape[0]),
             "reference_loss": base[0], "probes": {}}
    for name, probe in probes.items():
        loss, logits, grads, slow_logits = run(probe)
        found["probes"][name] = {
            "loss_rel_diff": abs(loss - base[0]) / abs(base[0]),
            "logit_rel_diff": ref._rel(logits, base[1]),
            **({"logit_row_rel_diff": ref._rows_rel(logits, base[1])}
               if hasattr(ref, "_rows_rel") else {}),
            "grad_rel_diff": {"/".join(p): ref._rel(g, b) for p, g, b
                              in zip(paths, grads, base[2])}}
        if slow is not None:
            found["probes"][name]["slow_logit_rel_diff"] = ref._rel(
                slow_logits, base[3])
        print(json.dumps({name: found["probes"][name]}), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(found, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
