"""The readings a decoder cell's limits are set from, on the chip.

    python scripts/laguna_s_limits.py --seeds 1,2,3 [--control-seeds 1]
        [--compare-seeds 1] [--steps 48] [--workload CELL] [--rehearse]
        [--out FILE]

Builds the cell (default ``laguna_s.lm_s4096_b1_v12k``; also
``kanana2.lm_s8192_b1_v16k``, or any cell whose reference has a
``control``) as ``benchmark/run.py`` does (pool, weights and state from
``--seed``; ``--rehearse`` at the toy size on the CPU). For the seeds named
it runs ``benchmark/reference/<config>.py``'s ``compare`` on the untrained
state: on the system (``--compare-seeds``), and on the control
(``--control-seeds``: the reference's own loss and logits in bfloat16,
``reference.control``), which has to come out not correct. Then every seed
trains ``--steps`` steps, step ``i`` on batch ``i mod pool`` as the
harness's warm-up and window do, and the losses are printed: the traffic's
``loss_band`` is read from them. One JSON line a seed; ``--out`` keeps them
all.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "laguna_s.lm_s4096_b1_v12k"


def _seeds(text):
    return [int(s) for s in text.split(",") if s]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=_seeds, required=True)
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    ap.add_argument("--compare-seeds", type=_seeds, default=[])
    ap.add_argument("--steps", type=int, default=48)
    ap.add_argument("--workload", default=CELL)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    sys.path[:0] = [os.path.join(ROOT, "benchmark"), ROOT]
    import run

    import jax
    if args.rehearse:
        jax.config.update("jax_platforms", "cpu")
    from apex_tpu import parallel
    from apex_tpu.utils import enable_compile_cache
    if not args.rehearse:
        enable_compile_cache()

    cell = run.load_json("workloads", args.workload + ".json")
    sizes = run.load_json("configs", cell["config"] + ".json")
    traffic = run.load_json("traffic", cell["traffic"] + ".json")
    if args.rehearse:
        sizes, traffic = run.with_toy(sizes), run.with_toy(traffic)
    config = run.load_module("configs", cell["config"])
    reference = run.load_module("reference", cell["config"])
    mesh = parallel.data_parallel_mesh()
    batch = traffic["per_chip_batch"] * len(jax.devices())

    found, step = [], None
    for seed in args.seeds:
        key = run.seed_key(seed)
        pool = run.make_pool(traffic, sizes, key, mesh, batch)
        built = config.build(sizes, key, mesh, pool[0])
        # one step program for every seed: it closes over nothing seeded
        step = step or built["step"]
        carry = built["carry"]
        row = {"seed": seed}
        for name, seeds, of in (
                ("system", args.compare_seeds, built),
                ("control", args.control_seeds,
                 reference.control(built, sizes))):
            if seed in seeds:
                row[name] = reference.compare(sizes, of, carry, pool[0])
        losses = []
        for i in range(args.steps):
            carry, loss, _ = step(carry, *pool[i % len(pool)])
            losses.append(loss)
        row["losses"] = [float(x) for x in jax.device_get(losses)]
        print(json.dumps(row), flush=True)
        found.append(row)
        del carry, built, pool
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(found, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
