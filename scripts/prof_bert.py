"""Per-op profile of the BERT-Large LAMB bench step (VERDICT r2 item 3).

Usage: python scripts/prof_bert.py [--batch N] [--seq N] [--top N]
           [--lint]

``--lint`` runs apexlint over the exact jitted step being profiled and
fails (exit 1) on any error-severity finding — the donation audit that
keeps this driver honest: the step carries the whole AmpState (fp32
params + LAMB m/v slots) in argnum 0, and donating it is what keeps
opt state from being re-allocated every step (apexlint APX101 flags
the miss, and quantifies the wasted HBM, if the donation ever drops).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax
import numpy as np


def main():
    batch = 16
    seq = 512
    top = 30
    argv = sys.argv
    if "--batch" in argv:
        batch = int(argv[argv.index("--batch") + 1])
    if "--seq" in argv:
        seq = int(argv[argv.index("--seq") + 1])
    if "--top" in argv:
        top = int(argv[argv.index("--top") + 1])

    from apex_tpu import prof
    from apex_tpu.utils import enable_compile_cache
    import bench

    enable_compile_cache()
    peak = prof.device_peak_flops()     # unknown device: raises

    # the ONE construction of this step (bench row + apexlint flagship
    # share it — see bench._bert_step_builder)
    step, state, (toks, labels), policy, enc, variables = \
        bench._bert_step_builder(batch, seq)

    import tempfile
    import time

    # donate the FULL carried state (argnum 0 = AmpState: fp32 params,
    # LAMB m/v arena slots, scalers) — apexlint's donation rule audits
    # this aliasing from the compiled HLO (--lint below / docs/linting.md)
    jstep = jax.jit(step, donate_argnums=(0,))
    from apex_tpu.prof import hlo as _hlo
    # ONE AOT compile feeds the cost analysis AND (under --lint) the
    # lint HLO pass — BERT-Large compiles are minutes-class, never twice
    compiled = jstep.lower(state, toks, labels).compile()
    ca = _hlo.cost_analysis_of(compiled)
    cost = {"flops": float(ca.get("flops", 0.0)),
            "bytes_accessed": float(ca.get("bytes accessed", 0.0))}

    if "--lint" in argv:
        from apex_tpu import lint
        rep = lint.lint_step(jstep, state, toks, labels, policy=policy,
                             compiled=compiled, fn_name="prof_bert_step")
        print(rep.table())
        if rep.errors:
            sys.exit(1)
    for _ in range(3):
        state, loss = jstep(state, toks, labels)
    float(loss)

    iters = 5
    logdir = tempfile.mkdtemp(prefix="apex_tpu_prof_bert_")
    t0 = time.perf_counter()
    with prof.trace(logdir):
        for _ in range(iters):
            state, loss = jstep(state, toks, labels)
        float(loss)
    wall = (time.perf_counter() - t0) / iters

    from apex_tpu.prof import xplane as _xplane
    profile = _xplane.parse_trace(logdir)
    dev_us = profile.module_us_per_run()    # no device runs: raises
    n_params = sum(int(np.prod(l.shape)) for l in
                   jax.tree_util.tree_leaves(variables["params"]))
    model_flops = 6.0 * n_params * batch * seq
    print(f"batch={batch} seq={seq} params={n_params/1e6:.1f}M")
    print(f"wall/iter={wall*1e6:.0f}us device/iter={dev_us:.0f}us "
          f"xla_flops={cost['flops']:.3g} "
          f"model_flops={model_flops:.3g} "
          f"bytes={cost['bytes_accessed']:.3g}")
    cats = "  ".join(f"{k}={v:.0f}us"
                     for k, v in list(profile.by_category().items())[:8])
    print(cats)
    print(profile.table(top=top))
    print("model-flops MFU:", model_flops / (dev_us * 1e-6) / peak)
    print("seq/s:", batch / (dev_us * 1e-6))


if __name__ == "__main__":
    main()
