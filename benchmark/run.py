"""One cell of the benchmark, one run.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is a training job on every local chip: a configuration
(``configs/<config>.py`` builds its donated step from the library's public
API, ``configs/<config>.json`` holds its sizes) fed with a traffic mix
(``traffic/<mix>.json``: shapes, batch, pool, loss band). The runner holds
no branch on a cell's, a configuration's or a metric's name: it finds each
by the name in ``workloads/<cell>.json`` and takes what the file gives.
A later PR adds a cell, a configuration, a mix, a per-layer metric
(``layer_metrics/<name>.py``) or a plain reference
(``reference/<config>.py``) by adding files.

The loop is closed and keeps one step in flight: step i+1 is dispatched,
then the loss of step i is awaited, and the time of each completion is
kept. ``--trace 0`` prints the end-to-end metrics; ``--trace 1`` profiles
about ten steps of the window and prints the per-layer metrics and a
breakdown. The last line of stdout is the one JSON object the driver
reads; progress goes on earlier lines.

It needs the chips the cell names: without a TPU, with another number of
chips, or on a chip that ``peaks.json`` does not know, it prints no result
and exits non-zero. ``--rehearse [N]`` (the driver never passes it) runs
the same code at the configuration's ``toy`` size on N virtual CPU
devices, to debug the harness; it reports no metric and is never correct.
"""

import time

_T0 = time.perf_counter()       # set-up is counted from here

import argparse                 # noqa: E402
import importlib.util           # noqa: E402
import json                     # noqa: E402
import math                     # noqa: E402
import os                       # noqa: E402
import shutil                   # noqa: E402
import statistics               # noqa: E402
import sys                      # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT = os.path.join(HERE, ".out")

WARMUP_STEPS = 3
TRACE_FROM_STEP = 5     # of the window; the profiler then covers
TRACE_STEPS = 12        # twelve steps, of which the reduction keeps ten
E2E_UNITS = {"samples_per_s_per_chip": "samples/s/chip",
             "step_ms_p90": "ms", "setup_s": "s"}


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(kind, name, required=True):
    """``benchmark/<kind>/<name>.py``, found by name."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.exists(path):
        if required:
            raise FileNotFoundError(path)
        return None
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def say(phase, **fields):
    print(json.dumps({"phase": phase,
                      "at_s": round(time.perf_counter() - _T0, 3),
                      **fields}), flush=True)


def with_toy(spec):
    """The file's ``toy`` entries laid over its real ones."""
    return {**spec, **spec.get("toy", {})}


# ---- traffic: one generator for every mix ----------------------------------

def seed_key(seed):
    """A raw threefry key from a seed of up to 64 bits (a driver's seed
    does not fit 32 signed bits)."""
    import numpy as np
    return np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                    np.uint32)


def make_pool(traffic, sizes, key, mesh, global_batch):
    """``pool`` batches, each a tuple of the mix's arrays, made on the
    devices in one jitted call and sharded over the batch."""
    import jax
    import jax.numpy as jnp

    from apex_tpu import parallel

    def one(key, spec):
        shape = (global_batch, *spec["shape"])
        dtype = jnp.dtype(spec["dtype"])
        if spec["dist"] == "randint":
            high = spec["high"]
            high = sizes[high] if isinstance(high, str) else high
            x = jax.random.randint(key, shape, 0, high, dtype)
        elif spec["dist"] == "uniform":
            x = jax.random.uniform(key, shape, jnp.float32).astype(dtype)
        else:
            raise ValueError(f"traffic: unknown dist {spec['dist']!r}")
        if "keep_share" in spec:
            # exactly round(share * n) entries of each row keep their
            # value, the others take ``fill`` (an unlabelled position)
            n = shape[-1]
            keep = round(spec["keep_share"] * n)
            score = jax.random.uniform(jax.random.fold_in(key, 1), shape)
            rank = jnp.argsort(jnp.argsort(score, axis=-1), axis=-1)
            x = jnp.where(rank < keep, x, jnp.asarray(spec["fill"], dtype))
        return x

    def generate(key):
        return tuple(
            tuple(one(jax.random.fold_in(jax.random.fold_in(key, b), a), spec)
                  for a, spec in enumerate(traffic["arrays"]))
            for b in range(traffic["pool"]))

    return jax.jit(generate,
                   out_shardings=parallel.batch_sharding(mesh))(key)


# ---- the measured loop ------------------------------------------------------

class Tracer:
    """Starts the profiler after ``TRACE_FROM_STEP`` completions of the
    window and stops it ``TRACE_STEPS`` later, with a step in flight
    both times."""

    def __init__(self, out_dir):
        self.dir = out_dir
        self.running = False
        shutil.rmtree(out_dir, ignore_errors=True)

    def tick(self, completed):
        import jax
        if completed == TRACE_FROM_STEP:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0    # host spans are TraceMe's
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.running = True
        elif completed == TRACE_FROM_STEP + TRACE_STEPS:
            self.stop()

    def stop(self):
        import jax
        if self.running:
            jax.profiler.stop_trace()
            self.running = False


def run_window(step, carry, pool, seconds, tracer=None):
    """Closed loop, one step in flight, until ``seconds`` have passed and
    the step then in flight is done. Returns the carry, the window's
    length, the interval to each completion and the device scalars
    ``(loss, finite)`` of every step."""
    import jax
    from jax.profiler import TraceAnnotation

    outs, done = [], []
    n = 0

    def dispatch():
        nonlocal carry, n
        with TraceAnnotation("bench/dispatch"):
            carry, loss, finite = step(carry, *pool[n % len(pool)])
        n += 1
        return loss, finite

    t0 = last = time.perf_counter()
    pending = dispatch()
    while True:
        nxt = dispatch()
        with TraceAnnotation("bench/wait_loss"):
            jax.block_until_ready(pending[0])
        now = time.perf_counter()
        done.append(now - last)
        last = now
        outs.append(pending)
        pending = nxt
        if now - t0 >= seconds:
            break
        if tracer is not None:
            tracer.tick(len(done))
    with TraceAnnotation("bench/drain"):
        jax.block_until_ready((pending, carry))
    now = time.perf_counter()
    done.append(now - last)
    outs.append(pending)
    if tracer is not None:
        tracer.stop()
    return carry, now - t0, done, outs


def percentile(values, q):
    """Nearest-rank: the smallest value with at least q% at or below it."""
    ordered = sorted(values)
    return ordered[max(0, math.ceil(q / 100 * len(ordered)) - 1)]


# ---- one run ------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", type=int, nargs="?", const=1, default=0,
                    metavar="N", help="toy size on N virtual CPU devices, "
                    "to debug the harness; never a result")
    args = ap.parse_args(argv)

    cell = load_json("workloads", args.workload + ".json")
    sizes = load_json("configs", cell["config"] + ".json")
    traffic = load_json("traffic", cell["traffic"] + ".json")
    peaks = load_json("peaks.json")
    if args.rehearse:
        sizes, traffic = with_toy(sizes), with_toy(traffic)

    import jax
    if args.rehearse:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", args.rehearse)
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if not args.rehearse:
        wrong = None
        if device["platform"] != "tpu":
            wrong = "needs a TPU (--rehearse debugs the harness on a CPU)"
        elif device["count"] != cell["chips"]:
            wrong = f"the cell is defined on {cell['chips']} chip(s)"
        elif device["kind"] not in peaks["chips"]:
            wrong = "this chip is not in benchmark/peaks.json"
        if wrong:
            print(f"benchmark: {args.workload}: {wrong}; JAX found {device}",
                  file=sys.stderr)
            return 1

    sys.path.insert(0, ROOT)
    from apex_tpu import parallel
    from apex_tpu.prof import compile_watch
    from apex_tpu.utils import enable_compile_cache

    cache_dir = None if args.rehearse else enable_compile_cache()
    compile_watch.install()

    def compiles():
        c = compile_watch.global_counters()
        return {"requests": int(c["compiles"]),
                "backend": int(c["compiles"] - c["cache_hits"])}

    say("start", workload=args.workload, seed=args.seed, device=device,
        rehearsal=bool(args.rehearse), compile_cache=cache_dir)

    # every local device on one data axis: one chip and four run this code
    mesh = parallel.data_parallel_mesh()
    chips = len(devices)
    global_batch = traffic["per_chip_batch"] * chips
    key = seed_key(args.seed)
    pool = make_pool(traffic, sizes, key, mesh, global_batch)
    built = load_module("configs", cell["config"]).build(
        sizes, key, mesh, pool[0])
    carry = built["carry"]
    jax.block_until_ready((pool, carry))
    say("weights_and_pool")

    lowered = built["step"].lower(carry, *pool[0])
    mosaic_calls = lowered.as_text().count("tpu_custom_call")
    say("traced_and_lowered", mosaic_calls=mosaic_calls)
    step = lowered.compile()
    mem = step.memory_analysis()
    say("compiled_or_loaded", compiles=compiles(), step_program_bytes={
        k: getattr(mem, k + "_size_in_bytes", None) for k in
        ("argument", "output", "alias", "temp", "generated_code")})

    checks = {}
    reference = load_module("reference", cell["config"], required=False)
    if reference is not None:
        found = reference.compare(sizes, built, carry, pool[0])
        checks["reference"] = bool(found.pop("ok"))
        say("reference", **found)

    losses = []
    for i in range(WARMUP_STEPS):
        carry, loss, _ = step(carry, *pool[i % len(pool)])
        losses.append(loss)
    jax.block_until_ready((carry, losses))
    losses = [float(x) for x in losses]
    before = compiles()
    setup_s = time.perf_counter() - _T0
    say("warm", setup_s=setup_s, warmup_losses=losses)

    tracer = (Tracer(os.path.join(OUT, args.workload, "trace"))
              if args.trace else None)
    # the pool goes on where the warm-up left it
    turned = pool[WARMUP_STEPS % len(pool):] + pool[:WARMUP_STEPS % len(pool)]
    carry, window_s, done, outs = run_window(
        step, carry, turned, args.seconds, tracer)
    in_window = {k: v - before[k] for k, v in compiles().items()}

    fetched = jax.device_get(outs)
    losses += [float(l) for l, _ in fetched]
    finite = [bool(f) for _, f in fetched]
    steps = len(done)
    skipped = finite.count(False)
    failed = sum(not math.isfinite(l) for l in losses[WARMUP_STEPS:])
    stats = [d.memory_stats() or {} for d in devices]
    # arrays and code are "in use"; a program's temporary space is kept
    # apart as "reserved" on this runtime: the chip holds the sum
    memory_peak = max(s.get("peak_bytes_in_use", 0)
                      + s.get("peak_bytes_reserved", 0) for s in stats)

    band = traffic["loss_band"]
    at = band["step"]
    first_ref = math.log(built["classes"])
    checks.update({
        "mosaic_calls_in_step": mosaic_calls > 0,
        "no_compile_in_window": in_window["backend"] == 0,
        "state_step_is_steps_taken":
            built["steps_taken"](carry) == WARMUP_STEPS + steps - skipped,
        "losses_finite": failed == 0 and all(map(math.isfinite, losses)),
        "first_loss_near_ln_classes":
            abs(losses[0] - first_ref) <= band["first_rel_tol"] * first_ref,
        "loss_in_band_at_step":
            len(losses) >= at and band["low"] <= losses[at - 1] <= band["high"],
    })
    say("window", steps=steps, window_s=window_s,
        step_ms_p50=statistics.median(done) * 1e3,
        step_ms_max=max(done) * 1e3, compiles_in_window=in_window,
        loss_at={str(i): losses[i - 1] for i in (1, 2, 5, 10, 20, at, 100, 200)
                 if i <= len(losses)},
        skipped_steps=skipped, memory_stats=stats[0], checks=checks)

    measured = {
        "samples_per_s_per_chip": steps * global_batch / window_s / chips,
        "step_ms_p90": percentile(done, 90) * 1e3,
        "setup_s": setup_s,
    }
    result = {"correct": all(checks.values()) and not args.rehearse,
              "attempted": steps, "failed": failed, "metrics": {},
              "device": {**device, "memory_peak_bytes": memory_peak}}

    if args.trace:
        import trace_reduce
        path = trace_reduce.newest_xplane(tracer.dir)
        trace = trace_reduce.load(path) if path else None
        run_info = {"steps": steps, "window_s": window_s, "chips": chips,
                    "global_batch": global_batch, "finite": finite,
                    "flops_per_sample": built["flops_per_sample"],
                    "peak_flops": peaks["chips"].get(device["kind"], {})
                                               .get("bf16_flops"),
                    "memory_peak_bytes": memory_peak}
        for name in cell["per_layer"]:
            reader = load_module("layer_metrics", name)
            value = reader.read(trace, run_info)
            if value is not None:
                measured[name] = value
                result["metrics"][name] = {"value": value,
                                           "unit": reader.UNIT}
        spans = trace_reduce.busy_and_window_s(trace) if trace else None
        if spans:
            result["device"]["busy_s"], result["device"]["window_s"] = spans
            result["breakdown"] = {
                "device_ops": [list(x) for x in trace_reduce.top_ops(trace)],
                "idle_gaps": [list(x) for x in
                              trace_reduce.idle_gaps(trace)[:10]]}
    else:
        result["metrics"] = {k: {"value": measured[k], "unit": u}
                             for k, u in E2E_UNITS.items()}

    if args.rehearse:
        # what the CPU computed shows that the code ran; it is no metric
        say("rehearsal_not_a_measurement", computed=measured,
            would_report=sorted(result["metrics"]),
            breakdown=result.pop("breakdown", None))
        result["metrics"] = {}
        for k in ("busy_s", "window_s"):
            result["device"].pop(k, None)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
