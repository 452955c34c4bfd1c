"""Minimal DDP + amp pattern — the `examples/simple/distributed` mirror.

Reference: `examples/simple/distributed/distributed_data_parallel.py:1-66`
(a Linear regression trained under amp O1 + apex DDP, launched with
`torch.distributed.launch`). TPU-native, there is no per-rank process
dance: one program shards the batch over a named mesh axis and `psum`s
gradients. Multi-host pods use the same script after
``apex_tpu.parallel.distributed_init()`` (the `multiproc` equivalent).

Also the minimal apex_tpu.monitor consumer: the train state carries the
in-graph Metrics pytree (``monitor=True``), a ``MetricsLogger`` ships it
to stdout/JSONL on an amortized flush cadence, and the per-step
collective traffic is read off the compiled HLO via
``ddp.collective_bytes`` — live telemetry with zero extra dispatches.

Also the minimal apex_tpu.trace consumer: ``--crash-dumps DIR`` installs
the per-rank flight recorder + hang watchdog
(``parallel.enable_crash_dumps``), wraps each step in
``trace.step``/``trace.span`` so dumps carry the span timeline, and
writes a Perfetto-loadable Chrome trace at the end — a wedged or dead
run leaves per-rank JSONL forensics instead of nothing.

Run (any host, any chip count — falls back to a virtual CPU mesh):

    python distributed_data_parallel.py [--steps 500]
                                        [--metrics-jsonl metrics.jsonl]
                                        [--crash-dumps dumps/]
"""

import argparse

import os
import sys

# allow running from a source checkout without installation
sys.path.insert(0, os.path.abspath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..")))


import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from apex_tpu import amp, monitor, parallel, trace
from apex_tpu.optim import FusedSGD
from apex_tpu.utils import enable_compile_cache


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--steps", default=500, type=int)
    parser.add_argument("--opt_level", default="O1", type=str)
    parser.add_argument("--metrics-jsonl", default=None, type=str,
                        help="also stream metrics to this JSONL file")
    parser.add_argument("--log-every", default=50, type=int,
                        help="flush cadence of the metrics logger")
    parser.add_argument("--crash-dumps", default=None, type=str,
                        help="directory for per-rank flight-recorder / "
                             "watchdog dumps + a Chrome trace")
    parser.add_argument("--hang-deadline", default=300.0, type=float,
                        help="watchdog deadline (s) when --crash-dumps "
                             "is set")
    args = parser.parse_args()
    enable_compile_cache()

    # FOR DISTRIBUTED: form the cluster first (no-op single-process;
    # honors MASTER_ADDR/RANK/WORLD_SIZE) — rank resolution below (per-
    # rank dump paths, mesh over the global device set) depends on it.
    parallel.distributed_init()

    # FORENSICS: flight recorder (excepthook/SIGTERM/atexit crash dumps)
    # + hang watchdog, one file per rank; the tracer feeds both.
    tracer, recorder = trace.Tracer(), None
    if args.crash_dumps:
        tracer, recorder, _wd, _cd = parallel.enable_crash_dumps(
            os.path.join(args.crash_dumps, "crash.jsonl"),
            hang_deadline_s=args.hang_deadline)

    # FOR DISTRIBUTED: one mesh over every available device; the same
    # script is SPMD across a pod once distributed_init() has run.
    mesh = parallel.data_parallel_mesh()
    ddp = parallel.DistributedDataParallel(mesh)

    N, D_in, D_out = 64, 1024, 16
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(N, D_in).astype(np.float32))
    y = jnp.asarray(rng.randn(N, D_out).astype(np.float32))

    w = jnp.asarray(rng.randn(D_in, D_out).astype(np.float32) * 0.01)
    b = jnp.zeros((D_out,), jnp.float32)
    params = {"w": w, "b": b}

    amp_opt, state = amp.initialize(params, FusedSGD(lr=1e-3),
                                    opt_level=args.opt_level,
                                    monitor=True)

    def step(state, xb, yb):
        def loss_fn(p):
            pred = xb @ p["w"] + p["b"]
            return jnp.mean(jnp.square(pred - yb))

        loss, grads, state, finite = amp_opt.backward(state, loss_fn)
        grads = ddp.sync(grads)                     # the DDP allreduce
        if not isinstance(finite, bool):
            # defensive: the default bf16 presets have no scaler (finite
            # is literally True). If this example is edited to fp16, the
            # COMMIT decision must be global — one shard overflowing
            # skips the step everywhere. Note the scaler *schedule* and
            # its event counters inside backward() still see shard-local
            # finiteness; a production fp16+DDP loop should sync grads
            # before unscaling via the standalone scaler API
            # (docs/amp.md "Loss scaling, standalone").
            finite = jax.lax.pmin(
                jnp.asarray(finite, jnp.int32), ddp.axis_name).astype(bool)
        state = amp_opt.apply_gradients(state, grads, finite)
        gloss = jax.lax.pmean(loss, ddp.axis_name)
        if state.metrics is not None:
            # backward recorded the shard-local loss; the logged stream
            # (fetched from shard 0) must carry the global mean — every
            # other gauge is already replicated (synced grads / params /
            # global finite)
            state = state._replace(metrics=state.metrics.record_loss(gloss))
        return state, gloss

    # the carried AmpState is donated (apexlint APX101: without it the
    # masters + optimizer state are double-allocated every step)
    spmd_step = jax.jit(jax.shard_map(
        step, mesh=mesh,
        in_specs=(P(), P(parallel.DATA_AXIS), P(parallel.DATA_AXIS)),
        out_specs=(P(), P()), check_vma=False), donate_argnums=(0,))

    # MONITORING: per-step collective traffic and model FLOPs are
    # compile-time constants read off the optimized HLO; attach()
    # derives both from ONE AOT compile (ddp.collective_bytes exposes
    # the same accounting with a per-opcode breakdown, at the cost of
    # its own compile). The logger then ships the in-graph health
    # counters off-device every --log-every steps (one amortized fetch).
    sinks = [monitor.StdoutSink()]
    if args.metrics_jsonl:
        sinks.append(monitor.JSONLSink(args.metrics_jsonl))
    logger = monitor.MetricsLogger(sinks, flush_every=args.log_every)
    logger.attach(spmd_step, state, x, y)
    print(f"collective traffic/step: {logger.collective_bytes_per_step} "
          "bytes")

    with tracer:
        for i in range(args.steps):
            with trace.step(i):
                with trace.span("dispatch"):
                    state, loss = spmd_step(state, x, y)
                # donation-safe snapshot: the next donated dispatch
                # invalidates the state's own metrics buffers
                m = monitor.metrics_snapshot(state.metrics)
                logger.record(m)
                if recorder is not None:
                    recorder.record_metrics(m)
    logger.close()
    if args.crash_dumps:
        path = trace.rank_path(
            os.path.join(args.crash_dumps, "timeline.json"))
        tracer.write_chrome_trace(path)
        print("span timeline ->", path)
    print("final loss = ", float(loss))


if __name__ == "__main__":
    main()
