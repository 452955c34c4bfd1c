"""Model FLOP/s utilisation of the device step: the configuration's FLOPs
per sample times the global batch, over the device time of one step and
the published bf16 peak of the chips (``peaks.json``). Recomputed
operations do not count; host time does not enter."""

UNIT = "%"
LAYER = "model step"
MOVES = "samples_per_s_per_chip"


def read(trace, run_info):
    import trace_reduce
    step_ms = None if trace is None else trace_reduce.device_step_ms(trace)
    if not step_ms or not run_info["peak_flops"]:
        return None
    flops = run_info["flops_per_sample"] * run_info["global_batch"]
    return 100.0 * flops / (step_ms / 1e3) / (
        run_info["chips"] * run_info["peak_flops"])
