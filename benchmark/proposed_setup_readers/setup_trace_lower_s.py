"""Seconds the process spent tracing to jaxprs and lowering to MLIR: what the
``trace`` and ``lower`` spans of ``apex_tpu.prof.compile_watch``'s timeline
cover, each second once (a jitted function called inside another is traced
inside its caller's span). After a correct run nothing is traced in or after
the window, so all of it is set-up: the step's, the weights' and the pool's
programs, the reference comparison's. None where the program records no
timeline or the run never installed the listener."""

UNIT = "s"
LAYER = "model step"
MOVES = "setup_s"


def read(trace, run_info):
    from apex_tpu.prof import compile_watch
    report = getattr(compile_watch, "setup_report", None)
    if report is None or not compile_watch.installed():
        return None
    return report().totals["trace_lower_s"]
