"""Compile requests the persistent cache did not answer: ``compile`` spans of
``apex_tpu.prof.compile_watch``'s timeline whose ``cache`` is not ``hit``. 0 on
a warm run; 1 or more says that a slow set-up compiled, and the spans' programs
say what. None where the program records no timeline or the run never
installed the listener."""

UNIT = "count"
LAYER = "compiler + device"
MOVES = "setup_s"


def read(trace, run_info):
    from apex_tpu.prof import compile_watch
    report = getattr(compile_watch, "setup_report", None)
    if report is None or not compile_watch.installed():
        return None
    return report().totals["backend_compiles"]
