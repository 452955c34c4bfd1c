"""Seconds the process spent in compile requests: what the ``compile`` spans of
``apex_tpu.prof.compile_watch``'s timeline cover, a backend compile or a load
from the persistent cache, whichever the request got (the span's ``cache``
says which). After a correct run nothing compiles in or after the window, so
all of it is set-up. None where the program records no timeline or the run
never installed the listener."""

UNIT = "s"
LAYER = "compiler + device"
MOVES = "setup_s"


def read(trace, run_info):
    from apex_tpu.prof import compile_watch
    report = getattr(compile_watch, "setup_report", None)
    if report is None or not compile_watch.installed():
        return None
    return report().totals["compile_s"]
