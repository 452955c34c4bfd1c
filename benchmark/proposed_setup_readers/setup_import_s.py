"""Seconds ``import apex_tpu`` took in this process: the package's ``import``
span of ``apex_tpu.prof.compile_watch``'s timeline, from before the first of
its fourteen subpackages to after the last (the children ``import/<sub>`` say
which). ``import jax`` and the TPU client's start lie before it, in
``process_age_at_import_s``. None where the program records no timeline or the
run never installed the listener."""

UNIT = "s"
LAYER = "library import"
MOVES = "setup_s"


def read(trace, run_info):
    from apex_tpu.prof import compile_watch
    report = getattr(compile_watch, "setup_report", None)
    if report is None or not compile_watch.installed():
        return None
    return report().totals["import_s"]
