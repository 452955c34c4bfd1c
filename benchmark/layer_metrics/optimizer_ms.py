"""Device time a step spends in the optimizer: the ops traced under amp's
``amp/update`` span or under any ``optim/<name>/<phase>`` scope, chip 0, per
step of the window."""

UNIT = "ms"
LAYER = "optimizers"
MOVES = "samples_per_s_per_chip"


def read(trace, run_info):
    import scope_reduce
    return scope_reduce.ms_per_step(trace, scope_reduce.in_optimizer)
