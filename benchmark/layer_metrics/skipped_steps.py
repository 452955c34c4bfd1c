"""Steps of the window whose gradients were not finite, so that amp
skipped the update (the ``finite`` flag ``amp_opt.backward`` returns)."""

UNIT = "count"
LAYER = "precision policy"
MOVES = "samples_per_s_per_chip"


def read(trace, run_info):
    return run_info["finite"].count(False)
