"""What ``benchmark/reference/lfm2_moe.py``'s comparison catches: the
reference against itself with one thing wrong, at the published widths.

    python scripts/lfm2_moe_probes.py [--toy] [--seed N] [--out FILE]

For each probe (everything in bfloat16, four taps, the taps reversed, ``B``
and ``C`` swapped, the q/k norm after the rotary, rotary on half of each
head, q head ``h`` reading k/v head ``h % 8``, expert weights normalised
over the held chosen experts only, a shared expert left in, an untied head)
it prints the numbers ``compare`` holds to its tolerances: the relative loss
difference, the relative L2 difference of the logits at the compared rows,
and the relative L2 difference of each compared gradient on the prefix. The
numbers in the reference's docstring and in PERF.md come from a run of this
on the chip; on a CPU use ``--toy``.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import kimi_linear_probes  # noqa: E402  (the runner; this file is its table)


def lfm2_moe_probes(jnp, sizes, length):
    return {
        "all_bf16": {"dtype": jnp.bfloat16},
        "four_taps": {"four_taps": True},
        "taps_reversed": {"taps_reversed": True},
        "b_and_c_swapped": {"swap_b_c": True},
        "qk_norm_after_rotary": {"norm_after_rotary": True},
        "rotary_on_half_the_head": {"half_rotary": True},
        "kv_head_by_modulo": {"kv_head_mod": True},
        "weights_over_held_only": {"over_held_only": True},
        "shared_expert_left_in": {"shared_left_in": True},
        "untied_head": {"untied_head": True},
    }


if __name__ == "__main__":
    sys.exit(kimi_linear_probes.main(None, "lfm2_moe", lfm2_moe_probes,
                                     __doc__))
