"""What ``benchmark/reference/qwen3_next.py``'s comparison catches: the
reference against itself with one thing wrong, at the published widths.

    python scripts/qwen3_next_probes.py [--toy] [--seed N] [--out FILE]

For each probe (a bfloat16 delta-rule state, everything in bfloat16, a
dropped 1/16 in attention, rotary over all 256 channels, rotary in
interleaved pairs, key heads tiled in place of interleaved, q head ``h``
reading k/v head ``h % 2``, sigmoid scores, expert weights normalised over
the held experts only, the shared expert ungated) it prints the numbers
``compare`` holds to its tolerances: the relative loss difference, the
relative L2 difference of the logits at the compared rows, the same with
every DeltaNet head's decay slowed (``reference.slowed``), and the relative
L2 difference of each compared gradient on the prefix. The numbers in the
reference's docstring and in PERF.md come from a run of this on the chip; on
a CPU use ``--toy``.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import kimi_linear_probes  # noqa: E402  (the runner; this file is its table)


def qwen3_next_probes(jnp, sizes, length):
    return {
        "bf16_state": {"state_dtype": jnp.bfloat16},
        "all_bf16": {"dtype": jnp.bfloat16},
        "no_softmax_scale": {"scaled": False},
        "rotary_over_all_channels": {"over_all": True},
        "rotary_interleaved_pairs": {"interleaved": True},
        "key_heads_tiled": {"tiled_keys": True},
        "kv_head_by_modulo": {"kv_head_mod": True},
        "sigmoid_scores": {"sigmoid_scores": True},
        "weights_over_held_only": {"over_held_only": True},
        "shared_expert_ungated": {"shared_ungated": True},
    }


if __name__ == "__main__":
    sys.exit(kimi_linear_probes.main(None, "qwen3_next", qwen3_next_probes,
                                     __doc__))
