"""The window layers' attention kernels' share of the chip's compute
roofline: the operations the band requires a step, over the device time of
the Mosaic calls under the scope ``swa/attn`` (``models/laguna.py``: the
forward kernel and the backward's two), over the chip's published bf16
peak (``peaks.json``). Bound by operations: a 512-key band at d 128 does
~128 operations a byte fetched.

The required operations (:func:`required_flops`) are the same whatever
implements the scope: nothing of them is read off a kernel. Each (query,
key) pair the band keeps, ``sum_t min(t + 1, sliding_window)`` a sequence,
takes 4 operations a channel and a q head forward (``q k`` and ``p v``) and
8 backward (``d p``, ``d q``, ``d k``, ``d v``); the backward's recompute of
the scores, the tiles' share outside the band and a recomputed block's
forward count in the time alone, so the share cannot pass 100. Heads, the
head size, the window, the number of window layers and the sequence length
are the cell's (``num_attention_heads_per_layer``, ``head_dim``,
``sliding_window``, ``layer_types`` and its traffic), from the files of the
cells that list this metric (:func:`cell_shape`: None where they disagree
or none does); sequences are the run's global batch. None where there is no
trace or no published peak; 0.0 where the model has no window layer."""

import json
import os

UNIT = "%"
LAYER = "sliding-window attention"
MOVES = "samples_per_s_per_chip"
HERE = os.path.dirname(os.path.abspath(__file__))
NAME = os.path.splitext(os.path.basename(__file__))[0]


def band_pairs(seq, window):
    """(query, key) pairs a sequence's band keeps: ``sum_t min(t + 1,
    window)``."""
    w = min(window, seq)
    return w * (w + 1) // 2 + (seq - w) * w


def required_flops(pairs, heads, head_dim, layers=1, sequences=1):
    """Operations the band requires a step: 4 forward and 8 backward a pair,
    a channel, a q head, a layer and a sequence."""
    return 12 * pairs * head_dim * heads * layers * sequences


def _load(*parts):
    with open(os.path.join(HERE, "..", *parts)) as f:
        return json.load(f)


def listed_cells():
    """The cells this metric's entry in ``BENCHMARK.json`` names."""
    for entry in _load("..", "BENCHMARK.json")["per_layer"]:
        if entry["name"] == NAME:
            return entry["workloads"]
    return []


def cell_shape():
    """``(pairs a sequence, q heads, head size, window layers)`` of the
    cells that list this metric, from their traffic and configuration
    files."""
    shapes = set()
    for cell in listed_cells():
        cell = _load("workloads", cell + ".json")
        sizes = _load("configs", cell["config"] + ".json")
        traffic = _load("traffic", cell["traffic"] + ".json")
        heads = {h for kind, h in zip(sizes["layer_types"],
                                      sizes["num_attention_heads_per_layer"])
                 if kind == "sliding_attention"}
        if len(heads) != 1:
            return None
        layers = sizes["layer_types"].count("sliding_attention")
        shapes.add((band_pairs(traffic["arrays"][0]["shape"][0],
                               sizes["sliding_window"]),
                    heads.pop(), sizes["head_dim"], layers))
    return shapes.pop() if len(shapes) == 1 else None


def read(trace, run_info):
    import scope_reduce
    found = scope_reduce.windowed(trace)
    peak = scope_reduce.published_peak("bf16_flops")
    if found is None or not peak:
        return None
    seconds = sum(
        r.total_us for r in found.profile.ops
        if "/swa/attn/" in "/" + scope_reduce.user_scope(r) + "/"
        and "tpu_custom_call" in r.hlo) / found.steps / 1e6
    shape = cell_shape()
    if not seconds or shape is None:
        return 0.0 if not seconds else None
    pairs, heads, head_dim, layers = shape
    needed = required_flops(pairs, heads, head_dim, layers,
                            run_info["global_batch"])
    return 100.0 * needed / seconds / peak
