"""The gated short convolution's share of its HBM roofline: the bytes the
operation requires a step, over the device time of everything under the
scope ``lconv/conv`` (``models/lfm2.py``), over the chip's published
bandwidth (``peaks.json``). Bound by bytes: ~10 operations an element
against 8 to 14 bytes.

The required bytes (:func:`required_bytes`) are the same whatever implements
the scope: nothing of them is read off a kernel. A conv layer's forward reads
``B``, ``C``, ``z`` and writes ``y`` (4 items a token-channel), its backward
reads those and ``d y`` and writes three cotangents (7 items), once a step
each: the forward a recomputed block runs again moves bytes no one required
and counts in the time alone, so the share cannot pass 100. Tokens are the
run's global batch times the sequence length of the cell's traffic, channels
and the number of conv layers the cell's configuration's (``hidden_size``,
``layer_types``), from the files of the cells that list this metric in
``BENCHMARK.json`` (:func:`cell_shape`: None where they disagree); an item is
``ITEM_BYTES``. None where there is no trace or no published bandwidth; 0.0
where the model has no such layer. A ``--rehearse`` run's toy sizes are not
read: it has no device trace where it is run."""

import json
import os

UNIT = "%"
LAYER = "gated short convolution"
MOVES = "samples_per_s_per_chip"
#: amp O1 (the configurations' ``precision``): the projection writes, and
#: the convolution reads and writes, bfloat16
ITEM_BYTES = 2
HERE = os.path.dirname(os.path.abspath(__file__))


def required_bytes(tokens, channels, item_bytes, layers=1):
    """HBM bytes the gated convolution of ``layers`` layers requires a step:
    forward 3 reads + 1 write, backward 4 reads + 3 writes, an item each a
    token-channel."""
    return layers * tokens * channels * item_bytes * (4 + 7)


def cell_shape():
    """``(tokens a sequence, channels, conv layers)`` of the cells that list
    this metric, from their traffic and configuration files."""
    def load(*parts):
        with open(os.path.join(HERE, "..", *parts)) as f:
            return json.load(f)
    name = os.path.splitext(os.path.basename(__file__))[0]
    entry = next(m for m in load("..", "BENCHMARK.json")["per_layer"]
                 if m["name"] == name)
    shapes = set()
    for cell in entry["workloads"]:
        cell = load("workloads", cell + ".json")
        sizes = load("configs", cell["config"] + ".json")
        traffic = load("traffic", cell["traffic"] + ".json")
        shapes.add((traffic["arrays"][0]["shape"][0], sizes["hidden_size"],
                    list(sizes["layer_types"]).count("conv")))
    return shapes.pop() if len(shapes) == 1 else None


def read(trace, run_info):
    import scope_reduce
    found = scope_reduce.windowed(trace)
    peak = scope_reduce.published_peak("hbm_bytes_per_s")
    if found is None or not peak:
        return None
    seconds = sum(
        r.total_us for r in found.profile.ops
        if "/lconv/conv/" in "/" + scope_reduce.user_scope(r) + "/"
    ) / found.steps / 1e6
    shape = cell_shape()
    if not seconds or shape is None:
        return 0.0 if not seconds else None
    seq, channels, layers = shape
    needed = required_bytes(run_info["global_batch"] * seq, channels,
                            ITEM_BYTES, layers)
    return 100.0 * needed / seconds / peak
