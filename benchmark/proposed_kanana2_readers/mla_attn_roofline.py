"""The latent-attention layers' attention kernels' share of the chip's
compute roofline: the operations causal attention requires a step, over the
device time of the Mosaic calls under the scope ``mla/attn``
(``models/mla.py``: the forward kernel and the backward's), over the chip's
published bf16 peak (``peaks.json``). Bound by operations: at 8192 tokens
and a head of 192 a tile does far more operations a byte than the chip's
ratio.

The required operations (:func:`required_flops`) are the same whatever
implements the scope: nothing of them is read off a kernel. Each (query,
key) pair the causal mask keeps, ``T (T + 1) / 2`` a sequence, takes ``2
(d_qk + d_v)`` operations a head forward (``q k`` at the q/k head size,
``p v`` at the value head size) and ``4 (d_qk + d_v)`` backward (``d p``
and ``d v`` at ``d_v``, ``d q`` and ``d k`` at ``d_qk``); the zeros that pad
``v`` to ``d_qk``, the backward's recompute of the scores, the tiles' share
above the diagonal and a recomputed block's forward count in the time
alone, so the share cannot pass 100. Heads, head sizes and the sequence
length are the cell's (``num_attention_heads``, ``qk_nope_head_dim`` +
``qk_rope_head_dim``, ``v_head_dim`` and its traffic), from the files of
the cells that list this metric (:func:`cell_shape`: None where they
disagree or none does); the layers are those whose ``mla/attn`` kernels the
trace holds (``layers_<i>/`` in their scope), sequences the run's global
batch. None where there is no trace or no published peak; 0.0 where the
model has no latent attention."""

import json
import os
import re

UNIT = "%"
LAYER = "latent attention"
MOVES = "samples_per_s_per_chip"
HERE = os.path.dirname(os.path.abspath(__file__))
NAME = os.path.splitext(os.path.basename(__file__))[0]
LAYER_ID = re.compile(r"(?:^|/)layers_(\d+)/")


def causal_pairs(seq):
    """(query, key) pairs a sequence's causal mask keeps."""
    return seq * (seq + 1) // 2


def required_flops(pairs, heads, qk_dim, v_dim, layers=1, sequences=1):
    """Operations causal attention requires a step: ``6 (d_qk + d_v)`` a
    pair, a head, a layer and a sequence, forward and backward."""
    return 6 * pairs * (qk_dim + v_dim) * heads * layers * sequences


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
    """``(pairs a sequence, heads, q/k head size, v head size)`` of the
    cells that list this metric, from their traffic and configuration
    files."""
    shapes = set()
    for cell in listed_cells():
        cell = _load("workloads", cell + ".json")
        sizes = _load("configs", cell["config"] + ".json")
        traffic = _load("traffic", cell["traffic"] + ".json")
        shapes.add((causal_pairs(traffic["arrays"][0]["shape"][0]),
                    sizes["num_attention_heads"],
                    sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"],
                    sizes["v_head_dim"]))
    return shapes.pop() if len(shapes) == 1 else None


def read(trace, run_info):
    import scope_reduce
    found = scope_reduce.windowed(trace)
    peak = scope_reduce.published_peak("bf16_flops")
    if found is None or not peak:
        return None
    calls = [r for r in found.profile.ops
             if "/mla/attn/" in "/" + scope_reduce.user_scope(r) + "/"
             and "tpu_custom_call" in r.hlo]
    seconds = sum(r.total_us for r in calls) / found.steps / 1e6
    shape = cell_shape()
    if not seconds or shape is None:
        return 0.0 if not seconds else None
    layers = {m.group(1) for r in calls
              for m in LAYER_ID.finditer(scope_reduce.user_scope(r))}
    needed = required_flops(*shape, len(layers), run_info["global_batch"])
    return 100.0 * needed / seconds / peak
