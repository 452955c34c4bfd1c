"""The programs of the cells that have no windowed attention and no YaRN
rotation lower to the same text as before either existed.

``ops.flash_attention``'s ``window=`` and ``models.partial_rotary``'s
``inv_freq=`` / ``scale=`` reach code that every decoder cell and BERT run:
the attention kernels' frontier, the kernels' masks and the rotation. With
``window=None`` and no YaRN those paths must be what they were, op for op.
Each case lowers a program (a cell's training step at its ``toy`` size, as
``benchmark/run.py --rehearse`` builds it, on one CPU device; or the op
alone at a shape whose causal grid the frontier cuts) and holds the SHA-256
of its StableHLO text to the one the tree before the window read. The text
prints no source locations, so the digests hold for any checkout path; they
move with any change of what the program computes, which is the point: a
change that means to alter one of these programs updates its digest here,
and says why. ``delta-rule-per-channel-kernels-d128`` holds the delta
rule's per-channel kernels (Kimi's cell) to the text they had before the
kernels of one decay a head were added beside them: no cell's toy step
reaches either at its 16-wide heads. Kimi's digest held when latent
attention stopped padding ``v`` to the q/k head size in the model: its toy's
two heads of 32 (``v`` 16) take the transposed kernels, where the op pads
``v`` and slices ``o`` itself, op for op as the model did; only the cells'
real heads (192 and 128, two a kernel step) reach the kernels that take
``v`` at its own head size.
"""

import hashlib
import importlib.util
import pathlib
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "benchmark"

#: cell -> digest of ``built["step"].lower(carry, *pool[0]).as_text()``
STEP_DIGESTS = {
    "qwen3_next.lm_s8192_b1_v19k":
        "05237c06e90975e2f20ef95620efa8ef898acf085233947740796a60bb6edeac",
    "lfm2_moe.lm_s8192_b2_v8k":
        "1083bb9a913fbaaa2a881083b9d95d964c5ade4a0235afca821ebb931c59d18b",
    "kimi_linear.lm_s8192_b1":
        "1ace634e34cf981d8809bb0e9fd0dc7b5477b297922bf75fdb567b9cbc5354af",
    "bert_large.mlm_s128_b64":
        "18bb914a35449b46e8bd93dc2275f4c338cc86751ae87f9b22419ecbf5cc8538",
}
#: op case -> digest of its gradient program's text
OP_DIGESTS = {
    "causal-gqa-d128-512-tiles-128x256":
        "c00bfe038e475f4d4858dcd31c3ba7f66b98987e089f4822e9152086034c937e",
    "causal-d64-384-tiles-128-padded":
        "d03d1880d813ef7112cc729cd5f3bfd645b93ee524732b0b1343b1d8787cfe47",
    "causal-single-block-fused-d64-256":
        "a17a6c14123437ce53cf26097be92b89eb06946ca5204b20f95f7c571f51ade7",
    "noncausal-d64-512-tiles-128":
        "6fea0cfbfabd4baf7e8d570bcdf4c75adf98e6048a7ace0c46ae8472dabe2e3f",
    "partial-rotary-half-of-64":
        "23102f09e7f780b725728cab96575a38087161dd098ba8dec639f5a1d526b9ac",
    "delta-rule-per-channel-kernels-d128":
        "c57d1fe6ed85de729307e16edef08e4d7a97486a4137ae8c0782ba626ae5a086",
}


def _runner():
    spec = importlib.util.spec_from_file_location(
        "benchmark_run_for_lowering", BENCH / "run.py")
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    return run


def step_text(cell):
    """A cell's step at its toy size, lowered as the runner lowers it."""
    run = _runner()
    from apex_tpu import parallel
    spec = run.load_json("workloads", cell + ".json")
    sizes = run.with_toy(run.load_json("configs", spec["config"] + ".json"))
    traffic = run.with_toy(run.load_json("traffic",
                                         spec["traffic"] + ".json"))
    mesh = parallel.data_parallel_mesh(jax.devices()[:1])
    key = run.seed_key(2147483659)
    pool = run.make_pool(traffic, sizes, key, mesh, traffic["per_chip_batch"])
    built = run.load_module("configs", spec["config"]).build(
        sizes, key, mesh, pool[0])
    return built["step"].lower(built["carry"], *pool[0]).as_text()


def op_text(case):
    from apex_tpu import ops
    from apex_tpu.models import partial_rotary
    shapes = {
        "causal-gqa-d128-512-tiles-128x256": (512, 4, 2, 128, True,
                                              (128, 256)),
        "causal-d64-384-tiles-128-padded": (384, 2, 2, 64, True, (128, 128)),
        "causal-single-block-fused-d64-256": (256, 2, 2, 64, True,
                                              (256, 256)),
        "noncausal-d64-512-tiles-128": (512, 2, 2, 64, False, (128, 128)),
    }
    if case == "partial-rotary-half-of-64":
        x = jax.ShapeDtypeStruct((1, 64, 2, 64), jnp.float32)
        return jax.jit(jax.grad(lambda x: jnp.sum(
            partial_rotary(x, 32, 1e6) ** 2))).lower(x).as_text()
    if case == "delta-rule-per-channel-kernels-d128":
        # the KDA kernels (interpreted), a decay a key channel: what the
        # kernels of one decay a head were added beside
        x = jax.ShapeDtypeStruct((1, 128, 4, 128), jnp.float32)
        beta = jax.ShapeDtypeStruct((1, 128, 4), jnp.float32)
        return jax.jit(jax.value_and_grad(lambda *a: jnp.sum(
            ops.gated_delta_rule(*a) ** 2), argnums=range(5))).lower(
                x, x, x, x, beta).as_text()
    t, h, hkv, d, causal, tiles = shapes[case]
    q = jax.ShapeDtypeStruct((1, t, h, d), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, t, hkv, d), jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(ops.flash_attention(
            q, k, v, None, d ** -0.5, causal, *tiles).astype(jnp.float32))
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).as_text()


def digest(text):
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("cell", sorted(STEP_DIGESTS))
def test_the_cells_steps_lower_as_before(cell):
    assert digest(step_text(cell)) == STEP_DIGESTS[cell]


@pytest.mark.parametrize("case", sorted(OP_DIGESTS))
def test_the_ops_lower_as_before(case):
    assert digest(op_text(case)) == OP_DIGESTS[case]


if __name__ == "__main__":
    # print the digests of this tree (run under tests/conftest.py's settings)
    sys.path.insert(0, str(ROOT / "tests"))
    import conftest  # noqa: F401
    for cell in sorted(STEP_DIGESTS):
        print(repr(cell), ":", repr(digest(step_text(cell))), ",")
    for case in sorted(OP_DIGESTS):
        print(repr(case), ":", repr(digest(op_text(case))), ",")
