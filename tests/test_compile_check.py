"""The on-device validation harness itself must not rot: run a fast
subset of its cases in interpret mode on the CI mesh, and check the
driver/JSON plumbing."""

import json

import pytest

from apex_tpu.ops import compile_check as cc


def test_case_registry_nonempty_and_named():
    names = [n for n, _ in cc.CASES]
    assert len(names) >= 20
    assert len(set(names)) == len(names)
    for family in ("attention", "layer_norm", "mlp", "xentropy",
                   "multi_tensor", "optim", "bn_act", "ckpt"):
        assert any(n.startswith(family + "/") for n in names), family


def test_ckpt_case_runs_green():
    """The ISSUE-6 acceptance case: a step with checkpointing attached
    compiles bit-identical HLO, donated and undonated."""
    assert cc.run(pattern="ckpt")


def test_fast_subset_runs_green(tmp_path):
    out = tmp_path / "cc.json"
    ok = cc.run(pattern="layer_norm", json_path=str(out))
    assert ok
    data = json.loads(out.read_text())
    assert data["ok"] and data["n_failed"] == 0
    assert data["backend"] == "cpu" and data["compiled"] is False
    assert all(r["ok"] for r in data["results"])


def test_multi_tensor_case_runs_green():
    ok = cc.run(pattern="multi_tensor")
    assert ok


def test_failure_is_reported(tmp_path, monkeypatch):
    def boom():
        raise AssertionError("intentional")

    monkeypatch.setattr(cc, "CASES", [("fake/boom", boom)])
    out = tmp_path / "cc.json"
    ok = cc.run(json_path=str(out))
    assert not ok
    data = json.loads(out.read_text())
    assert data["n_failed"] == 1
    assert "intentional" in data["results"][0]["error"]


def test_the_qwen3_next_cell_cases_run_at_a_small_size():
    """``attention/gqa-d256-cell`` and ``delta_rule/scalar-decay-shared-
    keys-cell`` (and its ``highest`` twin) hold the cell's heads; on the chip
    they run at its 8192 tokens, here interpreted at a few chunks."""
    names = [n for n, _ in cc.CASES]
    for name in ("attention/gqa-d256-cell",
                 "delta_rule/scalar-decay-shared-keys-cell",
                 "delta_rule/scalar-decay-shared-keys-cell-highest"):
        assert name in names
    cc._gqa_cell_case(t=256, prefix=128)
    cc._delta_rule_cell_case(t=192, prefix=64)
    cc._delta_rule_cell_case(t=128, prefix=64, precision="highest")


def test_the_scalar_vs_broadcast_case_runs_at_a_small_size():
    """``delta_rule/scalar-vs-broadcast-cell``: the kernels of one decay a
    head against the per-channel kernels fed the broadcast, at the cell's
    heads; on the chip at 8192 tokens to 1e-2, here interpreted at two
    chunks, where both are float32 throughout, to 1e-5."""
    assert "delta_rule/scalar-vs-broadcast-cell" in [n for n, _ in cc.CASES]
    cc._scalar_vs_broadcast_case(t=128, tol=1e-5)


def test_the_expert_layers_cell_cases_run_at_a_small_size(monkeypatch):
    """``moe/{4-of-64,8-of-256,10-of-512}-cell`` hold each decoder cell's
    routing (tokens, chosen of routed, held) and widths; on the chip they
    compile ``apex_gmm`` and ``apex_tgmm`` at those, here the same case runs
    interpreted at 64 tokens with the sorted rows in several tiles."""
    from apex_tpu.ops import grouped_matmul as gm
    names = [n for n, _ in cc.CASES]
    for name in ("moe/4-of-64-cell", "moe/8-of-256-cell",
                 "moe/10-of-512-cell"):
        assert name in names
    monkeypatch.setattr(gm, "ROW_TILE", 32)
    cc._experts_cell_case(64, 2, 16, 4, 32, 16)


@pytest.mark.parametrize("heads,kv_heads,d,tiles,d_v", [
    pytest.param(2, 2, 192, (128, 256), 128, id="mla-d192"),
    pytest.param(4, 1, 64, (128, 128), None, id="gqa-d64"),
    pytest.param(2, 1, 256, (256, 128), None, id="gqa-d256")])
def test_the_causal_skip_cell_cases_run_at_a_small_size(heads, kv_heads, d,
                                                         tiles, d_v):
    """``attention/causal-skip-*-s8192-cell`` hold the three decoder cells'
    heads and tiles over 8192 tokens on the chip (MLA's v at its own 128):
    the skipped grid against the whole grid, bit for bit. Here the same
    case, interpreted, at 512 tokens in several tiles."""
    names = [n for n, _ in cc.CASES]
    for name in ("attention/causal-skip-mla-d192-s8192-cell",
                 "attention/causal-skip-gqa-d64-s8192-cell",
                 "attention/causal-skip-gqa-d256-s8192-cell",
                 "attention/causal-skip-no-extra-dispatch"):
        assert name in names
    cc._causal_skip_case(1, heads, kv_heads, d, t=512, tiles=tiles, d_v=d_v)


def test_the_window_cell_cases_run_at_a_small_size():
    """``attention/window-{band,skip}-d128-s4096-cell`` hold the window
    cell's heads, band and tiles over 4096 tokens on the chip: against the
    dense band on a prefix, and the band's skip against the whole grid bit
    for bit. Here the same cases, interpreted, at 512 tokens and a window of
    100 keys (no whole number of tiles), in the op's 128-tiles for it."""
    names = [n for n, _ in cc.CASES]
    for name in ("attention/window-band-d128-s4096-cell",
                 "attention/window-skip-d128-s4096-cell"):
        assert name in names
    cc._gqa_cell_case(1, 4, 2, 128, t=512, prefix=256, window=100)
    cc._causal_skip_case(1, 4, 2, 128, t=512, window=100)


def test_the_causal_skip_reaches_the_causal_multi_block_kernels_alone():
    """``attention/causal-skip-no-extra-dispatch`` at 256 tokens in 128-tiles:
    non-causal and single-block programs are the same text with the skip and
    without it."""
    cc._causal_skip_reach_case(t=256, tile=128, heads=2)
