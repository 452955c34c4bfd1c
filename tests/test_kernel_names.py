"""The names the program gives the device trace: one per Pallas kernel
(``ops/_dispatch.py`` ``KERNEL_NAMES``) and one per optimizer phase
(``optim/<name>/<phase>``, ``optim/fused.py``).

The trace reader (``prof.xplane.own_scope``) and the benchmark's per-kernel
metrics key on them, so: every ``pallas_call`` of ``apex_tpu/ops`` goes
through the one naming place, every name is in the table and used once, and
in the lowered benchmark steps each scope is there and costs no op."""

import ast
import collections
import contextlib
import importlib.util
import pathlib
import re
import sys

import jax
import pytest

from apex_tpu.ops import _dispatch
from apex_tpu.prof.xplane import own_scope

ROOT = pathlib.Path(__file__).resolve().parents[1]
OPS = ROOT / "apex_tpu" / "ops"
#: the two functions that hand a caller's name on, and so hold no literal
FORWARDERS = {("_dispatch.py", "launch"), ("optim_kernels.py", "_launch")}


def _call_sites():
    """``(file, enclosing function, callee, name node or None)`` for every
    call in ``apex_tpu/ops`` that launches a kernel."""
    sites = []
    for path in sorted(OPS.glob("*.py")):
        tree = ast.parse(path.read_text())
        for fn in ast.walk(tree):
            if not isinstance(fn, ast.FunctionDef):
                continue
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                f = node.func
                callee = (f.attr if isinstance(f, ast.Attribute)
                          else getattr(f, "id", None))
                if callee not in ("pallas_call", "launch", "_launch"):
                    continue
                if isinstance(f, ast.Attribute) and callee != "pallas_call":
                    continue            # e.g. parallel.launch, not ours
                name = next((k.value for k in node.keywords
                             if k.arg == "name"), None)
                if callee == "_launch" and node.args:
                    name = node.args[0]
                sites.append((path.name, fn.name, callee,
                              isinstance(f, ast.Attribute), name))
    return sites


SITES = _call_sites()
LITERALS = [s[4].value for s in SITES
            if isinstance(s[4], ast.Constant) and isinstance(s[4].value, str)]


def test_one_naming_place():
    """``pl.pallas_call`` itself is called once, in ``_dispatch.pallas_call``;
    every other site passes a literal name, but for the two forwarders."""
    raw = [(f, fn) for f, fn, _c, is_attr, _n in SITES if is_attr]
    assert raw == [("_dispatch.py", "pallas_call")]
    for f, fn, callee, is_attr, name in SITES:
        if is_attr:
            continue
        if (f, fn) in FORWARDERS:
            assert isinstance(name, ast.Name) and name.id == "name", (f, fn)
        else:
            assert isinstance(name, ast.Constant), (f, fn, callee)


def test_the_table_is_what_the_sites_use():
    assert len(set(_dispatch.KERNEL_NAMES)) == len(_dispatch.KERNEL_NAMES)
    assert sorted(LITERALS) == sorted(_dispatch.KERNEL_NAMES)


@pytest.mark.parametrize("name", _dispatch.KERNEL_NAMES)
def test_kernel_name(name):
    assert re.fullmatch(r"apex_[a-z0-9_]+", name)
    assert LITERALS.count(name) == 1, "one call site for a name"
    # the reader finds it wherever the call sits in a user's module tree,
    # and under a differentiation with no scope round it
    deep = f"jit(step)/jvp(amp/fwd)/Enc/Block_3/{name}/pallas_call"
    bare = f"jit(loss)/transpose(jvp({name}))/pallas_call"
    assert own_scope(deep) == own_scope(bare) == name


def test_an_unknown_name_is_refused():
    with pytest.raises(ValueError, match="KERNEL_NAMES"):
        _dispatch.pallas_call(lambda *refs: None, name="apex_nameless",
                              out_shape=())


def test_ops_and_models_read_no_experiment_switch():
    """The only ``APEX_TPU_*`` variables ``ops/`` and ``models/`` know are
    the interpreter override and the autotuner's two: a kernel has one
    path, chosen from the shapes it sees (PR 30 deleted six switches that
    selected paths which had lost on the chip)."""
    found = set()
    for sub in ("ops", "models"):
        for path in (ROOT / "apex_tpu" / sub).rglob("*.py"):
            found |= set(re.findall(r"APEX_TPU_[A-Z0-9_]+", path.read_text()))
    assert found == {"APEX_TPU_FORCE_INTERPRET", "APEX_TPU_AUTOTUNE",
                     "APEX_TPU_AUTOTUNE_DB"}


# ---- the benchmark's steps, lowered: the scopes are there and cost nothing --

def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _lowered_toy_step(config, traffic):
    """The configuration's step at its toy size on one device, lowered the
    way ``benchmark/run.py`` lowers it."""
    run = _load("benchmark_run", ROOT / "benchmark" / "run.py")
    from apex_tpu import parallel
    sizes = run.with_toy(run.load_json("configs", config + ".json"))
    mix = run.with_toy(run.load_json("traffic", traffic + ".json"))
    mesh = parallel.data_parallel_mesh(jax.devices()[:1])
    key = run.seed_key(2147483659)
    pool = run.make_pool(mix, sizes, key, mesh, mix["per_chip_batch"])
    built = run.load_module("configs", config).build(sizes, key, mesh,
                                                     pool[0])
    return built["step"].lower(built["carry"], *pool[0])


def _ops(text):
    return collections.Counter(
        re.findall(r"= \"?((?:stablehlo|chlo|func|sdy)\.\w+|call)\b", text))


@pytest.mark.parametrize("config,traffic,scopes", [
    ("bert_large", "mlm_s512_b16",
     ["optim/lamb/arena", "optim/lamb/norms", "optim/lamb/update",
      "apex_rows_lamb_stage1", "apex_rows_lamb_stage2", "apex_rows_l2norm",
      # d = 32 at the toy size: the packed (B*H, S, D) attention kernels
      "apex_attn_fwd_packed", "apex_attn_bwd_dq_packed",
      "apex_attn_bwd_dkv_packed", "apex_layer_norm_fwd",
      "apex_layer_norm_bwd", "apex_xentropy_fwd", "apex_xentropy_bwd",
      # 2 x 128 rows at the toy size: a conditional between the two heads
      "mlm/head_gathered", "mlm/head_full"]),
    ("resnet50", "img224_b256",
     ["optim/sgd/arena", "optim/sgd/update", "apex_rows_sgd",
      "apex_xentropy_fwd", "apex_xentropy_bwd"]),
    ("kimi_linear", "lm_s8192_b1",
     ["optim/adam/arena", "optim/adam/update", "apex_rows_adam",
      "kda/proj", "kda/conv", "kda/gate", "kda/scan", "kda/out",
      "mla/proj", "mla/attn", "mla/out", "lm/head",
      # the expert layer's two rules are jitted: inside their functions a
      # location starts at the scope
      "moe/route", "moe/dispatch", "moe/experts", "moe/combine",
      "moe/shared",
      "apex_attn_fwd_packed", "apex_attn_bwd_dq_packed",
      "apex_attn_bwd_dkv_packed", "apex_xentropy_fwd", "apex_xentropy_bwd"]),
])
def test_scopes_reach_the_lowered_step_and_add_no_op(monkeypatch, config,
                                                     traffic, scopes):
    texts = {}
    for variant in ("scoped", "bare"):      # both lowered from this one line
        with monkeypatch.context() as m:
            if variant == "bare":
                m.setattr(jax, "named_scope",
                          lambda name: contextlib.nullcontext())
            texts[variant] = _lowered_toy_step(config, traffic).as_text(
                debug_info=True)
    scoped, bare = texts["scoped"], texts["bare"]
    for scope in scopes:
        assert re.search(rf'[/("]{scope}[/)]', scoped), scope
    # jax.named_scope gone, amp's spans and the optimizer's phases go with
    # it; a kernel's scope is pallas_call's own and stays
    assert "/optim/lamb/" not in bare and "/optim/sgd/" not in bare
    assert "/optim/adam/" not in bare and "/kda/scan/" not in bare
    assert "/amp/update/" not in bare
    assert _ops(scoped) == _ops(bare) and sum(_ops(scoped).values()) > 100
    # off a TPU the kernels are interpreted: no Mosaic call in either
    assert (scoped.count("tpu_custom_call") == bare.count("tpu_custom_call"))
    sys.modules.pop("benchmark_run", None)
