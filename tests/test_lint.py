"""apexlint — jaxpr/HLO static-analysis pass suite.

One seeded-violation fixture per rule (a small jaxpr / HLO module that
triggers exactly its rule) plus a negative twin that must NOT fire —
the per-rule contract ISSUE 5 demands — and the integration claims:

- the donation rule's wasted-bytes estimate for the PRE-fix
  ``prof_bert.py``-structure step (undonated) agrees with
  ``prof.memory_report``'s params+optimizer_state attribution within
  5%, and the donated twin lints clean;
- the post-fix flagship-structure steps produce zero error-severity
  findings (the no-false-positive guard behind the
  ``run_tier1.sh --smoke`` gate);
- Report plumbing: baseline suppression round-trip, lint JSONL events
  through ``MetricsLogger(lint_sink=...)`` validating under
  ``check_metrics_schema.py --kind lint`` (in-process and subprocess);
- the two ``lint/*`` compile-check cases run as registered.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu import amp, lint, models, monitor, prof
from apex_tpu.lint import findings as F
from apex_tpu.optim import FusedSGD

_REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
_SCHEMA_SCRIPT = os.path.join(_REPO_ROOT, "scripts",
                              "check_metrics_schema.py")


def _rules(findings):
    return sorted({f.rule for f in findings})


# --- jaxpr pass: seeded violation + negative twin per rule -------------------

class TestRngKeyReuse:
    def test_fires_on_raw_key_reuse(self):
        def f(key, x):
            a = jax.random.normal(key, (4,))
            b = jax.random.uniform(key, (4,))
            return a + b + x

        fs = lint.lint_jaxpr(f, jax.random.PRNGKey(0), jnp.zeros(4))
        hits = [f_ for f_ in fs if f_.rule == "rng-key-reuse"]
        assert len(hits) == 1 and hits[0].count == 2
        assert hits[0].severity == "error"

    def test_fires_on_typed_key_reuse(self):
        def f(key, x):
            return (jax.random.normal(key, (4,))
                    + jax.random.uniform(key, (4,)) + x)

        fs = lint.lint_jaxpr(f, jax.random.key(0), jnp.zeros(4))
        assert "rng-key-reuse" in _rules(fs)

    def test_split_then_use_is_reuse(self):
        # splitting a key and ALSO drawing from it is the classic bug
        def f(key):
            k1, _ = jax.random.split(key)
            return jax.random.normal(key, (2,)) + jax.random.normal(
                k1, (2,))

        assert "rng-key-reuse" in _rules(
            lint.lint_jaxpr(f, jax.random.PRNGKey(0)))

    def test_clean_split_does_not_fire(self):
        def f(key, x):
            k1, k2 = jax.random.split(key)
            return (jax.random.normal(k1, (4,))
                    + jax.random.uniform(k2, (4,)) + x)

        assert "rng-key-reuse" not in _rules(
            lint.lint_jaxpr(f, jax.random.PRNGKey(0), jnp.zeros(4)))


class TestF64Creep:
    def test_fires_on_f64(self):
        with jax.enable_x64(True):
            fs = lint.lint_jaxpr(
                lambda x: jnp.sum(x.astype(jnp.float64)),
                jnp.zeros(4, jnp.float32))
        hits = [f for f in fs if f.rule == "f64-creep"]
        assert len(hits) == 1 and hits[0].severity == "error"
        assert hits[0].count >= 1

    def test_clean_f32_does_not_fire(self):
        fs = lint.lint_jaxpr(lambda x: jnp.sum(x * 2), jnp.zeros(4))
        assert "f64-creep" not in _rules(fs)


class TestFp32MatmulInAmp:
    def test_fires_under_half_policy(self):
        pol = amp.Policy.from_opt_level("O2")

        def mm(a, b):
            return a @ b

        fs = lint.lint_jaxpr(mm, jnp.zeros((8, 128)),
                             jnp.zeros((128, 128)), policy=pol)
        hits = [f for f in fs if f.rule == "fp32-matmul-in-amp"]
        assert len(hits) == 1 and hits[0].severity == "warning"

    def test_bf16_matmul_does_not_fire(self):
        pol = amp.Policy.from_opt_level("O2")

        def mm(a, b):
            return a @ b

        fs = lint.lint_jaxpr(
            mm, jnp.zeros((8, 128), jnp.bfloat16),
            jnp.zeros((128, 128), jnp.bfloat16), policy=pol)
        assert "fp32-matmul-in-amp" not in _rules(fs)

    def test_inactive_without_policy(self):
        def mm(a, b):
            return a @ b

        fs = lint.lint_jaxpr(mm, jnp.zeros((8, 128)),
                             jnp.zeros((128, 128)))
        assert "fp32-matmul-in-amp" not in _rules(fs)


class TestHostCallback:
    def test_fires_on_debug_print(self):
        def f(x):
            jax.debug.print("x={x}", x=x.sum())
            return x * 2

        fs = lint.lint_jaxpr(f, jnp.ones(4))
        hits = [f_ for f_ in fs if f_.rule == "host-callback-in-step"]
        assert len(hits) == 1 and hits[0].severity == "error"
        assert hits[0].op == "debug_print"      # the primitive on jax 0.9.0

    def test_clean_step_does_not_fire(self):
        fs = lint.lint_jaxpr(lambda x: x * 2, jnp.ones(4))
        assert fs == []


# --- HLO pass: seeded violation + negative twin per rule ---------------------

def _toy_amp_step():
    """Small Amp O2 train step with real params/opt-state arg paths."""
    pol = amp.Policy.from_opt_level("O2")
    params = {"w": jnp.zeros((64, 64), jnp.float32),
              "b": jnp.zeros((64,), jnp.float32)}
    amp_opt = amp.Amp(pol, FusedSGD(lr=0.1, momentum=0.9))
    state = amp_opt.init(params)
    x = jnp.zeros((8, 64))
    y = jnp.zeros((8, 64))

    def step(state, x, y):
        def loss_fn(mp):
            return jnp.mean((x @ mp["w"] + mp["b"] - y) ** 2)
        loss, grads, state, finite = amp_opt.backward(state, loss_fn)
        return amp_opt.apply_gradients(state, grads, finite), loss

    return step, state, x, y, pol


class TestDonationMiss:
    def test_fires_on_undonated_step(self):
        step, state, x, y, pol = _toy_amp_step()
        rep = lint.lint_step(jax.jit(step), state, x, y, policy=pol)
        hits = rep.by_rule("donation-miss")
        assert hits and all(h.severity == "error" for h in hits)
        # evidence: arg paths name the carried state, bytes estimated
        assert any("opt_state" in (h.scope or "") for h in hits)
        assert all((h.bytes or 0) > 0 for h in hits)

    def test_donated_step_is_clean(self):
        step, state, x, y, pol = _toy_amp_step()
        rep = lint.lint_step(jax.jit(step, donate_argnums=(0,)),
                             state, x, y, policy=pol)
        assert rep.by_rule("donation-miss") == []
        assert rep.errors == []

    def test_inference_params_not_flagged(self):
        # params that never come back out have no output to donate
        # into — not carried state, not a finding
        params = {"w": jnp.zeros((64, 64)), "b": jnp.zeros((64,))}

        def infer(params, x):
            return x @ params["w"] + params["b"]

        rep = lint.lint_step(jax.jit(infer), params, jnp.zeros((8, 64)))
        assert rep.by_rule("donation-miss") == []


class TestImplicitResharding:
    def test_fires_on_unscoped_collective(self, mesh8):
        def step(x):
            return jax.lax.psum(x, "data")

        m = jax.jit(jax.shard_map(step, mesh=mesh8,
                                  in_specs=(P("data"),),
                                  out_specs=P("data"), check_vma=False))
        text = m.lower(jnp.ones((8, 128))).compile().as_text()
        hits = [f for f in lint.lint_hlo_text(text)
                if f.rule == "implicit-resharding"]
        assert len(hits) == 1
        assert hits[0].severity == "warning"
        assert hits[0].op == "all-reduce"
        assert (hits[0].bytes or 0) > 0      # wire-byte cost attached

    def test_known_scope_not_flagged(self, mesh8):
        from apex_tpu.trace.spans import span

        def step(x):
            with span("ddp/sync_gradients", kind="collective"):
                return jax.lax.psum(x, "data")

        m = jax.jit(jax.shard_map(step, mesh=mesh8,
                                  in_specs=(P("data"),),
                                  out_specs=P("data"), check_vma=False))
        text = m.lower(jnp.ones((8, 128))).compile().as_text()
        assert [f for f in lint.lint_hlo_text(text)
                if f.rule == "implicit-resharding"] == []

    def test_zero_scatter_gather_scopes_known(self, mesh8):
        # the ZeRO optimizer's own collectives run under
        # zero/grad_scatter / zero/param_gather spans — planned, clean
        from apex_tpu.optim.distributed import (_all_gather_shard,
                                                _reduce_scatter_mean)

        def step(x):
            s = _reduce_scatter_mean(x, "data", 8)
            return _all_gather_shard(s, "data")

        m = jax.jit(jax.shard_map(step, mesh=mesh8, in_specs=(P(),),
                                  out_specs=P(), check_vma=False))
        text = m.lower(jnp.ones((64, 128))).compile().as_text()
        assert [f for f in lint.lint_hlo_text(text)
                if f.rule == "implicit-resharding"] == []


class TestHostTransfer:
    def test_fires_on_compiled_callback(self):
        def f(x):
            jax.debug.print("x={x}", x=x.sum())
            return x * 2

        rep = lint.lint_step(f, jnp.ones(4))
        hits = rep.by_rule("host-transfer")
        assert hits and hits[0].severity == "error"

    def test_clean_step_has_no_host_traffic(self):
        rep = lint.lint_step(lambda x: x * 2, jnp.ones(4))
        assert rep.by_rule("host-transfer") == []


class TestTilePadding:
    def test_fires_on_off_grid_dot(self):
        def mm(a, b):
            return a @ b

        text = prof.hlo.compiled_hlo(mm, jnp.zeros((9, 100)),
                                     jnp.zeros((100, 130)))
        hits = [f for f in lint.lint_hlo_text(text)
                if f.rule == "tile-padding"]
        assert hits
        assert all((f.bytes or 0) > 0 for f in hits)
        assert all(f.severity in ("info", "warning") for f in hits)

    def test_aligned_dot_does_not_fire(self):
        def mm(a, b):
            return a @ b

        text = prof.hlo.compiled_hlo(mm, jnp.zeros((8, 128)),
                                     jnp.zeros((128, 128)))
        assert [f for f in lint.lint_hlo_text(text)
                if f.rule == "tile-padding"] == []


# --- donation rule vs memory_report: the 5% agreement claim ------------------

def _bert_style_step(layers=2, hidden=64, heads=2, vocab=1000,
                     batch=2, seq=32):
    """The BERT-LAMB step at test scale — the SAME construction the
    bench row / apexlint flagship / prof_bert.py share
    (bench._bert_step_builder), with a tiny encoder."""
    import bench
    enc = models.BertEncoder(vocab, hidden=hidden, layers=layers,
                             heads=heads, max_len=seq * 2)
    step, state, (toks, labels), policy, _enc, _vars = \
        bench._bert_step_builder(batch, seq, encoder=enc, vocab=vocab)
    return step, state, toks, labels, policy


class TestDonationVsMemoryReport:
    def test_prefix_wasted_bytes_agree_within_5pct(self):
        """The PRE-fix (undonated) prof_bert-structure step: the
        donation rule's wasted-bytes total must agree with the
        memory_report params+optimizer_state attribution within 5% —
        both read the same carried-state buffers off the same compiled
        module."""
        step, state, toks, labels, pol = _bert_style_step()
        compiled = jax.jit(step).lower(state, toks, labels).compile()
        rep = lint.lint_step(step, state, toks, labels, policy=pol,
                             compiled=compiled, min_donation_bytes=0)
        wasted = rep.wasted_bytes("donation-miss")
        assert wasted > 0
        mrep = prof.memory_report(compiled)
        attr = (mrep.classes["params"]
                + mrep.classes["optimizer_state"])
        assert attr > 0
        assert abs(wasted - attr) / attr < 0.05, (wasted, attr)

    @pytest.mark.slow       # second full BERT-structure compile (~15s);
    def test_postfix_step_lints_clean(self):     # smoke lints full-size
        step, state, toks, labels, pol = _bert_style_step()
        rep = lint.lint_step(jax.jit(step, donate_argnums=(0,)),
                             state, toks, labels, policy=pol)
        assert rep.errors == [], rep.table()


# --- no-false-positive guard: flagship-structure steps -----------------------

class TestFlagshipClean:
    @pytest.mark.slow       # ResNet-50 compile ~35s on XLA:CPU; the
    # full-size flagship guard is the run_tier1.sh --smoke apexlint
    # gate (zero error-severity findings, --fail-on error)
    def test_resnet_o2_structure_lints_clean(self):
        """The bench flagship step structure (ResNet + amp O2 +
        FusedSGD + donated carried state) at test scale: zero
        error-severity findings — the guard behind the smoke gate's
        full-size run."""
        import bench
        step, (state, batch_stats), (x, y) = bench._resnet_step_builder(
            4, 32, "O2")
        rep = lint.lint_step(jax.jit(step, donate_argnums=(0, 1)),
                             state, batch_stats, x, y,
                             policy=amp.Policy.from_opt_level("O2"))
        assert rep.errors == [], rep.table()


# --- precision pass (APX3xx): seeded violation + negative twin per rule ------

def _pp(fn, *args, policy=None):
    """Trace + precision-analyze; returns the findings list."""
    return lint.precision_analysis(
        jax.make_jaxpr(fn)(*args), policy=policy).findings


def _by(findings, rule):
    return [f for f in findings if f.rule == rule]


class TestUnscaledNarrowCast:                               # APX301
    def test_fires_on_raw_fp8_cast(self):
        fs = _pp(lambda x: x.astype(jnp.float8_e4m3fn),
                 jnp.ones((16,), jnp.float32))
        hits = _by(fs, "unscaled-narrow-cast")
        assert len(hits) == 1 and hits[0].severity == "error"
        assert hits[0].dtype_from == "fp32"
        assert hits[0].dtype_to == "fp8_e4m3"
        assert hits[0].scale_provenance == "unscaled"

    def test_site_scaled_cast_is_clean(self):
        # the O4 scaled-cast recipe: a dominating scale multiply
        fs = _pp(lambda x, s: (x * s).astype(jnp.float8_e4m3fn),
                 jnp.ones((16,), jnp.float32), jnp.float32(64.0))
        assert _by(fs, "unscaled-narrow-cast") == []

    def test_loss_scaled_fp8_cast_still_fires(self):
        # a global loss scale is NOT a per-site scale: fp8 exponents
        # need placing per site — provenance names the distinction
        def f(params, x, s):
            def loss_fn(p):
                return jnp.mean((x @ p) ** 2) * s
            return jax.grad(loss_fn)(params).astype(jnp.float8_e5m2)
        fs = _pp(f, jnp.ones((4, 4), jnp.float32),
                 jnp.ones((8, 4), jnp.float32), jnp.float32(1024.0))
        hits = _by(fs, "unscaled-narrow-cast")
        assert hits and hits[0].severity == "error"
        assert hits[0].scale_provenance == "loss-scaled"

    def test_fp16_warning_only_without_loss_scaling(self):
        def f(x):
            return x.astype(jnp.float16)
        x = jnp.ones((16,), jnp.float32)
        fs = _pp(f, x)                         # no policy: warning
        hits = _by(fs, "unscaled-narrow-cast")
        assert len(hits) == 1 and hits[0].severity == "warning"
        pol = amp.Policy.from_opt_level("O3")  # loss-scaled: clean
        assert pol.uses_loss_scaling
        assert _by(_pp(f, x, policy=pol), "unscaled-narrow-cast") == []

    def test_bf16_cast_exempt(self):
        fs = _pp(lambda x: x.astype(jnp.bfloat16),
                 jnp.ones((16,), jnp.float32))
        assert _by(fs, "unscaled-narrow-cast") == []


class TestDoubleRounding:                                   # APX302
    def test_fires_on_chained_narrowing(self):
        def f(x, s):
            y = x.astype(jnp.bfloat16)         # round 1 (f32 -> bf16)
            return (y * s.astype(jnp.bfloat16)).astype(
                jnp.float8_e4m3fn)             # round 2, scaled
        fs = _pp(f, jnp.ones((16,), jnp.float32), jnp.float32(8.0))
        hits = _by(fs, "double-rounding")
        assert len(hits) == 1 and hits[0].severity == "warning"
        assert hits[0].dtype_from == "bf16"
        assert hits[0].dtype_to == "fp8_e4m3"

    def test_round_trip_is_clean(self):
        # bf16 -> f32 -> bf16 destroys nothing new
        fs = _pp(lambda x: x.astype(jnp.float32).astype(jnp.bfloat16),
                 jnp.ones((16,), jnp.bfloat16))
        assert _by(fs, "double-rounding") == []

    def test_arithmetic_resets_depth(self):
        # a sum of rounded values is a new quantity: one narrowing of
        # it is a single rounding
        def f(x, y):
            a = x.astype(jnp.bfloat16) + y.astype(jnp.bfloat16)
            return a.astype(jnp.float32).astype(jnp.bfloat16)
        fs = _pp(f, jnp.ones((16,), jnp.float32),
                 jnp.ones((16,), jnp.float32))
        assert _by(fs, "double-rounding") == []


def _leaky_grad_step(unscale):
    def step(params, x, scale):
        def loss_fn(p):
            return jnp.mean((x @ p) ** 2) * scale   # scale_loss shape
        g = jax.grad(loss_fn)(params)
        if unscale:
            inv = (1.0 / scale).astype(jnp.float32)
            g = g.astype(jnp.float32) * inv         # unscale_grads
        return params - 0.1 * g
    return (step, jnp.ones((4, 4), jnp.float32),
            jnp.ones((8, 4), jnp.float32), jnp.float32(1024.0))


class TestScaleLeak:                                        # APX303
    def test_fires_when_unscale_missing(self):
        step, p, x, s = _leaky_grad_step(unscale=False)
        hits = _by(_pp(step, p, x, s), "scale-leak")
        assert hits and all(h.severity == "error" for h in hits)
        assert hits[0].scale_provenance == "loss-scaled"

    def test_unscaled_twin_is_clean(self):
        step, p, x, s = _leaky_grad_step(unscale=True)
        assert _by(_pp(step, p, x, s), "scale-leak") == []

    def test_one_unscaled_path_still_fires(self):
        # the unscale must happen on EVERY path: taint joins as union
        def f(pred, x, s):
            _ = jnp.sum(x) * s                      # mint the token
            return jax.lax.cond(pred, lambda: x * s, lambda: x)
        fs = _pp(f, jnp.asarray(True), jnp.ones((8,), jnp.float32),
                 jnp.float32(128.0))
        assert _by(fs, "scale-leak")

    def test_scalar_outputs_exempt(self):
        # the scaled loss / scaler-state update are scalar and benign
        def f(x, s):
            return jnp.sum(x) * s
        fs = _pp(f, jnp.ones((8,), jnp.float32), jnp.float32(2.0))
        assert _by(fs, "scale-leak") == []


class TestMasterWeightViolation:                            # APX304
    def _update(self):
        def f(params, g):
            return params - 0.1 * g
        return (f, jnp.ones((32, 32), jnp.bfloat16),
                jnp.ones((32, 32), jnp.bfloat16))

    def test_o2_half_update_is_error(self):
        f, p, g = self._update()
        hits = _by(_pp(f, p, g, policy=amp.Policy.from_opt_level("O2")),
                   "master-weight-violation")
        assert len(hits) == 1 and hits[0].severity == "error"
        assert hits[0].dtype_from == "bf16"
        assert hits[0].dtype_to == "fp32"

    def test_o3_half_update_is_info(self):
        # pure-half is O3's documented design: advisory, not error
        f, p, g = self._update()
        hits = _by(_pp(f, p, g, policy=amp.Policy.from_opt_level("O3")),
                   "master-weight-violation")
        assert len(hits) == 1 and hits[0].severity == "info"

    def test_no_policy_silent(self):
        f, p, g = self._update()
        assert _by(_pp(f, p, g), "master-weight-violation") == []

    def test_master_chain_twin_is_clean(self):
        def f(master32, g16):
            new = master32 - 0.1 * g16.astype(jnp.float32)
            return new.astype(jnp.bfloat16), new
        fs = _pp(f, jnp.ones((32, 32), jnp.float32),
                 jnp.ones((32, 32), jnp.bfloat16),
                 policy=amp.Policy.from_opt_level("O2"))
        assert _by(fs, "master-weight-violation") == []


class TestHalfAccumulation:                                 # APX305
    def test_fp16_dot_fires(self):
        fs = _pp(lambda a, b: a @ b,
                 jnp.ones((4, 4), jnp.float16), jnp.ones((4, 4),
                                                         jnp.float16))
        hits = _by(fs, "half-accumulation")
        assert len(hits) == 1 and hits[0].severity == "warning"

    def test_widened_dot_is_clean(self):
        def f(a, b):
            return jax.lax.dot_general(
                a, b, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        fs = _pp(f, jnp.ones((4, 4), jnp.float16),
                 jnp.ones((4, 4), jnp.float16))
        assert _by(fs, "half-accumulation") == []

    def test_bf16_dot_exempt(self):
        # the MXU widens bf16 dot accumulation in hardware
        fs = _pp(lambda a, b: a @ b,
                 jnp.ones((4, 4), jnp.bfloat16),
                 jnp.ones((4, 4), jnp.bfloat16))
        assert _by(fs, "half-accumulation") == []

    def test_fp16_accumulating_sum_fires(self):
        # cumsum keeps the operand dtype (also exercises the pjit
        # sub-jaxpr walk: jnp.cumsum traces as a nested jaxpr);
        # NB ``jnp.sum`` auto-widens to f32 even with ``dtype=f16``
        fs = _pp(lambda a: jnp.cumsum(a), jnp.ones((64,), jnp.float16))
        hits = _by(fs, "half-accumulation")
        assert hits and hits[0].severity == "warning"
        assert hits[0].op == "cumsum"

    def test_bf16_sum_is_info(self):
        # plain sum chains DO accumulate bf16 (unlike the MXU dot)
        fs = _pp(lambda a: jnp.cumsum(a), jnp.ones((64,), jnp.bfloat16))
        hits = _by(fs, "half-accumulation")
        assert hits and hits[0].severity == "info"

    def test_widened_sum_is_clean(self):
        fs = _pp(lambda a: jnp.sum(a, dtype=jnp.float32),
                 jnp.ones((64,), jnp.bfloat16))
        assert _by(fs, "half-accumulation") == []


def _fixture_report():
    from apex_tpu.monitor import numerics as nx
    path = os.path.join(_REPO_ROOT, "tests", "fixtures",
                        "bert_numerics_stats.json")
    with open(path) as f:
        return nx.precision_report(nx.stats_from_json(f.read()))


def _collective(dtype, scope="ddp/sync_gradients",
                opcode="all-reduce"):
    from apex_tpu.lint.spmd_pass import CollectiveInstr
    return CollectiveInstr(index=0, name=f"{opcode}.1", opcode=opcode,
                           channel_id=1, replica_groups=((0, 1),),
                           dtypes=(dtype,), bytes=1 << 20, scope=scope,
                           use_global_ids=False)


class TestWireDtypeUnsafe:                                  # APX306
    def _bf16_required(self):
        import dataclasses as dc
        rep = _fixture_report()
        rows = [dc.replace(r, required_dtype="bf16")
                for r in rep.rows[:3]]

        class _R:
            def __init__(self, rows):
                self.rows = rows

            def fp8_candidates(self, k=None):
                return []
        return _R(rows)

    def test_fires_on_narrow_wire(self):
        hits = lint.wire_dtype_findings(
            [_collective("f8e4m3fn")], self._bf16_required())
        assert len(hits) == 1 and hits[0].severity == "error"
        assert hits[0].id == "APX306"
        assert hits[0].dtype_from == "fp8_e4m3"
        assert hits[0].dtype_to == "bf16"
        assert hits[0].count == 3

    def test_committed_fixture_bf16_wire_is_clean(self):
        # the committed BERT fixture measures every site fp8-safe: a
        # bf16 grad sync is wide enough for all of them
        assert lint.wire_dtype_findings(
            [_collective("bf16")], _fixture_report()) == []

    def test_int8_wire_exempt(self):
        # the hierarchical int8 EF sync carries error feedback by
        # design — non-float wires are not precision subjects
        assert lint.wire_dtype_findings(
            [_collective("s8")], self._bf16_required()) == []

    def test_non_reduction_collectives_exempt(self):
        assert lint.wire_dtype_findings(
            [_collective("f8e4m3fn", opcode="all-gather")],
            self._bf16_required()) == []


class TestMisScaledToyAtEveryOptLevel:
    """Acceptance pin: a deliberately mis-scaled fp8-cast toy program
    — scaled loss, gradient cast to fp8 with no per-site scale, no
    unscale before commit — is caught by APX301 AND APX303 at every
    opt level (both rules are policy-independent by design)."""

    @pytest.mark.parametrize("lv", ["O0", "O1", "O2", "O3"])
    def test_caught(self, lv):
        def bad_step(params, x, scale):
            def loss_fn(p):
                return jnp.mean((x @ p) ** 2) * scale
            g = jax.grad(loss_fn)(params)
            g8 = g.astype(jnp.float8_e4m3fn)
            return params - 0.1 * g8.astype(jnp.float32)
        rep = lint.lint_step(
            bad_step, jnp.ones((4, 4), jnp.float32),
            jnp.ones((8, 4), jnp.float32), jnp.float32(1024.0),
            policy=amp.Policy.from_opt_level(lv),
            rules=("unscaled-narrow-cast", "scale-leak"))
        assert rep.by_rule("unscaled-narrow-cast"), rep.table()
        assert rep.by_rule("scale-leak"), rep.table()
        assert all(f.severity == "error" for f in rep.findings)


class TestAmpStepPrecisionClean:
    """No-false-positive guard: the real Amp machinery (scale_loss /
    unscale_grads / master-weight plumbing) certifies clean at every
    opt level — the fast-scale twin of the run_tier1.sh
    ``--opt-level all`` flagship sweep."""

    @pytest.mark.parametrize("lv", ["O0", "O1", "O2", "O3"])
    def test_toy_amp_step_has_no_precision_errors(self, lv):
        pol = amp.Policy.from_opt_level(lv)
        params = {"w": jnp.zeros((64, 64), jnp.float32),
                  "b": jnp.zeros((64,), jnp.float32)}
        amp_opt = amp.Amp(pol, FusedSGD(lr=0.1, momentum=0.9))
        state = amp_opt.init(params)
        x = jnp.zeros((8, 64))
        y = jnp.zeros((8, 64))

        def step(state, x, y):
            def loss_fn(mp):
                return jnp.mean((x @ mp["w"] + mp["b"] - y) ** 2)
            loss, grads, state, finite = amp_opt.backward(state,
                                                          loss_fn)
            return amp_opt.apply_gradients(state, grads, finite), loss

        fs = _pp(step, state, x, y, policy=pol)
        errors = [f for f in fs if f.severity == "error"]
        assert errors == [], errors


class TestPrecisionPreflight:
    def _clean_step(self):
        step, p, x, s = _leaky_grad_step(unscale=True)
        return jax.make_jaxpr(step)(p, x, s)

    def test_candidate_sites_pin_against_committed_fixture(self):
        # CI pin: the preflight's candidate-site set must equal the
        # committed fixture's measured site set (diff == empty) on a
        # statically-clean program — all 84 castable, ranked
        rep = _fixture_report()
        pf = lint.precision_preflight(self._clean_step(), report=rep)
        assert pf.blocking == []
        assert len(pf.rows) == len(rep.rows) == 84
        assert {r["site"] for r in pf.candidates} \
            == {r.site for r in rep.rows}
        ranks = [lint.DTYPE_NAMES.index(r["required_dtype"])
                 for r in pf.rows]
        assert ranks == sorted(ranks)
        assert "statically castable" in pf.table()

    def test_static_errors_block_every_candidate(self):
        bad = jax.make_jaxpr(
            lambda x: x.astype(jnp.float8_e4m3fn))(
                jnp.ones((8,), jnp.float32))
        pf = lint.precision_preflight(bad, report=_fixture_report())
        assert pf.blocking == ["APX301"]
        assert pf.candidates == [] and len(pf.rows) == 84
        assert "blocked by: APX301" in pf.table()

    def test_hlo_join_blocks_on_wire(self):
        # a narrow-wire APX306 error (static x measured join) blocks
        # the preflight exactly like a trace-side error
        import dataclasses as dc
        rep = _fixture_report()
        rep = dc.replace(rep, rows=[
            dc.replace(r, required_dtype="bf16") for r in rep.rows])
        hlo = ('HloModule m\nENTRY e {\n'
               '  p = f8e4m3fn[8]{0} parameter(0)\n'
               '  ROOT r = f8e4m3fn[8]{0} all-reduce(p), channel_id=1,'
               ' replica_groups={{0,1}}, to_apply=add,'
               ' metadata={op_name="ddp/sync_gradients"}\n}\n')
        from apex_tpu.lint.spmd_pass import extract_collective_schedule
        assert extract_collective_schedule(hlo)      # parser saw it
        pf = lint.precision_preflight(self._clean_step(), report=rep,
                                      hlo_text=hlo)
        assert pf.blocking == ["APX306"]
        assert pf.candidates == []


class TestSingleSharedTrace:
    def test_lint_step_traces_exactly_once(self, monkeypatch):
        """The de-dup satellite: jaxpr pass, APX204 and the precision
        pass share ONE ``jax.make_jaxpr`` trace inside ``lint_step``
        (and zero with ``jaxpr=`` pre-made), pinned alongside the
        CompileWatcher's zero-compile guarantee for trace-only rules."""
        from apex_tpu.prof import compile_watch as cw
        cw.install()
        step, state, x, y, pol = _toy_amp_step()
        calls = []
        real = jax.make_jaxpr

        def counted(fn, *a, **k):
            calls.append(fn)
            return real(fn, *a, **k)

        monkeypatch.setattr(jax, "make_jaxpr", counted)
        trace_rules = tuple(lint._JAXPR_RULES | lint._PRECISION_RULES)
        compiles0 = cw.global_counters()["compiles"]
        lint.lint_step(step, state, x, y, policy=pol,
                       rules=trace_rules)
        assert len(calls) == 1          # ONE shared trace, all passes
        assert cw.global_counters()["compiles"] == compiles0
        calls.clear()
        jaxpr = real(step)(state, x, y)
        lint.lint_step(None, policy=pol, jaxpr=jaxpr,
                       rules=trace_rules)
        assert calls == []              # pre-made trace: zero traces
        assert cw.global_counters()["compiles"] == compiles0


class TestPrecisionEvidenceContract:
    def test_dtype_fields_validated(self):
        with pytest.raises(ValueError):
            F.Finding(rule="unscaled-narrow-cast", message="m",
                      dtype_from="f32")        # HLO spelling, not ours
        with pytest.raises(ValueError):
            F.Finding(rule="scale-leak", message="m",
                      scale_provenance="scaled")

    def test_to_event_carries_evidence(self):
        f = F.Finding(rule="unscaled-narrow-cast", message="m",
                      dtype_from="fp32", dtype_to="fp8_e4m3",
                      scale_provenance="unscaled")
        ev = f.to_event()
        assert ev["dtype_from"] == "fp32"
        assert ev["dtype_to"] == "fp8_e4m3"
        assert ev["scale_provenance"] == "unscaled"

    def test_fingerprint_excludes_dtype_evidence(self):
        a = F.Finding(rule="unscaled-narrow-cast", message="m",
                      op="convert_element_type", scope="s",
                      dtype_from="fp32", dtype_to="fp8_e4m3")
        b = F.Finding(rule="unscaled-narrow-cast", message="m",
                      op="convert_element_type", scope="s",
                      dtype_from="bf16", dtype_to="fp8_e5m2")
        assert a.fingerprint() == b.fingerprint()

    def test_schema_negative_twins(self, tmp_path):
        sys.path.insert(0, os.path.join(_REPO_ROOT, "scripts"))
        try:
            import check_metrics_schema as cms
        finally:
            sys.path.pop(0)
        good = {"kind": "lint_finding", "rule": "unscaled-narrow-cast",
                "id": "APX301", "severity": "error", "message": "m",
                "dtype_from": "fp32", "dtype_to": "fp8_e4m3",
                "scale_provenance": "unscaled", "scope": None}
        assert cms.check_lint_lines([json.dumps(good)]) == []
        for field, bad_val in (("dtype_from", "f32"),
                               ("dtype_to", "float8"),
                               ("scale_provenance", "scaled")):
            bad = dict(good)
            bad[field] = bad_val
            errs = cms.check_lint_lines([json.dumps(bad)])
            assert errs, f"{field}={bad_val!r} must be rejected"


class TestDynamicsFlagshipClean:
    @pytest.mark.slow       # ResNet structural compile like the other
    def test_dynamics_step_lints_clean(self):        # flagship guards
        """The PR-19 dynamics-instrumented step (``--flagship
        dynamics``): zero error-severity findings on the empty
        baseline, like guarded/ckpt — the observatory's self-audit."""
        sys.path.insert(0, os.path.join(_REPO_ROOT, "scripts"))
        try:
            import apexlint
        finally:
            sys.path.pop(0)
        fn, args, policy, name = apexlint._build_flagship_dynamics()
        rep = lint.lint_step(fn, *args, policy=policy, fn_name=name)
        assert rep.errors == [], rep.table()


# --- Report / baseline / JSONL plumbing --------------------------------------

class TestReportPlumbing:
    def _report(self):
        def f(x):
            jax.debug.print("x={x}", x=x.sum())
            return x * 2

        return lint.lint_step(f, jnp.ones(4), fn_name="seeded")

    def test_severity_ordering_and_table(self):
        rep = self._report()
        sevs = [f.severity for f in rep.findings]
        assert sevs == sorted(sevs, key=F.SEVERITIES.index)
        t = rep.table()
        assert "APX004" in t and "fix:" in t

    def test_rule_catalog_is_stable(self):
        assert {r.id for r in F.RULES.values()} == {
            "APX001", "APX002", "APX003", "APX004",
            "APX101", "APX102", "APX103", "APX104",
            "APX201", "APX202", "APX203", "APX204",
            "APX301", "APX302", "APX303", "APX304",
            "APX305", "APX306"}
        for r in F.RULES.values():
            assert r.severity in F.SEVERITIES and r.fix and r.title

    def test_baseline_round_trip(self, tmp_path):
        rep = self._report()
        assert rep.errors
        path = tmp_path / "baseline.json"
        n = lint.save_baseline(str(path), rep)
        assert n >= 1
        baseline = lint.load_baseline(str(path))
        clean = rep.apply_baseline(baseline)
        assert len(clean) == 0 and clean.suppressed == len(rep)
        # a missing baseline file is an empty baseline (the committed
        # CI file starts empty on purpose)
        assert lint.load_baseline(str(tmp_path / "missing.json")) == []

    def test_committed_baseline_starts_empty(self):
        path = os.path.join(_REPO_ROOT, "scripts",
                            "apexlint_baseline.json")
        assert lint.load_baseline(path) == []

    def test_jsonl_round_trip_validates(self, tmp_path):
        """Report -> MetricsLogger lint channel -> JSONL ->
        check_metrics_schema --kind lint (module-level and subprocess
        CLI) — the round-trip acceptance test."""
        sys.path.insert(0, os.path.join(_REPO_ROOT, "scripts"))
        try:
            import check_metrics_schema as cms
        finally:
            sys.path.pop(0)
        rep = self._report()
        path = tmp_path / "lint.jsonl"
        logger = monitor.MetricsLogger(
            sinks=[], lint_sink=monitor.JSONLSink(str(path)))
        logger.attach_lint_report(rep)
        logger.close()
        lines = path.read_text().strip().splitlines()
        assert len(lines) == 1 + len(rep)
        assert json.loads(lines[0])["kind"] == "lint_report"
        assert cms.check_lint_lines(lines) == []
        proc = subprocess.run(
            [sys.executable, _SCHEMA_SCRIPT, "--kind", "lint",
             str(path)], capture_output=True, text=True, cwd=_REPO_ROOT)
        assert proc.returncode == 0, proc.stderr
        # and the validator actually rejects garbage
        bad = tmp_path / "bad.jsonl"
        bad.write_text('{"kind": "lint_finding", "rule": "x"}\n')
        assert cms.check_lint_lines(
            bad.read_text().splitlines()) != []

    def test_fingerprint_excludes_bytes(self):
        a = F.Finding(rule="donation-miss", message="m", op="arg0",
                      scope="state.params", bytes=100)
        b = F.Finding(rule="donation-miss", message="m", op="arg0",
                      scope="state.params", bytes=999)
        assert a.fingerprint() == b.fingerprint()


# --- compile-check cases ------------------------------------------------------

class TestCompileCheckCases:
    def _case(self, name):
        from apex_tpu.ops import compile_check as cc
        return dict(cc.CASES)[name]

    def test_no_extra_dispatch_case(self):
        self._case("lint/no-extra-dispatch")()

    def test_precision_no_extra_dispatch_case(self):
        # precision pass + preflight leave the step's HLO bit-identical
        # (donated and undonated, with and without the measured join)
        self._case("lint/precision-no-extra-dispatch")()

    @pytest.mark.slow       # compiles 5 kernel families (~20s); also
    def test_kernel_sweep_case(self):            # runs on-device via
        self._case("lint/kernel-sweep")()        # python -m apex_tpu.ops
