"""Operation cast-policy tables for the precision engine.

TPU-native redesign of the reference's cast lists
(`apex/amp/lists/functional_overrides.py:18-80`,
`apex/amp/lists/torch_overrides.py:7-117`): instead of monkey-patching a
framework namespace, these tables classify *our* library ops (and flax module
classes) into three groups, applied at the library boundary by
``apex_tpu.amp.policy_scope`` / the flax interceptor:

- HALF  ("whitelist"): tensor-core/MXU ops — run in the policy's half dtype.
- FLOAT ("blacklist"): reductions, norms, losses, transcendentals — fp32.
- PROMOTE: multi-input elementwise ops — widest input dtype wins.

Users can extend the tables with :func:`register_half_op`,
:func:`register_float_op`, :func:`register_promote_op` (the analogue of
``amp.register_half_function`` etc., `apex/amp/amp.py:30-64`).
"""

from __future__ import annotations

# --- Op-name tables (consulted by apex_tpu.ops / apex_tpu.layers) -----------

# MXU-bound ops: large matmuls/convs want bf16 on TPU
# (reference whitelist: conv*, linear, matmul/bmm/addmm..., rnn cells)
HALF_OPS = {
    "conv", "conv1d", "conv2d", "conv3d", "conv_transpose",
    "dense", "linear", "matmul", "einsum", "dot_general",
    "attention", "mlp", "rnn_cell", "lstm_cell", "gru_cell",
    # ops/moe.py: the held experts' batched matmuls
    "moe_experts",
}

# Precision-sensitive ops: keep fp32
# (reference blacklist: softmax/log_softmax, norms, losses, exp/pow/sum...)
FLOAT_OPS = {
    "softmax", "log_softmax", "layer_norm", "group_norm", "batch_norm",
    "rms_norm", "weight_norm", "cross_entropy", "softmax_cross_entropy",
    "nll_loss", "mse_loss", "l1_loss", "cosine_similarity",
    "exp", "expm1", "log", "log1p", "log2", "log10", "pow", "erf", "erfinv",
    "sum", "mean", "prod", "cumsum", "cumprod", "var", "std", "norm",
    "sigmoid_focal_loss", "renorm", "softplus", "gelu_exact",
    # ops/delta_rule.py: the recurrence's state, decay and triangular solve;
    # ops/moe.py: the router's scores, the choice and the weights;
    # ops/short_conv.py: the delta-rule layers' convolution, SiLU and l2-norm
    "gated_delta_rule", "moe_router", "short_conv",
    # models/qwen3_next.py: the rotation of q and k by position (angles up
    # to the context length: bfloat16 holds 8 bits of them)
    "rotary",
}

# Multi-arg elementwise ops: promote to the widest floating dtype
PROMOTE_OPS = {
    "add", "sub", "mul", "div", "addcmul", "addcdiv",
    "concatenate", "stack", "where", "equal", "maximum", "minimum",
    "atan2", "cross", "bilinear", "dot",
}

# Ops that must never see low precision (reference: binary_cross_entropy is
# *banned* under amp with a fix-it message, `functional_overrides.py:73-80`)
BANNED_HALF_OPS = {
    "binary_cross_entropy",
}

BANNED_MESSAGE = (
    "{name} is numerically unsafe in {dtype}. Compute it in float32 — e.g. "
    "use apex_tpu.ops.softmax_cross_entropy (fused, fp32 internals) or pass "
    "logits and use a *_with_logits loss, which is stable in mixed precision."
)


def classify(op_name: str) -> str:
    """Return 'half' | 'float' | 'promote' | 'neutral' for an op name."""
    if op_name in BANNED_HALF_OPS:
        return "banned"
    if op_name in HALF_OPS:
        return "half"
    if op_name in FLOAT_OPS:
        return "float"
    if op_name in PROMOTE_OPS:
        return "promote"
    return "neutral"


def register_half_op(name) -> None:
    """Classify op ``name`` (str) as half, or — given a ``(module,
    attr)`` pair — give a user-owned *raw function* the O1
    functional-patch half treatment (the reference's arbitrary-function
    registration, `apex/amp/amp.py:30-64`)."""
    if not isinstance(name, str):
        from apex_tpu.amp.functional_patch import register_raw_target
        register_raw_target(name[0], name[1], "half")
        return
    FLOAT_OPS.discard(name)
    PROMOTE_OPS.discard(name)
    HALF_OPS.add(name)


def register_float_op(name) -> None:
    """Classify op ``name`` (str) as fp32, or register a raw ``(module,
    attr)`` target for the fp32 functional patch."""
    if not isinstance(name, str):
        from apex_tpu.amp.functional_patch import register_raw_target
        register_raw_target(name[0], name[1], "float")
        return
    HALF_OPS.discard(name)
    PROMOTE_OPS.discard(name)
    FLOAT_OPS.add(name)


def register_promote_op(name: str) -> None:
    HALF_OPS.discard(name)
    FLOAT_OPS.discard(name)
    PROMOTE_OPS.add(name)


def unregister_op(name) -> None:
    """Remove an op-name (str) from every classification table, or —
    given a ``(module, attr)`` pair — drop a raw functional-patch
    registration (restoring the original immediately if a scope is
    live). Idempotent."""
    if not isinstance(name, str):
        from apex_tpu.amp.functional_patch import unregister_raw_target
        unregister_raw_target(name[0], name[1])
        return
    HALF_OPS.discard(name)
    FLOAT_OPS.discard(name)
    PROMOTE_OPS.discard(name)


# --- Flax module-class tables (consulted by the interceptor) ----------------

# user-registered module classes (the module-level analogue of
# register_half_function/register_float_function, `apex/amp/amp.py:30-64`;
# user registrations out-prioritise the built-in tables)
_EXTRA_HALF_MODULES: list = []
_EXTRA_FLOAT_MODULES: list = []


def register_half_module(cls) -> None:
    """Intercepted calls of ``cls`` run in the policy half dtype."""
    if cls in _EXTRA_FLOAT_MODULES:
        _EXTRA_FLOAT_MODULES.remove(cls)
    if cls not in _EXTRA_HALF_MODULES:
        _EXTRA_HALF_MODULES.append(cls)


def register_float_module(cls) -> None:
    """Intercepted calls of ``cls`` run in fp32."""
    if cls in _EXTRA_HALF_MODULES:
        _EXTRA_HALF_MODULES.remove(cls)
    if cls not in _EXTRA_FLOAT_MODULES:
        _EXTRA_FLOAT_MODULES.append(cls)


def _flax_module_tables():
    """Lazily build (HALF_MODULES, FLOAT_MODULES) tuples of flax classes.

    Mirrors the op surface of the reference O1 whitelist/blacklist
    (`functional_overrides.py:18-80`) at module granularity: everything
    MXU-bound (dense/conv/attention/embedding lookups feeding matmuls)
    goes half; statistics/norm modules stay fp32. User registrations are
    placed FIRST so they win isinstance checks over the built-ins.
    """
    import flax.linen as nn

    half = [nn.Dense, nn.DenseGeneral, nn.Conv, nn.ConvTranspose,
            nn.Einsum, nn.ConvLocal, nn.Embed,
            nn.MultiHeadDotProductAttention, nn.SelfAttention]
    flt = [nn.LayerNorm, nn.BatchNorm, nn.GroupNorm, nn.RMSNorm]
    # aliases present only in some flax versions
    for name, dest in (("MultiHeadAttention", half),
                       ("InstanceNorm", flt)):
        cls = getattr(nn, name, None)
        if cls is not None and cls not in dest:
            dest.append(cls)
    # the interceptor consults the user registries BEFORE these, so a
    # user re-registration (or registered subclass) of a built-in wins
    return tuple(half), tuple(flt)
