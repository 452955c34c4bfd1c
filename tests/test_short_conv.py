"""``ops.short_conv`` (convolution + SiLU + per-head l2-norm in the scan's
``(B, T, H d)`` layout, two Pallas kernels, interpreted here) against the
``jax.numpy`` form it replaces in the delta-rule layers, ``_short_conv`` +
``_l2_normalised``: outputs and the gradients of ``x`` and of the taps.
Then its gated, activation-free form (a gated short-convolution mixer
between its projections) against the sum written out."""

import jax
import jax.numpy as jnp
import pytest

from apex_tpu import amp
from apex_tpu.ops import short_conv as sc
from apex_tpu.ops.short_conv import short_conv, short_conv_reference

HEAD = 128
#: the normalised ranges of each model's calls, by channels: Kimi's q (every
#: head, scaled), k (every head) and v (none) over 4096; Qwen's one call
#: over 8192 (q scaled, k, then v plain)
RANGES = {
    "kimi_q": (4096, ((0, 4096, HEAD ** -0.5),)),
    "kimi_k": (4096, ((0, 4096, 1.0),)),
    "kimi_v": (4096, ()),
    "qwen_qkv": (8192, ((0, 2048, HEAD ** -0.5), (2048, 4096, 1.0))),
}


def plain(x, taps, norm, head=HEAD):
    """The models' own lines before this op: ``_short_conv``, heads cut
    out, ``_l2_normalised`` and the scale on the ranges."""
    y = sc._short_conv(x, taps)
    for lo, hi, scale in norm:
        heads = y[..., lo:hi].reshape(*y.shape[:2], -1, head)
        y = y.at[..., lo:hi].set(
            (sc._l2_normalised(heads) * scale).reshape(*y.shape[:2], -1))
    return y


def inputs(b, t, c, k, dtype, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(keys[0], (b, t, c)).astype(dtype)
    taps = jax.random.uniform(keys[1], (k, c), minval=-0.5, maxval=0.5)
    return x, taps, jax.random.normal(keys[2], (b, t, c))


def compare(x, taps, weight, norm, rel=1e-5):
    """Output and both gradients of the op against the plain form's."""
    both = lambda fn: (fn(x, taps), jax.grad(
        lambda x, taps: jnp.sum(fn(x, taps) * weight), argnums=(0, 1))(
            x, taps))
    out, (d_x, d_taps) = both(lambda x, taps: short_conv(x, taps, norm, HEAD))
    ref, (r_x, r_taps) = both(lambda x, taps: plain(x, taps, norm))
    assert out.shape == ref.shape and out.dtype == jnp.float32
    assert d_x.dtype == x.dtype and d_taps.dtype == jnp.float32
    assert d_x.shape == x.shape and d_taps.shape == taps.shape
    assert float(jnp.max(jnp.abs(out - ref))) <= rel * float(
        jnp.max(jnp.abs(ref)))
    f32 = lambda a: a.astype(jnp.float32)
    # a bfloat16 cotangent is rounded once, from float32, on both sides
    assert float(jnp.max(jnp.abs(f32(d_x) - f32(r_x)))) <= (
        1e-2 if x.dtype == jnp.bfloat16 else 1e-5) * float(
            jnp.max(jnp.abs(f32(r_x))))
    assert float(jnp.max(jnp.abs(d_taps - r_taps))) <= 2e-5 * float(
        jnp.max(jnp.abs(r_taps)))


@pytest.fixture
def small_blocks(monkeypatch):
    """Blocks of 32 tokens, so that a short sequence is several blocks and
    the rows before and after a block come from its neighbours."""
    monkeypatch.setattr(sc, "BLOCK_T", 32)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("case", sorted(RANGES))
def test_each_models_ranges(case, dtype, small_blocks):
    """4096 and 8192 channels with the normalised ranges of each model's
    calls, in the dtype the projection hands over under O1 and without."""
    channels, norm = RANGES[case]
    compare(*inputs(1, 48, channels, 4, dtype), norm)


@pytest.mark.parametrize("taps", [4, 2])
@pytest.mark.parametrize("batch", [1, 2])
@pytest.mark.parametrize("length", [100, 20, 64],
                         ids=["no_whole_block", "under_a_block", "whole"])
def test_lengths_batches_and_taps(length, batch, taps, small_blocks):
    """A length that is no whole block (padded, the padding's cotangent
    dropped), one shorter than a block, whole blocks; the grid's batch axis
    (``d taps`` sums over it in VMEM); ``K`` 4 and 2."""
    norm = ((0, 256, 0.25), (384, 512, 1.0))
    compare(*inputs(batch, length, 640, taps, jnp.float32, seed=length), norm)


@pytest.mark.parametrize("row", [31, 32, 63, 64],
                         ids=["last_of_block_0", "first_of_block_1",
                              "last_of_block_1", "first_of_block_2"])
def test_an_impulse_crosses_a_block_border(row, small_blocks):
    """One token alone: its output reaches the next ``K - 1`` tokens, across
    a block's border (the forward's rows before a block); a cotangent on
    one token alone reaches ``d x`` of the ``K - 1`` before it (the
    backward's rows after a block)."""
    _, taps, _ = inputs(1, 96, 256, 4, jnp.float32)
    at = jnp.zeros((1, 96, 256)).at[:, row].set(1.0)
    norm = ((0, 128, 1.0),)
    out = short_conv(at, taps, norm, HEAD)
    ref = plain(at, taps, norm)
    assert float(jnp.max(jnp.abs(out - ref))) <= 1e-6
    assert bool(jnp.all(out[:, row:row + 4] != 0)) and not bool(
        jnp.any(out[:, row + 4:])) and not bool(jnp.any(out[:, :row]))
    x = inputs(1, 96, 256, 4, jnp.float32, seed=1)[0]
    d_x, d_ref = (jax.grad(lambda x: jnp.sum(fn(x) * at))(x) for fn in (
        lambda x: short_conv(x, taps, norm, HEAD),
        lambda x: plain(x, taps, norm)))
    assert float(jnp.max(jnp.abs(d_x - d_ref))) <= 1e-6
    assert bool(jnp.all(d_x[:, row - 3:row + 1] != 0)) and not bool(
        jnp.any(d_x[:, row + 1:])) and not bool(jnp.any(d_x[:, :row - 3]))


def test_channels_past_the_taps_are_not_read(small_blocks):
    """``x`` wider than the taps (a projection that holds more than the
    convolution's inputs): the first ``C`` channels' result, a zero
    cotangent on the rest."""
    x, taps, weight = inputs(1, 48, 512, 4, jnp.bfloat16)
    wide = jnp.concatenate([x, jnp.full((1, 48, 256), jnp.nan, x.dtype)], -1)
    norm = ((0, 256, 1.0),)
    run = lambda x: short_conv(x, taps, norm, HEAD)
    assert bool(jnp.all(run(wide) == run(x)))
    d_wide, d_x = (jax.grad(lambda x: jnp.sum(run(x) * weight))(a)
                   for a in (wide, x))
    assert d_wide.shape == wide.shape
    assert bool(jnp.all(d_wide[..., :512] == d_x))
    assert not bool(jnp.any(d_wide[..., 512:]))


def test_float32_inside_whatever_comes_in():
    """Under O1 the op takes a half projection and patched ``jax.numpy``:
    convolution, SiLU and norm stay float32, on both paths."""
    policy = amp.Policy.from_opt_level("O1")
    for head in (HEAD, 16):
        x, taps, _ = inputs(1, 32, 2 * head, 4, jnp.bfloat16)
        norm = ((0, head, 0.5),)
        with amp.auto_cast(policy):
            out = short_conv(x, taps, norm, head)
        assert out.dtype == jnp.float32
        ref = short_conv_reference(x.astype(jnp.float32), taps, norm, head)
        assert float(jnp.max(jnp.abs(out - ref))) <= 1e-6
    assert amp.lists.classify("short_conv") == "float"


def test_the_head_size_picks_the_path():
    """Whole 128-lane tiles take the kernels, anything else (the toy
    configurations' 16) the ``jax.numpy`` form; no argument does."""
    lowered = lambda head: jax.jit(jax.value_and_grad(lambda x, taps: jnp.sum(
        short_conv(x, taps, ((0, head, 1.0),), head)), argnums=(0, 1))).lower(
            *inputs(1, 32, 2 * head, 4, jnp.float32)[:2]).as_text(
                debug_info=True)
    assert "apex_short_conv_fwd" not in lowered(16)
    text = lowered(HEAD)
    assert "apex_short_conv_fwd/pallas_call" in text
    assert "apex_short_conv_bwd/pallas_call" in text
    x, taps, _ = inputs(1, 32, 32, 4, jnp.float32)
    norm = ((0, 16, 2.0),)
    assert bool(jnp.all(short_conv(x, taps, norm, 16)
                        == plain(x, taps, norm, 16)))



def test_calls_of_one_shape_share_one_trace(monkeypatch):
    """The kernels' launchers are jitted (``ops._dispatch.jit_launcher``): a
    step that holds the op many times traces and lowers each kind once, as a
    private function every site calls; ``kernel_calls`` counts the calls.
    The trace is keyed on ``use_interpret`` too: lowered for the TPU first,
    the same process still runs the op interpreted."""
    from apex_tpu.ops import _dispatch
    x, taps, _ = inputs(1, 32, 256, 4, jnp.float32)
    norm = ((0, 128, 1.0),)

    def thrice(x, taps):
        for _ in range(3):
            x = short_conv(x, taps, norm, HEAD)
        return short_conv(x, taps, (), HEAD)         # another kind

    with monkeypatch.context() as m:
        m.setattr(_dispatch, "use_interpret", lambda: False)
        text = jax.jit(thrice).trace(x, taps).lower(
            lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert text.count('kernel_name = "apex_short_conv_fwd"') == 2
    assert _dispatch.kernel_calls(text) == {"apex_short_conv_fwd": 4}
    ref = x
    for n in (norm, norm, norm, ()):
        ref = plain(ref, taps, n)
    assert float(jnp.max(jnp.abs(thrice(x, taps) - ref))) <= 1e-6


# --- the gated form: no activation, a gate before the taps and one after ----

def gated_plain(x, taps, before, after):
    """``g_after * conv(g_before * x)`` written out: the sum over the taps
    of the gated input shifted, zeros before the sequence."""
    c, (k, t) = taps.shape[1], (taps.shape[0], x.shape[1])
    part = lambda at: x[..., at:at + c].astype(jnp.float32)
    v = jnp.pad(part(0) * part(before), ((0, 0), (k - 1, 0), (0, 0)))
    return part(after) * sum(v[:, j:j + t] * taps[j] for j in range(k))


def gated_inputs(b, t, c, k, dtype, parts=3, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(keys[0], (b, t, parts * c)).astype(dtype)
    taps = jax.random.uniform(keys[1], (k, c), minval=-3 ** -0.5,
                              maxval=3 ** -0.5)
    return x, taps, jax.random.normal(keys[2], (b, t, c))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("shape", [(2, 100, 256, 3, 3), (1, 64, 128, 3, 4),
                                   (1, 20, 384, 2, 3)],
                         ids=["b2_no_whole_block", "x_four_parts_wide",
                              "under_a_block_k2"])
def test_the_gated_form_against_the_sum_written_out(shape, dtype,
                                                    small_blocks):
    """``[x; g_after; g_before]`` side by side as LFM2's projection writes
    ``[B; C; z]``, three taps, no activation: the output in ``x``'s dtype
    and all four cotangents (``d B``, ``d C``, ``d z`` as one array of
    ``x``'s shape, a part that is no operand zero, and ``d taps``), kernels
    and ``jax.numpy`` form alike."""
    b, t, c, k, parts = shape
    x, taps, weight = gated_inputs(b, t, c, k, dtype, parts)
    gates = (2 * c, c)
    both = lambda fn: (fn(x, taps), jax.grad(lambda x, taps: jnp.sum(
        fn(x, taps).astype(jnp.float32) * weight), argnums=(0, 1))(x, taps))
    ref, (r_x, r_taps) = both(lambda x, taps: gated_plain(x, taps, *gates))
    f32 = lambda a: a.astype(jnp.float32)
    top = lambda a: float(jnp.max(jnp.abs(f32(a))))
    for fn in (short_conv, short_conv_reference):
        out, (d_x, d_taps) = both(
            lambda x, taps: fn(x, taps, (), HEAD, gates))
        assert out.shape == ref.shape and out.dtype == dtype
        assert d_x.shape == x.shape and d_x.dtype == dtype
        assert d_taps.shape == taps.shape and d_taps.dtype == jnp.float32
        half = 1e-2 if dtype == jnp.bfloat16 else 1e-5
        assert top(f32(out) - ref) <= half * top(ref)
        for part, name in enumerate(("d_x", "d_after", "d_before")):
            cut = slice(part * c, (part + 1) * c)
            assert top(f32(d_x[..., cut]) - f32(r_x[..., cut])) <= half * top(
                r_x[..., cut]), name
        assert not bool(jnp.any(d_x[..., 3 * c:]))
        assert top(d_taps - r_taps) <= (1e-2 if dtype == jnp.bfloat16
                                        else 2e-5) * top(r_taps)


@pytest.mark.parametrize("row", [31, 32], ids=["last_of_block_0",
                                               "first_of_block_1"])
def test_a_gated_impulse_crosses_a_block_border(row, small_blocks):
    """Three taps: a token's output reaches the two after it and a
    cotangent the two before it, across a block's border, through both
    gates."""
    x, taps, _ = gated_inputs(1, 96, 128, 3, jnp.float32)
    at = jnp.zeros((1, 96, 128)).at[:, row].set(1.0)
    only = x.at[..., :128].set(at)         # B one token, C and z everywhere
    run = lambda x: short_conv(x, taps, (), HEAD, (256, 128))
    out = run(only)
    assert float(jnp.max(jnp.abs(out - gated_plain(only, taps, 256, 128)))
                 ) <= 1e-6
    assert bool(jnp.all(out[:, row:row + 3] != 0)) and not bool(
        jnp.any(out[:, row + 3:])) and not bool(jnp.any(out[:, :row]))
    d_x = jax.grad(lambda x: jnp.sum(run(x) * at))(x)
    d_ref = jax.grad(lambda x: jnp.sum(gated_plain(x, taps, 256, 128) * at))(x)
    assert float(jnp.max(jnp.abs(d_x - d_ref))) <= 1e-6
    assert bool(jnp.all(d_x[:, row - 2:row + 1, :128] != 0))
    assert not bool(jnp.any(d_x[:, row + 1:, :128])) and not bool(
        jnp.any(d_x[:, :row - 2, :128]))


def test_the_gated_form_takes_the_same_two_kernels():
    """``gates`` picks the body, the head size the path: whole lane tiles
    lower to ``apex_short_conv_fwd`` / ``_bwd`` with one ``d x`` of ``x``'s
    shape, 64 channels take the ``jax.numpy`` form; a norm with gates or a
    gate that is no whole part of its own is refused."""
    def lowered(c):
        x, taps, _ = gated_inputs(1, 32, c, 3, jnp.bfloat16)
        return jax.jit(jax.value_and_grad(lambda x, taps: jnp.sum(short_conv(
            x, taps, (), c, (2 * c, c)).astype(jnp.float32)),
            argnums=(0, 1))).lower(x, taps).as_text(debug_info=True)
    assert "apex_short_conv" not in lowered(64)
    text = lowered(HEAD)
    assert "apex_short_conv_fwd/pallas_call" in text
    assert "apex_short_conv_bwd/pallas_call" in text
    assert "concatenate" not in text
    x, taps, _ = gated_inputs(1, 32, HEAD, 3, jnp.float32)
    for bad in (dict(norm=((0, 128, 1.0),), gates=(256, 128)),
                dict(gates=(64, 128)), dict(gates=(128, 128)),
                dict(gates=(0, 128))):
        with pytest.raises(AssertionError):
            short_conv(x, taps, head_dim=HEAD, **bad)
