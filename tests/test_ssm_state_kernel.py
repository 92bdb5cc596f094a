"""The pass over a Mamba-2 layer's matrix states as one in-place kernel
(``net/ssmstate.py``), held here (on the CPU, in interpret mode) to XLA's plain
form that the CPU and the toy widths run (``Mamba2Mixer._state_plain``), over
several steps of a layer with lanes reset between them; and compiled, without
a chip, for the v5e.

A small mixer at whole registers: 4 heads x 64 rows = 256 (head, row) pairs
along a register's lanes, a state of 128 along its sublanes. A lane's state is
64 KiB in bfloat16, so ``_BLOCK_BYTES`` is set to a few states: the walk
through three slots is several blocks long.
"""

import inspect
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from evotorch_tpu.neuroevolution.net import decoder as decoder_module
from evotorch_tpu.neuroevolution.net import ssmstate
from evotorch_tpu.neuroevolution.net.decoder import GraniteMoeHybridDecoder, Mamba2Mixer, _Dense, _fresh_lanes

DIM, HEADS, HEAD_DIM, STATE = 64, 4, 64, 128
INNER = HEADS * HEAD_DIM
STEPS = 4


def layer_of():
    return Mamba2Mixer(DIM, HEADS, HEAD_DIM, STATE)


def lanes_state(layer, lanes, dtype):
    state = _fresh_lanes(layer.initial_state(), lanes)
    floats = lambda s: s.astype(dtype) if jnp.issubdtype(s.dtype, jnp.floating) else s
    return jax.tree_util.tree_map(floats, state)


def operands(lanes, decay, seed=0):
    """A step's small operands: ``decay`` "drawn" (a head's own, in (0.2,
    1)), or one number for every head."""
    keys = jax.random.split(jax.random.key(seed), 4)
    if decay == "drawn":
        a = jax.random.uniform(keys[0], (lanes, HEADS), jnp.float32, 0.2, 1.0)
    else:
        a = jnp.full((lanes, HEADS), decay, jnp.float32)
    fed = jax.random.normal(keys[1], (lanes, INNER))
    return a, fed, jax.random.normal(keys[2], (lanes, STATE)), jax.random.normal(keys[3], (lanes, STATE))


def distance(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


def in_kernel(monkeypatch):
    """The layer's choice made for it: the kernel, interpreted."""
    monkeypatch.setattr(decoder_module, "_by_platform", lambda fused, plain, *args: fused(*args, interpret=True))


# two products and their sum an entry in both forms, but XLA's CPU backend is free to contract them into
# a fused multiply-add in one form and not in the other: the float32 sums may differ by a rounding of a
# product, and the stored entries then by a rounding of the stored dtype
PRODUCT_ROUNDING = 2.0**-22
STORED_ROUNDING = {"float32": 0.0, "bfloat16": 2.0**-7}


@pytest.mark.parametrize("decay", ["drawn", 0.0, 1.0])
@pytest.mark.parametrize("lanes, block_states", [(6, 2), (5, 2), (4, 4), (9, 3)])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_kernel_equals_the_plain_form(monkeypatch, dtype, lanes, block_states, decay):
    """The pass alone, ``STEPS`` times over the same state: blocks of 2 lanes
    of 6 and of 3 of 9 (the three slots wrap), 5 lanes that no block of 2
    divides (blocks of one lane), all lanes in one block; a decay of exactly 0
    (the state is what was fed) and of exactly 1 (nothing is forgotten)."""
    dtype = jnp.dtype(dtype)
    monkeypatch.setattr(ssmstate, "_BLOCK_BYTES", block_states * STATE * INNER * dtype.itemsize)
    group = ssmstate.lane_group(lanes, STATE, INNER, dtype.itemsize)
    assert group == (block_states if lanes % block_states == 0 else 1) and ssmstate.fits(lanes, HEADS, HEAD_DIM, STATE, dtype)
    start = jax.random.normal(jax.random.key(7), (lanes, STATE, INNER)).astype(dtype)
    kernel = jax.jit(lambda *a: ssmstate.state_pass(*a, interpret=True))
    plain = jax.jit(Mamba2Mixer._state_plain)
    got = want = start
    for step in range(STEPS):
        small = operands(lanes, decay, seed=step)
        before = np.asarray(want, np.float32)
        got, y_got, rewrote = kernel(got, *small)
        want, y_want, none = plain(want, *small)
        assert got.dtype == want.dtype == dtype and y_got.dtype == y_want.dtype == jnp.float32
        assert got.shape == start.shape and y_got.shape == y_want.shape == (lanes, INNER)
        assert rewrote.tolist() == [1] * lanes and none.tolist() == [0] * lanes and rewrote.dtype == none.dtype
        if dtype == jnp.bfloat16:  # and such a rounding of the float32 sum rarely crosses one of bfloat16's
            assert np.mean(np.asarray(got, np.float32) != np.asarray(want, np.float32)) < 1e-3
        a, fed, b, _ = (np.asarray(x, np.float32) for x in small)
        products = np.abs(before) * np.repeat(a, HEAD_DIM, axis=1)[:, None, :] + np.abs(b[:, :, None] * fed[:, None, :])
        allowed = PRODUCT_ROUNDING * products + STORED_ROUNDING[dtype.name] * np.abs(np.asarray(want, np.float32))
        assert np.all(np.abs(np.asarray(got, np.float32) - np.asarray(want, np.float32)) <= allowed)
        assert distance(y_got, y_want) < 1e-5  # the readout of the UNROUNDED state, in both
        got = want  # the next step starts from the same bits
    if decay == 0.0:  # nothing of the state before it is left: the outer product alone, rounded once
        fed, b = small[1], small[2]
        assert distance(got, (b[:, :, None] * fed[:, None, :]).astype(dtype)) < 1e-6
    # the readout is NOT taken from the rounded state: in bfloat16 that would stand 1e-3 off
    rounded = jnp.sum(want.astype(jnp.float32) * small[3][:, :, None], axis=1)
    if dtype == jnp.bfloat16:
        assert distance(rounded, y_want) > 10 * distance(y_got, y_want)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_a_layers_steps_with_a_reset_between_them(monkeypatch, dtype):
    """The whole mixer, six lanes in blocks of two, six steps; after the
    third the lanes 1 and 4 end an episode (``reset_state`` zeroes them and
    keeps what they held). With the kernel in the layer's place of choice the
    outputs, the states, what the ended lanes held and the counters are the
    plain form's, but ``kernel_updates``: one a step where the kernel ran,
    none where it did not."""
    dtype, lanes = jnp.dtype(dtype), 6
    monkeypatch.setattr(ssmstate, "_BLOCK_BYTES", 2 * STATE * INNER * dtype.itemsize)
    layer = layer_of()
    params = jax.tree_util.tree_map(lambda p: p.astype(dtype), layer.init(jax.random.key(1)))
    xs = jax.random.normal(jax.random.key(2), (6, lanes, DIM)).astype(dtype)
    ended = jnp.asarray([False, True, False, False, True, False])

    def run():
        step = jax.jit(lambda x, state: layer._forward(_Dense(params), x, state))
        state, outs = lanes_state(layer, lanes, dtype), []
        for at, x in enumerate(xs):
            if at == 3:
                state = layer.reset_state(state, ended)
                assert not np.any(np.asarray(state["ssm"], np.float32)[np.asarray(ended)])
            y, state = step(x, state)
            outs.append(y)
        return jnp.stack(outs), state

    want, plain_state = run()
    in_kernel(monkeypatch)
    got, kernel_state = run()
    tolerance = {"float32": 1e-5, "bfloat16": 2.0**-7}[dtype.name]
    assert distance(got, want) < tolerance and distance(kernel_state["ssm"], plain_state["ssm"]) < tolerance
    assert kernel_state["ssm"].shape == (lanes, STATE, INNER) and kernel_state["ssm"].dtype == dtype
    assert distance(kernel_state["ended"], plain_state["ended"]) < tolerance
    assert float(jnp.abs(kernel_state["ended"][1]).max()) > 0 and not np.any(np.asarray(kernel_state["ended"][0]))
    for name in ("updates", "resets"):
        assert kernel_state[name].tolist() == plain_state[name].tolist()
    assert kernel_state["updates"].tolist() == kernel_state["kernel_updates"].tolist() == [6] * lanes
    assert plain_state["kernel_updates"].tolist() == [0] * lanes


def test_sizes_the_kernel_takes():
    from evotorch_tpu.parallel import make_mesh

    bf16, f32 = jnp.dtype("bfloat16"), jnp.dtype("float32")
    assert ssmstate.fits(256, 64, 64, 128, bf16) and ssmstate.fits(256, 64, 64, 128, f32)  # the benchmark's layer
    assert ssmstate.lane_group(256, 128, 4096, 2) == 8 and ssmstate.lane_group(256, 128, 4096, 4) == 4  # a block's bytes decide
    assert ssmstate.lane_group(255, 128, 4096, 2) == 5 and ssmstate.lane_group(251, 128, 4096, 2) == 1  # whole blocks only
    assert ssmstate.lane_group(256, 1024, 8192, 2) == 0  # a state larger than a block
    assert ssmstate.fits(2, HEADS, HEAD_DIM, STATE, bf16)
    assert not ssmstate.fits(1, 64, 64, 128, bf16)  # the dense form: one lane under ``vmap``
    assert not ssmstate.fits(3, 4, 16, 8, f32)  # tests/test_decoder_ssm.py's mixer
    assert not ssmstate.fits(256, 64, 64, 64, bf16)  # half a register of state
    assert not ssmstate.fits(256, 3, 64, 128, bf16)  # 192 (head, row) pairs: a register and a half
    assert not ssmstate.fits(256, 8, 96, 128, bf16)  # heads that neither tile a register's lanes nor are tiled by them
    assert ssmstate.fits(256, 8, 256, 128, bf16) and ssmstate.fits(256, 64, 16, 128, bf16)
    assert not ssmstate.fits(256, 64, 64, 128, jnp.dtype("float16"))
    with jax.sharding.use_abstract_mesh(make_mesh({"pop": 4}).abstract_mesh):
        assert not ssmstate.fits(256, 64, 64, 128, bf16)  # the partitioner cannot split a kernel
    with jax.sharding.use_abstract_mesh(make_mesh({"pop": 1}).abstract_mesh):
        assert ssmstate.fits(256, 64, 64, 128, bf16)


def test_the_layer_picks_the_form_by_what_it_observes(monkeypatch):
    """``_state_pass`` hands the kernel to ``_by_platform`` at the kernel's
    sizes and runs the plain form itself elsewhere: under a mesh, for one
    lane, at a toy width. Nothing of the environment is asked."""
    from evotorch_tpu.parallel import make_mesh

    called = []
    monkeypatch.setattr(decoder_module, "_by_platform", lambda fused, plain, *args: called.append(1) or plain(*args))
    layer, lanes = layer_of(), 4
    args = (jnp.zeros((lanes, STATE, INNER), jnp.bfloat16), *operands(lanes, "drawn"))
    trace = lambda layer, *a: jax.jit(layer._state_pass).trace(*a)
    trace(layer, *args)
    assert called == [1]
    with jax.sharding.use_abstract_mesh(make_mesh({"pop": 4}).abstract_mesh):
        trace(layer, *args)
    trace(layer, *(x[:1] for x in args))
    toy = Mamba2Mixer(32, 4, 16, 8)
    trace(toy, jnp.zeros((lanes, 8, 64), jnp.float32), *(jnp.zeros((lanes, width)) for width in (4, 64, 8, 8)))
    assert called == [1]
    for module in (ssmstate, decoder_module):
        assert "os.environ" not in inspect.getsource(module) and "getenv" not in inspect.getsource(module)


# -- the decoder's lowerings -----------------------------------------------------
# Granite-4.0-H's mixer at its published head and state sizes (eight heads of them), thin around it
GRANITE = dict(
    hidden_size=256, num_attention_heads=4, num_key_value_heads=2, shared_intermediate_size=128,
    layer_types=["mamba", "mamba", "attention", "mamba"],
    mamba_n_heads=8, mamba_d_head=64, mamba_d_state=128, mamba_n_groups=1, mamba_d_conv=4, mamba_expand=2,
    mamba_conv_bias=True, mamba_proj_bias=False, num_local_experts=0, attention_bias=False,
    attention_multiplier=0.125, embedding_multiplier=12, residual_multiplier=0.22, logits_scaling=8,
    position_embedding_type="nope", tie_word_embeddings=True, rms_norm_eps=1e-5,
)
LAYERS = (0, 1)


def decoder_step(sharding=None, *, lanes=16, rank=4):
    """``trunk_delta_apply`` of a decoder of two Mamba-2 layers on abstract
    bfloat16 arguments: the jitted step and what to trace it with."""
    from evotorch_tpu.neuroevolution.net.functional import FlatParamsPolicy
    from evotorch_tpu.neuroevolution.net.lowrank import sample_trunk_delta_factors

    net = GraniteMoeHybridDecoder(**GRANITE, vocab_size=512, max_positions=8, layers_held=list(LAYERS), vocab_held=128)
    policy = FlatParamsPolicy(net)
    bf16 = jnp.bfloat16

    def abstract(tree, dtype=None):
        as_dtype = lambda leaf: dtype if dtype is not None and jnp.issubdtype(leaf.dtype, jnp.floating) else leaf.dtype
        return jax.tree_util.tree_map(
            lambda leaf: jax.ShapeDtypeStruct(leaf.shape, as_dtype(leaf), sharding=sharding), tree
        )

    flat = jax.ShapeDtypeStruct((policy.parameter_count,), jnp.float32)
    center = abstract(jax.eval_shape(policy.unravel, flat), bf16)
    factors = abstract(
        jax.eval_shape(lambda sigma: sample_trunk_delta_factors(jax.random.key(0), policy, sigma, rank), flat), bf16
    )
    state = jax.eval_shape(lambda: _fresh_lanes(net.initial_state(), lanes))
    z = abstract(jax.ShapeDtypeStruct((lanes, rank), bf16))
    ids = abstract(jax.ShapeDtypeStruct((lanes, 1), jnp.int32))
    step = jax.jit(lambda center, factors, z, x, state: net.trunk_delta_apply(center, factors, z, x, state), donate_argnums=4)
    return step, (center, factors, z, ids, abstract(state, bf16))


def test_the_cpu_lowering_holds_no_kernel():
    step, args = decoder_step()
    text = step.trace(*args).lower().as_text()
    assert "tpu_custom_call" not in text and ssmstate.KERNEL_NAME not in text


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as error:  # no libtpu here: nothing to compile with
        pytest.skip(f"no v5e:2x2 topology can be described here: {error}")


@pytest.mark.filterwarnings("ignore:Error reading persistent compilation cache entry")
def test_a_decoder_step_compiles_for_v5e_with_one_kernel_a_layer(v5e):
    """The real TPU compiler, Mosaic included: the step holds the kernel once
    a Mamba layer, each under the state's scope, its new states aliased to the
    states it was given, and no copy or transpose of a layer's states beside
    it."""
    from jax.sharding import SingleDeviceSharding

    step, args = decoder_step(SingleDeviceSharding(v5e.devices[0]))
    text = step.trace(*args).lower(lowering_platforms=("tpu",)).compile().as_text()
    calls = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    ours = [line for line in calls if ssmstate.KERNEL_NAME in line]
    assert len(ours) == len(LAYERS) and all("fwd_ssm_state" in line for line in ours)
    assert all("output_to_operand_aliasing={{0}: (1, {})}" in line for line in ours)
    moved = [
        line
        for line in text.splitlines()
        if (" copy(" in line or " transpose(" in line or " select(" in line) and "bf16[16,128,512]" in line
    ]
    assert not moved


def test_the_cells_layer_compiles_for_v5e(v5e):
    """The kernel alone at the benchmark's sizes (256 lanes of 128 x 4,096,
    bfloat16: three slots of 8 MiB), and in float32."""
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(v5e.devices[0])
    for dtype in (jnp.bfloat16, jnp.float32):
        shape = lambda *s, d=jnp.float32: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
        args = (shape(256, 128, 4096, d=dtype), shape(256, 64), shape(256, 4096), shape(256, 128), shape(256, 128))
        compiled = jax.jit(ssmstate.state_pass, donate_argnums=0).trace(*args).lower(lowering_platforms=("tpu",)).compile()
        assert ssmstate.KERNEL_NAME in compiled.as_text()
        assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20  # no second set of states


def test_the_report_counts_what_the_kernel_rewrote():
    """``state_report`` sums the layers' ``kernel_updates`` beside ``updates``
    (none on the CPU, where the plain form runs); a decoder without a
    recurrence reports neither."""
    from tests.test_decoder import small_decoder

    net = GraniteMoeHybridDecoder(**GRANITE, vocab_size=512, max_positions=4, layers_held=[0, 1, 2], vocab_held=128)
    state = _fresh_lanes(net.initial_state(), 3)
    for at, (updates, by_kernel) in enumerate([(5, 5), (5, 0)]):
        ssm = state["layers"][at]["ssm"]
        ssm.update(updates=ssm["updates"] + updates, kernel_updates=ssm["kernel_updates"] + by_kernel)
    report = net.state_report(state)
    assert int(report["ssm_state_updates"]) == 30 and int(report["ssm_state_kernel_updates"]) == 15
    assert report["ssm_state_kernel_updates"].ndim == 0
    other = small_decoder(4)
    assert "ssm_state_kernel_updates" not in other.state_report(_fresh_lanes(other.initial_state(), 3))
