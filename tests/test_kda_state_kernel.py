"""The pass over a KDA layer's matrix states as one in-place kernel
(``net/kdastate.py``), held here (on the CPU, in interpret mode) to XLA's plain
form that the CPU and the toy widths run (``KimiDeltaAttention._state_plain``),
over several steps of a layer with lanes reset between them; and compiled,
without a chip, for the v5e.

A small layer at whole registers: 16 heads (one packed bfloat16 register's
sublanes) x a head_dim of 128, Kimi-Linear's. A lane's state is 512 KiB in
bfloat16, so ``_BLOCK_BYTES`` is set to a few states: the walk through three
slots is several blocks long.
"""

import inspect
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from evotorch_tpu.neuroevolution.net import decoder as decoder_module
from evotorch_tpu.neuroevolution.net import kdastate, ssmstate
from evotorch_tpu.neuroevolution.net.decoder import KimiDeltaAttention, KimiLinearDecoder, _Dense, _fresh_lanes
from tests.test_kimi_linear import LINEAR, MODEL

DIM, HEADS, HEAD_DIM = 64, 16, 128
STEPS = 2


def layer_of():
    return KimiDeltaAttention(DIM, HEADS, HEAD_DIM)


def state_bytes(dtype):
    return HEAD_DIM * HEADS * HEAD_DIM * jnp.dtype(dtype).itemsize


def operands(lanes, decay, beta, seed=0):
    """A step's small operands as the layer makes them: ``decay`` "drawn" (a
    (head, key channel)'s own, in (0.2, 1)) or one number for all; ``k`` and
    ``q`` L2-normalised a head, ``q`` times ``head_dim ^ -1/2``; ``beta``
    "drawn" (in (0, 1)) or one number for all."""
    keys = jax.random.split(jax.random.key(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    if decay == "drawn":
        a = jax.random.uniform(keys[0], (lanes, HEADS, HEAD_DIM), jnp.float32, 0.2, 1.0)
    else:
        a = jnp.full((lanes, HEADS, HEAD_DIM), decay, jnp.float32)
    k = unit(jax.random.normal(keys[1], (lanes, HEADS, HEAD_DIM)))
    v = jax.random.normal(keys[2], (lanes, HEADS, HEAD_DIM))
    if beta == "drawn":
        b = jax.random.uniform(keys[3], (lanes, HEADS), jnp.float32)
    else:
        b = jnp.full((lanes, HEADS), beta, jnp.float32)
    q = unit(jax.random.normal(keys[4], (lanes, HEADS, HEAD_DIM))) * HEAD_DIM**-0.5
    return a, k, v, b, q


def distance(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


def in_kernel(monkeypatch):
    """The layer's choice made for it: the kernel, interpreted."""
    monkeypatch.setattr(decoder_module, "_by_platform", lambda fused, plain, *args: fused(*args, interpret=True))


# a product and a sum an entry in both forms, rounded apart (XLA's CPU backend may contract one form's into a
# fused multiply-add and not the other's); ``S'^T k`` is a sum of ``head_dim`` products in another order and, in
# the kernel, of ``S (a k)`` for ``(S a) k``: each of its terms may differ by two roundings; the stored entries
# then by a rounding of the stored dtype
PRODUCT_ROUNDING = 2.0**-22
STORED_ROUNDING = {"float32": 0.0, "bfloat16": 2.0**-7}


def allowed_difference(before, small, want, dtype):
    """Entry by entry, how far the kernel's new state may lie from the plain
    form's: the roundings of the update's product and sum, those of ``S'^T
    k`` carried through ``k beta``, and the stored dtype's rounding."""
    a, k, v, beta, q = (np.asarray(x, np.float64) for x in small)
    turned = lambda x: np.swapaxes(x, 1, 2)[..., None]  # (n, heads, key) -> (n, key, heads, 1)
    decayed = np.abs(before) * turned(a)
    read = np.sum(decayed * turned(k), axis=1)  # (n, heads, value)
    terms = np.sum(decayed * np.abs(turned(k)), axis=1)
    u = beta[:, :, None] * (v - read)
    read_rounding = (2 * HEAD_DIM * PRODUCT_ROUNDING) * terms + PRODUCT_ROUNDING * np.abs(v - read)
    product = decayed + np.abs(turned(k) * u[:, None])
    carried = np.abs(turned(k)) * (beta[:, :, None] * read_rounding)[:, None]
    return PRODUCT_ROUNDING * product + 2 * carried + STORED_ROUNDING[dtype.name] * np.abs(np.asarray(want, np.float64))


@pytest.mark.parametrize("decay, beta", [("drawn", "drawn"), (0.0, "drawn"), (1.0, "drawn"), ("drawn", 0.0), ("drawn", 1.0)])
@pytest.mark.parametrize("lanes, block_states", [(4, 2), (3, 2)])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_kernel_equals_the_plain_form(monkeypatch, dtype, lanes, block_states, decay, beta):
    """The pass alone, ``STEPS`` times over the same state: blocks of 2 lanes
    of 4 (the three slots wrap), 3 lanes that no block of 2 divides (blocks
    of one lane); a decay of exactly 0 (the state is what was fed), of
    exactly 1 (nothing is forgotten); ``beta`` 0 (no correction: the decay
    alone) and 1 (the whole correction)."""
    dtype = jnp.dtype(dtype)
    monkeypatch.setattr(ssmstate, "_BLOCK_BYTES", block_states * state_bytes(dtype))
    group = ssmstate.lane_group(lanes, HEAD_DIM, HEADS * HEAD_DIM, dtype.itemsize)
    assert group == (block_states if lanes % block_states == 0 else 1) and kdastate.fits(lanes, HEADS, HEAD_DIM, dtype)
    start = jax.random.normal(jax.random.key(7), (lanes, HEAD_DIM, HEADS, HEAD_DIM)).astype(dtype)
    plain = jax.jit(KimiDeltaAttention._state_plain)
    want = start
    for step in range(STEPS):
        small = operands(lanes, decay, beta, seed=step)
        before = np.asarray(want, np.float64)
        got, y_got, rewrote = kdastate.state_pass(jnp.copy(want), *small, interpret=True)  # the same bits a step
        want, y_want = plain(want, *small)
        assert got.dtype == want.dtype == dtype and y_got.dtype == y_want.dtype == jnp.float32
        assert got.shape == start.shape and y_got.shape == y_want.shape == (lanes, HEADS, HEAD_DIM)
        assert rewrote.tolist() == [1] * lanes and rewrote.dtype == jnp.int32
        if dtype == jnp.bfloat16:  # and such a rounding of the float32 sum rarely crosses one of bfloat16's
            assert np.mean(np.asarray(got, np.float32) != np.asarray(want, np.float32)) < 1e-3
        allowed = allowed_difference(before, small, want, dtype)
        assert np.all(np.abs(np.asarray(got, np.float64) - np.asarray(want, np.float64)) <= allowed)
        assert distance(y_got, y_want) < 1e-5  # the readout of the UNROUNDED state, in both
    a, k, v, b, q = small
    if decay == 0.0:  # nothing of the state before it is left: k (beta v)^T alone, rounded once
        fed = jnp.swapaxes(k, 1, 2)[..., None] * (b[:, :, None] * v)[:, None]
        assert distance(got, fed.astype(dtype)) < 1e-6
    if beta == 0.0:  # no correction: the decayed state alone
        decayed = jnp.asarray(before, jnp.float32) * jnp.swapaxes(a, 1, 2)[..., None]
        assert distance(got, decayed.astype(dtype)) < 1e-6
    # the readout is NOT taken from the rounded state: in bfloat16 that would stand 1e-3 off
    rounded = jnp.sum(want.astype(jnp.float32) * jnp.swapaxes(q, 1, 2)[..., None], axis=1)
    if dtype == jnp.bfloat16 and decay != 0.0:
        assert distance(rounded, y_want) > 10 * distance(y_got, y_want)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_a_layers_steps_with_a_reset_between_them(monkeypatch, dtype):
    """The whole block, four lanes in blocks of two, four steps; after the
    second the lanes 1 and 2 end an episode (``reset_state`` zeroes them and
    keeps what they held). With the kernel in the layer's place of choice the
    outputs, the states, what the ended lanes held and the counters are the
    plain form's, but ``kernel_updates``: one a step where the kernel ran,
    none where it did not."""
    dtype, lanes = jnp.dtype(dtype), 4
    monkeypatch.setattr(ssmstate, "_BLOCK_BYTES", 2 * state_bytes(dtype))
    layer = layer_of()
    params = jax.tree_util.tree_map(lambda p: p.astype(dtype), layer.init(jax.random.key(1)))
    xs = jax.random.normal(jax.random.key(2), (4, lanes, DIM)).astype(dtype)
    ended = jnp.asarray([False, True, True, False])

    def run():
        step = jax.jit(lambda x, state: layer._forward(_Dense(params), x, state))
        state = jax.tree_util.tree_map(
            lambda s: s.astype(dtype) if jnp.issubdtype(s.dtype, jnp.floating) else s,
            _fresh_lanes(layer.initial_state(), lanes),
        )
        outs = []
        for at, x in enumerate(xs):
            if at == 2:
                state = layer.reset_state(state, ended)
                assert not np.any(np.asarray(state["state"], np.float32)[np.asarray(ended)])
            y, state = step(x, state)
            outs.append(y)
        return jnp.stack(outs), state

    want, plain_state = run()
    in_kernel(monkeypatch)
    got, kernel_state = run()
    tolerance = {"float32": 1e-5, "bfloat16": 2.0**-7}[dtype.name]
    assert distance(got, want) < tolerance and distance(kernel_state["state"], plain_state["state"]) < tolerance
    assert kernel_state["state"].shape == (lanes, HEAD_DIM, HEADS, HEAD_DIM) and kernel_state["state"].dtype == dtype
    assert distance(kernel_state["ended"], plain_state["ended"]) < tolerance
    assert float(jnp.abs(kernel_state["ended"][1]).max()) > 0 and not np.any(np.asarray(kernel_state["ended"][0]))
    for name in ("updates", "resets"):
        assert kernel_state[name].tolist() == plain_state[name].tolist()
    assert kernel_state["updates"].tolist() == kernel_state["kernel_updates"].tolist() == [4] * lanes
    assert plain_state["kernel_updates"].tolist() == [0] * lanes


def test_sizes_the_kernel_takes():
    from evotorch_tpu.parallel import make_mesh

    bf16, f32 = jnp.dtype("bfloat16"), jnp.dtype("float32")
    assert kdastate.fits(512, 32, 128, bf16) and kdastate.fits(512, 32, 128, f32)  # the benchmark's layer
    assert ssmstate.lane_group(512, 128, 32 * 128, 2) == 8 and ssmstate.lane_group(512, 128, 32 * 128, 4) == 4
    assert kdastate.fits(2, HEADS, HEAD_DIM, bf16) and kdastate.fits(3, 64, 128, bf16) and kdastate.fits(3, 128, 128, f32)
    assert kdastate.fits(4, 16, 256, bf16)  # two registers along the value
    assert not kdastate.fits(1, 32, 128, bf16)  # the dense form: one lane under ``vmap``
    assert not kdastate.fits(3, 4, 8, f32)  # tests/test_kimi_linear.py's layer
    assert not kdastate.fits(512, 32, 64, bf16)  # half a register of value
    assert not kdastate.fits(512, 8, 128, bf16)  # half a packed register of heads
    assert not kdastate.fits(512, 48, 128, bf16) and not kdastate.fits(512, 256, 128, bf16)  # rows of 128 pairs
    assert not kdastate.fits(512, 32, 128, jnp.dtype("float16"))
    assert not kdastate.fits(4, 128, 1024, bf16)  # a state larger than a block
    with jax.sharding.use_abstract_mesh(make_mesh({"pop": 4}).abstract_mesh):
        assert not kdastate.fits(512, 32, 128, bf16)  # the partitioner cannot split a kernel
    with jax.sharding.use_abstract_mesh(make_mesh({"pop": 1}).abstract_mesh):
        assert kdastate.fits(512, 32, 128, bf16)


def test_the_layer_picks_the_form_by_what_it_observes(monkeypatch):
    """``_state_pass`` hands the kernel to ``_by_platform`` at the kernel's
    sizes and runs the plain form itself elsewhere: under a mesh, for one
    lane, at a toy width. Nothing of the environment is asked."""
    from evotorch_tpu.parallel import make_mesh

    called = []
    monkeypatch.setattr(decoder_module, "_by_platform", lambda fused, plain, *args: called.append(1) or plain(*args))
    layer, lanes = layer_of(), 4
    args = (jnp.zeros((lanes, HEAD_DIM, HEADS, HEAD_DIM), jnp.bfloat16), *operands(lanes, "drawn", "drawn"))
    trace = lambda layer, *a: jax.jit(layer._state_pass).trace(*a)
    trace(layer, *args)
    assert called == [1]
    with jax.sharding.use_abstract_mesh(make_mesh({"pop": 4}).abstract_mesh):
        trace(layer, *args)
    trace(layer, *(x[:1] for x in args))
    toy = KimiDeltaAttention(32, 4, 8)
    small = (jnp.zeros((lanes, 4, 8)),) * 3 + (jnp.zeros((lanes, 4)), jnp.zeros((lanes, 4, 8)))
    trace(toy, jnp.zeros((lanes, 8, 4, 8), jnp.float32), *small)
    assert called == [1]
    for module in (kdastate, ssmstate, decoder_module):
        assert "os.environ" not in inspect.getsource(module) and "getenv" not in inspect.getsource(module)


# -- the decoder's lowerings -----------------------------------------------------
# tests/test_kimi_linear.py's model with its KDA layers at Kimi-Linear's head_dim and sixteen heads of it
KDA_WIDE = dict(MODEL, linear_attn_config=dict(LINEAR, head_dim=HEAD_DIM, num_heads=HEADS))
LAYERS = (0, 1)  # KDA + the dense MLP, KDA + experts


def decoder_step(sharding=None, *, lanes=16, rank=4):
    """``trunk_delta_apply`` of a decoder of two KDA layers on abstract
    bfloat16 arguments: the jitted step and what to trace it with."""
    from evotorch_tpu.neuroevolution.net.functional import FlatParamsPolicy
    from evotorch_tpu.neuroevolution.net.lowrank import sample_trunk_delta_factors

    net = KimiLinearDecoder(
        **KDA_WIDE, num_experts=32, num_hidden_layers=8, vocab_size=64, max_positions=8, layers_held=list(LAYERS),
        experts_held=range(8, 16), vocab_held=16,
    )
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
    return net, step, (center, factors, z, ids, abstract(state, bf16))


def test_the_cpu_lowering_holds_no_kernel():
    _, step, args = decoder_step()
    text = step.trace(*args).lower().as_text()
    assert "tpu_custom_call" not in text and kdastate.KERNEL_NAME not in text


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
    a KDA layer, each under the state's scope, its new states aliased to the
    states it was given, and no copy or transpose of a layer's states beside
    it."""
    from jax.sharding import SingleDeviceSharding

    _, step, args = decoder_step(SingleDeviceSharding(v5e.devices[0]))
    text = step.trace(*args).lower(lowering_platforms=("tpu",)).compile().as_text()
    calls = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    ours = [line for line in calls if kdastate.KERNEL_NAME in line]
    assert len(ours) == len(LAYERS) and all("fwd_kda_state" in line for line in ours)
    assert all("output_to_operand_aliasing={{0}: (1, {})}" in line for line in ours)
    moved = [
        line
        for line in text.splitlines()
        if (" copy(" in line or " transpose(" in line or " select(" in line) and "bf16[16,128,16,128]" in line
    ]
    assert not moved


def test_the_cells_layer_compiles_for_v5e(v5e):
    """The kernel alone at the benchmark's sizes (512 lanes of 128 x 32 x 128,
    bfloat16: three slots of 8 MiB), and in float32."""
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(v5e.devices[0])
    for dtype in (jnp.bfloat16, jnp.float32):
        shape = lambda *s, d=jnp.float32: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
        heads = shape(512, 32, 128)
        args = (shape(512, 128, 32, 128, d=dtype), heads, heads, heads, shape(512, 32), heads)
        compiled = jax.jit(kdastate.state_pass, donate_argnums=0).trace(*args).lower(lowering_platforms=("tpu",)).compile()
        assert kdastate.KERNEL_NAME in compiled.as_text()
        assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20  # no second set of states


def test_the_report_counts_what_the_kernel_rewrote():
    """``state_report`` sums the KDA layers' ``kernel_updates`` beside
    ``updates`` (none on the CPU, where the plain form runs), under ``kda_``
    and not ``ssm_``."""
    net, _, _ = decoder_step()
    state = _fresh_lanes(net.initial_state(), 3)
    for at, (updates, by_kernel) in enumerate([(5, 5), (5, 0)]):
        kda = state["layers"][at]["kda"]
        kda.update(updates=kda["updates"] + updates, kernel_updates=kda["kernel_updates"] + by_kernel)
    report = net.state_report(state)
    assert int(report["kda_state_updates"]) == 30 and int(report["kda_state_kernel_updates"]) == 15
    assert report["kda_state_kernel_updates"].ndim == 0
    assert "ssm_state_kernel_updates" not in report and "ssm_state_updates" not in report
