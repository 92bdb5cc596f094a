"""The pass over the latent cache as one kernel (``net/latent.py``), held here
(on the CPU, in interpret mode) to XLA's plain ``einsum`` form that the CPU
and the toy widths run (``LatentAttention._cache_plain``), at the points of an
episode and of the ring that break a ragged walk; and compiled, without a
chip, for the v5e.

Three groups of eight lanes over a ring of three blocks of 64 positions. In
every case the rows a lane cannot read (``age > t``) hold LARGE values, so a
position that leaks through the walk or the mask shows as an error of
thousands, not of a rounding.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from evotorch_tpu.neuroevolution.net import latent
from evotorch_tpu.neuroevolution.net.decoder import Glm4MoeLiteDecoder, LatentAttention

LANES, HEADS, KV_RANK, ROPE, SLOTS, GROUP = 24, 5, 128, 64, 192, 8
STALE = 1.0e4


def layer_of():
    return LatentAttention(
        256, HEADS, q_rank=96, kv_rank=KV_RANK, nope_dim=192, rope_dim=ROPE, v_dim=64, slots=SLOTS, rope_theta=1e6
    )


def groups(first, second, third):
    """``t`` of the 24 lanes from three lists of eight."""
    return np.asarray(first + second + third, np.int32)


def reset_mid_episode():
    """Every lane at ``t`` = 150, then ``reset_state`` on all of group 0 and
    half of group 1: what the engine does at an episode's end."""
    layer = layer_of()
    state = jax.tree_util.tree_map(lambda s: jnp.broadcast_to(s, (LANES,) + s.shape), layer.initial_state())
    state = {**state, "t": jnp.full((LANES,), 150, jnp.int32), "step": jnp.full((LANES,), 150, jnp.int32)}
    ended = np.arange(LANES) < 12
    state = layer.reset_state(state, jnp.asarray(ended))
    assert not np.any(np.asarray(state["c"])[ended]) and int(state["step"][0]) == 150
    return np.asarray(state["t"])


same = lambda t: [t] * GROUP
# name: (every lane's t, the steps since the state was made, the blocks each group walks, counted by hand)
CASES = {
    "all_lanes_at_t0": (groups(same(0), same(0), same(0)), 0, [1, 1, 1]),
    # slot 50: every readable row lies in block 0
    "ragged_within_one_block": (groups([0, 3, 9, 27, 40, 49, 50, 50], same(50), [50, 0] * 4), 50, [1, 1, 1]),
    # slot 150 = row 22 of block 2: t <= 22 stays in it, t <= 86 reaches block 1, beyond that block 0
    "ragged_across_blocks": (
        groups([0, 5, 10, 15, 20, 21, 22, 22], [22, 23, 24, 50, 85, 86, 0, 1], [87, 100, 128, 149, 150, 7, 22, 23]),
        150,
        [1, 2, 3],
    ),
    "every_slot_full": (groups(same(191), same(191), same(191)), 191, [3, 3, 3]),
    # 292 steps into a ring of 192: slot 100 = row 36 of block 1. Group 0 reads all of it (the block that
    # holds the slot holds its newest and its oldest rows: one visit); group 1 blocks 1 and 0; group 2's
    # 126 rows run across the seam: blocks 1, 0 and 2
    "the_ring_wrapped": (
        groups(same(292), [0, 36, 37, 50, 50, 6, 49, 50], [101, 125, 125, 37, 0, 125, 102, 124]),
        292,
        [3, 2, 3],
    ),
    "lanes_reset_mid_episode": (reset_mid_episode, 150, [1, 3, 3]),
    # slot 128 = row 0 of block 2: one row of it is readable, the next 64 lie in block 1
    "slot_at_a_blocks_first_row": (groups(same(0), [1, 2, 32, 63, 64, 0, 1, 64], [65, 128, 100, 0, 1, 64, 127, 128]), 128, [1, 2, 3]),
    # slot 127 = row 63 of block 1: 64 rows of it are readable, row 65 lies in block 0
    "slot_at_a_blocks_last_row": (groups([0, 1, 2, 32, 50, 62, 63, 63], [64, 0, 63, 64, 1, 2, 3, 4], same(127)), 127, [1, 2, 2]),
}


def inputs(dtype, t, steps):
    slots = SLOTS
    keys = jax.random.split(jax.random.key(0), 4)
    q_lat = (0.5 * jax.random.normal(keys[0], (LANES, HEADS, KV_RANK))).astype(dtype)
    q_r = jax.random.normal(keys[1], (LANES, HEADS, ROPE)).astype(dtype)
    c = jax.random.normal(keys[2], (LANES, slots, KV_RANK))
    kr = jax.random.normal(keys[3], (LANES, slots, ROPE))
    slot = steps % slots
    age = np.mod(slot - np.arange(slots), slots)
    stale = jnp.asarray(age[None, :] > t[:, None])[:, :, None]
    c, kr = jnp.where(stale, STALE, c).astype(dtype), jnp.where(stale, -STALE, kr).astype(dtype)
    return q_lat, q_r, c, kr, jnp.asarray(t), jnp.asarray(slot, jnp.int32)


def distance(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


def in_float32(q_lat, q_r, c, kr, t, slot, scale):
    f = lambda x: x.astype(jnp.float32)
    slots = c.shape[1]
    age = jnp.mod(slot - jnp.arange(slots), slots)
    with jax.default_matmul_precision("highest"):
        s = (jnp.einsum("nhr,nsr->nhs", f(q_lat), f(c)) + jnp.einsum("nhd,nsd->nhs", f(q_r), f(kr))) * scale
        s = jnp.where((age[None, :] <= t[:, None])[:, None, :], s, -jnp.inf)
        return jnp.einsum("nhs,nsr->nhr", jax.nn.softmax(s, axis=-1), f(c))


# float32: the two forms differ by the order of float32 sums and by exp(s - m) over the running
# maximum against exp(s - max) / sum. bfloat16: both round a weight ONCE to bfloat16's eight bits,
# the plain form p / sum, the kernel p itself (divided by the float32 sum afterwards): each is off
# by at most half a unit in the last place, 2^-9 relative, so the weighted sums differ by at most
# 2^-8 = 3.9e-3 relative where every rounding falls the worst way; measured 1.5e-3 to 2.1e-3
TOLERANCE = {"float32": 1e-5, "bfloat16": 2.0**-8}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(CASES))
def test_kernel_equals_the_plain_form(case, dtype):
    dtype = jnp.dtype(dtype)
    t, steps, walked = CASES[case]
    t = t() if callable(t) else t
    layer = layer_of()
    scale = (layer.nope + layer.rope) ** -0.5
    args = inputs(dtype, t, steps)
    assert latent.fits(LANES, KV_RANK, SLOTS, dtype) and latent.lane_group(LANES, KV_RANK, dtype.itemsize) == GROUP
    got, fetched = jax.jit(lambda *a: latent.attend(*a, scale=scale, interpret=True))(*args)
    want, none_fetched = jax.jit(layer._cache_plain)(*args)
    assert got.dtype == want.dtype == jnp.float32 and got.shape == want.shape == (LANES, HEADS, KV_RANK)
    assert np.all(np.isfinite(got))
    # the blocks each group walked, counted by hand, and every lane of a group charged with them
    assert np.array_equal(latent.trips(args[4], args[5], SLOTS, GROUP), walked)
    assert np.array_equal(fetched, np.repeat(np.asarray(walked) * latent.BLOCK, GROUP))
    assert not np.any(none_fetched) and none_fetched.shape == fetched.shape and none_fetched.dtype == fetched.dtype
    assert distance(got, want) < TOLERANCE[dtype.name]
    # a stale row that leaked would stand thousands off; and the kernel lies no further from the
    # float32 evaluation than its bound allows
    assert float(np.max(np.abs(got))) < 10.0
    assert distance(got, in_float32(*args, scale)) < TOLERANCE[dtype.name]
    # the caller's dtype is the float32 accumulator rounded once, as the plain form rounds its own
    rounded = jax.jit(lambda *a: latent.attend(*a, scale=scale, out_dtype=dtype, interpret=True)[0])(*args)
    assert rounded.dtype == dtype and np.array_equal(rounded, got.astype(dtype))
    assert jax.jit(lambda *a: layer._cache_plain(*a, out_dtype=dtype)[0])(*args).dtype == dtype


def test_stale_rows_change_nothing():
    """What lies beyond a lane's ``t`` is never multiplied in: the same
    readable rows with zeros and with large values beyond them give the same
    bits."""
    t, steps, _ = CASES["ragged_across_blocks"]
    q_lat, q_r, c, kr, t, slot = inputs(jnp.dtype("bfloat16"), t, steps)
    run = jax.jit(lambda *a: latent.attend(*a, scale=0.0625, interpret=True)[0])
    blank = lambda x: jnp.where(jnp.abs(x) == jnp.asarray(STALE, x.dtype), 0, x)
    assert int(jnp.sum(blank(c) != c)) > LANES * KV_RANK  # there were stale rows to blank
    assert np.array_equal(run(q_lat, q_r, c, kr, t, slot), run(q_lat, q_r, blank(c), blank(kr), t, slot))


def test_sizes_the_kernel_takes():
    from evotorch_tpu.parallel import make_mesh

    bf16, f32 = jnp.dtype("bfloat16"), jnp.dtype("float32")
    assert latent.fits(512, 512, 512, bf16) and latent.fits(512, 512, 512, f32)  # the benchmark's layer
    assert latent.lane_group(512, 512, 4) * 2 == latent.lane_group(512, 512, 2)  # a block's bytes decide
    assert latent.BLOCK == 64 and latent.fits(8, 128, 128, bf16)
    assert not latent.fits(1, 512, 512, bf16)  # the dense form: one lane under ``vmap``
    assert not latent.fits(6, 512, 512, bf16)  # tests/test_decoder.py's populations
    assert not latent.fits(512, 512, 500, bf16)  # an odd number of slots
    assert not latent.fits(512, 512, 20, bf16)
    assert not latent.fits(512, 16, 512, bf16)  # tests/test_decoder.py's toy rank
    assert not latent.fits(512, 512, 512, jnp.dtype("float16"))
    with jax.sharding.use_abstract_mesh(make_mesh({"pop": 4}).abstract_mesh):
        assert not latent.fits(512, 512, 512, bf16)  # the partitioner cannot split a kernel
    with jax.sharding.use_abstract_mesh(make_mesh({"pop": 1}).abstract_mesh):
        assert latent.fits(512, 512, 512, bf16)


def test_the_layer_picks_the_form_by_what_it_observes(monkeypatch):
    """``_cache_pass`` hands the kernel to ``_by_platform`` at the kernel's
    sizes and runs the plain form itself elsewhere: under a mesh and for one
    lane."""
    from evotorch_tpu.neuroevolution.net import decoder as decoder_module
    from evotorch_tpu.parallel import make_mesh

    called = []
    monkeypatch.setattr(
        decoder_module, "_by_platform", lambda fused, plain, *args: called.append(1) or plain(*args)
    )
    layer = layer_of()
    t, steps, _ = CASES["ragged_across_blocks"]
    args = inputs(jnp.dtype("float32"), t, steps)
    trace = lambda *a: jax.jit(lambda *b: layer._cache_pass(*b, jnp.float32)).trace(*a)
    trace(*args)
    assert called == [1]
    with jax.sharding.use_abstract_mesh(make_mesh({"pop": 4}).abstract_mesh):
        trace(*args)
    trace(*(x[:1] for x in args[:5]), args[5])
    assert called == [1]


# -- the decoder's lowerings -----------------------------------------------------
# GLM-4.7-Flash's latent attention at its published widths over a short ring, around thin MLPs
GLM = dict(
    hidden_size=256, num_attention_heads=20, q_lora_rank=128, kv_lora_rank=512, qk_nope_head_dim=192,
    qk_rope_head_dim=64, v_head_dim=256, intermediate_size=64, moe_intermediate_size=32,
    num_experts_per_tok=4, n_shared_experts=1, first_k_dense_replace=1, routed_scaling_factor=1.8,
    norm_topk_prob=True, topk_method="noaux_tc", n_group=1, topk_group=1, rope_theta=1e6,
    rope_scaling=None, rms_norm_eps=1e-5, n_routed_experts=64, vocab_size=256, num_hidden_layers=6,
)
LAYERS = (0, 1, 2)


def decoder_step(sharding=None, *, lanes=64, slots=256, rank=4):
    """``trunk_delta_apply`` of a three-layer decoder on abstract bfloat16
    arguments: the jitted step and what to trace it with."""
    from evotorch_tpu.neuroevolution.net.functional import FlatParamsPolicy
    from evotorch_tpu.neuroevolution.net.lowrank import sample_trunk_delta_factors

    net = Glm4MoeLiteDecoder(**GLM, max_positions=slots, layers_held=LAYERS, experts_held=range(8), vocab_held=128)
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
    state = jax.eval_shape(
        lambda: jax.tree_util.tree_map(lambda s: jnp.broadcast_to(s, (lanes,) + s.shape), net.initial_state())
    )
    z = abstract(jax.ShapeDtypeStruct((lanes, rank), bf16))
    ids = abstract(jax.ShapeDtypeStruct((lanes, 1), jnp.int32))
    step = jax.jit(lambda center, factors, z, x, state: net.trunk_delta_apply(center, factors, z, x, state))
    return step, (center, factors, z, ids, abstract(state, bf16))


def test_the_cpu_lowering_holds_no_kernel():
    step, args = decoder_step()
    text = step.trace(*args).lower().as_text()
    assert "tpu_custom_call" not in text and latent.KERNEL_NAME not in text


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
    a layer, each under the latent cache's scope, reading the compressed rows
    where the write left them (no copy or transpose of them beside it; XLA may
    move this test's small RoPE keys into VMEM, which the cell's 64 MiB do not
    fit)."""
    from jax.sharding import SingleDeviceSharding

    step, args = decoder_step(SingleDeviceSharding(v5e.devices[0]))
    text = step.trace(*args).lower(lowering_platforms=("tpu",)).compile().as_text()
    calls = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    ours = [line for line in calls if latent.KERNEL_NAME in line]
    assert len(ours) == len(LAYERS) and all("fwd_latent_cache" in line for line in ours)
    moved = [
        line
        for line in text.splitlines()
        if (" copy(" in line or " transpose(" in line) and "bf16[64,256,512]" in line
    ]
    assert not moved


def test_the_cells_layer_compiles_for_v5e(v5e):
    """The kernel alone at the benchmark's sizes (512 lanes, 20 heads, 512
    slots of 512 + 64, bfloat16), and in float32."""
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(v5e.devices[0])
    for dtype in (jnp.bfloat16, jnp.float32):
        shape = lambda *s, d=dtype: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
        args = (
            shape(512, 20, 512), shape(512, 20, 64), shape(512, 512, 512), shape(512, 512, 64),
            shape(512, d=jnp.int32), shape(d=jnp.int32),
        )
        run = jax.jit(lambda *a: latent.attend(*a, scale=0.0625))
        text = run.trace(*args).lower(lowering_platforms=("tpu",)).compile().as_text()
        assert latent.KERNEL_NAME in text


def test_the_report_counts_what_was_fetched():
    """``state_report`` sums the layers' ``fetched`` beside ``read`` (nothing
    fetched on the CPU, where the plain form runs); a decoder without a latent
    cache reports neither."""
    from tests.test_decoder import glm_decoder, small_decoder

    net = glm_decoder(steps=4)
    state = jax.tree_util.tree_map(lambda s: jnp.broadcast_to(s, (3,) + s.shape), net.initial_state())
    attn = state["layers"][1]["attn"]
    state["layers"][1]["attn"].update(read=attn["read"] + 5, fetched=attn["fetched"] + 128)
    report = net.state_report(state)
    assert int(report["latent_positions_read"]) == 15 and int(report["latent_positions_fetched"]) == 384
    assert report["latent_positions_fetched"].ndim == 0
    other = small_decoder(4)
    state = jax.tree_util.tree_map(lambda s: jnp.broadcast_to(s, (3,) + s.shape), other.initial_state())
    assert "latent_positions_fetched" not in other.state_report(state)
