"""The held experts' grouped product as one kernel (``net/grouped.py``), held
here (on the CPU, in interpret mode) to XLA's plain ``ragged_dot`` form that
the CPU and the toy widths run (``SparseExperts._experts_plain``), on the
group patterns that break grouped kernels; and compiled, without a chip, for
the v5e at the benchmark's sizes.

Both forms are also measured against a float32 evaluation of the same pairs:
in bfloat16 the kernel rounds less than the plain form (float32 accumulation;
``hidden``, a pair's weighted output and the lanes' sums are rounded once
each), so it has to lie at least as close.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from evotorch_tpu.neuroevolution.net import grouped
from evotorch_tpu.neuroevolution.net.decoder import SparseExperts
from evotorch_tpu.tools.lowrank import DeltaFactor

LANES, DIM, WIDTH, HELD, RANK, TOP_K = 300, 256, 128, 4, 4, 2
ABSENT = HELD + 3  # an expert some other chip holds


def routes(pattern):
    """``(LANES, TOP_K)`` local expert ids: column 0 by ``pattern``, column 1
    an expert that is not held (so a lane never picks an expert twice)."""
    lane = np.arange(LANES)
    first = {
        # expert 2 gets nobody
        "an_empty_expert": np.where(lane % 3 == 2, 3, lane % 3),
        # 200 lanes on expert 1: two tiles of 128 rows
        "more_rows_than_one_tile": np.where(lane < 200, 1, lane % HELD),
        # every pair that hits a held expert hits expert 3: three tiles
        "every_pair_on_one_expert": np.full(LANES, 3),
        # nothing for the kernel to visit: zeros
        "no_pair_on_a_held_expert": np.full(LANES, ABSENT),
        # 130 lanes on expert 0: its second tile holds two rows
        "a_last_tile_partly_full": np.where(lane < 130, 0, np.where(lane % 2, 2, ABSENT)),
    }[pattern]
    return jnp.asarray(np.stack([first, np.full(LANES, ABSENT + 1)], axis=1), jnp.int32)


def seeded(dtype, seed=0):
    keys = jax.random.split(jax.random.key(seed), 12)
    shapes = {"gate": (DIM, WIDTH), "up": (DIM, WIDTH), "down": (WIDTH, DIM)}
    center, factors = {}, {}
    for at, (name, (fan_in, fan_out)) in enumerate(shapes.items()):
        std = fan_in**-0.5
        center[name] = std * jax.random.normal(keys[3 * at], (HELD, fan_in, fan_out))
        factors[name] = DeltaFactor(
            a=jax.random.normal(keys[3 * at + 1], (HELD, fan_in, RANK)),
            b=0.1 * std * jax.random.normal(keys[3 * at + 2], (HELD, fan_out, RANK)),
        )
    z = jax.random.normal(keys[9], (LANES, RANK)).astype(dtype)
    y = jax.random.normal(keys[10], (LANES, DIM)).astype(dtype)
    weights = jax.random.uniform(keys[11], (LANES, TOP_K), minval=0.2, maxval=1.0)
    return center, factors, z, y, weights


def in_float32(center, factors, z, y, local, weights):
    f = lambda t: jnp.asarray(t, jnp.float32)
    out = jnp.zeros(y.shape, jnp.float32)
    for e in range(HELD):
        def m(name, x):
            thin = (x @ f(factors[name].a[e])) * f(z)
            return x @ f(center[name][e]) + thin @ f(factors[name].b[e]).T

        hidden = jax.nn.silu(m("gate", f(y))) * m("up", f(y))
        out = out + m("down", hidden) * jnp.sum(jnp.where(local == e, weights, 0.0), -1)[:, None]
    return out


def distance(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


PATTERNS = [
    "an_empty_expert",
    "more_rows_than_one_tile",
    "every_pair_on_one_expert",
    "no_pair_on_a_held_expert",
    "a_last_tile_partly_full",
]
# float32: the two forms differ by the order of float32 sums. bfloat16: by one
# unit in the last place of a bfloat16 sum (2^-7 = 7.8e-3 relative); measured
# 6.1e-3 to 6.3e-3 over the patterns, most of it the plain form's own distance
# from the float32 evaluation (6.3e-3 to 6.5e-3; the kernel's 3.8e-3 to 3.9e-3)
TOLERANCE = {"float32": 1e-5, "bfloat16": 2.0**-7}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("pattern", PATTERNS)
def test_kernel_equals_the_plain_form(pattern, dtype):
    dtype = jnp.dtype(dtype)
    center, factors, z, y, weights = seeded(dtype)
    local = routes(pattern)
    layer = SparseExperts(DIM, WIDTH, 8, TOP_K, experts_held=range(HELD))
    assert grouped.fits(LANES, DIM, WIDTH, dtype, RANK)
    got, sizes, tiles = jax.jit(
        lambda *a: grouped.held_experts(*a, interpret=True)
    )(center, factors, z, y, local, weights)
    want, want_sizes, no_tiles = jax.jit(layer._experts_plain)(center, factors, z, y, local, weights)
    assert got.dtype == want.dtype == dtype and got.shape == want.shape
    held_pairs = np.asarray((local >= 0) & (local < HELD))
    expected = np.bincount(np.asarray(local)[held_pairs], minlength=HELD)
    assert np.array_equal(sizes, expected) and np.array_equal(want_sizes, expected)
    assert int(tiles) == int(np.sum(-(-expected // grouped.ROW_TILE))) and int(no_tiles) == 0
    exact = in_float32(center, factors, z, y, local, weights)
    if pattern == "no_pair_on_a_held_expert":
        assert not np.any(np.asarray(got, np.float32)) and not np.any(np.asarray(want, np.float32))
        return
    assert distance(got, want) < TOLERANCE[dtype.name]
    # never below the plain form's precision (in float32 both sit at rounding)
    assert distance(got, exact) <= max(distance(want, exact), 1e-6)


def test_sizes_the_kernel_takes():
    bf16, f32 = jnp.dtype("bfloat16"), jnp.dtype("float32")
    assert grouped.fits(512, 2048, 1024, bf16, 4)  # the benchmark's layer
    assert grouped.fits(512, 2048, 1024, f32, 4)
    assert grouped.fits(1, 128, 128, bf16, 1)
    assert not grouped.fits(512, 64, 32, f32, 2)  # tests/test_decoder.py's toy widths
    assert not grouped.fits(512, 2048, 1000, bf16, 4)
    assert not grouped.fits(512, 2048, 1024, jnp.dtype("float16"), 4)
    assert not grouped.fits(512, 2048, 1024, bf16, 256)
    assert not grouped.fits(50_000, 2048, 1024, bf16, 4)  # the lanes' sums would not stay in VMEM
    # a matrix's block stays within four MiB: the whole width in bfloat16, half in float32
    assert grouped._width_tile(2048, 1024, 2) == 1024 and grouped._width_tile(2048, 1024, 4) == 512


def test_the_width_is_walked_in_tiles_when_a_block_would_be_too_large(monkeypatch):
    """A float32 layer of the benchmark's widths splits the experts' width in
    two; here a small block limit does the same at the test's widths, and the
    sums over the width's tiles equal the one-block product."""
    dtype = jnp.dtype("float32")
    center, factors, z, y, weights = seeded(dtype)
    wide = {"gate": (DIM, 2 * WIDTH), "up": (DIM, 2 * WIDTH), "down": (2 * WIDTH, DIM)}
    keys = jax.random.split(jax.random.key(5), 9)
    for at, (name, (fan_in, fan_out)) in enumerate(wide.items()):
        center[name] = fan_in**-0.5 * jax.random.normal(keys[3 * at], (HELD, fan_in, fan_out))
        factors[name] = DeltaFactor(
            a=jax.random.normal(keys[3 * at + 1], (HELD, fan_in, RANK)),
            b=0.1 * fan_in**-0.5 * jax.random.normal(keys[3 * at + 2], (HELD, fan_out, RANK)),
        )
    local = routes("more_rows_than_one_tile")
    run = lambda: jax.jit(lambda *a: grouped.held_experts(*a, interpret=True))(
        center, factors, z, y, local, weights
    )
    whole = run()[0]
    monkeypatch.setattr(grouped, "_BLOCK_BYTES", DIM * WIDTH * 4)
    assert grouped._width_tile(DIM, 2 * WIDTH, 4) == WIDTH
    assert distance(run()[0], whole) < 1e-6
    assert distance(whole, in_float32(center, factors, z, y, local, weights)) < 1e-5


def test_eight_held_experts_walked_in_two_width_tiles():
    """The other cell's shape of the walk (GLM-4.7-Flash holds 8 experts of
    width 1,536, which a 2,048-wide bfloat16 layer walks in two tiles of 768):
    8 held experts whose width splits in two under the block limit as it
    stands. The limit is in bytes, so a width of two registers splits only
    past 4,096 float32 inputs: 4,224 here. Against the plain form and the
    float32 evaluation."""
    lanes, dim, width, held, rank = 256, 4224, 256, 8, 2
    dtype = jnp.dtype("float32")
    assert grouped.fits(lanes, dim, width, dtype, rank)
    assert grouped._width_tile(dim, width, 4) == 128  # two tiles
    assert grouped._width_tile(2048, 1536, 2) == 768  # the cell's: two tiles
    keys = jax.random.split(jax.random.key(7), 12)
    center, factors = {}, {}
    for at, (name, (fan_in, fan_out)) in enumerate(
        {"gate": (dim, width), "up": (dim, width), "down": (width, dim)}.items()
    ):
        std = fan_in**-0.5
        center[name] = std * jax.random.normal(keys[3 * at], (held, fan_in, fan_out))
        factors[name] = DeltaFactor(
            a=jax.random.normal(keys[3 * at + 1], (held, fan_in, rank)),
            b=0.1 * std * jax.random.normal(keys[3 * at + 2], (held, fan_out, rank)),
        )
    z = jax.random.normal(keys[9], (lanes, rank))
    y = jax.random.normal(keys[10], (lanes, dim))
    weights = jax.random.uniform(keys[11], (lanes, 4), minval=0.2, maxval=1.0)
    lane = np.arange(lanes)
    # top 4 of 64: a lane's four experts are distinct; ids past 7 are other chips'
    local = jnp.asarray(np.stack([np.where(lane % 2, lane % 7, 8 + lane % 7), 16 + lane % 5, 24 + lane % 3, np.where(lane < 150, 7, 40)], axis=1), jnp.int32)
    layer = SparseExperts(dim, width, 64, 4, experts_held=range(held))
    got, sizes, tiles = jax.jit(lambda *a: grouped.held_experts(*a, interpret=True))(
        center, factors, z, y, local, weights
    )
    want, want_sizes, _ = jax.jit(layer._experts_plain)(center, factors, z, y, local, weights)
    expected = np.bincount(np.asarray(local)[np.asarray(local) < held], minlength=held)
    assert np.array_equal(sizes, expected) and np.array_equal(want_sizes, expected)
    assert expected[7] > grouped.ROW_TILE  # expert 7 takes two row tiles as well
    assert int(tiles) == int(np.sum(-(-expected // grouped.ROW_TILE)))
    assert distance(got, want) < 1e-5

    def exact():
        out = jnp.zeros(y.shape, jnp.float32)
        for e in range(held):
            m = lambda name, x: x @ center[name][e] + ((x @ factors[name].a[e]) * z) @ factors[name].b[e].T
            hidden = jax.nn.silu(m("gate", y)) * m("up", y)
            out = out + m("down", hidden) * jnp.sum(jnp.where(local == e, weights, 0.0), -1)[:, None]
        return out

    with jax.default_matmul_precision("highest"):
        assert distance(got, exact()) < 1e-5


def test_a_mesh_over_the_lanes_gets_the_plain_form(monkeypatch):
    """No cell shards a decoder's lanes; a program traced under a mesh that
    does keeps XLA's form, which the partitioner can split."""
    from evotorch_tpu.neuroevolution.net import decoder as decoder_module
    from evotorch_tpu.parallel import make_mesh

    called = []
    monkeypatch.setattr(
        decoder_module, "_by_platform", lambda fused, plain, *args: called.append(1) or plain(*args)
    )
    center, factors, z, y, weights = seeded(jnp.dtype("float32"))
    layer = SparseExperts(DIM, WIDTH, 8, TOP_K, experts_held=range(HELD))
    chosen = routes("an_empty_expert")
    trace = lambda: jax.jit(layer._experts_grouped).trace(center, factors, z, y, chosen, weights)
    trace()
    assert called == [1]
    with jax.sharding.use_abstract_mesh(make_mesh({"pop": 4}).abstract_mesh):
        trace()
    assert called == [1]


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as error:  # no libtpu here: nothing to compile with
        pytest.skip(f"no v5e:2x2 topology can be described here: {error}")


@pytest.mark.filterwarnings("ignore:Error reading persistent compilation cache entry")
@pytest.mark.parametrize(
    "build",
    [
        lambda: SparseExperts(2048, 1024, 128, 8, experts_held=range(16), route_scale=2.826),
        lambda: SparseExperts(
            2048, 1536, 64, 4, experts_held=range(8), route_scale=1.8, post_norm=False, route_norm_eps=1e-20
        ),
    ],
    ids=["trinity_mini_16_of_128_x_1024", "glm47_flash_8_of_64_x_1536"],
)
def test_a_sparse_layers_step_compiles_for_v5e(v5e, build):
    """The real TPU compiler, Mosaic included, on the benchmark's sparse
    layers (Trinity-Mini's widths, 16 of 128 experts held; GLM-4.7-Flash's, 8
    of 64, the width walked in two tiles of 768; 512 lanes, rank 4,
    bfloat16): the step holds the kernel, once, and no ``ragged-dot``."""
    from jax.sharding import SingleDeviceSharding

    from evotorch_tpu.neuroevolution.net.functional import FlatParamsPolicy
    from evotorch_tpu.neuroevolution.net.lowrank import sample_trunk_delta_factors

    lanes, rank, bf16 = 512, 4, jnp.bfloat16
    layer = build()
    policy = FlatParamsPolicy(layer)
    one_chip = SingleDeviceSharding(v5e.devices[0])

    def on_chip(tree, dtype=None):
        return jax.tree_util.tree_map(
            lambda leaf: jax.ShapeDtypeStruct(leaf.shape, dtype or leaf.dtype, sharding=one_chip), tree
        )

    flat = jax.ShapeDtypeStruct((policy.parameter_count,), jnp.float32)
    center = on_chip(jax.eval_shape(policy.unravel, flat), bf16)
    factors = on_chip(
        jax.eval_shape(
            lambda sigma: sample_trunk_delta_factors(jax.random.key(0), policy, sigma, rank), flat
        ),
        bf16,
    )
    z, x = on_chip(jax.ShapeDtypeStruct((lanes, rank), bf16)), on_chip(jax.ShapeDtypeStruct((lanes, 2048), bf16))
    step = jax.jit(lambda center, factors, z, x: layer.trunk_delta_apply(center, factors, z, x, None))
    compiled = step.trace(center, factors, z, x).lower(lowering_platforms=("tpu",)).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1 and grouped.KERNEL_NAME in calls[0] and "fwd_experts" in calls[0]
    assert "ragged-dot" not in text


def test_the_report_counts_the_tiles():
    """``state_report`` hands the tiles and a tile's rows on as scalars (no
    tile on the CPU, where the plain form runs)."""
    from tests.test_decoder import small_decoder

    net = small_decoder(4)
    state = jax.tree_util.tree_map(lambda s: jnp.broadcast_to(s, (3,) + s.shape), net.initial_state())
    report = net.state_report(state)
    assert int(report["expert_row_tiles"]) == 0 and report["expert_row_tiles"].ndim == 0
    assert int(report["expert_tile_rows"]) == grouped.ROW_TILE and report["expert_tile_rows"].ndim == 0
