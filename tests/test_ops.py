import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from evotorch_tpu.ops import fused_centered_rank, sample_symmetric_gaussian
from evotorch_tpu.tools.ranking import centered


def test_xla_sampling_path():
    mu = jnp.array([1.0, -2.0, 0.0])
    sigma = jnp.array([0.5, 1.0, 2.0])
    out = sample_symmetric_gaussian(jax.random.key(0), mu, sigma, 1000)
    assert out.shape == (1000, 3)
    # antithetic pairs interleaved
    assert np.allclose(np.asarray(out[0::2] + out[1::2]), 2 * np.asarray(mu), atol=1e-5)
    assert np.allclose(np.asarray(jnp.mean(out, axis=0)), np.asarray(mu), atol=0.15)


def test_pallas_sampling_rejects_odd():
    with pytest.raises(ValueError):
        sample_symmetric_gaussian(jax.random.key(0), jnp.zeros(3), jnp.ones(3), 7)


def test_fused_centered_rank_matches_library():
    fit = jax.random.normal(jax.random.key(2), (64,))
    expected = np.asarray(centered(fit, higher_is_better=True))
    got = np.asarray(
        fused_centered_rank(fit, higher_is_better=True, use_pallas=True, interpret=True)
    )
    assert np.allclose(got, expected, atol=1e-6)
    # minimization direction
    expected = np.asarray(centered(fit, higher_is_better=False))
    got = np.asarray(
        fused_centered_rank(fit, higher_is_better=False, use_pallas=True, interpret=True)
    )
    assert np.allclose(got, expected, atol=1e-6)


def test_fused_centered_rank_with_ties():
    fit = jnp.array([1.0, 1.0, 2.0, 0.0])
    got = np.asarray(fused_centered_rank(fit, use_pallas=True, interpret=True))
    expected = np.asarray(centered(fit, higher_is_better=True))
    assert np.allclose(sorted(got), sorted(expected))
    assert got.sum() == pytest.approx(0.0, abs=1e-6)


def test_box_muller_math():
    # validate the in-kernel Box-Muller transform statistically (pure jnp)
    from evotorch_tpu.ops.sampling import _box_muller

    key = jax.random.key(3)
    bits_a = jax.random.bits(key, (200, 128), dtype=jnp.uint32)
    bits_b = jax.random.bits(jax.random.key(4), (200, 128), dtype=jnp.uint32)
    eps = np.asarray(_box_muller(bits_a, bits_b))
    assert abs(eps.mean()) < 0.02
    assert abs(eps.std() - 1.0) < 0.02


def test_fused_centered_rank_batched_pallas():
    fit = jax.random.normal(jax.random.key(5), (3, 32))
    got = np.asarray(fused_centered_rank(fit, use_pallas=True, interpret=True))
    expected = np.asarray(centered(fit, higher_is_better=True))
    assert got.shape == (3, 32)
    assert np.allclose(got, expected, atol=1e-6)


def test_fused_rank_off_chip_needs_explicit_interpret():
    # the backend never picks the mode: asked for off the chip, the kernel is
    # an error unless the caller passes interpret=True
    fit = jnp.arange(8, dtype=jnp.float32)
    with pytest.raises(ValueError, match="interpret mode"):
        fused_centered_rank(fit, use_pallas=True)


def _v5e_sharding():
    """A deviceless TPU v5e target: the installed libtpu compiles for a chip
    the host does not have (the real TPU compiler, Mosaic included)."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topology = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    return SingleDeviceSharding(topology.devices[0])


def _compile_for_v5e(fn, *args, **static_kwargs):
    return fn.trace(*args, **static_kwargs).lower(lowering_platforms=("tpu",)).compile()


# a warm suite cache holds the TPU executable a previous run compiled, and the
# compile-only client cannot load it back: jax warns and compiles afresh
_deviceless = pytest.mark.filterwarnings(
    "ignore:Error reading persistent compilation cache entry"
)


@_deviceless
@pytest.mark.parametrize("shape", [(2,), (1000,), (1024,), (3, 5, 1024)])
def test_fused_rank_compiles_for_v5e(shape):
    # the ends of what tools.ranking._use_fused_centered admits, plus a
    # batched input (a squeezed 1-D block of a (B, n) array is refused)
    fit = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=_v5e_sharding())
    _compile_for_v5e(fused_centered_rank, fit, higher_is_better=True, use_pallas=True)


@_deviceless
@pytest.mark.parametrize(
    "popsize,length",
    [
        (10_000, 12_305),  # the PGPE flagship: Humanoid 64x64
        (1_024, 66_048),  # bench_ops.py's second shape
        (10, 100),  # smaller than one (8, 128) tile on both axes
    ],
)
def test_fused_sampling_compiles_for_v5e(popsize, length):
    # the ungridded kernel asked for one (2, half, L) VMEM window and was
    # refused at the flagship shape; the grid keeps a step's blocks to MiBs
    sharding = _v5e_sharding()
    vec = jax.ShapeDtypeStruct((length,), jnp.float32, sharding=sharding)
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype, sharding=sharding)
    compiled = _compile_for_v5e(
        sample_symmetric_gaussian, key, vec, vec, num_solutions=popsize, use_pallas=True
    )
    assert compiled.memory_analysis().output_size_in_bytes >= popsize * length * 4


def _population_sized(text, popsize, length):
    """``(dtype, dims, layout)`` of every array in HLO ``text`` that holds the
    population's ``popsize x length`` elements or half of them, whatever its
    rank: the arrays a relayout of which is a pass over gigabytes."""
    sizes = (popsize * length, popsize * length // 2)
    return [
        found.groups()
        for found in re.finditer(r"\b([a-z]+\d+)\[([\d,]+)\](\{[^}]*\})?", text)
        if int(np.prod([int(d) for d in found.group(2).split(",")])) in sizes
    ]


@_deviceless
@pytest.mark.parametrize(
    "popsize,length",
    [
        (50_000, 12_305),  # humanoid_mlp64: the device's layout has the population in the LANES
        (10_000, 98_321),  # humanoid_mlp256: row-major
    ],
)
@pytest.mark.parametrize("program", ["ask_sample", "grad_grads"])
def test_dense_antithetic_programs_pass_over_the_population_once_for_v5e(program, popsize, length):
    # PGPE's dense sampler and its gradient, as the OO searcher jits and names
    # them, at the benchmark's two shapes: each program one pass over the
    # population in the layout the array has on the device. Before PR 38 XLA
    # pushed the interleave's unit axis into the generator (tiles of ONE
    # sublane: T(1,128)), relaid the 2.46 GB result three times and the
    # gradient's input once, beside 4.97 and 3.73 GB of temporaries; a CPU
    # suite sees none of that
    from evotorch_tpu import distributions
    from evotorch_tpu.distributions import SymmetricSeparableGaussian

    sharding = _v5e_sharding()
    vec = jax.ShapeDtypeStruct((length,), jnp.float32, sharding=sharding)
    parameters = {"mu": vec, "sigma": vec}
    if program == "ask_sample":
        key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype, sharding=sharding)
        traced = distributions._jitted_sample_for(SymmetricSeparableGaussian).trace(
            key, parameters, (), popsize
        )
    else:
        samples = jax.ShapeDtypeStruct((popsize, length), jnp.float32, sharding=sharding)
        fitnesses = jax.ShapeDtypeStruct((popsize,), jnp.float32, sharding=sharding)
        traced = distributions._jitted_grads_for(SymmetricSeparableGaussian).trace(
            parameters, samples, fitnesses, (), "centered", True
        )
    compiled = traced.lower(lowering_platforms=("tpu",)).compile()
    text = compiled.as_text()
    assert text.startswith(f"HloModule jit_evotorch_tpu_{program},"), text[:80]

    narrow = [
        shape
        for shape in _population_sized(text, popsize, length)
        if "T(1,128)" in (shape[2] or "") or "T(2,128)" in (shape[2] or "")
    ]
    assert narrow == [], narrow  # every vector op on whole (8, 128) registers

    # the entry computation's instructions that RESULT in an array of the population's size
    passes = [
        (found.group("op"), found.group(0)[:160])
        for found in re.finditer(
            r"^ *(?:ROOT )?\S+ = (?P<type>\(.*?\)|\S+) (?P<op>[\w-]+)\(.*$",
            text[text.index("\nENTRY ") :],
            flags=re.MULTILINE,
        )
        if found.group("op") != "parameter" and _population_sized(found.group("type"), popsize, length)
    ]
    relayouts = [line for op, line in passes if op.startswith(("copy", "reshape", "transpose", "pad"))]
    assert relayouts == [], relayouts  # no pass that only moves it
    # the sampler writes it once, in one fusion; the gradient reads it and makes no other
    assert [op for op, _ in passes] == (["fusion"] if program == "ask_sample" else []), passes
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20


def test_fused_centered_rank_degenerate_and_dtype():
    # review regression: n == 1 must match the XLA fallback (no NaN)
    out = fused_centered_rank(jnp.array([5.0]), use_pallas=True, interpret=True)
    assert float(out[0]) == 0.0
    f32 = fused_centered_rank(
        jnp.arange(4, dtype=jnp.float32), use_pallas=True, interpret=True
    )
    assert f32.dtype == jnp.float32
