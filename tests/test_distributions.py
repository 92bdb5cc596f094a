import jax
import jax.numpy as jnp
import numpy as np
import pytest

from evotorch_tpu.distributions import (
    ExpGaussian,
    ExpSeparableGaussian,
    SeparableGaussian,
    SymmetricSeparableGaussian,
    make_functional_grad_estimator,
    make_functional_sampler,
)


def test_separable_sample_stats():
    d = SeparableGaussian({"mu": jnp.array([1.0, -2.0]), "sigma": jnp.array([0.5, 2.0])})
    s = d.sample(20000, key=jax.random.key(0))
    assert s.shape == (20000, 2)
    assert np.allclose(np.asarray(jnp.mean(s, axis=0)), [1.0, -2.0], atol=0.05)
    assert np.allclose(np.asarray(jnp.std(s, axis=0)), [0.5, 2.0], atol=0.05)


def test_separable_gradients_direction():
    # fitness = x[0]: gradient of mu[0] should be positive when maximizing
    mu = jnp.zeros(3)
    sigma = jnp.ones(3)
    d = SeparableGaussian({"mu": mu, "sigma": sigma})
    samples = d.sample(4000, key=jax.random.key(1))
    fit = samples[:, 0]
    grads = d.compute_gradients(samples, fit, objective_sense="max", ranking_method="centered")
    assert float(grads["mu"][0]) > 10.0 * abs(float(grads["mu"][1]))
    # minimizing flips the sign
    grads_min = d.compute_gradients(samples, fit, objective_sense="min", ranking_method="centered")
    assert float(grads_min["mu"][0]) < 0


def test_separable_update_with_learning_rates():
    d = SeparableGaussian({"mu": jnp.zeros(2), "sigma": jnp.ones(2)})
    new = d.update_parameters(
        {"mu": jnp.array([1.0, 0.0]), "sigma": jnp.array([0.0, -0.5])},
        learning_rates={"mu": 0.1, "sigma": 0.2},
    )
    assert np.allclose(np.asarray(new.mu), [0.1, 0.0])
    assert np.allclose(np.asarray(new.sigma), [1.0, 0.9])


def test_symmetric_sampling_antithetic():
    d = SymmetricSeparableGaussian({"mu": jnp.array([5.0, 5.0]), "sigma": jnp.ones(2)})
    s = d.sample(10, key=jax.random.key(0))
    # interleaved pairs: s[0] + s[1] == 2*mu
    assert np.allclose(np.asarray(s[0::2] + s[1::2]), 10.0, atol=1e-5)
    with pytest.raises(ValueError):
        d.sample(5, key=jax.random.key(0))


def _plain_symmetric_sample(key, mu, sigma, num_solutions):
    """The antithetic sampler's three plain lines, kept here as the reference
    of the stream: draw ``(N / 2, L)``, stack the pair, interleave."""
    eps = jax.random.normal(key, (num_solutions // 2, mu.shape[-1]), dtype=mu.dtype) * sigma
    return jnp.stack([mu + eps, mu - eps], axis=1).reshape(num_solutions, mu.shape[-1])


def _one_place(mu, samples):
    """A unit in the last place of the larger of centre and sample: where
    ``mu + eps`` cancels, one rounding of ``eps`` is many places of the sum, and
    a compiler that fuses the multiply into the add in one form alone moves it."""
    return np.spacing(np.maximum(np.abs(np.asarray(mu)), np.abs(np.asarray(samples))))


def _seeded_parameters(seed, length, dtype=jnp.float32):
    k_mu, k_sigma = jax.random.split(jax.random.key(1000 + seed))
    mu = (3.0 * jax.random.normal(k_mu, (length,))).astype(dtype)
    sigma = (0.05 + jnp.abs(jax.random.normal(k_sigma, (length,)))).astype(dtype)
    return mu, sigma


@pytest.mark.parametrize(
    "seed,num_solutions,length",
    [
        (0, 6, 5),  # odd length, three directions
        (1, 20, 131),  # N / 2 no multiple of 8, L no multiple of 128
        (2, 10, 12_305),  # the flagship's length at a small popsize
        (3, 2, 1),
        (4, 64, 256),  # whole tiles both ways
    ],
)
def test_symmetric_sampler_keeps_the_stream(seed, num_solutions, length):
    # the elementwise sampler computes every normal from the counter that
    # jax.random.normal(key, (N / 2, L)) gives it: the same bits, interleaved
    key = jax.random.key(seed)
    mu, sigma = _seeded_parameters(seed, length)
    sample = jax.jit(
        lambda key, mu, sigma: SymmetricSeparableGaussian._sample(
            key, {"mu": mu, "sigma": sigma}, num_solutions
        )
    )
    normals = jax.random.normal(key, (num_solutions // 2, length))
    unit = np.asarray(sample(key, jnp.zeros(length), jnp.ones(length)))
    assert np.array_equal(unit[0::2], np.asarray(normals))
    assert np.array_equal(unit[1::2], -np.asarray(normals))
    centred = np.asarray(sample(key, jnp.zeros(length), sigma))
    # the directions, to two places: under jit XLA scales sigma by the normal's sqrt(2) first
    directions = np.asarray(normals * sigma)
    assert np.all(np.abs(centred[0::2] - directions) <= 2 * np.spacing(np.abs(directions)))
    assert np.array_equal(centred[0::2], -centred[1::2])  # antithetic, exactly
    samples = np.asarray(sample(key, mu, sigma))
    plain = np.asarray(jax.jit(_plain_symmetric_sample, static_argnums=3)(key, mu, sigma, num_solutions))
    assert samples.shape == plain.shape == (num_solutions, length)
    assert np.all(np.abs(samples - plain) <= _one_place(mu, plain))


@pytest.mark.parametrize("outer_jit", [False, True], ids=["eager", "outer_jit"])
@pytest.mark.parametrize("batch_shape", [(3,), (2, 2)], ids=["batch3", "batch2x2"])
def test_symmetric_sampler_keeps_the_stream_under_vmap(batch_shape, outer_jit):
    # make_functional_sampler splits the key over the batch and vmaps _sample:
    # the counter arithmetic must trace with batched key words, mu and sigma
    num_solutions, length = 12, 37
    batch = int(np.prod(batch_shape))
    mu, sigma = _seeded_parameters(7, batch * length)
    mu = mu.reshape(batch_shape + (length,))
    sigma = sigma.reshape(batch_shape + (length,))[(0,) * (len(batch_shape) - 1)]  # batched on its last batch axis only
    sampler = make_functional_sampler(SymmetricSeparableGaussian)
    run = lambda key, mu, sigma: sampler(key, num_solutions, {"mu": mu, "sigma": sigma})
    if outer_jit:
        run = jax.jit(run)
    key = jax.random.key(11)
    out = np.asarray(run(key, mu, sigma))
    assert out.shape == batch_shape + (num_solutions, length)
    flat_mu = jnp.reshape(mu, (batch, length))
    flat_sigma = jnp.broadcast_to(sigma, batch_shape + (length,)).reshape(batch, length)
    plain_sample = _plain_symmetric_sample
    if outer_jit:  # compiled like the form it is held to: XLA reorders the scalings under jit
        plain_sample = jax.jit(plain_sample, static_argnums=3)
    for lane, lane_key in enumerate(jax.random.split(key, batch)):
        plain = np.asarray(plain_sample(lane_key, flat_mu[lane], flat_sigma[lane], num_solutions))
        got = out.reshape(batch, num_solutions, length)[lane]
        assert np.all(np.abs(got - plain) <= _one_place(flat_mu[lane], plain))
        # the directions back out of the pairs: two roundings of samples this large
        directions = np.asarray(jax.random.normal(lane_key, (num_solutions // 2, length)) * flat_sigma[lane])
        recovered = (got[0::2] - got[1::2]) / 2
        assert np.all(np.abs(recovered - directions) <= 2 * _one_place(flat_mu[lane], plain[0::2]))


@pytest.mark.parametrize(
    "row_length,rows,cols",
    [
        (98_321, [0, 1, 43_690, 43_691, 39_999], [0, 1, 98_320]),  # popsize 80,000: 3.9e9, under 2**32
        (98_321, [43_691, 50_000, 2**31 - 1, 2**32 - 1], [0, 54_321, 98_320]),  # past it
        (2**32 - 1, [0, 1, 65_537, 2**32 - 1], [0, 2**32 - 2]),  # every half-product carries
        (65_536, [65_535, 65_536, 2**32 - 1], [0, 65_535]),
        (1, [0, 2**32 - 1], [0]),
    ],
)
def test_linear_index_words_past_32_bits(row_length, rows, cols):
    # row * row_length + col as two 32-bit words, against Python's integers;
    # a handful of (row, col) pairs: nothing of that size is allocated
    from evotorch_tpu.distributions import _linear_index_words

    row = jnp.asarray(np.array(rows, dtype=np.uint32))[:, None]
    col = jnp.asarray(np.array(cols, dtype=np.uint32))[None, :]
    hi, lo = _linear_index_words(row, col, row_length)
    assert hi.dtype == lo.dtype == jnp.uint32 and hi.shape == lo.shape == (len(rows), len(cols))
    for i, r in enumerate(rows):
        for j, c in enumerate(cols):
            assert (int(hi[i, j]) << 32) | int(lo[i, j]) == r * row_length + c


def test_linear_index_words_are_jax_random_bits_counter():
    # the counter jax itself gives draw [row, col] of a (rows, row_length)
    # request under jax_threefry_partitionable
    from jax._src.prng import iota_2x32_shape

    from evotorch_tpu.distributions import _linear_index_words

    rows, row_length = 9, 131
    want_hi, want_lo = iota_2x32_shape((rows, row_length))
    hi, lo = _linear_index_words(
        jax.lax.iota(jnp.uint32, rows)[:, None], jax.lax.iota(jnp.uint32, row_length)[None, :], row_length
    )
    assert np.array_equal(np.asarray(hi), np.asarray(want_hi))
    assert np.array_equal(np.asarray(lo), np.asarray(want_lo))


def test_symmetric_sampler_traces_past_32_bits():
    # popsize 90,000 x 98,321: direction 44,999's draws lie past 2**32; abstract
    sample = lambda key, mu, sigma: SymmetricSeparableGaussian._sample(
        key, {"mu": mu, "sigma": sigma}, 90_000
    )
    vec = jax.ShapeDtypeStruct((98_321,), jnp.float32)
    out = jax.eval_shape(sample, jax.random.key(0), vec, vec)
    assert out.shape == (90_000, 98_321) and out.dtype == jnp.float32


def _takes_the_elementwise_form(key, dtype=jnp.float32):
    mu = jnp.zeros(5, dtype)
    jaxpr = jax.make_jaxpr(
        lambda key, mu: SymmetricSeparableGaussian._sample(key, {"mu": mu, "sigma": mu + 1}, 6)
    )(key, mu)
    # the plain form draws through jax.random (random_bits); the elementwise
    # one binds threefry on its own counter
    return "random_bits" not in str(jaxpr) and "threefry2x32" in str(jaxpr)


@pytest.mark.parametrize(
    "make_key,dtype,elementwise",
    [
        (lambda: jax.random.key(0), jnp.float32, True),
        (lambda: jax.random.PRNGKey(0), jnp.float32, True),  # a raw key of the default implementation
        (lambda: jax.random.key(0, impl="rbg"), jnp.float32, False),
        (lambda: jax.random.key(0, impl="unsafe_rbg"), jnp.float32, False),
        (lambda: jax.random.key(0), jnp.bfloat16, False),
        (lambda: jax.random.key(0), jnp.float16, False),
    ],
    ids=["threefry", "raw_threefry", "rbg", "unsafe_rbg", "bfloat16", "float16"],
)
def test_symmetric_sampler_form_follows_key_and_dtype(make_key, dtype, elementwise):
    # what the sampler observes in its input decides the form; either way the
    # population is what jax.random.normal's stream gives, interleaved
    key = make_key()
    assert _takes_the_elementwise_form(key, dtype) == elementwise
    mu, sigma = _seeded_parameters(5, 33, dtype)
    samples = jax.jit(
        lambda key, mu, sigma: SymmetricSeparableGaussian._sample(key, {"mu": mu, "sigma": sigma}, 10)
    )(key, mu, sigma)
    assert samples.dtype == dtype and samples.shape == (10, 33)
    plain = jax.jit(_plain_symmetric_sample, static_argnums=3)(key, mu, sigma, 10)
    as64 = lambda x: np.asarray(x.astype(jnp.float32), dtype=np.float64)
    if elementwise:
        assert np.all(np.abs(as64(samples) - as64(plain)) <= _one_place(mu, plain))
    else:
        assert np.array_equal(as64(samples), as64(plain))
    places = 2.0 ** -jnp.finfo(dtype).nmant
    assert np.allclose(as64(samples[0::2] + samples[1::2]), 2 * as64(mu), atol=8 * places * (3 * 4 + 4))


def test_symmetric_sampler_float64_takes_the_plain_form():
    with jax.enable_x64(True):
        assert not _takes_the_elementwise_form(jax.random.key(0), jnp.float64)
        mu = jnp.full((3,), 5.0, jnp.float64)
        samples = SymmetricSeparableGaussian._sample(
            jax.random.key(0), {"mu": mu, "sigma": jnp.ones(3, jnp.float64)}, 8
        )
        assert samples.dtype == jnp.float64
        assert np.allclose(np.asarray(samples[0::2] + samples[1::2]), 10.0, atol=1e-12)


def test_symmetric_sampler_without_partitionable_threefry_takes_the_plain_form():
    # the counter is the row-major index only under jax_threefry_partitionable
    with jax.threefry_partitionable(False):
        key = jax.random.key(0)
        assert not _takes_the_elementwise_form(key)
        mu, sigma = _seeded_parameters(6, 7)
        samples = SymmetricSeparableGaussian._sample(key, {"mu": mu, "sigma": sigma}, 4)
        assert np.array_equal(np.asarray(samples), np.asarray(_plain_symmetric_sample(key, mu, sigma, 4)))


@pytest.mark.parametrize("impl", ["threefry2x32", "rbg"])
def test_symmetric_sampling_antithetic_whatever_the_key(impl):
    d = SymmetricSeparableGaussian({"mu": jnp.array([5.0, 5.0]), "sigma": jnp.ones(2)})
    s = d.sample(10, key=jax.random.key(0, impl=impl))
    assert np.allclose(np.asarray(s[0::2] + s[1::2]), 10.0, atol=1e-5)


def _pairwise_symmetric_gradients(parameters, samples, weights, ranking_used):
    """The dense antithetic gradient over the even rows alone, as it was until
    PR 38: the reference of the whole-population form."""
    from evotorch_tpu.distributions import _divide_grad, _zero_center_weights

    mu, sigma = parameters["mu"], parameters["sigma"]
    weights = _zero_center_weights(weights, ranking_used)
    noises = samples[0::2] - mu
    plus, minus = weights[0::2], weights[1::2]
    return {
        "mu": _divide_grad(parameters, "mu", ((plus - minus) / 2) @ noises, weights),
        "sigma": _divide_grad(
            parameters, "sigma", ((plus + minus) / 2) @ ((noises**2 - sigma**2) / sigma), weights
        ),
    }


@pytest.mark.parametrize("divide_by", [None, "num_solutions", "num_directions", "total_weight", "weight_stdev"])
@pytest.mark.parametrize("ranking_method", ["centered", "linear", "nes", "normalized", "raw"])
def test_symmetric_gradients_over_all_rows_match_the_pairwise_form(ranking_method, divide_by):
    from evotorch_tpu.tools.ranking import rank

    num_solutions, length = 200, 37
    mu, sigma = _seeded_parameters(8, length)
    parameters = {"mu": mu, "sigma": sigma}
    if divide_by is not None:
        parameters.update(divide_mu_grad_by=divide_by, divide_sigma_grad_by=divide_by)
    samples = SymmetricSeparableGaussian._sample(jax.random.key(9), parameters, num_solutions)
    fitnesses = -jnp.sum((samples - 1.0) ** 2, axis=-1) + jax.random.normal(jax.random.key(10), (num_solutions,))
    weights = rank(fitnesses, ranking_method, higher_is_better=True)
    got = jax.jit(
        lambda samples, weights: SymmetricSeparableGaussian._compute_gradients(
            parameters, samples, weights, ranking_method
        )
    )(samples, weights)
    want = _pairwise_symmetric_gradients(parameters, samples, weights, ranking_method)
    for name in ("mu", "sigma"):
        scale = float(jnp.max(jnp.abs(want[name])))
        assert np.allclose(np.asarray(got[name]), np.asarray(want[name]), rtol=1e-5, atol=1e-5 * scale), name


def test_symmetric_gradients_parenthood_ratio_untouched():
    # the CEM-style branch is SeparableGaussian's own: elites' mean and stdev
    mu, sigma = _seeded_parameters(12, 9)
    parameters = {"mu": mu, "sigma": sigma, "parenthood_ratio": 0.25}
    samples = SymmetricSeparableGaussian._sample(jax.random.key(13), parameters, 40)
    weights = jnp.sum(samples, axis=-1)
    got = SymmetricSeparableGaussian._compute_gradients(parameters, samples, weights, "raw")
    want = SeparableGaussian._compute_gradients_via_parenthood_ratio(parameters, samples, weights)
    for name in ("mu", "sigma"):
        assert np.array_equal(np.asarray(got[name]), np.asarray(want[name]))


def test_symmetric_gradients_solve_simple_quadratic():
    # maximize -|x - 3|^2 via symmetric PGPE-style updates
    d = SymmetricSeparableGaussian({"mu": jnp.zeros(4), "sigma": jnp.full((4,), 1.0)})
    key = jax.random.key(42)
    for _ in range(40):
        key, sub = jax.random.split(key)
        samples = d.sample(100, key=sub)
        fit = -jnp.sum((samples - 3.0) ** 2, axis=-1)
        grads = d.compute_gradients(samples, fit, objective_sense="max", ranking_method="centered")
        d = d.update_parameters(grads, learning_rates={"mu": 0.3, "sigma": 0.05})
    assert np.allclose(np.asarray(d.mu), 3.0, atol=0.5)


def test_exp_separable_snes_update():
    d = ExpSeparableGaussian({"mu": jnp.zeros(2), "sigma": jnp.ones(2)})
    new = d.update_parameters(
        {"mu": jnp.array([0.5, 0.0]), "sigma": jnp.array([1.0, -1.0])},
        learning_rates={"mu": 1.0, "sigma": 0.2},
    )
    assert np.allclose(np.asarray(new.mu), [0.5, 0.0])
    # sigma multiplied by exp(0.5 * lr * grad)
    assert np.allclose(np.asarray(new.sigma), [np.exp(0.1), np.exp(-0.1)], atol=1e-6)


def test_expgaussian_roundtrip_and_update():
    A = jnp.array([[2.0, 0.0], [0.5, 1.0]])
    d = ExpGaussian({"mu": jnp.array([1.0, 2.0]), "sigma": A})
    z = jax.random.normal(jax.random.key(0), (7, 2))
    x = d.to_global_coordinates(z)
    z2 = d.to_local_coordinates(x)
    assert np.allclose(np.asarray(z), np.asarray(z2), atol=1e-4)

    samples = d.sample(3000, key=jax.random.key(1))
    fit = samples[:, 0]
    grads = d.compute_gradients(samples, fit, objective_sense="max", ranking_method="centered")
    assert set(grads) == {"d", "M"}
    new = d.update_parameters(grads, learning_rates={"mu": 0.1, "sigma": 0.01})
    # A_inv stays the inverse of A after the expm update (float32 tolerance)
    assert np.allclose(np.asarray(new.A @ new.A_inv), np.eye(2), atol=2e-2)
    assert float(new.mu[0]) > float(d.mu[0])


def test_functional_sampler_batched():
    sampler = make_functional_sampler(SeparableGaussian)
    mu = jnp.stack([jnp.zeros(3), jnp.full((3,), 10.0)])  # batch of 2 searches
    sigma = jnp.ones(3)
    out = sampler(jax.random.key(0), 50, {"mu": mu, "sigma": sigma})
    assert out.shape == (2, 50, 3)
    assert abs(float(jnp.mean(out[0]))) < 0.5
    assert abs(float(jnp.mean(out[1])) - 10.0) < 0.5
    # batches get different noise
    assert not np.allclose(np.asarray(out[0]), np.asarray(out[1]) - 10.0)


def test_functional_grad_estimator_batched():
    est = make_functional_grad_estimator(
        SeparableGaussian, objective_sense="max", ranking_method="centered"
    )
    key = jax.random.key(0)
    mu = jnp.zeros((2, 3))
    sigma = jnp.ones(3)
    sampler = make_functional_sampler(SeparableGaussian)
    samples = sampler(key, 200, {"mu": mu, "sigma": sigma})
    fits = samples[..., 0]
    grads = est(samples, fits, {"mu": mu, "sigma": sigma})
    assert grads["mu"].shape == (2, 3)
    assert float(grads["mu"][0, 0]) > 0 and float(grads["mu"][1, 0]) > 0


def test_bound_function_grad_estimator():
    est = make_functional_grad_estimator(
        SymmetricSeparableGaussian,
        function=lambda xs: -jnp.sum(xs**2, axis=-1),
        objective_sense="max",
        ranking_method="centered",
        return_samples=True,
        return_fitnesses=True,
    )
    grads, samples, fits = est(
        jax.random.key(3), 100, {"mu": jnp.full((4,), 5.0), "sigma": jnp.ones(4)}
    )
    assert samples.shape == (100, 4)
    assert fits.shape == (100,)
    # maximizing -x^2 from mu=5: gradient pulls mu down
    assert all(float(g) < 0 for g in grads["mu"])


def test_unknown_parameter_rejected():
    with pytest.raises(ValueError):
        SeparableGaussian({"mu": jnp.zeros(2), "sigma": jnp.ones(2), "bogus": 1})


def test_kl_divergence():
    a = SeparableGaussian({"mu": jnp.zeros(2), "sigma": jnp.ones(2)})
    b = SeparableGaussian({"mu": jnp.zeros(2), "sigma": jnp.ones(2)})
    assert a.relative_entropy(b) == pytest.approx(0.0, abs=1e-6)
    c = SeparableGaussian({"mu": jnp.ones(2), "sigma": jnp.ones(2)})
    assert a.relative_entropy(c) > 0
