import jax.numpy as jnp
import numpy as np
import pytest

from evotorch_tpu.tools import ranking


def test_centered_basic():
    f = jnp.array([1.0, 3.0, 2.0, 4.0])
    u = ranking.centered(f, higher_is_better=True)
    # best solution (4.0) gets +0.5, worst (1.0) gets -0.5
    assert np.isclose(float(u[3]), 0.5)
    assert np.isclose(float(u[0]), -0.5)
    assert np.isclose(float(jnp.sum(u)), 0.0, atol=1e-6)


def test_centered_minimization():
    f = jnp.array([1.0, 3.0, 2.0, 4.0])
    u = ranking.centered(f, higher_is_better=False)
    assert np.isclose(float(u[0]), 0.5)
    assert np.isclose(float(u[3]), -0.5)


def test_linear_range():
    f = jnp.array([5.0, 1.0, 3.0])
    u = ranking.linear(f, higher_is_better=True)
    assert np.isclose(float(jnp.min(u)), 0.0)
    assert np.isclose(float(jnp.max(u)), 1.0)


def test_nes_properties():
    f = jnp.array([0.1, 0.9, 0.5, 0.3, 0.7])
    u = ranking.nes(f, higher_is_better=True)
    # weights sum to ~0 and the best solution has the largest weight
    assert np.isclose(float(jnp.sum(u)), 0.0, atol=1e-6)
    assert int(jnp.argmax(u)) == int(jnp.argmax(f))
    # worst weights are all equal to -1/n (clipped utilities)
    assert float(u[0]) == pytest.approx(-1.0 / 5.0, abs=1e-6)


def test_normalized():
    f = jnp.array([1.0, 2.0, 3.0])
    u = ranking.normalized(f, higher_is_better=True)
    assert np.isclose(float(jnp.mean(u)), 0.0, atol=1e-6)
    # unbiased stdev (ddof=1), matching the reference's torch.std
    assert np.isclose(float(np.std(np.asarray(u), ddof=1)), 1.0, atol=1e-5)
    # reference values for [3,1,2,5] (torch.std semantics)
    u = ranking.normalized(jnp.array([3.0, 1.0, 2.0, 5.0]), higher_is_better=True)
    assert np.allclose(np.asarray(u), [0.1462, -1.0247, -0.4392, 1.3178], atol=1e-3)


def test_raw_sign():
    f = jnp.array([1.0, -2.0])
    assert np.allclose(np.asarray(ranking.raw(f, higher_is_better=True)), [1.0, -2.0])
    assert np.allclose(np.asarray(ranking.raw(f, higher_is_better=False)), [-1.0, 2.0])


def test_rank_dispatcher_and_batching():
    f = jnp.array([[1.0, 2.0, 3.0], [3.0, 2.0, 1.0]])
    u = ranking.rank(f, "centered", higher_is_better=True)
    assert u.shape == (2, 3)
    assert np.allclose(np.asarray(u[0]), [-0.5, 0.0, 0.5])
    assert np.allclose(np.asarray(u[1]), [0.5, 0.0, -0.5])
    with pytest.raises(ValueError):
        ranking.rank(f, "bogus", higher_is_better=True)


def test_ties_get_distinct_ranks():
    f = jnp.array([1.0, 1.0, 1.0])
    u = ranking.centered(f, higher_is_better=True)
    assert np.isclose(float(jnp.sum(u)), 0.0, atol=1e-6)
    assert len(set(np.asarray(u).tolist())) == 3


def test_centered_fused_flag_is_an_error_off_the_chip(monkeypatch):
    # EVOTORCH_TPU_FUSED_RANK=1 asks for the compiled kernel through the
    # public rank() entry; off the chip that is an error, never a quiet
    # interpret-mode run (chip_smoke.py checks the kernel against
    # centered_xla where it compiles)
    import numpy as np

    from evotorch_tpu.tools.ranking import centered_xla, rank

    fit = jnp.asarray(np.random.default_rng(0).normal(size=257), jnp.float32)
    monkeypatch.setenv("EVOTORCH_TPU_FUSED_RANK", "1")
    with pytest.raises(ValueError, match="interpret mode"):
        rank(fit, "centered", higher_is_better=True)
    monkeypatch.setenv("EVOTORCH_TPU_FUSED_RANK", "0")
    want = rank(fit, "centered", higher_is_better=True)
    np.testing.assert_allclose(
        np.asarray(want), np.asarray(centered_xla(fit, higher_is_better=True)), atol=0
    )


def test_centered_fused_dispatch_bounds(monkeypatch):
    # outside [2, 1024] the dispatcher must stay on XLA even when forced
    import numpy as np

    from evotorch_tpu.tools import ranking as ranking_mod

    monkeypatch.setenv("EVOTORCH_TPU_FUSED_RANK", "1")
    assert not ranking_mod._use_fused_centered(1)
    assert not ranking_mod._use_fused_centered(4096)
    assert not ranking_mod._use_fused_centered(2048)  # over the VMEM budget
    assert ranking_mod._use_fused_centered(1024)
    monkeypatch.setenv("EVOTORCH_TPU_FUSED_RANK", "0")
    assert not ranking_mod._use_fused_centered(512)
    # big-n always works through the public entry regardless of the flag
    monkeypatch.setenv("EVOTORCH_TPU_FUSED_RANK", "1")
    fit = jnp.asarray(np.random.default_rng(1).normal(size=5000), jnp.float32)
    out = ranking_mod.rank(fit, "centered", higher_is_better=False)
    assert out.shape == (5000,)


def test_fused_rank_nan_semantics_match_xla():
    # a NaN fitness (diverged rollout) must rank identically in both paths:
    # argsort places NaN last, i.e. "best" — the fused kernel's total order
    # is lexicographic on (isnan, value, index)
    import numpy as np

    from evotorch_tpu.ops.ranking import fused_centered_rank
    from evotorch_tpu.tools.ranking import centered_xla

    fit = jnp.asarray([1.0, jnp.nan, 3.0, 2.0, jnp.nan, -1.0], jnp.float32)
    for hib in (True, False):
        got = fused_centered_rank(fit, higher_is_better=hib, use_pallas=True, interpret=True)
        want = centered_xla(fit, higher_is_better=hib)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


def test_fused_sampling_optin_dispatch(monkeypatch):
    # EVOTORCH_TPU_FUSED_SAMPLING is opt-in; the dispatcher must be OFF by
    # default (the kernel changes the random stream, not just the speed).
    # Set off the chip it is an error — the on-chip PRNG only lowers on the
    # TPU — never a warning and the XLA sampler
    import jax

    from evotorch_tpu.distributions import (
        SymmetricSeparableGaussian,
        _use_fused_sampling,
    )

    dist = SymmetricSeparableGaussian({"mu": jnp.zeros(4), "sigma": jnp.ones(4)})
    monkeypatch.delenv("EVOTORCH_TPU_FUSED_SAMPLING", raising=False)
    assert not _use_fused_sampling()
    assert dist.sample(6, key=jax.random.key(0)).shape == (6, 4)
    monkeypatch.setenv("EVOTORCH_TPU_FUSED_SAMPLING", "1")
    assert _use_fused_sampling()
    with pytest.raises(ValueError, match="interpret mode"):
        dist.sample(6, key=jax.random.key(0))
