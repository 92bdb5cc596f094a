"""Matched-action env-fidelity harness: native rigid-body vs real MuJoCo.

The native envs (``envs/halfcheetah.py`` etc.) claim ``-v4``/``-v5``-class
semantics in their docstrings; this module turns those claims into *measured*
statements. Both simulators are driven with **identical action sequences**
(smooth AR(1) exploration noise, plus an all-zero sequence — the zero-action
drift diagnostic), per-step reward terms are recorded on each side
(``batch_reward_terms`` on the native envs, ``MjVecEnv.last_terms`` on the
real ones), and the report summarizes per-term divergence: means on each
side, mean absolute per-step difference, and the correlation of the
per-step traces over the steps where both sims are still alive.

What this does and does not establish: the two engines integrate different
body plans with different contact models, so per-step traces are *not*
expected to match — the comparison measures whether the native tasks put the
policy in the same reward regime (velocity scale, control-cost scale,
survival behaviour) as the canonical benchmark. Scores earned on the native
sims are comparable to gymnasium scores only to the extent this report says
they are.

Run as a module (host physics + CPU JAX; needs no chip)::

    python -m evotorch_tpu.envs.mujoco.fidelity \
        --pairs halfcheetah,walker2d --seqs 8 --steps 300 \
        --out bench_curves/fidelity_r6.json --markdown
"""

from __future__ import annotations

import argparse
import json
from typing import Dict, Optional, Sequence

import numpy as np

__all__ = ["PAIRS", "run_fidelity", "format_fidelity_markdown"]

# native registry name -> (real gymnasium id, native env kwargs)
PAIRS: Dict[str, tuple] = {
    "halfcheetah": ("HalfCheetah-v5", {}),
    "walker2d": ("Walker2d-v5", {}),
    # survival-only pair: the native cartpole is the closest dynamics match
    # to InvertedPendulum-v5 (cart + pole, |angle| termination); only the
    # total-reward / episode statistics are comparable
    "cartpole": ("InvertedPendulum-v5", {"continuous_actions": True}),
}


def _action_sequences(rng: np.random.Generator, n_seqs: int, n_steps: int, act_dim: int):
    """Smooth AR(1) exploration actions in [-1, 1]; sequence 0 is all-zero
    (the zero-action drift check — free reward on either sim shows up as a
    nonzero velocity mean in that lane)."""
    rho, amp = 0.8, 0.6
    acts = np.zeros((n_seqs, n_steps, act_dim))
    for s in range(1, n_seqs):
        a = np.zeros(act_dim)
        for t in range(n_steps):
            a = rho * a + np.sqrt(1.0 - rho * rho) * rng.normal(0.0, amp, act_dim)
            acts[s, t] = a
    return np.clip(acts, -1.0, 1.0)


def _native_trajectories(env, actions: np.ndarray, seed: int) -> Dict[str, np.ndarray]:
    """Drive the native env with ``actions`` ``(S, T, na)``; returns per-step
    ``(S, T)`` term traces (NaN once a lane's episode has ended) + ``alive``.

    The per-call ``jax.jit`` wrappers below are baselined graftlint
    ``retrace`` findings: ``run_fidelity`` constructs a FRESH env per pair
    and drives it through here exactly once, so one trace per env is
    inherent — and caching the wrappers on env identity would never hit
    while pinning dead envs (and their executables) for the process
    lifetime."""
    import jax
    import jax.numpy as jnp

    S, T, _ = actions.shape
    keys = jax.random.split(jax.random.key(seed), S)
    batched = bool(getattr(env, "batched_native", False))
    if batched:
        state, _ = env.batch_reset(keys)
        step = jax.jit(env.batch_step)
    else:
        state, _ = jax.vmap(env.reset)(keys)
        step = jax.jit(jax.vmap(env.step))
    terms_fn = None
    if hasattr(env, "batch_reward_terms"):
        terms_fn = jax.jit(env.batch_reward_terms)
    has_terms = terms_fn is not None

    out = {"reward_total": np.full((S, T), np.nan), "alive": np.zeros((S, T), bool)}
    active = np.ones(S, dtype=bool)
    for t in range(T):
        a = jnp.asarray(actions[:, t, :])
        state, _, reward, done = step(state, a)
        reward, done = np.asarray(reward), np.asarray(done)
        out["reward_total"][active, t] = reward[active]
        out["alive"][:, t] = active
        if has_terms:
            terms = terms_fn(state.obs_state, jnp.clip(a, -1.0, 1.0).T)
            for name in ("x_velocity", "reward_ctrl", "reward_survive"):
                trace = out.setdefault(name, np.full((S, T), np.nan))
                trace[active, t] = np.asarray(terms[name])[active]
        active = active & ~done
        if not active.any():
            break
    return out


def _mujoco_trajectories(env_id: str, actions: np.ndarray, seed: int) -> Dict[str, np.ndarray]:
    """Same trace collection on the real env through :class:`MjVecEnv` (one
    lane per action sequence, single episode per lane)."""
    import gymnasium as gym

    from .mjvecenv import MjVecEnv

    S, T, _ = actions.shape
    venv = MjVecEnv(lambda: gym.make(env_id), S)
    try:
        venv.seed([seed + i for i in range(S)])
        venv.reset()
        out = {"reward_total": np.full((S, T), np.nan), "alive": np.zeros((S, T), bool)}
        active = np.ones(S, dtype=bool)
        for t in range(T):
            _, rewards, dones = venv.step(actions[:, t, :], active=active)
            out["reward_total"][active, t] = rewards[active]
            out["alive"][:, t] = active
            for name in ("x_velocity", "reward_ctrl", "reward_survive"):
                if name in venv.last_terms:
                    trace = out.setdefault(name, np.full((S, T), np.nan))
                    trace[active, t] = venv.last_terms[name][active]
            active = active & ~dones
            if not active.any():
                break
        return out
    finally:
        venv.close()


def _term_summary(native: np.ndarray, mujoco: np.ndarray, both: np.ndarray) -> dict:
    a, b = native[both], mujoco[both]
    summary = {
        "native_mean": float(np.nanmean(native)),
        "mujoco_mean": float(np.nanmean(mujoco)),
        "matched_steps": int(both.sum()),
    }
    if a.size >= 2:
        summary["mean_abs_diff"] = float(np.mean(np.abs(a - b)))
        sa, sb = np.std(a), np.std(b)
        summary["corr"] = (
            float(np.corrcoef(a, b)[0, 1]) if sa > 1e-12 and sb > 1e-12 else None
        )
    return summary


def run_fidelity(
    pairs: Optional[Sequence[str]] = None,
    *,
    n_seqs: int = 8,
    n_steps: int = 300,
    seed: int = 0,
) -> dict:
    """Run the matched-action comparison for each named pair (default: all of
    :data:`PAIRS`) and return the report dict (JSON-serializable)."""
    from ..registry import make_env

    names = list(PAIRS) if pairs is None else list(pairs)
    report = {
        "config": {"n_seqs": n_seqs, "n_steps": n_steps, "seed": seed},
        "pairs": {},
    }
    rng = np.random.default_rng(seed)
    for name in names:
        env_id, native_kwargs = PAIRS[name]
        env = make_env(name, **native_kwargs)
        act_dim = int(np.prod(env.action_space.shape))
        import gymnasium as gym

        probe = gym.make(env_id)
        mj_act_dim = int(np.prod(probe.action_space.shape))
        probe.close()
        if act_dim != mj_act_dim:
            raise ValueError(
                f"{name}: native action dim {act_dim} != {env_id} dim {mj_act_dim}"
            )
        actions = _action_sequences(rng, n_seqs, n_steps, act_dim)
        native = _native_trajectories(env, actions, seed)
        mujoco = _mujoco_trajectories(env_id, actions, seed)

        both = native["alive"] & mujoco["alive"]
        terms = {}
        for term in ("x_velocity", "reward_ctrl", "reward_survive", "reward_total"):
            if term in native and term in mujoco:
                terms[term] = _term_summary(native[term], mujoco[term], both)
        # zero-action drift: lane 0 carries the all-zero action sequence
        zero_drift = {}
        for side, traces in (("native", native), ("mujoco", mujoco)):
            if "x_velocity" in traces:
                lane = traces["x_velocity"][0]
                zero_drift[f"{side}_mean_velocity"] = float(np.nanmean(lane))
        pair_report = {
            "mujoco_env": env_id,
            "action_dim": act_dim,
            "native_weights": {
                "forward_reward_weight": float(getattr(env, "forward_reward_weight", 0.0)),
                "ctrl_cost_weight": float(getattr(env, "ctrl_cost_weight", 0.0)),
                "alive_bonus": float(getattr(env, "alive_bonus", 0.0)),
            },
            "terms": terms,
            "episode": {
                "native_mean_length": float(native["alive"].sum(axis=1).mean()),
                "mujoco_mean_length": float(mujoco["alive"].sum(axis=1).mean()),
            },
        }
        if zero_drift:
            pair_report["zero_action_drift"] = zero_drift
        report["pairs"][name] = pair_report
    return report


def format_fidelity_markdown(report: dict) -> str:
    """The fidelity report as markdown: one table per pair."""
    cfg = report["config"]
    lines = [
        "### Env-fidelity: native rigid-body vs real MuJoCo `-v5` (matched actions)",
        "",
        f"Harness: `python -m evotorch_tpu.envs.mujoco.fidelity` — "
        f"{cfg['n_seqs']} AR(1) action sequences (one all-zero) x "
        f"{cfg['n_steps']} steps, seed {cfg['seed']}. Per-step terms compared "
        "over the steps where both sims are alive.",
        "",
    ]
    for name, pair in report["pairs"].items():
        lines.append(f"**{name} vs {pair['mujoco_env']}**")
        lines.append("")
        lines.append("| term | native mean | mujoco mean | mean abs diff | corr |")
        lines.append("|---|---|---|---|---|")
        for term, s in pair["terms"].items():
            corr = s.get("corr")
            lines.append(
                f"| {term} | {s['native_mean']:+.3f} | {s['mujoco_mean']:+.3f} | "
                f"{s.get('mean_abs_diff', float('nan')):.3f} | "
                f"{'n/a' if corr is None else f'{corr:+.2f}'} |"
            )
        ep = pair["episode"]
        lines.append(
            f"| episode length | {ep['native_mean_length']:.0f} | "
            f"{ep['mujoco_mean_length']:.0f} | | |"
        )
        drift = pair.get("zero_action_drift")
        if drift:
            lines.append("")
            lines.append(
                "Zero-action drift (mean forward velocity, all-zero lane): "
                + ", ".join(f"{k} = {v:+.3f} m/s" for k, v in drift.items())
            )
        lines.append("")
    return "\n".join(lines)


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--pairs", default=None, help="comma list (default: all)")
    parser.add_argument("--seqs", type=int, default=8)
    parser.add_argument("--steps", type=int, default=300)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--out", default=None, help="write the JSON report here")
    parser.add_argument("--markdown", action="store_true", help="print the report as markdown tables")
    args = parser.parse_args(argv)

    # host-physics harness: JAX runs on the CPU, whatever the machine offers
    from ...resilience import setup_backend

    setup_backend(force_cpu=True)

    pairs = None if args.pairs is None else [p.strip() for p in args.pairs.split(",") if p.strip()]
    report = run_fidelity(pairs, n_seqs=args.seqs, n_steps=args.steps, seed=args.seed)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)
    if args.markdown:
        print(format_fidelity_markdown(report))
    else:
        print(json.dumps(report))


if __name__ == "__main__":
    main()
