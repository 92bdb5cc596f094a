"""The fused control step of ``envs/rigidbody.py`` on the chip, outside the
benchmark: what it costs per lane-step, and how far it lies from the plain
form and from another checkout's physics after one control step.

    python scripts/fused_physics_check.py [--env humanoid] [--lanes 50000,8192]
        [--steps 50] [--other chip_checkout/parent] [--cpu]

For each lane count: a seeded, perturbed population state (so that joints,
limits and contacts all act) is stepped ``--steps`` control steps inside one
jitted loop, once through ``physics_step_batched`` as the library dispatches
it (on a TPU: the kernel) and once through the plain form; the line gives
milliseconds per control step and microseconds per lane-step of each, and the
largest absolute difference of every state field after ONE control step
between the two forms. ``--other DIR`` loads ``DIR/evotorch_tpu/envs/
rigidbody.py`` (the parent commit, say) and adds its time and its one-step
differences from the plain form at the default matmul precision and at
``highest``: where the two differ, that checkout's physics rounded its
one-hot scatters and axis projections through bfloat16 on this device.
Prints one JSON line per lane count. ``--cpu`` rehearses (kernel in interpret
mode, a time that means nothing).
"""

import argparse
import importlib.util
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--env", default="humanoid")
    parser.add_argument("--lanes", default="50000,8192")
    parser.add_argument("--steps", type=int, default=50)
    parser.add_argument("--other", default=None)
    parser.add_argument("--cpu", action="store_true")
    args = parser.parse_args()
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    import numpy as np

    from evotorch_tpu.envs import make_env
    from evotorch_tpu.envs import rigidbody as rb
    from evotorch_tpu.resilience import device_record, setup_backend

    setup_backend(force_cpu=args.cpu)
    env = make_env(args.env)
    sys_, h = env.sys, env.dt / env.substeps

    other = None
    if args.other:
        path = os.path.join(args.other, "evotorch_tpu", "envs", "rigidbody.py")
        spec = importlib.util.spec_from_file_location("other_rigidbody", path)
        other = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(other)

    def fused(st, actions):
        if args.cpu:
            return rb._fused_step(sys_, st, actions, h, env.substeps, interpret=True)
        return rb.physics_step_batched(sys_, st, actions, env.dt, env.substeps)

    def plain(st, actions):
        return rb._plain_step(sys_, st, actions, h, env.substeps)

    def others(st, actions):
        return rb.BodyState(
            *other.physics_step_batched(sys_, other.BodyState(*st), actions, env.dt, env.substeps)
        )

    def looped(step):
        def loop(st, actions):
            return jax.lax.fori_loop(0, args.steps, lambda _, s: step(s, actions), st)

        return jax.jit(loop)

    forms = {"fused": fused, "plain": plain, **({"other": others} if other else {})}
    once = {name: jax.jit(step) for name, step in forms.items()}
    loops = {name: looped(step) for name, step in forms.items()}

    def timed(loop, st, actions):
        jax.block_until_ready(loop(st, actions))
        times = []
        for _ in range(3):
            started = time.perf_counter()
            jax.block_until_ready(loop(st, actions))
            times.append(time.perf_counter() - started)
        return 1e3 * sorted(times)[1] / args.steps

    def difference(a, b):
        return {
            name: float(jnp.max(jnp.abs(x - y)))
            for name, x, y in zip(rb.BodyState._fields, a, b)
        }

    for lanes in (int(n) for n in args.lanes.split(",")):
        keys = jax.random.split(jax.random.key(lanes), 6)
        state, _ = env.batch_reset(jax.random.split(keys[0], lanes))
        st = state.obs_state
        quat = st.quat + 0.1 * jax.random.normal(keys[1], st.quat.shape)
        st = rb.BodyState(
            pos=st.pos + 0.02 * jax.random.normal(keys[2], st.pos.shape),
            quat=quat / jnp.linalg.norm(quat, axis=1, keepdims=True),
            vel=st.vel + 0.5 * jax.random.normal(keys[3], st.vel.shape),
            ang=st.ang + 1.0 * jax.random.normal(keys[4], st.ang.shape),
        )
        actions = jax.random.uniform(keys[5], (sys_.num_act, lanes), minval=-1.0, maxval=1.0)

        line = {"env": args.env, "lanes": lanes, "steps": args.steps, "device": device_record()}
        one_plain = once["plain"](st, actions)
        line["scale"] = {n: float(jnp.max(jnp.abs(x))) for n, x in zip(rb.BodyState._fields, one_plain)}
        line["one_step_fused_minus_plain"] = difference(once["fused"](st, actions), one_plain)
        if other is not None:
            line["one_step_other_minus_plain"] = difference(once["other"](st, actions), one_plain)
            with jax.default_matmul_precision("highest"):
                exact = once["other"](st, actions)  # the precision is part of jit's cache key
            line["one_step_other_highest_minus_plain"] = difference(exact, one_plain)
        for name, loop in loops.items():
            ms = timed(loop, st, actions)
            line[name + "_ms_per_step"] = ms
            line[name + "_us_per_lane_step"] = 1e3 * ms / lanes
        assert all(np.isfinite(v) for v in line["one_step_fused_minus_plain"].values()), line
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
