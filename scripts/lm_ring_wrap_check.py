"""The decoder's checks that need the chip and do not fit the benchmark's
cell: the sliding-window ring WRAPPED at the published window (the cell's 256
steps never fill the 2,048-slot ring), and the cell's comparison with a lower
precision in the program's place (the control its bounds were set against).

    python scripts/lm_ring_wrap_check.py [--steps 2304] [--lanes 4] [--seed 1] [--cpu --tiny]
    python scripts/lm_ring_wrap_check.py --control int8,float8_e4m3,bfloat16 [--seed 1] [--cpu --tiny]
        [--cell glm47_flash_ep8.decode512] [--seeds 10]
    python scripts/lm_ring_wrap_check.py --cell granite4_h_micro_pp4.decode256 --control int8,bfloat16
    python scripts/lm_ring_wrap_check.py --cell kimi_linear_ep32.decode256 --control int8,bfloat16,no_correction,bf16_state

Default: ``trinity_mini_ep8``'s share of the model (``benchmark/configs/``), a
seeded trunk and one generation's rank-4 factors; ``--lanes`` lanes are
teacher-forced through a seeded id sequence of ``--steps`` tokens by the
population-wide stepwise forward (``net/decoder.py:stepwise_logits``: the
forward the rollout steps, cache as carried state, ``sliding_window`` 2,048 so
the ring wraps at step 2,048), and the plain whole-sequence reference
(``benchmark/reference/afmoe_decoder.py``) computes the same logits on each
lane's weights, layer by layer, with the routes the system chose. Prints one
JSON line: relative RMS error of all logits, of the logits at the positions
past the wrap alone, and the share of top-k sets that differ; exits non-zero
where the error past the wrap (of all positions, in a replay too short to
wrap) exceeds the cell's bound (``drivers/oo_lm_searcher.py:LOGIT_RTOL``).

``--control``: the cell's own session (``--cell``, default
``trinity_mini_ep8.decode256``: its driver, its lanes and steps; two
generations so that a population has been evaluated), then its
``reference_checks`` as the benchmark runs them (with ``--seeds N`` once for
each of N seeds from ``--seed`` on, each drawing other lanes: the system's
readings a cell's bounds are set from), and
once more per named precision with the REFERENCE, its matrices rounded to
that precision (int8 and float8 e4m3 scaled to the largest entry of a leaf),
in the program's place, through the same comparison and the same limits (a
name of the cell's driver's ``EQUATION_CONTROLS``, such as ``no_correction``,
stands for the reference with that equation changed instead).
Prints one JSON line with every comparison; exits non-zero unless the system
comes out ok and int8, the nearest precision below bfloat16, does not.
``--cpu --tiny`` rehearses either mode on the CPU (a window of 16; the cell's
rehearsal scale).
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def rounded(kind):
    """Round a weight matrix (or a stack of them) to ``kind`` and back to
    float32; vectors (norm weights, the expert bias) stay."""
    import jax.numpy as jnp

    def to(leaf):
        if leaf.ndim < 2:
            return leaf
        if kind == "bfloat16":
            return leaf.astype(jnp.bfloat16).astype(jnp.float32)
        top = jnp.max(jnp.abs(leaf))
        if kind == "int8":
            scale = top / 127.0
            return jnp.round(leaf / scale) * scale
        scale = top / 448.0  # float8 e4m3's largest finite value
        return (leaf / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale

    return to


def control(args, files):
    import jax

    from evotorch_tpu.observability import enable_persistent_cache
    from evotorch_tpu.resilience import setup_backend

    setup_backend(force_cpu=args.cpu)
    enable_persistent_cache()  # the cell's own programs: the benchmark's cache has them
    workload = files.workload(args.cell)
    config = files.config(workload["config"])
    scale = {key: (value if args.tiny else config[key]) for key, value in config["rehearse"].items()}
    driver = files.driver(workload["driver"])
    session = driver.build(files, config, workload, args.seed, scale)
    equations = getattr(driver, "EQUATION_CONTROLS", {})  # a changed equation of the reference, by name
    for _ in range(2):
        session.generation()
    session.block()
    out = {"device": jax.devices()[0].device_kind, "scale": scale, "system": session.reference_checks(args.seed)}
    others = [session.reference_checks(seed) for seed in range(args.seed + 1, args.seed + args.seeds)]
    for kind in args.control.split(","):
        out[kind] = session.reference_checks(args.seed, control=kind if kind in equations else rounded(kind))
    verdict = {
        name: all(check["ok"] for check in checks.values())
        for name, checks in out.items()
        if name not in ("device", "scale")
    }
    if others:
        out["system_other_seeds"] = others
        verdict["system"] = verdict["system"] and all(c["ok"] for checks in others for c in checks.values())
    out["ok"] = verdict
    out["peak_bytes_in_use"] = int((jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use", 0))
    print(json.dumps(out))
    return 0 if verdict["system"] and not verdict.get("int8", False) else 1


def main(argv):
    parser = argparse.ArgumentParser()
    parser.add_argument("--steps", type=int, default=2304)
    parser.add_argument("--lanes", type=int, default=4)
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument("--cpu", action="store_true")
    parser.add_argument("--tiny", action="store_true")
    parser.add_argument("--control", default=None, help="precisions, comma-separated: int8,float8_e4m3,bfloat16")
    parser.add_argument("--cell", default="trinity_mini_ep8.decode256", help="the cell --control runs")
    parser.add_argument("--seeds", type=int, default=1, help="--control: seeds the system's comparison is drawn with")
    args = parser.parse_args(argv)
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"

    from benchmark.harness.loader import BenchmarkFiles

    files = BenchmarkFiles(ROOT)
    if args.control:
        return control(args, files)
    config = files.config("trinity_mini_ep8")

    import jax
    import jax.numpy as jnp
    import numpy as np

    from evotorch_tpu.neuroevolution.net.decoder import AfmoeDecoder, stepwise_logits
    from evotorch_tpu.neuroevolution.net.functional import FlatParamsPolicy
    from evotorch_tpu.neuroevolution.net.lowrank import sample_trunk_delta_factors
    from evotorch_tpu.resilience import setup_backend
    from evotorch_tpu.tools.lowrank import TrunkDeltaParamsBatch

    setup_backend(force_cpu=args.cpu)
    driver = files.driver("oo_lm_searcher")
    scale = {"kept_sparse_layers": 1, "vocab_held": 512} if args.tiny else {}
    if args.tiny:
        config = dict(config, sliding_window=16)
        args.steps = min(args.steps, 40)
    ref = files.module_at(config["reference"]["forward"])
    s = ref.sizes(config, scale)
    first, past = config["experts_held"]
    net = AfmoeDecoder(
        **{key: config[key] for key in driver.MODEL_KEYS},
        num_experts=int(config["published"]["num_experts"]),
        vocab_size=int(config["published"]["vocab_size"]),
        max_positions=args.steps,
        layers_held=s["layers"],
        experts_held=range(int(first), int(past)),
        vocab_held=s["vocab"],
    )
    policy = FlatParamsPolicy(net)
    dtype = None if args.cpu else jnp.bfloat16
    k_center, k_factors, k_coeffs, k_ids = jax.random.split(jax.random.key(args.seed), 4)
    rank = int(config["trunk_delta_rank"])
    stdev = float(config["searcher"]["stdev_init"])

    @jax.jit
    def trunk(k_center, k_factors):
        sigma = jnp.full((policy.parameter_count,), stdev, jnp.float32)
        return policy.init_parameters(k_center), sample_trunk_delta_factors(k_factors, policy, sigma, rank)

    @jax.jit
    def system(batch, ids):
        return stepwise_logits(policy, batch, ids, compute_dtype=dtype)

    center, factors = trunk(k_center, k_factors)
    batch = TrunkDeltaParamsBatch(
        center=center, coeffs=jax.random.normal(k_coeffs, (args.lanes, rank)), factors=factors
    )
    ids = jax.random.randint(k_ids, (args.lanes, args.steps), 0, s["vocab"])
    logits, routes = system(batch, ids)
    window = s["window"]
    positions = np.broadcast_to(np.arange(args.steps), ids.shape)
    found = driver.reference_comparison(
        driver.LaneReference(ref, s, policy), batch, range(args.lanes), np.asarray(ids), positions,
        np.asarray(logits), np.asarray(routes), cuts=(0, window),
    )
    error = dict(zip(("all", "past_wrap"), found["relative_rms_error"]))
    flips, pairs = found["flips"], found["pairs"]
    bound = driver.LOGIT_RTOL
    judged = error["all"] if error["past_wrap"] is None else error["past_wrap"]  # no wrap in a short replay
    ok = judged <= bound and found["finite"]
    out = {
        "ok": bool(ok),
        "device": jax.devices()[0].device_kind,
        "lanes": args.lanes,
        "steps": args.steps,
        "sliding_window": window,
        "relative_rms_error": error,
        "bound": bound,
        "top_k_sets_differ_share": flips / pairs if pairs else None,
        "peak_bytes_in_use": int((jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use", 0)),
    }
    print(json.dumps(out))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
