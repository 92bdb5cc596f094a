"""Driver: evolution strategies on a language model, through the path a
researcher calls: ``VecNE(env=TokenCopyEnv(...), network=AfmoeDecoder(...),
eval_mode="budget")`` + ``PGPE(..., lowrank_rank=("trunk_delta", k))`` +
``searcher.step()``, one whole generation per call. Every lane decodes
``decode_steps`` tokens under its own perturbed weights (a seeded prompt fed
one token a step, then its own greedy tokens), the population in the
shared-trunk form. The session protocol is ``drivers/oo_searcher.py``'s.

The configuration file holds the published model's keys; ``num_experts``,
``vocab_size`` and ``num_hidden_layers`` there are what THIS chip holds (they
are under ``reduced``; ``published`` has the model's own), and ``scale`` may
shrink popsize, steps, sparse layers and rows for the CPU rehearsal.

``reference_checks`` holds what the TIMED program emitted (the ids every lane
consumed in the last evaluation, which the decoder keeps in its state) against
the plain whole-sequence reference (``benchmark/reference/
afmoe_decoder.py``); the four figures, their bounds and the reasons are below.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

# the library's token environment and decoder: a checkout without them cannot
# run this cell and fails here, before any backend or compile
from evotorch_tpu.envs.tokens import TokenCopyEnv
from evotorch_tpu.neuroevolution.net.decoder import AfmoeDecoder, stepwise_logits

from evotorch_tpu.algorithms import PGPE
from evotorch_tpu.neuroevolution import VecNE
from evotorch_tpu.tools.lowrank import DeltaFactor

DTYPES = {"bfloat16": jnp.bfloat16, "float32": None}
MODEL_KEYS = (
    "hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
    "intermediate_size", "moe_intermediate_size", "num_experts_per_tok",
    "num_shared_experts", "num_dense_layers", "layer_types", "sliding_window",
    "rope_theta", "route_scale", "route_norm", "score_func", "rms_norm_eps",
    "mup_enabled",
)

# The comparison with the reference (PERF.md section 6, PR 28, has every
# reading). What is compared is the last evaluation of the warm-up, the same
# compiled 512-lane rollout the window times: the decoder's state keeps the id
# every lane consumed at every step and the lane's position in its episode
# there (``VecNE.last_policy_report``), so the tokens a lane emitted are its
# next observations. ``checked_lanes`` lanes are drawn with ``--seed`` (lanes
# that ended an episode early first, so a reset is replayed) and
#
# 1. the record is replayed, teacher-forced, resets where it shows them,
#    through the population-wide stepwise forward over ALL lanes (the same
#    ``_batched_forward`` on the same shapes as the timed step: 512 lanes,
#    4,096 expert rows) with the checked lanes' logits kept;
# 2. the plain float32 "highest" reference runs the same ids, whole sequence,
#    on each checked lane's weights (written out here from trunk, factors and
#    coefficients, one layer at a time so that it fits), once going on with
#    the experts the system chose and once on its own.
#
# With seeded random weights the eighth and ninth of the router's 128 scores
# lie within bf16's rounding of each other at one position in ten, and ONE
# swapped expert moves that position's logits by tens of percent, so the
# logits are compared with the routes fixed and the routes are counted apart.
#
# - TOKENS_REPLAYED: share of the emitted tokens that the replay's argmax
#   reproduces. Ties the logits that are compared to the timed program: a
#   wrong token, a bad reset of the cache, an argmax or an integer cast gone
#   wrong in the engine reads near zero. On the v5e 97.6% to 98.4% (1,730
#   tokens a run): the rollout and the replay are two compilations of one
#   forward, the compiler fuses them differently, so bf16 roundings fall
#   elsewhere and the first of 25,024 logits changes at one position in
#   fifty. Another forward of the same precision agrees less: the reference
#   with bf16-rounded weights 96.9% with itself in float32, the float32
#   reference 92.6% to 93.9% with the system. The bound is the geometric mean
#   of the first readings' shares of tokens NOT reproduced (2.0% and 6.9%),
#   held as a count with three binomial standard deviations of room.
# - LOGIT_RTOL: relative RMS error of the replay's logits against the
#   reference's under the system's routes. On the v5e the system read 1.47e-2
#   to 1.72e-2 (8 lanes x 256 positions, 19 runs) and 1.26e-2 over the
#   positions past the wrap of a 2,304-step replay; the reference with its
#   matrices rounded to bf16, in the program's place, reads 0.70e-2
#   (activations, cache, factors and coefficients are bf16 too in the system).
#   The nearest precision below: int8 weights (scaled to the largest of a
#   leaf) read 6.0e-2 at the cell's 8 lanes x 256 (5.3e-2 at 2 lanes), float8
#   e4m3 10.8e-2. A dropped shared expert, a missing route_scale, or RoPE on
#   the full layer read 0.3 or more (tier-1 holds each on the CPU). The bound
#   was set at the geometric mean of the first readings (1.6e-2 and 5.0e-2)
#   and leaves 1.6x of room above the largest reading of the system and 1.9x
#   below int8.
# - ROUTE_FLIP_SHARE: share of (position, sparse layer) pairs whose top-k
#   SETS differ between the system's router and the reference's. The router
#   is float32 on both sides but reads bf16 hidden states here: the system
#   read 9.8% to 11.1% (8.7% with the ring wrapped); bf16-rounded weights
#   alone 4.9%, int8 weights 35.7% (33.1% at 2 lanes), float8 60.0%; 7
#   experts instead of 8, or a bias that weighed, is every pair. The bound is
#   the geometric mean of the first readings (10.4% and 29.9%), held as a
#   COUNT with three standard deviations of a binomial's room (a rehearsal
#   compares eight pairs, the cell 8,192): 1.6x above the system's largest,
#   1.9x below int8.
# - TOKENS_AGREED: share of the emitted tokens that the reference, going on
#   with its OWN routes, also puts first. Nothing of the system's goes into
#   the reference here but the ids. The system read 92.6% to 93.9%; int8
#   weights in the program's place 82.5% (bf16-rounded weights alone 96.9%).
#   The bound is the geometric mean of the shares that differ (6.9% and
#   17.5%), held as a count like the one above: 1.5x above the system's
#   largest, 1.6x below int8.
TOKENS_REPLAYED = 0.96
LOGIT_RTOL = 2.8e-2
ROUTE_FLIP_SHARE = 0.176
TOKENS_AGREED = 0.89


class _Lowers:
    """What harness/scopes.py asks a session's ``problem`` for: the lowered
    evaluation program of the population form this session evaluates."""

    def __init__(self, problem, searcher):
        self._problem, self._searcher = problem, searcher

    def lower_evaluation(self, popsize):
        return self._problem.lower_evaluation(popsize, like=self._searcher.population.values)


class Session:
    def __init__(self, files, config, workload, seed, scale):
        traffic = workload["traffic"]
        self.popsize = int(scale["popsize"])
        self.decode_steps = int(scale["decode_steps"])
        self.compute_dtype = DTYPES[config["compute_dtype"]]
        self._checked_lanes = int(scale["checked_lanes"])
        # ``traffic.search_seed``: the grouped expert product's time follows the
        # routing (more on the fullest expert, more time), and the routing
        # follows the weights: from seed to seed a generation varies by 1.2%
        # (PERF.md, PR 28). As in ``humanoid_mlp64.episodes``, the search then
        # starts from a seed fixed in the workload file, every run of a commit
        # does the same work, and ``--seed`` draws the lanes that the
        # comparison with the reference checks
        search_seed = traffic.get("search_seed")
        search_seed = int(seed if search_seed is None else search_seed)
        self._reference = files.module_at(config["reference"]["forward"])
        self._sizes = self._reference.sizes(config, scale)
        first, past = config["experts_held"]
        self.network = AfmoeDecoder(
            **{key: config[key] for key in MODEL_KEYS},
            num_experts=int(config["published"]["num_experts"]),
            vocab_size=int(config["published"]["vocab_size"]),
            max_positions=self.decode_steps,
            layers_held=self._sizes["layers"],
            experts_held=range(int(first), int(past)),
            vocab_held=self._sizes["vocab"],
        )
        # a rehearsal's few steps are half prompt, so that tokens are emitted
        self.env = TokenCopyEnv(
            self._sizes["vocab"], min(int(config["prompt_length"]), max(self.decode_steps // 2, 1)), self.decode_steps
        )
        self.vecne = VecNE(
            self.env,
            self.network,
            eval_mode=traffic["eval_mode"],
            num_actors=traffic["num_actors"],
            episode_length=self.decode_steps,
            compute_dtype=self.compute_dtype,
            observation_normalization=bool(config["observation_normalization"]),
            # at 705M parameters every vector of the solution's length is 2.8 GB
            # of the chip's 16: no bounds, no best-and-worst snapshots
            initial_bounds=None,
            store_solution_stats=False,
            seed=search_seed,
        )
        self.parameter_count = self.vecne.solution_length
        if self.parameter_count != self._reference.parameter_count(self._sizes):
            raise ValueError("the library's parameter count is not the reference's")
        if self.popsize == int(config["popsize"]) and self.parameter_count != int(config["parameter_count"]):
            raise ValueError(
                f"the network has {self.parameter_count} parameters, the configuration says"
                f" {config['parameter_count']}"
            )
        recipe = dict(config["searcher"])
        if recipe.pop("class") != "PGPE":
            raise ValueError("this driver runs PGPE")
        radius = float(recipe["stdev_init"]) * math.sqrt(self.parameter_count)
        policy = self.vecne.policy
        self.searcher = PGPE(
            self.vecne,
            popsize=self.popsize,
            lowrank_rank=("trunk_delta", int(config["trunk_delta_rank"])),
            # the seeded initial trunk stands in for a checkpoint
            center_init=jax.jit(policy.init_parameters)(jax.random.key(search_seed)),
            stdev_init=float(recipe["stdev_init"]),
            center_learning_rate=float(recipe["center_learning_rate_over_radius"]) * radius,
            stdev_learning_rate=float(recipe["stdev_learning_rate"]),
            optimizer=recipe["optimizer"],
            optimizer_config={"max_speed": float(recipe["max_speed_over_radius"]) * radius},
            ranking_method=recipe["ranking_method"],
        )
        self.problem = _Lowers(self.vecne, self.searcher)
        self.devices = jax.devices()[: int(workload["chips"])]
        interactions = self.popsize * self.decode_steps  # budget: every lane-step counts
        self.per_call = {
            "generations": 1,
            "interactions": interactions,
            "interactions_max": interactions,
            "episodes": None,
            "telemetry_lag": 1,
        }
        # for the per-layer readers (benchmark/harness/lm_floors.py)
        self.lm_config = config
        self.lm_sizes = self._sizes
        state = jax.eval_shape(self.network.initial_state)
        self.cache_bytes = self.popsize * sum(
            math.prod(layer["attn"][name].shape) * (2 if self.compute_dtype is not None else 4)
            for layer in state["layers"]
            for name in ("k", "v")
        )

    # -- the measured path ---------------------------------------------------
    def generation(self):
        self.searcher.step()

    def block(self):
        jax.block_until_ready(self.searcher.population.evals)

    def mark(self):
        if self.searcher.step_count == 0:
            return {"interactions": 0, "episodes": 0, "finite": True, "telemetry": None}
        status = self.searcher.status
        telemetry = self.vecne.last_group_telemetry
        total = None if telemetry is None else telemetry.total()
        return {
            "interactions": int(status["total_interaction_count"]),
            "episodes": int(status["total_episode_count"]),
            "finite": bool(jnp.isfinite(self.searcher.population.evals).all()),
            "telemetry": None
            if total is None
            else {n: int(getattr(total, n)) for n in ("env_steps", "episodes", "capacity", "nonfinite")},
        }

    def policy_counters(self):
        """The scalars of the last evaluation's report."""
        report = self.vecne.last_policy_report
        if report is None:
            return None
        return {name: int(value) for name, value in report.items() if jnp.ndim(value) == 0}

    # -- the comparison with the plain reference -----------------------------
    def reference_checks(self, seed, control=None):
        """The four figures above for the evaluation in hand (the last of the
        warm-up: ``searcher.population`` is what it ran). ``control``: a
        function that rounds a weight leaf to a lower precision; the REFERENCE
        with its weights rounded so, going on with its own routes, then takes
        the program's place (its logits, its routes, its first tokens), and
        the comparison has to come out not ok (scripts/lm_ring_wrap_check.py
        --control)."""
        policy = self.vecne.policy
        batch = self.searcher.population.values
        report = self.vecne.last_policy_report
        ids, positions = np.asarray(report["ids_seen"]), np.asarray(report["positions_seen"])
        record_ok = bool(
            ids.shape == positions.shape == (self.popsize, self.decode_steps)
            and (positions[:, 0] == 0).all()
            and ((positions[:, 1:] == positions[:, :-1] + 1) | (positions[:, 1:] == 0)).all()
            and (ids >= 0).all()
            and (ids < self._sizes["vocab"]).all()
        )
        lanes = lanes_to_check(positions, self._checked_lanes, seed)
        emitted = emitted_tokens(ids[lanes], positions[lanes], self.env.prompt_length, self.env.max_episode_steps)
        lane_reference = LaneReference(self._reference, self._sizes, policy)
        if control is None:

            @jax.jit
            def replay(batch, ids, positions, lanes):
                return stepwise_logits(
                    policy, batch, ids, positions=positions, lanes=lanes, compute_dtype=self.compute_dtype
                )

            logits, routes = replay(batch, jnp.asarray(ids), jnp.asarray(positions), jnp.asarray(lanes))
            logits, routes = np.asarray(logits), np.asarray(routes)  # (c, T, V), (T, sparse, c, k)
        else:
            stand_in = [lane_reference(batch, lane, ids[lane], positions[lane], None, weights=control) for lane in lanes]
            logits = np.stack([found["free_logits"] for found in stand_in])
            routes = np.stack([np.stack(found["free_routes"], axis=1) for found in stand_in], axis=2)
            where, _ = emitted
            emitted = where, np.argmax(logits, axis=-1)[where]
        found = reference_comparison(lane_reference, batch, lanes, ids, positions, logits, routes, emitted)
        error, flips, pairs = found["relative_rms_error"][0], found["flips"], found["pairs"]
        flips_allowed = ROUTE_FLIP_SHARE * pairs + 3.0 * math.sqrt(ROUTE_FLIP_SHARE * pairs)
        tokens = max(found["tokens"], 1)

        def enough(count, share):
            """``count`` of the emitted tokens is ``share`` of them, less
            three standard deviations of a binomial's room (a rehearsal emits
            a handful of tokens, the cell some 1,800)."""
            return bool(count >= share * tokens - 3.0 * math.sqrt(share * (1.0 - share) * tokens))

        return {
            "record": {
                "ok": record_ok and found["tokens"] > 0,
                "lanes": [int(lane) for lane in lanes],
                "episodes_begun_midway": int(np.sum(positions[lanes][:, 1:] == 0)),
                "emitted_tokens": found["tokens"],
                "lane_tokens_replayed_agreed": found["by_lane"],
            },
            "replay": {
                "ok": enough(found["replayed"], TOKENS_REPLAYED),
                "tokens_replayed_share": found["replayed"] / tokens,
                "bound": TOKENS_REPLAYED,
            },
            "logits": {
                "ok": bool(found["finite"] and error <= LOGIT_RTOL),
                "relative_rms_error": error,
                "bound": LOGIT_RTOL,
                "lanes": len(lanes),
                "logits_per_lane": int(logits.shape[1] * logits.shape[2]),
            },
            "routes": {
                "ok": bool(flips <= flips_allowed),
                "top_k_sets_differ_share": flips / pairs if pairs else 0.0,
                "bound": ROUTE_FLIP_SHARE,
                "pairs_that_differ": flips,
                "pairs_allowed": flips_allowed,
                "position_layer_pairs": pairs,
            },
            "tokens": {
                "ok": enough(found["agreed"], TOKENS_AGREED),
                "tokens_agreed_share": found["agreed"] / tokens,
                "bound": TOKENS_AGREED,
            },
        }


def lanes_to_check(positions, count, seed):
    """``count`` lanes drawn with ``seed``: of those whose record shows an
    episode begun midway (an early end, so a reset), a quarter of ``count`` at
    most come first."""
    rng = np.random.default_rng(int(seed))
    midway = np.flatnonzero((positions[:, 1:] == 0).any(axis=1))
    rest = np.setdiff1d(np.arange(positions.shape[0]), midway)
    first = rng.permutation(midway)[: count // 4]
    return np.sort(np.concatenate([first, rng.permutation(rest)[: count - len(first)]])).astype(np.int32)


def emitted_tokens(ids, positions, prompt_length, max_episode_steps):
    """Where a lane's record shows a token it emitted, and the token: a mask
    ``(n, T)`` over the steps whose action is known, and the tokens at the
    mask's places. The action of step ``s`` is the id consumed at ``s + 1``
    where the episode goes on past its prompt; where the next step begins an
    episode before the cap, the action was id 0 (it ends an episode). The
    last step's action, and one at the cap, were not consumed: unknown."""
    here, after = positions[:, :-1], positions[:, 1:]
    goes_on = (after == here + 1) & (after >= prompt_length)
    ended = (after == 0) & (here + 1 >= prompt_length) & (here + 1 < max_episode_steps)
    where = np.zeros(positions.shape, bool)
    where[:, :-1] = goes_on | ended
    tokens = np.where(ended, 0, ids[:, 1:])
    return where, tokens[where[:, :-1]]


def lane_leaf(center, factor, row):
    """One lane's parameter leaf, written out: ``W_c + B diag(z) A^T`` (the
    factors' layouts are ``tools/lowrank.py:DeltaFactor``'s)."""
    a, b = factor.a, factor.b
    with jax.default_matmul_precision("highest"):
        if a.ndim == 3:  # stacked experts (expert, in, out)
            return center + jnp.einsum("eim,m,eom->eio", a, row, b)
        if a.shape[0] == 0:  # a vector: b holds its directions
            return center + b @ row
        return center + jnp.einsum("om,m,im->oi", b, row, a)


class LaneReference:
    """The plain reference ``ref`` on one lane of a trunk-delta batch: the
    lane's weights are written out and run layer by layer (one layer's
    float32 weights of one lane at a time, so that it fits)."""

    def __init__(self, ref, sizes, policy):
        def lane_piece(select):
            """The float32 parameters ``select(tree)`` of one lane."""

            @jax.jit
            def piece(center, factors, row):
                return jax.tree_util.tree_map(
                    lambda c, f: lane_leaf(c, f, row),
                    select(policy.unravel(center)),
                    select(factors),
                    is_leaf=lambda x: isinstance(x, DeltaFactor),
                )

            return piece

        self._sizes = sizes
        self._ends = lane_piece(lambda t: {k: t[k] for k in ("embed", "final_norm", "head")})
        self._layers = [lane_piece(lambda t, at=at: t["layers"][at]) for at in range(len(sizes["layers"]))]
        self._run_layer = [
            jax.jit(lambda p, h, forced, positions, index=index: ref.layer(p, h, index, sizes, forced, positions))
            for index in sizes["layers"]
        ]
        self._run_embed = jax.jit(lambda p, ids: ref.embed(p, ids, sizes))
        self._run_head = jax.jit(lambda p, h: ref.head(p, h, sizes))

    def __call__(self, batch, lane, ids, positions, routes, weights=None):
        """The lane's reference over the ids ``(T,)`` it consumed at the
        ``positions`` ``(T,)`` of its episodes, twice through the same
        weights: going on with ``routes`` ``(T, sparse layers, k)``, the
        experts the system chose, so that the logits compare arithmetic
        (``logits`` ``(T, V)`` float64 and ``own_routes`` ``[(T, k), ...]``,
        what the reference's router chose on the way; both None where
        ``routes`` is None), and going on with its own (``free_logits``,
        ``free_routes``). ``weights``: a function applied to every leaf first
        (a lower precision's rounding)."""
        lower = (lambda tree: tree) if weights is None else (lambda tree: jax.tree_util.tree_map(weights, tree))
        row = batch.coeffs[lane]
        outer = lower(self._ends(batch.center, batch.factors, row))
        free = self._run_embed(outer, ids)
        forced = None if routes is None else free
        own_routes, free_routes = [], []
        for at, index in enumerate(self._sizes["layers"]):
            dense = index < self._sizes["num_dense_layers"]
            params = lower(self._layers[at](batch.center, batch.factors, row))
            if forced is not None:
                forced, own = self._run_layer[at](
                    params, forced, None if dense else routes[:, len(own_routes)], positions
                )
                if own is not None:
                    own_routes.append(np.asarray(own))
            free, own = self._run_layer[at](params, free, None, positions)
            if own is not None:
                free_routes.append(np.asarray(own))
        return {
            "logits": None if forced is None else np.asarray(self._run_head(outer, forced), dtype=np.float64),
            "own_routes": None if forced is None else own_routes,
            "free_logits": np.asarray(self._run_head(outer, free), dtype=np.float64),
            "free_routes": free_routes,
        }


def sets_that_differ(chosen, other):
    """Positions whose top-k SETS differ between two ``(T, k)`` id arrays."""
    return int(np.sum(np.any(np.sort(chosen, axis=-1) != np.sort(np.asarray(other), axis=-1), axis=-1)))


def reference_comparison(lane_reference, batch, lanes, ids, positions, logits, routes, emitted=None, cuts=(0,)):
    """The plain reference against what the stepwise forward gave for
    ``lanes`` of the trunk-delta ``batch``: ``ids`` and ``positions`` ``(N,
    T)`` of all lanes, ``logits`` ``(c, T, V)`` and ``routes`` ``(T, sparse
    layers, c, k)`` of the ``c`` checked ones, ``emitted`` as
    ``emitted_tokens`` gives it for them. Returns the relative RMS error of
    the logits from position ``cut`` on for each of ``cuts``, the (position,
    sparse layer) pairs whose top-k sets differ between the system's router
    and the reference's (``flips`` of ``pairs``), of the emitted ``tokens``
    how many the given logits put first (``replayed``) and how many the
    reference on its own routes puts first (``agreed``), and whether every
    logit is finite."""
    sums = [[0.0, 0.0] for _ in cuts]
    flips = pairs = 0
    by_lane = []  # [lane, emitted tokens, of them replayed, of them agreed]
    finite = True
    given = 0
    for at, lane in enumerate(lanes):
        found = lane_reference(batch, lane, ids[lane], positions[lane], routes[:, :, at])
        for layer, own in enumerate(found["own_routes"]):
            flips += sets_that_differ(own, routes[:, layer, at])
            pairs += own.shape[0]
        got, want = np.asarray(logits[at], dtype=np.float64), found["logits"]
        finite = finite and bool(np.isfinite(got).all())
        for total, cut in zip(sums, cuts):
            total[0] += float(np.sum((got[cut:] - want[cut:]) ** 2))
            total[1] += float(np.sum(want[cut:] ** 2))
        if emitted is not None:
            where = emitted[0][at]
            said = emitted[1][given : given + int(where.sum())]
            given += int(where.sum())
            by_lane.append([
                int(lane),
                len(said),
                int(np.sum(np.argmax(got, axis=-1)[where] == said)),
                int(np.sum(np.argmax(found["free_logits"], axis=-1)[where] == said)),
            ])
    return {
        "relative_rms_error": [math.sqrt(e / n) if n else None for e, n in sums],
        "flips": flips,
        "pairs": pairs,
        "tokens": sum(row[1] for row in by_lane),
        "replayed": sum(row[2] for row in by_lane),
        "agreed": sum(row[3] for row in by_lane),
        "by_lane": by_lane,
        "finite": finite,
    }


def build(files, config, workload, seed, scale):
    return Session(files, config, workload, seed, scale)
