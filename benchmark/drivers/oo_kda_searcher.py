"""Driver: evolution strategies on a hybrid linear-attention language model,
through the path a researcher calls: ``VecNE(env=TokenCopyEnv(...),
network=KimiLinearDecoder(...), eval_mode="budget")`` + ``PGPE(...,
lowrank_rank=("trunk_delta", k))`` + ``searcher.step()``, one whole generation
per call. Every lane decodes ``decode_steps`` tokens under its own perturbed
weights (a seeded prompt fed one token a step, then its own greedy tokens);
its state is four gated delta-rule (KDA) layers' windows and matrix states,
REWRITTEN whole every step, beside one latent-attention layer's cache.

The session protocol, the measured path (``generation`` / ``block`` /
``mark`` / ``policy_counters``) and the generic pieces of the comparison
(``lanes_to_check``, ``emitted_tokens``, ``LaneReference``,
``sets_that_differ``) are ``drivers/oo_lm_searcher.py``'s, imported; what
differs is the network that is built, a reference layer that hands back a
KDA layer's states beside its routes, and the bounds below.

The configuration file holds the published model's keys; ``num_experts``,
``vocab_size`` and ``num_hidden_layers`` there are what THIS chip holds (they
are under ``reduced``; ``published`` has the model's own), and ``scale`` may
shrink popsize, steps, layers and rows for the CPU rehearsal.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

# the library's hybrid linear-attention decoder: a checkout without it cannot
# run this cell and fails here, before any compile
from evotorch_tpu.neuroevolution.net.decoder import KimiLinearDecoder, stepwise_logits

from benchmark.drivers import oo_lm_searcher as lm
from evotorch_tpu.algorithms import PGPE
from evotorch_tpu.envs.tokens import TokenCopyEnv
from evotorch_tpu.neuroevolution import VecNE

MODEL_KEYS = (
    "hidden_size", "num_attention_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
    "qk_rope_head_dim", "v_head_dim", "mla_use_nope", "linear_attn_config", "intermediate_size",
    "moe_intermediate_size", "num_experts_per_token", "num_shared_experts", "first_k_dense_replace",
    "moe_layer_freq", "moe_renormalize", "moe_router_activation_func", "routed_scaling_factor",
    "num_expert_group", "topk_group", "rope_theta", "rope_scaling", "rms_norm_eps", "tie_word_embeddings",
)

# The comparison with the reference (what the TIMED program emitted on
# ``checked_lanes`` lanes drawn with ``--seed``, replayed teacher-forced
# through the population-wide stepwise forward over ALL lanes, against the
# plain float32 "highest" whole-sequence reference on each checked lane's
# written-out weights, going on with the experts the system chose; the
# routes counted apart, as in the other expert cells), and one figure more
# that is the timed program's ALONE: what its lanes' KDA states held when the
# lanes last ended an episode (``kda_ended_state`` of
# ``VecNE.last_policy_report``: the engine's own carry, after its own steps
# and resets) against the reference's recurrence at that position. A lane is
# a lane: each figure of errors is a lane's OWN relative RMS error,
# root-mean-squared over the checked lanes. The controls, which have to come
# out NOT ok, take the program's place: the reference with int8-rounded
# weights (scaled to the largest of a leaf), the nearest precision below
# bfloat16, and the reference without the delta rule's correction
# (``EQUATION_CONTROLS``; ``scripts/lm_ring_wrap_check.py --cell
# kimi_linear_ep32.decode256 --control int8,bfloat16,no_correction,bf16_state
# --seeds 4``). PERF.md section 6, PR 42, has every reading.
#
# READINGS (my chip runs, PR 42, TPU v5 lite; 32 lanes x 256 positions a
# reading, about 7,100 emitted tokens, 32,768 (position, sparse layer)
# pairs; "system" is thirteen draws of lanes over two calls, "int8" two,
# "bf16" (the reference with its matrices rounded so) and "no correction"
# one each). Each bound lies between the system's worst reading and int8's,
# at their geometric mean:
# - LOGIT_RTOL: relative RMS error of the replay's logits against the
#   reference's under the system's routes. The system 2.712e-2 to 2.820e-2;
#   bf16 weights alone 1.14e-2; int8 9.18e-2 and 9.79e-2; no correction
#   0.67. A changed equation reads 0.64 to 1.3 at a tiny size in float32,
#   where the unchanged ones read 2.4e-6 (tier-1 holds five on the CPU). The bound: 1.8x above
#   the system's largest, 1.8x below int8.
# - ROUTE_FLIP_SHARE: (position, sparse layer) pairs whose top-8 SETS differ
#   between the system's router and the reference's (both float32, the
#   system's on bf16 hidden states). The system 20.7% to 21.4% (top 8 of 256
#   has more near-ties at its edge than GLM's top 4 of 64, which reads 6-7%);
#   bf16 weights alone 9.4%; int8 56.0%, 56.8%; no correction 98.3%. Held as a COUNT
#   with three binomial standard deviations of room: 1.6x above the system,
#   1.6x below int8.
# - TOKENS_REPLAYED: emitted tokens the replay's argmax reproduces. The
#   system 97.5% to 98.3% (two compilations of one forward round bf16
#   elsewhere); a control's replay is its own (100%), so the bound is taken
#   as the other expert cells take it: the geometric mean of the largest
#   shares NOT reproduced by the replay (2.5%) and by the float32 reference on
#   its own routes (7.7%), 4.4%, held as a count with three binomial standard
#   deviations of room. It ties the logits that are compared to the TIMED
#   program: a wrong token, reset or cast in the engine reads near zero.
# - TOKENS_AGREED: emitted tokens that the reference, going on with its OWN
#   routes, also puts first. The system 92.3% to 93.3%; bf16 weights alone
#   97.0%; int8 80.3%, 79.2%; no correction 11.3%. The bound is the geometric mean of
#   the shares that differ (7.7% and 19.7%: 12.3%), held as a count like the
#   one above: 1.6x above the system's largest, 1.6x below int8.
# - STATE_RTOL: relative RMS error of the timed program's ended KDA states
#   (each state summed over its key axis: 4 layers x 32 heads x 128 numbers a
#   lane) against the reference's recurrence. The system 1.487e-2 to
#   1.540e-2; bf16 weights alone 0.62e-2; int8 4.91e-2, 5.18e-2; no correction 4.65.
#   1.8x above the system's largest, 1.8x below int8. It is here for what no
#   other figure sees, a fault in the recurrent state of the TIMED program: a
#   state the engine left zeroed or unchanged reads 1.0, one it did not reset
#   reads the episode before.
# int8 comes out not ok by four of the five, the reference without the
# correction by all five.
TOKENS_REPLAYED = 0.96
LOGIT_RTOL = 5.0e-2
ROUTE_FLIP_SHARE = 0.34
TOKENS_AGREED = 0.88
STATE_RTOL = 2.7e-2
#: what a control changes in the reference's sizes (``reference/kimi_linear_decoder.py``)
EQUATION_CONTROLS = {"no_correction": {"correction": False}, "bf16_state": {"stored": "bfloat16"}}


class KdaLaneReference(lm.LaneReference):
    """``LaneReference`` for a reference whose ``layer`` hands back a KDA
    layer's states (summed over the key axis, at every position) beside its
    routes: both passes keep them."""

    def __call__(self, batch, lane, ids, positions, routes, weights=None):
        lower = (lambda tree: tree) if weights is None else (lambda tree: jax.tree_util.tree_map(weights, tree))
        row = batch.coeffs[lane]
        outer = lower(self._ends(batch.center, batch.factors, row))
        free = self._run_embed(outer, ids)
        forced = None if routes is None else free
        own_routes, free_routes, sums, free_sums = [], [], [], []
        for at, index in enumerate(self._sizes["layers"]):
            params = lower(self._layers[at](batch.center, batch.factors, row))
            if forced is not None:
                force = None if index < self._sizes["num_dense_layers"] else routes[:, len(own_routes)]
                forced, own, summed = self._run_layer[at](params, forced, force, positions)
                own_routes += [] if own is None else [np.asarray(own)]
                sums += [] if summed is None else [np.asarray(summed)]
            free, own, summed = self._run_layer[at](params, free, None, positions)
            free_routes += [] if own is None else [np.asarray(own)]
            free_sums += [] if summed is None else [np.asarray(summed)]
        return {
            "logits": None if forced is None else np.asarray(self._run_head(outer, forced), dtype=np.float64),
            "own_routes": None if forced is None else own_routes,
            "sums": None if forced is None else sums,
            "free_logits": np.asarray(self._run_head(outer, free), dtype=np.float64),
            "free_routes": free_routes,
            "free_sums": free_sums,
        }


class Session(lm.Session):
    def __init__(self, files, config, workload, seed, scale):
        traffic = workload["traffic"]
        self.popsize = int(scale["popsize"])
        self.decode_steps = int(scale["decode_steps"])
        self.compute_dtype = lm.DTYPES[config["compute_dtype"]]
        self._checked_lanes = int(scale["checked_lanes"])
        # ``traffic.search_seed``: as in the other decoder cells the search
        # starts from a seed fixed in the workload file (the grouped expert
        # product's time follows the routing), every run of a commit does the
        # same work, and ``--seed`` draws the lanes that the comparison checks
        search_seed = traffic.get("search_seed")
        search_seed = int(seed if search_seed is None else search_seed)
        self._reference = files.module_at(config["reference"]["forward"])
        self._sizes = self._reference.sizes(config, scale)
        first, past = config["experts_held"]
        self.network = KimiLinearDecoder(
            **{key: config[key] for key in MODEL_KEYS},
            num_experts=int(config["published"]["num_experts"]),
            vocab_size=int(config["published"]["vocab_size"]),
            num_hidden_layers=int(config["published"]["num_hidden_layers"]),
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
            # at 602M parameters every vector of the solution's length is 2.4 GB
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
        self.searcher = PGPE(
            self.vecne,
            popsize=self.popsize,
            lowrank_rank=("trunk_delta", int(config["trunk_delta_rank"])),
            # the seeded initial trunk stands in for a checkpoint
            center_init=jax.jit(self.vecne.policy.init_parameters)(jax.random.key(search_seed)),
            stdev_init=float(recipe["stdev_init"]),
            center_learning_rate=float(recipe["center_learning_rate_over_radius"]) * radius,
            stdev_learning_rate=float(recipe["stdev_learning_rate"]),
            optimizer=recipe["optimizer"],
            optimizer_config={"max_speed": float(recipe["max_speed_over_radius"]) * radius},
            ranking_method=recipe["ranking_method"],
        )
        self.problem = lm._Lowers(self.vecne, self.searcher)
        self.devices = jax.devices()[: int(workload["chips"])]
        interactions = self.popsize * self.decode_steps  # budget: every lane-step counts
        self.per_call = {
            "generations": 1,
            "interactions": interactions,
            "interactions_max": interactions,
            "episodes": None,
            "telemetry_lag": 1,
        }
        # for the per-layer readers (benchmark/harness/kda_floors.py)
        self.kda_sizes = self._sizes
        self._lane_reference = KdaLaneReference(self._reference, self._sizes, self.vecne.policy)

    # -- the comparison with the plain reference -----------------------------
    def reference_checks(self, seed, control=None):
        """The five figures above for the evaluation in hand (the last of the
        warm-up: ``searcher.population`` is what it ran). ``control``: a
        function that rounds a weight leaf to a lower precision, or a name of
        ``EQUATION_CONTROLS``; the REFERENCE with its weights rounded so, or
        its equation changed so, going on with its own routes, then takes the
        program's place (its logits, its routes, its first tokens, its
        states)."""
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
        lanes = lm.lanes_to_check(positions, self._checked_lanes, seed)
        where, said = lm.emitted_tokens(ids[lanes], positions[lanes], self.env.prompt_length, self.env.max_episode_steps)

        def last_end(lane, logits):
            """The step after which ``lane`` last ended an episode: the last
            one where it ran into the cap there or emitted id 0 past its
            prompt there (the record cannot show the last step's action:
            ``logits``, the lane's ``(T, V)``, say it), else the one before
            its last episode began (None: it ended none)."""
            last = positions[lane][-1] + 1
            if last >= self.env.max_episode_steps or (last >= self.env.prompt_length and np.argmax(logits[-1]) == 0):
                return self.decode_steps - 1
            begun = np.flatnonzero(positions[lane][1:] == 0)
            return int(begun[-1]) if len(begun) else None

        def at_end(sums, lane, logits):
            """A lane's KDA states where it last ended an episode, ``(KDA
            layers, heads, head_dim)``, from the reference's states at every
            position."""
            sums, end = np.stack(sums).astype(np.float64), last_end(lane, logits)
            return np.zeros_like(sums[:, 0]) if end is None else sums[:, end]

        if control is None:

            @jax.jit
            def replay(batch, ids, positions, lanes):
                return stepwise_logits(
                    policy, batch, ids, positions=positions, lanes=lanes, compute_dtype=self.compute_dtype
                )

            logits, routes = replay(batch, jnp.asarray(ids), jnp.asarray(positions), jnp.asarray(lanes))
            logits, routes = np.asarray(logits), np.asarray(routes)  # (c, T, V), (T, sparse, c, k)
            # what the TIMED program's KDA states held when the checked lanes last ended an episode
            ended = np.asarray(report["kda_ended_state"][jnp.asarray(lanes)].astype(jnp.float32), dtype=np.float64)
        else:
            if isinstance(control, str):
                stand_in = KdaLaneReference(self._reference, {**self._sizes, **EQUATION_CONTROLS[control]}, policy)
                weights = None
            else:
                stand_in, weights = self._lane_reference, control
            found = [stand_in(batch, lane, ids[lane], positions[lane], None, weights=weights) for lane in lanes]
            logits = np.stack([one["free_logits"] for one in found])
            routes = np.stack([np.stack(one["free_routes"], axis=1) for one in found], axis=2)
            ended = np.stack([at_end(one["free_sums"], lane, one["free_logits"]) for one, lane in zip(found, lanes)])
            said = np.argmax(logits, axis=-1)[where]
        # lane by lane: [lane, emitted tokens, of them replayed, of them agreed, the logits' relative RMS error,
        # the ended states' relative RMS error]
        by_lane, given, flips, pairs = [], 0, 0, 0
        for at, lane in enumerate(lanes):
            found = self._lane_reference(batch, lane, ids[lane], positions[lane], routes[:, :, at])
            for layer, own in enumerate(found["own_routes"]):
                flips += lm.sets_that_differ(own, routes[:, layer, at])
                pairs += own.shape[0]
            got = np.asarray(logits[at], dtype=np.float64)
            errors = {}
            for name, mine, theirs in (
                ("logits", got, found["logits"]),
                ("state", ended[at], at_end(found["sums"], lane, got)),
            ):
                norm = float(np.sum(theirs**2))
                errors[name] = math.sqrt(float(np.sum((mine - theirs) ** 2)) / norm) if norm else float("inf")
            mine = said[given : given + int(where[at].sum())]
            given += len(mine)
            by_lane.append([
                int(lane),
                len(mine),
                int(np.sum(np.argmax(got, axis=-1)[where[at]] == mine)),
                int(np.sum(np.argmax(found["free_logits"], axis=-1)[where[at]] == mine)),
                errors["logits"],
                errors["state"],
            ])
        # a lane is a lane: each one's own relative error, root-mean-squared over the checked lanes
        error, state_error = (math.sqrt(sum(row[i] ** 2 for row in by_lane) / len(by_lane)) for i in (4, 5))
        emitted, replayed, agreed = (sum(row[i] for row in by_lane) for i in (1, 2, 3))
        tokens = max(emitted, 1)
        flips_allowed = ROUTE_FLIP_SHARE * pairs + 3.0 * math.sqrt(ROUTE_FLIP_SHARE * pairs)

        def enough(count, share):
            """``count`` of the emitted tokens is ``share`` of them, less
            three standard deviations of a binomial's room (a rehearsal emits
            a handful of tokens, the cell some 7,000)."""
            return bool(count >= share * tokens - 3.0 * math.sqrt(share * (1.0 - share) * tokens))

        counters = self.policy_counters() or {}
        return {
            "record": {
                "ok": record_ok and emitted > 0,
                "lanes": [int(lane) for lane in lanes],
                "episodes_begun_midway": int(np.sum(positions[lanes][:, 1:] == 0)),
                "episodes_begun_midway_all_lanes": int(np.sum(positions[:, 1:] == 0)),
                "emitted_tokens": emitted,
                "distinct_tokens_emitted": int(len(np.unique(said))),
                "lane_tokens_replayed_agreed_errors": by_lane,
                "kda_state_updates": counters.get("kda_state_updates"),
                "kda_lane_resets": counters.get("kda_lane_resets"),
            },
            "replay": {
                "ok": enough(replayed, TOKENS_REPLAYED),
                "tokens_replayed_share": replayed / tokens,
                "bound": TOKENS_REPLAYED,
            },
            "logits": {
                "ok": bool(np.isfinite(logits).all() and error <= LOGIT_RTOL),
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
                "ok": enough(agreed, TOKENS_AGREED),
                "tokens_agreed_share": agreed / tokens,
                "bound": TOKENS_AGREED,
            },
            "state": {
                "ok": bool(np.isfinite(ended).all() and state_error <= STATE_RTOL),
                "ended_state_relative_rms_error": state_error,
                "bound": STATE_RTOL,
                "numbers_per_lane": int(ended[0].size),
            },
        }


def build(files, config, workload, seed, scale):
    return Session(files, config, workload, seed, scale)
