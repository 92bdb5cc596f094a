"""Driver: evolution strategies on a state-space hybrid language model,
through the path a researcher calls: ``VecNE(env=TokenCopyEnv(...),
network=GraniteMoeHybridDecoder(...), eval_mode="budget")`` + ``PGPE(...,
lowrank_rank=("trunk_delta", k))`` + ``searcher.step()``, one whole generation
per call. Every lane decodes ``decode_steps`` tokens under its own perturbed
weights (a seeded prompt fed one token a step, then its own greedy tokens);
its state is nine Mamba-2 layers' windows and matrix states, REWRITTEN whole
every step, beside one attention layer's key/value cache.

The session protocol, the measured path (``generation`` / ``block`` /
``mark`` / ``policy_counters``) and the generic pieces of the comparison
(``lanes_to_check``, ``emitted_tokens``, ``LaneReference``) are
``drivers/oo_lm_searcher.py``'s, imported; what differs is the network that is
built, the tied ends of the lane's written-out weights, and the bounds below.
No layer of this family routes: the figures that have a meaning without routes
decide, with one more on the lanes' recurrent state as the timed program left
it, and the reference runs once a lane.

The configuration file holds the published model's keys; ``vocab_size`` and
``num_hidden_layers`` there are what THIS chip holds (they are under
``reduced``; ``published`` has the model's own), and ``scale`` may shrink
popsize, steps, layers and rows for the CPU rehearsal.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

# the library's state-space hybrid decoder: a checkout without it cannot run
# this cell and fails here, before any backend or compile
from evotorch_tpu.neuroevolution.net.decoder import GraniteMoeHybridDecoder, stepwise_logits

from benchmark.drivers import oo_lm_searcher as lm
from evotorch_tpu.algorithms import PGPE
from evotorch_tpu.envs.tokens import TokenCopyEnv
from evotorch_tpu.neuroevolution import VecNE

MODEL_KEYS = (
    "hidden_size", "num_attention_heads", "num_key_value_heads", "shared_intermediate_size", "layer_types",
    "mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_n_groups", "mamba_d_conv", "mamba_expand",
    "mamba_conv_bias", "mamba_proj_bias", "num_local_experts", "attention_bias", "attention_multiplier",
    "embedding_multiplier", "residual_multiplier", "logits_scaling", "position_embedding_type",
    "tie_word_embeddings", "rms_norm_eps",
)

# The comparison with the reference is ``drivers/oo_lm_searcher.py``'s (what
# the TIMED program emitted on ``checked_lanes`` lanes drawn with ``--seed``,
# replayed teacher-forced through the population-wide stepwise forward over
# ALL lanes, against the plain float32 "highest" whole-sequence reference on
# each checked lane's written-out weights: a causal convolution over the
# sequence and the recurrence in a scan where the system carries a window and
# a matrix state from step to step in bfloat16), and one figure more that is
# the timed program's ALONE: what its lanes' matrix states held when the lanes
# last ended an episode (``ssm_ended_state`` of ``VecNE.last_policy_report``:
# the engine's own carry, after the engine's own steps and resets; the replay
# has no part in it) against the reference's recurrence at that position.
# 32 lanes are checked, and a lane is a lane: each figure of errors is a
# lane's OWN relative RMS error, root-mean-squared over the checked lanes (a
# figure pooled by energy follows the few lanes with large logits: over 8
# lanes it read 0.75e-2 to 3.11e-2 from draw to draw, and int8 6.8e-2 at 8
# lanes but 3.0e-2 to 3.9e-2 at 32). The control is the reference with
# int8-rounded weights (scaled to the largest of a leaf) in the program's
# place, the nearest precision below bfloat16, which has to come out NOT ok
# (``scripts/lm_ring_wrap_check.py --cell granite4_h_micro_pp4.decode256
# --control int8,bfloat16``). PERF.md section 6, PR 34, has every reading.
#
# READINGS (my chip runs, PR 34, TPU v5 lite; 32 lanes x 256 positions a
# reading, 7,168 emitted tokens; "system" is 14 draws of lanes, "int8" three,
# "bf16" one: the reference with its matrices rounded so in the program's
# place):
# - LOGIT_RTOL: the relative RMS error of the replay's logits against the
#   reference's. The system 2.28e-2 to 2.75e-2 (a lane's own error 0.42e-2 to
#   5.0e-2 over 448 lanes, median 2.46e-2); int8 7.10e-2, 7.26e-2 and 7.74e-2
#   (its lanes 1.05e-2 to 19.7e-2, median 7.1e-2); bf16 weights alone 0.84e-2.
#   A fifth to three tenths of the system's error is bfloat16 STORAGE of the
#   state (the same lanes replayed with float32 states, windows and cache read
#   1.73e-2 where they read 2.19e-2, 1.90e-2 where 2.66e-2, 1.96e-2 where
#   2.62e-2, pooled over 8 lanes): a lane repeats one token, below, so a slow
#   head's state grows toward a fixed point whose increments fall under
#   bfloat16's half-ulp. A changed equation reads 0.9e-2 to 0.9 at a tiny size
#   in float32, where the unchanged ones read 1e-6 (tier-1 holds each of seven
#   on the CPU). The bound is the geometric mean of 2.75e-2 and 7.10e-2: 1.6x
#   above the system's largest, 1.6x below int8's smallest (int8 is 2.6x the
#   system, not the 4x of the other decoder cells: rounding the weights moves
#   a lane that sits at a fixed point less than it moves one that wanders).
#   THIS figure tells int8 from bfloat16.
# - STATE_RTOL: the relative RMS error of the timed program's ended states
#   (each matrix state summed over its last axis: 9 layers x 64 heads x 64
#   numbers a lane) against the reference's. The system 5.43e-2 to 6.12e-2 (a
#   lane 4.1e-2 to 11.2e-2); bf16 weights alone 0.84e-2: the rest is bfloat16
#   storage near the fixed point, which the state shows undiluted; int8
#   7.68e-2, 7.83e-2, 7.93e-2: this figure cannot tell int8 from bfloat16 and
#   is not asked to. It is here for what no other figure sees, a fault in the
#   recurrent state of the TIMED program: a state the engine left zeroed or
#   unchanged reads 1.0, one it did not reset reads the episode before (tier-1
#   plants each of the three in the engine's carry at a small size: 0.25 or
#   more where the sound engine reads 1e-6). The bound is the geometric mean
#   of the system's largest and 1.0: 4x of room each way.
# - TOKENS_REPLAYED, TOKENS_AGREED: shares of the emitted tokens that the
#   replay's argmax reproduces, and that the reference puts first on its own.
#   With seeded weights a TIED model puts the token it just read first: its
#   embedding row, times 12, is in the residual and is the head's row too (a
#   logit of 1.0 to 1.2 against a runner-up of 0.4 to 0.5 among 25,088), so
#   every lane repeats its prompt's last token and no episode ends early. The
#   replay read 100% in all 14 draws, the reference 99.96% to 100%, bf16 100%,
#   int8 99.96% to 100%: no bound can lie between readings that are equal.
#   (Rows drawn twelve times smaller end the parroting and not the
#   repetition: a lane then settles on one to three tokens of its own, still
#   no id 0 among 57,344 tokens, and int8's logits come within 1.3x of the
#   system's. Not kept: PERF.md section 6.) The two stay as the tie between
#   the logits that are compared and the TIMED program (a wrong token, an
#   argmax or an integer cast gone wrong in the engine reads near zero), at
#   99% with three binomial standard deviations of room; int8 comes out not
#   ok by the logits alone.
LOGIT_RTOL = 4.4e-2
STATE_RTOL = 0.25
TOKENS_REPLAYED = 0.99
TOKENS_AGREED = 0.99


class TiedLaneReference(lm.LaneReference):
    """``LaneReference`` for a model whose head is its embedding: the ends
    written out for a lane are the embedding's rows and the final norm."""

    def __init__(self, ref, sizes, policy):
        super().__init__(ref, sizes, policy)

        @jax.jit
        def ends(center, factors, row):
            tree = policy.unravel(center)
            return {key: lm.lane_leaf(tree[key], factors[key], row) for key in ("embed", "final_norm")}

        self._ends = ends


class Session(lm.Session):
    def __init__(self, files, config, workload, seed, scale):
        traffic = workload["traffic"]
        self.popsize = int(scale["popsize"])
        self.decode_steps = int(scale["decode_steps"])
        self.compute_dtype = lm.DTYPES[config["compute_dtype"]]
        self._checked_lanes = int(scale["checked_lanes"])
        # ``traffic.search_seed``: as in the other decoder cells the search
        # starts from a seed fixed in the workload file, every run of a commit
        # does the same work, and ``--seed`` draws the lanes that the
        # comparison with the reference checks
        search_seed = traffic.get("search_seed")
        search_seed = int(seed if search_seed is None else search_seed)
        self._reference = files.module_at(config["reference"]["forward"])
        self._sizes = self._reference.sizes(config, scale)
        self.network = GraniteMoeHybridDecoder(
            **{key: config[key] for key in MODEL_KEYS},
            vocab_size=int(config["published"]["vocab_size"]),
            max_positions=self.decode_steps,
            layers_held=self._sizes["layers"],
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
            # at 798M parameters every vector of the solution's length is 3.2 GB
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
        # for the per-layer readers (benchmark/harness/ssm_floors.py)
        self.ssm_sizes = self._sizes
        self._lane_reference = TiedLaneReference(self._reference, self._sizes, self.vecne.policy)

    # -- the comparison with the plain reference -----------------------------
    def reference_checks(self, seed, control=None):
        """The four figures above for the evaluation in hand (the last of the
        warm-up: ``searcher.population`` is what it ran). ``control``: a
        function that rounds a weight leaf to a lower precision; the REFERENCE
        with its weights rounded so then takes the program's place (its
        logits, its first tokens, its states)."""
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

        def last_end(lane):
            """The step after which ``lane`` last ended an episode: the last
            one where it ran into the cap there, else the one before its last
            episode began (None: it ended none)."""
            if positions[lane][-1] + 1 >= self.env.max_episode_steps:
                return self.decode_steps - 1
            begun = np.flatnonzero(positions[lane][1:] == 0)
            return int(begun[-1]) if len(begun) else None

        def reference(lane, weights=None):
            """The lane's float64 reference logits ``(T, V)`` (no layer
            routes, so going on with its own routes is all there is) and its
            Mamba-2 layers' matrix states where the lane last ended an
            episode, summed over their last axis ``(layers, heads,
            head_dim)``: the reference's ``layer`` hands the sums of every
            position back where the other families' hand back their routes."""
            found = self._lane_reference(batch, lane, ids[lane], positions[lane], None, weights=weights)
            sums, end = np.stack(found["free_routes"]).astype(np.float64), last_end(lane)
            return found["free_logits"], np.zeros_like(sums[:, 0]) if end is None else sums[:, end]

        if control is not None:
            stand_in = [reference(lane, control) for lane in lanes]
            logits = np.stack([found[0] for found in stand_in])
            ended = np.stack([found[1] for found in stand_in])
            said = np.argmax(logits, axis=-1)[where]
        else:

            @jax.jit
            def replay(batch, ids, positions, lanes):
                return stepwise_logits(
                    policy, batch, ids, positions=positions, lanes=lanes, compute_dtype=self.compute_dtype
                )[0]

            logits = np.asarray(replay(batch, jnp.asarray(ids), jnp.asarray(positions), jnp.asarray(lanes)))
            # what the TIMED program's matrix states held when the checked lanes last ended an episode
            ended = np.asarray(report["ssm_ended_state"][jnp.asarray(lanes)].astype(jnp.float32), dtype=np.float64)
        # lane by lane: [lane, emitted tokens, of them replayed, of them agreed, the logits' relative RMS error,
        # the ended states' relative RMS error]
        by_lane, given = [], 0
        for at, lane in enumerate(lanes):
            want, want_ended = reference(lane)
            got = np.asarray(logits[at], dtype=np.float64)
            errors = {}
            for name, mine, theirs in (("logits", got, want), ("state", ended[at], want_ended)):
                norm = float(np.sum(theirs**2))
                errors[name] = math.sqrt(float(np.sum((mine - theirs) ** 2)) / norm) if norm else float("inf")
            mine = said[given : given + int(where[at].sum())]
            given += len(mine)
            by_lane.append([
                int(lane),
                len(mine),
                int(np.sum(np.argmax(got, axis=-1)[where[at]] == mine)),
                int(np.sum(np.argmax(want, axis=-1)[where[at]] == mine)),
                errors["logits"],
                errors["state"],
            ])
        # a lane is a lane: each one's own relative error, root-mean-squared over the checked lanes
        error, state_error = (math.sqrt(sum(row[i] ** 2 for row in by_lane) / len(by_lane)) for i in (4, 5))
        emitted, replayed, agreed = (sum(row[i] for row in by_lane) for i in (1, 2, 3))
        tokens = max(emitted, 1)

        def enough(count, share):
            """``count`` of the emitted tokens is ``share`` of them, less
            three standard deviations of a binomial's room (a rehearsal emits
            a handful of tokens, the cell some 1,800)."""
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
                "ssm_state_updates": counters.get("ssm_state_updates"),
                "ssm_lane_resets": counters.get("ssm_lane_resets"),
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
