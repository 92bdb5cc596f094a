"""Driver: evolution strategies on a language model whose experts are chosen
before its attention, through the path a researcher calls:
``VecNE(env=TokenCopyEnv(...), network=SmallThinkerDecoder(...),
eval_mode="budget")`` + ``PGPE(..., lowrank_rank=("trunk_delta", k))`` +
``searcher.step()``, one whole generation per call. Every lane decodes
``decode_steps`` tokens under its own perturbed weights (a seeded prompt fed
one token a step, then its own greedy tokens); every layer's softmax router
decides the lane's six experts from the attention's normed input, the
attention runs over the lane's cache (NoPE full in layer 0, a RoPE ring of
4,096 slots in layers 1-3), and the held ReGLU experts run on the
post-attention stream.

The session protocol, the measured path (``generation`` / ``block`` /
``mark`` / ``policy_counters``) and the generic pieces of the comparison
(``lanes_to_check``, ``emitted_tokens``, ``LaneReference``,
``reference_comparison``) are ``drivers/oo_lm_searcher.py``'s, imported; what
differs is the network that is built, the controls that change an equation
of the reference, and the bounds below.

The configuration file holds the published model's keys;
``moe_num_primary_experts``, ``vocab_size`` and ``num_hidden_layers`` there
are what THIS chip holds (they are under ``reduced``; ``published`` has the
model's own), and ``scale`` may shrink popsize, steps, layers and rows for
the CPU rehearsal.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

# the library's route-ahead decoder: a checkout without it cannot run this
# cell and fails here, before any compile
from evotorch_tpu.neuroevolution.net.decoder import SmallThinkerDecoder, stepwise_logits

from benchmark.drivers import oo_lm_searcher as lm
from evotorch_tpu.algorithms import PGPE
from evotorch_tpu.envs.tokens import TokenCopyEnv
from evotorch_tpu.neuroevolution import VecNE

MODEL_KEYS = (
    "hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim", "moe_ffn_hidden_size",
    "moe_num_active_primary_experts", "moe_primary_router_apply_softmax", "norm_topk_prob", "rope_layout",
    "sliding_window_layout", "sliding_window_size", "rope_theta", "rope_scaling", "rms_norm_eps",
    "tie_word_embeddings",
)
#: the configuration's key that sizes the window's ring
WINDOW_KEY = "sliding_window_size"

# The comparison with the reference is drivers/oo_lm_searcher.py's: what the
# TIMED program emitted on ``checked_lanes`` lanes drawn with ``--seed``,
# replayed teacher-forced through the population-wide stepwise forward over
# ALL lanes, against the plain float32 "highest" whole-sequence reference on
# each checked lane's written-out weights, going on with the experts the
# system chose; the routes counted apart. The controls, which have to come
# out NOT ok, take the program's place: the reference with int8-rounded
# weights (scaled to the largest of a leaf), the nearest precision below
# bfloat16, and the reference with a changed equation (``EQUATION_CONTROLS``:
# ``route_after`` routes from RMSNorm(h) AFTER the attention, Trinity's
# placement; ``swiglu`` has SiLU where the family has ReLU), so that the
# comparison tells this model from its neighbour
# (``scripts/lm_ring_wrap_check.py --cell smallthinker_ep4.decode128
# --control int8,bfloat16,route_after,swiglu --seeds 6``).
#
# READINGS (TPU v5 lite, jax 0.9.0; 32 lanes x 128 positions a reading,
# 3,072 emitted tokens, 16,384 (position, layer) pairs; "system" is six
# draws of lanes in one process, each control one reading; PERF.md has them). Each bound lies
# between the system's worst reading and int8's, at their geometric mean:
# - LOGIT_RTOL: relative RMS error of the replay's logits against the
#   reference's under the system's routes. The system 1.138e-2 to 1.401e-2
#   (1.020e-2 past the wrap of a 4,352-step replay at the published window);
#   bf16 weights alone 0.719e-2; int8 7.351e-2; SiLU experts 6.589e-2; the
#   router after the attention 0.220. A changed equation reads 5e-2 or more at
#   a tiny size in float32, where the unchanged ones read 1e-6 (tier-1). The
#   bound: 2.3x above the system's largest, 2.3x below int8.
# - ROUTE_FLIP_SHARE: (position, layer) pairs whose top-6 SETS differ between
#   the system's router and the reference's (both float32, the system's on
#   bf16 hidden states). The system 4.45% to 5.29%; bf16 weights alone 2.53%;
#   int8 22.34%; SiLU experts 21.17% (the routes of the layers after the
#   first read the experts' output); the router after the attention 99.61%.
#   Held as a COUNT with three binomial standard deviations of room: 2.2x
#   above the system, 2.0x below int8.
# - TOKENS_REPLAYED: emitted tokens the replay's argmax reproduces. The
#   system 96.65% to 97.49% (two compilations of one forward round bf16
#   elsewhere); a control's replay is its own (100%), so the bound is taken
#   as the other expert cells take it: the geometric mean of the largest
#   shares NOT reproduced by the replay (3.35%) and by the float32 reference
#   on its own routes (4.23%), 3.8%, held as a count with three binomial
#   standard deviations of room. It ties the logits that are compared to the
#   TIMED program: a wrong token, reset or cast in the engine reads near zero.
# - TOKENS_AGREED: emitted tokens that the reference, going on with its OWN
#   routes, also puts first. The system 95.77% to 96.39%; bf16 weights alone
#   97.92%; int8 86.49%; SiLU experts 86.33%; the router after the attention
#   48.86%. The bound is the geometric mean of the shares that differ (4.23%
#   and 13.51%: 7.6%), held as a count like the one above.
# int8 and SiLU experts come out not ok by three of the four figures (logits,
# routes, tokens), the router after the attention by the same three.
TOKENS_REPLAYED = 0.96
LOGIT_RTOL = 3.2e-2
ROUTE_FLIP_SHARE = 0.109
TOKENS_AGREED = 0.92
#: what a control changes in the reference's sizes (``reference/smallthinker_decoder.py``)
EQUATION_CONTROLS = {"route_after": {"route": "after"}, "swiglu": {"activation": "silu"}}


def network(config, sizes, max_positions):
    """The library's decoder for this configuration's share: the layers and
    rows ``sizes`` (``reference/smallthinker_decoder.py:sizes``) hold."""
    first, past = config["experts_held"]
    return SmallThinkerDecoder(
        **{key: config[key] for key in MODEL_KEYS},
        moe_num_primary_experts=int(config["published"]["moe_num_primary_experts"]),
        vocab_size=int(config["published"]["vocab_size"]),
        num_hidden_layers=int(config["published"]["num_hidden_layers"]),
        max_positions=max_positions,
        layers_held=sizes["layers"],
        experts_held=range(int(first), int(past)),
        vocab_held=sizes["vocab"],
    )


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
        self.network = network(config, self._sizes, self.decode_steps)
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
            # at 657M parameters every vector of the solution's length is 2.6 GB
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
        # for the per-layer readers (benchmark/harness/lm_scopes.py, lm_floors.py,
        # ahead_floors.py): the reference's sizes, with the layers' kinds as
        # the lm readers name them, so that the cache's reader finds its shape
        self.lm_sizes = {
            **self._sizes,
            "layer_types": ["sliding_attention" if sliding else "full_attention" for sliding in self._sizes["sliding_layout"]],
        }
        self.trunk_delta_rank = int(config["trunk_delta_rank"])
        state = jax.eval_shape(self.network.initial_state)
        self.cache_bytes = self.popsize * sum(
            math.prod(layer["attn"][name].shape) * (2 if self.compute_dtype is not None else 4)
            for layer in state["layers"]
            for name in ("k", "v")
        )

    # -- the comparison with the plain reference -----------------------------
    def reference_checks(self, seed, control=None):
        """The four figures above for the evaluation in hand (the last of the
        warm-up: ``searcher.population`` is what it ran). ``control``: a
        function that rounds a weight leaf to a lower precision, or a name of
        ``EQUATION_CONTROLS``; the REFERENCE with its weights rounded so, or
        its equation changed so, going on with its own routes, then takes the
        program's place (its logits, its routes, its first tokens)."""
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
        emitted = lm.emitted_tokens(ids[lanes], positions[lanes], self.env.prompt_length, self.env.max_episode_steps)
        lane_reference = lm.LaneReference(self._reference, self._sizes, policy)
        if control is None:

            @jax.jit
            def replay(batch, ids, positions, lanes):
                return stepwise_logits(
                    policy, batch, ids, positions=positions, lanes=lanes, compute_dtype=self.compute_dtype
                )

            logits, routes = replay(batch, jnp.asarray(ids), jnp.asarray(positions), jnp.asarray(lanes))
            logits, routes = np.asarray(logits), np.asarray(routes)  # (c, T, V), (T, layers, c, k)
        else:
            if isinstance(control, str):
                stand_in = lm.LaneReference(self._reference, {**self._sizes, **EQUATION_CONTROLS[control]}, policy)
                weights = None
            else:
                stand_in, weights = lane_reference, control
            found = [stand_in(batch, lane, ids[lane], positions[lane], None, weights=weights) for lane in lanes]
            logits = np.stack([one["free_logits"] for one in found])
            routes = np.stack([np.stack(one["free_routes"], axis=1) for one in found], axis=2)
            where, _ = emitted
            emitted = where, np.argmax(logits, axis=-1)[where]
        found = lm.reference_comparison(lane_reference, batch, lanes, ids, positions, logits, routes, emitted)
        error, flips, pairs = found["relative_rms_error"][0], found["flips"], found["pairs"]
        flips_allowed = ROUTE_FLIP_SHARE * pairs + 3.0 * math.sqrt(ROUTE_FLIP_SHARE * pairs)
        tokens = max(found["tokens"], 1)

        def enough(count, share):
            """``count`` of the emitted tokens is ``share`` of them, less
            three standard deviations of a binomial's room (a rehearsal emits
            a handful of tokens, the cell some 3,000)."""
            return bool(count >= share * tokens - 3.0 * math.sqrt(share * (1.0 - share) * tokens))

        counters = self.policy_counters() or {}
        return {
            "record": {
                "ok": record_ok and found["tokens"] > 0,
                "lanes": [int(lane) for lane in lanes],
                "episodes_begun_midway": int(np.sum(positions[lanes][:, 1:] == 0)),
                "episodes_begun_midway_all_lanes": int(np.sum(positions[:, 1:] == 0)),
                "emitted_tokens": found["tokens"],
                "distinct_tokens_emitted": int(len(np.unique(emitted[1]))),
                "lane_tokens_replayed_agreed": found["by_lane"],
                "expert_pairs_routed_ahead": counters.get("expert_pairs_routed_ahead"),
                "expert_layer_steps": counters.get("expert_layer_steps"),
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


def build(files, config, workload, seed, scale):
    return Session(files, config, workload, seed, scale)
