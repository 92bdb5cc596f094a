"""Scopes inside the compiled rollout (``observability/scopes.py``): every
engine's compiled text names all six, ``instruction_scopes`` reads them off a
text, and ``VecNE.lower_evaluation`` lowers the program ``evaluate`` runs."""

import re

import jax
import jax.numpy as jnp
import pytest

from evotorch_tpu import SolutionBatch
from evotorch_tpu.analysis import track_compiles
from evotorch_tpu.neuroevolution import VecNE
from evotorch_tpu.neuroevolution.net.runningnorm import RunningNorm
from evotorch_tpu.neuroevolution.net.vecrl import _compacting_fns
from evotorch_tpu.observability.compilecache import past_persistent_cache
from evotorch_tpu.observability.scopes import (
    ROLLOUT_SCOPES,
    SCOPE_PREFIX,
    instruction_scopes,
    scope,
)

NETWORK = "Linear(obs_length, 8) >> Tanh() >> Linear(8, act_length)"
POPSIZE = 12
EPISODE_LENGTH = 6
ENGINES = ("budget", "episodes", "episodes_refill", "episodes_compact.chunk")

# of the loop body's instructions that compute something, the share that may
# carry no scope: by the metadata alone (compiler-made fusion roots and the
# loop's own counter have none), and once the compiler-made have inherited
# their neighbours' (the counter is left)
UNSCOPED_BOUND = {False: 0.20, True: 0.06}
# no arithmetic of the program's own: operands, tuples, control flow, data
# movement the compiler inserts
PLUMBING = {
    "parameter", "get-tuple-element", "tuple", "constant", "copy", "bitcast",
    "while", "conditional", "call",
}


def problem(eval_mode, **kwargs):
    return VecNE(
        "hopper",
        NETWORK,
        episode_length=EPISODE_LENGTH,
        eval_mode=eval_mode,
        observation_normalization=True,
        compute_dtype=jnp.bfloat16,
        seed=5,
        **kwargs,
    )


@pytest.fixture(scope="module")
def compiled_texts():
    """``compiled.as_text()`` of each engine at a small popsize, compiled past
    the persistent cache, whose key ignores scope names: an executable cached
    before a scope was added or moved comes back with the metadata it had."""
    with past_persistent_cache():
        texts = {}
        for mode in ("budget", "episodes"):
            texts[mode] = problem(mode).lower_evaluation(POPSIZE).compile().as_text()
        refill = problem("episodes_refill", refill_config={"width": 4})
        texts["episodes_refill"] = refill.lower_evaluation(POPSIZE).compile().as_text()

        compact = problem("episodes_compact")
        env, policy = compact._env, compact._policy
        init_fn, chunk_fn, _, _ = _compacting_fns(
            env, policy, 1, EPISODE_LENGTH, EPISODE_LENGTH + 1, True, None, None, None, jnp.bfloat16
        )
        params = jnp.zeros((POPSIZE, policy.parameter_count), jnp.float32)
        carry, forward_params = init_fn(
            params, jax.random.key(0), RunningNorm(env.observation_size).stats
        )
        texts["episodes_compact.chunk"] = (
            chunk_fn.lower(forward_params, carry, num_steps=3).compile().as_text()
        )
    return texts


def loop_body_instructions(text):
    """``[(instruction name, opcode)]`` of the largest ``while`` body."""
    computations, current = {}, None
    for line in text.splitlines():
        header = re.match(r"^(?:ENTRY\s+)?(%[^\s(]+)\s*\(.*\{\s*$", line)
        if header:
            current = computations.setdefault(header.group(1), [])
        elif line.startswith("}"):
            current = None
        elif current is not None:
            instruction = re.match(r"^\s*(?:ROOT\s+)?(%[^\s=]+) = .*?\s([a-z][a-z\-]*)\(", line)
            if instruction:
                current.append(instruction.groups())
    bodies = re.findall(r"\swhile\(.*?body=(%[^\s,}]+)", text)
    return max((computations[name] for name in bodies), key=len)


@pytest.mark.parametrize("engine", ENGINES)
def test_every_scope_is_in_the_compiled_text(compiled_texts, engine):
    found = set(instruction_scopes(compiled_texts[engine], inherit=False).values())
    assert found >= set(ROLLOUT_SCOPES), set(ROLLOUT_SCOPES) - found


@pytest.mark.parametrize("inherit", [False, True], ids=["by_metadata", "inherited"])
@pytest.mark.parametrize("engine", ENGINES)
def test_the_loop_body_is_scoped(compiled_texts, engine, inherit):
    scopes = instruction_scopes(compiled_texts[engine], inherit=inherit)
    body = [
        name
        for name, opcode in loop_body_instructions(compiled_texts[engine])
        if opcode not in PLUMBING
    ]
    assert len(body) >= 15  # the rollout's loop, not some small inner one
    unscoped = [name for name in body if scopes[name] is None]
    assert len(unscoped) / len(body) < UNSCOPED_BOUND[inherit], unscoped


# -- instruction_scopes on a hand-written text ---------------------------------

HAND_WRITTEN = """\
HloModule jit_f, is_scheduled=true, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %sine.1 = f32[8]{0} sine(f32[8]{0} %param_0), metadata={op_name="jit(f)/while/body/evotorch_tpu.env_step/sin" source_file="f.py" source_line=3}
}

%fused_computation.5 (param_0.1: f32[8], param_1.1: f32[8]) -> bf16[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  %param_1.1 = f32[8]{0} parameter(1)
  %sub.1 = f32[8]{0} subtract(f32[8]{0} %param_0.1, f32[8]{0} %param_1.1), metadata={op_name="jit(f)/while/body/evotorch_tpu.obs_norm/sub"}
  %div.1 = f32[8]{0} divide(f32[8]{0} %sub.1, f32[8]{0} %param_1.1), metadata={op_name="jit(f)/while/body/evotorch_tpu.obs_norm/div"}
  %convert.1 = bf16[8]{0} convert(f32[8]{0} %div.1), metadata={op_name="jit(f)/while/body/evotorch_tpu.policy_forward/convert_element_type"}
  ROOT %copy.2 = bf16[8]{0} copy(bf16[8]{0} %convert.1)
}

%body.2 (arg: (s32[], f32[8])) -> (s32[], f32[8]) {
  %arg = (s32[], f32[8]{0}) parameter(0)
  %get-tuple-element.4 = f32[8]{0} get-tuple-element((s32[], f32[8]{0}) %arg), index=1
  %copy-start.3 = (f32[8]{0:S(1)}, f32[8]{0}, u32[]{:S(2)}) copy-start(f32[8]{0} %get-tuple-element.4)
  %copy-done.3 = f32[8]{0:S(1)} copy-done((f32[8]{0:S(1)}, f32[8]{0}, u32[]{:S(2)}) %copy-start.3)
  %sine_fusion.2 = f32[8]{0} fusion(f32[8]{0:S(1)} %copy-done.3), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/while/body/evotorch_tpu.env_step/sin"}
  %dot_general.0 = f32[8]{0} dot(f32[8,8]{1,0} %w, f32[8]{0} %get-tuple-element.4), metadata={op_name="jit(f)/while/body/closed_call/evotorch_tpu.policy_forward/nij,nj->ni/dot_general" source_file="f.py" source_line=2}
  %sine_add_fusion = f32[8]{0} fusion(f32[8]{0} %dot_general.0), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/while/body/evotorch_tpu.env_step/add" source_file="f.py"}
  %negate.7 = f32[8]{0} negate(f32[8]{0} %sine_add_fusion), metadata={op_name="jit(f)/while/body/evotorch_tpu.rollout_edges/evotorch_tpu.env_reset/neg"}
  %exp.8 = f32[8]{0} exponential(f32[8]{0} %negate.7), metadata={op_name="jit(f)/while/body/evotorch_tpu.policy_forward/unravel/exp"}
  %tanh.9 = f32[8]{0} tanh(f32[8]{0} %exp.8), metadata={op_name="jit(f)/while/body/vmap(evotorch_tpu.obs_norm/tanh)"}
  %abs.10 = f32[8]{0} abs(f32[8]{0} %tanh.9), metadata={op_name="jit(f)/while/body/evotorch_tpu.not_declared/abs"}
  %sqrt.11 = f32[8]{0} sqrt(f32[8]{0} %abs.10), metadata={op_name="jit(f)/while/body/evotorch_tpu.contractual/evotorch_tpu.contract/sqrt"}
  %copy.12 = f32[8]{0} copy(f32[8]{0} %sqrt.11)
  %divide_copy_fusion = bf16[8]{0} fusion(f32[8]{0} %sqrt.11, f32[8]{0} %copy.12), kind=kLoop, calls=%fused_computation.5
  %wrapped_reduce = f32[]{:T(128)} fusion(f32[8]{0} %copy.12), kind=kLoop, calls=%fused_computation.6
  %add.13 = s32[] add(s32[] %i, s32[] %one), metadata={op_name="jit(f)/while/body/add"}
  ROOT %tuple.14 = (s32[], f32[8]{0}) tuple(s32[] %add.13, f32[8]{0} %copy.12)
}

ENTRY %main.20 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  %while.15 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %tuple.3), condition=%cond.1, body=%body.2, metadata={op_name="jit(f)/while"}
  ROOT %fusion = f32[8]{0} fusion(f32[8]{0} %gte), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(f)/evotorch_tpu.rollout_edges/div"}
}
"""


@pytest.mark.parametrize(
    "instruction, named, inherited",
    [
        # what the metadata names, and what inheritance adds where it names nothing
        ("%dot_general.0", "policy_forward", "policy_forward"),  # a plain instruction in a loop body
        ("%sine_add_fusion", "env_step", "env_step"),  # a fusion carries a scope too
        ("%sine.1", "env_step", "env_step"),  # ROOT of a second (fused) computation
        ("%fusion", "rollout_edges", "rollout_edges"),  # ROOT of the entry computation
        ("%negate.7", "rollout_edges", "rollout_edges"),  # nested declared scopes: the outermost
        ("%exp.8", "policy_forward", "policy_forward"),  # a free nested name below a declared one
        ("%tanh.9", "obs_norm", "obs_norm"),  # a transformation wraps the component
        ("%sqrt.11", "contract", "contract"),  # a member's name as a prefix is no member
        ("%abs.10", None, "contract"),  # evotorch_tpu.<not a member>: its user's
        ("%divide_copy_fusion", None, "obs_norm"),  # a compiler-made root: what most of the fused name
        ("%copy.2", None, "policy_forward"),  # that root itself, no user: its operand's
        ("%copy.12", None, "obs_norm"),  # no metadata at all: its scoped user's
        ("%wrapped_reduce", None, "obs_norm"),  # nothing fused is listed, no user: its operand's, once that has one
        ("%copy-start.3", None, "env_step"),  # an async prefetch: through its done to the fusion that reads it
        ("%copy-done.3", None, "env_step"),
        ("%add.13", None, None),  # the loop's counter: metadata without a scope, only plumbing around it
        ("%get-tuple-element.4", None, None),  # plumbing takes no scope
        ("%while.15", None, None),  # the loop itself is not its operands' work
        ("%param_0", None, None),  # operands are listed, scopeless
    ],
)
def test_instruction_scopes_on_a_hand_written_text(instruction, named, inherited):
    assert instruction_scopes(HAND_WRITTEN, inherit=False)[instruction] == named
    assert instruction_scopes(HAND_WRITTEN)[instruction] == inherited


def test_instruction_scopes_lists_every_instruction_of_every_computation():
    scopes = instruction_scopes(HAND_WRITTEN)
    assert len(scopes) == 28
    assert "%tuple.14" in scopes and "%x" in scopes and "%arg" in scopes
    assert set(scopes) == set(instruction_scopes(HAND_WRITTEN, inherit=False))
    assert instruction_scopes("") == {}


# -- scope() ---------------------------------------------------------------------


def test_scope_refuses_an_undeclared_name():
    with pytest.raises(ValueError, match="ROLLOUT_SCOPES"):
        scope("policy")
    with pytest.raises(ValueError):
        scope(SCOPE_PREFIX + "policy_forward")  # the bare name is what is declared


@pytest.mark.parametrize("name", ROLLOUT_SCOPES)
def test_scope_names_the_ops_traced_inside_it(name):
    def f(x):
        with scope(name):
            return jnp.sin(x) + 1.0

    with past_persistent_cache():
        text = jax.jit(f).lower(jnp.ones(4)).compile().as_text()
    assert name in set(instruction_scopes(text).values())


# -- VecNE.lower_evaluation --------------------------------------------------------


@pytest.mark.parametrize("num_actors", [None, 2], ids=["one_device", "mesh_of_2"])
def test_lower_evaluation_is_the_program_evaluate_runs(num_actors):
    vecne = problem("budget", num_actors=num_actors)
    values = 0.01 * jax.random.normal(jax.random.key(1), (POPSIZE, vecne.solution_length))
    with track_compiles() as log:
        for _ in range(3):  # over a mesh the dispatched program is steady from the third call
            batch = SolutionBatch(vecne, values=values)
            vecne.evaluate(batch)
            values = batch.values + 0.0 * batch.evals[:, :1]  # laid out as a searcher's next ask
    dispatched = {"run_vectorized_rollout", "global_eval"} & {
        re.sub(r"^jit\((.*)\)$", r"\1", name) for name in log.names
    }
    assert len(dispatched) == 1

    lowered = vecne.lower_evaluation(POPSIZE)
    module = re.match(r"module @(\S+)", lowered.as_text()).group(1)
    assert module == "jit_" + dispatched.pop()
    stats = vecne._obs_norm.stats
    assert [(a.shape, a.dtype) for a in jax.tree_util.tree_leaves(lowered.in_avals)] == [
        (x.shape, x.dtype)
        for x in (batch.values, vecne._rng_key, *jax.tree_util.tree_leaves(stats))
    ]
    # more than the name: nothing is left to compile, so it is the very
    # executable the last evaluation dispatched
    with track_compiles() as log:
        lowered.compile()
    assert log.names == []


def test_lower_evaluation_refuses_what_has_no_single_program():
    with pytest.raises(ValueError, match="episodes_compact"):
        problem("episodes_compact").lower_evaluation(POPSIZE)
