"""Names for the work inside the compiled rollout.

The evaluation is one device program (``run_vectorized_rollout`` and its
siblings in ``neuroevolution/net/vecrl.py``), and a device trace times its
ops without saying which layer an op belongs to. ``scope(name)`` is
``jax.named_scope("evotorch_tpu." + name)``: it writes the name into the
``op_name`` metadata of every HLO instruction traced inside it and changes
nothing else. The optimized program is the same program (the persistent
cache's key does not even see the names: jax strips debug info from the
module it hashes, so an executable cached before a scope was added or moved
is handed back WITHOUT it; clear the cache after touching scopes).

``ROLLOUT_SCOPES`` is the one place the names are declared:

- ``policy_forward``: the population-wide forward (dense
  ``vmap(module.apply)`` over the unravelled population, low-rank,
  trunk-delta), with the casts into and out of the compute dtype;
- ``env_step``: the env substep and the mapping of the policy's output to
  an action;
- ``env_reset``: the fresh reset inside the loop and the per-lane select
  between fresh and stepped state;
- ``obs_norm``: normalising observations and updating (and, across shards,
  merging) their running statistics;
- ``contract``: the rest of a control step: PRNG chains, done flags, reward
  adjustments, scores, episode and step counters, activity masks, the
  refill queue;
- ``rollout_edges``: what runs once per program, outside the loop: the first
  reset and statistics, parameter casts, forward contexts (the unravel of a
  dense population into per-layer blocks among them), score averaging,
  quarantine, telemetry packing.

``FORWARD_SCOPES`` name the parts of a forward that has parts worth telling
apart (the decoder of ``net/decoder.py``), INSIDE ``policy_forward``:
``fwd_attention`` (projections, norms, RoPE, cache write, scores over the
cache), ``fwd_router`` (the expert layer's norm, router, top-k),
``fwd_experts`` (the sort of the pairs, the grouped product over the held
experts, the shared expert), ``fwd_dense_mlp``, ``fwd_head`` (embedding
gather, final norm, head), and ``fwd_latent_cache``, worn INSIDE
``fwd_attention`` by what latent attention does over its compressed cache
(the write, the scores over ``c`` and ``k_r``, the softmax, the weighted sum
over ``c``; the up-projections folded into the query and output paths stay
``fwd_attention``); ``fwd_ssm`` (a recurrent mixer: norm, ``in_proj``, the
convolution over the lane's window, the gated norm, ``out_proj``) and,
INSIDE it, ``fwd_ssm_state`` (whatever touches the matrix state: decay,
outer product, readout; on a TPU the kernel of ``net/ssmstate.py`` and the
gathering of its small operands); ``fwd_kda`` (a gated delta-rule block:
norm, projections, the three convolutions, the gates, the output's gated
norm, ``o_proj``) and, INSIDE it, ``fwd_kda_state`` (whatever touches its
matrix state: decay, ``S'^T k``, the rank-1 update, readout, write-back).
``instruction_scopes`` keeps reading the OUTERMOST
rollout scope, so what read ``policy_forward`` before still does;
``instruction_scopes(..., names=FORWARD_SCOPES)`` reads the INNERMOST
component among the forward's names.

A v5e trace names an op by its instruction (``%fusion.12 = ...``) and, unless
the HLO proto is recorded with it, carries no metadata; the compiled
program's text carries both. ``instruction_scopes`` reads the text, so
instruction name is the join key: scope from the text, seconds from the
trace (``benchmark/harness/scopes.py``). With the HLO proto recorded, xprof
shows the same names on its own.

``SEARCHER_PHASES`` name the work of one generation OUTSIDE the compiled
rollout, on the host's side: the parts of ``SearchAlgorithm.step()``.
``phase(name)`` is the one call a site makes: it enters
``jax.profiler.TraceAnnotation("evotorch_tpu." + name)`` (the profiler's
clock: what ``SearchAlgorithm.run(profile_dir=...)`` and the benchmark's
``--trace 1`` read) and, when the host span tracer is installed
(``EVOTORCH_TRACE``, ``tracer.start_tracing``), the Chrome span of the same
name. The phases are SIBLINGS that tile a step inside ``generation``:

- ``grad``: ranking the fitnesses and the gradient estimate;
- ``update``: the optimizer's step, the distribution's update and its clamps
  (the trunk-delta ``tell``, which ranks inside its one program, whole);
- ``ask``: sampling and building the ``SolutionBatch``, nothing else;
- ``evaluate``: ``Problem.evaluate`` (hooks, the evaluation, best/worst);
- ``status``: everything else a step does: ``mean_eval``, counters, hooks,
  the status refresh, loggers.

``phase_jit(name, fn, **jit_kwargs)`` is ``jax.jit`` for a function one of the
phases dispatches: the program is called ``jit_evotorch_tpu_<phase>_<fn's
name>``, so a device trace's ``XLA Modules`` line (and xprof) says which phase
a program belongs to (``benchmark/harness/phases.py`` reads it). The name is
the ONLY difference in the lowered text. jax's persistent-cache key starts with
the module's name, so a renamed program misses the cache once. The evaluation
program keeps its own name.
"""

from __future__ import annotations

import contextlib
import functools
import re
from collections import Counter
from typing import Dict, Optional

import jax

from . import tracer

__all__ = [
    "ROLLOUT_SCOPES",
    "FORWARD_SCOPES",
    "SEARCHER_PHASES",
    "SCOPE_PREFIX",
    "PROGRAM_PREFIX",
    "scope",
    "phase",
    "phase_jit",
    "instruction_scopes",
]

ROLLOUT_SCOPES = (
    "policy_forward",
    "env_step",
    "env_reset",
    "obs_norm",
    "contract",
    "rollout_edges",
)
FORWARD_SCOPES = (
    "fwd_attention",
    "fwd_router",
    "fwd_experts",
    "fwd_dense_mlp",
    "fwd_head",
    "fwd_latent_cache",
    "fwd_ssm",
    "fwd_ssm_state",
    "fwd_kda",
    "fwd_kda_state",
)
SEARCHER_PHASES = ("grad", "update", "ask", "evaluate", "status")
_GENERATION = "generation"  # the span that encloses a step's phases
SCOPE_PREFIX = "evotorch_tpu."
#: what a program dispatched by a phase is called after ``jit_``; then the phase
PROGRAM_PREFIX = "evotorch_tpu_"


def scope(name: str):
    """``jax.named_scope("evotorch_tpu.<name>")`` for a declared name."""
    if name not in ROLLOUT_SCOPES and name not in FORWARD_SCOPES:
        raise ValueError(
            f"{name!r} is not one of ROLLOUT_SCOPES {ROLLOUT_SCOPES} or FORWARD_SCOPES {FORWARD_SCOPES}"
        )
    return jax.named_scope(SCOPE_PREFIX + name)


@contextlib.contextmanager
def _both(annotation, span):
    with annotation, span:
        yield


def phase(name: str, **args):
    """The context manager of one declared phase of a generation (or of the
    enclosing ``generation``): the profiler's annotation
    ``evotorch_tpu.<name>`` and, with the host span tracer on, the Chrome span
    of the same name carrying ``args``. With both off it costs what the bare
    annotation costs."""
    if name not in SEARCHER_PHASES and name != _GENERATION:
        raise ValueError(f"{name!r} is not {_GENERATION!r} or one of SEARCHER_PHASES {SEARCHER_PHASES}")
    annotation = jax.profiler.TraceAnnotation(SCOPE_PREFIX + name)
    if tracer._TRACER is None:
        return annotation
    return _both(annotation, tracer.span(SCOPE_PREFIX + name, "algo", **args))


def phase_jit(name: str, fn, **jit_kwargs):
    """``jax.jit(fn, **jit_kwargs)`` under the program name
    ``evotorch_tpu_<phase>_<fn's name>`` (leading underscores dropped)."""
    if name not in SEARCHER_PHASES:
        raise ValueError(f"{name!r} is not one of SEARCHER_PHASES {SEARCHER_PHASES}")

    @functools.wraps(fn)
    def named(*args, **kwargs):
        return fn(*args, **kwargs)

    named.__name__ = named.__qualname__ = f"{PROGRAM_PREFIX}{name}_{fn.__name__.lstrip('_')}"
    return jax.jit(named, **jit_kwargs)


# `  ROOT %fusion.3 = f32[8]{0} fusion(...), ..., metadata={op_name="..." ...}`
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?(%[^\s=]+) = (.*)$")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?(%[^\s(]+)\s*\(.*\{\s*$")
_OPCODE = re.compile(r"(?:^|\s)([a-z][a-z\-]*)\(")  # after the shape, whose `T(8,128)` is upper case
_CALLS = re.compile(r"\bcalls=(%[\w.\-]+)")
_NAME = re.compile(r"%[\w.\-]+")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
# a path component, also where a transformation wraps it: `vmap(evotorch_tpu.x/...)`
_COMPONENT = re.compile(r"(?:^|[/(])" + re.escape(SCOPE_PREFIX) + r"(\w+)(?=[/)]|$)")
# control flow and tuple plumbing compute nothing of their own: they neither
# take a neighbour's scope nor hand one on (a `while` is not its operands' work)
_PLUMBING = frozenset(
    ("while", "conditional", "call", "tuple", "get-tuple-element", "parameter", "constant")
)


def _named_scope(line: str, names=ROLLOUT_SCOPES) -> Optional[str]:
    """The outermost component of the line's ``op_name`` that is one of the
    rollout's scopes; for any other ``names``, the innermost that is one of
    them."""
    op_name = _OP_NAME.search(line)
    if op_name is None:
        return None
    found = [c.group(1) for c in _COMPONENT.finditer(op_name.group(1)) if c.group(1) in names]
    if not found:
        return None
    return found[0] if names is ROLLOUT_SCOPES else found[-1]


def _most_named(scopes, names) -> Optional[str]:
    named = Counter(scopes[name] for name in names if scopes[name] is not None)
    return named.most_common(1)[0][0] if named else None


def instruction_scopes(
    hlo_text: str, *, inherit: bool = True, names=ROLLOUT_SCOPES
) -> Dict[str, Optional[str]]:
    """``{instruction name: scope or None}`` for every ``%name = ...`` line of
    every computation in ``compiled.as_text()``. The scope is the OUTERMOST
    component of the instruction's ``op_name`` path that is
    ``evotorch_tpu.<member of ROLLOUT_SCOPES>``; an instruction without
    metadata, or with no such component, has none of its own. With
    ``names=FORWARD_SCOPES`` it is the INNERMOST component among those names
    (the parts of a forward, which sit inside ``policy_forward``).

    ``inherit`` (default): the compiler makes instructions of its own, without
    metadata: the root of a fusion (a convert, a copy, a bitcast), the async
    copies that prefetch an operand into fast memory, relayout copies, the
    ``dynamic-update-slice`` fusions it rewrites a concatenate into. On a v5e
    they are a seventh of the flagship rollout's device time. Such an
    instruction works for its neighbours, so it takes, in this order, the
    scope that most of the instructions it ``calls`` (fuses) name, else most
    of its users, else most of its operands, inside its computation and
    repeated until nothing changes; control flow and tuple plumbing stay out
    of it. ``inherit=False`` reads the metadata alone."""
    scopes: Dict[str, Optional[str]] = {}
    computations: Dict[str, dict] = {}  # computation -> {instruction: (opcode, names in its line)}
    members = None
    for line in hlo_text.splitlines():
        instruction = _INSTRUCTION.match(line)
        if instruction is None:
            header = _COMPUTATION.match(line)
            if header is not None:
                members = computations.setdefault(header.group(1), {})
            continue
        name, rest = instruction.groups()
        scopes[name] = _named_scope(rest, names)
        if members is not None:
            opcode = _OPCODE.search(rest)
            members[name] = (opcode.group(1) if opcode else None, rest)
    if inherit:
        for members in computations.values():
            _inherit(scopes, members, computations)
    return scopes


def _inherit(scopes, members, computations) -> None:
    """Give the scopeless instructions of one computation their neighbours'
    scope (see ``instruction_scopes``)."""
    pending = [n for n, (opcode, _) in members.items() if scopes[n] is None and opcode not in _PLUMBING]
    if not pending:
        return
    for name in pending:  # a fusion, an async pair: from what it wraps
        called = _CALLS.search(members[name][1])
        if called is not None and called.group(1) in computations:
            scopes[name] = _most_named(scopes, computations[called.group(1)])
    operands = {
        name: [
            other
            for other in _NAME.findall(rest)
            if other != name and other in members and members[other][0] not in _PLUMBING
        ]
        for name, (opcode, rest) in members.items()
        if opcode not in _PLUMBING
    }
    users: Dict[str, list] = {name: [] for name in operands}
    for name, reads in operands.items():
        for other in reads:
            users[other].append(name)
    pending = [name for name in pending if scopes[name] is None]
    while pending:
        found = {}
        for name in pending:
            scope = _most_named(scopes, users[name]) or _most_named(scopes, operands[name])
            if scope is not None:
                found[name] = scope
        if not found:
            break
        scopes.update(found)
        pending = [name for name in pending if name not in found]
