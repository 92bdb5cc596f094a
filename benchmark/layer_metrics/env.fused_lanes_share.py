"""Useful lanes over the lanes the fused physics kernel's grid computes, in
percent, read off the cell's compiled evaluation program (the text
harness/scopes.py obtains): ``envs/rigidbody.py`` names every instance of its
kernel ``rigidbody_fused_step_<useful>_of_<computed>`` with the lanes one
device holds and the whole 1,024-lane blocks its grid steps, and the name is
the custom call's in the text. Says that the kernel is in the timed program
and what its tail block wastes; 0 where the program holds no such kernel
(XLA's plain form runs, as before PR 29), nothing without a device trace.
Not in a decoder's cell, which names its forward's own layer (``lm forward``,
...): its environment emits tokens, with no physics kernel to look for."""

import re

LAYER = "env substep"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "env_steps_per_s"

CALL = re.compile(r'^.*custom_call_target="tpu_custom_call".*$', re.MULTILINE)
NAME = re.compile(r"rigidbody_fused_step_(\d+)_of_(\d+)")


def applies(workload):
    layers = workload["layers"]
    return LAYER in layers and not any(layer.endswith(" forward") and layer != "policy forward" for layer in layers)


def share(text):
    """100 x useful / computed lanes over the kernel's instances in ``text``."""
    names = (NAME.search(call.group(0)) for call in CALL.finditer(text))
    instances = [(int(name.group(1)), int(name.group(2))) for name in names if name]
    if not instances:
        return 0.0
    return 100.0 * sum(useful for useful, _ in instances) / sum(computed for _, computed in instances)


def measure(run):
    from benchmark.harness import scopes

    return share(scopes.evaluation_text(run, scopes.ROLLOUT_READS)) if scopes.lowers(run) else None
