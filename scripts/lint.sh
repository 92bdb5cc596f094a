#!/bin/bash
# graftlint entry point: run the JAX correctness/performance static-analysis
# suite (evotorch_tpu/analysis) over the gated surface — evotorch_tpu/,
# bench*.py, examples/, __graft_entry__.py and scripts/*.py — and exit
# non-zero on any non-baselined finding (or stale baseline entry).
#
# Pure-AST: finishes in a few seconds and never initializes a jax backend
# (so it needs no chip). Pass extra args through (e.g. --no-baseline to see
# the grandfathered findings, --checkers prng,retrace for a subset).
set -euo pipefail
cd "$(dirname "$0")/.."
exec python -m evotorch_tpu.analysis "$@"
