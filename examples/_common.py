"""Shared example plumbing: platform selection."""

import argparse
import os
import sys

# run from anywhere: the package lives one directory up
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def setup_platform():
    parser = argparse.ArgumentParser()
    parser.add_argument("--cpu", action="store_true", help="force the CPU backend")
    parser.add_argument("--generations", type=int, default=None)
    args, _ = parser.parse_known_args()
    from evotorch_tpu.observability import enable_persistent_cache
    from evotorch_tpu.resilience import setup_backend

    # --cpu (or JAX_PLATFORMS=cpu) asks for the 8-virtual-device CPU;
    # anything else requires an accelerator and fails without one
    setup_backend(args.cpu)
    enable_persistent_cache()
    return args
