#!/usr/bin/env sh
# Tier-1 verify (see ROADMAP.md). Must pass on a bare environment:
# jax + numpy + pytest only — no zstandard, no hypothesis.
set -eu
cd "$(dirname "$0")"
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}" exec python -m pytest -x -q "$@"
