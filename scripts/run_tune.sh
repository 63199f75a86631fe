#!/usr/bin/env bash
# Produce and print a demo tuner profile on the 8-device CPU mesh — the
# zero-to-profile walkthrough for MLSL_TUNE (docs/TUNING.md §10). On a real
# slice, drop the CPU-mesh env vars and run the same command: the sweep
# measures whatever backend JAX is attached to, and the profile lands keyed
# by that topology's fingerprint.
#
# Usage: scripts/run_tune.sh [profile-path] [extra algo_sweep_bench args...]
set -euo pipefail
cd "$(dirname "$0")/.."

PROFILE="${1:-/tmp/mlsl_tune_profile.demo.json}"
shift || true

env JAX_PLATFORMS=cpu \
    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python benchmarks/algo_sweep_bench.py --smoke --profile-out "$PROFILE" "$@"

echo
echo "=== tuned profile: $PROFILE ==="
python -m json.tool "$PROFILE"
echo
echo "Use it:  MLSL_TUNE_PROFILE=$PROFILE python your_training.py"
echo "Retune:  MLSL_TUNE=1 MLSL_TUNE_PROFILE=$PROFILE python your_training.py"
