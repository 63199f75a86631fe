#!/bin/sh
# Environment setup for mlsl_tpu (the analog of the reference's
# scripts/mlslvars.sh: exports the root, library path and python path, with a
# mode selector). Usage:
#   source scripts/mlsltpuvars.sh [tpu|cpusim]
# 'cpusim' configures an 8-device virtual CPU mesh (multi-chip simulation);
# 'tpu' (default) leaves the real accelerator configuration untouched.

# BASH_SOURCE works when sourced from bash/zsh; plain sh sourcing falls back to
# the current directory (source from the repo root in that case).
_mlsl_script="${BASH_SOURCE:-$0}"
case "$_mlsl_script" in
  */mlsltpuvars.sh) MLSL_TPU_ROOT="$(cd "$(dirname "$_mlsl_script")/.." && pwd)" ;;
  *) MLSL_TPU_ROOT="$(pwd)" ;;
esac
export MLSL_TPU_ROOT

PYTHONPATH="${MLSL_TPU_ROOT}:${PYTHONPATH}"
export PYTHONPATH

LD_LIBRARY_PATH="${MLSL_TPU_ROOT}/native:${LD_LIBRARY_PATH}"
export LD_LIBRARY_PATH

case "${1:-tpu}" in
  cpusim)
    export JAX_PLATFORMS=cpu
    export XLA_FLAGS="--xla_force_host_platform_device_count=8 ${XLA_FLAGS}"
    echo "mlsl_tpu: 8-device CPU simulation mode"
    ;;
  tpu)
    ;;
  *)
    echo "usage: source mlsltpuvars.sh [tpu|cpusim]" >&2
    ;;
esac
