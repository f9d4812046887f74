#!/usr/bin/env bash
# Launcher (successor of the reference's bin/run-pipeline.sh).
#
# Usage: bin/run-pipeline.sh <pipeline-name-or-reference-class> [args...]
#   e.g. bin/run-pipeline.sh mnist-random-fft --synthetic 1000
#        bin/run-pipeline.sh pipelines.images.mnist.MnistRandomFFT --synthetic 1000
#
# Environment:
#   KEYSTONE_DEVICES=cpu8   run on 8 virtual CPU devices (test mesh)
#   JAX_PLATFORMS           obeyed when set (cpu is how to ask for the CPU);
#                           unset means TPU, and no TPU is a non-zero exit
set -euo pipefail
DIR="$(cd "$(dirname "${BASH_SOURCE[0]}")/.." && pwd)"
export PYTHONPATH="$DIR${PYTHONPATH:+:$PYTHONPATH}"

if [[ "${KEYSTONE_DEVICES:-}" == "cpu8" ]]; then
  export JAX_PLATFORMS=cpu
  export XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8"
fi

exec python -m keystone_tpu "$@"
