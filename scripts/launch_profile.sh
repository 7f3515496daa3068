#!/usr/bin/env bash
# Launch profile for perf-measuring legs: pins the JAX/XLA host environment
# so benchmark numbers are comparable across runs and machines.
#
#   scripts/launch_profile.sh python -m benchmarks.applier_bench --quick
#
# - one XLA host device (the benches measure single-server dispatch, and a
#   multi-device host partitions the BLAS threadpool unpredictably);
#   override with LAUNCH_DEVICES=N for sharding experiments
# - f32 default dtype (the wire format and every reference chain is f32;
#   an x64 default would silently double apply costs)
# - the platform is JAX's own choice (JAX_PLATFORMS is left as the caller
#   set it), so a launch on a TPU host runs on the chip
# - tcmalloc via LD_PRELOAD when present (steadier allocation tails than
#   glibc malloc on the 1-core CI box); silently skipped when absent
set -euo pipefail

DEVICES="${LAUNCH_DEVICES:-1}"
export XLA_FLAGS="--xla_force_host_platform_device_count=${DEVICES}${XLA_FLAGS:+ $XLA_FLAGS}"
export JAX_DEFAULT_DTYPE_BITS="${JAX_DEFAULT_DTYPE_BITS:-32}"

for lib in /usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4 \
           /usr/lib/x86_64-linux-gnu/libtcmalloc.so.4 \
           /usr/lib/libtcmalloc_minimal.so; do
  if [ -e "$lib" ]; then
    export LD_PRELOAD="$lib${LD_PRELOAD:+:$LD_PRELOAD}"
    break
  fi
done

exec "$@"
