#!/usr/bin/env bash
# Production launcher for the serving CLI.
#
#   bash src/repro/launch/run.sh --arch mixtral-8x7b --offload \
#       --requests 64 --rate 4 [...]
#
# Everything after the script name is forwarded verbatim to
# `python -m repro.launch.serve`.  Override the module with
# REPRO_MODULE (e.g. REPRO_MODULE=repro.launch.compress for the
# offline pipeline, or REPRO_MODULE=benchmarks.bench_serving below a
# checkout root).
#
# Knobs (all optional, env-overridable):
#   REPRO_HOST_DEVICES=N   force N XLA host-platform devices (CPU
#                          expert-parallel runs, e.g. `--mesh ep=8`)
#   REPRO_KERNEL_IMPL      kernel dispatch policy: auto | pallas |
#                          pallas_interpret | ref (see kernels/ops.py)
#   REPRO_AUTOTUNE=1       time the fused-kernel tile candidates on
#                          this device at boot and persist the winners
#                          (kernels/autotune.py); default = table lookup
#   XLA_EXTRA_FLAGS        appended to the XLA_FLAGS this script sets
set -euo pipefail

repo_root="$(cd -- "$(dirname -- "${BASH_SOURCE[0]}")/../../.." && pwd)"

# -- allocator: tcmalloc if the host has it (large stack/plane allocs churn
# glibc malloc), and silence its large-alloc reports — expert stacks are
# routinely gigabytes
for so in /usr/lib/x86_64-linux-gnu/libtcmalloc.so.4 \
          /usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4; do
    if [[ -e "$so" ]]; then
        export LD_PRELOAD="$so${LD_PRELOAD:+:$LD_PRELOAD}"
        export TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD=60000000000
        break
    fi
done

# -- logging: XLA/TSL banner noise off unless the caller asked for it
export TF_CPP_MIN_LOG_LEVEL="${TF_CPP_MIN_LOG_LEVEL:-4}"

# -- dtypes: f32 end to end (never silently promote to f64 on CPU)
export JAX_ENABLE_X64="${JAX_ENABLE_X64:-0}"
export JAX_DEFAULT_DTYPE_BITS="${JAX_DEFAULT_DTYPE_BITS:-32}"

# -- XLA flags: step markers at the outer while loop (the decode scan) so
# profiles bucket per decode chunk; REPRO_HOST_DEVICES forces a CPU
# device mesh.  XLA_FLAGS holds only flags jaxlib registers (it aborts on
# any other).  TPU compiler flags go to libtpu through LIBTPU_INIT_ARGS,
# appended to what the machine already sets there, never replacing it.
xla_flags="--xla_step_marker_location=STEP_MARK_AT_TOP_LEVEL_WHILE_LOOP"
if [[ -n "${REPRO_HOST_DEVICES:-}" ]]; then
    xla_flags="$xla_flags --xla_force_host_platform_device_count=${REPRO_HOST_DEVICES}"
fi
export XLA_FLAGS="$xla_flags${XLA_EXTRA_FLAGS:+ $XLA_EXTRA_FLAGS}${XLA_FLAGS:+ $XLA_FLAGS}"
if compgen -G "/dev/accel*" > /dev/null || [[ -c /dev/vfio/vfio ]]; then
    # latency-hiding scheduling for the offload/collective overlap
    export LIBTPU_INIT_ARGS="${LIBTPU_INIT_ARGS:+$LIBTPU_INIT_ARGS }--xla_tpu_enable_latency_hiding_scheduler=true"
fi

export PYTHONPATH="$repo_root/src${PYTHONPATH:+:$PYTHONPATH}"

exec /usr/bin/env python3 -m "${REPRO_MODULE:-repro.launch.serve}" "$@"
