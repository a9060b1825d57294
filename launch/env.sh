# Tuned runtime preset for serving/benchmark runs.
#
#     source launch/env.sh
#     python -m benchmarks.run --only hotpath --emit-bench-json
#
# Safe to source anywhere: every knob is guarded (tcmalloc only when
# the library exists, user-set values win) so the preset degrades to a
# no-op on minimal containers rather than breaking the interpreter.

# bash/zsh know the sourced-file path; plain sh does not — there,
# fall back to $PWD (i.e. source from the repo root)
_REPRO_ROOT="$(cd "$(dirname "${BASH_SOURCE:-$0}")/.." 2>/dev/null && pwd)"
[ -d "${_REPRO_ROOT}/src/repro" ] || _REPRO_ROOT="$(pwd)"
case ":${PYTHONPATH:-}:" in
  *":${_REPRO_ROOT}/src:"*) ;;
  *) export PYTHONPATH="${_REPRO_ROOT}/src${PYTHONPATH:+:$PYTHONPATH}" ;;
esac

# tcmalloc: long-lived serving processes fragment glibc malloc under
# the engine's churn of small batch buffers; tcmalloc holds steady.
# The report threshold silences "large alloc" spam for model weights.
for _lib in /usr/lib/x86_64-linux-gnu/libtcmalloc.so.4 \
            /usr/lib/libtcmalloc.so.4 /usr/lib64/libtcmalloc.so.4; do
  if [ -e "${_lib}" ] && [ -z "${LD_PRELOAD:-}" ]; then
    export LD_PRELOAD="${_lib}"
    export TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD=60000000000
    break
  fi
done
unset _lib

# quiet the TF/XLA C++ banner noise that otherwise floods bench logs
export TF_CPP_MIN_LOG_LEVEL="${TF_CPP_MIN_LOG_LEVEL:-4}"

# dtype pinning: the kernels accumulate in f32 by construction; x64
# mode would silently double every buffer and halve throughput
export JAX_ENABLE_X64="${JAX_ENABLE_X64:-0}"
export JAX_DEFAULT_DTYPE_BITS="${JAX_DEFAULT_DTYPE_BITS:-32}"

# kernel tier: leave REPRO_KERNEL_TIER unset to follow the platform
# (tpu on a TPU backend; interpret/ref on the CPU); export it to pin one.
unset _REPRO_ROOT
