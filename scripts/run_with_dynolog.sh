#!/usr/bin/env bash
# Job wrapper: run a JAX training command with a dynologd daemon alongside
# (reference analog: scripts/slurm/run_with_dyno_wrapper.sh:20-32 — start
# daemon with the IPC monitor, export the env the in-app shim needs, exec
# the job, tear the daemon down on exit). Works under SLURM (srun this
# script) or on a TPU VM directly.
set -euo pipefail

REPO_DIR="$(cd "$(dirname "${BASH_SOURCE[0]}")/.." && pwd)"
DYNOLOGD="${DYNOLOGD:-$REPO_DIR/build/src/dynologd}"
DYNOLOG_PORT="${DYNOLOG_PORT:-1778}"
DYNOLOG_ENDPOINT="${DYNOLOG_ENDPOINT:-dynolog}"
LOG_FILE="${DYNOLOG_LOG_FILE:-/tmp/dynolog_tpu_$$.jsonl}"

if [[ $# -lt 1 ]]; then
  echo "usage: $0 <training command...>" >&2
  exit 1
fi

# The daemon starts before the job, so the TPU backend is named: `grpc`
# binds the job's runtime metric service (localhost:8431) when it comes
# up, where the default `auto` would resolve now, find no runtime yet, and
# stay without TPU metrics.
"$DYNOLOGD" \
  --port="$DYNOLOG_PORT" \
  --enable_ipc_monitor \
  --ipc_endpoint_name="$DYNOLOG_ENDPOINT" \
  --enable_tpu_monitor \
  --tpu_metric_backend=grpc \
  --json_log_file="$LOG_FILE" \
  --nouse_JSON &
DAEMON_PID=$!
trap 'kill "$DAEMON_PID" 2>/dev/null || true' EXIT

# Env consumed by the dynolog_tpu Python shim (and honored by libkineto
# clients for wire-compat): which daemon endpoint to register with.
export DYNOLOG_ENDPOINT
export KINETO_USE_DAEMON=1

"$@"
