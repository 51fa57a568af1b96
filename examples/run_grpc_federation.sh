#!/usr/bin/env bash
# Multi-process federation over gRPC on one host (ref
# run_fedavg_distributed_pytorch.sh:16-35, which wraps mpirun; here each
# participant is a plain OS process — clients first, server last, but any
# order works: the first send per peer blocks until the peer is up).
#
# One process per chip: client ranks (1..K) pin themselves to the host CPU
# (fedml_tpu/cli.py run()), so on a TPU host only the rank-0 server opens
# the chip.
#
# Cross-host: give every process the same --ip_config CSV ("rank,ip" lines,
# ref grpc_ipconfig.csv) and run each rank on its machine.
set -euo pipefail

ROUNDS=${ROUNDS:-5}
CLIENTS=${CLIENTS:-2}
PORT=${PORT:-9400}

common=(--algorithm fedavg --runtime grpc
        --dataset synthetic --model lr
        --client_num_in_total "$CLIENTS" --client_num_per_round "$CLIENTS"
        --comm_round "$ROUNDS" --batch_size 16 --lr 0.1
        --base_port "$PORT" --seed 1)

pids=()
trap '[ "${#pids[@]}" -gt 0 ] && kill "${pids[@]}" 2>/dev/null || true' EXIT
for rank in $(seq 1 "$CLIENTS"); do
  python -m fedml_tpu "${common[@]}" --rank "$rank" &
  pids+=($!)
done

python -m fedml_tpu "${common[@]}" --rank 0   # server: blocks until done

for pid in "${pids[@]}"; do wait "$pid"; done
echo "federation complete"
