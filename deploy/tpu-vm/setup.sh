#!/usr/bin/env bash
# Provision a TPU VM and run the full RAG stack on it.
# The TPU-native replacement for the reference's GPU deployment story
# (docker compose + NIM containers): on TPU VMs the engine runs directly
# on the host (jax[tpu] ships in the VM image) and the app containers
# ride alongside.
#
# Usage:
#   ./setup.sh create   # create the TPU VM
#   ./setup.sh install  # install the framework + systemd units on the VM
#   ./setup.sh bench [cell]  # run one cell of the benchmark on the VM (benchmark/README.md)
set -euo pipefail

TPU_NAME="${TPU_NAME:-gaie-tpu-v5e}"
ZONE="${ZONE:-us-west4-a}"
ACCEL="${ACCEL:-v5litepod-8}"
VERSION="${VERSION:-v2-alpha-tpuv5-lite}"
REPO_URL="${REPO_URL:-$(git -C "$(dirname "$0")/../.." remote get-url origin 2>/dev/null || echo .)}"

create() {
  gcloud compute tpus tpu-vm create "$TPU_NAME" \
    --zone="$ZONE" \
    --accelerator-type="$ACCEL" \
    --version="$VERSION"
}

run_on_vm() {
  gcloud compute tpus tpu-vm ssh "$TPU_NAME" --zone="$ZONE" --command="$1"
}

install() {
  run_on_vm "
    set -e
    sudo apt-get update -qq && sudo apt-get install -y -qq git python3-pip
    git clone ${REPO_URL} gaie-tpu || (cd gaie-tpu && git pull)
    cd gaie-tpu
    pip install -e . 'jax[tpu]' -f https://storage.googleapis.com/jax-releases/libtpu_releases.html
    sudo cp deploy/tpu-vm/engine-server.service /etc/systemd/system/
    sudo cp deploy/tpu-vm/chain-server.service /etc/systemd/system/
    sudo cp deploy/tpu-vm/playground.service /etc/systemd/system/
    sudo systemctl daemon-reload
    sudo systemctl enable --now engine-server chain-server playground
  "
}

bench() {
  run_on_vm "cd gaie-tpu && python3 benchmark/run.py --workload $1 --seed 1 --seconds 45 --trace 0"
}

case "${1:-}" in
  create) create ;;
  install) install ;;
  bench) bench "${2:-mistral7b.decode-closed64}" ;;
  *) echo "usage: $0 {create|install|bench [cell]}" >&2; exit 2 ;;
esac
