#!/usr/bin/env bash
# v4-32 launch rehearsal on the fake mesh: pod time — whenever it exists —
# must start from a TESTED script, not playbook prose. Two acts, both
# executable with zero TPU hardware:
#
#   1. The v4-32 PROCESS GEOMETRY: a 4-host × 8-device gang (32 global
#      devices) launched exactly the way docs/POD_PLAYBOOK.md launches a
#      real pod — dlsupervise providing the DLS_* rendezvous contract,
#      each "host" a process with 8 fake CPU devices, running the
#      config-2 driver end-to-end (pure-DP data=32 layout).
#   2. The config-5 MESH LAYOUT at pod scale: fsdp × tensor = 32 over 32
#      fake devices through the real driver flags (fsdp=16 tensor=2 here —
#      the tiny variant has 2 kv heads; the POD_PLAYBOOK 7B row's
#      tensor=4 divides its 32 kv heads fine on a real pod).
#
#   bash tools/pod_rehearsal.sh           # both acts
#   bash tools/pod_rehearsal.sh 2         # one
#
# Prints one row per act.
set -u -o pipefail
cd "$(dirname "$0")/.."
export JAX_PLATFORMS=cpu
export PYTHONPATH="$(pwd):${PYTHONPATH:-}"

log_row() {  # name, ok, secs
  printf '| %s | %s | %s | %ss |\n' \
    "$(date -u +%Y-%m-%dT%H:%M:%SZ)" "$1" "$2" "$3"
}

overall=0
if [ $# -eq 0 ]; then set -- 1 2; fi
for act in "$@"; do
  t0=$(date +%s)
  case "$act" in
    1)
      # v4-32 = 4 hosts × 8 chips. dlsupervise exports DLS_COORDINATOR /
      # DLS_NUM_PROCESSES / DLS_PROCESS_ID; the driver's default
      # master("auto") joins the gang exactly as on real hosts. The env
      # keeps 8 fake devices PER PROCESS (unlike smoke.sh's single
      # process, this exercises the multi-process assembly in put_global).
      out=$(XLA_FLAGS="--xla_force_host_platform_device_count=8" \
        dlsupervise -n 4 --max-restarts 0 -- \
        python examples/train_resnet.py --variant resnet18 --image-size 32 \
          --steps 3 --batch-size 32 2>&1)
      rc=$?
      name="pod-rehearsal-1 (4x8 gang, config-2 DP)"
      pat="train summary"
      ;;
    2)
      # master stays "auto" (the pod form): the driver pins mesh.data=1
      # and fsdp*tensor=32 absorbs all fake devices — local[N] would ask
      # for N MORE data-parallel executors on top of that. tensor=2 (not
      # the playbook's 7B tensor=4) because the TINY variant has 2 kv
      # heads; 7B's 32 kv heads divide 4 fine on a real pod.
      out=$(XLA_FLAGS="--xla_force_host_platform_device_count=32" \
        python examples/train_llama_lora.py \
          --variant tiny --fsdp 16 --tensor 2 --batch-size 16 \
          --steps 2 2>&1)
      rc=$?
      name="pod-rehearsal-2 (fsdp=16 x tensor=2, config-5)"
      pat="tokens_per_sec_per_chip"
      ;;
    *)
      echo "unknown act '$act'; valid: 1 2" >&2; exit 2 ;;
  esac
  secs=$(( $(date +%s) - t0 ))
  if [ $rc -eq 0 ] && grep -q "$pat" <<<"$out"; then
    log_row "$name" yes "$secs"
  else
    log_row "$name" "NO (rc=$rc)" "$secs"
    overall=1
    echo "---- act $act failed; last lines:"; tail -8 <<<"$out"
  fi
done
exit $overall
