#!/usr/bin/env bash
# Round-4 CPU evidence queue (round-3 verdict items 4, 5, 6): the runs that
# do NOT need the chip, sized to this 1-core host (~10 h total):
#   1. cifar10/resnet8 IFCA hard-r rerun on the HARDENED prototype task at
#      round-2's reduced scale (replaces the "superseded with no
#      successor" evidence; defined scale stays on the TPU queue).
#   2. fed_shakespeare/rnn AUE at 10 clients, 1000 samples/client (the
#      round-2 weak item carried over twice; 50-client stays on TPU).
#   3. femnist/cnn Ada at 20 clients on the hardened task (same purpose
#      as 1; 100-client defined scale stays on TPU).
#   4+5. FMoW with a CONV model (cnn): FedDrift vs win-1 on the hardened
#      62-class task (round-3 verdict: fnn-64 was the one model-family
#      downgrade in committed evidence).
# Sentinel semantics: .done on zero exit only.
set -uo pipefail
cd "$(dirname "$0")/.."

FAIL=0
run() { # out_dir args...
  local out="runs/$1"; shift
  if [ -f "$out/.done" ]; then echo "=== skip (done) $out"; return; fi
  echo "=== $(date +%T) $out"
  # replace-in-place reruns: clear the superseded artifact so the fresh
  # metrics can't sit beside a stale one (--flat_out_dir writes directly
  # to $out — no nested auto-named dir, no post-hoc flattening)
  rm -rf "$out"
  if python -m feddrift_tpu run --flat_out_dir --platform cpu --seed 0 \
       --out_dir "$out" "$@"; then
    touch "$out/.done"
  else
    echo "!!! failed $out"
    FAIL=1
  fi
}

# 1. IFCA hard-r on cifar10/resnet8, hardened task, round-2 reduced scale
#    (4 clients, M=2, 3x8 rounds, batch 16 — PARITY.md conv section)
run cifar10-resnet8-softclusterwin-1-hard-r-s0 \
    --dataset cifar10 --model resnet8 --concept_drift_algo softclusterwin-1 \
    --concept_drift_algo_arg hard-r --concept_num 2 --change_points rand \
    --client_num_in_total 4 --client_num_per_round 4 \
    --train_iterations 3 --comm_round 8 --epochs 5 --batch_size 16 \
    --sample_num 64 --lr 0.05 --frequency_of_the_test 2

# 2. AUE on fed_shakespeare/rnn at 10 clients, >=1000 samples/client
run fed_shakespeare-rnn-aue-10c-s0 \
    --dataset fed_shakespeare --model rnn --concept_drift_algo aue \
    --concept_num 3 --change_points rand \
    --client_num_in_total 10 --client_num_per_round 10 \
    --train_iterations 3 --comm_round 20 --epochs 5 --batch_size 32 \
    --sample_num 1000 --lr 0.1 --frequency_of_the_test 5

# 3. Adaptive-FedAvg on femnist/cnn at 20 clients, hardened task
#    (lr 3e-3: the PARITY-documented rate that learns this task)
run femnist-cnn-ada-win-1_iter-s0 \
    --dataset femnist --model cnn --concept_drift_algo ada \
    --concept_drift_algo_arg win-1_iter --concept_num 2 --change_points rand \
    --client_num_in_total 20 --client_num_per_round 10 \
    --train_iterations 5 --comm_round 12 --epochs 5 --batch_size 32 \
    --sample_num 500 --lr 0.003 --frequency_of_the_test 3

# 4. FMoW / cnn FedDrift (canonical packed arg, M=4) — reduced rounds
run fmow-cnn-softcluster-H_A_C_1_10_0-s0 \
    --dataset fmow --model cnn --concept_drift_algo softcluster \
    --concept_drift_algo_arg H_A_C_1_10_0 --concept_num 4 --change_points A \
    --client_num_in_total 10 --client_num_per_round 10 \
    --train_iterations 5 --comm_round 15 --epochs 5 --batch_size 64 \
    --sample_num 500 --lr 0.003 --frequency_of_the_test 5

# 5. FMoW / cnn win-1 baseline, same shape
run fmow-cnn-win-1-s0 \
    --dataset fmow --model cnn --concept_drift_algo win-1 \
    --concept_num 1 --change_points A \
    --client_num_in_total 10 --client_num_per_round 10 \
    --train_iterations 5 --comm_round 15 --epochs 5 --batch_size 64 \
    --sample_num 500 --lr 0.003 --frequency_of_the_test 5

exit $FAIL
