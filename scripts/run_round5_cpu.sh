#!/usr/bin/env bash
# Round-5 CPU evidence queue: conv runs on the NEW '-smooth' conv-learnable
# synthetic family (round-4 verdict item 3 — ends the single-source-of-conv
# -truth problem), ordered so compile-cache hits come first:
#   1. cifar10-smooth / resnet8 IFCA hard-r — SAME shapes as the realdigits
#      rerun (4 clients, M=2, 2x6 rounds, b32) so the fused programs are
#      already in .jax_cache.
#   2. femnist-smooth / cnn Adaptive-FedAvg — SAME shapes as the round-4
#      real-digits run (20 clients, 5x12 rounds, b32): cache hit.
#   3+4. fmow-smooth / cnn FedDrift vs win-1 — the conv FMoW pair (verdict
#      item: the committed quartet is fnn-only). Fresh compile, sized to
#      the 1-core host (b32, 5x8 rounds).
#   5. femnist / cnn Ada at 50 clients on REAL digits (verdict item 4,
#      half of config 4's defined scale) — fresh compile, queued last.
# Sentinel semantics: .done on zero exit only.
set -uo pipefail
cd "$(dirname "$0")/.."

FAIL=0
run() { # out_dir args...
  local out="runs/$1"; shift
  if [ -f "$out/.done" ]; then echo "=== skip (done) $out"; return; fi
  rm -rf "$out"
  echo "=== $(date +%T) $out"
  if python -m feddrift_tpu run --flat_out_dir --platform cpu --seed 0 \
       --out_dir "$out" "$@"; then
    touch "$out/.done"
  else
    echo "!!! failed $out"
    FAIL=1
  fi
}

# 1. IFCA hard-r on cifar10-smooth/resnet8 (shapes = realdigits rerun)
run cifar10-smooth-resnet8-hard-r-s0 \
    --dataset cifar10-smooth --model resnet8 \
    --concept_drift_algo softclusterwin-1 --concept_drift_algo_arg hard-r \
    --concept_num 2 --change_points rand \
    --client_num_in_total 4 --client_num_per_round 4 \
    --train_iterations 2 --comm_round 6 --epochs 5 --batch_size 32 \
    --sample_num 500 --lr 0.05 --frequency_of_the_test 2

# 2. Adaptive-FedAvg on femnist-smooth/cnn (shapes = round-4 real run)
run femnist-smooth-cnn-ada-win-1_iter-s0 \
    --dataset femnist-smooth --model cnn --concept_drift_algo ada \
    --concept_drift_algo_arg win-1_iter --concept_num 2 --change_points rand \
    --client_num_in_total 20 --client_num_per_round 10 \
    --train_iterations 5 --comm_round 12 --epochs 5 --batch_size 32 \
    --sample_num 500 --lr 0.003 --frequency_of_the_test 3

# 3. FMoW-smooth / cnn FedDrift (canonical packed arg, M=4)
run fmow-smooth-cnn-softcluster-H_A_C_1_10_0-s0 \
    --dataset fmow-smooth --model cnn --concept_drift_algo softcluster \
    --chunk_rounds false \
    --concept_drift_algo_arg H_A_C_1_10_0 --concept_num 4 --change_points A \
    --client_num_in_total 10 --client_num_per_round 10 \
    --train_iterations 2 --comm_round 4 --epochs 5 --batch_size 32 \
    --sample_num 500 --lr 0.003 --frequency_of_the_test 4

# 4. FMoW-smooth / cnn win-1 baseline, same shape (M=1)
run fmow-smooth-cnn-win-1-s0 \
    --dataset fmow-smooth --model cnn --concept_drift_algo win-1 \
    --chunk_rounds false \
    --concept_num 1 --change_points A \
    --client_num_in_total 10 --client_num_per_round 10 \
    --train_iterations 2 --comm_round 4 --epochs 5 --batch_size 32 \
    --sample_num 500 --lr 0.003 --frequency_of_the_test 4

# 5. Ada on femnist/cnn at 50 clients, REAL digits (half defined scale)
run femnist-cnn-ada-win-1_iter-50c-s0 \
    --dataset femnist --model cnn --concept_drift_algo ada \
    --concept_drift_algo_arg win-1_iter --concept_num 2 --change_points rand \
    --client_num_in_total 50 --client_num_per_round 10 \
    --train_iterations 3 --comm_round 12 --epochs 5 --batch_size 32 \
    --sample_num 500 --lr 0.003 --frequency_of_the_test 3 \
    --data_dir data/real_formats

exit $FAIL
