#!/usr/bin/env bash
# Pipeline launcher — the `bin/run-pipeline.sh <PipelineClass> <args...>`
# entry point (Ref: bin/run-pipeline.sh wrapping spark-submit, BASELINE.json).
# Here it maps the reference's pipeline class names onto python modules.
#
# The platform is JAX's to choose: JAX_PLATFORMS=cpu runs on the CPU, unset
# takes the TPU when there is one. Compiled programs are kept where
# JAX_COMPILATION_CACHE_DIR says, else in .xla_compile_cache/ of the repo.
#
# A fit takes every chip of the host (the mesh is jax.devices(); rows are
# sharded over it, nothing else chooses it). The ImageNet fit at published
# width on a four-chip host, 8,192 rows a chip:
#   bin/run-pipeline.sh ImageNetSiftLcsFV --gmm-k 256 --synthetic-n 32768 \
#     --synthetic-classes 1000
#
# Env knobs (the KEYSTONE_MEM analog):
#   KEYSTONE_NUM_DEVICES=N         virtual CPU device count (testing meshes)
#   KEYSTONE_NO_FUSE=1             disable chain fusion (debugging)
#   KEYSTONE_AUTO_CACHE=1          profile + auto-insert cache nodes
#   KEYSTONE_CACHE_DIR=path        fitted-prefix store; a rerun with the same
#                                  data + hyperparams skips refits entirely
#                                  (default: .keystone_cache next to the repo;
#                                  set empty to disable)
set -euo pipefail

if [[ $# -lt 1 ]]; then
  echo "usage: $0 <Pipeline> [args...]" >&2
  echo "pipelines: MnistRandomFFT LinearPixels RandomPatchCifar" >&2
  echo "           NewsgroupsPipeline AmazonReviewsPipeline TimitPipeline" >&2
  echo "           VOCSIFTFisher ImageNetSiftLcsFV RandomPatchCifarKernel" >&2
  exit 64
fi

PIPELINE="$1"; shift

case "$PIPELINE" in
  MnistRandomFFT)        MOD=keystone_tpu.pipelines.images.mnist_random_fft ;;
  LinearPixels)          MOD=keystone_tpu.pipelines.images.linear_pixels ;;
  RandomPatchCifar)      MOD=keystone_tpu.pipelines.images.random_patch_cifar ;;
  RandomPatchCifarKernel) MOD=keystone_tpu.pipelines.images.random_patch_cifar_kernel ;;
  NewsgroupsPipeline)    MOD=keystone_tpu.pipelines.text.newsgroups ;;
  AmazonReviewsPipeline) MOD=keystone_tpu.pipelines.text.amazon_reviews ;;
  TimitPipeline)         MOD=keystone_tpu.pipelines.speech.timit ;;
  VOCSIFTFisher)         MOD=keystone_tpu.pipelines.images.voc_sift_fisher ;;
  ImageNetSiftLcsFV)     MOD=keystone_tpu.pipelines.images.imagenet_sift_lcs_fv ;;
  *) echo "unknown pipeline: $PIPELINE" >&2; exit 64 ;;
esac

REPO_DIR="$(cd "$(dirname "${BASH_SOURCE[0]}")/.." && pwd)"
export PYTHONPATH="${REPO_DIR}${PYTHONPATH:+:$PYTHONPATH}"
export KEYSTONE_CACHE_DIR="${KEYSTONE_CACHE_DIR-${REPO_DIR}/.keystone_cache}"

if [[ ! -f "${REPO_DIR}/${MOD//.//}.py" ]]; then
  echo "pipeline $PIPELINE is not implemented yet (module $MOD missing)" >&2
  exit 69
fi

if [[ -n "${KEYSTONE_NUM_DEVICES:-}" ]]; then
  export XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=${KEYSTONE_NUM_DEVICES}"
fi

exec python -m "$MOD" "$@"
