#!/bin/sh
# Usage: ./run.sh [MNIST.conf|MNIST_CONV.conf|LeNet.conf] [key=value ...]
# The confs say dev = tpu, which fails where no TPU is visible: pass
# dev=cpu to train on the CPU (./run.sh MNIST.conf dev=cpu).
# Fetches MNIST if possible; falls back to the synthetic generator in
# zero-egress environments (same idx format, trains the same configs).
set -e
conf=${1:-MNIST.conf}
shift 2>/dev/null || true

have_all() {
    for f in train-images-idx3-ubyte train-labels-idx1-ubyte \
             t10k-images-idx3-ubyte t10k-labels-idx1-ubyte; do
        [ -f "data/$f.gz" ] || return 1
    done
}

fetch_all() {
    command -v wget >/dev/null || return 1
    base=https://ossci-datasets.s3.amazonaws.com/mnist
    for f in train-images-idx3-ubyte train-labels-idx1-ubyte \
             t10k-images-idx3-ubyte t10k-labels-idx1-ubyte; do
        wget -q --timeout=10 --tries=1 "$base/$f.gz" \
            -O "$tmp/$f.gz" || return 1
    done
}

if ! have_all; then
    tmp=$(mktemp -d)
    if fetch_all; then
        mkdir -p data && mv "$tmp"/*.gz data/
        echo "downloaded MNIST"
    else
        echo "download unavailable; generating synthetic MNIST-format data"
        python ../../tools/make_synth_mnist.py --out ./data \
            --train 2000 --test 500
    fi
    rm -rf "$tmp"
fi

mkdir -p models
PYTHONPATH=../.. python -m cxxnet_tpu "$conf" model_dir=models "$@"
