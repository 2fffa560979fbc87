"""Train the paper's five CNNs with sparse backprop and report the
trace-driven accelerator cost model per scenario (the paper's Fig. 15
experiment, end to end: real training → real traces → cycle model).

Run:  PYTHONPATH=src python examples/cnn_training.py [--net vgg16] [--steps 5]
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp

from repro.core.policy import IN_OUT_WR
from repro.data.pipeline import image_batch
from repro.launch.cache import use_compile_cache
from repro.models.cnn import NETWORKS, build_cnn


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--net", default="vgg16", choices=list(NETWORKS))
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--image-size", type=int, default=16)
    ap.add_argument("--width", type=float, default=0.25)
    ap.add_argument("--kernel-impl", default="xla_ref",
                    choices=["xla_ref", "pallas"])
    ap.add_argument("--queue-builder", default="prefix_sum",
                    choices=["prefix_sum", "argsort"],
                    help="compact-queue construction on the pallas impl: "
                         "on-device prefix-sum compaction (default) or the "
                         "argsort reference")
    args = ap.parse_args()
    use_compile_cache()

    model = build_cnn(args.net, image_size=args.image_size, width=args.width,
                      num_classes=100)
    params = model.init(jax.random.key(0))
    policy = IN_OUT_WR.with_(kernel_impl=args.kernel_impl,
                             queue_builder=args.queue_builder)

    @jax.jit
    def step(params, img, labels):
        loss, grads = jax.value_and_grad(
            lambda p: model.loss(p, img, labels, policy))(params)
        params = jax.tree.map(lambda p, g: p - 0.05 * g, params, grads)
        return params, loss

    print(f"training {args.net} with IN+OUT+WR sparse backprop…")
    for i in range(args.steps):
        img, labels = image_batch(0, i, batch=8, image_size=args.image_size,
                                  num_classes=100)
        params, loss = step(params, img, labels)
        print(f"  step {i}: loss {float(loss):.4f}")

    print("\naccelerator cost model (full ImageNet geometry, batch 16):")
    from benchmarks.common import network_totals
    totals = network_totals(args.net)
    dc = totals["DC"]["total_cycles"]
    for sc in ("DC", "IN", "IN_OUT", "IN_OUT_WR"):
        t = totals[sc]
        print(f"  {sc:10s}  {t['iteration_ms']:9.2f} ms/iter   "
              f"speedup {dc / t['total_cycles']:.2f}x   "
              f"energy {t['total_energy_j']:.2f} J")


if __name__ == "__main__":
    main()
