"""AlexNet throughput benchmark.

Full AlexNet (227×227×3, one tower, 16-class head on the synthetic
corpus — the classifier width changes <2% of the FLOPs) trained through
the streaming pipeline: host decode/augment in threads, uint8 windows
shipped to the device, whole fwd+bwd+update scan per window. Timing is
epoch-aligned and includes every stage; the first epoch (compilation)
is excluded, and both the MEDIAN and the BEST of ``n_samples`` whole
epochs are returned (each sampled epoch times every stage
inclusively).

With a real ImageNet tree under ``root.imagenet.loader.base_dir`` the
same benchmark measures real-JPEG decode throughput; the synthetic
corpus (noise + prototype generation, roughly JPEG-decode-priced)
stands in when no data exists (zero-egress environment) and is labelled
by the caller as such.
"""

import time


def alexnet_images_per_sec(n_samples=3):
    import veles.prng as prng
    prng.seed_all(99)
    from veles.config import root
    from veles.loader.base import CLASS_TRAIN
    from veles.znicz_tpu.models import imagenet
    from bench import _run_one_chunk

    root.imagenet.loader.update({
        "minibatch_size": 128, "n_train": 1536, "n_valid": 256,
        "n_classes": 16})
    root.imagenet.decision.max_epochs = 1024
    # patience must exceed warmup+measured epochs: XLAStep clamps
    # chunks to the remaining fail_iterations (see bench.py), and the
    # default 50 < the 56 epochs this bench dispatches
    root.imagenet.decision.fail_iterations = 100000
    wf = imagenet.create_workflow(name="BenchAlexNet")
    wf.initialize(device="xla")
    loader, step = wf.loader, wf.xla_step
    # pin the adaptive ramp's steady state (8 epochs ≈ 2s/dispatch)
    # so the samples time it rather than the ramp
    step.epochs_per_dispatch = 8

    def count(ld):
        return int(ld.minibatch_size) \
            if ld.minibatch_class == CLASS_TRAIN else 0

    import jax
    _run_one_chunk(loader, step, count)     # epoch 1: compile + run
    _run_one_chunk(loader, step, count)     # chunk-ramp compile
    rates = []
    for _ in range(n_samples):
        t0 = time.perf_counter()
        images = _run_one_chunk(loader, step, count)
        jax.block_until_ready(step.params)
        rates.append(images / (time.perf_counter() - t0))
    rates.sort()
    # median AND best (bench.py's key convention)
    return rates[len(rates) // 2], rates[-1]


if __name__ == "__main__":
    # key convention (bench.py module docstring, since round 4):
    # primary "value" = median; best under the explicit _best key
    med, best = alexnet_images_per_sec()
    print('{"metric": "alexnet_synth_images_per_sec", "value": %.1f, '
          '"best": %.1f}' % (med, best))
