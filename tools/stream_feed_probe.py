"""Can the streaming input pipeline feed the chip? (VERDICT r4 weak #8)

Measures, with the flagship ImageNet featurizer (SIFT+LCS Fisher
vectors, the same jitted chunk program ``run_streaming`` uses):

- ``producer_imgs_per_s``   — host-side batch production alone (synthetic
  render here; tar+JPEG decode when a corpus is staged)
- ``device_imgs_per_s``     — device featurize alone, one resident chunk
- ``e2e_sync_imgs_per_s``   — the round-trip WITHOUT overlap (prefetch=0,
  no decode-ahead thread): the round-4 behavior
- ``e2e_overlap_imgs_per_s``— decode-ahead thread + bounded in-flight
  device chunks (the shipped default)

and classifies the pipeline input-bound vs compute-bound:
min(producer, device) is the overlap ceiling; e2e_overlap should sit
near it, and e2e_sync near the harmonic combination. Writes
STREAM_FEED.json.
"""

from __future__ import annotations

import json
import subprocess
import time

import numpy as np


def main() -> None:
    import jax

    from keystone_tpu.core.runtime import init_backend

    init_backend()
    dev = jax.devices()[0]
    import jax.numpy as jnp

    from keystone_tpu.loaders.imagenet_stream import synthetic_source
    from keystone_tpu.loaders.streaming import (
        ColumnReservoir,
        featurize_stream,
        prefetch_batches,
    )
    from keystone_tpu.models.imagenet_sift_lcs_fv import (
        ImageNetConfig,
        _branch_apply,
        _descriptor_cols,
    )
    from keystone_tpu.core.batching import apply_in_chunks
    from keystone_tpu.models.fisher_common import FisherBranch
    from keystone_tpu.ops.images import GrayScaler, PixelScaler
    from keystone_tpu.ops.lcs import LCSExtractor
    from keystone_tpu.ops.sift import SIFTExtractor
    from keystone_tpu.ops.util import ZipVectors

    on_tpu = dev.platform != "cpu"
    # CPU: tiny shapes — the point of a CPU run is validating the probe
    # itself (SIFT at 256² is minutes/pass on host); the artifact of
    # record comes from the chip session
    n = 4096 if on_tpu else 128
    size = 256 if on_tpu else 64
    conf = ImageNetConfig(
        synthetic=n, synthetic_classes=8, image_size=size,
        stream_batch=256 if on_tpu else 64, chunk_size=32,
        desc_dim=64 if on_tpu else 16, vocab_size=16 if on_tpu else 4,
        sift_scales=5 if on_tpu else 2,
        num_pca_samples=50_000, num_gmm_samples=50_000,
    )

    gray = PixelScaler() >> GrayScaler()
    sift = SIFTExtractor(num_scales=conf.sift_scales)
    lcs = LCSExtractor(
        stride=conf.lcs_stride, stride_start=conf.lcs_border,
        sub_patch_size=conf.lcs_patch,
    )
    sift_fn = jax.jit(lambda b: sift(gray(b)))
    lcs_fn = jax.jit(lambda b: lcs(PixelScaler()(b)))
    sift_branch = FisherBranch(
        conf.desc_dim, conf.vocab_size, conf.num_pca_samples,
        conf.num_gmm_samples, conf.seed,
    )
    lcs_branch = FisherBranch(
        conf.desc_dim, conf.vocab_size, conf.num_pca_samples,
        conf.num_gmm_samples, conf.seed + 100,
    )

    source = synthetic_source(conf, "train")

    # quick branch fit from the first batch's descriptor columns, exactly
    # like run_streaming pass 1 but truncated — the probe measures
    # throughput, not accuracy
    res_s, res_l = (
        ColumnReservoir(conf.num_pca_samples, 0),
        ColumnReservoir(conf.num_gmm_samples, 1),
    )
    first = next(source())[0]
    res_s.add(_descriptor_cols(apply_in_chunks(sift_fn, first, conf.chunk_size)))
    res_l.add(_descriptor_cols(apply_in_chunks(lcs_fn, first, conf.chunk_size)))
    sift_branch.fit_from_samples(res_s.sample())
    lcs_branch.fit_from_samples(res_l.sample())

    featurize_chunk = jax.jit(
        lambda b: ZipVectors()(
            [
                _branch_apply(sift_branch, sift_fn(b)),
                _branch_apply(lcs_branch, lcs_fn(b)),
            ]
        )
    )

    # warm the executable
    warm = jnp.zeros(
        (conf.chunk_size, conf.image_size, conf.image_size, 3), jnp.float32
    )
    jax.block_until_ready(featurize_chunk(warm))

    out = {
        "backend": dev.platform,
        "device": str(dev.device_kind) if hasattr(dev, "device_kind") else "",
        "n_images": n,
        "stream_batch": conf.stream_batch,
        "chunk_size": conf.chunk_size,
    }

    # 1. producer alone
    t = time.perf_counter()
    got = 0
    for imgs, _ in source():
        got += len(imgs)
    out["producer_imgs_per_s"] = round(got / (time.perf_counter() - t), 1)

    # 2. device alone (resident chunk)
    iters = max(n // conf.chunk_size, 8)
    t = time.perf_counter()
    for _ in range(iters):
        r = featurize_chunk(warm)
    jax.block_until_ready(r)
    out["device_imgs_per_s"] = round(
        conf.chunk_size * iters / (time.perf_counter() - t), 1
    )

    def image_batches():
        for imgs, _ in source():
            yield imgs

    # 3. synchronous round trip (round-4 behavior)
    t = time.perf_counter()
    f = featurize_stream(
        image_batches(), featurize_chunk, chunk_size=conf.chunk_size,
        prefetch=0,
    )
    out["e2e_sync_imgs_per_s"] = round(n / (time.perf_counter() - t), 1)

    # 4. overlapped (decode-ahead thread + in-flight device chunks)
    t = time.perf_counter()
    f2 = featurize_stream(
        prefetch_batches(image_batches(), depth=2), featurize_chunk,
        chunk_size=conf.chunk_size,
    )
    out["e2e_overlap_imgs_per_s"] = round(n / (time.perf_counter() - t), 1)
    np.testing.assert_allclose(f, f2, rtol=1e-5, atol=1e-5)

    ceiling = min(out["producer_imgs_per_s"], out["device_imgs_per_s"])
    out["overlap_ceiling_imgs_per_s"] = ceiling
    out["bound"] = (
        "input-bound"
        if out["producer_imgs_per_s"] < out["device_imgs_per_s"]
        else "compute-bound"
    )
    out["overlap_efficiency"] = round(
        out["e2e_overlap_imgs_per_s"] / ceiling, 3
    )
    out["git_sha"] = subprocess.run(
        ["git", "rev-parse", "HEAD"], capture_output=True, text=True
    ).stdout.strip()

    with open("STREAM_FEED.json", "w") as fh:
        json.dump(out, fh, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
