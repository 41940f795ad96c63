#!/usr/bin/env python
"""What arrives on the device is what the native loader built.

The loader lends its batch buffers (sparknet_tpu/native): a buffer must
not be rewritten while a transfer still reads it.  This drives two
loaders of one seed at the benchmark cell's sizes (batches of 1024
crops of 227 from 256x256 uint8 images, mirror, mean image): one
through ``prefetch_to_device`` with its default ``jax.device_put``, each
staged array read back from the device two batches *after* it was
handed over, as a step one ahead holds it; one plain, summed at
hand-out.  Prints a crc32 pair per batch and one last line with a
digest of the staged ones; exit 1 on any difference.  The digest of
another checkout's run (the parent's, whose loader copies every batch
out) must be the same: copy this file into that checkout's ``scripts/``
and run it there.
"""

import hashlib
import os
import sys
import zlib

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BATCHES = 32  # more than twice the buffers a loader ever holds (10-12)
IN_FLIGHT = 2


def main() -> int:
    import jax

    from sparknet_tpu import native
    from sparknet_tpu.data.prefetch import prefetch_to_device

    rng = np.random.default_rng(26)
    images = rng.integers(0, 256, (2048, 256, 256, 3), dtype=np.uint8)
    labels = rng.integers(0, 1000, 2048).astype(np.int32)
    mean = rng.normal(110.0, 20.0, (256, 256, 3)).astype(np.float32)

    def loader():
        return native.NativeLoader(
            images, labels, 1024, crop=227, train=True, mirror=True,
            mean_image=mean, seed=26,
        )

    staged_loader, plain_loader = loader(), loader()
    feed = prefetch_to_device(staged_loader, size=2)
    held, digest, wrong = [], hashlib.sha256(), 0

    def read_back(index, staged, host_crc):
        nonlocal wrong
        jax.block_until_ready(staged)
        # what comes back from a TPU may be strided; the crc is of C order
        crc = zlib.crc32(np.ascontiguousarray(staged["data"]))
        digest.update(crc.to_bytes(4, "big"))
        wrong += crc != host_crc
        print(f"batch {index:2d} staged {crc:08x} at hand-out {host_crc:08x}")

    try:
        for index in range(BATCHES):
            staged, plain = next(feed), next(plain_loader)
            held.append((index, staged, zlib.crc32(plain["data"])))
            del plain
            if len(held) > IN_FLIGHT:
                read_back(*held.pop(0))
        while held:
            read_back(*held.pop(0))
        stats = staged_loader.stats()
    finally:
        feed.close()
        staged_loader.close()
        plain_loader.close()
    print(
        f"feed checksums: {jax.devices()[0].device_kind}, {BATCHES} batches, "
        f"{wrong} differ, buffers allocated "
        f"{stats.get('buffers_allocated', 'n/a (every batch copied)')}, "
        f"digest {digest.hexdigest()[:16]}"
    )
    return int(wrong > 0)


if __name__ == "__main__":
    sys.exit(main())
