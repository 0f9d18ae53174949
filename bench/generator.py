"""The one traffic generator: images and arrival times from a seed.

A mix is a data file under ``traffic/``; a cell's file may override its
numbers.  Two loops exist:

* ``closed``: ``outstanding`` clients, each sending its next image as
  soon as its previous answer is back (bulk classification);
* ``open``: arrivals on a schedule, whatever the server does.  The gaps
  are exponential, scaled so that exactly ``rate_per_s * seconds``
  requests fall in the window: every seed gives the same amount of work
  in another order.  This is a Poisson process conditioned on its count.
"""
from __future__ import annotations

import numpy as np

#: elements of the seeded pixel buffer that images are cut from
POOL_ELEMENTS = 1 << 22
#: offset step between consecutive images: 64-byte aligned, as a buffer
#: fresh from a client would be, and an odd multiple of 16 elements, so the
#: offsets of the first POOL_ELEMENTS / 16 requests are all different
OFFSET_STEP = 16 * 7919


class Images:
    """Distinct images as views into one seeded buffer of pixels.

    Image ``i`` starts ``i * OFFSET_STEP`` elements into the buffer
    (modulo its length): no two of a run's first 262,144 requests carry
    the same image, and cutting one costs no copy.  Pixels are uniform in
    [-1, 1], the range MobileNet's preprocessing gives.
    """

    def __init__(self, shape, seed: int):
        self.shape = tuple(shape)
        self.size = int(np.prod(self.shape))
        rng = np.random.default_rng((seed, 1))
        n = POOL_ELEMENTS + self.size
        raw = np.empty(n + 16, np.float32)
        start = (-raw.ctypes.data % 64) // 4
        self._buf = raw[start:start + n]
        self._buf[:] = rng.uniform(-1.0, 1.0, n)

    def __getitem__(self, i: int) -> np.ndarray:
        off = (i * OFFSET_STEP) % POOL_ELEMENTS
        return self._buf[off:off + self.size].reshape(self.shape)

    def batch(self, ids) -> np.ndarray:
        return np.stack([self[int(i)] for i in ids])


def arrival_offsets(rate_per_s: float, seconds: float,
                    seed: int) -> np.ndarray:
    """Due times, in seconds from the window's start, of an open loop."""
    n = int(round(rate_per_s * seconds))
    if n < 1:
        raise ValueError(f"{rate_per_s}/s over {seconds} s sends nothing")
    rng = np.random.default_rng((seed, 2))
    t = np.cumsum(rng.exponential(1.0 / rate_per_s, size=n + 1))
    return t[:n] * (seconds / t[n])


def sample_key(seed: int, rid: int) -> int:
    """A seeded pseudo-random 64-bit key per request (splitmix64): the
    requests with the smallest keys form the seed's sample to compare."""
    z = (seed * 0x9E3779B97F4A7C15 + rid + 1) & 0xFFFFFFFFFFFFFFFF
    z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & 0xFFFFFFFFFFFFFFFF
    z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & 0xFFFFFFFFFFFFFFFF
    return z ^ (z >> 31)
