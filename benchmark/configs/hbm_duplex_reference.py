"""Plain reference of the ``hbm_duplex`` configuration: what every chunk
must hold, in numpy alone, independent of the transport and of the
device-side generator the runner uses (benchmark/runners/transport.py makes
the chip's payloads with jax.numpy from the same published formula; this
file decides whether the bytes that arrived are right).

A chunk from endpoint ``src`` to ``dst`` with index ``i`` is ``nbytes`` of
a 32-bit hash of the lane number, salted by (seed, src, dst, i), whose
first 16 bytes are replaced by a header of four little-endian uint32:
round, index, seed (low 32 bits), and a tag word (src, dst, seed's high
bits).  Same data, same answers: an exact comparison, limit 0.
"""

from __future__ import annotations

import numpy as np

HEADER_BYTES = 16
_M32 = 0xFFFFFFFF


def salt(seed: int, src: int, dst: int, index: int) -> int:
    x = (int(seed) * 0x9E3779B1 + src * 0x85EBCA6B + dst * 0xC2B2AE35
         + index * 0x27D4EB2F + 0x165667B1) & _M32
    x ^= x >> 15
    x = (x * 0x2C1B3C6D) & _M32
    x ^= x >> 12
    return x


def body_words(seed: int, src: int, dst: int, index: int, nwords: int) -> np.ndarray:
    """uint32[nwords]: murmur3's finalizer over lane number + salt."""
    x = np.arange(nwords, dtype=np.uint32) * np.uint32(0x9E3779B1)
    x += np.uint32(salt(seed, src, dst, index))
    x ^= x >> np.uint32(16)
    x *= np.uint32(0x85EBCA6B)
    x ^= x >> np.uint32(13)
    x *= np.uint32(0xC2B2AE35)
    x ^= x >> np.uint32(16)
    return x


def header_words(round_no: int, index: int, seed: int, src: int, dst: int) -> np.ndarray:
    tag = (0xB0000000 | (src << 20) | (dst << 12) | ((int(seed) >> 32) & 0xFFF)) & _M32
    return np.array([round_no & _M32, index & _M32, int(seed) & _M32, tag],
                    dtype=np.uint32)


def chunk(seed: int, src: int, dst: int, index: int, round_no: int,
          nbytes: int) -> np.ndarray:
    """The whole chunk as uint8[nbytes]."""
    words = body_words(seed, src, dst, index, nbytes // 4)
    words[:4] = header_words(round_no, index, seed, src, dst)
    return words.view(np.uint8)


def header_ok(first16, round_no: int, index: int, seed: int, src: int,
              dst: int) -> bool:
    got = np.ascontiguousarray(np.asarray(first16, np.uint8)).view(np.uint32)
    return bool(np.array_equal(got, header_words(round_no, index, seed, src, dst)))


def mismatched_bytes(got, seed: int, src: int, dst: int, index: int,
                     round_no: int) -> int:
    """How many bytes of ``got`` differ from the chunk it should be."""
    got = np.asarray(got).reshape(-1).view(np.uint8)
    want = chunk(seed, src, dst, index, round_no, got.size)
    return int(np.count_nonzero(got != want))
