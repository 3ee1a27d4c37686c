"""The host tier's initial rows, made from the seed.

Rows are N(0, 1/dim) float32, drawn in chunks of ``CHUNK_ROWS`` rows. Chunk
``c`` has its own generator, seeded by ``(seed, c)``, so chunks are drawn in
parallel threads (numpy releases the interpreter lock while it fills an
array) and any one chunk can be drawn again on its own.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

CHUNK_ROWS = 1 << 18


def seed_key(seed: int) -> int:
    """A non-negative seed for numpy's SeedSequence, from any whole number."""
    return int(seed) % (1 << 64)


def fill_chunk(out: np.ndarray, seed: int, chunk: int) -> None:
    """Draw chunk ``chunk`` of the table into ``out`` (its rows, in place)."""
    rng = np.random.default_rng([seed_key(seed), chunk])
    rng.standard_normal(out=out, dtype=np.float32)
    out *= np.float32(1.0 / np.sqrt(out.shape[1]))


def make_rows(seed: int, rows: int, dim: int, threads: int = 0) -> np.ndarray:
    """The whole ``(rows, dim)`` float32 table."""
    data = np.empty((rows, dim), np.float32)
    starts = range(0, rows, CHUNK_ROWS)
    threads = threads or max(1, min(16, (os.cpu_count() or 2) - 1))
    with ThreadPoolExecutor(threads) as ex:
        list(ex.map(
            lambda lo: fill_chunk(
                data[lo:lo + CHUNK_ROWS], seed, lo // CHUNK_ROWS
            ),
            starts,
        ))
    return data


def rows_of(seed: int, ids: np.ndarray, dim: int, threads: int = 0) -> np.ndarray:
    """The initial rows of the sorted global ``ids``, drawing only the
    chunks that hold them, one chunk per thread at a time."""
    out = np.empty((ids.size, dim), np.float32)
    chunk_of = ids // CHUNK_ROWS
    cuts = np.flatnonzero(np.diff(chunk_of)) + 1
    groups = np.split(np.arange(ids.size), cuts)

    def one(pos):
        c = int(chunk_of[pos[0]])
        block = np.empty((CHUNK_ROWS, dim), np.float32)
        fill_chunk(block, seed, c)
        out[pos] = block[ids[pos] - c * CHUNK_ROWS]

    threads = threads or max(1, min(16, (os.cpu_count() or 2) - 1))
    with ThreadPoolExecutor(threads) as ex:
        list(ex.map(one, groups))
    return out
