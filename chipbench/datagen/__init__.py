"""Generators, one module per schema, found by the ``schema`` name in a
configuration file.  Each exposes ``ROWS_PER_SF`` and
``generate(data_dir, scale, seed, tables) -> {table: rows}``."""
import zlib

import numpy as np


def table_rng(seed: int, table: str) -> np.random.Generator:
    """One stream per (seed, table): a subset of the tables gets the
    same rows as the whole schema would."""
    return np.random.default_rng([int(seed), zlib.crc32(table.encode())])
