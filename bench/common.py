"""Pieces shared by the generators, the algorithm drivers and the harness:
the graph a generator hands over, seeding, and precision rounding for
the plain references and their lower-precision controls."""
from __future__ import annotations

import sys
import time
from dataclasses import dataclass
from typing import Optional

import numpy as np


def log(msg: str) -> None:
    """Progress on stderr, with the wall-clock time."""
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


@dataclass
class Graph:
    """A directed graph as the benchmark generated it, on the host.

    ``src``/``dst`` are int32 edge slots (every arc of the deployment,
    self-loops and duplicates included), sorted by ``src``; ``weights``
    is float32 per slot or None for unweighted graphs."""
    n: int
    src: np.ndarray
    dst: np.ndarray
    weights: Optional[np.ndarray] = None
    partitions: int = 1

    @property
    def edge_slots(self) -> int:
        return int(len(self.src))

    def edge_list(self) -> np.ndarray:
        return np.stack([self.src, self.dst], axis=1)


def seed_key(seed: int, stream: int):
    """A JAX PRNG key for one random stream of a run. Any whole number is a
    valid seed: it goes through NumPy's SeedSequence, so seeds past 32 bits
    stay distinct (``jax.random.key`` truncates them)."""
    import jax
    import jax.numpy as jnp
    words = np.random.SeedSequence(seed % (1 << 64),
                                   spawn_key=(stream,)).generate_state(
                                       2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words))


def exact(x):
    """The references' own precision: float64, no rounding."""
    return np.asarray(x, np.float64)


def bfloat16(x):
    """Round every intermediate to bfloat16, the precision a later change
    could be tempted to keep vertex values in (the control)."""
    import ml_dtypes
    return np.asarray(x, np.float64).astype(ml_dtypes.bfloat16) \
        .astype(np.float64)


@dataclass
class Outcome:
    """What an algorithm driver hands back after the window.

    ``work`` is the directed edge slots processed in the window (the
    traffic's unit of work times the graph's slots); ``steps`` the
    supersteps the window ran; ``answers`` one array of final vertex values
    per answer due in the window (None for a job that never halted);
    ``supersteps`` the engine's superstep count behind the last answer;
    ``value_channels`` the vertex value channels the program sends from."""
    work: float
    steps: int
    answers: list
    supersteps: int
    value_channels: int
