"""What every task shares: the traffic file, the seed's key, and the job a
task makes (``tasks/<name>.py``, named by the configuration's ``"task"``).

A traffic file holds the round's parameters (``fft``, ``strategy``, the
warm-up) and, under ``data``, the parameters of its task's generator. A job
holds the public, private and test splits, the private rows of each client,
and the number of histogram bins the FedAuto rows are counted over.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Any, Dict, List

import jax
import numpy as np

HERE = Path(__file__).resolve().parent


def load_traffic(name: str, here: Path = HERE) -> Dict[str, Any]:
    return json.loads((here / "traffic" / f"{name}.json").read_text())


def seed_key(seed: int):
    """A PRNG key that keeps every bit of a seed wider than 32 bits."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


@dataclasses.dataclass
class Split:
    x: Any              # a row per sample, on the device
    y: np.ndarray       # the row's targets, on the host


@dataclasses.dataclass
class Job:
    public: Split
    private: Split
    test: Split
    client_indices: List[np.ndarray]   # rows of ``private`` per client
    n_classes: int                     # histogram bins of the FedAuto rows
