"""The one traffic generator: a traffic mix file in, arrival tensors out.

A mix (``chipbench/traffic/<name>.json``) is data: the scheduler settings of
one ``simulate`` call, its horizon, the metric streams it asks for, the
arrival process and its parameters, and the limits of the output check.
``draw`` turns a mix, a deployment's rates and a seed into the inputs of
``mix["draws"]`` calls; the seed changes the arrivals only.

An arrival process is the benchmark's own, so a later change to the
program's generators cannot change what a cell offers. :data:`PROCESSES`
holds the paper's §5.1 Poisson traffic; any other process is a new file,
``chipbench/processes/<process>.py``, whose ``draw(rng, rates, T, **params)``
returns (T, I, C) float32 tuple counts with mean ``rates`` on the streams
that have any. A process whose file is missing fails the run and names it.
"""
from __future__ import annotations

import json
import os

import numpy as np

from chipbench import lookup

HERE = os.path.dirname(os.path.abspath(__file__))
MIXES = os.path.join(HERE, "traffic")
PROCESS_FILES = os.path.join(HERE, "processes")


def load_mix(name: str) -> dict:
    with open(os.path.join(MIXES, f"{name}.json")) as f:
        return json.load(f)


def poisson(rng, rates: np.ndarray, T: int) -> np.ndarray:
    """(T, I, C) float32 tuple counts ~ Poisson(``rates``), drawn on the
    nonzero streams only."""
    rows, cols = np.nonzero(rates)
    out = np.zeros((T,) + rates.shape, np.float32)
    out[:, rows, cols] = rng.poisson(np.broadcast_to(rates[rows, cols], (T, len(rows))))
    return out


PROCESSES = {"poisson": poisson}


def process(name: str):
    """The ``draw`` of arrival process ``name``: :data:`PROCESSES` first,
    else its file."""
    if name in PROCESSES:
        return PROCESSES[name]
    return lookup.module(PROCESS_FILES, name, "arrival process").draw


def n_slots(mix: dict) -> int:
    """Arrival slots one call needs: the horizon plus the lookahead window."""
    return int(mix["T"]) + int(mix["window"]) + 1


def draw(mix: dict, rates: np.ndarray, seed: int) -> list[dict]:
    """The inputs ``{"actual": (T+W+1, I, C)}`` of each of the mix's
    ``draws`` calls, from ``seed``."""
    arr = mix["arrivals"]
    draw_one = process(arr["process"])
    params = {k: v for k, v in arr.items() if k != "process"}
    return [{"actual": draw_one(np.random.default_rng([int(seed), d]), rates,
                                n_slots(mix), **params)}
            for d in range(int(mix["draws"]))]
