"""Shared benchmark machinery (paper §2.1 methodology on this host).

Phases per benchmark: preparation (allocate + warm: the jit compile also
plays the TLB-warm role), synchronization (block_until_ready), measurement
(`telemetry.span` around the blocked call — the ONE clock the production
paths and the benchmark suites share), result collection (median of k).
When the telemetry stream is enabled each rep also lands in it as a
``bench.rep`` event, so a captured benchmark run feeds the same drift
report as production traffic.
"""

from __future__ import annotations

from typing import Callable, Dict, List

import jax
import numpy as np

from repro import telemetry

WARMUP = 2
REPS = 5


def time_s(fn: Callable[[], object], reps: int = REPS,
           warmup: int = WARMUP, name: str = "bench.rep") -> float:
    """Median wall seconds of fn() (each call fully blocked)."""
    for _ in range(warmup):
        jax.block_until_ready(fn())
    out: List[float] = []
    for rep in range(reps):
        with telemetry.span(name, rep=rep) as sp:
            jax.block_until_ready(fn())
        out.append(sp.wall_s)
    return float(np.median(out))


def refuse_child_on_tpu(what: str) -> None:
    """Raise when this process sees a TPU.

    `what` measures in a child process on fake CPU devices.  A chip belongs
    to one process: this one holds it, so a child could only fail, hang, or
    quietly measure the CPU.  On a TPU host these phases are refused."""
    if jax.default_backend() == "tpu":
        raise RuntimeError(
            f"{what} runs in a child process on fake CPU devices, but this "
            f"process holds the TPU: the child would not measure the chip. "
            f"Run it on a CPU host (JAX_PLATFORMS=cpu).")


class Csv:
    """Collects `name,us_per_call,derived` rows (benchmarks/run.py format)."""

    def __init__(self):
        self.rows: List[Dict] = []

    def add(self, name: str, us_per_call: float, derived: str = "") -> None:
        self.rows.append({"name": name, "us_per_call": us_per_call,
                          "derived": derived})
        print(f"{name},{us_per_call:.4g},{derived}", flush=True)

    def header(self) -> None:
        print("name,us_per_call,derived", flush=True)
