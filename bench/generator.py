"""The one traffic generator: every mix is a data file under ``traffic/``.

A mix's ``mode`` names the file ``modes/<mode>.py`` that drives the program
with what this generator makes from the mix's parameters; a new mode adds
its file there, and here only a generator of inputs that no mode makes yet.

``job``  a batch job: walks start from every ``source_stride``-th vertex,
         ``walks_per_vertex`` (the configuration's) walks each.
         ``warmup_supersteps`` supersteps run before the window opens.
"""

from __future__ import annotations

import numpy as np


def job_sources(num_vertices: int, traffic: dict, walks_per_vertex: int) -> np.ndarray:
    starts = np.arange(0, num_vertices, int(traffic.get("source_stride", 1)), dtype=np.int64)
    return np.repeat(starts, walks_per_vertex)
