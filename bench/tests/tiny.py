"""Benchmark cells shrunk to a size the CPU runs in seconds.

The cell's own files are loaded and cut down: the registered arch's
``reduced()`` widths, small scenes, a few slots. The program's attention
runs its XLA path on the CPU. Only tests use this.
"""
from __future__ import annotations

import copy
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import run  # noqa: E402

MODEL_KEYS = run.MODEL_KEYS
SCENE = {"num_map": 16, "num_agents": 6, "num_steps": 10,
         "map_valid": [10, 16], "agents_valid": [2, 6], "radius": 60.0}
#: an open-loop mix of one-sample requests, for the generator's other
#: arrival process: (cell whose files it borrows, traffic changes)
OPEN = ("se2-wosac-serve",
        {"samples": 1, "arrivals": {"process": "poisson", "rate": 40.0}})


class TinyCell(run.Cell):
    """A cell whose program is the registered arch's reduced variant."""

    def program_config(self, get_sim_arch):
        return get_sim_arch(self.config["arch"]).reduced().agent_sim_config()


def tiny_cell(workload, *, seed=1, seconds=0.5, trace=False, control=False,
              slots=3, limits=None, check_lanes=3, num_steps=10):
    """``workload``: a cell of ``BENCHMARK.json``, or ``"open"``."""
    from repro.configs import get_sim_arch

    name, changes = OPEN if workload == "open" else (workload, {})
    cell = run.Cell.from_benchmark(name, seed=seed, seconds=seconds,
                                   trace=trace, control=control)
    config = copy.deepcopy(cell.config)
    pc = get_sim_arch(config["arch"]).reduced().agent_sim_config()
    config["model"] = {k: getattr(pc, k) for k in MODEL_KEYS}
    config["serve_slots"] = slots
    traffic = dict(copy.deepcopy(cell.traffic), **changes)
    scene = dict(SCENE, num_steps=num_steps)
    traffic["scene"] = scene
    traffic["t_hist"] = 4 if traffic["samples"] > 1 else 5
    traffic["t_total"] = scene["num_steps"]
    traffic["samples"] = min(traffic["samples"], 4)
    traffic["scene_pool"] = 3
    traffic["check_lanes"] = check_lanes
    return TinyCell(workload, config, traffic,
                    cell.limits if limits is None else limits, seed=seed,
                    seconds=seconds, trace=trace, control=control)


def readings(cell):
    """Run the cell; returns its outcome."""
    return run.execute(cell)
