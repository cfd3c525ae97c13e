"""The work one training step needs by the cell's shapes, asked of the
cost function that the configuration keeps with it.

A configuration's file names, under ``"costs"``, a Python file (relative
to the manifest, under ``paths``) with one function

    step_costs(cfg, per_chip_batch, device_sampling) -> dict

per step and per chip, for what the algorithm needs whatever implements
it. This module knows no model: it loads that file and holds the answer
to the keys the harness and the readers ask for:

* ``edges``         sampled edges of one step (``edges_per_s_chip``);
* ``flops``, ``bytes``  the whole step (``step.mfu_roofline``);
* ``draw_bytes``    what the draws read and write on the device, 0 where
  the device draws nothing (``draw.kernel_roofline`` is then silent);
* ``gather_bytes``, ``opt_bytes``  the gathers' and the optimizer's part
  of ``bytes``.
"""

from __future__ import annotations

import functools
import importlib.util
import os

REQUIRED = ("edges", "flops", "bytes", "draw_bytes", "gather_bytes",
            "opt_bytes")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@functools.lru_cache(maxsize=32)
def _cost_module(path: str):
    spec = importlib.util.spec_from_file_location(
        "benchmark_costs_" + os.path.basename(path)[:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def step_costs(cfg: dict, per_chip_batch: int, device_sampling: bool,
               root: str = ROOT) -> dict:
    """``root`` is the directory the configuration's paths start from
    (the manifest's)."""
    mod = _cost_module(os.path.abspath(os.path.join(root, cfg["costs"])))
    out = mod.step_costs(cfg, int(per_chip_batch), bool(device_sampling))
    bad = [k for k in REQUIRED if not out.get(k, -1) >= 0]
    if bad or out["edges"] <= 0:
        raise ValueError(
            f"{cfg['costs']}: step_costs must give {REQUIRED}, none "
            f"negative and edges above 0; wrong or missing: {bad or 'edges'}")
    return out
