"""The benchmark's traffic generator: it reads a mix's parameters
(``traffic/<mix>.json``) and returns the flows as plain arrays.  The
program is handed only these flows, so a change to the program's own
generators cannot move the yardstick.

A mix's ``pattern`` names a module beside this one,
``traffic/<pattern>.py``, whose ``flows(mix, group, config)`` draws the
flows; a new mix of a known pattern is a data file alone.
"""
from __future__ import annotations

import importlib
from pathlib import Path

import numpy as np

TRAFFIC = Path(__file__).resolve().parent


def patterns() -> list[str]:
    """The known pattern names: the modules beside this one."""
    return sorted(p.stem for p in TRAFFIC.glob("*.py")
                  if p.stem != Path(__file__).stem)


def flows(mix: dict, group: np.ndarray, config: dict) -> dict:
    """``{"src", "dst", "size", "start"}`` int32 arrays for a mix;
    ``group`` maps each endpoint to its group, ``config`` is the cell's
    configuration."""
    known = patterns()
    if mix.get("pattern") not in known:
        raise ValueError(f"unknown traffic pattern {mix.get('pattern')!r}; "
                         f"known: {', '.join(known)}")
    mod = importlib.import_module(f"traffic.{mix['pattern']}")
    return mod.flows(mix, np.asarray(group), config)
