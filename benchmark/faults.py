"""The control and the planted faults: each a patch of the program under
test that a run must read as not correct. Each configuration's module
(`configs/<config>.py`) lists the ones its cells can have
(`faults_for(traffic)`) and makes them (`patched(name, traffic)`):

  control   the reference computed one precision below what the
            configuration states (fp32 -> bfloat16)
  unchanged a step that returns its state unchanged
  half      half of the batch left out
  exchange  the exchange between chips left out
  altered   an answer altered where it is produced

`control.py` runs the control on the chip; `tests/test_faults.py` runs
every one of them on the CPU.
"""
import os

import lib

FAULTS = ("control", "unchanged", "half", "exchange", "altered")


def _config(cell: dict):
    return lib.load_module(os.path.join(lib.BENCH_DIR, "configs",
                                        cell["config"] + ".py"),
                           "config_" + cell["config"])


def applies(cell: dict, traffic: dict, name: str) -> bool:
    return name in _config(cell).faults_for(traffic)


def patched(cell: dict, traffic: dict, name: str):
    if not applies(cell, traffic, name):
        raise ValueError(f"no fault {name!r} for {cell['name']}")
    return _config(cell).patched(name, traffic)
