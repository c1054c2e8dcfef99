"""Each cell's run, with the harness's look for a chip skipped, at a size
a test run holds: correct when the program is sound, and not correct
with the control or any planted fault patched in underneath. The size is
the cell's own file, `tests/sizes/<cell>.json`: traffic keys that
override the cell's traffic file."""
import contextlib
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import faults  # noqa: E402
import lib  # noqa: E402
import run  # noqa: E402

SPEC = lib.benchmark_spec()
CASES = []
for cell in SPEC["workloads"]:
    traffic = lib.load_json(os.path.join(BENCH, "traffic",
                                         cell["name"] + ".json"))
    for name in (None,) + faults.FAULTS:
        if name is None or faults.applies(cell, traffic, name):
            CASES.append((cell, name))


@pytest.mark.parametrize("cell,fault", CASES,
                         ids=[f"{c['name']}-{f or 'sound'}"
                              for c, f in CASES])
def test_run_is_correct_only_when_sound(cell, fault):
    import jax

    if len(jax.devices()) < cell["chips"]:
        pytest.skip(f"needs {cell['chips']} devices")
    traffic = lib.load_json(os.path.join(BENCH, "traffic",
                                         cell["name"] + ".json"))
    small = lib.load_json(os.path.join(BENCH, "tests", "sizes",
                                       cell["name"] + ".json"))
    patch = (faults.patched(cell, traffic, fault) if fault
             else contextlib.nullcontext())
    with patch:
        result, data = run.run_cell(cell["name"], 2**31 + 17, 1.0, False,
                                    require_tpu=False,
                                    traffic_override=small)
    assert data["records"], "the window completed no request"
    assert result["correct"] is (fault is None), result["checks"]
