"""BENCHMARK.json and the files it names: the contract's shapes, every file
found by name, the generators' schedules as written, and the comparison
functions at tiny sizes on the CPU.

    python3 -m pytest benchmark/tests -q
"""
import os
import re
import sys
import threading
import time

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import lib  # noqa: E402

SPEC = lib.benchmark_spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = {w["name"]: w for w in SPEC["workloads"]}
E2E = {m["name"]: m for m in SPEC["end_to_end"]}
LAYER = {m["name"]: m for m in SPEC["per_layer"]}


def kind(name):
    return lib.load_module(
        os.path.join(BENCH, "traffic", "kinds", name + ".py"), "k_" + name)


def reports(cell, metric):
    return "workloads" not in metric or cell in metric["workloads"]


def text_ok(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"][:2] == ["python3", "benchmark/run.py"]
    assert SPEC["paths"] == ["benchmark"]
    assert isinstance(SPEC["run_seconds"], int)
    assert 1 <= SPEC["run_seconds"] <= 51


def test_a_full_check_fits_its_time_with_24_cells():
    runs = 2 + 14 * 24
    total = runs * (SPEC["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200


@pytest.mark.parametrize("entry", SPEC["configs"] + SPEC["workloads"] +
                         SPEC["end_to_end"] + SPEC["per_layer"],
                         ids=lambda e: e["name"])
def test_names_and_units(entry):
    assert NAME.match(entry["name"])
    if "unit" in entry:
        assert UNIT.match(entry["unit"])
        assert entry["better"] in ("lower", "higher")
    for key in ("why", "layer", "source"):
        if key in entry:
            assert text_ok(entry[key]), key
    for key in ("config", "traffic"):
        if key in entry:
            assert NAME.match(entry[key])


def test_unique_names():
    for group in ("configs", "workloads"):
        names = [e["name"] for e in SPEC[group]]
        assert len(names) == len(set(names))
    metrics = list(E2E) + list(LAYER)
    assert len(metrics) == len(set(metrics))


@pytest.mark.parametrize("cfg", SPEC["configs"], ids=lambda c: c["name"])
def test_configs(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert cfg["source"].startswith("https://")
    assert cfg["file"] == f"benchmark/configs/{cfg['name']}.json"
    data = lib.load_json(os.path.join(lib.ROOT, cfg["file"]))
    assert data["reduced"] == cfg["reduced"]
    assert all(NAME.match(k) and k in data for k in cfg["reduced"])
    assert os.path.isfile(os.path.join(BENCH, "configs",
                                       cfg["name"] + ".py"))
    assert any(w["config"] == cfg["name"] for w in SPEC["workloads"])
    for setting in data.get("env", {}).values():
        assert setting["why"]


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda c: c["name"])
def test_cells(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["chips"] in (1, 4)
    assert cell["traffic"] == cell["name"]
    traffic = lib.load_json(os.path.join(BENCH, "traffic",
                                         cell["traffic"] + ".json"))
    kind(traffic["kind"])
    e2e = [m for m in E2E if reports(cell["name"], E2E[m])]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(reports(cell["name"], m) for m in LAYER.values())


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda c: c["name"])
def test_cells_have_a_test_size(cell):
    # the size tests/test_faults.py runs the cell at on the CPU: traffic
    # keys of the cell, each overridden with a value of the same type
    traffic = lib.load_json(os.path.join(BENCH, "traffic",
                                         cell["traffic"] + ".json"))
    small = lib.load_json(os.path.join(BENCH, "tests", "sizes",
                                       cell["name"] + ".json"))
    assert isinstance(small, dict) and small
    assert "kind" not in small
    for key, value in small.items():
        assert key in traffic, key
        assert type(value) is type(traffic[key]), key


def test_at_most_half_of_the_cells_take_four_chips():
    four = sum(1 for w in SPEC["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(SPEC["workloads"]) // 2)


@pytest.mark.parametrize("metric", SPEC["end_to_end"] + SPEC["per_layer"],
                         ids=lambda m: m["name"])
def test_metrics(metric):
    is_e2e = metric["name"] in E2E
    keys = {"name", "unit", "better", "source", "workloads"}
    keys |= {"bound"} if is_e2e else {"layer", "moves"}
    assert set(metric) <= keys and set(metric) >= keys - {"workloads"}
    reader = lib.load_module(os.path.join(BENCH, "metrics",
                                          metric["name"] + ".py"), "m")
    assert callable(reader.read)
    for cell in metric.get("workloads", []):
        assert cell in CELLS
    if is_e2e:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
        return
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    moved = E2E[metric["moves"]]
    for cell in CELLS:
        if reports(cell, metric):
            assert reports(cell, moved), (cell, metric["name"])


@pytest.mark.parametrize("metric", SPEC["per_layer"],
                         ids=lambda m: m["name"])
def test_reads_none_without_its_input(metric):
    # an untraced run, with no counters and no acknowledged request
    reader = lib.load_module(os.path.join(BENCH, "metrics",
                                          metric["name"] + ".py"), "m")
    data = {"records": [{"ok": False}] * 3, "trace": None,
            "host_spans": None}
    assert reader.read(data) is None


@pytest.mark.parametrize("metric", [m for m in SPEC["per_layer"]
                                    if m["unit"] == "us"],
                         ids=lambda m: m["name"])
def test_a_time_is_better_lower(metric):
    assert metric["better"] == "lower"


def test_one_layer_name_per_layer():
    layers = {m["layer"] for m in LAYER.values()}
    assert all(text_ok(x) for x in layers)


# -- the generators ---------------------------------------------------------

def test_closed_loop_keeps_in_flight_and_stops_at_the_close():
    k = kind("closed_loop")
    params = {"in_flight": 3, "sizes_kib": [64], "names": 16, "payloads": 3}
    live, peak, lock = [0], [0], threading.Lock()

    def send(req):
        with lock:
            live[0] += 1
            peak[0] = max(peak[0], live[0])
        time.sleep(0.01)
        with lock:
            live[0] -= 1
        return True, ""

    t0 = time.monotonic() + 0.02
    recs = k.drive(send, params, 1, 0, t0, 0.3, time.monotonic)
    assert peak[0] == 3
    assert all(r["sent"] < t0 + 0.3 for r in recs)
    assert len(recs) >= 3 * 0.3 / 0.02
    by_name = {}
    for r in sorted(recs, key=lambda r: r["k"]):
        by_name.setdefault(r["name"], []).append(r["payload"])
    # successive writes of one name never carry the same payload
    assert all(a != b for p in by_name.values() for a, b in zip(p, p[1:]))


def test_closed_loop_refuses_payloads_that_share_a_factor_with_names():
    k = kind("closed_loop")
    with pytest.raises(ValueError):
        next(k.requests({"sizes_kib": [4], "names": 16, "payloads": 4}, 0))


def test_merge_rounds_alternate_sets():
    k = kind("merge_rounds")
    params = {"buckets": 2, "bucket_mib": 1, "sets": 2}
    t0 = time.monotonic()
    recs = k.drive(lambda req: (time.sleep(0.01), (True, ""))[1], params,
                   1, 0, t0, 0.1, time.monotonic)
    assert [r["set"] for r in recs] == [i % 2 for i in range(len(recs))]
    assert all(r["nbytes"] == 2 << 20 for r in recs)


# -- the comparisons, at tiny sizes on the CPU -------------------------------

def test_checksums_agree_on_host_and_device_and_see_one_bit():
    import numpy as np

    x = lib.seeded_fp32(2**31 + 99, (1, 0), 4099)
    assert np.isfinite(x).all()
    dev = lib.device_checksum_fn()
    import jax.numpy as jnp

    assert dev(jnp.asarray(x)) == lib.host_checksum(x)
    y = x.copy()
    y.view(np.uint32)[4000] ^= 1
    assert lib.host_checksum(y) != lib.host_checksum(x)


def test_last_acknowledged_write_candidates():
    store = lib.load_module(os.path.join(BENCH, "configs",
                                         "tensor_store.py"), "ts")

    def w(p, sent, done, ok=True):
        return {"nbytes": 4, "payload": p, "sent": sent, "done": done,
                "ok": ok}

    assert store._candidates([w(0, 0, 1), w(1, 2, 3)]) == {(4, 1)}
    # overlapping writes: either may be the one the store applied last
    assert store._candidates([w(0, 0, 2), w(1, 1, 3)]) == {(4, 0), (4, 1)}
    # a failed write may or may not have landed
    assert store._candidates([w(0, 0, 1), w(1, 2, 3, ok=False)]) == {
        (4, 0), (4, 1)}


def test_merge_reference_error_on_four_devices():
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs four devices")
    from brpc_tpu.parallel import MeshChannel, make_mesh

    merge = lib.load_module(os.path.join(BENCH, "configs", "grad_merge.py"),
                            "gm")
    devs = jax.devices()[:4]
    mesh = make_mesh({"x": 4}, devices=devs)
    (grads,) = merge.make_sets(mesh, "x", 1, 1, 1024, 2**31 + 5)
    out = MeshChannel(mesh, "x").allreduce(grads[0])
    assert merge.reference_error(grads[0], out, devs) < 1e-5
    wrong = out + 1e-3
    assert merge.reference_error(grads[0], wrong, devs) > 1e-4
