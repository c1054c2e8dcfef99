"""The `kv.ycsb_b` cell's parts on the CPU: YCSB's generator (mix and
skew), the record formula's numpy and jnp twins, the candidate rule of the
final-state check, the stale-read check on hand-built histories, and the
five per-layer readers."""
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import lib  # noqa: E402
import table_roofline  # noqa: E402

SPEC = lib.benchmark_spec()
SEED = 2**31 + 4242
TRAFFIC = lib.load_json(os.path.join(BENCH, "traffic", "kv.ycsb_b.json"))


def load(path, name):
    return lib.load_module(os.path.join(BENCH, path), name)


ycsb = load("traffic/kinds/ycsb.py", "k_ycsb")
cfg = load("configs/ycsb_table.py", "c_ycsb_table")


def reader(name):
    return load(f"metrics/{name}.py", "m_" + name)


# -- the generator ----------------------------------------------------------

def test_generator_mix_and_skew():
    seq = ycsb.requests(TRAFFIC, SEED, 0)
    ops = [next(seq) for _ in range(200_000)]
    reads = sum(op["op"] == "read" for op in ops) / len(ops)
    assert abs(reads - 0.95) <= 0.01
    keys = np.array([op["key"] for op in ops])
    assert keys.min() >= 0 and keys.max() < TRAFFIC["records"]
    _, counts = np.unique(keys, return_counts=True)
    hottest = counts.max() / len(ops)
    assert abs(hottest / (1 / 26.46902820178302) - 1) <= 0.2
    fields = [op["field"] for op in ops if op["op"] == "update"]
    assert set(fields) == set(range(10))
    assert all(op["field"] == -1 for op in ops if op["op"] == "read")
    assert [op["k"] for op in ops[:5]] == list(range(5))


def test_generator_is_seeded_per_client():
    def first(seed, client):
        seq = ycsb.requests(TRAFFIC, seed, client)
        return [(op["op"], op["key"], op["field"])
                for op in (next(seq) for _ in range(64))]

    assert first(SEED, 1) == first(SEED, 1)
    assert first(SEED, 1) != first(SEED, 2)
    assert first(SEED, 1) != first(SEED + 1, 1)


def test_ycsb_hash_and_zipfian_edges():
    # FNV-1 64 of the 8 zero bytes, as YCSB's Utils.fnvhash64 gives it
    h = 0xCBF29CE484222325
    for _ in range(8):
        h = (h * 1099511628211) % (1 << 64)
    want = h - (1 << 64) if h >= 1 << 63 else h
    assert ycsb.fnvhash64([0])[0] == abs(want)
    # below 1/zeta the draw is item 0, then item 1
    u = np.array([0.0, 0.5 / ycsb.ZIPF_ZETAN, 1.2 / ycsb.ZIPF_ZETAN, 0.999])
    items = ycsb.zipfian_items(u)
    assert items[:3].tolist() == [0, 0, 1]
    assert 1 < items[3] < ycsb.ZIPF_ITEMS


def test_generator_refuses_what_ycsb_does_not_define():
    with pytest.raises(ValueError):
        next(ycsb.requests(dict(TRAFFIC, zipfian_constant=0.9), SEED, 0))
    with pytest.raises(ValueError):
        next(ycsb.requests(dict(TRAFFIC, update_proportion=0.1), SEED, 0))


# -- the record formula -----------------------------------------------------

def test_record_formula_twins_agree():
    import jax.numpy as jnp

    keys = np.array([0, 1, 2, 4095, 8388606, 8388607, 2**31 + 5],
                    np.uint32)
    for seed in (0, SEED, 2**40 + 17):
        host = cfg.record_words_np(seed, keys)
        dev = cfg.record_words(jnp.uint32(cfg.seed_base(seed,
                                                        cfg.TAG_RECORD)),
                               jnp.asarray(keys), jnp)
        assert host.shape == (len(keys), 250) and host.dtype == np.uint32
        assert (np.asarray(dev) == host).all()
    assert (cfg.record_words_np(SEED, keys)
            != cfg.record_words_np(SEED + 1, keys)).mean() > 0.99


def test_table_built_in_slices_holds_the_formula(monkeypatch):
    import jax

    monkeypatch.setattr(cfg, "BUILD_ROWS", 384)  # 3 slices, the last
    records = 1000  # overlapping the one before it
    table = cfg.build_table(SEED, records, jax.devices()[0])
    assert table.shape == (records, 250) and table.dtype == np.uint32
    assert (np.asarray(table) ==
            cfg.record_words_np(SEED, np.arange(records))).all()


def test_decode_names_each_version():
    keys = np.array([3, 9])
    rows = cfg.record_words_np(SEED, keys).copy()
    rows[0, 25:50] = cfg.update_words(SEED, 2, 77, 3, 1)[0]  # field 1
    rows[1, 0] ^= 1  # field 0 altered
    rows[1, 225:250] = cfg.update_words(SEED, 1, 5, 8, 9)[0]  # key 8's
    ver = cfg.decode(SEED, keys, rows)
    assert ver[0].tolist() == [cfg.INITIAL, (2 << 32) | 77] + \
        [cfg.INITIAL] * 8
    assert ver[1, 0] == cfg.BAD and ver[1, 9] == cfg.BAD
    assert (ver[1, 1:9] == cfg.INITIAL).all()


# -- the comparisons --------------------------------------------------------

def w(v, sent, done, ok=True):
    return {"version": v, "sent": sent, "done": done, "ok": ok}


def test_candidate_rule():
    # tensor_store._candidates' cases
    assert cfg.candidates([w(0, 0, 1), w(1, 2, 3)]) == {1}
    assert cfg.candidates([w(0, 0, 2), w(1, 1, 3)]) == {0, 1}
    assert cfg.candidates([w(0, 0, 1), w(1, 2, 3, ok=False)]) == {0, 1}
    # the bulk-loaded words stay a candidate only while no write is acked
    assert cfg.candidates([w(0, 0, 1, ok=False)]) == {0, cfg.INITIAL}
    # an acked write strictly followed by another acked one is out, even
    # beside a failed one
    assert cfg.candidates([w(0, 0, 1), w(1, 2, 3), w(2, 4, 9, ok=False)]) \
        == {1, 2}


def reads_of(*rows):
    """Reads (key, sent, done, versions) as the clients' arrays."""
    return {"key": np.array([r[0] for r in rows]),
            "sent": np.array([r[1] for r in rows], float),
            "done": np.array([r[2] for r in rows], float),
            "ok": np.ones(len(rows), bool),
            "ver": np.array([r[3] for r in rows], np.int64)}


def test_stale_reads_are_caught_in_a_hand_built_history():
    a, b = (1 << 32) | 10, (2 << 32) | 20
    ini = [cfg.INITIAL] * 10
    writes = {(5, 3): [w(a, 1.0, 2.0), w(b, 3.0, 4.0)]}
    fresh = ini[:3] + [b] + ini[4:]
    older = ini[:3] + [a] + ini[4:]
    good = reads_of(
        (5, 0.5, 0.9, ini),  # before any write
        (5, 1.5, 2.5, older),  # sent while a was in flight: either
        (5, 1.5, 2.5, ini),
        (5, 3.5, 3.9, older),  # b not yet acked when the read was sent
        (5, 4.5, 5.0, fresh),
        (6, 4.5, 5.0, ini))  # another key
    assert cfg.read_faults(good, writes) == (0, 0)
    stale = reads_of(
        (5, 2.5, 2.7, ini),  # a was acked before this read was sent
        (5, 4.5, 5.0, older),  # b was acked before this read was sent
        (5, 4.5, 5.0, fresh))
    assert cfg.read_faults(stale, writes) == (0, 2)


def test_bad_reads_are_caught_in_a_hand_built_history():
    a = (1 << 32) | 10
    ini = [cfg.INITIAL] * 10
    writes = {(5, 3): [w(a, 1.0, 2.0)]}
    bad = reads_of(
        (5, 0.1, 0.5, ini[:3] + [a] + ini[4:]),  # before a was sent
        (5, 3.0, 3.5, ini[:3] + [a, a] + ini[5:]),  # a in field 4 too
        (6, 3.0, 3.5, ini[:3] + [a] + ini[4:]),  # a on another key
        (5, 3.0, 3.5, [cfg.BAD] + ini[1:3] + [a] + ini[4:]))
    assert cfg.read_faults(bad, writes) == (4, 0)


def test_wrong_fields_counts_fields_off_their_candidates():
    keys = np.array([3, 4])
    rows = cfg.record_words_np(SEED, keys).copy()
    v = (1 << 32) | 7
    rows[0, 50:75] = cfg.update_words(SEED, 1, 7, 3, 2)[0]
    writes = {(3, 2): [w(v, 0, 1)], (4, 0): [w((1 << 32) | 8, 2, 3)]}
    # key 4's field 0 was acknowledged but still holds its bulk load
    assert cfg.wrong_fields(SEED, keys, rows, writes) == 1
    rows[0, 0] ^= 1
    assert cfg.wrong_fields(SEED, keys, rows, writes) == 2


# -- the readers ------------------------------------------------------------

LAYER = {m["name"]: m for m in SPEC["per_layer"]}
READERS = ("ops_per_batch.kv", "queue_wait_us_per_op.kv",
           "batch_us_per_batch.kv", "table_wait_us_per_batch.kv",
           "table_hbm_roofline.kv")


def test_entries_of_the_cell():
    # the readers tested below report in this cell; other cells may share
    # them
    for name in READERS:
        assert "kv.ycsb_b" in LAYER[name]["workloads"]
        assert LAYER[name]["moves"] == "tensor_GBps"
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "kv.ycsb_b" in e2e["tensor_GBps"]["workloads"]
    cell = {c["name"]: c for c in SPEC["workloads"]}["kv.ycsb_b"]
    assert (cell["config"], cell["chips"]) == ("ycsb_table", 1)


def counted(**after):
    before = {k: 0 for k in after}
    return {"table_counters": {"before": before, "after": after}}


def test_counter_readers():
    data = counted(table_batches=100, table_reads=1900, table_updates=100,
                   table_updates_superseded=3, table_queue_wait_ns=4e8)
    assert reader("ops_per_batch.kv").read(data) == pytest.approx(20.0)
    assert reader("queue_wait_us_per_op.kv").read(data) == \
        pytest.approx(200.0)
    # a program without the counters, a run without them
    for name in ("ops_per_batch.kv", "queue_wait_us_per_op.kv"):
        assert reader(name).read({"records": []}) is None
        assert reader(name).read({"table_counters": {
            "before": {}, "after": {}}}) is None


def test_span_readers():
    data = {"records": [], "host_spans": {
        "host_span_totals": {"table.batch": 0.5, "table.wait": 0.3},
        "host_span_counts": {"table.batch": 1000, "table.wait": 1000}}}
    assert reader("batch_us_per_batch.kv").read(data) == pytest.approx(500)
    assert reader("table_wait_us_per_batch.kv").read(data) == \
        pytest.approx(300)
    for name in ("batch_us_per_batch.kv", "table_wait_us_per_batch.kv"):
        assert reader(name).read({"trace": None}) is None
        assert reader(name).read({"host_spans": {
            "host_span_totals": {}, "host_span_counts": {}}}) is None


def test_roofline_reader():
    assert table_roofline.least_bytes(10, 4) == 10 * 2000 + 4 * 100
    data = counted(table_batches=10, table_reads=819_000,
                   table_updates=0, table_queue_wait_ns=0)
    data.update(device_kind="TPU v5 lite", trace={"busy_s": 0.004})
    # 1.638 GB at 819 GB/s is 2 ms: half of 4 ms busy
    assert reader("table_hbm_roofline.kv").read(data) == pytest.approx(50.0)
    assert reader("table_hbm_roofline.kv").read(
        dict(data, trace=None)) is None
    assert reader("table_hbm_roofline.kv").read(
        {"trace": {"busy_s": 1.0}}) is None
