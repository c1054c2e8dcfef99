"""ycsb_table: YCSB core workload B ("read mostly") against a table held in
HBM by a TableService on the native runtime, in the process that holds the
chip, driven by host-only client processes on the same host.

The server is `rpc.Server(ServerOptions(use_native_runtime=True))` with
`TableService`; each client is a child process on JAX's CPU backend that
calls `Table.Read` and `Table.Update` through an ordinary `Channel`.

The table is `uint32[records, 250]`: ten fields of 25 words (100 B). It is
built on the chip in slices, into a donated buffer, from a seeded formula:
word j of record k starts as `record_words(seed, k)[j]`, which has a numpy
twin for the host and a jnp twin for the chip. An update that client c
makes as its op k to (key, field) writes 25 words: c, k, then 23 words
hashed from (seed, c, k, key, field). So any field read back decodes to
the initial record or to the one write that produced it.

Correctness, every limit 0:

  bad_reads     reads that returned a field that is no version of its
                (key, field): neither the initial words nor a write's, a
                write made to another field, or one sent after the read
                completed. Clients check every read; the server the rest.
  stale_reads   reads that saw a field older than an update to it that was
                acknowledged before the read was sent.
  wrong_fields  after the window, (key, field)s whose bytes on the chip are
                not a candidate: the last acknowledged write, or a write
                that overlapped it or failed, or the initial words where
                no write was acknowledged. Checked: every field of every
                key written in the window, and of 65,536 seeded keys.

Run as `ycsb_table.py --client <json>` it is one client process.
"""
import base64
import gc
import io
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

import numpy as np  # noqa: E402

import lib  # noqa: E402

FIELDS, FIELD_WORDS = 10, 25  # YCSB's fieldcount and 100 B fieldlength
INITIAL, BAD, NOT_READ = -1, -2, -3  # field versions besides writes
TAG_RECORD, TAG_UPDATE = 0x5EED0001, 0x5EED0002
GOLD = 0x9E3779B9
BUILD_ROWS = 1 << 18  # rows the chip builds per slice
CHECK_ROWS = 8192  # rows gathered per program in the final-state check
UNTOUCHED = 65536  # seeded keys whose every field is checked too
KEYS_STREAM = 4  # seeded streams: (4,) the untouched keys
WARM_MS = 120000


# -- the record formula (numpy on the host, jnp on the chip) ----------------

def _mix(h, xp):
    """A 32-bit finalizer (lowbias32); wraps the same in numpy and jnp."""
    h = h ^ (h >> 16)
    h = h * xp.uint32(0x7FEB352D)
    h = h ^ (h >> 15)
    h = h * xp.uint32(0x846CA68B)
    return h ^ (h >> 16)


def seed_base(seed: int, tag: int) -> int:
    """The seed (any integer, taken mod 2^64) folded into 32 bits."""
    s = seed % (1 << 64)
    h = np.array([tag], np.uint32)
    for word in (s & 0xFFFFFFFF, s >> 32):
        h = _mix(h ^ np.uint32(word), np)
    return int(h[0])


def record_words(base, keys, xp):
    """u32[n, 250]: the initial words of records `keys` (u32[n]) from a
    seed's base (seed_base(seed, TAG_RECORD))."""
    rows = _mix(xp.asarray(base, dtype=xp.uint32) ^ keys, xp)[:, None]
    cols = xp.arange(FIELDS * FIELD_WORDS, dtype=xp.uint32) * \
        xp.uint32(GOLD)
    return _mix(rows ^ cols[None, :], xp)


def record_words_np(seed: int, keys):
    return record_words(seed_base(seed, TAG_RECORD),
                        np.asarray(keys, dtype=np.uint32), np)


def update_words(seed: int, client, op, key, field):
    """u32[m, 25]: the words that client's op writes to (key, field)."""
    c, k, key, field = (np.asarray(a, dtype=np.uint32).reshape(-1)
                        for a in (client, op, key, field))
    h = np.full(c.shape, seed_base(seed, TAG_UPDATE), np.uint32)
    for word in (c, k, key, field):
        h = _mix(h ^ word, np)
    cols = np.arange(2, FIELD_WORDS, dtype=np.uint32) * np.uint32(GOLD)
    return np.concatenate([c[:, None], k[:, None],
                           _mix(h[:, None] ^ cols[None, :], np)], axis=1)


def version(client, op):
    return (np.asarray(client, np.int64) << 32) | np.asarray(op, np.int64)


def decode(seed: int, keys, rows):
    """int64[n, fields]: per field of each row, INITIAL, the version of
    the write whose words it holds, or BAD."""
    keys = np.asarray(keys, dtype=np.int64)
    n = len(keys)
    f = np.asarray(rows, dtype=np.uint32).reshape(n, -1, FIELD_WORDS)
    init = record_words_np(seed, keys).reshape(f.shape)
    out = np.full(f.shape[:2], BAD, np.int64)
    is_init = (f == init).all(axis=2)
    out[is_init] = INITIAL
    r, fld = np.nonzero(~is_init)
    if len(r):
        c, k = f[r, fld, 0], f[r, fld, 1]
        ok = (update_words(seed, c, k, keys[r], fld) == f[r, fld]).all(1)
        out[r[ok], fld[ok]] = version(c[ok], k[ok])
    return out


def build_table(seed: int, records: int, device):
    """The table on `device`, built in slices of BUILD_ROWS into one
    donated buffer (a bulk load: no RPC per record)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    words = FIELDS * FIELD_WORDS
    rows = min(BUILD_ROWS, records)
    table = jax.jit(lambda: jnp.zeros((records, words), jnp.uint32),
                    out_shardings=SingleDeviceSharding(device))()

    def fill(table, base, start):
        keys = start.astype(jnp.uint32) + jnp.arange(rows, dtype=jnp.uint32)
        return jax.lax.dynamic_update_slice(
            table, record_words(base, keys, jnp), (start, 0))

    fill = jax.jit(fill, donate_argnums=0)
    base = np.uint32(seed_base(seed, TAG_RECORD))
    for start in range(0, records, rows):
        # the last slice ends at the table's end (it may overlap)
        table = fill(table, base, np.int32(min(start, records - rows)))
    return table.block_until_ready()


# -- the server (the process that holds the chip) ---------------------------

def run(win):
    # the program under test first: a program without it fails here, at once
    from brpc_tpu.rpc.table_service import COUNTERS, TableService
    from brpc_tpu import bvar, native, rpc

    native.load()  # builds the native core on a checkout's first run
    cfg, traffic = win.config, win.traffic
    records = int(traffic["records"])
    shape = (FIELDS, FIELD_WORDS * 4)
    if (cfg["fieldcount"], cfg["fieldlength"]) != shape or \
            (traffic["field_count"], traffic["field_length"]) != shape:
        raise ValueError(f"records here are {shape[0]} fields of "
                         f"{shape[1]} B, as the configuration states")
    clients = [_spawn_client(win, i) for i in range(int(traffic["clients"]))]
    srv = svc = None
    try:
        device = win.devices[0]
        table = build_table(win.seed, records, device)
        svc = TableService(table)
        del table  # the service owns it; it is donated batch by batch
        srv = rpc.Server(rpc.ServerOptions(use_native_runtime=True))
        srv.add_service(svc)
        if srv.start("127.0.0.1:0") != 0:
            raise RuntimeError("table server failed to start")
        for c in clients:
            _expect_line(c, "up")
            _send(c, {"target": str(srv.listen_endpoint)})
        for c in clients:
            _expect_line(c, "ready")

        def counters():
            return {n: bvar.find_exposed(n).get_value() for n in COUNTERS}

        before = counters()
        t0 = win.begin()
        for c in clients:
            _send(c, {"t0": t0, "seconds": win.seconds})
        results = [_unpack(json.loads(_expect_line(c, "{")))
                   for c in clients]
        win.end(_records(results))
        win.data["table_counters"] = {"before": before, "after": counters()}
        win.data["device_kind"] = device.device_kind
        win.data["memory_peak_bytes"] = lib.memory_peak(win.devices)
        _check(win, svc, results)
    finally:
        for c in clients:
            if c.poll() is None:
                c.kill()
            c.wait()
        if srv is not None:
            srv.stop()
            srv.join(10)
        if svc is not None:
            svc.close()


def _spawn_client(win, index: int):
    args = {"seed": win.seed, "client": index, "traffic": win.traffic}
    # clients run on the CPU backend; their compiles stay out of the
    # chip's cache
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--client",
         json.dumps(args)],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True, env=env,
        cwd=lib.ROOT)


def _send(proc, msg: dict):
    proc.stdin.write(json.dumps(msg) + "\n")
    proc.stdin.flush()


def _expect_line(proc, prefix: str) -> str:
    while True:
        line = proc.stdout.readline()
        if not line:
            raise RuntimeError(f"client {proc.pid} exited with "
                               f"{proc.wait()} before sending {prefix!r}")
        if line.startswith(prefix):
            return line


def _pack(arrays: dict) -> str:
    buf = io.BytesIO()
    np.savez(buf, **arrays)
    return base64.b64encode(buf.getvalue()).decode()


def _unpack(res: dict) -> dict:
    with np.load(io.BytesIO(base64.b64decode(res["arrays"]))) as z:
        res["arrays"] = {k: z[k] for k in z.files}
    return res


def _records(results):
    """The harness's request records (due, sent, done, ok, nbytes, error)
    from the clients' compact arrays: a read acknowledges the record it
    returns (1,000 B), an update the field it writes (100 B)."""
    out = []
    for res in results:
        a = res["arrays"]
        errors = dict((int(k), e) for k, e in res["errors"])
        for kind, nbytes in (("read", 1000), ("update", 100)):
            for k, sent, done, ok in zip(a[kind + "_k"].tolist(),
                                         a[kind + "_sent"].tolist(),
                                         a[kind + "_done"].tolist(),
                                         a[kind + "_ok"].tolist()):
                out.append({"due": sent, "sent": sent, "done": done,
                            "ok": ok, "nbytes": nbytes,
                            "error": errors.get(k, "")})
    return out


# -- the comparison with the plain semantics --------------------------------

def candidates(writes):
    """Versions a (key, field) may hold after `writes`, dicts with
    version, sent, done and ok: tensor_store._candidates' rule. A write
    is out only if an acknowledged write was sent after it completed; a
    failed write may have landed at any time, so it stays in, and the
    initial words stay in while no write was acknowledged."""
    acked = [w["sent"] for w in writes if w["ok"]]
    last = max(acked, default=float("-inf"))
    out = {w["version"] for w in writes if not w["ok"] or w["done"] >= last}
    if not acked:
        out.add(INITIAL)
    return out


def _writes(results):
    """{(key, field): [write, ...]} over every client's updates."""
    by = {}
    for res in results:
        a, c = res["arrays"], res["client"]
        for k, key, f, sent, done, ok in zip(
                a["update_k"].tolist(), a["update_key"].tolist(),
                a["update_field"].tolist(), a["update_sent"].tolist(),
                a["update_done"].tolist(), a["update_ok"].tolist()):
            by.setdefault((key, f), []).append(
                {"version": (c << 32) | k, "sent": sent, "done": done,
                 "ok": ok})
    return by


def read_faults(reads, writes):
    """(bad, stale): reads, among those acknowledged, that saw a version
    no write to that (key, field) made or one sent after the read
    completed; and reads that saw a version older than an update to the
    field acknowledged before the read was sent. `reads`: dict of arrays
    key, sent, done, ok and ver (int64[n, fields])."""
    index = {w["version"]: (kf, w) for kf, ws in writes.items()
             for w in ws}
    bad = stale = 0
    order = {}
    for kf, ws in writes.items():
        ws = sorted(ws, key=lambda w: w["done"])
        done = np.array([w["done"] for w in ws])
        # the latest send among acknowledged writes done by each point
        sent = np.maximum.accumulate(
            [w["sent"] if w["ok"] else float("-inf") for w in ws])
        order[kf] = (done, sent)
    ok = np.asarray(reads["ok"], bool)
    for key, rsent, rdone, ver in zip(reads["key"][ok].tolist(),
                                      reads["sent"][ok].tolist(),
                                      reads["done"][ok].tolist(),
                                      reads["ver"][ok].tolist()):
        is_bad = is_stale = False
        for f, v in enumerate(ver):
            if v == BAD:
                is_bad = True
                continue
            w = None
            if v != INITIAL:
                kf, w = index.get(v, (None, None))
                if kf != (key, f) or w["sent"] > rdone:
                    is_bad = True
                    continue
            if (key, f) not in order:
                continue
            done, sent = order[(key, f)]
            n = int(np.searchsorted(done, rsent, side="left"))
            if n and sent[n - 1] > float("-inf") and (
                    w is None or (w["ok"] and w["done"] < sent[n - 1])):
                is_stale = True
        bad += is_bad
        stale += is_stale
    return bad, stale


def wrong_fields(seed: int, keys, rows, writes) -> int:
    """Fields of `rows` (the chip's records `keys`) that hold no candidate
    of their (key, field)."""
    wrong = 0
    for key, ver in zip(np.asarray(keys).tolist(),
                        decode(seed, keys, rows).tolist()):
        for f, v in enumerate(ver):
            ws = writes.get((key, f))
            if v not in (candidates(ws) if ws else {INITIAL}):
                wrong += 1
    return wrong


def _gather(table, keys):
    """The records `keys` from the chip, CHECK_ROWS at a time."""
    import jax

    take = jax.jit(lambda t, k: t[k])
    out = []
    for i in range(0, len(keys), CHECK_ROWS):
        part = np.zeros(CHECK_ROWS, np.int32)
        chunk = keys[i:i + CHECK_ROWS]
        part[:len(chunk)] = chunk
        out.append(np.asarray(take(table, part))[:len(chunk)])
    return np.concatenate(out) if out else np.zeros((0, 0), np.uint32)


def _check(win, svc, results):
    limits = win.config["limits"]
    writes = _writes(results)
    bad = sum(res["bad_reads"] for res in results)
    stale = 0
    for res in results:
        a = res["arrays"]
        b, s = read_faults({"key": a["read_key"], "sent": a["read_sent"],
                            "done": a["read_done"], "ok": a["read_ok"],
                            "ver": a["read_ver"]}, writes)
        bad += b
        stale += s
    written = sorted({key for key, _ in writes})
    rng = np.random.default_rng([win.seed % (1 << 64), KEYS_STREAM])
    others = np.setdiff1d(rng.integers(0, svc.records, UNTOUCHED), written)
    keys = np.concatenate([np.array(written, np.int64), others])
    wrong = wrong_fields(win.seed, keys, _gather(svc.table, keys), writes)
    sys.stderr.write(f"reads {sum(len(r['arrays']['read_key']) for r in results)}"
                     f", fields written {len(writes)}, keys checked "
                     f"{len(keys)}\n")
    win.check("bad_reads", bad, limits["bad_reads"])
    win.check("stale_reads", stale, limits["stale_reads"])
    win.check("wrong_fields", wrong, limits["wrong_fields"])


# -- the control and the planted faults (benchmark/faults.py) ---------------

def faults_for(traffic: dict):
    """One chip has no exchange to leave out."""
    return ("control", "unchanged", "half", "altered")


def patched(name: str, traffic: dict):
    """The control or a planted fault, patched into the program (see
    benchmark/faults.py). The control serves reads from a copy of the
    table taken before the window, the bulk-loaded records; the service
    still applies every update."""
    from brpc_tpu.rpc.table_service import TableService

    run_batch = TableService._run

    def patched_run(self, bucket, at, values, keys):
        if name == "control":
            snapshot = self.__dict__.get("_snapshot")
            if snapshot is None:  # before the first batch: the bulk load
                snapshot = self._snapshot = np.asarray(self._table)
            run_batch(self, bucket, at, values, keys)
            return snapshot[keys]
        real = np.nonzero(at[:, 0] < self.records)[0]
        dropped = {"unchanged": real,
                   "half": real[:(len(real) + 1) // 2]}.get(name, [])
        at = at.copy()
        at[dropped, 0] = self.records + np.asarray(dropped, np.int32)
        rows = run_batch(self, bucket, at, values, keys)
        if name == "altered":
            rows = rows.copy()
            rows[0, 0] ^= 1  # the first row read in each batch
        return rows

    return lib.swap(TableService, "_run", patched_run)


# -- one client process (JAX on the CPU backend) ----------------------------

def client_main(args: dict):
    import time

    sys.path.insert(0, lib.ROOT)
    from brpc_tpu import rpc
    from brpc_tpu.rpc.proto import table_service_pb2 as tb_pb2

    t = args["traffic"]
    seed, me = int(args["seed"]), int(args["client"])
    kind = lib.load_module(os.path.join(BENCH_DIR, "traffic", "kinds",
                                        t["kind"] + ".py"), "kind")
    timeout_ms = float(t["timeout_ms"])
    print("up", flush=True)
    target = json.loads(sys.stdin.readline())["target"]
    ch = rpc.Channel(rpc.ChannelOptions(timeout_ms=timeout_ms, max_retry=0,
                                        connect_timeout_ms=5000))
    if ch.init(target) != 0:
        raise SystemExit(f"channel to {target} failed")
    bad = [0]

    def read(key, timeout):
        cntl = rpc.Controller()
        cntl.timeout_ms = timeout
        resp = tb_pb2.TableReadResponse()
        ch.call_method("Table.Read", cntl, tb_pb2.TableReadRequest(key=key),
                       resp)
        return cntl, resp

    def send(op):
        if op["op"] == "read":
            cntl, resp = read(op["key"], timeout_ms)
            if cntl.failed():
                return False, cntl.error_text
            rows = np.frombuffer(resp.record, dtype="<u4")
            if rows.size != FIELDS * FIELD_WORDS:
                op["ver"] = [BAD] * FIELDS
            else:
                op["ver"] = decode(seed, [op["key"]], rows[None])[0]
            if (np.asarray(op["ver"]) == BAD).any():
                bad[0] += 1
            return True, ""
        cntl = rpc.Controller()
        cntl.timeout_ms = timeout_ms
        value = update_words(seed, me, op["k"], op["key"], op["field"])
        resp = tb_pb2.TableUpdateResponse()
        ch.call_method("Table.Update", cntl, tb_pb2.TableUpdateRequest(
            key=op["key"], field=op["field"],
            value=value.astype("<u4").tobytes()), resp)
        return (not cntl.failed() and resp.ok), cntl.error_text

    # warm the connection and the server's read path: reads only, so the
    # table holds its bulk load when the window opens
    rng = np.random.default_rng([seed % (1 << 64), 5, me])
    for key in rng.integers(0, int(t["records"]), 4).tolist():
        cntl, _ = read(key, WARM_MS)
        if cntl.failed():
            raise SystemExit(f"warm-up read: {cntl.error_text}")
    print("ready", flush=True)
    go = json.loads(sys.stdin.readline())
    # the generator's own collector pauses would read as the server's
    gc.disable()
    records = kind.drive(send, t, seed, me, go["t0"], go["seconds"],
                         time.monotonic)
    gc.enable()
    reads = [r for r in records if r["op"] == "read"]
    updates = [r for r in records if r["op"] == "update"]
    arrays = {}
    for kind_, rs in (("read", reads), ("update", updates)):
        for col, dtype in (("k", np.int64), ("key", np.int64),
                           ("sent", np.float64), ("done", np.float64),
                           ("ok", bool)):
            arrays[f"{kind_}_{col}"] = np.array([r[col] for r in rs], dtype)
    arrays["update_field"] = np.array([r["field"] for r in updates],
                                      np.int64)
    arrays["read_ver"] = np.array(
        [r["ver"] if r["ok"] else [NOT_READ] * FIELDS for r in reads],
        np.int64).reshape(len(reads), FIELDS)
    print(json.dumps({"client": me, "bad_reads": bad[0],
                      "errors": [[r["k"], r["error"]] for r in records
                                 if not r["ok"]],
                      "arrays": _pack(arrays)}), flush=True)
    ch.close()


if __name__ == "__main__":
    if sys.argv[1:2] != ["--client"]:
        raise SystemExit("usage: ycsb_table.py --client <json>")
    client_main(json.loads(sys.argv[2]))
