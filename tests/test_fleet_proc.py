"""Cross-process fleet serving tests (sampling/fleet_proc.py).

Three tiers, cheapest first:

  * Transport units — frame codec, corrupt-frame rejection, deadlines,
    backoff-schedule reuse, heartbeat staleness — run against an in-process
    mini peer thread: no worker processes, no engines, milliseconds each.
  * The spill-transfer ledger law across a framed wire round-trip.
  * ONE non-slow end-to-end representative: 2 worker processes behind a
    FleetRouter, kill -9 mid-decode, zero drops + cross-process greedy
    parity (the gate chaos_serve._run_proc_fleet_chaos's docstring promises
    this file runs non-slow). The remaining wire-kind scenarios, SIGTERM
    drain, and live cross-worker spill transfer are @slow.
"""

import os
import signal
import socket
import subprocess
import threading
import zlib

import numpy as np
import pytest

from midgpt_tpu.robustness.backoff import backoff_delays
from midgpt_tpu.sampling import fleet_proc as fp
from midgpt_tpu.sampling.fleet_proc import (
    ReplicaGoneError,
    ReplicaTransport,
    SpillTransferItem,
    TransportError,
    WireFrameError,
    decode_frame,
    encode_frame,
)


# -- frame codec ------------------------------------------------------------


def test_frame_roundtrip_preserves_tree_and_dtypes():
    tree = {
        "op": "submit",
        "none": None,
        "flag": True,
        "n": 7,
        "x": 2.5,
        "s": "tok",
        "nested": {"list": [1, [2, {"deep": "yes"}]]},
        "k_f32": np.linspace(0, 1, 24, dtype=np.float32).reshape(2, 3, 4),
        "v_i8": np.arange(-8, 8, dtype=np.int8).reshape(4, 4),
        "ids": np.array([5, 6, 7], dtype=np.int32),
        "scalar": np.array(3.5, dtype=np.float64),
        "blocks": {"k": np.ones((2, 8), np.uint8)},
    }
    out = decode_frame(encode_frame(tree))
    assert out["op"] == "submit" and out["none"] is None
    assert out["flag"] is True and out["n"] == 7 and out["x"] == 2.5
    assert out["nested"] == {"list": [1, [2, {"deep": "yes"}]]}
    for key in ("k_f32", "v_i8", "ids", "scalar"):
        assert out[key].dtype == tree[key].dtype, key
        assert out[key].shape == tree[key].shape, key
        np.testing.assert_array_equal(out[key], tree[key])
    np.testing.assert_array_equal(out["blocks"]["k"], tree["blocks"]["k"])
    # landed arrays must be mutable (SpillTier.corrupt_one writes in place)
    assert out["k_f32"].flags.writeable
    out["k_f32"][0, 0, 0] = -1.0


def test_frame_rejects_garbage_before_decode():
    data = encode_frame({"op": "step", "payload": list(range(64))})

    with pytest.raises(WireFrameError) as ei:
        decode_frame(data[:3])
    assert ei.value.reason == "truncated" and ei.value.nbytes == 3

    with pytest.raises(WireFrameError) as ei:
        decode_frame(b"XGW1" + data[4:])
    assert ei.value.reason == "bad_magic"

    with pytest.raises(WireFrameError) as ei:
        decode_frame(data[:-2])
    assert ei.value.reason == "truncated"

    flipped = bytearray(data)
    flipped[-1] ^= 0xFF
    with pytest.raises(WireFrameError) as ei:
        decode_frame(bytes(flipped))
    assert ei.value.reason == "checksum"

    huge = fp._HEADER.pack(fp._MAGIC, fp.MAX_FRAME_BYTES + 1, 0)
    with pytest.raises(WireFrameError) as ei:
        decode_frame(huge)
    assert ei.value.reason == "length"


def test_error_contract_fields_are_present():
    """The GC016 registry (analysis/error_contracts.py) pins these
    signatures; this is the runtime half — every contract field lands as
    an attribute on a live instance."""
    te = TransportError("x", host="h", port=9, rpc="step", deadline_s=1.5)
    assert (te.host, te.port, te.rpc, te.deadline_s) == ("h", 9, "step", 1.5)
    assert isinstance(te, ConnectionError)

    wf = WireFrameError("x", reason="checksum", nbytes=12)
    assert (wf.reason, wf.nbytes) == ("checksum", 12)
    assert isinstance(wf, ValueError)

    rg = ReplicaGoneError("x", host="h", port=9, rpc="harvest", attempts=3)
    assert (rg.host, rg.port, rg.rpc, rg.attempts) == ("h", 9, "harvest", 3)
    assert isinstance(rg, ConnectionError)

    from midgpt_tpu.analysis.error_contracts import ERROR_CONTRACTS

    for name in ("TransportError", "WireFrameError", "ReplicaGoneError"):
        assert name in ERROR_CONTRACTS


# -- transport vs an in-process mini peer -----------------------------------


class _MiniPeer(threading.Thread):
    """Frame-speaking peer thread: echoes each request as
    {"ok": True, "seq": ...}; mode "mute" swallows requests so the
    caller's per-RPC deadline is the only way out."""

    def __init__(self, mode: str = "echo"):
        super().__init__(daemon=True)
        self.mode = mode
        self.srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self.srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self.srv.bind(("127.0.0.1", 0))
        self.srv.listen(8)
        self.port = self.srv.getsockname()[1]
        self._halt = threading.Event()
        self.start()

    def run(self):
        self.srv.settimeout(0.05)
        while not self._halt.is_set():
            try:
                conn, _ = self.srv.accept()
            except socket.timeout:
                continue
            except OSError:
                return  # stop() closed the listener under us
            with conn:
                conn.settimeout(0.05)
                while not self._halt.is_set():
                    try:
                        req = fp.read_frame(conn)
                    except socket.timeout:
                        continue
                    except (ConnectionError, OSError, WireFrameError):
                        break
                    if self.mode == "mute":
                        continue
                    try:
                        fp.write_frame(
                            conn, {"ok": True, "seq": req.get("seq")}
                        )
                    except (ConnectionError, OSError):
                        break

    def stop(self):
        self._halt.set()
        self.srv.close()
        self.join(timeout=2)


@pytest.fixture
def echo_peer():
    peer = _MiniPeer("echo")
    yield peer
    peer.stop()


@pytest.fixture
def mute_peer():
    peer = _MiniPeer("mute")
    yield peer
    peer.stop()


def test_deadline_expiry_escalates_to_replica_gone(mute_peer):
    slept = []
    t = ReplicaTransport(
        "127.0.0.1",
        mute_peer.port,
        rpc_deadline_s=0.15,
        call_retries=2,
        retry_base_s=0.01,
        sleep=slept.append,
    )
    with pytest.raises(ReplicaGoneError) as ei:
        t.call("ping")
    e = ei.value
    assert e.attempts == 2 and e.rpc == "ping"
    assert (e.host, e.port) == ("127.0.0.1", mute_peer.port)
    # both attempts timed out at the socket, each dropping the connection
    assert t.deadline_expiries == 2
    assert t.connects == 2 and t.reconnects == 1
    assert isinstance(e.__cause__, TransportError)
    assert e.__cause__.deadline_s == 0.15
    t.close()


def test_retry_sleeps_follow_the_shared_backoff_schedule(mute_peer):
    """The transport must reuse robustness/backoff.py verbatim: the sleeps
    between attempts ARE backoff_delays(retries, base_s), not a private
    schedule (pinned so the two can't drift apart)."""
    slept = []
    t = ReplicaTransport(
        "127.0.0.1",
        mute_peer.port,
        rpc_deadline_s=0.1,
        call_retries=3,
        retry_base_s=0.07,
        sleep=slept.append,
    )
    with pytest.raises(ReplicaGoneError):
        t.call("ping")
    assert slept == list(backoff_delays(3, 0.07))
    assert t.retries == len(slept) == 2
    t.close()


def test_connect_refused_is_replica_gone():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        dead_port = s.getsockname()[1]
    t = ReplicaTransport(
        "127.0.0.1", dead_port, call_retries=2, retry_base_s=0.0,
        sleep=lambda _d: None,
    )
    with pytest.raises(ReplicaGoneError) as ei:
        t.call("hello")
    assert ei.value.attempts == 2
    assert t.connects == 0  # never got a connection at all


def test_heartbeat_tracks_injected_clock(echo_peer):
    ticks = iter([10.0, 10.5, 20.0, 20.25])
    t = ReplicaTransport(
        "127.0.0.1", echo_peer.port, clock=lambda: next(ticks)
    )
    assert t.heartbeat_age(99.0) is None  # no RPC yet: no heartbeat
    t.call("ping")
    assert t.last_ok == 10.5
    assert t.heartbeat_age(12.5) == pytest.approx(2.0)
    t.call("ping")  # a fresh RPC resets staleness
    assert t.last_ok == 20.25
    assert t.heartbeat_age(20.25) == pytest.approx(0.0)
    assert t.stats()["rpc_p95_ms"] >= t.stats()["rpc_p50_ms"] > 0
    t.close()


def test_wire_corrupt_rejected_pre_decode_then_recovers(echo_peer):
    t = ReplicaTransport(
        "127.0.0.1", echo_peer.port, call_retries=3, retry_base_s=0.0,
        sleep=lambda _d: None,
    )
    t.arm_wire_corrupt()
    reply = t.call("ping")
    assert reply["ok"] is True
    assert t.corrupt_frames == 1  # checksum rejected the flipped frame
    assert t.retries == 1 and t.reconnects == 1  # fresh conn recovered it
    t.close()


def test_wire_stall_counts_deadline_then_recovers(echo_peer):
    t = ReplicaTransport(
        "127.0.0.1", echo_peer.port, call_retries=3, retry_base_s=0.0,
        sleep=lambda _d: None,
    )
    t.arm_wire_stall()
    reply = t.call("ping")
    assert reply["ok"] is True
    assert t.deadline_expiries == 1 and t.retries == 1
    t.close()


def test_conn_drop_reconnects_transparently(echo_peer):
    t = ReplicaTransport("127.0.0.1", echo_peer.port)
    assert t.call("ping")["ok"] is True
    t.drop_conn()
    assert t.call("ping")["ok"] is True  # no retry needed, just reconnect
    assert t.forced_drops == 1 and t.reconnects == 1 and t.retries == 0
    assert t.stats()["rpc_count"] == 2
    t.close()


# -- spill transfer ledger across the wire ----------------------------------


def _transfer_items(n, wv="inline"):
    rng = np.random.default_rng(42)
    return [
        SpillTransferItem(
            key=(7, i),
            blocks={
                "k": rng.standard_normal((2, 8, 4)).astype(np.float32),
                "v": rng.standard_normal((2, 8, 4)).astype(np.float32),
            },
            checksum=zlib.crc32(b"page-%d" % i),
            weights_version=wv,
        )
        for i in range(n)
    ]


def test_spill_transfer_ledger_closes_across_wire_roundtrip():
    """Conservation across the boundary: pages leaving one tier through
    `transferred` re-enter another through `received` — after a real frame
    encode/decode — and BOTH ledgers keep closing (SpillTier.assert_ledger).
    Checksums must arrive unchanged: take-side verification covers transit
    and residence with the one spill-time number."""
    from midgpt_tpu.sampling.fleet import SpillTier

    items = _transfer_items(3)
    a, b = SpillTier(), SpillTier()
    a.import_entries(items)
    assert a.ledger()["received"] == 3 and a.resident_count() == 3
    a.assert_ledger("after landing")

    exported = a.export_entries()
    assert a.resident_count() == 0 and a.ledger()["transferred"] == 3
    a.assert_ledger("after export")  # moved out, still conserved

    # the actual wire: frame the export exactly like the spill RPCs do
    wired = decode_frame(
        encode_frame(
            [
                {
                    "key": list(it.key),
                    "blocks": it.blocks,
                    "checksum": it.checksum,
                    "weights_version": it.weights_version,
                }
                for it in exported
            ]
        )
    )
    landed = [
        SpillTransferItem(
            key=tuple(int(t) for t in d["key"]),
            blocks=d["blocks"],
            checksum=int(d["checksum"]),
            weights_version=str(d["weights_version"]),
        )
        for d in wired
    ]
    assert b.import_entries(landed) == 3
    b.assert_ledger("after import")
    out = {it.key: it for it in b.export_entries()}
    for it in items:
        got = out[it.key]
        assert got.checksum == it.checksum  # original spill-time crc32
        np.testing.assert_array_equal(got.blocks["k"], it.blocks["k"])

    # a duplicate delivery (retried RPC) discards, never double-counts
    b.import_entries(landed)
    c = SpillTier()
    c.import_entries(landed)
    c.import_entries(landed)
    led = c.ledger()
    assert led["received"] == 6 and led["stale_discarded"] == 3
    assert c.resident_count() == 3
    c.assert_ledger("after duplicate delivery")


# -- end-to-end worker processes --------------------------------------------


def test_proc_kill9_failover_representative():
    """THE cheap cross-process gate (kept non-slow deliberately — the
    chaos_serve proc docstrings cite this file for it): two worker
    processes behind a FleetRouter, SIGKILL the busiest mid-decode, and
    the fleet must finish every accepted stream token-for-token equal to
    a fault-free single-worker reference, with the router process
    compiling nothing."""
    from midgpt_tpu.robustness.chaos_serve import run_serving_chaos

    s = run_serving_chaos("proc_kill9@6", seed=0, n_requests=4)
    assert s["procs"] is True
    assert s["faults_fired"].get("proc_kill9", 0) >= 1
    assert s["dropped_streams"] == 0
    assert s["parity_checked"] == 4 and s["parity_ok"] == 4
    assert s["proc_failovers"] >= 1 and s["failovers"] >= 1
    assert s["failed_over_streams"] >= 1
    assert s["fleet_size"] == 2 and s["alive"] == 1
    assert s["pages_conserved"] is True
    assert s["router_compiles_delta"] == 0
    assert s["transport"]["rpc_count"] > 0
    assert s["transport"]["wire_bytes"] >= 1  # frames did cross the socket


@pytest.mark.slow
def test_proc_conn_drop_absorbed():
    from midgpt_tpu.robustness.chaos_serve import run_serving_chaos

    s = run_serving_chaos("conn_drop@5", seed=0, n_requests=4)
    assert s["dropped_streams"] == 0
    assert s["parity_ok"] == s["parity_checked"] == 4
    assert s["transport"]["reconnects"] >= 1
    assert s["alive"] == 2  # absorbed by the transport: nobody failed over
    assert s["proc_failovers"] == 0


@pytest.mark.slow
def test_proc_wire_corrupt_absorbed():
    from midgpt_tpu.robustness.chaos_serve import run_serving_chaos

    s = run_serving_chaos("wire_corrupt@5", seed=0, n_requests=4)
    assert s["dropped_streams"] == 0
    assert s["parity_ok"] == s["parity_checked"] == 4
    assert s["transport"]["corrupt_frames"] >= 1
    assert s["transport"]["retries"] >= 1
    assert s["alive"] == 2


@pytest.mark.slow
def test_proc_wire_stall_absorbed():
    from midgpt_tpu.robustness.chaos_serve import run_serving_chaos

    s = run_serving_chaos("wire_stall@5", seed=0, n_requests=4)
    assert s["dropped_streams"] == 0
    assert s["parity_ok"] == s["parity_checked"] == 4
    assert s["transport"]["deadline_expiries"] >= 1
    assert s["alive"] == 2


@pytest.mark.slow
def test_sigterm_drains_worker_to_clean_exit():
    """SIGTERM routes through the preempt flag: the worker refuses new
    admissions with NON-retryable backpressure, finishes its in-flight
    streams, and exits 0 once idle and disconnected."""
    from midgpt_tpu.robustness.chaos_serve import _tiny_cfg, _trace, proc_worker_spec
    from midgpt_tpu.sampling.serve import BackpressureError

    proc, port = fp.spawn_worker(proc_worker_spec(0))
    try:
        rep = fp.connect_replica(port)
        trace = _trace(_tiny_cfg(), 1, 3, shared=True)
        uids = [rep.submit(p, m) for p, m in trace[:2]]
        os.kill(rep.pid, signal.SIGTERM)
        rep.step()  # worker notices the flag between RPCs
        with pytest.raises(BackpressureError) as ei:
            rep.submit(*trace[2])
        assert ei.value.retryable is False
        rep.run()  # in-flight streams still finish
        for uid in uids:
            assert rep.finished[uid].status == "ok"
        rep.assert_conserved("after drain")
        rep.close()
        assert proc.wait(timeout=60) == 0
    finally:
        try:
            proc.kill()
        except OSError:
            pass


@pytest.mark.slow
def test_spill_transfer_between_live_workers():
    """A trie flush spills KV into worker A's host tier; export_spill /
    import_spill move it to worker B over the wire and BOTH workers'
    conservation laws (pool + spill ledger, checked in-process via the
    conserve RPC) keep closing."""
    from midgpt_tpu.robustness.chaos_serve import _tiny_cfg, _trace, proc_worker_spec

    workers = fp.spawn_workers(proc_worker_spec(0), 2)
    try:
        a, b = (fp.connect_replica(port) for _, port in workers)
        trace = _trace(_tiny_cfg(), 1, 4, shared=True)
        for prompt, m in trace:
            a.submit(prompt, m)
        a.run()
        a._evict_shared_prefix_fault()  # flush the trie -> spill to tier

        items = a.export_spill()
        assert items, "trie flush spilled nothing — the test lost its prey"
        assert b.import_spill(items) == len(items)

        a.assert_conserved("after export")
        b.assert_conserved("after import")
        assert a.spill_ledger()["transferred"] == len(items)
        assert b.spill_ledger()["received"] == len(items)
        assert b.spill_ledger()["resident"] == len(items)
        a.close()
        b.close()
    finally:
        for proc, _port in workers:
            try:
                proc.kill()
                proc.wait(timeout=10)
            except (OSError, subprocess.TimeoutExpired):
                pass
