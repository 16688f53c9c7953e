"""WAL-replay equivalence: the property L10 checks statically.

A GCS rehydrates two ways — replaying ``wal.pkl`` through the live
``_op_*`` bodies, or loading ``snapshot.pkl`` through ``_restore_state``
(compaction switches ops from the first representation to the second).
L10 statically verifies every WAL op's tables round-trip through both;
this suite verifies the dynamic half: a cluster state built from a
diverse op mix must be table-for-table identical whichever path
rehydrates it. Runs with RTPU_SANITIZE armed and the interleaving
fuzzer driving adversarial schedules (conftest arms both for this
module).
"""

from __future__ import annotations

import os
import pickle
import shutil

from ray_tpu.core.cluster.gcs import _WAL_OPS, GcsServer

KEY = b"k" * 16

NODE_A = b"a" * 16
NODE_B = b"b" * 16
NODE_C = b"c" * 16
ADDR_A = ("127.0.0.1", 7001)
ADDR_B = ("127.0.0.1", 7002)
ADDR_C = ("127.0.0.1", 7003)


def _seed_ops():
    """A state-building op mix covering every table _WAL_OPS protects:
    nodes (with drain lifecycle), kv (all mutating sub-ops), named
    actors, actor table + specs, locations + sizes, freed tombstones,
    pubsub channels/cursors, and the function table."""
    oid1, oid2, oid3 = b"1" * 16, b"2" * 16, b"3" * 16
    aid1, aid2 = b"x" * 16, b"y" * 16
    return [
        ("register_node", NODE_A, ADDR_A, {"CPU": 4}, {"slice": 0}, {}),
        ("register_node", NODE_B, ADDR_B, {"CPU": 2}, {"slice": 1},
         {"zone": "z1"}),
        ("kv", "put", "job/1", {"status": "PENDING"}),
        ("kv", "merge", "job/1", {"status": "RUNNING", "pid": 42}),
        ("kv", "cas_merge", "job/1",
         ({"status": "RUNNING"}, {"status": "SUCCEEDED"})),
        ("kv", "cas_merge", "job/1",
         ({"status": "RUNNING"}, {"status": "LOST-RACE"})),  # must lose
        ("kv", "put", "cfg", {"v": 1}),
        ("kv", "del", "cfg"),
        ("register_actor", aid1, {"state": "ALIVE", "node": NODE_A}),
        ("register_actor_spec", aid1, {"cls": "Counter", "restarts": 1}),
        ("name_actor", "counter", aid1, ADDR_A),
        ("register_actor", aid2, {"state": "ALIVE", "node": NODE_B}),
        ("name_actor", "doomed", aid2, ADDR_B),
        ("drop_actor_name", "doomed", aid2),
        ("drop_actor_spec", aid2),
        ("loc_add", oid1, ADDR_A, 128),
        ("loc_add_batch", [oid2, oid3], ADDR_B, [64, None]),
        ("loc_add", oid2, ADDR_A, None),
        ("loc_drop", oid3, ADDR_B),
        ("freed_add", [oid3]),
        ("publish", "events", {"kind": "checkpoint", "step": 1}),
        ("publish", "events", {"kind": "checkpoint", "step": 2}),
        ("register_fn", b"f" * 16, b"pickled-fn"),
        ("drain_node", NODE_B),
        ("node_drained", NODE_B),
        ("register_node", NODE_C, ADDR_C, {"CPU": 1}, {}, {}),
        ("unregister_node", NODE_C),
    ]


def _comparable(gcs: GcsServer) -> dict:
    state = gcs._snapshot_state()
    # view_version is a cache-invalidation counter, not table data:
    # _restore_state deliberately bumps it so every client re-reads
    state.pop("view_version")
    return state


def _reopen_from_copy(src_dir: str, dst_dir: str) -> GcsServer:
    shutil.copytree(src_dir, dst_dir)
    return GcsServer(port=0, authkey=KEY, persistence_path=dst_dir)


def test_wal_replay_equals_snapshot_restore(tmp_path):
    ops = _seed_ops()
    # the mix must exercise every WAL op (so this test fails loudly when
    # someone adds a WAL op without extending the mix)
    assert {op[0] for op in ops} >= set(_WAL_OPS)

    live_dir = str(tmp_path / "live")
    live = GcsServer(port=0, authkey=KEY, persistence_path=live_dir)
    try:
        for op in ops:
            live._handle(op, {})
        want = _comparable(live)

        # path 1: WAL-only replay — copy the dir while the server is
        # live (each record is flushed on apply), before any compaction,
        # so the copy holds the raw log and no snapshot
        assert not os.path.exists(os.path.join(live_dir, "snapshot.pkl"))
        replayed = _reopen_from_copy(live_dir, str(tmp_path / "replay"))
        try:
            assert _comparable(replayed) == want
        finally:
            replayed.close()
    finally:
        live.close()

    # path 2: snapshot restore — close() compacted the WAL into
    # snapshot.pkl, so this copy rehydrates through _restore_state
    assert os.path.getsize(os.path.join(live_dir, "wal.pkl")) == 0
    restored = _reopen_from_copy(live_dir, str(tmp_path / "restore"))
    try:
        got = _comparable(restored)
        assert set(got) == set(want)
        for table in want:  # table-for-table: name the diverging table
            assert got[table] == want[table], table
    finally:
        restored.close()


def test_rehydrated_gcs_rehydrates_again(tmp_path):
    # the property must hold transitively: WAL-replay -> compaction ->
    # snapshot-restore converges to the same tables (a nondeterministic
    # replay body or a snapshot/restore gap would drift on generation 2)
    gen0_dir = str(tmp_path / "gen0")
    gen0 = GcsServer(port=0, authkey=KEY, persistence_path=gen0_dir)
    try:
        for op in _seed_ops():
            gen0._handle(op, {})
        want = _comparable(gen0)
    finally:
        gen0.close()

    gen1 = GcsServer(port=0, authkey=KEY, persistence_path=gen0_dir)
    try:
        gen1._handle(("kv", "put", "gen", 1), {})
        want["kv"]["gen"] = 1
        assert _comparable(gen1) == want
    finally:
        gen1.close()

    gen2 = GcsServer(port=0, authkey=KEY, persistence_path=gen0_dir)
    try:
        assert _comparable(gen2) == want
    finally:
        gen2.close()


def test_torn_wal_tail_replays_clean_prefix(tmp_path):
    # a crash mid-append leaves a torn final record: replay must keep
    # every complete record and drop only the tail (the same contract
    # the L4 waivers in _load_persisted document)
    live_dir = str(tmp_path / "live")
    live = GcsServer(port=0, authkey=KEY, persistence_path=live_dir)
    try:
        live._handle(("kv", "put", "a", 1), {})
        want = _comparable(live)
        live._handle(("kv", "put", "b", 2), {})
    finally:
        live._server.close()  # skip close(): leave the raw WAL behind
        live._stop = True     # but not the health loop, for the process's life
        if live._wal is not None:
            live._wal.close()
            live._wal = None

    wal_path = os.path.join(live_dir, "wal.pkl")
    with open(wal_path, "rb") as f:
        first = pickle.load(f)
        keep = f.tell()
    assert first == ("kv", ("put", "a", 1))
    with open(wal_path, "rb") as f:
        data = f.read()
    with open(wal_path, "wb") as f:
        f.write(data[:keep + 3])  # second record torn mid-frame

    reborn = GcsServer(port=0, authkey=KEY, persistence_path=live_dir)
    try:
        got = _comparable(reborn)
        assert got["kv"].get("a") == 1
        assert "b" not in got["kv"]
        assert got == want
    finally:
        reborn.close()
