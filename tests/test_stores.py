"""The tiered storage hierarchy (DESIGN.md §10/§11): DiskStore/TieredStore
semantics, disk-tier fault injection (truncated/missing blobs, full-disk
refusal — typed errors, promptly, never a hang), compile-time spill/load
chains, per-tier budget validation, and tier transparency — bounded-host
plans reproduce the unbounded oracle bit-for-bit on the threaded runtime
under every dispatch policy (a seeded mirror of the hypothesis property,
so it runs without the extra dep)."""
import os
import random as pyrandom
import threading
import time

import numpy as np
import pytest

from repro.core import (BuildConfig, MemgraphOOM, MemOp, OpKind,
                        build_memgraph)
from repro.core.dispatch import COMPUTE, DISK, POLICY_NAMES, engine_of
from repro.core.memgraph import RaceError
from repro.core.runtime import (DiskStore, HostStore, TieredStore,
                                TurnipRuntime, eval_taskgraph, make_store,
                                run_in_order)
from repro.core.simulate import HardwareModel, simulate
from repro.core.stores import DiskCorruptionError, DiskFullError

from helpers import (fig3_taskgraph, graph_inputs, int_inputs,
                     random_taskgraph)

UNITS = dict(size_fn=lambda v: 1)


# ----------------------------------------------------------------- stores
class TestDiskStore:
    def test_roundtrip_array_and_block(self, tmp_path):
        ds = DiskStore(tmp_path)
        a = np.arange(12, dtype=np.float32).reshape(3, 4)
        blk = {"k": np.ones((2, 3), np.float16), "v": np.zeros((2,), np.int8)}
        ds.put("a", a)
        ds.put(("r", 0), blk)
        assert "a" in ds and ("r", 0) in ds and "nope" not in ds
        np.testing.assert_array_equal(ds.get("a"), a)
        got = ds.get(("r", 0))
        np.testing.assert_array_equal(got["k"], blk["k"])
        assert ds.read_bytes == a.nbytes + blk["k"].nbytes + blk["v"].nbytes
        assert ds.resident_bytes == ds.read_bytes    # both values resident
        ds.drop("a")
        assert "a" not in ds and ds.resident_bytes < ds.read_bytes
        ds.close()

    def test_extended_dtypes_read_back_as_themselves(self, tmp_path):
        """bfloat16 KV blocks (what a TPU cache holds) come back from the
        log as bfloat16, bit-exact, not as anonymous void words."""
        import ml_dtypes
        ds = DiskStore(tmp_path)
        k = np.linspace(-3, 3, 24, dtype=np.float32).astype(
            ml_dtypes.bfloat16).reshape(2, 12)
        ds.put(("r", 0), {"k": k, "v": k[::-1].copy()})
        got = ds.get(("r", 0))
        assert got["k"].dtype == k.dtype and got["v"].dtype == k.dtype
        assert got["k"].tobytes() == k.tobytes()
        ds.close()

    def test_close_removes_private_dir(self):
        ds = DiskStore()
        ds.put("x", np.ones(4))
        root = ds._dir
        assert root is not None and root.exists()
        ds.close()
        assert not root.exists()


class TestTieredStore:
    def test_auto_lru_spill_and_read_through(self):
        ts = TieredStore({}, host_capacity=100)
        a, b, c = (np.full(10, i, np.float64) for i in range(3))  # 80 B each
        ts.put_offload("a", a)
        ts.put_offload("b", b)                    # over 100 B: spills "a"
        assert ts.tier_of("a") == "disk" and ts.tier_of("b") == "host"
        assert ts.resident_bytes == 80
        ts.put_offload("c", c)                    # spills LRU ("b")
        assert ts.tier_of("b") == "disk"
        np.testing.assert_array_equal(ts.get_offload("a"), a)  # read-through
        assert ts.disk.read_bytes == 80
        assert ts.tier_of("a") == "host"          # staged back (and touched)
        ts.close()

    def test_plan_driven_spill_load_drop(self):
        ts = TieredStore({}, auto_spill=False)
        v = np.arange(6, dtype=np.float32)
        ts.put_offload("k", v)
        ts.spill("k")
        assert ts.tier_of("k") == "disk" and ts.resident_bytes == 0
        ts.spill("k")                              # idempotent
        ts.load("k")
        assert ts.tier_of("k") == "host"
        ts.spill("k")                              # dedup: no second write
        assert ts.disk.write_bytes == v.nbytes
        np.testing.assert_array_equal(ts.peek_offload("k"), v)
        ts.spill("k", drop=True)                   # dead data: all tiers
        assert ts.tier_of("k") is None and ts.peek_offload("k") is None
        ts.close()

    def test_pop_drops_disk_copy_too(self):
        ts = TieredStore({})
        ts.put_offload("k", np.ones(8))
        ts.spill("k")
        ts.pop_offload("k")
        assert ts.tier_of("k") is None and ts.disk.resident_bytes == 0
        ts.close()

    def test_peak_counter(self):
        hs = HostStore({})
        hs.put_offload("a", np.ones(16))
        hs.pop_offload("a")
        assert hs.peak_resident_bytes == 128 and hs.resident_bytes == 0

    def test_overwrite_invalidates_stale_disk_twin(self):
        """Regression (data corruption): overwriting a host-resident key
        left the old disk blob alive, and the next spill dedup-skipped the
        write ('immutable disk copy already exists') — a later
        read-through returned the OLD bytes."""
        ts = TieredStore({}, auto_spill=False)
        old, new = np.arange(8.0), np.arange(8.0) * 10
        ts.put_offload("k", old)
        ts.spill("k")
        ts.load("k")                      # host copy back; disk twin alive
        ts.put_offload("k", new)          # overwrite supersedes the twin
        assert "k" not in ts.disk         # twin invalidated immediately
        ts.spill("k")                     # must really write, not dedup
        assert ts.tier_of("k") == "disk"
        np.testing.assert_array_equal(ts.get_offload("k"), new)
        ts.close()

    def test_overwrite_of_disk_only_key_invalidates_twin(self):
        """Same bug, other tier: the overwritten key's bytes lived only on
        disk — prev is None in put_offload, so nothing ever dropped the
        blob and the dedup spill kept resurrecting the old bytes."""
        ts = TieredStore({}, auto_spill=False)
        ts.put_offload("k", np.zeros(4))
        ts.spill("k")                     # host copy gone, blob holds zeros
        ts.put_offload("k", np.ones(4))
        ts.spill("k")
        np.testing.assert_array_equal(ts.get_offload("k"), np.ones(4))
        ts.close()

    def test_read_through_respects_host_budget(self):
        """Regression: load() admitted bytes without the eviction path, so
        a burst of read-throughs pushed resident_bytes past host_capacity
        with auto_spill on and no eviction ever ran."""
        ts = TieredStore({}, host_capacity=200)
        vals = {k: np.full(16, i, np.float64) for i, k in
                enumerate("abcde")}              # 128 B each, cap = 1 key
        for k, v in vals.items():
            ts.put_offload(k, v)                 # LRU-spills predecessors
        for k, v in vals.items():                # read-through sweep
            np.testing.assert_array_equal(ts.get_offload(k), v)
            assert ts.resident_bytes <= 200, \
                f"read-through of {k!r} burst the host budget"
        ts.close()


# ------------------------------------------------- disk-tier faults (§11)
class TestDiskFaults:
    """Truncated/missing spill files and full-disk refusal raise typed
    errors promptly — no executor or stream may hang on rotten bytes."""

    def test_rotted_log_raises_typed(self, tmp_path):
        ds = DiskStore(tmp_path)
        ds.put("k", np.arange(8, dtype=np.float32))
        # wipe the log out from under the store (rotted storage): the
        # record frame no longer matches the index entry
        assert ds._log_path is not None
        os.truncate(ds._log_path, 0)
        with pytest.raises(DiskCorruptionError, match="torn or corrupt"):
            ds.get("k")
        ds.close()

    def test_truncated_record_raises_typed(self, tmp_path):
        ds = DiskStore(tmp_path)
        ds.put("k", np.arange(64, dtype=np.float64))
        path = ds._log_path
        path.write_bytes(path.read_bytes()[:13])      # torn mid-write
        with pytest.raises(DiskCorruptionError):
            ds.get("k")
        # an unknown key is caller error, not corruption
        with pytest.raises(KeyError):
            ds.get("never-put")
        ds.close()

    def test_full_disk_refusal_prompt_and_typed(self):
        ds = DiskStore(capacity=100)
        ds.put("a", np.zeros(10, np.float64))          # 80 B
        with pytest.raises(DiskFullError, match="disk tier full"):
            ds.put("b", np.zeros(10, np.float64))
        # refusal left the tier unchanged; freeing space readmits
        assert ds.resident_bytes == 80 and "b" not in ds
        ds.drop("a")
        ds.put("b", np.zeros(10, np.float64))
        # overwriting charges only the delta, not put-size twice
        ds.put("b", np.zeros(12, np.float64))
        assert ds.resident_bytes == 96
        ds.close()

    def test_drop_get_race_is_keyerror_not_corruption(self):
        """Regression: DiskStore.get resolved the path under the lock but
        read the file outside it; a concurrent drop unlinking mid-read
        surfaced as DiskCorruptionError for a healthy, legitimately-freed
        blob. The dropped-key case must be a KeyError."""
        ds = DiskStore()
        reading = threading.Event()
        dropped = threading.Event()

        class _PausedRead(DiskStore):
            pass

        orig = DiskStore._read_blob

        def paused(self, entry):
            reading.set()                      # reader is past the lock
            assert dropped.wait(5)             # drop lands mid-read
            return orig(self, entry)

        ds._read_blob = paused.__get__(ds)     # instance-level seam
        ds.put("k", np.arange(16.0))
        result: list = []

        def reader():
            try:
                result.append(ds.get("k"))
            except BaseException as e:
                result.append(e)

        t = threading.Thread(target=reader)
        t.start()
        assert reading.wait(5)
        ds.drop("k")                           # unlink while the read runs
        ds.put("k", np.arange(4.0))            # and re-put: fresh path —
        dropped.set()                          # the old read is stale, not rot
        t.join(5)
        assert result, "reader never finished"
        assert isinstance(result[0], KeyError), \
            f"drop/get race misreported as {result[0]!r}"
        # a genuinely rotten record is still corruption, not KeyError
        ds._read_blob = orig.__get__(ds)
        ds.put("r", np.arange(4.0))
        off, _, _ = ds._files["r"]
        with open(ds._log_path, "r+b") as f:
            f.seek(off)
            f.write(b"rot")                    # stomp the record frame
        with pytest.raises(DiskCorruptionError):
            ds.get("r")
        ds.close()

    def test_drop_get_hammer_never_reports_corruption(self):
        """Unseamed probabilistic mirror of the race: concurrent get/drop/
        put cycles may see values or KeyError, never corruption."""
        ds = DiskStore()
        errs: list = []
        stop = threading.Event()

        def reader():
            while not stop.is_set():
                try:
                    ds.get("k", count=False)
                except KeyError:
                    pass
                except BaseException as e:     # pragma: no cover
                    errs.append(e)
                    return

        threads = [threading.Thread(target=reader) for _ in range(3)]
        for t in threads:
            t.start()
        v = np.arange(64.0)
        for _ in range(200):
            ds.put("k", v)
            ds.drop("k")
        stop.set()
        for t in threads:
            t.join(10)
        ds.close()
        assert not errs, f"drop/get race escalated: {errs[0]!r}"

    def test_tiered_auto_spill_surfaces_refusal(self):
        ts = TieredStore({}, host_capacity=100, disk_capacity=100)
        ts.put_offload("a", np.zeros(10))
        ts.put_offload("b", np.full(10, 2.0))          # spills "a": disk 80
        with pytest.raises(DiskFullError):
            ts.put_offload("c", np.zeros(10))          # next spill overflows
        # refusal rolled the hierarchy back: the spill victim's only copy
        # went back to the host tier, the refused admission was undone,
        # and the host budget still holds
        assert ts.tier_of("b") == "host"
        np.testing.assert_array_equal(ts.peek_offload("b"), np.full(10, 2.0))
        assert ts.tier_of("c") is None
        assert ts.resident_bytes <= 100
        ts.close()

    def test_refused_overwrite_keeps_old_disk_twin(self):
        """A refused put_offload must leave the hierarchy at its pre-put
        state *including* the overwritten key's disk twin: invalidating
        the twin before the admission stands would destroy the last copy
        on refusal."""
        ts = TieredStore({}, host_capacity=80, disk_capacity=80)
        ts.put_offload("k", np.zeros(10))              # 80 B
        ts.spill("k")                                  # old bytes disk-only
        ts.put_offload("other", np.ones(10))           # host holds 80/80
        with pytest.raises(DiskFullError):
            ts.put_offload("k", np.full(10, 2.0))      # eviction can't fit
        # the refusal lost nothing: k's OLD bytes are still readable
        np.testing.assert_array_equal(ts.get_offload("k"), np.zeros(10))
        ts.close()

    def test_plan_driven_spill_refusal_keeps_host_copy(self):
        ts = TieredStore({}, auto_spill=False, disk=DiskStore(capacity=0))
        ts.put_offload("k", np.arange(4.0))
        with pytest.raises(DiskFullError):
            ts.spill("k")
        assert ts.tier_of("k") == "host"               # nothing changed
        np.testing.assert_array_equal(ts.get_offload("k"), np.arange(4.0))
        ts.close()

    def test_runtime_surfaces_load_fault_and_joins_all_streams(self):
        """A rotten blob hit by a LOAD on the disk engine must surface as
        DiskCorruptionError from run() — promptly, with every stream
        (compute, DMA, *and* disk) deterministically joined on the error
        path. A silently dead disk thread would wedge the consumers."""

        class _RottenDisk(DiskStore):
            def get(self, key, *, count: bool = True):
                raise DiskCorruptionError(f"injected rot for {key!r}")

        tg = fig3_taskgraph()
        res = build_memgraph(tg, BuildConfig(capacity=3, host_capacity=1,
                                             **UNITS))
        assert res.n_loads > 0
        store = TieredStore(int_inputs(tg), auto_spill=False,
                            disk=_RottenDisk())
        before = set(threading.enumerate())
        t0 = time.monotonic()
        try:
            with pytest.raises(DiskCorruptionError, match="injected rot"):
                TurnipRuntime(tg, res, mode="nondet", policy="random",
                              seed=0,
                              store_factory=lambda inputs: store
                              ).run(int_inputs(tg))
        finally:
            store.close()
        assert time.monotonic() - t0 < 30            # prompt, not a hang
        leaked = {t for t in set(threading.enumerate()) - before
                  if t.name.startswith("turnip-")}
        assert not leaked, f"streams leaked on error path: {leaked}"

    def test_runtime_surfaces_spill_fault_promptly(self):
        """Same discipline for the write side: a full disk met by a SPILL
        vertex raises DiskFullError out of run(), threads joined."""
        tg = fig3_taskgraph()
        res = build_memgraph(tg, BuildConfig(capacity=3, host_capacity=1,
                                             **UNITS))
        assert res.n_spills > 0
        store = TieredStore(int_inputs(tg), auto_spill=False,
                            disk=DiskStore(capacity=0))
        before = set(threading.enumerate())
        try:
            with pytest.raises(DiskFullError):
                TurnipRuntime(tg, res, mode="nondet", policy="random",
                              seed=0,
                              store_factory=lambda inputs: store
                              ).run(int_inputs(tg))
        finally:
            store.close()
        leaked = {t for t in set(threading.enumerate()) - before
                  if t.name.startswith("turnip-")}
        assert not leaked, f"streams leaked on error path: {leaked}"


# ----------------------------------------------------- log compaction
class TestCompaction:
    """Append-only spill.log compaction (DESIGN.md §10): when dead bytes
    dominate, the live records are streamed into a fresh log and
    atomically swapped in. Compaction is an optimization — every failure
    mode must leave the store fully functional on the old log."""

    def test_overwrite_churn_triggers_and_shrinks_log(self):
        ds = DiskStore(compact_min_bytes=1)
        keep = np.arange(256, dtype=np.float64)          # 2048 B payload
        ds.put("keep", keep)
        for i in range(8):
            ds.put("churn", np.full(256, float(i)))
        assert ds.n_compactions >= 1
        assert ds.compacted_reclaimed_bytes > 0
        # the on-disk log matches the index's view and holds far less
        # than the total bytes ever appended
        assert ds._log_path is not None
        assert os.stat(ds._log_path).st_size == ds._end
        assert ds._end < 9 * (ds._HDR.size + keep.nbytes)
        # write_bytes counts spill traffic only — compaction's internal
        # rewrite must not inflate it
        assert ds.write_bytes == 9 * keep.nbytes
        np.testing.assert_array_equal(ds.get("keep"), keep)
        np.testing.assert_array_equal(ds.get("churn"), np.full(256, 7.0))
        ds.close()
        assert not ds._retired_fds                       # no fd leak

    def test_drop_triggers_compaction(self):
        ds = DiskStore(compact_min_bytes=1)
        big = np.arange(256, dtype=np.float64)
        ds.put("a", big)
        ds.put("b", 2 * big)
        ds.drop("a")                 # dead == live → fraction 0.5 crossed
        assert ds.n_compactions == 1
        assert "a" not in ds
        assert ds._end == ds._HDR.size + big.nbytes
        assert ds.dead_bytes == 0
        np.testing.assert_array_equal(ds.get("b"), 2 * big)
        ds.close()

    def test_no_compaction_below_min_bytes_or_when_disabled(self):
        for ds in (DiskStore(),                          # default 1 MiB floor
                   DiskStore(compact_dead_fraction=None,
                             compact_min_bytes=1)):      # knob off
            for i in range(8):
                ds.put("churn", np.full(64, float(i)))
            assert ds.n_compactions == 0
            assert ds.dead_bytes > 0
            np.testing.assert_array_equal(ds.get("churn"), np.full(64, 7.0))
            ds.close()

    def test_crash_at_publish_leaves_old_log_intact(self):
        """Kill the compaction at its commit point: the atomic-rename
        seam raises. The store must carry on against the old log — the
        triggering put succeeds, every key reads back byte-exact, and
        the half-built tmp file is cleaned up."""
        ds = DiskStore(compact_min_bytes=1)

        def boom(tmp, path):
            raise OSError("injected crash at publish")

        ds._publish_compaction = boom                    # instance seam
        keep = np.arange(256, dtype=np.float64)
        ds.put("keep", keep)
        for i in range(8):
            ds.put("churn", np.full(256, float(i)))      # crossings swallowed
        assert ds.n_compactions == 0
        assert ds.dead_bytes > 0                         # nothing reclaimed
        assert ds._log_path is not None
        assert not ds._log_path.with_name("spill.log.compact").exists()
        np.testing.assert_array_equal(ds.get("keep"), keep)
        np.testing.assert_array_equal(ds.get("churn"), np.full(256, 7.0))
        del ds._publish_compaction                       # heal the seam
        ds.put("churn", np.full(256, 9.0))               # re-trigger
        assert ds.n_compactions >= 1
        np.testing.assert_array_equal(ds.get("keep"), keep)
        np.testing.assert_array_equal(ds.get("churn"), np.full(256, 9.0))
        ds.close()

    def test_crash_during_rewrite_leaves_old_log_intact(self, monkeypatch):
        """Kill the compaction mid-rewrite (fsync of the tmp log fails —
        strictly before the commit point). Old log untouched, tmp
        cleaned, store functional; once I/O heals the next trigger
        compacts successfully."""
        real_fsync = os.fsync
        monkeypatch.setattr(os, "fsync",
                            lambda fd: (_ for _ in ()).throw(
                                OSError("injected crash during rewrite")))
        ds = DiskStore(compact_min_bytes=1)
        keep = np.arange(256, dtype=np.float64)
        ds.put("keep", keep)
        for i in range(8):
            ds.put("churn", np.full(256, float(i)))
        assert ds.n_compactions == 0
        assert ds._log_path is not None
        assert not ds._log_path.with_name("spill.log.compact").exists()
        np.testing.assert_array_equal(ds.get("keep"), keep)
        monkeypatch.setattr(os, "fsync", real_fsync)
        ds.put("churn", np.full(256, 9.0))
        assert ds.n_compactions >= 1
        np.testing.assert_array_equal(ds.get("keep"), keep)
        np.testing.assert_array_equal(ds.get("churn"), np.full(256, 9.0))
        ds.close()

    def test_writes_during_the_unlocked_copy_survive_compaction(self):
        """The bulk copy runs without the store lock: a put, a re-put and a
        drop that land in the middle of it are all honoured by the
        published log, and its dead-byte count is exact."""
        ds = DiskStore(compact_min_bytes=1, compact_inline=False)
        a, b, c = (np.full(256, float(i)) for i in range(3))
        ds.put("a", a)
        ds.put("b", b)
        ds.put("gone", c)
        for i in range(3):
            ds.put(("junk", i), c)
            ds.drop(("junk", i))         # dead bytes now dominate the log
        assert ds.n_compactions == 0 and ds.compaction_due()
        orig = DiskStore._read_record
        mutated = []

        def seam(self, fd, off, n):
            if not mutated:              # first record of the bulk copy
                mutated.append(True)
                self.put("new", 2 * a)   # appended past the snapshot
                self.put("b", 3 * b)     # re-put: the copied record is stale
                self.drop("gone")        # copied, then dropped
            return orig(self, fd, off, n)

        ds._read_record = seam.__get__(ds)
        assert ds.compact_if_due()
        assert ds.n_compactions == 1 and mutated
        np.testing.assert_array_equal(ds.get("a"), a)
        np.testing.assert_array_equal(ds.get("b"), 3 * b)
        np.testing.assert_array_equal(ds.get("new"), 2 * a)
        assert "gone" not in ds
        live = sum(ds._HDR.size + n for _, n, _ in ds._files.values())
        assert ds.dead_bytes == ds._end - live > 0
        assert os.stat(ds._log_path).st_size == ds._end
        ds.close()

    def test_concurrent_writers_and_compactions_lose_nothing(self):
        """More writer threads than cores put, re-put and drop their own
        keys while compactions run inline and from a thread of their own;
        every key ends with its last value, and no dropped key returns."""
        import sys
        switch = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        ds = DiskStore(compact_min_bytes=1)
        n_threads = (os.cpu_count() or 1) + 2
        want: list[dict] = [{} for _ in range(n_threads)]
        stop = threading.Event()

        def writer(t):
            rng = pyrandom.Random(t)
            for i in range(60):
                key = (t, rng.randrange(4))
                if rng.random() < 0.3:
                    ds.drop(key)
                    want[t].pop(key, None)
                else:
                    ds.put(key, np.full(64, float(i)))
                    want[t][key] = float(i)

        def compactor():
            while not stop.is_set():
                ds.compact_if_due()
                time.sleep(1e-4)     # leave the writers' own turns free
        try:
            threads = [threading.Thread(target=writer, args=(t,))
                       for t in range(n_threads)]
            extra = threading.Thread(target=compactor)
            extra.start()
            for th in threads:
                th.start()
            for th in threads:
                th.join(60)
            stop.set()
            extra.join(60)
            assert not any(th.is_alive() for th in threads + [extra])
        finally:
            sys.setswitchinterval(switch)
        assert ds.n_compactions > 0
        for t in range(n_threads):
            for k in range(4):
                key = (t, k)
                if key in want[t]:
                    np.testing.assert_array_equal(
                        ds.get(key), np.full(64, want[t][key]))
                else:
                    assert key not in ds
        live = sum(ds._HDR.size + n for _, n, _ in ds._files.values())
        assert ds.dead_bytes == ds._end - live
        ds.close()

    def test_no_inline_compaction_when_the_owner_compacts(self):
        ds = DiskStore(compact_min_bytes=1, compact_inline=False)
        for i in range(8):
            ds.put("churn", np.full(256, float(i)))
        ds.drop("churn")
        assert ds.n_compactions == 0 and ds.compaction_due()
        assert ds.compact_if_due() and not ds.compaction_due()
        assert ds._end == 0 and ds.dead_bytes == 0
        ds.close()

    def test_reader_paused_across_compaction_retries(self):
        """A get() that resolved its index entry, then lost the CPU while
        a compaction rewrote the log, reads at a stale offset of the NEW
        log. The generation counter must send it back for a retry — the
        caller sees the correct bytes, never a spurious error."""
        ds = DiskStore(compact_min_bytes=1, compact_dead_fraction=None)
        junk = np.zeros(512)
        a = np.arange(64.0)
        ds.put("junk", junk)         # "k" lands at a nonzero offset...
        ds.put("k", a)
        ds.drop("junk")              # ...that compaction will move to 0
        reading = threading.Event()
        resume = threading.Event()
        orig = DiskStore._read_blob
        calls: list = []

        def seam(self, entry):
            calls.append(entry)
            if len(calls) == 1:      # pause only the first, stale read
                reading.set()
                assert resume.wait(5)
            return orig(self, entry)

        ds._read_blob = seam.__get__(ds)
        result: list = []

        def reader():
            try:
                result.append(ds.get("k"))
            except BaseException as e:
                result.append(e)

        t = threading.Thread(target=reader)
        t.start()
        assert reading.wait(5)       # reader holds a pre-compaction entry
        ds.compact_dead_fraction = 0.01
        ds.put("x", np.ones(4))
        ds.put("x", np.ones(4))      # overwrite trigger: rewrites the log
        assert ds.n_compactions == 1
        resume.set()
        t.join(5)
        assert result, "reader never finished"
        assert not isinstance(result[0], BaseException), \
            f"stale-offset read after compaction escalated: {result[0]!r}"
        np.testing.assert_array_equal(result[0], a)
        assert len(calls) >= 2, "generation bump did not force a retry"
        ds.close()


# ------------------------------------------------------- compiled plans
def tiered_build(cap=3, host_cap=2, **kw):
    tg = fig3_taskgraph()
    kw = {**UNITS, **kw}
    res = build_memgraph(tg, BuildConfig(capacity=cap, host_capacity=host_cap,
                                         **kw))
    return tg, res


class TestCompiledTiering:
    def test_plan_spills_and_validates_budget(self):
        tg, res = tiered_build(cap=3, host_cap=1)
        assert res.n_spills > 0 and res.n_loads > 0
        assert res.peak_host <= 1
        res.memgraph.validate(check_races=True, host_capacity=1)
        prof = res.memgraph.host_tier_profile()
        assert prof["peak_units"] <= 1
        # two-hop reloads are annotated with their tier
        tiers = {v.tier for v in res.memgraph.vertices.values()
                 if v.op == MemOp.RELOAD}
        assert "disk" in tiers

    def test_budget_validation_catches_violation(self):
        tg, res = tiered_build(cap=3, host_cap=2)
        with pytest.raises(RaceError, match="host-tier budget"):
            res.memgraph.validate(host_capacity=0)

    def test_store_selection(self):
        tg, res = tiered_build(cap=3, host_cap=1)
        assert isinstance(make_store(res.memgraph, {}), TieredStore)
        tg2, res2 = tiered_build(cap=3, host_cap=None)
        store = make_store(res2.memgraph, {})
        assert isinstance(store, HostStore)
        assert not isinstance(store, TieredStore)

    def test_disk_vertices_on_disk_engine_only(self):
        tg, res = tiered_build(cap=3, host_cap=1)
        sim = simulate(res.memgraph, HardwareModel(transfer_jitter=0.5),
                       mode="nondet", policy="transfer-first",
                       record_timeline=True)
        disk_names = {v.name for v in res.memgraph.vertices.values()
                      if v.op in (MemOp.SPILL, MemOp.LOAD)}
        assert disk_names
        for (_a, _b, _dev, eng, name) in sim.timeline:
            assert (eng == DISK) == (name in disk_names)

    def test_host_oom_when_tensor_exceeds_tier(self):
        # 3 device slots (forces offload), but a single tensor outsizes
        # the whole host tier: nothing can ever be staged
        with pytest.raises(MemgraphOOM, match="host tier"):
            tiered_build(cap=9, host_cap=2, size_fn=lambda v: 3)


# ------------------------------------------- tier transparency (seeded)
class TestTierTransparency:
    """Seeded mirror of test_property_memgraph's hypothesis property: any
    (device, host, disk) configuration must match the dataflow oracle."""

    def test_random_graphs_all_policies(self):
        n_exercised = 0
        for seed in range(10):
            tg = random_taskgraph(pyrandom.Random(seed))
            try:
                res = build_memgraph(tg, BuildConfig(
                    capacity=3, host_capacity=1 + seed % 3, **UNITS))
            except MemgraphOOM:
                continue
            if res.n_loads == 0:
                continue
            res.memgraph.validate(check_races=True,
                                  host_capacity=1 + seed % 3)
            inputs = graph_inputs(tg, seed)
            ref = eval_taskgraph(tg, inputs)
            # adversarial sequential orders
            for i in range(2):
                r = pyrandom.Random(seed * 7 + i)
                order = res.memgraph.topo_order(key=lambda m: r.random())
                out = run_in_order(tg, res, inputs, order)
                for k in ref:
                    np.testing.assert_array_equal(out[k], ref[k])
            # threaded runtime, every policy, both modes
            for policy in POLICY_NAMES:
                for mode in ("nondet", "fixed"):
                    rr = TurnipRuntime(tg, res, mode=mode, policy=policy,
                                       seed=seed).run(inputs)
                    for k in ref:
                        np.testing.assert_array_equal(rr.outputs[k], ref[k])
            n_exercised += 1
        assert n_exercised >= 3    # the sweep must hit real disk plans

    def test_working_set_exceeding_host_tier_completes(self):
        """The acceptance scenario: device working set ≫ host tier, all
        traffic flows through disk, results oracle-equal under
        random/fixed/critical-path with real disk files moving."""
        tg = fig3_taskgraph()
        inputs = int_inputs(tg)
        ref = eval_taskgraph(tg, inputs)
        res = build_memgraph(tg, BuildConfig(capacity=3, host_capacity=1,
                                             **UNITS))
        assert res.n_spills > 0
        for policy in ("random", "fixed", "critical-path"):
            rr = TurnipRuntime(tg, res, mode="nondet", policy=policy,
                               seed=2).run(inputs)
            for k in ref:
                np.testing.assert_array_equal(rr.outputs[k], ref[k])
            assert rr.disk_spill_bytes > 0 and rr.disk_load_bytes > 0
            assert rr.transfer_time[DISK] >= 0.0

    def test_latency_injected_disk_still_correct(self):
        """Slow disk hops (the two-hop nondeterminism source) change
        timing, never results — and disk latency rides the disk engine."""
        tg = fig3_taskgraph()
        inputs = int_inputs(tg)
        ref = eval_taskgraph(tg, inputs)
        res = build_memgraph(tg, BuildConfig(capacity=3, host_capacity=1,
                                             **UNITS))

        def latency(v):
            return 0.004 if engine_of(v) == DISK else 0.0005

        rr = TurnipRuntime(tg, res, mode="nondet", policy="critical-path",
                           seed=5, latency=latency).run(inputs)
        for k in ref:
            np.testing.assert_array_equal(rr.outputs[k], ref[k])
        # timeline: disk ops only ever occupy the disk engine
        disk_rows = [t for t in rr.timeline if t[3] == DISK]
        assert disk_rows
        for (_a, _b, _dev, eng, name) in rr.timeline:
            is_disk = name.startswith(("spill:", "load:", "drop:"))
            assert (eng == DISK) == is_disk
