"""Segment-log storage unit tests (ISSUE 8): record framing + CRC
recovery, rollover/recycle, committed offsets, DurableRingBuffer
contract (spill, ack floor, put_front reinstatement, restart
re-exposure), and the replay cursor."""

import glob
import os

import numpy as np
import pytest

from psana_ray_tpu.records import EndOfStream, FrameRecord
from psana_ray_tpu.storage import (
    REPLAY_BEGIN,
    REPLAY_RESUME,
    DurableRingBuffer,
    SegmentLog,
)
from psana_ray_tpu.transport.ring import EMPTY


def _rec(i, value=None, shape=(1, 8, 8)):
    return FrameRecord(
        0, i, np.full(shape, i if value is None else value, np.uint16), 9.5
    )


def _log(tmp_path, **kw):
    kw.setdefault("segment_bytes", 1 << 20)
    kw.setdefault("fsync", "none")
    return SegmentLog(str(tmp_path / "log"), name="t", **kw)


class TestSegmentLog:
    def test_append_read_round_trip_all_payload_kinds(self, tmp_path):
        log = _log(tmp_path)
        o0 = log.append(_rec(7))
        o1 = log.append(EndOfStream(total_events=7, producer_rank=3))
        o2 = log.append({"arbitrary": "pickle"})
        assert (o0, o1, o2) == (0, 1, 2)
        back = log.read(o0)
        assert back.equals(_rec(7)) and back.panels.dtype == np.uint16
        eos = log.read(o1)
        assert isinstance(eos, EndOfStream) and eos.producer_rank == 3
        assert log.read(o2) == {"arbitrary": "pickle"}
        log.close()

    def test_offsets_survive_reopen(self, tmp_path):
        log = _log(tmp_path)
        for i in range(5):
            log.append(_rec(i))
        log.commit(2, "")
        log.commit(4, "model-v2")
        log.close()
        log2 = _log(tmp_path)
        assert log2.next_offset == 5
        assert log2.committed("") == 2
        assert log2.committed("model-v2") == 4
        assert log2.read(3).event_idx == 3
        log2.close()

    def test_commit_is_monotonic(self, tmp_path):
        log = _log(tmp_path)
        log.append(_rec(0))
        assert log.commit(0, "g") is True
        assert log.commit(0, "g") is False  # no regress, no rewrite
        log.close()

    def test_rollover_and_recycle_bound_disk(self, tmp_path):
        log = _log(tmp_path, segment_bytes=4096, retain_segments=2)
        q = DurableRingBuffer(log, maxsize=500, ram_items=8, name="t")
        for i in range(100):
            assert q.put(_rec(i))
        assert log.stats()["segments"] > 3  # really rolled
        out = q.get_batch(200, timeout=0)
        assert len(out) == 100
        q.ack_delivered(out)
        s = log.stats()
        # retention: at most retain+1 live segments of consumed history
        assert s["segments"] <= 3
        assert s["first_retained_offset"] > 0  # history really recycled
        # recycled segments sit on the free list OUT of the seg namespace
        free = glob.glob(str(tmp_path / "log" / "free-*.seg"))
        assert len(free) == s["free_segments"] <= 2
        log.close()

    def test_torn_tail_truncated_and_flagged(self, tmp_path):
        log = _log(tmp_path)
        for i in range(6):
            log.append(_rec(i))
        seg = log._segments[-1]
        victim_pos = seg.find(5)
        path = seg.path
        log.close()
        with open(path, "r+b") as f:  # corrupt the LAST record's payload
            f.seek(victim_pos + 24)
            f.write(b"\xde\xad\xbe\xef")
        log2 = _log(tmp_path)
        assert log2.torn_tail_repaired is True
        assert log2.next_offset == 5  # truncated to the last valid record
        assert log2.read(4).event_idx == 4
        # the repaired region appends cleanly again
        assert log2.append(_rec(50)) == 5
        assert log2.read(5).event_idx == 50
        log2.close()

    def test_free_segment_leftovers_ignored_on_boot(self, tmp_path):
        log = _log(tmp_path)
        log.append(_rec(0))
        log.close()
        # a crash can leave retired free-* files around: they must never
        # scan as history
        open(str(tmp_path / "log" / "free-9.seg"), "wb").write(b"\x01" * 64)
        log2 = _log(tmp_path)
        assert log2.next_offset == 1
        assert not os.path.exists(str(tmp_path / "log" / "free-9.seg"))
        log2.close()

    def test_oversized_record_fails_fast(self, tmp_path):
        log = _log(tmp_path, segment_bytes=4096)
        with pytest.raises(ValueError, match="segment_bytes"):
            log.append(_rec(0, shape=(4, 64, 64)))  # 32 KB > 4 KB segment
        log.close()

    def test_offset_store_compacts(self, tmp_path):
        log = _log(tmp_path)
        log.append(_rec(0))
        for i in range(3000):  # enough lines to cross the threshold
            log.commit(i, f"g{i % 7}")
        path = str(tmp_path / "log" / "offsets.jsonl")
        assert os.path.getsize(path) < 128 * 1024
        log.close()
        log2 = _log(tmp_path)
        assert log2.committed("g0") == 2996
        log2.close()


class TestDurableRingBuffer:
    def test_contract_parity_with_ringbuffer(self, tmp_path):
        q = DurableRingBuffer(_log(tmp_path), maxsize=2, name="t")
        assert q.get() is EMPTY
        assert q.put(_rec(0)) and q.put(_rec(1))
        assert q.put(_rec(2)) is False  # full, rejected, NOT logged
        assert q.log.next_offset == 2
        assert q.get().event_idx == 0
        assert q.size() == 1
        stats = q.stats()
        assert stats["durable"] is True and stats["puts"] == 2

    def test_spill_beyond_ram_bounded_depth(self, tmp_path):
        q = DurableRingBuffer(
            _log(tmp_path), maxsize=64, ram_items=4, name="t"
        )
        for i in range(40):
            assert q.put(_rec(i))
        st = q.stats()
        assert st["resident"] == 4 and st["spilled"] == 36
        out = q.get_batch(64, timeout=0)
        assert [r.event_idx for r in out] == list(range(40))
        # spilled records decode to full-fidelity owned copies
        assert np.array_equal(out[20].panels, _rec(20).panels)
        assert q.stats()["spilled"] == 0

    def test_ack_floor_advances_only_over_acked_prefix(self, tmp_path):
        q = DurableRingBuffer(_log(tmp_path), maxsize=16, name="t")
        for i in range(6):
            q.put(_rec(i))
        a, b, c = q.get(), q.get(), q.get()
        q.ack_delivered([b])  # out-of-order ack: floor must NOT move
        assert q.stats()["committed_offset"] == -1
        q.ack_delivered([a])
        assert q.stats()["committed_offset"] == 1  # a+b contiguous now
        q.ack_delivered([c])
        assert q.stats()["committed_offset"] == 2

    def test_put_front_reinstates_original_offset(self, tmp_path):
        q = DurableRingBuffer(_log(tmp_path), maxsize=16, name="t")
        q.put(_rec(0))
        q.put(_rec(1))
        x = q.get()
        logged = q.log.next_offset
        q.put_front(x)  # crash-redelivery path: NO duplicate append
        assert q.log.next_offset == logged
        y = q.get()
        assert y.event_idx == 0
        q.ack_delivered([y])
        assert q.stats()["committed_offset"] == 0

    def test_restart_reexposes_unconsumed_range(self, tmp_path):
        q = DurableRingBuffer(_log(tmp_path), maxsize=32, name="t")
        for i in range(10):
            q.put(_rec(i))
        q.put(EndOfStream(total_events=10))
        got = q.get_batch(4, timeout=0)
        q.ack_delivered(got)
        delivered_unacked = q.get_batch(2, timeout=0)  # popped, NEVER acked
        assert [r.event_idx for r in delivered_unacked] == [4, 5]
        q.log.close()  # crash: nothing graceful beyond page cache
        q2 = DurableRingBuffer(_log(tmp_path), maxsize=32, name="t")
        rest = q2.get_batch(32, timeout=0)
        idxs = [getattr(r, "event_idx", "EOS") for r in rest]
        # rewind to committed offset: the unacked 4,5 REDELIVER (dupes
        # possible), 6..9 + EOS arrive, nothing lost
        assert idxs == [4, 5, 6, 7, 8, 9, "EOS"]
        q2.log.close()

    def test_commit_on_get_mode(self, tmp_path):
        q = DurableRingBuffer(
            _log(tmp_path), maxsize=8, name="t", commit_on_get=True
        )
        q.put(_rec(0))
        q.put(_rec(1))
        q.get()
        assert q.stats()["committed_offset"] == 0
        assert q.stats()["outstanding"] == 0  # nothing tracked

    def test_replay_cursor_begin_and_resume(self, tmp_path):
        q = DurableRingBuffer(_log(tmp_path), maxsize=32, name="t")
        for i in range(8):
            q.put(_rec(i))
        live = q.get_batch(8, timeout=0)
        q.ack_delivered(live)  # live consumption complete
        cur = q.open_replay("model-v2", REPLAY_BEGIN)
        first = cur.next_batch(3)
        assert [r.event_idx for r in first] == [0, 1, 2]
        assert cur.commit() is True
        # resume continues after the committed position
        cur2 = q.open_replay("model-v2", REPLAY_RESUME)
        rest = cur2.next_batch(32)
        assert [r.event_idx for r in rest] == [3, 4, 5, 6, 7]
        assert cur2.caught_up()
        # a second group is independent
        cur3 = q.open_replay("model-v3", REPLAY_RESUME)
        assert [r.event_idx for r in cur3.next_batch(2)] == [0, 1]

    def test_heartbeat_suffix_surfaces_durability_breadcrumbs(self, tmp_path):
        from psana_ray_tpu.obs.tracing import obs_status_suffix

        log = _log(tmp_path, segment_bytes=4096)
        q = DurableRingBuffer(log, maxsize=200, ram_items=2, name="t")
        for i in range(20):  # forces rollovers AND spill
            q.put(_rec(i))
        suffix = obs_status_suffix()
        assert "durable[" in suffix
        assert "roll=" in suffix and "spill=" in suffix and "torn=" in suffix
        log.close()

    def test_replay_does_not_disturb_live_queue(self, tmp_path):
        q = DurableRingBuffer(_log(tmp_path), maxsize=32, name="t")
        for i in range(5):
            q.put(_rec(i))
        cur = q.open_replay("g", REPLAY_BEGIN)
        assert len(cur.next_batch(100)) == 5
        assert q.size() == 5  # live depth untouched
        assert [r.event_idx for r in q.get_batch(8, timeout=0)] == list(range(5))


@pytest.mark.parametrize("policy", ["none", "batch", "always"])
def test_the_flush_policy_is_the_one_given_for_the_life_of_the_log(tmp_path, monkeypatch, policy):
    """What a machine crash can lose is what the configuration states:
    N appends make exactly the syncs the policy says (counted, not
    timed), and the log has no dial that moves it afterwards."""
    from psana_ray_tpu.storage.segment import Segment

    syncs = []
    real_sync = Segment.sync
    monkeypatch.setattr(Segment, "sync", lambda seg: (syncs.append(seg), real_sync(seg))[1])
    n, every = 24, 8
    said = {"none": 0, "batch": n // every, "always": n}[policy]
    log = _log(tmp_path, fsync=policy, fsync_batch_n=every)
    for i in range(n):
        log.append(_rec(i))
    assert len(syncs) == said
    assert (log.fsync, log.fsync_batch_n) == (policy, every)
    assert not [name for name in dir(log) if name.startswith("set_")]
    for dial, value in (("fsync", "none"), ("fsync_batch_n", 1 << 20)):
        with pytest.raises(AttributeError):
            setattr(log, dial, value)
    for i in range(n, 2 * n):  # the same cadence after the attempts
        log.append(_rec(i))
    assert len(syncs) == 2 * said
    log.close()
