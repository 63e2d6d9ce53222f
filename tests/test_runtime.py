"""Producer runtime + DataReader client: rendezvous, backpressure,
barrier-then-EOS ordering, max_steps, masking, fault detection, metrics."""

import threading
import time

import numpy as np
import pytest

from psana_ray_tpu.config import (
    MaskConfig,
    PipelineConfig,
    RetrievalMode,
    SourceConfig,
    TransportConfig,
)
from psana_ray_tpu.consumer import DataReader, DataReaderError
from psana_ray_tpu.producer import ProducerRuntime, parse_arguments
from psana_ray_tpu.records import EndOfStream, FrameRecord, is_eos
from psana_ray_tpu.transport import Registry, RingBuffer


def _config(num_events=12, num_consumers=1, detector="epix100", **src_kw):
    return PipelineConfig(
        source=SourceConfig(
            exp="synthetic", run=1, detector_name=detector, num_events=num_events, **src_kw
        ),
        transport=TransportConfig(num_consumers=num_consumers, queue_size=64),
    )


class TestProducerRuntime:
    def test_end_to_end_all_events_then_eos(self):
        cfg = _config(num_events=10)
        rt = ProducerRuntime(cfg, num_local_shards=2)
        rt.run(block=False)

        got, eos = [], []
        with DataReader() as reader:
            while True:
                item = reader.read_wait(timeout=5.0)
                if item is None:
                    pytest.fail("starved before EOS")
                if is_eos(item):
                    eos.append(item)
                    break
                got.append(item)
        rt.join()
        # every event exactly once, EOS strictly after all data
        assert sorted(r.event_idx for r in got) == list(range(10))
        assert len(eos) == 1
        assert rt.metrics.frames.count == 10

    def test_eos_per_consumer(self):
        cfg = _config(num_events=4, num_consumers=3)
        rt = ProducerRuntime(cfg, num_local_shards=1)
        rt.run(block=True)
        q = Registry.default().resolve("default", "shared_queue", retries=1, interval_s=0.1)
        items = [q.get_wait(timeout=1.0) for _ in range(7)]
        assert sum(is_eos(i) for i in items) == 3  # parity: producer.py:124-125

    def test_max_steps(self):
        cfg = _config(num_events=100, max_steps=5)
        rt = ProducerRuntime(cfg, num_local_shards=1)
        rt.run(block=True)
        assert rt.metrics.frames.count == 5

    def test_mask_applied_host_side(self, tmp_path):
        # parity: np.where(mask, data, 0), producer.py:92-95
        mask = np.zeros((1, 704, 768), np.uint8)  # all-bad manual mask
        path = tmp_path / "mask.npy"
        np.save(path, mask)
        cfg = _config(num_events=2)
        cfg = PipelineConfig(
            source=cfg.source,
            mask=MaskConfig(manual_mask_path=str(path)),
            transport=cfg.transport,
        )
        rt = ProducerRuntime(cfg, num_local_shards=1)
        rt.run(block=True)
        with DataReader() as reader:
            rec = reader.read_wait(timeout=2.0)
        assert rec.panels.sum() == 0

    def test_queue_death_mid_stream_exits_cleanly(self):
        cfg = _config(num_events=5000, detector="epix100")
        cfg.transport.queue_size = 2  # force backpressure so death is seen
        rt = ProducerRuntime(cfg, num_local_shards=1)
        q = rt.bootstrap()
        rt.run(block=False)
        time.sleep(0.2)
        Registry.default().destroy("default", "shared_queue")  # kills queue
        rt.join()  # must return, not raise/hang — parity: producer.py:112-114

    def test_sharded_ranks_disjoint(self):
        cfg = _config(num_events=9)
        rt = ProducerRuntime(cfg, num_local_shards=3)
        rt.run(block=True)
        from psana_ray_tpu.transport import EMPTY

        q = Registry.default().resolve("default", "shared_queue", retries=1, interval_s=0.1)
        recs = [
            i
            for i in iter(lambda: q.get_wait(timeout=0.5), EMPTY)
            if not is_eos(i)
        ]
        by_rank = {}
        for r in recs:
            by_rank.setdefault(r.shard_rank, []).append(r.event_idx)
        assert set(by_rank) == {0, 1, 2}
        assert sorted(sum(by_rank.values(), [])) == list(range(9))


class TestDataReaderParity:
    def test_context_manager_and_nonblocking_read(self):
        Registry.default().get_or_create("default", "shared_queue", lambda: RingBuffer(8))
        with DataReader() as reader:
            assert reader.read() is None  # empty, parity data_reader.py:35
            assert reader.size() == 0

    def test_missing_queue_raises_reader_error(self):
        cfg = TransportConfig(rendezvous_retries=2, rendezvous_interval_s=0.01)
        with pytest.raises(DataReaderError, match="could not find"):
            DataReader(queue_name="nope", config=cfg).connect()

    def test_dead_queue_maps_to_reader_error(self):
        q = Registry.default().get_or_create("default", "shared_queue", lambda: RingBuffer(8))
        reader = DataReader().connect()
        q.close()
        with pytest.raises(DataReaderError):
            reader.read()

    def test_unconnected_read_raises(self):
        with pytest.raises(DataReaderError, match="not connected"):
            DataReader().read()

    def test_iteration_stops_at_eos(self):
        q = Registry.default().get_or_create("default", "shared_queue", lambda: RingBuffer(16))
        for i in range(3):
            q.put(FrameRecord(0, i, np.zeros((1, 2, 2), np.float32), 1.0))
        q.put(EndOfStream())
        with DataReader() as reader:
            seen = [r.event_idx for r in reader]
        assert seen == [0, 1, 2]


class TestCLI:
    def test_reference_flag_spellings(self):
        cfg, args = parse_arguments(
            [
                "--exp", "synthetic", "--run", "58", "--detector_name", "epix10k2M",
                "--calib", "--uses_bad_pixel_mask", "--queue_name", "q1",
                "--queue_size", "400", "--num_consumers", "4", "--max_steps", "100",
                "--ray_namespace", "ns", "--log_level", "DEBUG",
            ]
        )
        assert cfg.source.run == 58
        assert cfg.source.mode == RetrievalMode.CALIB
        assert cfg.mask.uses_bad_pixel_mask
        assert cfg.transport.queue_size == 400
        assert cfg.transport.num_consumers == 4
        assert cfg.transport.namespace == "ns"
        assert cfg.source.max_steps == 100

    def test_defaults_rendezvous_with_data_reader(self):
        # quirk 3 fixed: producer and DataReader share ONE default surface
        cfg, _ = parse_arguments([])
        reader = DataReader()
        assert cfg.transport.queue_name == reader.queue_name
        assert cfg.transport.namespace == reader.namespace


class TestMultiRuntimeEos:
    """Two producer runtimes on ONE queue: a consumer must receive every
    event from BOTH before stopping, even when one finishes far earlier
    (VERDICT r1 weak #4; reference avoided this with a global MPI barrier,
    producer.py:119-126)."""

    def _two_runtimes(self, num_events, delay_b=0.0, num_consumers=1):
        q = Registry.default().get_or_create(
            "default", "shared_queue", lambda: RingBuffer(256)
        )
        cfgs = [_config(num_events=num_events, num_consumers=num_consumers) for _ in range(2)]
        rts = [
            ProducerRuntime(
                cfgs[i], num_local_shards=1, shard_rank_offset=i, total_shards=2
            )
            for i in range(2)
        ]
        rts[0].run(block=False)

        def _delayed():
            time.sleep(delay_b)
            rts[1].run(block=True)

        # daemonic: a runtime wedged behind a starved consumer must fail
        # the test, not hang the pytest process at exit
        tb = threading.Thread(target=_delayed, daemon=True)
        tb.start()
        return rts, tb

    def test_consumer_waits_for_slow_producer(self):
        rts, tb = self._two_runtimes(num_events=10, delay_b=0.5)
        with DataReader() as reader:
            got = [r.event_idx for r in reader]
        rts[0].join()
        tb.join()
        assert sorted(got) == list(range(10))  # nothing dropped

    def test_eos_records_carry_coverage(self):
        rts, tb = self._two_runtimes(num_events=4)
        rts[0].join()
        tb.join()
        q = Registry.default().resolve("default", "shared_queue", retries=1, interval_s=0.1)
        items = []
        while True:
            item = q.get_wait(timeout=0.5)
            from psana_ray_tpu.transport import EMPTY

            if item is EMPTY:
                break
            items.append(item)
        eos = [i for i in items if is_eos(i)]
        assert {e.producer_rank for e in eos} == {0, 1}
        assert all(e.total_shards == 2 and e.shards_done == 1 for e in eos)

    # measured 6-8/30 flaky on a 1-core host at a flat 30 s join
    # (CHANGES.md). Root cause was NOT starvation: two competing
    # consumers each re-popped their own flushed sibling EOS marker
    # within one GIL slice, never handing it over — a livelock fixed at
    # the source (EosTally.flush_duplicates callers now yield after a
    # starved flush; see consumer.iter_records). Hardened here too: the
    # join deadline scales with core scarcity and the workers are
    # daemonic, so a regression fails the test instead of wedging the
    # pytest session at exit. 0/30 failures post-fix on the 1-core box.
    def test_two_consumers_two_runtimes(self):
        import os

        rts, tb = self._two_runtimes(num_events=12, delay_b=0.3, num_consumers=2)
        results = {}

        def consume(cid):
            with DataReader() as reader:
                results[cid] = [r.event_idx for r in reader]

        threads = [
            threading.Thread(target=consume, args=(c,), daemon=True) for c in range(2)
        ]
        for t in threads:
            t.start()
        # two consumers + two producer runtimes timeshare the machine:
        # give the 30 s budget a 4-way-parallelism baseline (120 s on one
        # core, 30 s at >= 4)
        join_s = 30.0 * max(1.0, 4.0 / (os.cpu_count() or 1))
        deadline = time.monotonic() + join_s
        for t in threads:
            t.join(timeout=max(0.1, deadline - time.monotonic()))
        assert not any(t.is_alive() for t in threads), (
            f"competing consumers starved past the {join_s:.0f}s join deadline"
        )
        rts[0].join()
        tb.join()
        all_idx = sorted(results[0] + results[1])
        assert all_idx == list(range(12))  # union exact, no loss, no dupes


class TestEosNeverDropped:
    def test_duplicate_eos_held_when_queue_full(self):
        """code-review r2 finding: a full queue must not swallow a sibling
        consumer's EOS marker — it is held and returned once space frees."""
        from psana_ray_tpu.records import EndOfStream, EosTally

        q = RingBuffer(maxsize=1)
        tally = EosTally()
        tally.observe(EndOfStream(producer_rank=0, shards_done=1, total_shards=2))
        dup = EndOfStream(producer_rank=0, shards_done=1, total_shards=2)
        assert not tally.process(dup)  # duplicate, stream not complete
        q.put("blocker")  # queue full
        tally.flush_duplicates(q)  # cannot place it yet
        assert q.size() == 1
        q.get()  # space frees
        tally.flush_duplicates(q)
        assert is_eos(q.get())  # marker survived for the sibling

    def test_iter_records_stop_leaves_frames_for_siblings(self):
        q = Registry.default().get_or_create("default", "shared_queue", lambda: RingBuffer(16))
        for i in range(6):
            q.put(FrameRecord(0, i, np.zeros((1, 2, 2), np.float32), 1.0))
        q.put(EndOfStream())
        seen = []
        with DataReader() as reader:
            for rec in reader.iter_records(stop=lambda: len(seen) >= 3):
                seen.append(rec.event_idx)
        assert seen == [0, 1, 2]
        assert q.size() == 4  # 3 frames + EOS untouched for siblings


class TestShardTopology:
    """CLI shard topology: mpirun/srun rank-derived (code-review r2 —
    previously unreachable from the CLI, making the README's multi-process
    flow duplicate events and under-deliver EOS)."""

    def test_explicit_flags_win(self, monkeypatch):
        from psana_ray_tpu.producer import shard_topology

        monkeypatch.setenv("OMPI_COMM_WORLD_RANK", "3")
        monkeypatch.setenv("OMPI_COMM_WORLD_SIZE", "4")
        _, args = parse_arguments(
            ["--num_shards", "2", "--shard_rank_offset", "10", "--total_shards", "20"]
        )
        assert shard_topology(args) == (10, 20)

    def test_mpi_env_derives_topology(self, monkeypatch):
        from psana_ray_tpu.producer import shard_topology

        monkeypatch.setenv("OMPI_COMM_WORLD_RANK", "2")
        monkeypatch.setenv("OMPI_COMM_WORLD_SIZE", "4")
        _, args = parse_arguments(["--num_shards", "2"])
        assert shard_topology(args) == (4, 8)  # rank*local, world*local

    def test_slurm_env(self, monkeypatch):
        from psana_ray_tpu.producer import shard_topology

        for var in ("OMPI_COMM_WORLD_RANK", "PMI_RANK"):
            monkeypatch.delenv(var, raising=False)
        monkeypatch.setenv("SLURM_PROCID", "1")
        monkeypatch.setenv("SLURM_NTASKS", "3")
        _, args = parse_arguments([])
        assert shard_topology(args) == (1, 3)

    def test_no_launcher_single_process(self, monkeypatch):
        from psana_ray_tpu.producer import shard_topology

        for var in ("OMPI_COMM_WORLD_RANK", "PMI_RANK", "SLURM_PROCID"):
            monkeypatch.delenv(var, raising=False)
        _, args = parse_arguments(["--num_shards", "3"])
        assert shard_topology(args) == (0, 3)


class TestBatchedProducerPath:
    def test_producer_over_tcp_uses_batched_puts(self):
        """Over tcp:// the producer must move N frames per round trip
        (code-review r2: put_batch was dead code on the product path)."""
        from psana_ray_tpu.transport.ring import RingBuffer
        from psana_ray_tpu.transport.tcp import TcpQueueServer

        srv = TcpQueueServer(RingBuffer(256), host="127.0.0.1").serve_background()
        try:
            cfg = _config(num_events=20)
            cfg.transport.address = f"tcp://127.0.0.1:{srv.port}"
            rt = ProducerRuntime(cfg, num_local_shards=1)
            rt.run(block=True)
            # config (namespace, queue_name) now selects a NAMED queue on
            # the server (OPEN opcode) — the default queue stays untouched
            assert srv.queue.stats()["puts"] == 0
            named = srv.open_named(cfg.transport.namespace, cfg.transport.queue_name)
            # server saw far fewer put RPCs than frames (batch size 16)
            stats = named.stats()
            assert stats["puts"] == 21  # 20 frames + 1 EOS landed
            drained = [named.get() for _ in range(21)]
            idx = [r.event_idx for r in drained if not is_eos(r)]
            assert sorted(idx) == list(range(20))
            assert sum(is_eos(r) for r in drained) == 1
        finally:
            srv.shutdown()

    def test_sender_retries_partial_batch_accept(self):
        from psana_ray_tpu.producer import _Sender
        from psana_ray_tpu.transport.backoff import BackoffPolicy
        from psana_ray_tpu.transport.ring import RingBuffer
        from psana_ray_tpu.utils.metrics import PipelineMetrics

        class BatchRing(RingBuffer):  # RingBuffer + put_batch surface
            def put_batch(self, items):
                n = 0
                for it in items:
                    if not self.put(it):
                        break
                    n += 1
                return n

        q = BatchRing(maxsize=4)
        stop = threading.Event()
        sender = _Sender(q, BackoffPolicy(0.001, 0.002, 0.0), stop, PipelineMetrics(), 8)
        recs = [FrameRecord(0, i, np.zeros((1, 2, 2), np.float32), 1.0) for i in range(8)]
        drained = []

        def drain_later():
            time.sleep(0.05)
            while len(drained) < 8:
                item = q.get_wait(timeout=1.0)
                drained.append(item)

        t = threading.Thread(target=drain_later)
        t.start()
        for r in recs:
            assert sender.send(r)
        assert sender.flush()
        t.join()
        assert [r.event_idx for r in drained] == list(range(8))  # FIFO kept


# ---------------------------------------------------------------------------
# what a CLI offers, it uses; what a layer is given, it checks there
# ---------------------------------------------------------------------------

def _parser_of(cli, monkeypatch):
    """The ``ArgumentParser`` a CLI module builds, caught at its
    ``parse_args``: nothing past the parser's construction runs."""
    import argparse
    import importlib

    class _Built(Exception):
        pass

    def caught(parser, args=None, namespace=None):
        raise _Built(parser)

    monkeypatch.setattr(argparse.ArgumentParser, "parse_args", caught)
    mod = importlib.import_module(f"psana_ray_tpu.{cli}")
    with pytest.raises(_Built) as built:
        (parse_arguments if cli == "producer" else mod.main)([])
    return mod, built.value.args[0]


def _namespace_reads(path):
    """Every ``a.<name>`` / ``args.<name>`` read and every ``getattr(x,
    "<name>", ...)`` of a module, by ``ast``."""
    import ast

    names = set()
    for node in ast.walk(ast.parse(path.read_text())):
        if (
            isinstance(node, ast.Attribute) and isinstance(node.ctx, ast.Load)
            and isinstance(node.value, ast.Name) and node.value.id in ("a", "args")
        ):
            names.add(node.attr)
        elif (
            isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
            and node.func.id == "getattr" and len(node.args) >= 2
            and isinstance(node.args[1], ast.Constant)
        ):
            names.add(node.args[1].value)
    return names


def _defined_in(path):
    import ast

    return [n.name for n in ast.parse(path.read_text()).body if isinstance(n, ast.FunctionDef)]


@pytest.mark.parametrize("cli", ["producer", "consumer", "queue_server", "sfx"])
def test_every_flag_a_cli_defines_is_read(cli, monkeypatch):
    """Every ``dest`` of the parser is read: by the module that built
    the parser, or — a flag of a shared ``add_*_args`` — by the module
    that defines it (its ``configure_*_from_args`` takes the namespace).
    A flag nobody reads is an option the operator sets to no effect."""
    import pathlib

    mod, parser = _parser_of(cli, monkeypatch)
    dests = {action.dest for action in parser._actions} - {"help"}
    assert len(dests) > 20, "the parser was not the CLI's own"
    package = pathlib.Path(mod.__file__).parent
    shared = {
        path for path in package.rglob("*.py")
        if path != pathlib.Path(mod.__file__)
        and any(fn.startswith("add_") and fn.endswith("_args") for fn in _defined_in(path))
    }
    read = _namespace_reads(pathlib.Path(mod.__file__))
    read_there = set().union(*(_namespace_reads(path) for path in shared))
    dead = sorted(d for d in dests if d not in read and d not in read_there)
    assert not dead, f"{cli} defines flags nothing reads: {dead}"


def _dial_put_window(tmp_path):
    from psana_ray_tpu.transport.tcp import TcpQueueClient, TcpQueueServer

    srv = TcpQueueServer(RingBuffer(16), host="127.0.0.1").serve_background()
    try:
        for given in (0, -7):  # held at 1: stop-and-wait, and it still delivers
            c = TcpQueueClient("127.0.0.1", srv.port, put_window=given)
            assert c._put_window == 1
            for i in range(3):
                assert c.put_pipelined(FrameRecord(0, i, np.zeros((1, 2, 2), np.float32), 1.0))
                assert len(c._put_unacked) <= 1
            assert c.flush_puts()
            assert [c.get_wait(timeout=2.0).event_idx for _ in range(3)] == [0, 1, 2]
            c.disconnect()
    finally:
        srv.shutdown()


def _dial_stream_window(tmp_path):
    from psana_ray_tpu.transport.tcp import STREAM, TcpQueueClient, TcpQueueServer

    srv = TcpQueueServer(RingBuffer(16), host="127.0.0.1").serve_background()
    try:
        for given, taken in ((0, 1), (-5, 1), (10**6, 4096)):  # the server's own bounds
            base = STREAM.stats()["credit_window"]
            c = TcpQueueClient("127.0.0.1", srv.port)
            c.stream_open(window=given)
            deadline = time.monotonic() + 5.0
            while STREAM.stats()["credit_window"] == base and time.monotonic() < deadline:
                time.sleep(0.01)
            assert STREAM.stats()["credit_window"] - base == taken
            c.disconnect()
            while STREAM.stats()["credit_window"] != base and time.monotonic() < deadline:
                time.sleep(0.01)
    finally:
        srv.shutdown()


def _dial_prefetch_depth(tmp_path):
    from psana_ray_tpu.infeed import InfeedPipeline
    from psana_ray_tpu.infeed.pipeline import DevicePrefetcher

    for given in (0, -1):
        with pytest.raises(ValueError, match="prefetch_depth"):
            DevicePrefetcher(iter(()), prefetch_depth=given)
        with pytest.raises(ValueError, match="prefetch_depth"):
            InfeedPipeline(RingBuffer(4), batch_size=2, prefetch_depth=given, place_on_device=False)
    # over range: past what the pooled arenas can keep alive
    with pytest.raises(ValueError, match="prefetch_depth"):
        InfeedPipeline(RingBuffer(4), batch_size=2, prefetch_depth=5, batcher_buffers=8)


def _dial_retain_segments(tmp_path):
    from psana_ray_tpu.storage import SegmentLog

    for given in (0, -3):  # a log keeps its active segment whatever it is told
        log = SegmentLog(str(tmp_path / f"r{given}"), segment_bytes=1 << 16, retain_segments=given, fsync="none")
        assert log.retain_segments == 1
        log.close()


def _dial_fsync_batch_n(tmp_path):
    from psana_ray_tpu.storage import SegmentLog

    for given in (0, -4):  # never rarer than asked: held at every append
        log = SegmentLog(str(tmp_path / f"f{given}"), segment_bytes=1 << 16, fsync="batch", fsync_batch_n=given)
        assert log.fsync_batch_n == 1
        log.close()


def _dial_ram_items(tmp_path):
    from psana_ray_tpu.storage import DurableRingBuffer, SegmentLog

    for given, taken in ((None, 8), (0, 8), (-2, 1), (3, 3)):
        log = SegmentLog(str(tmp_path / f"q{given}"), segment_bytes=1 << 16, fsync="none")
        q = DurableRingBuffer(log, maxsize=8, ram_items=given, name="t")
        assert q.ram_items == taken
        for i in range(5):
            assert q.put(FrameRecord(0, i, np.zeros((1, 2, 2), np.uint16), 1.0))
        assert q.stats()["spilled"] == max(0, 5 - taken)
        assert [q.get().event_idx for _ in range(5)] == list(range(5))  # spilled or not, in order
        log.close()


def _dial_min_per_class(tmp_path):
    from psana_ray_tpu.utils.bufpool import BufferPool

    for given, taken in ((-3, 0), (0, 0), (2, 2)):
        pool = BufferPool(min_per_class=given)
        assert pool.min_per_class == taken
        pool.lease(1024).release()  # the floor only ever adds to the adaptive peak
        assert pool.stats()["leases"] == 0


def _dial_poll_interval_s(tmp_path):
    from psana_ray_tpu.infeed.batcher import batches_from_queue

    for given in (0, 0.0, -0.01):  # 0 would spin on the pop
        with pytest.raises(ValueError, match="poll_interval_s"):
            # (max_wait_s: a loop that took the value would end, not spin)
            next(batches_from_queue(RingBuffer(4), 2, poll_interval_s=given, max_wait_s=0.05), None)


_DIALS = {
    "put_window": _dial_put_window,
    "stream_window": _dial_stream_window,
    "prefetch_depth": _dial_prefetch_depth,
    "retain_segments": _dial_retain_segments,
    "fsync_batch_n": _dial_fsync_batch_n,
    "ram_items": _dial_ram_items,
    "min_per_class": _dial_min_per_class,
    "poll_interval_s": _dial_poll_interval_s,
}


@pytest.mark.parametrize("dial", list(_DIALS))
def test_a_dial_out_of_range_is_clamped_or_refused_where_it_is_given(dial, tmp_path):
    """No layer keeps a setter for a plane above it, so a value's only
    fence is where it is given — the constructor, or the server that
    takes it off the wire: zero, negative and over-range values meet
    the clamp or the refusal documented there."""
    _DIALS[dial](tmp_path)
